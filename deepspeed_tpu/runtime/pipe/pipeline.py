"""Pipeline parallelism.

Capability analogue of the reference's ``runtime/pipe/``
(``PipelineModule`` module.py:86, 1F1B ``TrainSchedule`` schedule.py:189,
p2p send/recv, ``PipelineEngine.train_batch`` engine.py:337).  TPU-native
design: no instruction interpreter and no p2p processes — the pipeline is a
single SPMD program over the ``pp`` mesh axis:

* the stacked layer parameters (L, ...) are sharded over ``pp`` on the layers
  axis — that IS the uniform ``partition_method`` of ``PipelineModule``;
* inside ``shard_map``, a ``lax.scan`` over M + P - 1 ticks runs each stage's
  local layers and hands activations to the next stage with ``ppermute``
  (the SendActivation/RecvActivation instructions, on ICI);
* backward is jax autodiff through the scan: the reversed ppermutes are the
  SendGrad/RecvGrad instructions — a GPipe schedule with bubble
  2(P-1)/(M+P-1); embeddings/logits stay outside the pipelined region (they
  live on every rank, the analogue of TiedLayerSpec replication).

Two schedules, selected by the ``schedule`` argument of
:func:`pipeline_loss_fn` (or from a DeepSpeed-style config's
``pipeline.schedule`` key via :func:`make_pipeline_loss_fn`):

* ``'gpipe'`` — forward scan + jax autodiff backward.  Residuals for all M
  microbatch ticks are stored: peak activation memory O(M).
* ``'1f1b'`` — true interleaved one-forward-one-backward
  (reference ``runtime/pipe/schedule.py:189`` ``TrainSchedule``): a single
  scan over M + 2P - 1 ticks where EVERY tick runs one stage forward and one
  stage backward (hand-written vjp), with per-stage input ring buffers of
  depth 2P — peak activation memory O(P), independent of M.  The last stage
  seeds each microbatch's backward from the loss head the tick after its
  forward, exactly the reference's steady state.  Exposed through
  ``jax.custom_vjp`` (forward computes loss AND grads; backward scales the
  stored grads by the cotangent), so it drops into the engine's ordinary
  ``value_and_grad`` path, loss scaling included.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from ...parallel.topology import MeshTopology, get_topology


def _check_microbatch_divisibility(B: int, topo, M: int) -> None:
    """The global batch is split over dp*fsdp shards BEFORE microbatching —
    each shard's slice must divide evenly into M microbatches."""
    b_shards = topo.size("dp") * topo.size("fsdp")
    if (B // b_shards) % M != 0:
        raise ValueError(
            f"per-data-shard batch {B}//{b_shards}={B // b_shards} not "
            f"divisible by num_microbatches {M} (global batch {B} is split "
            f"over dp*fsdp={b_shards} shards before microbatching)")


def _resolve_stage_attention(cfg, attn_fn, topo, S: int):
    """Decide whether the pipeline runs with an sp-sharded sequence.

    Returns (seq_sharded, attn_fn): ``attn_fn`` is None when the bound
    ulysses body must be constructed inside the shard_map (it needs the
    local rope slice), a plain AttentionFn otherwise.
    """
    from ...models import transformer as tfm

    sp = topo.size("sp")
    if attn_fn is not None:
        return False, attn_fn
    if cfg.attn_impl == "ring" and sp > 1:
        raise ValueError(
            "attn_impl='ring' cannot run inside the pipelined stack (its "
            "ppermute ring would nest the sp loop in every tick); use "
            "'ulysses' for pp × sp or 'flash' for full-sequence stages")
    if cfg.attn_impl == "ulysses" and sp > 1:
        if S % sp != 0:
            raise ValueError(f"seq len {S} not divisible by sp={sp}")
        return True, None
    impl = "flash" if cfg.attn_impl in ("ulysses", "ring") else cfg.attn_impl
    return False, tfm.resolve_attention(impl)


def _bind_stage_attention(seq_sharded: bool, attn_fn, cos, sin, s_l: int):
    """Inside the pipeline shard_map: slice rope tables to this sp rank's
    rows and bind the ulysses all-to-all attention when seq-sharded."""
    if not seq_sharded:
        return cos, sin, attn_fn
    from ...sequence.ulysses import ulysses_attention_bound

    r = lax.axis_index("sp")
    cos_l = (lax.dynamic_slice_in_dim(cos, r * s_l, s_l)
             if cos is not None else None)
    sin_l = (lax.dynamic_slice_in_dim(sin, r * s_l, s_l)
             if sin is not None else None)
    return cos_l, sin_l, ulysses_attention_bound


def _stage_fn(layer_params, x, cfg, attn_fn, cos, sin):
    """Run this stage's local slice of the layer stack (scan over L/P layers)."""
    from ...models import transformer as tfm

    def body(h, lp):
        a_in = tfm._norm(h, lp["ln1"], cfg.norm, cfg.norm_eps)
        attn_out = tfm._attention_block(a_in, lp["attn"], cfg, cos, sin,
                                        attn_fn)
        m_src = h if cfg.parallel_residual else h + attn_out
        m_in = tfm._norm(m_src, lp["ln2"], cfg.norm, cfg.norm_eps)
        if cfg.num_experts > 0:
            from ...moe.layer import dense_moe_block

            mlp_out = dense_moe_block(m_in, lp["moe"], cfg)
        else:
            mlp_out = tfm._mlp_block(m_in, lp["mlp"], cfg)
        h = (h + attn_out + mlp_out) if cfg.parallel_residual \
            else (m_src + mlp_out)
        return h, None

    policy = tfm._remat_policy(cfg.remat_policy)
    if policy is not None:
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    x, _ = lax.scan(body, x, layer_params)
    return x


def pipeline_apply(layer_params: Dict[str, Any], x: jax.Array, cfg,
                   num_microbatches: int,
                   attn_fn=None, topo: Optional[MeshTopology] = None
                   ) -> jax.Array:
    """Apply the pipelined layer stack to ``x`` (B, S, H).

    B must be divisible by num_microbatches; the layers axis of every leaf in
    ``layer_params`` must be divisible by the pp size.
    """
    from ...models import transformer as tfm

    topo = topo or get_topology()
    pp = topo.size("pp")
    if pp == 1:
        cos, sin = (None, None)
        if cfg.position == "rope":
            cos, sin = tfm.rope_table(x.shape[1], cfg.rot_dim, cfg.rope_theta)
        return _stage_fn(layer_params, x, cfg, attn_fn, cos, sin)

    B, S, H = x.shape
    M = num_microbatches
    _check_microbatch_divisibility(B, topo, M)
    seq_sharded, attn_fn = _resolve_stage_attention(cfg, attn_fn, topo, S)

    cos, sin = (None, None)
    if cfg.position == "rope":
        cos, sin = tfm.rope_table(S, cfg.rot_dim, cfg.rope_theta)

    def local(layer_params, x):
        me = lax.axis_index("pp")
        n = axis_size("pp")
        # per-device shapes: batch/seq may be dp/sp-sharded
        b_l, s_l, h_l = x.shape
        mb_l = b_l // M
        xm = x.reshape(M, mb_l, s_l, h_l)
        fwd_perm = [(i, (i + 1) % n) for i in range(n)]
        cos_l, sin_l, af = _bind_stage_attention(seq_sharded, attn_fn, cos,
                                                 sin, s_l)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (zeros once the batch is drained)
            mb_idx = jnp.minimum(t, M - 1)
            fresh = jnp.where(t < M, 1.0, 0.0).astype(x.dtype)
            inject = lax.dynamic_index_in_dim(xm, mb_idx, 0, keepdims=False)
            inp = jnp.where(me == 0, inject * fresh, state)
            y = _stage_fn(layer_params, inp, cfg, af, cos_l, sin_l)
            # last stage collects finished microbatch (valid when t >= n-1)
            out_idx = jnp.clip(t - (n - 1), 0, M - 1)
            take = (t >= n - 1) & (t - (n - 1) < M)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            upd = jnp.where(take & (me == n - 1), y, cur)
            outputs = lax.dynamic_update_index_in_dim(outputs, upd, out_idx, 0)
            state = lax.ppermute(y, "pp", fwd_perm)
            return (state, outputs), None

        state0 = jnp.zeros((mb_l, s_l, h_l), x.dtype)
        out0 = jnp.zeros((M, mb_l, s_l, h_l), x.dtype)
        (_, outputs), _ = lax.scan(tick, (state0, out0),
                                   jnp.arange(M + n - 1))
        # hand the collected result from the last stage to every pp rank
        outputs = lax.psum(jnp.where(me == n - 1, outputs, 0.0), "pp")
        return outputs.reshape(b_l, s_l, h_l)

    # pp × sp composition: with attn_impl='ulysses' the sequence axis stays
    # sp-sharded through the whole pipeline (stage boundaries included) and
    # the stage attention does its head↔seq all-to-all on the bound sp axis;
    # otherwise the sequence enters unsharded and stages see the full S
    batch_axes = ("dp", "fsdp")
    x_spec = P(batch_axes, "sp" if seq_sharded else None, None)
    # layers axis of every param leaf sharded over pp
    param_spec = jax.tree.map(lambda _: P("pp"), layer_params)
    return shard_map(local, mesh=topo.mesh,
                     in_specs=(param_spec, x_spec), out_specs=x_spec,
                     check_vma=False)(layer_params, x)


def make_pipeline_loss_fn(cfg, ds_config=None, attn_fn=None):
    """Build a pipelined loss_fn from a DeepSpeed-style config's ``pipeline``
    section (``schedule``, ``num_microbatches``) — the wiring for
    PipelineConfig (reference: engine.py consuming the ``pipeline`` dict).

    ``ds_config`` may be a dict (the JSON config), a DeepSpeedTPUConfig, or
    None (defaults: schedule='1f1b', num_microbatches=2).
    """
    from ..config import DeepSpeedTPUConfig, PipelineConfig
    from ..config_utils import is_auto

    if ds_config is None:
        pipe_cfg = PipelineConfig()
    elif isinstance(ds_config, DeepSpeedTPUConfig):
        pipe_cfg = ds_config.pipeline
    else:
        pipe_cfg = PipelineConfig(**dict(ds_config).get("pipeline", {}))
    m = pipe_cfg.num_microbatches
    num_microbatches = 2 if is_auto(m) else int(m)

    def loss_fn(params, batch, rng=None):
        return pipeline_loss_fn(params, batch, cfg, num_microbatches,
                                attn_fn=attn_fn, schedule=pipe_cfg.schedule)

    return loss_fn


# ---------------------------------------------------------------------------
# 1F1B (interleaved) schedule
# ---------------------------------------------------------------------------


def _head_loss(h, head_params, labels, mask, cfg):
    """Final norm + logits + CE, SUMMED over this microbatch's tokens; aux is
    the correct-prediction count.  (The last pipeline stage runs this per
    microbatch to seed its backward — the reference's loss+``backward``
    instructions at schedule.py:227.)"""
    from ...models import transformer as tfm

    dt = jnp.dtype(cfg.dtype)
    h = tfm._norm(h, head_params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = h @ head_params["w"].astype(dt)
    if "b" in head_params:
        logits = logits + head_params["b"].astype(dt)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    correct = ((logits.argmax(-1) == labels).astype(jnp.float32) * mask).sum()
    return (nll * mask).sum(), correct


def _run_1f1b(layer_params, head_params, x, labels, mask, cfg, M, attn_fn,
              topo):
    """One-forward-one-backward pipeline: a single shard_map'd scan computing
    the summed loss AND all grads.

    Schedule (P = pp size, ticks t = 0..M+2P-2; every stage does one forward
    unit and one backward unit per tick):
      forward  of microbatch m at stage i on tick  t = i + m
      backward of microbatch m at stage i on tick  t = 2P - 1 - i + m
    In-flight microbatches at stage i = 2(P - i) - 1 ≤ 2P - 1, so saved stage
    inputs live in a ring buffer of depth 2P — O(P) activation memory where
    GPipe-through-autodiff stores O(M) tick residuals.  Backward units
    recompute the stage forward from the saved input (vjp), the pipelined
    equivalent of per-layer remat.
    """
    from ...models import transformer as tfm

    P_ = topo.size("pp")
    n = P_
    B, S, H = x.shape
    seq_sharded, attn_fn = _resolve_stage_attention(cfg, attn_fn, topo, S)
    cos, sin = (None, None)
    if cfg.position == "rope":
        cos, sin = tfm.rope_table(S, cfg.rot_dim, cfg.rope_theta)

    def local(lp, hp, x, labels, mask):
        me = lax.axis_index("pp")
        b_l, s_l, h_l = x.shape
        mb_l = b_l // M
        cos_l, sin_l, af = _bind_stage_attention(seq_sharded, attn_fn, cos,
                                                 sin, s_l)

        def stage(lp_, xin):
            return _stage_fn(lp_, xin, cfg, af, cos_l, sin_l)
        xm = x.reshape(M, mb_l, s_l, h_l)
        lm = labels.reshape(M, mb_l, s_l)
        mm = mask.reshape(M, mb_l, s_l)
        R = 2 * n  # ring depth: ≥ max in-flight (2n-1, at stage 0)
        fwd_perm = [(i, (i + 1) % n) for i in range(n)]
        bwd_perm = [(i, (i - 1) % n) for i in range(n)]
        T = M + 2 * n - 1

        g_lp0 = jax.tree.map(jnp.zeros_like, lp)
        g_hp0 = jax.tree.map(jnp.zeros_like, hp)

        def tick(carry, t):
            (in_buf, fwd_in, bwd_in, g_lp, g_hp, dx_buf, loss_sum,
             correct_sum) = carry

            # ---- forward unit: microbatch m_f = t - me ------------------
            m_f = t - me
            f_valid = (m_f >= 0) & (m_f < M)
            m_f_c = jnp.clip(m_f, 0, M - 1)
            inject = lax.dynamic_index_in_dim(xm, m_f_c, 0, keepdims=False)
            x_in = jnp.where(me == 0, inject, fwd_in)
            slot_f = jnp.remainder(m_f_c, R)
            prev = lax.dynamic_index_in_dim(in_buf, slot_f, 0, keepdims=False)
            in_buf = lax.dynamic_update_index_in_dim(
                in_buf, jnp.where(f_valid, x_in, prev), slot_f, 0)
            y = stage(lp, x_in)

            # ---- backward unit: microbatch m_b = t - (2n - 1 - me) ------
            m_b = t - (2 * n - 1 - me)
            b_valid = (m_b >= 0) & (m_b < M)
            m_b_c = jnp.clip(m_b, 0, M - 1)
            slot_b = jnp.remainder(m_b_c, R)
            x_saved = lax.dynamic_index_in_dim(in_buf, slot_b, 0, keepdims=False)
            lab_b = lax.dynamic_index_in_dim(lm, m_b_c, 0, keepdims=False)
            msk_b = lax.dynamic_index_in_dim(mm, m_b_c, 0, keepdims=False)

            def last_stage_bwd(x_s, g_in, lab, msk):
                # loss head + stage in ONE vjp: a single recompute yields the
                # microbatch loss, stage/head param grads, and the input grad
                def full(lp_, hp_, x_):
                    return _head_loss(stage(lp_, x_), hp_, lab, msk, cfg)

                (l, corr), (dlp, dhp, dxi) = jax.value_and_grad(
                    full, argnums=(0, 1, 2), has_aux=True)(lp, hp, x_s)
                return l, corr, dlp, dhp, dxi

            def mid_stage_bwd(x_s, g_in, lab, msk):
                _, vjp_fn = jax.vjp(lambda lp_, x_: stage(lp_, x_), lp, x_s)
                dlp, dxi = vjp_fn(g_in)
                z = jnp.zeros((), jnp.float32)
                return z, z, dlp, g_hp0, dxi

            l_m, c_m, dlp, dhp, dxi = lax.cond(
                me == n - 1, last_stage_bwd, mid_stage_bwd,
                x_saved, bwd_in, lab_b, msk_b)

            g_lp = jax.tree.map(
                lambda a, d: a + jnp.where(b_valid, d, jnp.zeros_like(d)),
                g_lp, dlp)
            g_hp = jax.tree.map(
                lambda a, d: a + jnp.where(b_valid, d, jnp.zeros_like(d)),
                g_hp, dhp)
            loss_sum = loss_sum + jnp.where(b_valid, l_m, 0.0)
            correct_sum = correct_sum + jnp.where(b_valid, c_m, 0.0)
            dxi = jnp.where(b_valid, dxi, jnp.zeros_like(dxi))
            dx_buf = lax.dynamic_update_index_in_dim(
                dx_buf,
                jnp.where(b_valid,
                          dxi,
                          lax.dynamic_index_in_dim(dx_buf, m_b_c, 0,
                                                   keepdims=False)),
                m_b_c, 0)

            # hand-offs (SendActivation / SendGrad, on ICI)
            fwd_in = lax.ppermute(y, "pp", fwd_perm)
            bwd_in = lax.ppermute(dxi, "pp", bwd_perm)
            return (in_buf, fwd_in, bwd_in, g_lp, g_hp, dx_buf, loss_sum,
                    correct_sum), None

        carry0 = (
            jnp.zeros((R, mb_l, s_l, h_l), x.dtype),
            jnp.zeros((mb_l, s_l, h_l), x.dtype),
            jnp.zeros((mb_l, s_l, h_l), x.dtype),
            g_lp0, g_hp0,
            jnp.zeros((M, mb_l, s_l, h_l), x.dtype),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (in_buf, _, _, g_lp, g_hp, dx_buf, loss_sum,
         correct_sum), _ = lax.scan(tick, carry0, jnp.arange(T))

        # reductions: data-sharding axes (batch; plus sp when the sequence
        # is ulysses-sharded) sum grads/loss; g_hp/loss live on the last pp
        # stage, dx on stage 0 — psum selects
        data_axes = ("dp", "fsdp") + (("sp",) if seq_sharded else ())
        g_lp = jax.tree.map(lambda a: lax.psum(a, data_axes), g_lp)
        g_hp = jax.tree.map(
            lambda a: lax.psum(
                jnp.where(me == n - 1, a, jnp.zeros_like(a)),
                data_axes + ("pp",)),
            g_hp)
        loss_sum = lax.psum(jnp.where(me == n - 1, loss_sum, 0.0),
                            data_axes + ("pp",))
        correct_sum = lax.psum(jnp.where(me == n - 1, correct_sum, 0.0),
                               data_axes + ("pp",))
        dx = lax.psum(jnp.where(me == 0, dx_buf, jnp.zeros_like(dx_buf)),
                      ("pp",))
        return g_lp, g_hp, dx.reshape(b_l, s_l, h_l), loss_sum, correct_sum

    batch_axes = ("dp", "fsdp")
    seq_axis = "sp" if seq_sharded else None
    x_spec = P(batch_axes, seq_axis, None)
    lab_spec = P(batch_axes, seq_axis)
    param_spec = jax.tree.map(lambda _: P("pp"), layer_params)
    head_spec = jax.tree.map(lambda _: P(), head_params)
    g_lp, g_hp, dx, loss_sum, correct_sum = shard_map(
        local, mesh=topo.mesh,
        in_specs=(param_spec, head_spec, x_spec, lab_spec, lab_spec),
        out_specs=(param_spec, head_spec, x_spec, P(), P()),
        check_vma=False)(layer_params, head_params, x, labels, mask)
    return (loss_sum, correct_sum), (g_lp, g_hp, dx)


def _make_1f1b_fn(cfg, M: int, attn_fn, topo):
    """Build the custom_vjp wrapper: forward computes loss AND grads (that is
    what interleaving means — backward work happens inside the schedule);
    backward just scales the stored grads by the loss cotangent."""

    @jax.custom_vjp
    def f(layer_params, head_params, x, labels, mask):
        sums, _ = _run_1f1b(layer_params, head_params, x, labels, mask,
                            cfg, M, attn_fn, topo)
        return sums

    def f_fwd(layer_params, head_params, x, labels, mask):
        sums, grads = _run_1f1b(layer_params, head_params, x, labels,
                                mask, cfg, M, attn_fn, topo)
        return sums, grads

    def f_bwd(res, g):
        g_lp, g_hp, dx = res
        g_loss = g[0]  # cotangent of loss_sum; correct_sum is non-diff

        def scale(t):
            return jax.tree.map(lambda a: a * g_loss.astype(a.dtype), t)

        # labels are integer (float0 tangent); the mask is non-differentiated
        return (scale(g_lp), scale(g_hp), dx * g_loss.astype(dx.dtype),
                np.zeros(dx.shape[:2], jax.dtypes.float0),
                jnp.zeros(dx.shape[:2], jnp.float32))

    f.defvjp(f_fwd, f_bwd)
    return f


def pipeline_loss_fn(params, batch, cfg, num_microbatches: int = 2,
                     attn_fn=None, schedule: str = "gpipe",
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Drop-in loss_fn running the layer stack through the pipeline.
    Reference surface: ``PipelineEngine.train_batch`` semantics (loss averaged
    over microbatches) but differentiable as one program.

    ``schedule='gpipe'`` stores O(M) residuals and backprops via autodiff;
    ``schedule='1f1b'`` runs the interleaved schedule with O(P) activation
    memory (see module docstring).  Grads are exactly equal between the two.
    """
    from ...models import transformer as tfm

    dt = jnp.dtype(cfg.dtype)
    tokens = batch["input_ids"]
    B, S = tokens.shape

    x = tfm.embed_tokens(params, tokens, cfg)

    if schedule == "1f1b" and get_topology().size("pp") > 1:
        topo = get_topology()
        M = num_microbatches
        _check_microbatch_divisibility(B, topo, M)
        labels, mask = tfm.shift_labels(batch)
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32)
        mask = mask.astype(jnp.float32)
        if cfg.tie_embeddings:
            w = params["embed"]["tokens"].T
        else:
            w = params["lm_head"]["w"]
        head_params = {"final_norm": params["final_norm"], "w": w}
        if not cfg.tie_embeddings and "b" in params["lm_head"]:
            head_params["b"] = params["lm_head"]["b"]  # gpt-j head bias
        f = _make_1f1b_fn(cfg, M, attn_fn, topo)
        loss_sum, correct_sum = f(params["layers"], head_params, x, labels,
                                  mask)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = loss_sum / denom
        return loss, {"loss": loss, "accuracy": correct_sum / denom,
                      "tokens": denom}
    if schedule not in ("gpipe", "1f1b"):  # 1f1b at pp=1 == dense fallthrough
        raise ValueError(f"unknown pipeline schedule {schedule!r} "
                         "(supported: 'gpipe', '1f1b')")

    x = pipeline_apply(params["layers"], x, cfg, num_microbatches,
                       attn_fn=attn_fn)

    x = tfm._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["tokens"].astype(dt).T
    else:
        logits = x @ params["lm_head"]["w"].astype(dt)
        if "b" in params["lm_head"]:
            logits = logits + params["lm_head"]["b"].astype(dt)

    labels, mask = tfm.shift_labels(batch)
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mask = mask.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = (nll * mask).sum() / denom
    acc = (((logits.argmax(-1) == labels).astype(jnp.float32)) * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": denom}
