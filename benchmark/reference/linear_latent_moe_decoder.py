"""Plain reference for a decoder whose layers are a mixer and an FFN each, the
mixer Kimi Delta Attention (KDA: a delta-rule matrix state a head under a gate
a channel) or latent attention without positions, the FFN dense or
sigmoid-routed experts of which one chip holds a share (Kimi-Linear-48B-A3B,
``model_type: kimi_linear``).

Written from the model's ``config.json`` and the published description of the
family (KDA: the Kimi Linear report's recurrence; MLA: DeepSeek-V2; the router:
DeepSeek-V3's sigmoid scores with a correction bias), not from the program's
model file.  On one sequence ``x (S, hidden)``, ``h = RMSNorm(x; ln1)``:

    KDA layer (``linear_attn_config.kda_layers``), H heads of d_k = d_v:
    [q~ | k~ | v~]_t = silu(sum_j c_j (h W_qkv)_{t - 3 + j})   4 taps, causal,
                                                   depthwise, zeros before 0
    q = q~ / |q~| / sqrt(d_k);  k = k~ / |k~|;  v = v~             a head
    a_t = exp(-exp(A_log) softplus(h W_f_down W_f_up + dt_bias))  (0,1)^{d_k}
    b_t = sigmoid(h w_beta)                                        a scalar
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T;  S_0 = 0
    o_t = S_t^T q_t
    x <- x + (RMSNorm_head(o_t) * sigmoid(h W_g_down W_g_up)) W_o

    latent layer (``full_attn_layers``), no rotation (``mla_use_nope``):
    [q_nope | q_r]_j = (h W_q)_j;  c_kv = RMSNorm(h W_kva);  k_r = h W_kr
    [k_nope | v]_j = (c_kv W_kvb)_j
    a_{t,s,j} = (q_nope_j . k_nope_j + q_r_j . k_r) / sqrt(nope + rope)
    p = softmax over s <= t;  x <- x + concat(sum_s p v_j) W_o

    m = RMSNorm(x; ln2); the first layer x <- x + SwiGLU(m), every other the
    routed FFN of ``latent_sparse_moe_decoder.moe`` (the family's rule: the
    top k of sigmoid scores + correction bias, renormalised, times
    routed_scaling_factor, the experts THIS CHIP HOLDS, the shared expert)
    logits = RMSNorm(x_L; norm_f) W_head

The recurrence runs A TOKEN AT A TIME (``lax.scan``; no chunks, no cache, no
batching), float32 under ``jax.default_matmul_precision("highest")``.  It
shares no code with the program.  The routed FFN, the dense FFN and the
expanded attention under a mask are the other latent reference's functions
(``latent_sparse_moe_decoder.py``: plain ``jnp`` like this file), given a
causal mask.

Departures and choices, all of them:

* THE SHARE: as ``latent_sparse_moe_decoder.py``'s (``experts_held`` experts
  from ``first_expert`` on; ``faults={"held_left_out"}`` drops one).
* q, k and v are ONE projection ``W_qkv`` whose thirds they are, and one
  conv over the three side by side: the same sums as three of each.
* ``forced`` (None for the model): the experts each position is to use, in
  place of the reference's own top-k (``benchmark/held_choice_tap.py``).
* ``faults``: named WRONG programs, one fault each, which the comparison
  that decides ``correct`` is sized against
  (``benchmark/tests/kimilinear_wrong_programs.py``).  Of the mechanism:
  ``gate_per_head`` (one decay a head: the mean of its channels' log a: a
  gated DeltaNet), ``decay_after_delta`` ((Diag(a) applied to the corrected
  state: S_t = Diag(a)(I - b k k^T) S + b k v^T), ``no_beta`` (b = 1),
  ``no_l2norm``, ``state_bf16`` (the state rounded to bfloat16 after every
  token), ``rope_applied`` (the 64 columns turned, theta 10,000),
  ``scale_from_nope`` (1 / sqrt(128)), ``held_left_out``.  Of the engine:
  ``state_lost`` / ``conv_lost`` (the state / the conv's inputs zeroed every
  ``LOST_EVERY`` tokens: a program that loses them between mixed steps),
  ``stale_start`` (a sequence begins from another's state, not zeros).  Of
  what the configuration ``assumed``: ``gate_no_dt_bias``, ``q_unscaled``,
  ``conv_no_silu``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, FrozenSet, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_decoder import (F32, _margins, dense_weight,
                                               head_logits, rms_norm)
from benchmark.reference.latent_sparse_moe_decoder import (attend, dense_ffn,
                                                           moe, rope_pairs)
from benchmark.reference.ssm_moe_decoder import router  # the family's rule

FAULTS = ("gate_per_head", "decay_after_delta", "no_beta", "no_l2norm",
          "state_bf16", "rope_applied", "scale_from_nope", "held_left_out",
          "state_lost", "conv_lost", "stale_start", "gate_no_dt_bias",
          "q_unscaled", "conv_no_silu")
NONE: FrozenSet[str] = frozenset()
#: the tokens between two losses of ``state_lost`` / ``conv_lost``: a step's
#: token budget in the cell
LOST_EVERY = 512


@partial(jax.jit, static_argnames=("heads", "faults", "lost_every"))
def kda_inputs(a, w, *, heads: int, faults: FrozenSet[str] = NONE,
               lost_every: int = LOST_EVERY):
    """What the recurrence reads of ``a (S, hidden)``: ``q, k, log_a (S, H,
    d_k)``, ``v (S, H, d_v)``, ``b (S, H)``."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        x = a @ dense_weight(w["w_qkv"])  # (S, 3 H d_k)
        cw = w["conv_w"].astype(F32)  # (taps, 3 H d_k), oldest first
        taps = cw.shape[0]
        at = jnp.arange(s)
        acc = jnp.zeros_like(x)
        for j in range(taps):
            back = taps - 1 - j
            seen = at >= back
            if "conv_lost" in faults:  # nothing from before the step's edge
                seen &= (at % lost_every) >= back
            shifted = jnp.pad(x, ((back, 0), (0, 0)))[:s]  # x_{t - back}
            acc = acc + cw[j] * jnp.where(seen[:, None], shifted, 0.0)
        x = acc if "conv_no_silu" in faults else jax.nn.silu(acc)
        q, k, v = (t.reshape(s, heads, -1) for t in jnp.split(x, 3, axis=-1))
        dk = q.shape[-1]
        if "no_l2norm" not in faults:
            q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
            k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        if "q_unscaled" not in faults:
            q = q / jnp.sqrt(F32(dk))
        f = (a @ w["w_f_down"].astype(F32)) @ w["w_f_up"].astype(F32)
        if "gate_no_dt_bias" not in faults:
            f = f + w["dt_bias"].astype(F32)
        log_a = -jnp.exp(w["A_log"].astype(F32))[:, None] \
            * jax.nn.softplus(f).reshape(s, heads, dk)
        if "gate_per_head" in faults:
            log_a = jnp.broadcast_to(log_a.mean(-1, keepdims=True),
                                     log_a.shape)
        b = jax.nn.sigmoid(a @ w["w_beta"].astype(F32))  # (S, H)
        if "no_beta" in faults:
            b = jnp.ones_like(b)
        return q, k, v, log_a, b


@partial(jax.jit, static_argnames=("faults", "lost_every"))
def kda_scan(q, k, v, log_a, b, stop, *, faults: FrozenSet[str] = NONE,
             lost_every: int = LOST_EVERY):
    """The recurrence A TOKEN AT A TIME → ``(o (S, H, d_v), the state (H,
    d_k, d_v) after ``stop`` tokens)``: a token from ``stop`` on leaves the
    state as it is (its output is then of no use)."""
    with jax.default_matmul_precision("highest"):
        heads, dk = q.shape[1:]

        def step(S, inp):
            q_t, k_t, v_t, la_t, b_t, t = inp
            old = S
            if "state_lost" in faults:
                S = jnp.where(t % lost_every == 0, 0.0, S)
            decay = jnp.exp(la_t)[:, :, None]
            if "decay_after_delta" in faults:
                S = S - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum(
                    "hkv,hk->hv", S, k_t)[:, None, :]
                S = S * decay + b_t[:, None, None] * k_t[:, :, None] \
                    * v_t[:, None, :]
            else:
                S = S * decay
                u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
                S = S + k_t[:, :, None] * u[:, None, :]
            if "state_bf16" in faults:  # (a cast there and back is dropped
                # where XLA may keep excess precision)
                S = jax.lax.reduce_precision(S, exponent_bits=8,
                                             mantissa_bits=7)
            S = jnp.where(t < stop, S, old)
            return S, jnp.einsum("hkv,hk->hv", S, q_t)

        S0 = jnp.zeros((heads, dk, v.shape[-1]), F32)
        if "stale_start" in faults:
            S0 = S0 + 0.05
        S, o = jax.lax.scan(
            step, S0, (q, k, v, log_a, b, jnp.arange(q.shape[0])))
        return o, S


def kda_layer(a, w, length=None, *, heads: int, eps: float,
              faults: FrozenSet[str] = NONE, lost_every: int = LOST_EVERY):
    """One KDA mixer on ``a (S, hidden)`` → ``(its output (S, hidden), the
    state (H, d_k, d_v) after ``length`` tokens; None: after all)``."""
    proj = frozenset(faults & {"conv_lost", "conv_no_silu", "no_l2norm",
                               "q_unscaled", "gate_no_dt_bias",
                               "gate_per_head", "no_beta"})
    q, k, v, log_a, b = kda_inputs(a, w, heads=heads, faults=proj,
                                   lost_every=lost_every)
    o, S = kda_scan(q, k, v, log_a, b,
                    a.shape[0] if length is None else length,
                    faults=frozenset(faults - proj), lost_every=lost_every)
    return kda_out(o, a, w, eps=eps), S


@partial(jax.jit, static_argnames=("eps",))
def kda_out(o, a, w, *, eps: float):
    """The head's norm, the output gate and the out projection."""
    with jax.default_matmul_precision("highest"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * w["o_norm"].astype(F32)
        gate = jax.nn.sigmoid(
            (a @ w["w_g_down"].astype(F32)) @ w["w_g_up"].astype(F32))
        return (o.reshape(a.shape[0], -1) * gate) @ dense_weight(w["wo"])


@partial(jax.jit, static_argnames=("heads", "dims", "eps", "theta", "faults"))
def latent_inputs(a, w, *, heads: int, dims, eps: float, theta: float,
                  faults: FrozenSet[str] = NONE):
    """→ (q_nope (S, H, nope), q_r (S, H, rope), c_kv (S, rank), k_r (S,
    rope)): nothing turned (``rope_applied``: the wrong program's)."""
    nope, rp = dims
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        q = (a @ dense_weight(w["w_q"])).reshape(s, heads, nope + rp)
        c_kv = rms_norm(a @ dense_weight(w["w_kva"]), w["kv_a_norm"], eps)
        q_r, k_r = q[..., nope:], a @ w["w_kr"].astype(F32)
        if "rope_applied" in faults:
            q_r = rope_pairs(q_r, theta, rp)
            k_r = rope_pairs(k_r[:, None, :], theta, rp)[:, 0]
        return q[..., :nope], q_r, c_kv, k_r


def layer_weights(params: Mapping[str, Any], kind: str, i: int
                  ) -> Dict[str, Any]:
    """Layer ``i`` of stack ``kind`` ("K": first norm and KDA, "A": first
    norm and latent attention, "D": second norm and dense FFN, "S": second
    norm and routed FFN) of the program's parameter tree under this file's
    names.  The only place that knows the program's layout."""
    lay = params["layers"][kind]
    take = partial(jax.tree.map, lambda t: t[i])
    if kind == "K":
        inner = {k: take(v) for k, v in lay["kda"].items() if k != "o_norm"}
        return {"ln1": lay["ln1"]["scale"][i],
                "o_norm": lay["kda"]["o_norm"]["scale"][i], **inner}
    if kind == "A":
        at = lay["attn"]
        return {"ln1": lay["ln1"]["scale"][i],
                "kv_a_norm": at["kv_a_norm"]["scale"][i],
                **{k: take(at[k]) for k in ("w_q", "w_kva", "w_kr", "w_kvb",
                                            "wo")}}
    inner = lay["mlp" if kind == "D" else "moe"]
    return {"ln2": lay["ln2"]["scale"][i],
            **{k: take(v) for k, v in inner.items()}}


def _router_keys(model: Mapping[str, Any]) -> Dict[str, Any]:
    return dict(top_k=model["num_experts_per_token"],
                norm_topk=bool(model["moe_renormalize"]),
                scaling=float(model["routed_scaling_factor"]))


def mixers(model: Mapping[str, Any]) -> list:
    """"K" or "A" a layer, from the published 1-indexed lists."""
    lin = model["linear_attn_config"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    out = []
    for i in range(1, model["num_hidden_layers"] + 1):
        if (i in kda) == (i in full):
            raise ValueError(f"layer {i} is in both or in neither of "
                             f"kda_layers and full_attn_layers")
        out.append("K" if i in kda else "A")
    return out


def whole_pass(params: Mapping[str, Any], model: Mapping[str, Any],
               tokens: jax.Array, last: Optional[int] = None,
               faults: FrozenSet[str] = NONE,
               forced: Optional[jax.Array] = None, keep: bool = True,
               length: Optional[int] = None) -> Dict[str, Any]:
    """One pass over ``tokens (S,)`` and everything a comparison reads from
    it: ``logits`` (of the final ``last`` positions), ``margin`` (each
    position's smallest router margin), ``router_inputs`` (a list, routed
    layer by routed layer, NumPy) and ``states`` (a list, KDA layer by KDA
    layer: the state after ``length`` tokens (None: all) ``(H, d_k, d_v)``,
    NumPy).
    ``forced (routed layers, S, k)``: the experts each position uses (a row
    of -1: the reference's own).  ``keep`` False: the lists come back
    empty."""
    faults = frozenset(faults)
    x = params["embed"]["tokens"][tokens].astype(F32)
    s = tokens.shape[0]
    eps = float(model["rms_norm_eps"])
    heads = model["num_attention_heads"]
    dims = (model["qk_nope_head_dim"], model["qk_rope_head_dim"])
    dense_first = model["first_k_dense_replace"]
    margin = jnp.full((s,), jnp.inf, F32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    inputs, states = [], []
    seen = {"K": 0, "A": 0, "D": 0, "S": 0}
    for i, mixer in enumerate(mixers(model)):
        w = layer_weights(params, mixer, seen[mixer])
        seen[mixer] += 1
        a = rms_norm(x, w["ln1"], eps)
        if mixer == "K":
            out, S = kda_layer(
                a, w, length, heads=model["linear_attn_config"]["num_heads"],
                eps=eps, faults=faults)
            if keep:
                states.append(np.asarray(S))
            del S
        else:
            q_nope, q_r, c_kv, k_r = latent_inputs(
                a, w, heads=heads, dims=dims, eps=eps,
                theta=float(model["rope_theta"]), faults=faults)
            out = attend(q_nope, q_r, c_kv, k_r, causal, w, nope=dims[0],
                         faults=frozenset(faults & {"scale_from_nope"}))
            del q_nope, q_r, c_kv, k_r
        x = x + out
        del a, out
        ffn = "D" if i < dense_first else "S"
        fw = layer_weights(params, ffn, seen[ffn])
        m = rms_norm(x, fw["ln2"], eps)
        if ffn == "D":
            x = x + dense_ffn(m, fw)
        else:
            out, mg = moe(m, fw, None if forced is None
                          else forced[seen["S"]], **_router_keys(model),
                          first=int(model["first_expert"]),
                          faults=frozenset(faults & {"held_left_out"}))
            x = x + out
            margin = jnp.minimum(margin, mg)
            if keep:
                inputs.append(np.asarray(m))
            del out
        seen[ffn] += 1
        del m
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    return {"logits": head_logits(x, params["final_norm"]["scale"],
                                  params["lm_head"]["w"], eps=eps),
            "margin": margin, "router_inputs": inputs, "states": states}


def own_choices(params: Mapping[str, Any], model: Mapping[str, Any],
                inputs: list) -> jax.Array:
    """The experts the reference's router picks at every position of every
    routed layer ``(routed layers, S, k)``, on what each layer's router read
    along some pass (``whole_pass``'s ``router_inputs``)."""
    picked = []
    for i, m in enumerate(inputs):
        w = layer_weights(params, "S", i)
        picked.append(router(m, w["router"], w["router_bias"],
                             **_router_keys(model))[2])
    return jnp.stack(picked)


def logits(params, model, tokens, last=None, faults=NONE, forced=None
           ) -> jax.Array:
    return whole_pass(params, model, tokens, last, faults, forced,
                      keep=False)["logits"]


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int,
                   faults: FrozenSet[str] = NONE):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference's OWN routing,
    which reads the whole sequence in one uncached pass (``faults``: under a
    named wrong program's)."""
    lg = logits(params, model, sequence, last=len(sequence) - n_prompt + 1,
                faults=faults)
    return _margins(lg[:-1], sequence[n_prompt:])
