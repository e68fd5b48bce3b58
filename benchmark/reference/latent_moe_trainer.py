"""Plain reference for TRAINING a decoder with latent attention (MLA, no query
compression, no indexer), a leading dense layer, softmax-routed experts of
which one chip holds a share, shared experts and the sequence-wise balance
loss (DeepSeek-V2-Lite, ``model_type: deepseek_v2``): the loss, its
gradients, AND what one AdamW step makes of the parameters
(:func:`adamw_step`).

Written from the model's ``config.json`` and the published description
(DeepSeek-V2, sections 2.1 and 2.2; the released modelling code's gate), not
from the program's model file.  On one sequence ``x (S, hidden)``, a layer,
``h = RMSNorm(x; ln1)``, position ``t``, keys ``s <= t``:

    [q_nope(128) | q_rope(64)]_j = (h W_q)_j                       16 heads
    [c_kv(512) | k_rope(64)] = h [W_kva | W_kr];  c_kv <- RMSNorm(c_kv)
    RoPE (adjacent pairs, YaRN's blended frequencies) on q_rope and on the
    one k_rope every head shares
    [k_nope(128) | v(128)]_j = (c_kv W_kvb)_j
    a_{t,s,j} = (q_nope_j . k_nope_j + q_rope_j . k_rope) x scale
    scale = (128 + 64)^-0.5 x m(40, 0.707)^2,  m(s, a) = 0.1 a ln s + 1
    p = softmax over s <= t;  o_j = sum_s p v_j;  x <- x + concat(o) W_o

    m = RMSNorm(x; ln2)
    layer 0:   x <- x + (silu(m W_gate) * (m W_in)) W_out            10,944
    layers 1+: P = softmax(m W_r) over ALL 64 experts, float32; the 6
               largest; gates the raw probabilities (norm_topk_prob false,
               routed_scaling_factor 1);  x <- x + sum over the chosen
               experts THIS CHIP HOLDS of P_e SwiGLU_e(m) + SwiGLU_shared(m)
               (the two shared experts are one SwiGLU of 2 x 1,408)
    balance loss of the layer (seq_aux): sum_i f_i mean_t(P_i) over the
               sequence, f_i = 64 / (6 S) x the sequence's tokens that chose i
    loss = mean over the B (S - 1) predicted positions of the next token's
           cross-entropy over the vocabulary slice
           + alpha x the mean over the sequences of the layers' balance losses

Float32 under ``jax.default_matmul_precision("highest")``; no kernel, no
``jax.checkpoint``.  It shares no code with the program.

Departures and choices, all of them:

* COMPUTED IN STAGES, so that it fits beside nothing else on one chip at 8,192
  positions and the published widths.  A sequence at a time, a layer at a
  time, and a layer in three stages (projections; attention, a head at a
  time: one head's scores are 0.27 GB; output projection and FFN).  The
  forward sweep keeps each layer's input; the backward sweep runs ``jax.vjp``
  of each stage on the kept input.  The mathematics is the chain rule over
  the same functions; nothing is approximated.
* THE SHARE.  ``model["experts_held"]`` experts from ``model["first_expert"]``
  on are this chip's (the weights' expert axis holds just them).  The router
  scores all ``n_routed_experts`` and picks its top k among all; what the
  absent experts would have added is left out of the forward and of the
  backward alike (the guide's rule for one chip of an expert-parallel group).
  The balance loss is over all 64: the router is whole on every chip.  Each
  held expert is computed on every row and masked by its gate (dense, not
  gathered): the same sum.
* ``W_kva`` and ``W_kr`` are two matrices, the same mathematics as one with
  their columns side by side.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights`` and raised to float32; gradients come back in the tree's
  own layout, float32.
* ``assumed`` in the configuration file: the balance loss's coefficient
  (0.001), adjacent-pair RoPE, cos and sin times ``m(40, mscale) / m(40,
  mscale_all_dim)`` = 1.
* ``faults``: named WRONG programs, one fault each, which the comparison that
  decides ``correct`` is sized against (:data:`FAULTS`).
* THE OPTIMIZER'S STEP is AdamW as its paper writes it, from moments at zero,
  without weight decay (the configuration has none), in float32, the result
  rounded to the dtype the parameters are held in: the configuration holds
  them in bfloat16 WITHOUT a float32 copy, so a change under half a unit in
  the last place of a parameter is lost in the deployment and here alike (a
  norm's scale near 1 does not move at a learning rate of 2e-4).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, FrozenSet, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
BF16 = jnp.bfloat16
NONE: FrozenSet[str] = frozenset()
#: ``no_mscale``: YaRN's mscale left out of the softmax scale; ``scale_from_
#: nope``: the scale from 128, not 192; ``v_from_k_nope``: v read from the
#: k_nope half of ``c_kv W_kvb``; ``no_shared``: the shared experts left out;
#: ``gates_renormalised``: the chosen probabilities divided by their sum;
#: ``no_balance_loss``; ``absent_counted``: one absent expert computed with a
#: held one's weights; ``rope_halves``: RoPE over (i, i + 32); ``no_yarn``:
#: plain RoPE frequencies; ``router_bf16`` and ``sums_bf16``: the nearest
#: precision below the configuration's (router logits, or every matmul's
#: operands and sums, in bfloat16)
FAULTS = ("no_mscale", "scale_from_nope", "v_from_k_nope", "no_shared",
          "gates_renormalised", "no_balance_loss", "absent_counted",
          "rope_halves", "no_yarn", "router_bf16", "sums_bf16")


def _mm(a: jax.Array, b: jax.Array, faults: FrozenSet[str]) -> jax.Array:
    if "sums_bf16" in faults:
        return jnp.dot(a.astype(BF16), b.astype(BF16),
                       preferred_element_type=BF16).astype(F32)
    return a @ b


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(model: Mapping[str, Any],
                  faults: FrozenSet[str] = NONE) -> float:
    dn, dr = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    scale = (dn if "scale_from_nope" in faults else dn + dr) ** -0.5
    rs = model.get("rope_scaling")
    if rs and "no_mscale" not in faults:
        scale *= yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    return scale


def rope_angles(model: Mapping[str, Any], s: int,
                faults: FrozenSet[str] = NONE) -> Tuple[jax.Array, jax.Array]:
    """cos and sin ``(S, dims / 2)``: YaRN's frequencies as the released code
    blends them (a frequency that turns more than ``beta_fast`` times in the
    original context stays, one that turns fewer than ``beta_slow`` times is
    divided by ``factor``, a linear ramp between)."""
    dims, base = model["qk_rope_head_dim"], float(model["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dims, 2, dtype=np.float64) / dims)
    rs = model.get("rope_scaling")
    inv, factor = extra, 1.0
    if rs and "no_yarn" not in faults:
        orig = rs["original_max_position_embeddings"]

        def correction(turns):
            return dims * math.log(orig / (turns * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(correction(rs["beta_fast"])), 0)
        high = min(math.ceil(correction(rs["beta_slow"])), dims - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dims // 2) - low) / (high - low), 0, 1)
        inv = extra / rs["factor"] * ramp + extra * (1 - ramp)
        factor = (yarn_get_mscale(rs["factor"], rs["mscale"])
                  / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray(np.cos(ang) * factor, F32),
            jnp.asarray(np.sin(ang) * factor, F32))


def rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array,
               halves: bool = False) -> jax.Array:
    """``x (S, heads, dims)``: position p rotates the pair ``(2i, 2i + 1)``;
    ``halves``: the pairs ``(i, i + dims / 2)``, the wrong program."""
    c, s = cos[:, None, :], sin[:, None, :]
    if halves:
        d = x.shape[-1] // 2
        a, b = x[..., :d], x[..., d:]
        return jnp.concatenate([a * c - b * s, b * c + a * s], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


# ---------------------------------------------------------------------------
# the three stages of a layer, on one sequence
# ---------------------------------------------------------------------------


def project(x, w, cos, sin, *, model, faults):
    """``x (S, h)`` → ``q (H, S, 192)``, ``k (H, S, 192)``, ``v (H, S, 128)``."""
    H = model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    s = x.shape[0]
    h = rms_norm(x, w["ln1"], model["rms_norm_eps"])
    # one product for [W_q | W_kva | W_kr]: their columns side by side
    n_q, n_c = w["w_q"].shape[1], w["w_kva"].shape[1]
    hw = _mm(h, jnp.concatenate([w["w_q"], w["w_kva"], w["w_kr"]], 1), faults)
    q = hw[:, :n_q].reshape(s, H, dn + dr)
    halves = "rope_halves" in faults
    q_rope = rope_pairs(q[..., dn:], cos, sin, halves)
    c_kv = rms_norm(hw[:, n_q:n_q + n_c], w["kv_a_norm"],
                    model["rms_norm_eps"])
    k_rope = rope_pairs(hw[:, None, n_q + n_c:], cos, sin,
                        halves)  # (S, 1, 64): one, shared by the heads
    kv = _mm(c_kv, w["w_kvb"].reshape(c_kv.shape[-1], H * (dn + dv)),
             faults).reshape(s, H, dn + dv)
    k_nope = kv[..., :dn]
    v = kv[..., :dv] if "v_from_k_nope" in faults else kv[..., dn:]
    q = jnp.concatenate([q[..., :dn], q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (s, H, dr))], -1)
    return (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2))


def attend_head(q, k, v, *, scale, faults):
    """One head: ``q, k (S, 192)``, ``v (S, 128)`` → ``(S, 128)``."""
    s = q.shape[0]
    a = _mm(q, k.T, faults) * scale
    a = jnp.where(jnp.tril(jnp.ones((s, s), bool)), a, -jnp.inf)
    return _mm(jax.nn.softmax(a, axis=-1), v, faults)


def router(m, w_r, *, model, faults):
    """→ (probabilities ``(S, E)``, chosen experts ``(S, k)``, gates)."""
    if "router_bf16" in faults:
        logits = jnp.dot(m.astype(BF16), w_r.astype(BF16),
                         preferred_element_type=BF16).astype(F32)
    else:
        logits = m @ w_r  # float32 whatever ``sums_bf16`` says: the gate's own
    probs = jax.nn.softmax(logits, axis=-1)
    gates, chosen = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"] or "gates_renormalised" in faults:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return probs, chosen, gates * model["routed_scaling_factor"]


def swiglu(m, w_gate, w_in, w_out, faults):
    """``(silu(m W_gate) * (m W_in)) W_out``; gate and up as one product of
    their columns side by side (a product at "highest" takes the TPU's
    compiler seconds, and a run compiles every one of them)."""
    both = _mm(m, jnp.concatenate([w_gate, w_in], 1), faults)
    f = w_gate.shape[1]
    return _mm(jax.nn.silu(both[:, :f]) * both[:, f:], w_out, faults)


def finish(x, o, w, *, model, faults, sparse):
    """``x (S, h)`` the layer's input, ``o (H, S, 128)`` the heads' outputs →
    (the layer's output, its balance loss for this sequence, the router's
    input, probabilities and choices)."""
    s = x.shape[0]
    x = x + _mm(o.transpose(1, 0, 2).reshape(s, -1), w["wo"], faults)
    m = rms_norm(x, w["ln2"], model["rms_norm_eps"])
    if not sparse:
        return (x + swiglu(m, w["w_gate"], w["w_in"], w["w_out"], faults),
                jnp.zeros((), F32), None)
    E, k = model["n_routed_experts"], model["num_experts_per_tok"]
    first, held = model["first_expert"], model["experts_held"]
    probs, chosen, gates = router(m, w["router"], model=model, faults=faults)
    def add_expert(y, ew):  # one held expert: every row, masked by its gate
        e, w_gate, w_in, w_out = ew
        gate = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        return y + gate[:, None] * swiglu(m, w_gate, w_in, w_out,
                                          faults), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), w["w_gate"], w["w_in"], w["w_out"]))
    if "absent_counted" in faults:  # the expert after the share, as if held
        gate = jnp.sum(jnp.where(chosen == (first + held) % E, gates, 0.0), -1)
        y = y + gate[:, None] * swiglu(m, w["w_gate"][0], w["w_in"][0],
                                       w["w_out"][0], faults)
    if "no_shared" not in faults:
        y = y + swiglu(m, w["sh_w_gate"], w["sh_w_in"], w["sh_w_out"], faults)
    f = jnp.zeros((E,), F32).at[chosen.reshape(-1)].add(1.0) * (E / (k * s))
    aux = jnp.sum(jax.lax.stop_gradient(f) * probs.mean(0))
    return x + y, aux, (m, probs, chosen)


def head_loss(x, w, labels, *, model, faults):
    """The sequence's summed next-token cross-entropy: position t predicts
    ``labels[t]`` = token t + 1; the last position predicts nothing."""
    lg = _mm(rms_norm(x[:-1], w["final_norm"], model["rms_norm_eps"]),
             w["head"], faults)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


# ---------------------------------------------------------------------------
# the program's tree
# ---------------------------------------------------------------------------


def layer_weights(params: Mapping[str, Any], model: Mapping[str, Any], i: int
                  ) -> Tuple[Dict[str, jax.Array], bool]:
    """Layer ``i`` of the program's parameter tree under this file's names,
    float32, and whether its FFN is routed.  With :func:`tree_of`, the only
    place that knows the program's layout: stack "A" every layer's norms and
    attention, "D" the dense FFNs, "S" the routed ones."""
    dense = model["first_k_dense_replace"]
    lay = params["layers"]
    at = lay["A"]["attn"]
    w = {"ln1": lay["A"]["ln1"]["scale"][i], "ln2": lay["A"]["ln2"]["scale"][i],
         "kv_a_norm": at["kv_a_norm"]["scale"][i],
         **{k: at[k][i] for k in ("w_q", "w_kva", "w_kr", "w_kvb", "wo")}}
    sparse = i >= dense
    inner = lay["S"]["moe"] if sparse else lay["D"]["mlp"]
    w.update({k: v[i - dense if sparse else i] for k, v in inner.items()})
    return jax.tree.map(lambda t: t.astype(F32), w), sparse


def tree_of(layer_grads: List[Dict[str, jax.Array]], embed, final_norm, head,
            model: Mapping[str, Any]) -> Dict[str, Any]:
    """The layers' gradients under this file's names → the program's tree."""
    dense = model["first_k_dense_replace"]

    def stack(rows, key):
        return jnp.stack([g[key] for g in rows])

    A, D, S = layer_grads, layer_grads[:dense], layer_grads[dense:]
    layers: Dict[str, Any] = {"A": {
        "ln1": {"scale": stack(A, "ln1")}, "ln2": {"scale": stack(A, "ln2")},
        "attn": {"kv_a_norm": {"scale": stack(A, "kv_a_norm")},
                 **{k: stack(A, k) for k in ("w_q", "w_kva", "w_kr", "w_kvb",
                                             "wo")}}}}
    if D:
        layers["D"] = {"mlp": {k: stack(D, k)
                               for k in ("w_gate", "w_in", "w_out")}}
    if S:
        layers["S"] = {"moe": {k: stack(S, k) for k in (
            "router", "w_gate", "w_in", "w_out", "sh_w_gate", "sh_w_in",
            "sh_w_out")}}
    return {"embed": {"tokens": embed}, "layers": layers,
            "final_norm": {"scale": final_norm}, "lm_head": {"w": head}}


# ---------------------------------------------------------------------------
# loss and gradients
# ---------------------------------------------------------------------------


class _Stages:
    """The jitted stages and their ``jax.vjp``s for one (model, faults)."""

    def __init__(self, model: Mapping[str, Any], faults: FrozenSet[str]):
        scale = softmax_scale(model, faults)
        kw = dict(model=model, faults=faults)
        project_ = partial(project, **kw)
        head_ = partial(head_loss, **kw)
        one_head = partial(attend_head, scale=scale, faults=faults)

        def attend(q, k, v):  # a head at a time
            return jax.lax.map(lambda a: one_head(*a), (q, k, v))

        def attend_vjp(q, k, v, do):
            return jax.lax.map(
                lambda a: jax.vjp(one_head, *a[:3])[1](a[3]), (q, k, v, do))

        self.project = jax.jit(project_)
        self.project_vjp = jax.jit(
            lambda x, w, cos, sin, cot: jax.vjp(
                lambda x_, w_: project_(x_, w_, cos, sin), x, w)[1](cot))
        self.attend = jax.jit(attend)
        self.attend_vjp = jax.jit(attend_vjp)
        self.finish, self.finish_vjp = {}, {}
        for sparse in (False, True):
            fin = partial(finish, sparse=sparse, **kw)
            self.finish[sparse] = jax.jit(fin)
            self.finish_vjp[sparse] = jax.jit(
                lambda x, o, w, cot, fin=fin: jax.vjp(
                    lambda *a: fin(*a)[:2], x, o, w)[1](cot))
        self.head = jax.jit(head_)
        self.head_vjp = jax.jit(
            lambda x, w, labels, cot: jax.vjp(
                lambda x_, w_: head_(x_, w_, labels), x, w)[1](cot))


def loss_and_grads(params: Mapping[str, Any], model: Mapping[str, Any],
                   input_ids: np.ndarray, faults: FrozenSet[str] = NONE,
                   grads: bool = True) -> Dict[str, Any]:
    """→ ``loss`` (cross-entropy + alpha x balance loss), ``ce``, ``aux`` (the
    layers' balance losses summed, mean over the sequences, before alpha),
    ``grads`` (the program's tree, float32; None without ``grads``),
    ``router`` (for the FIRST sequence, routed layer by routed layer: the
    router's input rounded to bfloat16 as the program would see it, the
    float32 probabilities and the choices).  ``model``: the published keys as
    run, with ``experts_held``, ``first_expert`` and ``aux_loss_alpha``."""
    faults = frozenset(faults)
    unknown = faults - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    ids = np.asarray(input_ids)
    B, s = ids.shape
    L = model["num_hidden_layers"]
    alpha = 0.0 if "no_balance_loss" in faults else model["aux_loss_alpha"]
    count = B * (s - 1)
    with jax.default_matmul_precision("highest"):
        st = _Stages(model, faults)
        cos, sin = rope_angles(model, s, faults)
        embed = params["embed"]["tokens"].astype(F32)
        head_w = {"final_norm": params["final_norm"]["scale"].astype(F32),
                  "head": params["lm_head"]["w"].astype(F32)}
        ce = aux = 0.0
        taps: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        g_layers = None
        g_embed = jnp.zeros_like(embed) if grads else None
        g_head = jax.tree.map(jnp.zeros_like, head_w) if grads else None
        for b in range(B):
            tok = jnp.asarray(ids[b])
            xs = [embed[tok]]
            for i in range(L):  # the forward sweep keeps each layer's input
                w, sparse = layer_weights(params, model, i)
                o = st.attend(*st.project(xs[-1], w, cos, sin))
                x, a, tap = st.finish[sparse](xs[-1], o, w)
                aux += float(a) / B
                if tap is not None and b == 0:
                    taps.append((np.asarray(tap[0].astype(BF16).astype(F32)),
                                 np.asarray(tap[1]), np.asarray(tap[2])))
                xs.append(x)
            ce += float(st.head(xs[-1], head_w, tok[1:])) / count
            if not grads:
                continue
            g, gh = st.head_vjp(xs[-1], head_w, tok[1:],
                                jnp.asarray(1.0 / count, F32))
            g_head = jax.tree.map(jnp.add, g_head, gh)
            rows: List[Dict[str, jax.Array]] = []
            for i in reversed(range(L)):
                w, sparse = layer_weights(params, model, i)
                q, k, v = st.project(xs[i], w, cos, sin)
                o = st.attend(q, k, v)
                gx, go, gw = st.finish_vjp[sparse](
                    xs[i], o, w, (g, jnp.asarray(alpha / B, F32)))
                gx2, gw2 = st.project_vjp(xs[i], w, cos, sin,
                                          st.attend_vjp(q, k, v, go))
                g = gx + gx2
                rows.append(jax.tree.map(jnp.add, gw, gw2))
                xs.pop()
            rows.reverse()
            g_layers = rows if g_layers is None else [
                jax.tree.map(jnp.add, a_, b_) for a_, b_ in zip(g_layers, rows)]
            g_embed = g_embed.at[tok].add(g)
    out = {"loss": ce + alpha * aux, "ce": ce, "aux": aux, "router": taps,
           "grads": None}
    if grads:
        out["grads"] = tree_of(g_layers, g_embed, g_head["final_norm"],
                               g_head["head"], model)
    return out


# ---------------------------------------------------------------------------
# one optimizer step
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "step"))
def _adamw_leaf(p, g, *, lr, b1, b2, eps, step):
    g = g.astype(F32)
    m = (1.0 - b1) * g / (1.0 - b1 ** step)
    v = (1.0 - b2) * g * g / (1.0 - b2 ** step)
    return (p.astype(F32) - lr * m / (jnp.sqrt(v) + eps)).astype(p.dtype)


def adamw_step(params: Mapping[str, Any], grads: Mapping[str, Any], *,
               lr: float, b1: float = 0.9, b2: float = 0.999,
               eps: float = 1e-8, step: int = 1) -> Dict[str, Any]:
    """The parameters after step ``step`` of AdamW from moments at zero (so
    only the first step is what a trainer makes), without weight decay: in
    float32, ``m = (1 - b1) g``, ``v = (1 - b2) g^2``, each over its bias
    correction ``1 - b^step``, ``p <- p - lr m / (sqrt(v) + eps)``, then
    rounded to the dtype the parameter is held in (to nearest: bfloat16
    parameters without a float32 copy are part of the configuration), a leaf
    at a time."""
    return jax.tree.map(partial(_adamw_leaf, lr=float(lr), b1=float(b1),
                                b2=float(b2), eps=float(eps), step=int(step)),
                        params, grads)
