"""Plain reference for TRAINING a decoder of window and full attention layers
mixed (grouped queries), a gate on the attention's output, norms on both sides
of each branch, leading dense layers, sigmoid-routed experts of which one chip
holds a share, one shared expert, and a router bias that a rule moves
(Trinity-Mini, ``model_type: afmoe``): the loss, its gradients, every expert's
assignments, the bias after the rule, AND what one AdamW step makes of the
other parameters (``latent_moe_trainer.adamw_step``, the optimizer's paper).

Written from the model's ``config.json`` and the released ``afmoe`` modelling
code's reading of each key, not from the program's model file.  On one
sequence ``x (S, h)``, a layer of kind "sliding" or "full", position ``t``:

    x_0   = E[token] * sqrt(h)                                  mup_enabled
    a     = RMSNorm(x; ln1)
    q,k,v = heads(a Wq), heads(a Wk), heads(a Wv);  g = a Wg    no bias
    q,k   = RMSNorm(q; q_norm), RMSNorm(k; k_norm)   over each head's 128
    q,k   = RoPE(q, k) on a "sliding" layer; UNROTATED on a "full" layer
    o_t   = softmax_s(q_t . k_s / sqrt(128)) v_s,  s <= t, and on a "sliding"
            layer s > t - window;  query head j reads K/V head j // (H / KV)
    x     = x + RMSNorm((o * sigmoid(g)) Wo; ln1_post)
    m     = RMSNorm(x; ln2)
    f     = SwiGLU(m)                                 a leading dense layer
    f     = SwiGLU_shared(m) + sum over the chosen experts THIS CHIP HOLDS of
            w_e SwiGLU_e(m)                           a routed layer:
            s = sigmoid(m W_r), float32; the 8 largest of s + b;
            w = route_scale * s_e / (sum of the chosen s + 1e-20)
    x     = x + RMSNorm(f; ln2_post)
    loss  = mean over the B (S - 1) predicted positions of the next token's
            cross-entropy over the vocabulary slice; NO balance loss
    after the step, each routed layer: c_e = the step's assignments to expert
            e (all of them, held here or not);
            d = load_balance_coeff * sign(mean(c) - c);  b <- b + d - mean(d)

Float32 under ``jax.default_matmul_precision("highest")``; no kernel, no
``jax.checkpoint`` but round the head's blocks.  It shares no code with the
program.

Departures and choices, all of them:

* COMPUTED IN STAGES, as ``latent_moe_trainer.py`` is, so that 16,384
  positions at the published widths fit beside nothing else on one chip: a
  sequence at a time, a layer at a time, a layer in three stages
  (projections; attention, a query head at a time: one head's scores are
  1.07 GB; gate, output projection and FFN), the head's loss in blocks of
  :data:`HEAD_BLOCK` positions.  The backward sweep runs ``jax.vjp`` of each
  stage on the kept input: the chain rule over the same functions.
* THE SHARE.  ``model["experts_held"]`` experts from ``model["first_expert"]``
  on are this chip's.  The router scores all ``num_experts`` and picks among
  all; what the absent experts would have added is left out of forward and
  backward alike; the counts are of ALL experts.  Each held expert is
  computed on every row and masked by its weight: the same sum.
* RoPE rotates adjacent pairs ``(2i, 2i + 1)``, the program's convention;
  the Hugging Face port's half-split pairs are the same function of permuted
  Wq / Wk columns and q / k norm scales (``rope_halves`` shows the other
  reading on the same weights).
* ``assumed`` in the configuration file: the rule itself, the counts being
  this chip's tokens', RoPE on the window layers only, muP read as the
  embedding's scale alone, the window's edge.
* ``faults``: named WRONG programs, one fault each, which the comparison that
  decides ``correct`` is sized against (:data:`FAULTS`).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, FrozenSet, List, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.latent_moe_trainer import adamw_step

F32 = jnp.float32
BF16 = jnp.bfloat16
NONE: FrozenSet[str] = frozenset()
#: positions the head's loss takes at a time
HEAD_BLOCK = 2048
#: ``no_gate``: the attention's output ungated; ``rope_on_full`` /
#: ``no_rope_on_window``: position on the wrong kind of layer; ``rope_halves``:
#: RoPE over (i, i + 64); ``no_window``: a window layer sees every key;
#: ``window_less_one``: a query sees window - 1 keys; ``no_post_attn_norm`` /
#: ``no_post_mlp_norm``: a post-branch norm left out; ``qk_norm_whole``: q and
#: k normed over all heads' width at once; ``biased_weight``: the biased score
#: used as the weight; ``no_renorm``; ``no_route_scale``; ``embed_unscaled``;
#: ``no_shared``; ``absent_counted``: one absent expert computed with a held
#: one's weights; ``bias_differentiated``: the bias given the gradient its
#: score has and AdamW's step beside the rule; ``bias_left``: the rule not
#: run; ``rule_uncentred``: ``b + d`` without ``- mean(d)``; ``sums_bf16``:
#: the nearest precision below the configuration's (every matmul's operands
#: and sums in bfloat16)
FAULTS = ("no_gate", "rope_on_full", "no_rope_on_window", "rope_halves",
          "no_window", "window_less_one", "no_post_attn_norm",
          "no_post_mlp_norm", "qk_norm_whole", "biased_weight", "no_renorm",
          "no_route_scale", "embed_unscaled", "no_shared", "absent_counted",
          "bias_differentiated", "bias_left", "rule_uncentred", "sums_bf16")


def _mm(a: jax.Array, b: jax.Array, faults: FrozenSet[str]) -> jax.Array:
    if "sums_bf16" in faults:
        return jnp.dot(a.astype(BF16), b.astype(BF16),
                       preferred_element_type=BF16).astype(F32)
    return a @ b


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope_angles(model: Mapping[str, Any], s: int
                ) -> Tuple[jax.Array, jax.Array]:
    """cos and sin ``(S, head_dim / 2)``, plain RoPE at ``rope_theta``."""
    d = model["head_dim"]
    inv = 1.0 / float(model["rope_theta"]) ** (
        np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
         halves: bool = False) -> jax.Array:
    """``x (S, heads, D)``: position p rotates the pair ``(2i, 2i + 1)``;
    ``halves``: the pairs ``(i, i + D / 2)``."""
    c, s = cos[:, None, :], sin[:, None, :]
    if halves:
        d = x.shape[-1] // 2
        a, b = x[..., :d], x[..., d:]
        return jnp.concatenate([a * c - b * s, b * c + a * s], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], -1).reshape(x.shape)


def kinds_of(model: Mapping[str, Any]) -> List[str]:
    """"sliding" or "full", a layer, from the published ``layer_types``."""
    return [t.split("_")[0] for t in model["layer_types"]]


# ---------------------------------------------------------------------------
# the three stages of a layer, on one sequence
# ---------------------------------------------------------------------------


def project(x, w, cos, sin, *, model, faults, kind):
    """``x (S, h)`` → ``q (H, S, D)``, ``k, v (KV, S, D)``, ``g (S, H D)``."""
    H, KV, D = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    eps = model["rms_norm_eps"]
    s = x.shape[0]
    a = rms_norm(x, w["ln1"], eps)
    # one product for [Wq | Wk | Wv | Wg]: their columns side by side
    hw = _mm(a, jnp.concatenate([w["wq"], w["wk"], w["wv"], w["wg"]], 1),
             faults)
    nq, nk = H * D, KV * D
    q, k = hw[:, :nq], hw[:, nq:nq + nk]
    if "qk_norm_whole" in faults:
        q = rms_norm(q, jnp.tile(w["q_norm"], H), eps)
        k = rms_norm(k, jnp.tile(w["k_norm"], KV), eps)
    q, k = q.reshape(s, H, D), k.reshape(s, KV, D)
    if "qk_norm_whole" not in faults:
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    rotate = kind == "sliding"
    if "rope_on_full" in faults:
        rotate = True
    if "no_rope_on_window" in faults:
        rotate = False
    if rotate:
        halves = "rope_halves" in faults
        q, k = rope(q, cos, sin, halves), rope(k, cos, sin, halves)
    v = hw[:, nq + nk:nq + 2 * nk].reshape(s, KV, D)
    return (q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            hw[:, nq + 2 * nk:])


def window_of(model: Mapping[str, Any], kind: str,
              faults: FrozenSet[str]) -> int:
    """Keys a query sees on a layer of ``kind``, itself among them (0: all
    before it)."""
    if kind != "sliding" or "no_window" in faults:
        return 0
    return model["sliding_window"] - ("window_less_one" in faults)


def attend_head(q, k, v, *, window, faults):
    """One query head: ``q, k, v (S, D)`` → ``(S, D)``."""
    s, d = q.shape
    a = _mm(q, k.T, faults) * d ** -0.5
    t, u = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = u <= t
    if window:
        keep = keep & (u > t - window)
    return _mm(jax.nn.softmax(jnp.where(keep, a, -jnp.inf), axis=-1), v,
               faults)


def router(m, w_r, bias, *, model, faults):
    """→ (scores ``(S, E)``, chosen experts ``(S, k)``, their weights)."""
    scores = jax.nn.sigmoid(m @ w_r)  # float32 whatever ``sums_bf16`` says
    choice = scores + bias
    _, chosen = jax.lax.top_k(choice, model["num_experts_per_tok"])
    weights = jnp.take_along_axis(
        choice if "biased_weight" in faults else scores, chosen, axis=-1)
    if "bias_differentiated" in faults:  # the bias's gradient: its score's
        b = bias[chosen]
        weights = weights + b - jax.lax.stop_gradient(b)
    if model["route_norm"] and "no_renorm" not in faults:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in faults:
        weights = weights * model["route_scale"]
    return scores, chosen, weights


def swiglu(m, w_gate, w_in, w_out, faults):
    """``(silu(m W_gate) * (m W_in)) W_out``, gate and up as one product."""
    both = _mm(m, jnp.concatenate([w_gate, w_in], 1), faults)
    f = w_gate.shape[1]
    return _mm(jax.nn.silu(both[:, :f]) * both[:, f:], w_out, faults)


def finish(x, o, g, w, *, model, faults, sparse):
    """``x (S, h)`` the layer's input, ``o (H, S, D)`` the heads' outputs,
    ``g (S, H D)`` the gate's projection → (the layer's output; for a routed
    layer the router's input, scores and choices, and every expert's
    count)."""
    s = x.shape[0]
    eps = model["rms_norm_eps"]
    o = o.transpose(1, 0, 2).reshape(s, -1)
    if "no_gate" not in faults:
        o = o * jax.nn.sigmoid(g)
    att = _mm(o, w["wo"], faults)
    if "no_post_attn_norm" not in faults:
        att = rms_norm(att, w["ln1_post"], eps)
    x = x + att
    m = rms_norm(x, w["ln2"], eps)
    tap = None
    if not sparse:
        y = swiglu(m, w["w_gate"], w["w_in"], w["w_out"], faults)
    else:
        E = model["num_experts"]
        first, held = model["first_expert"], model["experts_held"]
        scores, chosen, weights = router(m, w["router"], w["router_bias"],
                                         model=model, faults=faults)

        def add_expert(y, ew):  # one held expert: every row, by its weight
            e, w_gate, w_in, w_out = ew
            gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
            return y + gate[:, None] * swiglu(m, w_gate, w_in, w_out,
                                              faults), None

        y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
            jnp.arange(held), w["w_gate"], w["w_in"], w["w_out"]))
        if "absent_counted" in faults:  # the expert after the share
            gate = jnp.sum(jnp.where(chosen == (first + held) % E, weights,
                                     0.0), -1)
            y = y + gate[:, None] * swiglu(m, w["w_gate"][0], w["w_in"][0],
                                           w["w_out"][0], faults)
        if "no_shared" not in faults:
            y = y + swiglu(m, w["sh_w_gate"], w["sh_w_in"], w["sh_w_out"],
                           faults)
        counts = jnp.zeros((E,), jnp.int32).at[chosen.reshape(-1)].add(1)
        tap = (m, scores, chosen, counts)
    if "no_post_mlp_norm" not in faults:
        y = rms_norm(y, w["ln2_post"], eps)
    return x + y, tap


def head_loss(x, w, labels, *, model, faults):
    """The sequence's summed next-token cross-entropy: position t predicts
    ``labels[t]`` = token t + 1, the last position nothing; in blocks."""
    n = x.shape[0] - 1
    block = min(HEAD_BLOCK, n + 1)
    pad = -n % block
    xs = jnp.pad(x[:-1], ((0, pad), (0, 0))).reshape(-1, block, x.shape[1])
    ls = jnp.pad(labels, (0, pad)).reshape(-1, block)
    live = (jnp.arange(n + pad) < n).reshape(-1, block)

    @jax.checkpoint
    def one(a):
        xb, lb, keep = a
        lg = _mm(rms_norm(xb, w["final_norm"], model["rms_norm_eps"]),
                 w["head"], faults)
        nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                                   lb[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(keep, nll, 0.0))

    return jax.lax.map(one, (xs, ls, live)).sum()


# ---------------------------------------------------------------------------
# the program's tree
# ---------------------------------------------------------------------------

_ATTN = ("wq", "wk", "wv", "wg", "wo")
_NORMS = ("ln1", "ln2", "ln1_post", "ln2_post")
_MOE = ("router", "router_bias", "w_gate", "w_in", "w_out", "sh_w_gate",
        "sh_w_in", "sh_w_out")
_MLP = ("w_gate", "w_in", "w_out")


def layer_weights(params: Mapping[str, Any], model: Mapping[str, Any], i: int
                  ) -> Tuple[Dict[str, jax.Array], bool]:
    """Layer ``i`` of the program's parameter tree under this file's names,
    float32, and whether its FFN is routed.  With :func:`tree_of`, the only
    place that knows the program's layout: stack "A" every layer's norms and
    attention, "D" the dense FFNs, "S" the routed ones."""
    dense = model["num_dense_layers"]
    lay = params["layers"]
    at = lay["A"]["attn"]
    w = {**{k: lay["A"][k]["scale"][i] for k in _NORMS},
         **{k: at[k]["scale"][i] for k in ("q_norm", "k_norm")},
         **{k: at[k][i] for k in _ATTN}}
    sparse = i >= dense
    inner = lay["S"]["moe"] if sparse else lay["D"]["mlp"]
    w.update({k: v[i - dense if sparse else i] for k, v in inner.items()})
    return jax.tree.map(lambda t: t.astype(F32), w), sparse


def tree_of(layer_grads: List[Dict[str, jax.Array]], embed, final_norm, head,
            model: Mapping[str, Any]) -> Dict[str, Any]:
    """The layers' gradients under this file's names → the program's tree."""
    dense = model["num_dense_layers"]

    def stack(rows, key):
        return jnp.stack([g[key] for g in rows])

    A, D, S = layer_grads, layer_grads[:dense], layer_grads[dense:]
    layers: Dict[str, Any] = {"A": {
        **{k: {"scale": stack(A, k)} for k in _NORMS},
        "attn": {**{k: {"scale": stack(A, k)} for k in ("q_norm", "k_norm")},
                 **{k: stack(A, k) for k in _ATTN}}}}
    if D:
        layers["D"] = {"mlp": {k: stack(D, k) for k in _MLP}}
    if S:
        layers["S"] = {"moe": {k: stack(S, k) for k in _MOE}}
    return {"embed": {"tokens": embed}, "layers": layers,
            "final_norm": {"scale": final_norm}, "lm_head": {"w": head}}


# ---------------------------------------------------------------------------
# loss and gradients
# ---------------------------------------------------------------------------


class _Stages:
    """The jitted stages and their ``jax.vjp``s for one (model, faults)."""

    def __init__(self, model: Mapping[str, Any], faults: FrozenSet[str]):
        kw = dict(model=model, faults=faults)
        group = (model["num_attention_heads"]
                 // model["num_key_value_heads"])
        head_ = partial(head_loss, **kw)
        self.project, self.project_vjp = {}, {}
        self.attend, self.attend_vjp = {}, {}
        for kind in set(kinds_of(model)):
            proj = partial(project, kind=kind, **kw)
            one_head = partial(attend_head, faults=faults,
                               window=window_of(model, kind, faults))

            def attend(q, k, v, one_head=one_head):  # a query head at a time
                return jax.lax.map(
                    lambda a: one_head(a[0], k[a[1] // group],
                                       v[a[1] // group]),
                    (q, jnp.arange(q.shape[0])))

            def attend_vjp(q, k, v, do, one_head=one_head):
                dq, dk, dv = jax.lax.map(
                    lambda a: jax.vjp(one_head, a[0], k[a[1] // group],
                                      v[a[1] // group])[1](a[2]),
                    (q, jnp.arange(q.shape[0]), do))
                fold = lambda t: t.reshape((-1, group) + t.shape[1:]).sum(1)
                return dq, fold(dk), fold(dv)

            self.project[kind] = jax.jit(proj)
            self.project_vjp[kind] = jax.jit(
                lambda x, w, cos, sin, cot, proj=proj: jax.vjp(
                    lambda x_, w_: proj(x_, w_, cos, sin), x, w)[1](cot))
            self.attend[kind] = jax.jit(attend)
            self.attend_vjp[kind] = jax.jit(attend_vjp)
        self.finish, self.finish_vjp = {}, {}
        for sparse in (False, True):
            fin = partial(finish, sparse=sparse, **kw)
            self.finish[sparse] = jax.jit(fin)
            self.finish_vjp[sparse] = jax.jit(
                lambda x, o, g, w, cot, fin=fin: jax.vjp(
                    lambda *a: fin(*a)[0], x, o, g, w)[1](cot))
        self.head = jax.jit(head_)
        self.head_vjp = jax.jit(
            lambda x, w, labels, cot: jax.vjp(
                lambda x_, w_: head_(x_, w_, labels), x, w)[1](cot))


def loss_and_grads(params: Mapping[str, Any], model: Mapping[str, Any],
                   input_ids: np.ndarray, faults: FrozenSet[str] = NONE,
                   grads: bool = True) -> Dict[str, Any]:
    """→ ``loss`` (the mean cross-entropy), ``grads`` (the program's tree,
    float32; None without ``grads``; ``router_bias``'s are zero unless
    ``bias_differentiated``), ``counts`` (int ``(routed layers,
    num_experts)``: the batch's assignments to every expert), ``router`` (for
    the FIRST sequence, routed layer by routed layer: the router's input
    rounded to bfloat16 as the program would see it, the float32 scores and
    the choices).  ``model``: the published keys as run, with
    ``experts_held`` and ``first_expert``."""
    faults = frozenset(faults)
    unknown = faults - set(FAULTS)
    if unknown:
        raise ValueError(f"unknown faults {sorted(unknown)}")
    ids = np.asarray(input_ids)
    B, s = ids.shape
    L = model["num_hidden_layers"]
    kinds = kinds_of(model)
    count = B * (s - 1)
    scale = 1.0 if not model["mup_enabled"] or "embed_unscaled" in faults \
        else float(model["hidden_size"]) ** 0.5
    with jax.default_matmul_precision("highest"):
        st = _Stages(model, faults)
        cos, sin = rope_angles(model, s)
        embed = params["embed"]["tokens"].astype(F32)
        head_w = {"final_norm": params["final_norm"]["scale"].astype(F32),
                  "head": params["lm_head"]["w"].astype(F32)}
        ce = 0.0
        counts = np.zeros((L - model["num_dense_layers"],
                           model["num_experts"]), np.int64)
        taps: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        g_layers = None
        g_embed = jnp.zeros_like(embed) if grads else None
        g_head = jax.tree.map(jnp.zeros_like, head_w) if grads else None
        for b in range(B):
            tok = jnp.asarray(ids[b])
            xs = [embed[tok] * scale]
            routed = 0
            for i in range(L):  # the forward sweep keeps each layer's input
                w, sparse = layer_weights(params, model, i)
                q, k, v, g = st.project[kinds[i]](xs[-1], w, cos, sin)
                o = st.attend[kinds[i]](q, k, v)
                x, tap = st.finish[sparse](xs[-1], o, g, w)
                if tap is not None:
                    counts[routed] += np.asarray(tap[3])
                    routed += 1
                    if b == 0:
                        taps.append((
                            np.asarray(tap[0].astype(BF16).astype(F32)),
                            np.asarray(tap[1]), np.asarray(tap[2])))
                xs.append(x)
            ce += float(st.head(xs[-1], head_w, tok[1:])) / count
            if not grads:
                continue
            gx, gh = st.head_vjp(xs[-1], head_w, tok[1:],
                                 jnp.asarray(1.0 / count, F32))
            g_head = jax.tree.map(jnp.add, g_head, gh)
            rows: List[Dict[str, jax.Array]] = []
            for i in reversed(range(L)):
                w, sparse = layer_weights(params, model, i)
                q, k, v, g = st.project[kinds[i]](xs[i], w, cos, sin)
                o = st.attend[kinds[i]](q, k, v)
                gx1, go, gg, gw = st.finish_vjp[sparse](xs[i], o, g, w, gx)
                gx2, gw2 = st.project_vjp[kinds[i]](
                    xs[i], w, cos, sin,
                    (*st.attend_vjp[kinds[i]](q, k, v, go), gg))
                gx = gx1 + gx2
                rows.append(jax.tree.map(jnp.add, gw, gw2))
                xs.pop()
            rows.reverse()
            g_layers = rows if g_layers is None else [
                jax.tree.map(jnp.add, a_, b_) for a_, b_ in zip(g_layers, rows)]
            g_embed = g_embed.at[tok].add(gx * scale)
    out = {"loss": ce, "counts": counts, "router": taps, "grads": None}
    if grads:
        out["grads"] = tree_of(g_layers, g_embed, g_head["final_norm"],
                               g_head["head"], model)
    return out


# ---------------------------------------------------------------------------
# the rule, and one step
# ---------------------------------------------------------------------------


def bias_after_rule(bias: np.ndarray, counts: np.ndarray, coeff: float,
                    faults: FrozenSet[str] = NONE) -> np.ndarray:
    """``b + d - mean(d)``, ``d = coeff x sign(mean(c) - c)``, a routed layer
    a row, in float32."""
    bias = np.asarray(bias, np.float32)
    if "bias_left" in faults:
        return bias
    c = np.asarray(counts, np.float32)
    d = np.float32(coeff) * np.sign(c.mean(-1, keepdims=True) - c)
    if "rule_uncentred" not in faults:
        d = d - d.mean(-1, keepdims=True)
    return bias + d.astype(np.float32)


def bias_after_step(bias: np.ndarray, bias_grads: np.ndarray,
                    counts: np.ndarray, model: Mapping[str, Any],
                    faults: FrozenSet[str] = NONE, **optimizer) -> np.ndarray:
    """The routers' biases after the first step: the rule on ``counts``,
    and before it AdamW's step on ``bias_grads`` under
    ``bias_differentiated`` alone."""
    bias = jnp.asarray(bias, F32)
    if "bias_differentiated" in faults:
        bias = adamw_step(bias, jnp.asarray(bias_grads, F32), **optimizer)
    return bias_after_rule(np.asarray(bias), counts,
                           model["load_balance_coeff"], frozenset(faults))


def first_step(params: Mapping[str, Any], grads: Mapping[str, Any],
               counts: np.ndarray, model: Mapping[str, Any],
               faults: FrozenSet[str] = NONE, **optimizer) -> Dict[str, Any]:
    """The parameters after the first step: AdamW on every leaf but the
    routers' biases, which :func:`bias_after_step` moves."""
    after = adamw_step(params, grads, **optimizer)
    moe = after["layers"]["S"]["moe"]
    bias = bias_after_step(
        params["layers"]["S"]["moe"]["router_bias"],
        grads["layers"]["S"]["moe"]["router_bias"], counts, model, faults,
        **optimizer)
    return {**after, "layers": {**after["layers"], "S": {"moe": {
        **moe, "router_bias": jnp.asarray(bias)}}}}
