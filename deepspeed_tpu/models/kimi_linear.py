"""A decoder whose layers are a MIXER and an FFN each, the mixer of two kinds
(``model_type: kimi_linear``, Kimi-Linear-48B-A3B): Kimi Delta Attention
(``"K"``: a matrix state a head, updated by a delta rule under a gate a
CHANNEL) or latent attention (``"A"``: MLA without query compression, without
an indexer and without positions), by ``cfg.kda_pattern``; the FFN dense or
routed by ``cfg.mlp_layer_types`` (``models/latent_sparse.py``'s, with the
share of the experts this chip holds).

A KDA layer, on ``h = RMSNorm(x)`` at position ``t``, a head (``d_k = d_v =
kda_head_dim``):

    [q~ | k~ | v~] = silu(causal depthwise conv(h W_qkv))        ``kda_conv``
    q = L2norm(q~) / sqrt(d_k);  k = L2norm(k~);  v = v~
    log a = -exp(A_log) softplus(h W_f_down W_f_up + dt_bias)    (d_k values)
    b = sigmoid(h w_beta)                                        (a scalar)
    S_t = (I - b k k^T) Diag(a) S_{t-1} + b k v^T                float32
    o = S_t^T q               ``kda_decode_update`` / ``kda_chunk_scan``
    y = (RMSNorm(o) * sigmoid(h W_g_down W_g_up)) W_o          ``kda_gate_out``

The decay comes BEFORE the delta correction and is a vector: those two make
it KDA and no gated DeltaNet.  A latent layer is ``latent_sparse.py``'s at
``q_lora_rank`` 0 and ``index_topk`` 0 with NO rotation (``mla_use_nope``):
the ``qk_rope_head_dim`` columns of the query and of the shared key are kept
and not turned; the cache entry is ``[RMSNorm(c_kv) | k_r]``; every query
attends over every earlier key.

The parameters are stacked BY KIND: ``params["layers"]["K"]`` the KDA layers'
first norm and mixer, ``["A"]`` the latent layers', ``["D"]`` / ``["S"]`` the
dense and the routed layers' second norm and FFN.  The model is SERVED
(``inference/v2/programs.linear_latent_layers`` calls the pieces below); it is
not trained: the chunked scan has no backward (ROADMAP R4).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import transformer as tfm

KINDS = ("K", "A", "D", "S")

#: what the trainer and every forward that is no served step program say
NOT_TRAINED = (
    "a model with KDA layers (kda_pattern: a delta-rule matrix state a head "
    "beside latent attention) is served by the v2 engine (inference/v2) "
    "only: the chunked delta-rule scan has no backward and no whole-sequence "
    "forward is written (ROADMAP R4)")


def check_config(cfg) -> None:
    """What the step programs compute, or a refusal by name."""
    n = cfg.num_layers
    if len(cfg.kda_pattern) != n or set(cfg.kda_pattern) - {"K", "A"}:
        raise ValueError(
            f"kda_pattern names {len(cfg.kda_pattern)} layers of kinds "
            f"{sorted(set(cfg.kda_pattern))}; num_layers is {n} and the "
            f"kinds are 'K' (KDA) and 'A' (latent attention)")
    if not ({"K", "A"} <= set(cfg.kda_pattern)):
        raise ValueError("a kda_pattern model is served with at least one "
                         "KDA and one latent attention layer")
    got = cfg.mlp_layer_types
    if len(got) != n or set(got) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types names {len(got)} layers of kinds "
                         f"{sorted(set(got))}; num_layers is {n}")
    if not (cfg.kda_num_heads and cfg.kda_head_dim and cfg.kda_gate_rank
            and cfg.kda_conv_kernel > 1 and cfg.kda_chunk_size > 0):
        raise ValueError("a KDA layer needs kda_num_heads, kda_head_dim, "
                         "kda_gate_rank, kda_conv_kernel and kda_chunk_size")
    if not cfg.kv_lora_rank or cfg.q_lora_rank or cfg.index_topk \
            or cfg.indexer_types:
        raise ValueError("the latent layers beside KDA have a latent "
                         "(kv_lora_rank > 0), no query compression and no "
                         "indexer")
    for name in ("mixer_pattern", "layer_types", "sliding_window",
                 "eva_window", "qk_norm", "parallel_residual"):
        if getattr(cfg, name):
            raise ValueError(f"kda_pattern with {name} is not something the "
                             f"program computes")
    if cfg.position != "none":
        raise ValueError("a kda_pattern model's latent layers take no "
                         "position (the KDA layers carry the order): "
                         "position must be 'none'")
    if "sparse" in got and not (
            cfg.num_experts and 0 <= cfg.moe_first_expert
            and cfg.moe_first_expert + cfg.experts_held <= cfg.num_experts):
        raise ValueError(
            f"experts {cfg.moe_first_expert} to {cfg.moe_first_expert} + "
            f"{cfg.experts_held} are not among {cfg.num_experts}")


def layers_of(cfg, kind: str) -> int:
    """Layers in stack ``kind`` ("K", "A", "D", "S")."""
    if kind in "KA":
        return sum(k == kind for k in cfg.kda_pattern)
    return sum(t == ("dense" if kind == "D" else "sparse")
               for t in cfg.mlp_layer_types)


def pattern(cfg) -> Tuple[str, ...]:
    """One letter a layer for ``ssm_hybrid.segments``: the mixer's, upper
    case over a routed FFN and lower case over a dense one."""
    return tuple(k if t == "sparse" else k.lower()
                 for k, t in zip(cfg.kda_pattern, cfg.mlp_layer_types))


def stacks_of(letter: str) -> Dict[str, bool]:
    """The two stacks a layer of ``pattern``'s letter draws from."""
    return {letter.upper(): True, "S" if letter.isupper() else "D": True}


def kda_width(cfg) -> int:
    """Channels of one of q, k and v over all heads."""
    return cfg.kda_num_heads * cfg.kda_head_dim


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg) -> Dict[str, Any]:
    pd = jnp.dtype(cfg.param_dtype)
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    nh, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    rkv, E, held = cfg.kv_lora_rank, cfg.num_experts, cfg.experts_held
    H, dk, r, kc = (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank,
                    cfg.kda_conv_kernel)
    w = H * dk
    Lk, La, Ld, Ls = (layers_of(cfg, k) for k in KINDS)
    keys = iter(jax.random.split(rng, 40))
    dense = tfm._dense_init

    def ones(*shape):
        return {"scale": jnp.ones(shape, pd)}

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    # THE GATE reaches back: log a = -A softplus(f + dt_bias) with f about
    # N(0, 1) under these projections; dt_bias the inverse softplus of a step
    # drawn log-uniformly in [1e-3, 4e-3] and A uniform in [0.5, 1.5), so the
    # median channel's log a is about -0.002 e^f a token, -0.85 over 256
    # tokens: it keeps more than 1/e of a write after 256 tokens, and a
    # program that loses the state between steps reads wrong.  Both stay
    # float32 whatever param_dtype is
    step = jnp.exp(uniform((Lk, w), math.log(1e-3), math.log(4e-3)))
    layers: Dict[str, Any] = {
        "K": {"ln1": ones(Lk, h), "kda": {
            "w_qkv": dense(next(keys), (Lk, h, 3 * w), h, pd),
            "conv_w": dense(next(keys), (Lk, kc, 3 * w), kc, pd),
            "w_f_down": dense(next(keys), (Lk, h, r), h, pd),
            "w_f_up": dense(next(keys), (Lk, r, w), r, pd),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(uniform((Lk, H), 0.5, 1.5)),
            "w_beta": dense(next(keys), (Lk, h, H), h, pd),
            "w_g_down": dense(next(keys), (Lk, h, r), h, pd),
            "w_g_up": dense(next(keys), (Lk, r, w), r, pd),
            "o_norm": ones(Lk, dk),
            "wo": dense(next(keys), (Lk, w, h), w, pd)}},
        "A": {"ln1": ones(La, h), "attn": {
            "w_q": dense(next(keys), (La, h, nh * (dn + dr)), h, pd),
            "w_kva": dense(next(keys), (La, h, rkv), h, pd),
            "w_kr": dense(next(keys), (La, h, dr), h, pd),
            "kv_a_norm": ones(La, rkv),
            "w_kvb": dense(next(keys), (La, rkv, nh, dn + dv), rkv, pd),
            "wo": dense(next(keys), (La, nh * dv, h), nh * dv, pd)}},
        "D": {"ln2": ones(Ld, h), "mlp": {
            "w_in": dense(next(keys), (Ld, h, f), h, pd),
            "w_gate": dense(next(keys), (Ld, h, f), h, pd),
            "w_out": dense(next(keys), (Ld, f, h), f, pd)}},
        "S": {"ln2": ones(Ls, h), "moe": {
            "router": dense(next(keys), (Ls, h, E), h, pd),
            # a checkpoint tensor; drawn small, so that it changes some
            # choices and a program that drops it is seen
            "router_bias": 0.02 * jax.random.normal(next(keys), (Ls, E)),
            # the experts THIS CHIP holds
            "w_in": dense(next(keys), (Ls, held, h, fe), h, pd),
            "w_gate": dense(next(keys), (Ls, held, h, fe), h, pd),
            "w_out": dense(next(keys), (Ls, held, fe, h), fe, pd),
            "sh_w_in": dense(next(keys), (Ls, h, fs), h, pd),
            "sh_w_gate": dense(next(keys), (Ls, h, fs), h, pd),
            "sh_w_out": dense(next(keys), (Ls, fs, h), fs, pd)}},
    }
    layers = {k: v for k, v in layers.items() if layers_of(cfg, k)}
    return {
        "embed": {"tokens": dense(next(keys), (cfg.vocab_size, h), h, pd)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), pd)},
        "lm_head": {"w": dense(next(keys), (h, cfg.vocab_size), h, pd)},
    }


def param_axes(cfg) -> Dict[str, Any]:
    """Logical axes of :func:`init_params`'s tree: the heads' channels are
    ``heads`` (tensor parallel), the held experts' axis ``expert``, latents,
    the low-rank gate maps' inner width and the router whole."""
    ln = {"scale": ("layers", "embed")}
    low = {"scale": ("layers", None)}
    layers: Dict[str, Any] = {
        "K": {"ln1": dict(ln), "kda": {
            "w_qkv": ("layers", "embed", "heads"),
            "conv_w": ("layers", None, "heads"),
            "w_f_down": ("layers", "embed", None),
            "w_f_up": ("layers", None, "heads"),
            "dt_bias": ("layers", "heads"), "A_log": ("layers", None),
            "w_beta": ("layers", "embed", None),
            "w_g_down": ("layers", "embed", None),
            "w_g_up": ("layers", None, "heads"),
            "o_norm": dict(low), "wo": ("layers", "heads", "embed")}},
        "A": {"ln1": dict(ln), "attn": {
            "w_q": ("layers", "embed", "heads"),
            "w_kva": ("layers", "embed", None),
            "w_kr": ("layers", "embed", None), "kv_a_norm": dict(low),
            "w_kvb": ("layers", None, "heads", None),
            "wo": ("layers", "heads", "embed")}},
        "D": {"ln2": dict(ln), "mlp": {
            "w_in": ("layers", "embed", "mlp"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_out": ("layers", "mlp", "embed")}},
        "S": {"ln2": dict(ln), "moe": {
            "router": ("layers", "embed", None),
            "router_bias": ("layers", None),
            "w_in": ("layers", "expert", "embed", "mlp"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_out": ("layers", "expert", "mlp", "embed"),
            "sh_w_in": ("layers", "embed", "mlp"),
            "sh_w_gate": ("layers", "embed", "mlp"),
            "sh_w_out": ("layers", "mlp", "embed")}},
    }
    layers = {k: v for k, v in layers.items() if layers_of(cfg, k)}
    return {"embed": {"tokens": ("vocab", "embed")}, "layers": layers,
            "final_norm": {"scale": ("embed",)},
            "lm_head": {"w": ("embed", "vocab")}}


def num_params(cfg, include_embed: bool = True) -> int:
    """Parameters of the model with the experts THIS configuration holds."""
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    nh, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    rkv, r = cfg.kv_lora_rank, cfg.kda_gate_rank
    H, dk, w = cfg.kda_num_heads, cfg.kda_head_dim, kda_width(cfg)
    per = {
        "K": h + h * 3 * w + cfg.kda_conv_kernel * 3 * w + 2 * (h * r + r * w)
        + w + H + h * H + dk + w * h,
        "A": h + h * nh * (dn + dr) + h * (rkv + dr) + rkv
        + rkv * nh * (dn + dv) + nh * dv * h,
        "D": h + 3 * h * f,
        "S": h + h * cfg.num_experts + cfg.num_experts
        + 3 * cfg.experts_held * h * fe + 3 * h * fs,
    }
    total = sum(per[k] * layers_of(cfg, k) for k in KINDS) + h
    if include_embed:
        total += 2 * cfg.vocab_size * h
    return total


# ---------------------------------------------------------------------------
# the KDA layer's pieces (the step programs call them)
# ---------------------------------------------------------------------------


def kda_in_proj(a, p):
    """``a (..., h)`` → the conv's input ``(..., 3 x H x d_k)``: q, k and v
    of every head side by side, one product."""
    with jax.named_scope("kda_in_proj"):
        return tfm._lin(a, p, "w_qkv", "b_qkv")


def kda_inputs(qkv, a, p, cfg):
    """The conv's output ``qkv (..., 3 x H x d_k)`` and the layer's normed
    input ``a (..., h)`` → what the recurrence reads, float32: ``q (..., H,
    d_k)`` unit length over ``sqrt(d_k)``, ``k`` unit length, ``v``, ``log_a
    (..., H, d_k)`` negative, ``b (..., H)`` in (0, 1)."""
    H, dk = cfg.kda_num_heads, cfg.kda_head_dim
    lead, f32 = qkv.shape[:-1], jnp.float32
    with jax.named_scope("kda_gate_in"):
        q, k, v = (x.reshape(lead + (H, dk)).astype(f32)
                   for x in jnp.split(qkv, 3, axis=-1))

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)

        low = jnp.dot(a, p["w_f_down"].astype(a.dtype),
                      preferred_element_type=f32).astype(a.dtype)
        f = jnp.dot(low, p["w_f_up"].astype(a.dtype),
                    preferred_element_type=f32)
        log_a = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
            f + p["dt_bias"].astype(f32)).reshape(lead + (H, dk))
        b = jax.nn.sigmoid(jnp.dot(a, p["w_beta"].astype(a.dtype),
                                   preferred_element_type=f32))
        return unit(q) * dk ** -0.5, unit(k), v, log_a, b


def kda_out(o, a, p, cfg):
    """``o (..., H, d_v)`` float32 from the recurrence and the layer's normed
    input ``a`` → the layer's output ``(..., h)``: RMSNorm inside each head,
    the sigmoid gate of ``a``'s low-rank map, the out projection."""
    lead = a.shape[:-1]
    f32 = jnp.float32
    with jax.named_scope("kda_gate_out"):
        low = jnp.dot(a, p["w_g_down"].astype(a.dtype),
                      preferred_element_type=f32).astype(a.dtype)
        gate = jax.nn.sigmoid(jnp.dot(low, p["w_g_up"].astype(a.dtype),
                                      preferred_element_type=f32))
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg.norm_eps)
        o = o * p["o_norm"]["scale"].astype(f32)
        y = (o.reshape(lead + (-1,)) * gate).astype(a.dtype)
    with jax.named_scope("kda_out_proj"):
        return tfm._lin(y, p, "wo", "bo")
