"""A decoder with LATENT attention (MLA), a learned indexer that picks the
keys each query attends over (DSA) and shares its pick with the layers behind
it, leading dense layers and routed experts of which this chip may hold a
share (``model_type: glm_moe_dsa``, GLM-5.2).  A layer, on ``h =
RMSNorm(x)`` at position ``t``:

    c_q = RMSNorm(h W_qa);  [q_nope | q_rope]_j = (c_q W_qb)_j     a head j
    [c_kv | k_rope] = h [W_kva | W_kr];  c_kv <- RMSNorm(c_kv)
    RoPE (pairs) on q_rope and on k_rope, which every head shares
    [k_nope | v]_j = (c_kv W_kvb)_j
    a_{t,s,j} = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)
    softmax over the keys s in S_t;  o_j = sum p v_j;  out = concat(o) W_o

    a layer ``indexer_types`` calls "full" picks S_t:
    qI_j = (c_q W_Iq)_j;  kI = LayerNorm(h W_Ik);  RoPE on both's first dims
    I_{t,s} = sum_j w_j relu(qI_j . kI_s),  w = h W_Iw / sqrt(J x D)
    S_t = the ``index_topk`` keys s <= t of largest I_{t,s}
    a "shared" layer attends over the S_t of the nearest "full" layer before

    FFN: SwiGLU (``mlp_layer_types`` "dense"), or sigmoid-routed experts and
    one shared expert ("sparse": ``moe/dropless.py``, which is told which
    experts this chip holds).

What is cached a token a layer is ``[c_kv | k_rope]`` (after the norm, after
RoPE) and, on a "full" layer, ``kI``.  Serving never expands ``k_nope`` or
``v``: ``W_kvb`` is ABSORBED into the query (``q_nope W_kvb^K`` meets ``c_kv``)
and into the output (``sum p c_kv`` goes through ``W_kvb^V``), the same
mathematics (:func:`absorb`, :func:`unabsorb`;
``ops/pallas/latent_attention.py`` attends).  :func:`forward_hidden`, the
whole-sequence forward, is the EXPANDED form; ``tests/test_glm52.py`` holds
the two to each other.

The parameters are stacked BY KIND (as ``ssm_hybrid.py``'s):
``params["layers"]["A"]`` every layer's norms and attention, ``["I"]`` the
indexers of the "full" layers, ``["D"]`` the dense FFNs, ``["S"]`` the
routed ones; :func:`layer_plan` says which index of which stack a layer reads.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import transformer as tfm

KINDS = ("A", "I", "D", "S")


def check_config(cfg) -> None:
    n = cfg.num_layers
    for name, kinds in (("indexer_types", {"full", "shared"}),
                        ("mlp_layer_types", {"dense", "sparse"})):
        got = getattr(cfg, name)
        if len(got) != n or set(got) - kinds:
            raise ValueError(f"{name} names {len(got)} layers of kinds "
                             f"{sorted(set(got))}; num_layers is {n} and the "
                             f"kinds are {sorted(kinds)}")
    if cfg.indexer_types[0] != "full":
        raise ValueError("the first layer has no layer before it to share a "
                         "selection with: indexer_types[0] must be 'full'")
    if cfg.mixer_pattern or cfg.layer_types or cfg.sliding_window \
            or cfg.position != "rope":
        raise ValueError("latent attention with window layers, a mixer "
                         "pattern or another position than rope is not "
                         "something the program computes")
    if cfg.head_dim != cfg.qk_nope_head_dim:
        raise ValueError(f"head_dim {cfg.head_dim} is the no-position part "
                         f"of a head's query: qk_nope_head_dim is "
                         f"{cfg.qk_nope_head_dim}")
    if "sparse" in cfg.mlp_layer_types and not (
            cfg.num_experts and 0 <= cfg.moe_first_expert
            and cfg.moe_first_expert + cfg.experts_held <= cfg.num_experts):
        raise ValueError(
            f"experts {cfg.moe_first_expert} to {cfg.moe_first_expert} + "
            f"{cfg.experts_held} are not among {cfg.num_experts}")


@dataclasses.dataclass(frozen=True)
class Layer:
    """What is static about one layer: whether it picks its own keys
    (``full``), the index in stack "I" of the indexer whose pick it attends
    over, its FFN's stack ("D" or "S") and its index there."""
    full: bool
    index: int
    ffn: str
    ffn_index: int


def layer_plan(cfg) -> Tuple[Layer, ...]:
    plan, fulls, seen = [], 0, {"D": 0, "S": 0}
    for it, mt in zip(cfg.indexer_types, cfg.mlp_layer_types):
        fulls += it == "full"
        ffn = "D" if mt == "dense" else "S"
        plan.append(Layer(it == "full", fulls - 1, ffn, seen[ffn]))
        seen[ffn] += 1
    return tuple(plan)


def layers_of(cfg, kind: str) -> int:
    """Layers in stack ``kind`` ("A", "I", "D", "S")."""
    if kind == "A":
        return cfg.num_layers
    if kind == "I":
        return sum(t == "full" for t in cfg.indexer_types)
    return sum(t == ("dense" if kind == "D" else "sparse")
               for t in cfg.mlp_layer_types)


def pattern(cfg) -> Tuple[str, ...]:
    """One letter a layer for ``ssm_hybrid.segments``: upper case picks its
    own keys, ``d`` / ``s`` the FFN."""
    return tuple(("D" if l.ffn == "D" else "S") if l.full else l.ffn.lower()
                 for l in layer_plan(cfg))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg) -> Dict[str, Any]:
    pd = jnp.dtype(cfg.param_dtype)
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    nh, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    J, D = cfg.index_n_heads, cfg.index_head_dim
    E, held = cfg.num_experts, cfg.experts_held
    L, Lf, Ld, Ls = (layers_of(cfg, k) for k in KINDS)
    keys = iter(jax.random.split(rng, 32))
    dense = tfm._dense_init

    def ones(*shape):
        return {"scale": jnp.ones(shape, pd)}

    layers: Dict[str, Any] = {
        "A": {"ln1": ones(L, h), "ln2": ones(L, h), "attn": {
            "w_qa": dense(next(keys), (L, h, rq), h, pd),
            "q_a_norm": ones(L, rq),
            "w_qb": dense(next(keys), (L, rq, nh * (dn + dr)), rq, pd),
            "w_kva": dense(next(keys), (L, h, rkv), h, pd),
            "w_kr": dense(next(keys), (L, h, dr), h, pd),
            "kv_a_norm": ones(L, rkv),
            "w_kvb": dense(next(keys), (L, rkv, nh, dn + dv), rkv, pd),
            "wo": dense(next(keys), (L, nh * dv, h), nh * dv, pd)}},
        "I": {"index": {
            "w_iq": dense(next(keys), (Lf, rq, J * D), rq, pd),
            "w_ik": dense(next(keys), (Lf, h, D), h, pd),
            "ik_norm": {"scale": jnp.ones((Lf, D), pd),
                        "bias": jnp.zeros((Lf, D), pd)},
            "w_iw": dense(next(keys), (Lf, h, J), h, pd)}},
        "D": {"mlp": {
            "w_in": dense(next(keys), (Ld, h, f), h, pd),
            "w_gate": dense(next(keys), (Ld, h, f), h, pd),
            "w_out": dense(next(keys), (Ld, f, h), f, pd)}},
        "S": {"moe": {
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
    return {
        "embed": {"tokens": dense(next(keys), (cfg.vocab_size, h), h, pd)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), pd)},
        "lm_head": {"w": dense(next(keys), (h, cfg.vocab_size), h, pd)},
    }


def num_params(cfg, include_embed: bool = True) -> int:
    """Parameters of the model with the experts THIS configuration holds
    (all of them for the published one)."""
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    nh, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                      cfg.qk_rope_head_dim, cfg.v_head_dim)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    J, D = cfg.index_n_heads, cfg.index_head_dim
    per = {
        "A": 2 * h + h * rq + rq + rq * nh * (dn + dr) + h * (rkv + dr) + rkv
        + rkv * nh * (dn + dv) + nh * dv * h,
        "I": rq * J * D + h * D + 2 * D + h * J,
        "D": 3 * h * f,
        "S": h * cfg.num_experts + cfg.num_experts
        + 3 * cfg.experts_held * h * fe + 3 * h * fs,
    }
    total = sum(per[k] * layers_of(cfg, k) for k in KINDS) + h
    if include_embed:
        total += 2 * cfg.vocab_size * h
    return total


# ---------------------------------------------------------------------------
# a layer's pieces (the step programs call them too)
# ---------------------------------------------------------------------------


def rope_tables(cfg, max_len: int):
    """cos and sin ``(max_len, qk_rope_head_dim / 2)``, for the attention's
    rotated part and the indexer's alike (``rope_type: default``: no
    scaling)."""
    return tfm.rope_table(max_len, cfg.qk_rope_head_dim, cfg.rope_theta)


def _rope_first(x, rope, positions, dims: int):
    """RoPE in pairs on the first ``dims`` of ``x (..., heads, D)``."""
    return tfm.rope_at(x, rope[0][:, :dims // 2], rope[1][:, :dims // 2],
                       positions)


def queries(a, p, cfg, rope, positions):
    """``a (..., h)`` → ``(c_q (..., q_lora_rank)`` after its norm, ``q_nope
    (..., H, nope)``, ``q_rope (..., H, rope)`` rotated)."""
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("latent_q_proj"):
        c_q = tfm._norm(tfm._lin(a, p, "w_qa", "b_qa"), p["q_a_norm"],
                        "rmsnorm", cfg.norm_eps)
        q = tfm._lin(c_q, p, "w_qb", "b_qb").reshape(
            a.shape[:-1] + (nh, dn + dr))
        return c_q, q[..., :dn], _rope_first(q[..., dn:], rope, positions, dr)


def cache_entry(a, p, cfg, rope, positions, width: Optional[int] = None):
    """``a (..., h)`` → what the latent pool holds of each token: ``[c_kv
    after its norm | k_rope after RoPE]``, zero-padded to ``width``."""
    with jax.named_scope("latent_kv_proj"):
        c_kv = tfm._norm(tfm._lin(a, p, "w_kva", "b_kva"), p["kv_a_norm"],
                         "rmsnorm", cfg.norm_eps)
        k_r = _rope_first(tfm._lin(a, p, "w_kr", "b_kr")[..., None, :], rope,
                          positions, cfg.qk_rope_head_dim)[..., 0, :]
        entry = jnp.concatenate([c_kv, k_r], axis=-1)
        pad = (width or entry.shape[-1]) - entry.shape[-1]
        return jnp.pad(entry, [(0, 0)] * (entry.ndim - 1) + [(0, pad)])


def absorb(q_nope, q_rope, w_kvb, width: int):
    """The absorbed query ``(..., H, width)``: ``[q_nope W_kvb^K | q_rope |
    0]``, which meets a pool row ``[c_kv | k_rope | 0]`` in one dot product
    (``w_kvb (kv_lora_rank, H, nope + v)``; bfloat16 operands, float32 sums,
    rounded to the activation type as every projection's output is)."""
    dn = q_nope.shape[-1]
    with jax.named_scope("latent_absorb_q"):
        q_abs = jnp.einsum("...hn,chn->...hc", q_nope,
                           w_kvb[..., :dn].astype(q_nope.dtype),
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
        q = jnp.concatenate([q_abs, q_rope], axis=-1)
        pad = width - q.shape[-1]
        return jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])


def unabsorb(o_lat, w_kvb, dn: int, dtype):
    """``o_lat (..., H, kv_lora_rank)`` float32, the attention's weighted sum
    of latents → ``(..., H x v)`` through ``W_kvb^V``."""
    with jax.named_scope("latent_absorb_o"):
        o = jnp.einsum("...hc,chv->...hv", o_lat.astype(dtype),
                       w_kvb[..., dn:].astype(dtype),
                       preferred_element_type=jnp.float32).astype(dtype)
        return o.reshape(o.shape[:-2] + (-1,))


def index_queries(c_q, a, p, cfg, rope, positions):
    """→ ``(qI (..., J, D)`` rotated on its first dims, ``w (..., J)``
    float32, the heads' weights with the ``1 / sqrt(J x D)`` folded in)."""
    J, D = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("dsa_index_proj"):
        q = tfm._lin(c_q, p, "w_iq", "b_iq").reshape(c_q.shape[:-1] + (J, D))
        w = jnp.dot(a, p["w_iw"].astype(a.dtype),
                    preferred_element_type=jnp.float32) * (J * D) ** -0.5
        return _rope_first(q, rope, positions, cfg.qk_rope_head_dim), w


def index_key(a, p, cfg, rope, positions):
    """``a (..., h)`` → what the indexer's pool holds of each token: ``kI
    (..., D)`` after its LayerNorm (with bias) and RoPE."""
    with jax.named_scope("dsa_index_proj"):
        k = tfm._norm(tfm._lin(a, p, "w_ik", "b_ik"), p["ik_norm"],
                      "layernorm", 1e-6)
        return _rope_first(k[..., None, :], rope, positions,
                           cfg.qk_rope_head_dim)[..., 0, :]


def softmax_scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


# ---------------------------------------------------------------------------
# the whole-sequence forward (training forward, v1 engine, tests): EXPANDED
# ---------------------------------------------------------------------------


def forward_hidden(params: Dict[str, Any], tokens: jax.Array, cfg,
                   attn_fn=None) -> jax.Array:
    """tokens (B, S) → hidden states (B, S, h) after the final norm.  The
    expanded form: ``k_nope`` and ``v`` of every key are made from its latent
    and every query attends under an ``(S, S)`` mask of its selection; the
    layers unrolled."""
    from ..moe.dropless import serving_moe_block
    from ..ops.pallas.latent_attention import index_scores, topk_mask

    del attn_fn  # the selection is a mask no attention kernel here takes
    Bn, S = tokens.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    k_sel = min(cfg.index_topk, S)
    x = tfm.embed_tokens(params, tokens, cfg)
    rope = rope_tables(cfg, S)
    pos = jnp.broadcast_to(jnp.arange(S), (Bn, S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    lay = params["layers"]
    mask = None
    for i, l in enumerate(layer_plan(cfg)):
        lp = jax.tree.map(lambda a: a[i], lay["A"])
        p = lp["attn"]
        a = tfm._norm(x, lp["ln1"], "rmsnorm", cfg.norm_eps)
        c_q, q_nope, q_rope = queries(a, p, cfg, rope, pos)
        entry = cache_entry(a, p, cfg, rope, pos)
        c_kv, k_rope = entry[..., :cfg.kv_lora_rank], entry[
            ..., cfg.kv_lora_rank:]
        if l.full:
            ip = jax.tree.map(lambda a: a[l.index], lay["I"])["index"]
            qi, w = index_queries(c_q, a, ip, cfg, rope, pos)
            ki = index_key(a, ip, cfg, rope, pos)
            sc = jax.vmap(index_scores)(qi, w, ki)  # (B, S, S)
            mask = jax.vmap(lambda s: topk_mask(
                jnp.where(causal, s, -jnp.inf), k_sel))(sc)
        kv = jnp.einsum("bsc,chn->bshn", c_kv, p["w_kvb"].astype(c_kv.dtype),
                        preferred_element_type=jnp.float32)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope.astype(jnp.float32),
                        kv[..., :dn])
             + jnp.einsum("bthr,bsr->bhts", q_rope.astype(jnp.float32),
                          k_rope.astype(jnp.float32))) * softmax_scale(cfg)
        s = jnp.where(mask[:, None], s, -1e30)
        o = jnp.einsum("bhts,bshv->bthv", jax.nn.softmax(s, axis=-1),
                       kv[..., dn:]).astype(x.dtype)
        x = x + tfm._lin(o.reshape(Bn, S, -1), p, "wo", "bo")
        m = tfm._norm(x, lp["ln2"], "rmsnorm", cfg.norm_eps)
        fp = jax.tree.map(lambda a: a[l.ffn_index], lay[l.ffn])
        if l.ffn == "D":
            x = x + tfm._mlp_block(m, fp["mlp"], cfg)
        else:
            x = x + serving_moe_block(m, fp["moe"], cfg)[0]
    return tfm._norm(x, params["final_norm"], "rmsnorm", cfg.norm_eps)
