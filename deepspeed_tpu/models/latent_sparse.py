"""A decoder with LATENT attention (MLA), leading dense layers and routed
experts of which this chip may hold a share; with a learned indexer that picks
the keys each query attends over (DSA) and shares its pick with the layers
behind it (``model_type: glm_moe_dsa``, GLM-5.2: served), or without one and
without query compression (``model_type: deepseek_v2``, DeepSeek-V2-Lite:
``index_topk`` 0, ``q_lora_rank`` 0, ``[q_nope | q_rope]_j = (h W_q)_j``;
TRAINED, :func:`forward_train`).  A layer, on ``h = RMSNorm(x)`` at position
``t``:

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
the two to each other.  Without an indexer the expanded form is dense causal
attention at a query-key width (nope + rope) that is not the value width:
:func:`forward_train` hands it to ``attn_fn`` (the flash kernel, which takes
a value width of its own), scans the layers by stack under the config's
remat policy, and returns the routed layers' balance loss and counters beside
the hidden states.

The parameters are stacked BY KIND (as ``ssm_hybrid.py``'s):
``params["layers"]["A"]`` every layer's norms and attention, ``["I"]`` the
indexers of the "full" layers, ``["D"]`` the dense FFNs, ``["S"]`` the
routed ones; :func:`layer_plan` says which index of which stack a layer reads.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import transformer as tfm

KINDS = ("A", "I", "D", "S")


def check_config(cfg) -> None:
    n = cfg.num_layers
    checked = [("mlp_layer_types", {"dense", "sparse"})]
    if cfg.index_topk:
        checked.append(("indexer_types", {"full", "shared"}))
        if not cfg.q_lora_rank:
            raise ValueError("the indexer's queries are made from the "
                             "compressed query: index_topk needs q_lora_rank")
    elif cfg.indexer_types:
        raise ValueError("indexer_types without index_topk: a model has an "
                         "indexer or it has none")
    for name, kinds in checked:
        got = getattr(cfg, name)
        if len(got) != n or set(got) - kinds:
            raise ValueError(f"{name} names {len(got)} layers of kinds "
                             f"{sorted(set(got))}; num_layers is {n} and the "
                             f"kinds are {sorted(kinds)}")
    if cfg.index_topk and cfg.indexer_types[0] != "full":
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
    # no indexer: no layer picks, every layer attends over all it sees
    picks = cfg.indexer_types or ("none",) * cfg.num_layers
    for it, mt in zip(picks, cfg.mlp_layer_types):
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

    if rq:  # queries through a bottleneck
        q_proj = {"w_qa": dense(next(keys), (L, h, rq), h, pd),
                  "q_a_norm": ones(L, rq),
                  "w_qb": dense(next(keys), (L, rq, nh * (dn + dr)), rq, pd)}
    else:
        q_proj = {"w_q": dense(next(keys), (L, h, nh * (dn + dr)), h, pd)}
    layers: Dict[str, Any] = {
        "A": {"ln1": ones(L, h), "ln2": ones(L, h), "attn": {
            **q_proj,
            "w_kva": dense(next(keys), (L, h, rkv), h, pd),
            "w_kr": dense(next(keys), (L, h, dr), h, pd),
            "kv_a_norm": ones(L, rkv),
            "w_kvb": dense(next(keys), (L, rkv, nh, dn + dv), rkv, pd),
            "wo": dense(next(keys), (L, nh * dv, h), nh * dv, pd)}},
        "I": {} if not cfg.index_topk else {"index": {
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
            **({"router_bias": 0.02 * jax.random.normal(next(keys), (Ls, E))}
               if cfg.moe_router == "sigmoid" else {}),
            # the experts THIS CHIP holds
            "w_in": dense(next(keys), (Ls, held, h, fe), h, pd),
            "w_gate": dense(next(keys), (Ls, held, h, fe), h, pd),
            "w_out": dense(next(keys), (Ls, held, fe, h), fe, pd),
            "sh_w_in": dense(next(keys), (Ls, h, fs), h, pd),
            "sh_w_gate": dense(next(keys), (Ls, h, fs), h, pd),
            "sh_w_out": dense(next(keys), (Ls, fs, h), fs, pd)}},
    }
    layers = {k: v for k, v in layers.items() if v}  # no empty stack
    return {
        "embed": {"tokens": dense(next(keys), (cfg.vocab_size, h), h, pd)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), pd)},
        "lm_head": {"w": dense(next(keys), (h, cfg.vocab_size), h, pd)},
    }


def param_axes(cfg) -> Dict[str, Any]:
    """Logical axes of :func:`init_params`'s tree.  The heads' axis is
    ``heads`` (tensor parallel), the held experts' ``expert`` (so that a mesh
    with an ``ep`` axis divides them), latents and the router whole."""
    ln = {"scale": ("layers", "embed")}
    low = {"scale": ("layers", None)}
    attn = {"w_kva": ("layers", "embed", None),
            "w_kr": ("layers", "embed", None), "kv_a_norm": dict(low),
            "w_kvb": ("layers", None, "heads", None),
            "wo": ("layers", "heads", "embed")}
    if cfg.q_lora_rank:
        attn.update(w_qa=("layers", "embed", None), q_a_norm=dict(low),
                    w_qb=("layers", None, "heads"))
    else:
        attn["w_q"] = ("layers", "embed", "heads")
    layers: Dict[str, Any] = {
        "A": {"ln1": dict(ln), "ln2": dict(ln), "attn": attn}}
    if cfg.index_topk:
        layers["I"] = {"index": {
            "w_iq": ("layers", None, None), "w_ik": ("layers", "embed", None),
            "ik_norm": {"scale": ("layers", None), "bias": ("layers", None)},
            "w_iw": ("layers", "embed", None)}}
    if layers_of(cfg, "D"):
        layers["D"] = {"mlp": {"w_in": ("layers", "embed", "mlp"),
                               "w_gate": ("layers", "embed", "mlp"),
                               "w_out": ("layers", "mlp", "embed")}}
    if layers_of(cfg, "S"):
        moe = {"router": ("layers", "embed", None),
               "w_in": ("layers", "expert", "embed", "mlp"),
               "w_gate": ("layers", "expert", "embed", "mlp"),
               "w_out": ("layers", "expert", "mlp", "embed"),
               "sh_w_in": ("layers", "embed", "mlp"),
               "sh_w_gate": ("layers", "embed", "mlp"),
               "sh_w_out": ("layers", "mlp", "embed")}
        if cfg.moe_router == "sigmoid":
            moe["router_bias"] = ("layers", None)
        layers["S"] = {"moe": moe}
    return {"embed": {"tokens": ("vocab", "embed")}, "layers": layers,
            "final_norm": {"scale": ("embed",)},
            "lm_head": {"w": ("embed", "vocab")}}


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
        "A": 2 * h + (h * rq + rq + rq * nh * (dn + dr) if rq
                      else h * nh * (dn + dr)) + h * (rkv + dr) + rkv
        + rkv * nh * (dn + dv) + nh * dv * h,
        "I": rq * J * D + h * D + 2 * D + h * J,
        "D": 3 * h * f,
        "S": h * cfg.num_experts
        + (cfg.num_experts if cfg.moe_router == "sigmoid" else 0)
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
    rotated part and the indexer's alike: plain RoPE at ``rope_theta``
    (``rope_type: default``), or what ``rope_params`` says of "full" layers
    (YaRN's blended frequencies, cos and sin times its
    ``attention_factor``)."""
    return tfm.rope_table_of(max_len, cfg.qk_rope_head_dim,
                             cfg.rope_of("full"))


def _rope_first(x, rope, positions, dims: int):
    """RoPE in pairs on the first ``dims`` of ``x (..., heads, D)``
    (``rope`` None: a model without positions, nothing is turned)."""
    if rope is None:
        return x
    return tfm.rope_at(x, rope[0][:, :dims // 2], rope[1][:, :dims // 2],
                       positions)


def queries(a, p, cfg, rope, positions):
    """``a (..., h)`` → ``(c_q (..., q_lora_rank)`` after its norm, ``q_nope
    (..., H, nope)``, ``q_rope (..., H, rope)`` rotated).  Without query
    compression (``q_lora_rank`` 0) ``c_q`` is None and the heads are ``a
    W_q``."""
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("latent_q_proj"):
        if cfg.q_lora_rank:
            c_q = tfm._norm(tfm._lin(a, p, "w_qa", "b_qa"), p["q_a_norm"],
                            "rmsnorm", cfg.norm_eps)
            q = tfm._lin(c_q, p, "w_qb", "b_qb")
        else:
            c_q, q = None, tfm._lin(a, p, "w_q", "b_q")
        q = q.reshape(a.shape[:-1] + (nh, dn + dr))
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
    """``1 / sqrt(nope + rope)``; under DeepSeek's YaRN times ``m(factor,
    mscale_all_dim) ** 2`` with ``m(s, a) = 0.1 a ln s + 1`` (DeepSeek-V2-
    Lite: 192 ** -0.5 x 1.2608 ** 2 = 0.1147)."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    rope = cfg.rope_of("full")
    if rope.factor and rope.mscale_all_dim:
        scale *= (0.1 * rope.mscale_all_dim * math.log(rope.factor) + 1) ** 2
    return scale


# ---------------------------------------------------------------------------
# the whole-sequence forward (training forward, v1 engine, tests): EXPANDED
# ---------------------------------------------------------------------------


def forward_hidden(params: Dict[str, Any], tokens: jax.Array, cfg,
                   attn_fn=None) -> jax.Array:
    """tokens (B, S) → hidden states (B, S, h) after the final norm.  The
    expanded form: ``k_nope`` and ``v`` of every key are made from its latent
    and every query attends under an ``(S, S)`` mask of its selection; the
    layers unrolled."""
    if not cfg.index_topk:  # dense causal attention: the trained forward
        return forward_train(params, tokens, cfg, attn_fn)[0]
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


# ---------------------------------------------------------------------------
# the trained forward: no indexer, dense causal attention through ``attn_fn``
# ---------------------------------------------------------------------------

#: the counters a routed layer's ``stats`` hold, as the step's metrics name
#: them (``moe/dropless._routed_ffn_share``: held experts that got a row, the
#: largest rows of one, the assignments that were local)
MOE_COUNTERS = ("moe_experts_hit", "moe_rows_max", "moe_local_rows")


def attend_expanded(a, p, cfg, rope, positions, attn_fn):
    """One layer's latent attention on ``a (B, S, h)`` in the EXPANDED form:
    every key's ``k_nope`` and ``v`` made from its latent, the one rotated
    key broadcast to the heads, ``attn_fn`` over a query-key width of nope +
    rope and a value width of ``v_head_dim`` (``v`` is never padded)."""
    B, S, _ = a.shape
    nh, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    rkv = cfg.kv_lora_rank
    with jax.named_scope("mla_qkv"):
        _, q_nope, q_rope = queries(a, p, cfg, rope, positions)
        entry = cache_entry(a, p, cfg, rope, positions)
        kv = jnp.einsum("bsc,chn->bshn", entry[..., :rkv],
                        p["w_kvb"].astype(a.dtype),
                        preferred_element_type=jnp.float32).astype(a.dtype)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            entry[..., None, rkv:], (B, S, nh, cfg.qk_rope_head_dim))],
            axis=-1)
        v = kv[..., dn:]
    with jax.named_scope("mla_attn"):
        o = attn_fn(q, k, v, softmax_scale(cfg))
    with jax.named_scope("mla_out"):
        return tfm._lin(o.reshape(B, S, nh * dv), p, "wo", "bo")


def _scaled_attention(cfg, attn_fn):
    """``(q, k, v, scale) -> o`` over ``attn_fn`` (None: the config's): the
    flash kernel takes the scale; the other implementations divide by
    ``sqrt(D)`` themselves, so the rest of it is folded into ``q``."""
    if attn_fn is None and cfg.attn_impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention

        return lambda q, k, v, scale: flash_attention(
            q, k, v, causal=True, sm_scale=scale)
    fn = attn_fn or tfm.resolve_attention(cfg.attn_impl)
    return lambda q, k, v, scale: fn(
        q * jnp.asarray(scale * q.shape[-1] ** 0.5, q.dtype), k, v,
        causal=True)


#: a DENSE FFN whose intermediate over the whole batch would take more than
#: this many bytes runs over a slice of the sequence at a time, each slice
#: rematerialised in the backward (``sequence/tiled_compute.tiled_map``)
_FFN_SLICE_BYTES = 128 << 20


def _ffn_seq_tile(batch: int, seq: int, cfg) -> int:
    """The positions a dense FFN takes at a time: the sequence halved until
    ``batch x positions x intermediate_size`` fits :data:`_FFN_SLICE_BYTES`
    (2 x 8,192 x 10,944 in bfloat16: 2,048 positions, 90 MB)."""
    row = batch * cfg.intermediate_size * jnp.dtype(cfg.dtype).itemsize
    tile = seq
    while tile % 2 == 0 and tile * row > _FFN_SLICE_BYTES:
        tile //= 2
    return tile


def forward_train(params: Dict[str, Any], tokens: jax.Array, cfg,
                  attn_fn=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, S) → (hidden states (B, S, h) after the final norm, extras).
    A model WITHOUT an indexer.  The layers are scanned stack by stack (each
    run of layers of one FFN kind is one ``lax.scan`` over its slice of
    stacks "A" and "D" / "S") under ``cfg.remat_policy``; ``extras`` holds,
    for a model with routed layers, ``moe_aux_loss`` (the layers' balance
    losses summed, before the coefficient) and the :data:`MOE_COUNTERS` as
    means over the routed layers, float32 scalars."""
    from jax.ad_checkpoint import checkpoint_name

    from ..moe.dropless import dropless_moe_block_with_losses
    from ..sequence.tiled_compute import tiled_map

    if cfg.index_topk:
        raise ValueError("forward_train attends over every key a query "
                         "sees: a model with an indexer is served")
    Bn, S = tokens.shape
    attend = _scaled_attention(cfg, attn_fn)
    with jax.named_scope("embed"):
        x = tfm.embed_tokens(params, tokens, cfg)
    rope = rope_tables(cfg, S)
    pos = jnp.broadcast_to(jnp.arange(S), (Bn, S))
    lay = params["layers"]

    def layer_body(x, lp, ffn):
        a_p, f_p = lp
        a = tfm._norm(x, a_p["ln1"], "rmsnorm", cfg.norm_eps)
        x = x + checkpoint_name(
            attend_expanded(a, a_p["attn"], cfg, rope, pos, attend),
            "attn_out")
        m = tfm._norm(x, a_p["ln2"], "rmsnorm", cfg.norm_eps)
        if ffn == "D":
            mlp = tiled_map(lambda t: tfm._mlp_block(t, f_p["mlp"], cfg), m,
                            _ffn_seq_tile(Bn, S, cfg), axis=1)
            return x + checkpoint_name(mlp, "mlp_out"), None
        y, aux, _, stats = dropless_moe_block_with_losses(m, f_p["moe"], cfg)
        return x + checkpoint_name(y, "mlp_out"), (
            aux, stats[:3].astype(jnp.float32))

    policy = tfm._remat_policy(cfg.remat_policy)
    body = layer_body
    if policy is not None:
        body = jax.checkpoint(layer_body, policy=policy, prevent_cse=False,
                              static_argnums=(2,))

    plan = layer_plan(cfg)
    aux_sum, stats_sum, routed = jnp.zeros((), jnp.float32), 0.0, 0
    with jax.named_scope("layers"):
        start = 0
        while start < len(plan):  # one scan a run of layers of one FFN kind
            ffn, end = plan[start].ffn, start + 1
            while end < len(plan) and plan[end].ffn == ffn:
                end += 1
            f0 = plan[start].ffn_index
            stacks = (
                jax.tree.map(lambda w: w[start:end], lay["A"]),
                jax.tree.map(lambda w: w[f0:f0 + end - start], lay[ffn]))
            x, out = jax.lax.scan(
                lambda c, lp, ffn=ffn: body(c, lp, ffn), x, stacks)
            if ffn == "S":
                aux_sum = aux_sum + out[0].sum()
                stats_sum = stats_sum + out[1].sum(axis=0)
                routed += end - start
            start = end
    x = tfm._norm(x, params["final_norm"], "rmsnorm", cfg.norm_eps)
    extras: Dict[str, jax.Array] = {}
    if routed:
        extras["moe_aux_loss"] = aux_sum
        for i, name in enumerate(MOE_COUNTERS):
            extras[name] = stats_sum[i] / routed
    return x, extras
