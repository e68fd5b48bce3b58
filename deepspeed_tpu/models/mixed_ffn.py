"""A decoder of grouped-query attention layers whose FFN differs by layer:
leading DENSE layers before ROUTED ones, of which this chip may hold a share
(``model_type: afmoe``, Trinity-Mini: TRAINED, :func:`forward_train`).  The
attention is ``transformer._attention_block``'s with what the config switches
on (a gate on its output, a norm a head on q and k, position by layer kind);
a layer, on the residual stream ``x``:

    x <- x + post_attn( Attention( ln1(x) ) )        ``post_branch_norm``
    m  = ln2(x)
    f  = SwiGLU(m)                                   a "dense" layer
    f  = SwiGLU_shared(m) + sum over the chosen experts THIS CHIP holds of
         w_e SwiGLU_e(m)                             a "sparse" layer
    x <- x + post_mlp(f)

The parameters are stacked BY KIND, as ``latent_sparse.py``'s:
``params["layers"]["A"]`` every layer's norms and attention, ``["D"]`` the
dense FFNs, ``["S"]`` the routed ones.  Each run of layers of one FFN kind is
scanned by the period of its attention kinds, the kinds static inside.

A sigmoid router's correction bias is, with ``moe_bias_update_rate`` > 0, a
leaf that a RULE moves and no gradient: :func:`rule_moved` names it to the
engine (``ModelSpec.rule_moved``) and :func:`apply_bias_rule` is the rule
(``ModelSpec.apply_rules``), fed by ``moe_expert_counts`` in the step's
metrics: the assignments to ALL ``num_experts`` experts of each routed
layer, held here or not.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from . import transformer as tfm
from .latent_sparse import MOE_COUNTERS, _ffn_seq_tile

KINDS = ("A", "D", "S")

NOT_SERVED = ("a model with a gate on its attention's output, norms after "
              "its branches or dense layers ahead of routed ones is trained "
              "(models/mixed_ffn.py): the step programs of inference/v2 "
              "compute none of the three yet")


def check_config(cfg) -> None:
    n = cfg.num_layers
    got = cfg.mlp_layer_types
    if len(got) != n or set(got) - {"dense", "sparse"}:
        raise ValueError(f"mlp_layer_types names {len(got)} layers of kinds "
                         f"{sorted(set(got))}; num_layers is {n} and the "
                         f"kinds are ['dense', 'sparse']")
    if cfg.mixer_pattern or cfg.eva_window or cfg.parallel_residual \
            or cfg.norm == "layernorm" or not cfg.is_gated_mlp \
            or cfg.tie_embeddings:
        raise ValueError("mlp_layer_types beside a mixer pattern, EVA "
                         "attention, a parallel residual, LayerNorm, an "
                         "ungated FFN or tied embeddings is not something "
                         "the program computes")
    if "sparse" in got and not (
            cfg.num_experts and 0 <= cfg.moe_first_expert
            and cfg.moe_first_expert + cfg.experts_held <= cfg.num_experts):
        raise ValueError(
            f"experts {cfg.moe_first_expert} to {cfg.moe_first_expert} + "
            f"{cfg.experts_held} are not among {cfg.num_experts}")
    if cfg.moe_bias_update_rate and cfg.moe_router != "sigmoid":
        raise ValueError("moe_bias_update_rate moves a sigmoid router's "
                         "correction bias; a softmax router has none")


def layers_of(cfg, kind: str) -> int:
    """Layers in stack ``kind`` ("A", "D", "S")."""
    if kind == "A":
        return cfg.num_layers
    return sum(t == ("dense" if kind == "D" else "sparse")
               for t in cfg.mlp_layer_types)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg) -> Dict[str, Any]:
    pd = jnp.dtype(cfg.param_dtype)
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    E, held = cfg.num_experts, cfg.experts_held
    L, Ld, Ls = (layers_of(cfg, k) for k in KINDS)
    keys = iter(jax.random.split(rng, 24))
    dense = tfm._dense_init
    norm_init = jnp.zeros if cfg.norm == "gemma_rmsnorm" else jnp.ones

    def ones(*shape):
        return {"scale": norm_init(shape, pd)}

    attn = {"wq": dense(next(keys), (L, h, nh * hd), h, pd),
            "wk": dense(next(keys), (L, h, nkv * hd), h, pd),
            "wv": dense(next(keys), (L, h, nkv * hd), h, pd),
            "wo": dense(next(keys), (L, nh * hd, h), nh * hd, pd),
            **tfm.init_attention_extras(next(keys), cfg, L, pd)}
    if cfg.qk_norm:
        attn["q_norm"], attn["k_norm"] = ones(L, nh * hd), ones(L, nkv * hd)
    A = {"ln1": ones(L, h), "ln2": ones(L, h), "attn": attn}
    if cfg.post_branch_norm:
        A["ln1_post"], A["ln2_post"] = ones(L, h), ones(L, h)
    layers: Dict[str, Any] = {"A": A}
    if Ld:
        layers["D"] = {"mlp": {
            "w_in": dense(next(keys), (Ld, h, f), h, pd),
            "w_gate": dense(next(keys), (Ld, h, f), h, pd),
            "w_out": dense(next(keys), (Ld, f, h), f, pd)}}
    if Ls:
        moe = {"router": dense(next(keys), (Ls, h, E), h, pd),
               # the experts THIS CHIP holds
               "w_in": dense(next(keys), (Ls, held, h, fe), h, pd),
               "w_gate": dense(next(keys), (Ls, held, h, fe), h, pd),
               "w_out": dense(next(keys), (Ls, held, fe, h), fe, pd)}
        if cfg.moe_router == "sigmoid":
            # zero in a published model before training; drawn small, so
            # that it changes some choices and a program that drops it is
            # seen.  Float32 whatever the parameters' type: the rule's step
            # is under a bfloat16's last place
            moe["router_bias"] = 0.02 * jax.random.normal(
                next(keys), (Ls, E), jnp.float32)
        if fs:
            moe.update(
                sh_w_in=dense(next(keys), (Ls, h, fs), h, pd),
                sh_w_gate=dense(next(keys), (Ls, h, fs), h, pd),
                sh_w_out=dense(next(keys), (Ls, fs, h), fs, pd))
        layers["S"] = {"moe": moe}
    return {
        "embed": {"tokens": dense(next(keys), (cfg.vocab_size, h), h, pd)},
        "layers": layers,
        "final_norm": ones(h),
        "lm_head": {"w": dense(next(keys), (h, cfg.vocab_size), h, pd)},
    }


def param_axes(cfg) -> Dict[str, Any]:
    """Logical axes of :func:`init_params`'s tree: ``transformer.param_axes``'
    names, the held experts' axis ``expert``, the router whole."""
    ln = {"scale": ("layers", "embed")}
    attn = {"wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            **tfm.attention_extras_axes(cfg)}
    if cfg.qk_norm:
        attn["q_norm"] = {"scale": ("layers", "heads")}
        attn["k_norm"] = {"scale": ("layers", "kv_heads")}
    A = {"ln1": dict(ln), "ln2": dict(ln), "attn": attn}
    if cfg.post_branch_norm:
        A["ln1_post"], A["ln2_post"] = dict(ln), dict(ln)
    layers: Dict[str, Any] = {"A": A}
    if layers_of(cfg, "D"):
        layers["D"] = {"mlp": {"w_in": ("layers", "embed", "mlp"),
                               "w_gate": ("layers", "embed", "mlp"),
                               "w_out": ("layers", "mlp", "embed")}}
    if layers_of(cfg, "S"):
        moe = {"router": ("layers", "embed", None),
               "w_in": ("layers", "expert", "embed", "mlp"),
               "w_gate": ("layers", "expert", "embed", "mlp"),
               "w_out": ("layers", "expert", "mlp", "embed")}
        if cfg.moe_router == "sigmoid":
            moe["router_bias"] = ("layers", None)
        if cfg.moe_shared_size:
            moe.update(sh_w_in=("layers", "embed", "mlp"),
                       sh_w_gate=("layers", "embed", "mlp"),
                       sh_w_out=("layers", "mlp", "embed"))
        layers["S"] = {"moe": moe}
    return {"embed": {"tokens": ("vocab", "embed")}, "layers": layers,
            "final_norm": {"scale": ("embed",)},
            "lm_head": {"w": ("embed", "vocab")}}


def num_params(cfg, include_embed: bool = True) -> int:
    """Parameters of the model with the experts THIS configuration holds
    (all of them for the published one)."""
    h, f, fe, fs = (cfg.hidden_size, cfg.intermediate_size, cfg.expert_width,
                    cfg.moe_shared_size)
    qh, kvh = cfg.num_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    per = {
        "A": 2 * h + 2 * h * qh + 2 * h * kvh
        + (qh + kvh if cfg.qk_norm else 0)
        + (2 * cfg.head_dim if cfg.qk_norm_per_head else 0)
        + (h * qh if cfg.attn_gate else 0)
        + (2 * h if cfg.post_branch_norm else 0),
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
# the leaf a rule moves
# ---------------------------------------------------------------------------


def rule_moved(params: Dict[str, Any], cfg) -> Any:
    """``ModelSpec.rule_moved`` for ``params``: True on the routers' biases
    (None for a model whose bias is a trained leaf, or that has none)."""
    if not cfg.moe_bias_update_rate or "S" not in params["layers"]:
        return None
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) == "router_bias",
        params)


def bias_after_rule(bias: jax.Array, counts: jax.Array, rate: float
                    ) -> jax.Array:
    """``b + d - mean(d)``, ``d = rate x sign(mean(c) - c)``, a layer a row
    (``bias``, ``counts``: ``(layers, num_experts)``)."""
    c = counts.astype(jnp.float32)
    d = rate * jnp.sign(c.mean(-1, keepdims=True) - c)
    return bias + (d - d.mean(-1, keepdims=True)).astype(bias.dtype)


def apply_bias_rule(params: Dict[str, Any], metrics: Dict[str, jax.Array],
                    cfg) -> Dict[str, Any]:
    """``ModelSpec.apply_rules``: the parameters after the optimizer's update
    → the same with every routed layer's ``router_bias`` moved by the step's
    ``moe_expert_counts`` (a mean over the micro-batches where the engine
    accumulates, which the sign does not see)."""
    moe = params["layers"]["S"]["moe"]
    bias = bias_after_rule(moe["router_bias"], metrics["moe_expert_counts"],
                           cfg.moe_bias_update_rate)
    return {**params, "layers": {**params["layers"], "S": {"moe": {
        **moe, "router_bias": bias}}}}


def spec_rules(params: Dict[str, Any], cfg) -> Dict[str, Any]:
    """``ModelSpec``'s two fields for ``params``, as keyword arguments
    (none for a model no rule moves a leaf of)."""
    moved = rule_moved(params, cfg)
    if moved is None:
        return {}
    return {"rule_moved": moved,
            "apply_rules": partial(apply_bias_rule, cfg=cfg)}


# ---------------------------------------------------------------------------
# the trained forward
# ---------------------------------------------------------------------------


def _period(kinds: Tuple[str, ...]) -> int:
    """The shortest p with ``kinds`` = its first p repeated and cut."""
    for p in range(1, len(kinds) + 1):
        if kinds == (kinds[:p] * -(-len(kinds) // p))[:len(kinds)]:
            return p
    return len(kinds)


def forward_train(params: Dict[str, Any], tokens: jax.Array, cfg,
                  attn_fn=None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, S) → (hidden states (B, S, h) after the final norm, extras).
    Each run of layers of one FFN kind is scanned over the periods of its
    attention kinds (a period's layers unrolled inside, their kinds static;
    what is left of a period at the run's end is unrolled after the scan)
    under ``cfg.remat_policy``.  ``extras`` holds, for a model with routed
    layers, ``latent_sparse.MOE_COUNTERS`` as means over the routed layers
    (float32 scalars) and ``moe_expert_counts``: the assignments to every
    expert, ``(routed layers, num_experts)``."""
    from jax.ad_checkpoint import checkpoint_name

    from ..moe.dropless import expert_counts, route, serving_moe_block
    from ..sequence.tiled_compute import tiled_map

    Bn, S = tokens.shape
    kinds = cfg.layer_kinds
    by_kind = {kind: tfm.attention_of_kind(cfg, kind, S, attn_fn)
               for kind in sorted(set(kinds))}
    with jax.named_scope("embed"):
        x = tfm.embed_tokens(params, tokens, cfg)
    lay = params["layers"]

    def norm(t, p):
        return tfm._norm(t, p, cfg.norm, cfg.norm_eps)

    def layer_body(x, lp, kind, ffn):
        a_p, f_p = lp
        fn, (cos, sin) = by_kind[kind]
        attn = tfm._attention_block(norm(x, a_p["ln1"]), a_p["attn"], cfg,
                                    cos, sin, fn)
        if cfg.post_branch_norm:
            attn = norm(attn, a_p["ln1_post"])
        x = x + checkpoint_name(attn, "attn_out")
        m = norm(x, a_p["ln2"])
        out = None
        if ffn == "D":
            y = tiled_map(lambda t: tfm._mlp_block(t, f_p["mlp"], cfg), m,
                          _ffn_seq_tile(Bn, S, cfg), axis=1)
        else:
            p = f_p["moe"]
            # the bias moves the CHOICE and takes no gradient: a rule moves
            # it, where it is trained at all (a served step traces none of
            # this: ``route`` itself is as it was)
            bias = p.get("router_bias")
            r = route(m.reshape(Bn * S, -1), p["router"], cfg,
                      None if bias is None else jax.lax.stop_gradient(bias))
            y, stats = serving_moe_block(m, p, cfg, routing=r)
            out = (stats[:3].astype(jnp.float32),
                   expert_counts(r.experts, cfg.num_experts))
        if cfg.post_branch_norm:
            y = norm(y, a_p["ln2_post"])
        return x + checkpoint_name(y, "mlp_out"), out

    policy = tfm._remat_policy(cfg.remat_policy)
    body = layer_body
    if policy is not None:
        body = jax.checkpoint(layer_body, policy=policy, prevent_cse=False,
                              static_argnums=(2, 3))

    routed = []  # a routed run's (stats (n, 3), counts (n, E))
    with jax.named_scope("layers"):
        start, seen = 0, {"D": 0, "S": 0}
        types = cfg.mlp_layer_types
        while start < len(types):  # one run of layers of one FFN kind
            end = start + 1
            while end < len(types) and types[end] == types[start]:
                end += 1
            ffn = "D" if types[start] == "dense" else "S"
            f0, n = seen[ffn], end - start
            run_kinds = kinds[start:end]
            p = _period(run_kinds)
            whole = n // p * p  # the layers the scan takes, p at a time

            def stacks(lo, hi):
                return (jax.tree.map(lambda w: w[start + lo:start + hi],
                                     lay["A"]),
                        jax.tree.map(lambda w: w[f0 + lo:f0 + hi], lay[ffn]))

            def period_body(x, lp, ffn=ffn, run_kinds=run_kinds, p=p):
                outs = []
                for i in range(p):
                    x, out = body(x, jax.tree.map(lambda w: w[i], lp),
                                  run_kinds[i], ffn)
                    outs.append(out)
                return x, (None if ffn == "D" else jax.tree.map(
                    lambda *a: jnp.stack(a), *outs))

            x, out = jax.lax.scan(period_body, x, jax.tree.map(
                lambda w: w.reshape((n // p, p) + w.shape[1:]),
                stacks(0, whole)))
            if ffn == "S":
                routed.append(jax.tree.map(
                    lambda a: a.reshape((whole,) + a.shape[2:]), out))
            for i in range(whole, n):  # the cut period at the run's end
                x, out = body(x, jax.tree.map(lambda w: w[0],
                                              stacks(i, i + 1)),
                              run_kinds[i], ffn)
                if ffn == "S":
                    routed.append(jax.tree.map(lambda a: a[None], out))
            seen[ffn] += n
            start = end
    x = norm(x, params["final_norm"])
    extras: Dict[str, jax.Array] = {}
    if routed:
        stats, counts = (jnp.concatenate(a, axis=0) for a in zip(*routed))
        # a layer that holds every expert counts no local rows
        for i, name in enumerate(MOE_COUNTERS[:stats.shape[1]]):
            extras[name] = stats[:, i].mean()
        extras["moe_expert_counts"] = counts
    return x, extras
