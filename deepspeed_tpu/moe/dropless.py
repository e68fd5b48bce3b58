"""Dropless MoE: every top-k assignment is computed, as a grouped GEMM.

Capability analogue of the reference's modern MoE inference/training path
(``inference/v2/kernels/cutlass_ops/moe_gemm`` + dropless routing): no
capacity buckets, no token dropping, so a token's output never depends on
the tokens batched beside it.  That is why this is THE serving path: the
inference engines call :func:`routed_ffn` for every MoE model, whatever
``moe_routing`` the model was trained with.

Four stages, each under a named scope a device trace can find:

``moe_route``     float32 softmax of the router logits, top-k, the weights by
                  the config's rule (``moe_norm_topk``: renormalised to sum 1,
                  or the raw probabilities); or sigmoid scores with a
                  correction bias for the choice (``moe_router``);
``moe_dispatch``  tokens scattered once into the tile-aligned grouped layout
                  (``ops/pallas/grouped_matmul.tile_aligned_layout``), whose
                  ``tile_m`` follows the step's assignments (:func:`moe_tile_m`);
``moe_experts``   the expert FFN as three grouped GEMMs: bf16
                  (``grouped_matmul``) or int8 codes dequantized in the kernel
                  (``grouped_mixed_gemm``), by the weight's type;
``moe_combine``   weighted expert outputs gathered back and summed per token;
``moe_shared``    a shared expert (``sh_w_in`` / ``sh_w_out``), where the model
                  has one: every row through ``mixed_gemm``, added after.

Training (``moe/layer.py`` with ``moe_routing='dropless'``) calls
:func:`dropless_moe_block_with_losses`, which is the same four stages plus
the router's auxiliary losses; serving pays for no loss.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.grouped_matmul import grouped_matmul, tile_aligned_layout
from ..ops.pallas.grouped_mixed_gemm import grouped_mixed_gemm
from ..ops.pallas.mixed_gemm import LayerOf, QuantizedWeight

_MIN_TILE_M, _MAX_TILE_M = 16, 512

def moe_tile_m(assignments: int, num_experts: int) -> int:
    """Rows of one M tile of the grouped layout, from the two static sizes of
    the step program: T assignments (tokens x top-k) over E experts.

    The smallest power of two that holds twice the mean rows an expert gets,
    between 16 (one packed bf16 sublane tile) and 512 (``mixed_gemm``'s
    widest M tile).  Twice the mean, so that under near-uniform routing an
    expert is one tile (its weights are fetched once and no tile is mostly
    another expert's padding) and a hot expert just takes more tiles.  The
    padded layout is ``(ceil(T / tile_m) + E) * tile_m`` rows, at most about
    three times T: a decode step of 32 rows x top-8 over 64 experts
    (T = 256) gets 16, a mixed step of 512 tokens (T = 4,096) gets 128."""
    want = -(-2 * assignments // num_experts)
    tile = _MIN_TILE_M
    while tile < min(want, _MAX_TILE_M):
        tile *= 2
    return tile


def padded_rows(assignments: int, num_experts: int) -> int:
    """Rows of the grouped layout a step program of T assignments computes
    on (static; what ``tile_aligned_layout`` returns as ``M_pad``)."""
    tile = moe_tile_m(assignments, num_experts)
    return (-(-assignments // tile) + num_experts) * tile


#: the most M tiles the layout of a SHARE of the experts may spend on the
#: assignments themselves (each tile the step does not fill still costs a
#: grid step of every N tile of three GEMMs)
_SHARE_MAX_TILES = 32


def share_tile_m(assignments: int, num_experts: int, held: int) -> int:
    """``moe_tile_m`` for a layer that holds ``held`` of ``num_experts``
    experts.  The layout is static and has to hold EVERY assignment (all T
    may fall on the held experts; nothing is dropped), while a step is
    expected to send ``T x held / num_experts`` of them here.  Sized for the
    expected rows alone (T = 4,096 over 16 of 256: tiles of 32) the layout
    would be 144 tiles of which 16 hold rows, and the 128 empty ones cost
    more grid steps than the full ones cost time; sized for T it would be
    tiles of 512 that hold 16 rows each.  So: the tile of the expected rows,
    but no smaller than what cuts T into ``_SHARE_MAX_TILES`` tiles (128 at
    T = 4,096; 16 at a decode step's 128)."""
    tile = moe_tile_m(-(-assignments * held // num_experts), held)
    while tile < _MAX_TILE_M and assignments > _SHARE_MAX_TILES * tile:
        tile *= 2
    return tile


def share_padded_rows(assignments: int, num_experts: int, held: int) -> int:
    """``padded_rows`` of a share's layout: tiles for all T assignments, one
    more for each held expert and one for the group that lives elsewhere."""
    tile = share_tile_m(assignments, num_experts, held)
    return (-(-assignments // tile) + held + 1) * tile


class Routing(NamedTuple):
    weights: jax.Array  # (N, k) float32: the gate weight of each assignment
    experts: jax.Array  # (N, k) int32
    probs: jax.Array  # (N, E) float32 softmax, for the training losses
    logits: jax.Array  # (N, E) float32


def route(x2: jax.Array, router: jax.Array, cfg,
          bias: Optional[jax.Array] = None) -> Routing:
    """``x2 (N, H)`` → top-k experts and their weights, in float32, by the
    config's rule: softmax over all experts, or (``moe_router: "sigmoid"``)
    sigmoid scores, the choice made on the scores plus the per-expert
    correction ``bias (E,)``, the weights the chosen experts' UNBIASED scores,
    renormalised (``moe_norm_topk``) and scaled by ``moe_routed_scaling``
    (``probs`` are then the scores)."""
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x2.astype(jnp.float32), router.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        if getattr(cfg, "moe_router", "softmax") == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            choice = scores if bias is None \
                else scores + bias.astype(jnp.float32)
            _, experts = jax.lax.top_k(choice, cfg.moe_top_k)
            weights = jnp.take_along_axis(scores, experts, axis=-1)
            if getattr(cfg, "moe_norm_topk", True):
                weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
            weights = weights * cfg.moe_routed_scaling
            return Routing(weights, experts.astype(jnp.int32), scores, logits)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, cfg.moe_top_k)
        if getattr(cfg, "moe_norm_topk", True):
            weights = weights / jnp.maximum(
                weights.sum(-1, keepdims=True), 1e-9)
        return Routing(weights, experts.astype(jnp.int32), probs, logits)


def _expert_gemm(a, w, tile_group, pad_sizes, used_tiles, tile_m):
    if isinstance(w, LayerOf):  # the layer stack (L, E, K, N), read in place
        return grouped_mixed_gemm(a, w.stack, tile_group, pad_sizes,
                                  used_tiles, tile_m=tile_m, layer=w.layer)
    if isinstance(w, QuantizedWeight):
        return grouped_mixed_gemm(a, w, tile_group, pad_sizes, used_tiles,
                                  tile_m=tile_m)
    return grouped_matmul(a, w.astype(a.dtype), tile_group, pad_sizes,
                          tile_m=tile_m)


def routed_ffn(x2: jax.Array, p: Dict[str, Any], cfg, *,
               routing: Optional[Routing] = None,
               valid: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """The routed expert FFN on ``x2 (N, H)``: route → layout → experts →
    combine.  → ``(y (N, H), stats)`` with ``stats`` int32 ``(2,)``: experts
    that got at least one row, and the largest rows-per-expert, counted over
    the rows ``valid (N,)`` marks (all rows when None).

    ``p`` is a layer's ``moe`` dict.  Its expert weights are ``(E, K, N)``
    arrays or ``QuantizedWeight`` nodes, or a ``LayerOf`` the whole stack
    ``(L, E, K, N)``, whose layer's experts the kernel reads in place (a scan
    that sliced them would copy one layer's codes before every call)."""
    N, H = x2.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    held = getattr(cfg, "experts_held", E)
    if held != E:
        return _routed_ffn_share(x2, p, cfg, routing, valid)
    dt = x2.dtype
    T = N * k
    tile_m = moe_tile_m(T, E)
    r = routing if routing is not None else route(
        x2, p["router"], cfg, p.get("router_bias"))

    with jax.named_scope("moe_dispatch"):
        expert_flat = r.experts.reshape(T)
        positions, tile_group, pad_sizes, M_pad = tile_aligned_layout(
            expert_flat, E, T, tile_m)
        counts = jnp.bincount(expert_flat, length=E)
        used_tiles = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
        xs = jnp.zeros((M_pad, H), dt).at[positions].set(
            jnp.repeat(x2, k, axis=0))
        if valid is not None:
            counts = jnp.bincount(
                expert_flat, weights=jnp.repeat(valid, k).astype(jnp.int32),
                length=E)
        stats = jnp.stack([jnp.sum(counts > 0), jnp.max(counts)]
                          ).astype(jnp.int32)
        if getattr(cfg, "moe_tap_choices", False):  # tooling only
            stats = jnp.concatenate([stats, expert_flat])

    with jax.named_scope("moe_experts"):
        def gmm(a, key):
            return _expert_gemm(a, p[key], tile_group, pad_sizes, used_tiles,
                                tile_m)

        if "w_gate" in p:
            hmid = jax.nn.silu(gmm(xs, "w_gate")) * gmm(xs, "w_in")
        elif getattr(cfg, "activation", "") == "relu2":  # ungated
            hmid = jnp.square(jax.nn.relu(gmm(xs, "w_in")))
        else:
            hmid = jax.nn.gelu(gmm(xs, "w_in"), approximate=True)
        ys = gmm(hmid, "w_out")  # (M_pad, H)

    with jax.named_scope("moe_combine"):
        # a token's k expert outputs, weighted and summed in float32: the sum
        # of a token does not depend on where its rows lie in the layout
        picked = ys[positions].reshape(N, k, H).astype(jnp.float32)
        y = jnp.sum(picked * r.weights[..., None], axis=1).astype(dt)
    return y, stats


def _routed_ffn_share(x2, p, cfg, routing, valid):
    """``routed_ffn`` for a layer that holds experts ``moe_first_expert`` to
    ``+ moe_experts_held`` of ``num_experts`` (one chip of an expert-parallel
    group, without its exchange): the router scores ALL experts and every
    row picks its top-k among all; the assignments that fall on a held
    expert are laid out, computed and combined under their own weights, the
    others are counted and LEFT OUT, so the output is this share's part of
    the layer's routed sum (the parts of all shares add up to the whole
    layer: ``tests/test_glm52.py``).  → ``(y, stats)`` with ``stats`` int32
    ``(3,)``: held experts that got a row, the largest rows of one, and the
    assignments that were local, over the rows ``valid`` marks."""
    N, H = x2.shape
    E, k = cfg.num_experts, cfg.moe_top_k
    held, first = cfg.experts_held, cfg.moe_first_expert
    dt = x2.dtype
    T = N * k
    tile_m = share_tile_m(T, E, held)
    r = routing if routing is not None else route(
        x2, p["router"], cfg, p.get("router_bias"))

    with jax.named_scope("moe_dispatch"):
        expert_flat = r.experts.reshape(T)
        local = (expert_flat >= first) & (expert_flat < first + held)
        # group ``held``: the experts that live elsewhere, laid out LAST, so
        # the tiles that hold rows to compute are the first ``used_tiles``
        group = jnp.where(local, expert_flat - first, held)
        positions, tile_group, pad_sizes, M_pad = tile_aligned_layout(
            group, held + 1, T, tile_m)
        tile_group = jnp.minimum(tile_group, held - 1)  # a block that exists
        counts = jnp.bincount(group, length=held + 1)[:held]
        used_tiles = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
        at = jnp.where(local, positions, M_pad)  # elsewhere: written nowhere
        xs = jnp.zeros((M_pad, H), dt).at[at].set(
            jnp.repeat(x2, k, axis=0), mode="drop")
        if valid is not None:
            counts = jnp.bincount(
                group, weights=jnp.repeat(valid, k).astype(jnp.int32),
                length=held + 1)[:held]
        stats = jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                           jnp.sum(counts)]).astype(jnp.int32)
        if getattr(cfg, "moe_tap_choices", False):  # tooling only
            stats = jnp.concatenate([stats, expert_flat])

    with jax.named_scope("moe_experts"):
        def gmm(a, key):
            return _expert_gemm(a, p[key], tile_group, pad_sizes[:held],
                                used_tiles, tile_m)

        hmid = jax.nn.silu(gmm(xs, "w_gate")) * gmm(xs, "w_in")
        ys = gmm(hmid, "w_out")

    with jax.named_scope("moe_combine"):
        # rows past ``used_tiles`` were never computed: read as zero, not as
        # whatever the buffer held
        picked = jnp.where(local[:, None], ys[jnp.minimum(at, M_pad - 1)], 0)
        y = jnp.sum(picked.reshape(N, k, H).astype(jnp.float32)
                    * r.weights[..., None], axis=1).astype(dt)
    return y, stats


def serving_moe_block(x: jax.Array, p: Dict[str, Any], cfg, *,
                      valid: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """The MoE FFN of every inference engine: ``x (..., H)`` → ``(y, stats)``
    (``stats`` as :func:`routed_ffn` gives them).  ``p`` is the layer's
    ``moe`` dict."""
    x2 = x.reshape(-1, x.shape[-1])
    y, stats = routed_ffn(x2, p, cfg,
                          valid=None if valid is None else valid.reshape(-1))
    if "sh_w_in" in p:
        # the shared expert: every row, unweighted, beside the routed ones
        from ..models.transformer import _lin, apply_activation

        with jax.named_scope("moe_shared"):
            if "sh_w_gate" in p:  # a gated shared expert (SwiGLU)
                mid = apply_activation(
                    _lin(x2, p, "sh_w_gate", "sh_b_gate"), cfg.activation
                ) * _lin(x2, p, "sh_w_in", "sh_b_in")
            else:
                mid = apply_activation(_lin(x2, p, "sh_w_in", "sh_b_in"),
                                       cfg.activation)
            y = y + _lin(mid, p, "sh_w_out", "sh_b_out")
    y = y.reshape(x.shape)
    if getattr(cfg, "moe_use_residual", False):
        from .layer import _prmoe_combine

        y = _prmoe_combine(x, y, p, cfg)
    return y, stats


def dropless_moe_block_with_losses(x: jax.Array, p: Dict[str, Any], cfg
                                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, H) → (y, aux_loss, z_loss); router losses as in
    ``moe/layer.py`` (Switch aux loss + St-MoE z-loss)."""
    B, S, H = x.shape
    E = cfg.num_experts
    x2 = x.reshape(B * S, H)
    r = route(x2, p["router"], cfg)
    z_loss = jnp.mean(jax.nn.logsumexp(r.logits, axis=-1) ** 2)
    me = jnp.mean(r.probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(r.experts[:, 0], E), axis=0)
    aux_loss = E * jnp.sum(me * ce)
    y, _ = routed_ffn(x2, p, cfg, routing=r)
    return y.reshape(B, S, H), aux_loss, z_loss
