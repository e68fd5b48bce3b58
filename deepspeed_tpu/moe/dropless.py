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
``moe_dispatch``  the tile-aligned grouped layout planned
                  (``ops/pallas/grouped_matmul.tile_aligned_layout``; its
                  ``tile_m`` follows the step's assignments, :func:`moe_tile_m`)
                  and inverted without a scatter (``layout_sources``: each
                  row's token), then the tokens GATHERED into it under those
                  indices (``ops/pallas/moe_rows.gather_rows``: a mixed
                  step's on the MXU, and only the tiles that hold rows; a
                  decode step's few assignments scattered, as they were);
``moe_experts``   the expert FFN as three grouped GEMMs: bf16
                  (``grouped_matmul``) or int8 codes dequantized in the kernel
                  (``grouped_mixed_gemm``), by the weight's type;
``moe_combine``   weighted expert outputs gathered back (XLA's gather: its
                  source is a layout's worth of rows) and summed per token
                  in float32;
``moe_shared``    a shared expert (``sh_w_in`` / ``sh_w_out``), where the model
                  has one: every row through ``mixed_gemm``, added after.

Training (``moe/layer.py`` with ``moe_routing='dropless'``, and the latent
model's trained forward) calls :func:`dropless_moe_block_with_losses`: the
same stages on one routing, a chip's share of the experts and the shared
expert included, plus the router's losses under ``moe_aux``; serving pays for
no loss.  Every stage is differentiable: the grouped GEMM's backward is two
more kernels (``ops/pallas/grouped_matmul.py``), and rows of the layout that
no kernel wrote are read through ``jnp.where`` only, forward and backward.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.grouped_matmul import (grouped_matmul, layout_sources,
                                         tile_aligned_layout)
from ..ops.pallas.grouped_mixed_gemm import grouped_mixed_gemm
from ..ops.pallas.mixed_gemm import LayerOf, QuantizedWeight
from ..ops.pallas.moe_rows import gather_rows

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
                          tile_m=tile_m, used_tiles=used_tiles)


def _valid_counts(group: jax.Array, valid: jax.Array, groups: int
                  ) -> jax.Array:
    """The assignments ``valid`` marks, by group (``jnp.bincount`` with
    weights, as a compare and a sum: the TPU walks a scatter-add of T
    indices one after another, 36 us at T = 4,096)."""
    hit = group[:, None] == jnp.arange(groups, dtype=group.dtype)[None, :]
    return jnp.sum(hit & valid.astype(bool)[:, None], axis=0,
                   dtype=jnp.int32)


def expert_counts(experts: jax.Array, num_experts: int) -> jax.Array:
    """The assignments ``experts (N, k)`` makes to EACH of ``num_experts``
    experts, int32 ``(num_experts,)``, held here or not: what a rule that
    balances the router's load is fed (``models/mixed_ffn.py``)."""
    with jax.named_scope("moe_route"):
        flat = experts.reshape(-1)
        return _valid_counts(flat, jnp.ones_like(flat), num_experts)


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
        xs = gather_rows(
            x2, positions.reshape(N, k), used_tiles, rows=M_pad,
            tile_m=tile_m, sources=functools.partial(
                layout_sources, expert_flat, counts, tile_group, pad_sizes,
                tile_m))
        if valid is not None:
            counts = _valid_counts(expert_flat, jnp.repeat(valid, k), E)
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


#: a share's layout whose rows x hidden would take this many bytes or more
#: is computed in ROUNDS (:func:`_share_in_rounds`) and the array is never
#: made; a smaller one (every serving step: 77 MB at most in the benchmark's
#: cells, where a trained step of 16,384 tokens has 421 MB) is made at once
_ROUNDS_FROM_BYTES = 128 << 20


def _routed_ffn_share(x2, p, cfg, routing, valid):
    """``routed_ffn`` for a layer that holds experts ``moe_first_expert`` to
    ``+ moe_experts_held`` of ``num_experts`` (one chip of an expert-parallel
    group, without its exchange): the router scores ALL experts and every
    row picks its top-k among all; the assignments that fall on a held
    expert are laid out, computed and combined under their own weights, the
    others are counted and LEFT OUT, so the output is this share's part of
    the layer's routed sum (the parts of all shares add up to the whole
    layer: ``tests/test_glm52.py``, ``tests/test_dsv2lite.py``), and so is
    every gradient.  → ``(y, stats)`` with ``stats`` int32 ``(3,)``: held
    experts that got a row, the largest rows of one, and the assignments
    that were local, over the rows ``valid`` marks."""
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
        counts = local_counts = jnp.bincount(group, length=held + 1)[:held]
        used_tiles = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
        at = jnp.where(local, positions, M_pad)  # elsewhere: written nowhere
        if valid is not None:
            counts = _valid_counts(group, jnp.repeat(valid, k), held)
        stats = jnp.stack([jnp.sum(counts > 0), jnp.max(counts),
                           jnp.sum(counts)]).astype(jnp.int32)
        if getattr(cfg, "moe_tap_choices", False):  # tooling only
            stats = jnp.concatenate([stats, expert_flat])

    if M_pad * H * dt.itemsize >= _ROUNDS_FROM_BYTES:
        return _share_in_rounds(x2, p, r.weights, local.reshape(N, k),
                                at.reshape(N, k), tile_group,
                                pad_sizes[:held], used_tiles, tile_m,
                                E), stats

    with jax.named_scope("moe_dispatch"):
        # the group that lives elsewhere has no row in ``src``, and
        # ``used_tiles`` ends the walk behind the local rows
        xs = gather_rows(
            x2, jnp.where(local, positions, -1).reshape(N, k), used_tiles,
            rows=M_pad, tile_m=tile_m, sources=functools.partial(
                layout_sources, group, local_counts, tile_group,
                pad_sizes[:held], tile_m))

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


@jax.custom_vjp
def _rows_in(x2, src, filled, pos, mine):
    """A round's rows in expert order: ``xs (C, H) = x2[src]`` where an
    assignment fills the row, zero elsewhere.  The backward is a GATHER too:
    a token's cotangent is the sum of its own (at most k) rows of ``dxs``,
    found through ``pos (N, k)`` where ``mine`` says the assignment lies in
    this round.  (XLA would transpose the gather into a scatter-add of C
    rows, which the TPU serialises: 7 ms a pass at 22,528 x 2,048.)  Rows
    that no kernel wrote are never selected."""
    return jnp.where(filled[:, None], x2[src], 0)


def _rows_in_fwd(x2, src, filled, pos, mine):
    return _rows_in(x2, src, filled, pos, mine), (pos, mine)


def _rows_in_bwd(res, dxs):
    pos, mine = res
    acc = jnp.zeros((pos.shape[0], dxs.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        acc = acc + jnp.where(mine[:, j, None],
                              dxs[pos[:, j]].astype(jnp.float32), 0)
    return acc.astype(dxs.dtype), None, None, None, None


_rows_in.defvjp(_rows_in_fwd, _rows_in_bwd)


@jax.custom_vjp
def _rows_out(ys, weights, pos, mine, src, gate, filled):
    """A round's weighted outputs summed a token: ``y (N, H)`` float32 ``=
    sum_j mine[n, j] weights[n, j] ys[pos[n, j]]``, k gathers of N rows.  The
    backward: a row's cotangent is its token's, times its gate (a gather of C
    rows through ``src``); a weight's is its row's dot with the token's."""
    acc = jnp.zeros((pos.shape[0], ys.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        acc = acc + jnp.where(
            mine[:, j, None],
            ys[pos[:, j]].astype(jnp.float32) * weights[:, j, None], 0)
    return acc


def _rows_out_fwd(ys, weights, pos, mine, src, gate, filled):
    return (_rows_out(ys, weights, pos, mine, src, gate, filled),
            (ys, pos, mine, src, gate, filled))


def _rows_out_bwd(res, dy):
    ys, pos, mine, src, gate, filled = res
    dys = jnp.where(filled[:, None], dy[src] * gate[:, None], 0
                    ).astype(ys.dtype)
    dw = jnp.stack([jnp.where(mine[:, j], jnp.sum(
        ys[pos[:, j]].astype(jnp.float32) * dy, axis=-1), 0)
        for j in range(pos.shape[1])], axis=1)
    return dys, dw, None, None, None, None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


def _share_in_rounds(x2, p, weights, local, at, tile_group, pad_sizes,
                     used_tiles, tile_m: int, num_experts: int):
    """The share's FFN on a layout too large to hold: a trained step of
    16,384 tokens x top 6 has 98,304 assignments, the layout must have room
    for all of them (every one may be local; nothing is dropped), and about
    an eighth are.  So no array of the layout's rows x H is ever made.  The
    layout stays what it is, index arithmetic (``at (N, k)``: each local
    assignment's row; ``local``: which are), and is walked in ROUNDS of
    ``round_tiles`` tiles, about one and a half times the expected local
    rows: a round gathers its rows' tokens (``_rows_in``), runs the three
    grouped GEMMs on them and sums the weighted outputs a token
    (``_rows_out``); a round past the rows (``lax.cond``) does nothing.
    Under near-uniform routing the first round is the only one that runs,
    and under any routing every local assignment is computed.  Each round is
    rematerialised in the backward; forward and backward move rows by
    gathers alone."""
    N, H = x2.shape
    k = at.shape[1]
    T, held = N * k, pad_sizes.shape[0]
    tiles = tile_group.shape[0]
    round_tiles = min(tiles, -(-3 * T * held // (2 * num_experts * tile_m))
                      + held)
    R = -(-tiles // round_tiles)
    C = round_tiles * tile_m

    with jax.named_scope("moe_dispatch"):
        # the token (-1: no assignment lies there) and the gate of each row
        # of the layout (int32 / float32 vectors)
        rows = R * C
        flat = jnp.where(local, at, rows).reshape(T)  # elsewhere: nowhere
        src = jnp.full((rows,), -1, jnp.int32).at[flat].set(
            jnp.arange(T, dtype=jnp.int32) // k, mode="drop")
        gate = jnp.zeros((rows,), jnp.float32).at[flat].set(
            weights.reshape(T), mode="drop")
        tg = jnp.pad(tile_group, (0, R * round_tiles - tiles), mode="edge")
        starts = jnp.cumsum(pad_sizes) - pad_sizes

    def one_round(acc, inp):
        j, src_j, gate_j, tg_j = inp
        filled_j, src_j = src_j >= 0, jnp.maximum(src_j, 0)
        used_j = jnp.clip(used_tiles - j * round_tiles, 0, round_tiles)
        # the rows of each held expert that lie in this round
        sizes_j = jnp.clip(jnp.minimum(starts + pad_sizes, (j + 1) * C)
                           - jnp.maximum(starts, j * C), 0, C)

        def compute(acc):
            with jax.named_scope("moe_dispatch"):
                mine = local & (at >= j * C) & (at < (j + 1) * C)
                pos = jnp.clip(at - j * C, 0, C - 1)
                xs = _rows_in(x2, src_j, filled_j, pos, mine)
            with jax.named_scope("moe_experts"):
                def gmm(a, key):
                    return _expert_gemm(a, p[key], tg_j, sizes_j, used_j,
                                        tile_m)

                hmid = jax.nn.silu(gmm(xs, "w_gate")) * gmm(xs, "w_in")
                ys = gmm(hmid, "w_out")
            with jax.named_scope("moe_combine"):
                return acc + _rows_out(ys, weights, pos, mine, src_j, gate_j,
                                       filled_j)

        return jax.lax.cond(used_j > 0, compute, lambda a: a, acc), None

    acc, _ = jax.lax.scan(
        jax.checkpoint(one_round), jnp.zeros((N, H), jnp.float32),
        (jnp.arange(R, dtype=jnp.int32), src.reshape(R, C),
         gate.reshape(R, C), tg.reshape(R, round_tiles)))
    return acc.astype(x2.dtype)


def serving_moe_block(x: jax.Array, p: Dict[str, Any], cfg, *,
                      valid: Optional[jax.Array] = None,
                      routing: Optional[Routing] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """The MoE FFN of every inference engine, and of the trained forward
    (:func:`dropless_moe_block_with_losses`, which hands in the ``routing``
    its losses were made from): ``x (..., H)`` → ``(y, stats)`` (``stats`` as
    :func:`routed_ffn` gives them).  ``p`` is the layer's ``moe`` dict."""
    x2 = x.reshape(-1, x.shape[-1])
    y, stats = routed_ffn(x2, p, cfg, routing=routing,
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


def balance_loss(r: Routing, cfg, batch: int) -> jax.Array:
    """The router's balance loss over ``r`` (``batch x S`` rows, a sequence's
    rows together).  ``moe_seq_aux``: DeepSeek's sequence-wise form, the mean
    over the sequences of ``sum_i f_i P_i`` with ``f_i = E / (K S) x`` the
    sequence's tokens that chose expert i among their top K and ``P_i`` the
    expert's mean probability over the sequence; else Switch's: ``E sum_i
    (share of rows whose FIRST choice is i) x (mean probability of i)`` over
    all rows.  The gradient flows through the probabilities alone.  Over ALL
    ``num_experts``, whatever share of them is held here: the router is
    whole on every chip of an expert-parallel group."""
    E, k = cfg.num_experts, cfg.moe_top_k
    if getattr(cfg, "moe_seq_aux", False):
        S = r.probs.shape[0] // batch
        chosen = jax.nn.one_hot(r.experts.reshape(batch, S * k), E,
                                dtype=jnp.float32).sum(axis=1)  # (B, E)
        f = chosen * (E / (k * S))
        P = r.probs.reshape(batch, S, E).mean(axis=1)
        return jnp.mean(jnp.sum(f * P, axis=-1))
    me = jnp.mean(r.probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(r.experts[:, 0], E), axis=0)
    return E * jnp.sum(me * ce)


def dropless_moe_block_with_losses(x: jax.Array, p: Dict[str, Any], cfg
                                   ) -> Tuple[jax.Array, jax.Array, jax.Array,
                                              jax.Array]:
    """THE trained MoE FFN: ``x (B, S, H)`` → ``(y, aux_loss, z_loss,
    stats)``.  :func:`serving_moe_block`'s mathematics (the routed experts,
    all or this chip's share of them, the shared expert, the PR-MoE mix) on
    one routing, plus the router's losses made from the same routing
    (:func:`balance_loss`; St-MoE's z-loss) under scope ``moe_aux``.
    Gradients reach the held experts, the shared expert and, through the
    gates of the assignments computed here and through the losses, the
    router; what experts held elsewhere would add is left out of the forward
    and of the backward alike.  ``stats`` as :func:`routed_ffn` gives them."""
    B, S, H = x.shape
    r = route(x.reshape(B * S, H), p["router"], cfg, p.get("router_bias"))
    with jax.named_scope("moe_aux"):
        z_loss = jnp.mean(jax.nn.logsumexp(r.logits, axis=-1) ** 2)
        aux_loss = balance_loss(r, cfg, B)
    y, stats = serving_moe_block(x, p, cfg, routing=r)
    return y, aux_loss, z_loss, stats
