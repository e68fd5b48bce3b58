"""Grouped (per-expert) matmul — the MoE FFN hot op.

Capability analogue of the reference's CUTLASS MoE grouped GEMM
(``inference/v2/kernels/cutlass_ops/moe_gemm/``): one kernel computing
``out[r] = lhs[r] @ rhs[g(r)]`` where rows are grouped by expert, instead of
the capacity-padded ``(E,C,H)×(E,H,F)`` batched einsum.

TPU-native form: rows arrive in a TILE-ALIGNED layout — each group's rows
padded up to a multiple of the m-tile so every grid tile belongs to exactly
one group.  A scalar-prefetched ``tile_group`` array then steers each tile's
``rhs`` BlockSpec to its expert's weights: the kernel body is a single dense
``(tm, K) @ (K, tn)`` MXU matmul, and group routing costs nothing inside the
kernel.  (This is the simple cousin of megablocks' block-diagonal design:
alignment padding ≤ E·tm rows, negligible at MoE token counts.)

``jax.lax.ragged_dot`` is the fallback off-TPU and for shapes the Mosaic
tiling rules reject; it accepts the same padded layout (padding rows are
zeros whose outputs the caller discards).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

def _pick_tile_k(K: int) -> int:
    for cand in (1024, 512, 256, 128):
        if K % cand == 0:
            return cand
    return 0


def _use_pallas(M: int, K: int, N: int, tile_m: int, tile_n: int) -> bool:
    if backend.interpret():  # the host CPU runs ragged_dot, by design
        return False
    # Mosaic tiling: K and N 128-aligned lanes, the M tile whole packed bf16
    # sublane tiles (16 rows: a decode step's few rows an expert)
    ok = (M % tile_m == 0 and _pick_tile_k(K) > 0 and N % tile_n == 0
          and tile_m % 16 == 0 and tile_n % 128 == 0)
    if not ok:
        backend.warn_fallback(
            "grouped_matmul", f"M={M}, K={K}, N={N} do not tile into "
            f"tile_m={tile_m}, tile_n={tile_n} (16-row, 128-lane tiles)")
    return ok


def _gmm_kernel(tile_group_ref, lhs_ref, rhs_ref, out_ref, acc_ref, *,
                nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(lhs_ref[:], rhs_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _flush():
        out_ref[:] = acc_ref[:].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "tile_n"))
def _gmm_pallas(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                tile_m: int, tile_n: int) -> jax.Array:
    M, K = lhs.shape
    E, _, N = rhs.shape
    tile_k = _pick_tile_k(K)
    nk = K // tile_k
    grid = (M // tile_m, N // tile_n, nk)  # k innermost: sequential accum
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, tile_k), lambda i, j, kk, tg: (i, kk)),
                pl.BlockSpec((1, tile_k, tile_n),
                             lambda i, j, kk, tg: (tg[i], kk, j)),
            ],
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   lambda i, j, kk, tg: (i, j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        name="grouped_matmul",
    )(tile_group, lhs, rhs)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   padded_group_sizes: jax.Array, tile_m: int = 512,
                   tile_n: int = 1024) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[tile_group[r // tile_m]]``.

    ``lhs``: (M, K) tile-aligned grouped rows (M multiple of tile_m);
    ``rhs``: (E, K, N); ``tile_group``: (M // tile_m,) int32 expert per tile;
    ``padded_group_sizes``: (E,) row counts of the padded layout (for the
    ragged_dot fallback).  Differentiable: backward runs through ragged_dot's
    transpose rules (full-precision grads).
    """
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, (lhs.shape, rhs.shape)

    # shrink-only clamp: largest 128-multiple tile dividing N
    while tile_n > 128 and N % tile_n != 0:
        tile_n //= 2
    if not _use_pallas(M, K, N, tile_m, tile_n):
        return jax.lax.ragged_dot(lhs, rhs, padded_group_sizes)

    @jax.custom_vjp
    def f(lhs, rhs):
        return _gmm_pallas(lhs, rhs, tile_group, tile_m, tile_n)

    def f_fwd(lhs, rhs):
        return f(lhs, rhs), (lhs, rhs)

    def f_bwd(res, g):
        lhs, rhs = res
        # dlhs[r] = g[r] @ rhs[g(r)]^T — the same grouped matmul with
        # transposed weights; drhs via ragged_dot's transpose rule
        dlhs = grouped_matmul(g, rhs.swapaxes(1, 2), tile_group,
                              padded_group_sizes, tile_m, tile_n)
        _, vjp = jax.vjp(
            lambda r: jax.lax.ragged_dot(lhs, r, padded_group_sizes), rhs)
        (drhs,) = vjp(g)
        return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype)

    f.defvjp(f_fwd, f_bwd)
    return f(lhs, rhs)


def tile_aligned_layout(expert_flat: jax.Array, num_experts: int, T: int,
                        tile_m: int) -> Tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array]:
    """Plan the tile-aligned grouped layout for ``T`` assignments.

    Returns (positions (T,), tile_group (M_pad//tile_m,),
    padded_group_sizes (E,), M_pad) where ``positions[a]`` is assignment
    ``a``'s row in the padded layout.  ``M_pad`` is static:
    ceil(T/tile_m) + num_experts extra tiles cover any group split.
    """
    E = num_experts
    m_tiles = (T + tile_m - 1) // tile_m + E
    M_pad = m_tiles * tile_m

    counts = jnp.bincount(expert_flat, length=E)
    padded = ((counts + tile_m - 1) // tile_m) * tile_m
    offsets = jnp.concatenate([jnp.zeros((1,), padded.dtype),
                               jnp.cumsum(padded)[:-1]])
    # rank of each assignment within its expert (stable order)
    onehot = jax.nn.one_hot(expert_flat, E, dtype=jnp.int32)  # (T, E)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)  # assignments ahead, same e
    rank = jnp.take_along_axis(rank, expert_flat[:, None], axis=1)[:, 0]
    positions = offsets[expert_flat] + rank  # (T,)

    ends = jnp.cumsum(padded)  # (E,)
    tile_start = jnp.arange(m_tiles, dtype=ends.dtype) * tile_m
    tile_group = jnp.clip(
        jnp.searchsorted(ends, tile_start, side="right"), 0, E - 1
    ).astype(jnp.int32)
    pad_sizes = jnp.concatenate([
        padded[:-1],
        jnp.asarray([M_pad], padded.dtype) - jnp.sum(padded[:-1])[None],
    ]).astype(jnp.int32)
    return positions.astype(jnp.int32), tile_group, pad_sizes, M_pad
