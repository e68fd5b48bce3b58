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

The backward is two more kernels of the same layout: ``grouped_matmul_dlhs``
(``g @ rhs[e]^T``, the experts read as they are stored) and
``grouped_matmul_drhs`` (``lhs_e^T @ g_e``, an expert's consecutive tiles
summed in VMEM).  A layout made for a SHARE of the experts holds tiles for
every assignment and fills an eighth of them: ``used_tiles`` lets all three
kernels skip the rest and fetch nothing for them.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend

#: scoped VMEM a call may take: the widest blocks here (a whole K or N of an
#: expert width that has no divisor of 256 or more, float32 accumulator
#: beside them) are about 14 MB double-buffered
_VMEM_LIMIT = 64 * 1024 * 1024


def _pick_tile(n: int, cap: int) -> int:
    """The block of a K or N dimension ``n``: the largest power-of-two
    multiple of 128 up to ``cap`` that divides it; where that is under 256
    (an expert width such as 1408 = 11 x 128) and ``n`` is at most 2048, all
    of ``n``, because steps of 128 columns leave the MXU mostly waiting; 0
    when ``n`` is no multiple of 128."""
    tile = cap
    while tile >= 128 and n % tile:
        tile //= 2
    if tile < 128:
        return 0
    if tile < 256 and n <= 2048:
        return n
    return tile


def _use_pallas(M: int, K: int, N: int, tile_m: int) -> bool:
    if backend.interpret():  # the host CPU runs ragged_dot, by design
        return False
    # Mosaic tiling: K and N 128-aligned lanes, the M tile whole packed bf16
    # sublane tiles (16 rows: a decode step's few rows an expert)
    ok = (M % tile_m == 0 and _pick_tile(K, 1024) > 0
          and _pick_tile(N, 1024) > 0 and tile_m % 16 == 0)
    if not ok:
        backend.warn_fallback(
            "grouped_matmul", f"M={M}, K={K}, N={N} do not tile into "
            f"tile_m={tile_m} (16-row, 128-lane tiles)")
    return ok


def _live(i, j, kk, used, nj: int, nk: int):
    """Grid step ``(i, j, kk)``, or for an M tile past the ``used`` ones the
    last step that had rows (``grouped_mixed_gemm.live_step``'s rule): a
    skipped step names the blocks the step before it named, so the pipeline
    moves nothing for it."""
    on = i < used
    return (jnp.where(on, i, jnp.maximum(used - 1, 0)),
            jnp.where(on, j, nj - 1), jnp.where(on, kk, nk - 1))


def _gmm_kernel(tile_group_ref, used_ref, lhs_ref, rhs_ref, out_ref, acc_ref,
                *, nk: int, transpose_rhs: bool):
    """One (tile_m, tile_n) output tile's step over a K tile; with
    ``transpose_rhs`` the expert's block is ``(tile_n, tile_k)`` as it is
    stored, and the last dimension of both is contracted."""
    del tile_group_ref  # the index maps read it
    i, kk = pl.program_id(0), pl.program_id(2)

    @pl.when(i < used_ref[0])
    def _compute():
        @pl.when(kk == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot_general(
            lhs_ref[:], rhs_ref[0],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(kk == nk - 1)
        def _flush():
            out_ref[:] = acc_ref[:].astype(out_ref.dtype)


def _gmm_pallas(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                used_tiles: jax.Array, tile_m: int,
                transpose_rhs: bool = False) -> jax.Array:
    """``rhs (E, K, N)``, or with ``transpose_rhs`` ``(E, N, K)`` read as it
    lies (the backward's ``g @ rhs^T`` makes no transposed copy of the
    experts)."""
    M, K = lhs.shape
    N = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tile_k, tile_n = _pick_tile(K, 1024), _pick_tile(N, 1024)
    nj, nk = N // tile_n, K // tile_k

    def at(block):
        def index_map(i, j, kk, tg, used):
            i, j, kk = _live(i, j, kk, used[0], nj, nk)
            return block(i, j, kk, tg)
        return index_map

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((1, tile_n, tile_k),
                                at(lambda i, j, kk, tg: (tg[i], j, kk)))
    else:
        rhs_spec = pl.BlockSpec((1, tile_k, tile_n),
                                at(lambda i, j, kk, tg: (tg[i], kk, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tile_m, nj, nk),  # k innermost: sequential accum
            in_specs=[
                pl.BlockSpec((tile_m, tile_k),
                             at(lambda i, j, kk, tg: (i, kk))),
                rhs_spec,
            ],
            # a skipped step keeps the last live tile's block: no tile of
            # rows that nobody reads goes to HBM
            out_specs=pl.BlockSpec((tile_m, tile_n),
                                   at(lambda i, j, kk, tg: (i, j))),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=backend.interpret(),
        name="grouped_matmul_dlhs" if transpose_rhs else "grouped_matmul",
    )(tile_group, used_tiles, lhs, rhs)


def _drhs_kernel(tile_group_ref, used_ref, lhs_ref, g_ref, out_ref, acc_ref):
    """One M tile's ``lhs^T @ g`` added to its expert's ``(tile_k, tile_n)``
    block: the tiles of one expert are consecutive, so the accumulator is
    zeroed at an expert's first tile and written at its last."""
    i = pl.program_id(2)
    used = used_ref[0]
    last = jnp.maximum(used - 1, 0)

    @pl.when(i < used)
    def _compute():
        here = tile_group_ref[i]
        before = tile_group_ref[jnp.maximum(i - 1, 0)]
        after = tile_group_ref[jnp.minimum(i + 1, last)]

        @pl.when((i == 0) | (before != here))
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        acc_ref[:] += jax.lax.dot_general(
            lhs_ref[:], g_ref[:], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when((i == last) | (after != here))
        def _flush():
            out_ref[0] = acc_ref[:].astype(out_ref.dtype)


def _drhs_pallas(lhs: jax.Array, g: jax.Array, tile_group: jax.Array,
                 used_tiles: jax.Array, num_groups: int, tile_m: int,
                 dtype) -> jax.Array:
    """``out[e] = sum over the rows r of expert e of lhs[r]^T g[r]``, ``(E, K,
    N)``: the weight gradient of ``grouped_matmul``.  An expert without a
    tile is never visited and its block never written: the caller zeroes
    it."""
    M, K = lhs.shape
    N = g.shape[1]
    tile_k, tile_n = _pick_tile(K, 512), _pick_tile(N, 1024)

    def row(i, used):
        return jnp.where(i < used, i, jnp.maximum(used - 1, 0))

    return pl.pallas_call(
        _drhs_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tile_k, N // tile_n, M // tile_m),  # rows innermost
            in_specs=[
                pl.BlockSpec((tile_m, tile_k),
                             lambda kb, nb, i, tg, used: (row(i, used[0]),
                                                          kb)),
                pl.BlockSpec((tile_m, tile_n),
                             lambda kb, nb, i, tg, used: (row(i, used[0]),
                                                          nb)),
            ],
            out_specs=pl.BlockSpec(
                (1, tile_k, tile_n),
                lambda kb, nb, i, tg, used: (tg[row(i, used[0])], kb, nb)),
            scratch_shapes=[pltpu.VMEM((tile_k, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((num_groups, K, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=backend.interpret(),
        name="grouped_matmul_drhs",
    )(tile_group, used_tiles, lhs, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gmm(lhs, rhs, tile_group, padded_group_sizes, used_tiles, tile_m):
    return _gmm_pallas(lhs, rhs, tile_group, used_tiles, tile_m)


def _gmm_fwd(lhs, rhs, tile_group, padded_group_sizes, used_tiles, tile_m):
    out = _gmm_pallas(lhs, rhs, tile_group, used_tiles, tile_m)
    return out, (lhs, rhs, tile_group, padded_group_sizes, used_tiles)


def _gmm_bwd(tile_m, res, g):
    """dlhs[r] = g[r] @ rhs[g(r)]^T, the same kernel over the experts as they
    are stored; drhs[e] = lhs_e^T @ g_e, one pass over the rows.  Both skip
    the tiles past ``used_tiles``, as the forward does: rows there are never
    written and never read."""
    lhs, rhs, tile_group, padded_group_sizes, used_tiles = res
    g = g.astype(lhs.dtype)
    dlhs = _gmm_pallas(g, rhs, tile_group, used_tiles, tile_m,
                       transpose_rhs=True)
    drhs = _drhs_pallas(lhs, g, tile_group, used_tiles, rhs.shape[0], tile_m,
                        rhs.dtype)
    drhs = jnp.where((padded_group_sizes > 0)[:, None, None], drhs, 0)
    return dlhs, drhs, None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, tile_group: jax.Array,
                   padded_group_sizes: jax.Array, tile_m: int = 512,
                   used_tiles: Optional[jax.Array] = None) -> jax.Array:
    """``out[r] = lhs[r] @ rhs[tile_group[r // tile_m]]``.

    ``lhs``: (M, K) tile-aligned grouped rows (M multiple of tile_m);
    ``rhs``: (E, K, N); ``tile_group``: (M // tile_m,) int32 expert per tile;
    ``padded_group_sizes``: (E,) row counts of the padded layout (the
    ragged_dot fallback's groups, and which experts have a tile at all);
    ``used_tiles``: int32 scalar, the tiles that hold rows (None: all).  Tiles
    past it are skipped in the forward and in both backward kernels: their
    output rows are NEVER WRITTEN, so a caller reads them through a mask
    (``jnp.where``, never a product).  Differentiable: dlhs through the same
    kernel, drhs through ``grouped_matmul_drhs``, float32 sums in both.
    """
    M, K = lhs.shape
    E, K2, N = rhs.shape
    assert K == K2, (lhs.shape, rhs.shape)
    usable = _use_pallas(M, K, N, tile_m)
    # chosen once per shape, while the caller's program is traced
    tracer.add_event("kernel/grouped_matmul_tiles", attrs={
        "e": E, "k": K, "n": N, "rows": M, "tile_m": tile_m,
        **({"tile_n": _pick_tile(N, 1024), "tile_k": _pick_tile(K, 1024)}
           if usable else
           {"xla": 1} if backend.interpret() else {"fallback": 1})})
    if not usable:
        return jax.lax.ragged_dot(lhs, rhs, padded_group_sizes)
    if used_tiles is None:
        used_tiles = jnp.int32(M // tile_m)
    return _gmm(lhs, rhs, tile_group, padded_group_sizes,
                jnp.reshape(used_tiles, (1,)).astype(jnp.int32), tile_m)


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


def layout_sources(expert_flat: jax.Array, counts: jax.Array,
                   tile_group: jax.Array, pad_sizes: jax.Array,
                   tile_m: int) -> jax.Array:
    """The inverse of ``tile_aligned_layout``'s ``positions``: ``src
    (M_pad,)`` int32, the assignment that lies at each row of the layout, -1
    where none does.  Made WITHOUT a scatter (the TPU walks a scatter of T
    indices one after another, as it walks a scatter of rows) and without a
    gather of single indices (walked alike: 87 us for 12,288 of them): a
    stable ``argsort`` of the assignments' groups is expert order, and a
    tile's rows are ``tile_m`` CONSECUTIVE entries of it, from the group's
    unpadded start plus the rank of the tile's first row (its offset from
    the group's padded start), valid while the rank is under the group's
    count: one slice a tile.  The groups are those of ``counts`` and
    ``pad_sizes`` (``tile_group`` names no other): an assignment of a later
    group (a share's rows that live elsewhere) has no row here."""
    T = expert_flat.shape[0]
    order = jnp.pad(jnp.argsort(expert_flat, stable=True), (0, tile_m))

    def of_tile(by_group):
        return by_group.at[tile_group].get(mode="promise_in_bounds")

    tile_start = jnp.arange(tile_group.shape[0], dtype=jnp.int32) * tile_m
    rank0 = tile_start - of_tile(jnp.cumsum(pad_sizes) - pad_sizes)
    first = jnp.clip(of_tile(jnp.cumsum(counts) - counts) + rank0, 0, T)
    rows = jax.vmap(lambda at: jax.lax.dynamic_slice(order, (at,), (tile_m,))
                    )(first)
    rank = rank0[:, None] + jnp.arange(tile_m, dtype=jnp.int32)[None, :]
    src = jnp.where(rank < of_tile(counts)[:, None], rows, -1)
    return src.reshape(-1).astype(jnp.int32)
