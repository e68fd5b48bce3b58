"""Grouped W8A16 GEMM: ``out[r] = x[r] @ dequant(codes[g(r)], scales[g(r)])``.

The routed experts of a served MoE model, weight-only quantized: what
``grouped_matmul`` is to bf16 experts and ``mixed_gemm`` to one dense
projection.  Rows arrive in the tile-aligned grouped layout
(``grouped_matmul.tile_aligned_layout``): every M tile belongs to one expert,
and a scalar-prefetched ``tile_group`` steers the tile's code and scale
blocks to that expert's.  The grid is ``mixed_gemm``'s, (M tiles, N tiles,
K tiles) with a float32 accumulator in VMEM that the first K tile zeroes and
the last writes out, the body is ``mixed_gemm``'s too
(``mixed_gemm.dequantize_walk``: one quantization group and one column chunk
at a time, bf16 into the MXU), and so is the tile: ``pick_gemm_tiles`` with
the caller's ``tile_m`` for ``tm`` (``moe/dropless.moe_tile_m``) gives the
widest ``tn`` and as many whole groups of K as make a step about 2 MB of
codes.  An expert of 2 MB (OLMoE 2048 x 1024, Mellum2 2304 x 896) is one
step; Nemotron-3's 2688 x 1920 is all of N in three K tiles, GLM-5.2's
6144 x 2048 in six.  Measured on the chip (``scripts/grouped_gemm_alone.py``,
PERF.md section 5): width is what a tile must have (640 columns stream a
fifth slower than 896 or more), depth from 640 rows on makes no difference
while the codes' HBM time bounds the call.  The scale block is the tile's
whole K column ``(K / group, tn)`` indexed at the K tile's first group: the
stored ``(…, K / group, N)`` array is read as it lies, with no reshaped copy
of the scales.

Three things differ from the dense kernel, all because experts are many and
small:

* **Tiles past the rows are skipped, and fetch nothing.**  The layout
  always holds ``num_experts`` spare tiles; ``used_tiles``
  (scalar-prefetched) tells the body where the rows end.  A skipped step
  computes nothing and names, for every operand and for the output, the
  blocks of the LAST STEP THAT HAD ROWS (``live_step``: M tile, N tile and
  K tile all held), so the pipeline moves nothing for it; its output rows
  are never written and never read.
* **An expert's codes are fetched once a tile of its rows.**  Consecutive M
  tiles of one expert name the same blocks only where the expert is one
  grid step (OLMoE, Mellum2); a larger expert is fetched again for each M
  tile its rows fill.  ``moe_tile_m`` sizes tiles at twice the mean rows an
  expert gets, so under near-uniform routing an expert is one tile (at 128
  rows a tile its matmuls take about as long as the fetch).  A trace shows
  where that fails: the ring event's ``steps_per_expert`` above 1 beside a
  step whose ``moe_rows_max`` is above ``tile_m``.
* **The layer is an index, not a slice.**  Codes and scales may be the whole
  stack ``(L, E, K, N)`` with ``layer`` scalar-prefetched: a ``pallas_call``
  cannot fuse a slice of its operand, so a layer scan that sliced the experts
  would write and read one layer's codes (0.4 GB for OLMoE) before each call.

int8 codes on rows that tile run the kernel whatever the expert's size;
other widths of code and rows that do not tile fall back to
dequantize-then-``ragged_dot`` with ``backend.warn_fallback``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend
from .mixed_gemm import (_VMEM_LIMIT, GemmTiles, QuantizedWeight,
                         dequantize_gemm_weight, dequantize_walk,
                         layer_of_stack, pick_gemm_tiles)


def pick_grouped_tiles(rows: int, tile_m: int, k: int, n: int, bits: int,
                       group: int, x_itemsize: int = 2
                       ) -> Optional[GemmTiles]:
    """``pick_gemm_tiles`` for ``rows`` laid out in M tiles of ``tile_m``:
    the dense rule's int8 tile (the widest ``tn``, as many whole groups of K
    as make a step about 2 MB of codes) on rows that tile, or None.  (An
    expert width that is no multiple of 128, such as 1856 = 29 x 64, has no
    lane-aligned ``tn``: the quantizer stores such a width zero-padded to
    the next multiple, ``inference/quantization.py``.)"""
    if bits != 8 or rows % tile_m or tile_m % 16:
        return None
    return pick_gemm_tiles(rows, k, n, bits, group, x_itemsize, tm=tile_m)


def _kernel(tile_group_ref, used_ref, layer_ref, x_ref, c_ref, s_ref, o_ref,
            acc_ref, *, group: int):
    """One (tile_m, tn) output tile's step over a k-tile: the rows of one
    expert against ``c_ref (tk, tn)`` of its codes and the scales of the
    tile's whole K column ``s_ref (K / group, tn)``."""
    del tile_group_ref, layer_ref  # the index maps read them
    kk, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(pl.program_id(0) < used_ref[0])
    def _compute():
        dequantize_walk(x_ref, c_ref, s_ref, o_ref, acc_ref, kk, nk, bits=8,
                        group=group)


def live_step(i, j, kk, used, nj: int, nk: int):
    """Grid step ``(i, j, kk)`` of M, N and K tiles, or for an M tile past
    the ``used`` ones the last step that had rows, ``(used - 1, nj - 1,
    nk - 1)``: a skipped step names the blocks the step before it named, so
    the pipeline fetches nothing for it."""
    on = i < used
    return (jnp.where(on, i, jnp.maximum(used - 1, 0)),
            jnp.where(on, j, nj - 1), jnp.where(on, kk, nk - 1))


def _grouped_pallas(x, codes, scales, tile_group, used_tiles, layer,
                    tiles: GemmTiles, group: int):
    """``codes (L, E, K, N)``, ``scales (L, E, K / group, N)``."""
    M, K = x.shape
    N = codes.shape[-1]
    tm, tn, tk = tiles.tm, tiles.tn, tiles.tk
    nj, nk = N // tn, K // tk

    def at(block):
        """The index map of an operand whose block a LIVE step ``(i, j, kk)``
        names ``block(i, j, kk, tile_group, layer)``."""
        def index_map(i, j, kk, tg, used, lay):
            i, j, kk = live_step(i, j, kk, used[0], nj, nk)
            return block(i, j, kk, tg, lay[0])
        return index_map

    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm, nj, nk),
            in_specs=[
                pl.BlockSpec((tm, tk), at(lambda i, j, kk, tg, lay: (i, kk))),
                pl.BlockSpec((None, None, tk, tn),
                             at(lambda i, j, kk, tg, lay: (lay, tg[i], kk, j))),
                # the scales of the tile's whole K column, as they are stored:
                # the block does not move along kk, and no reshaped copy of
                # the scales exists
                pl.BlockSpec((None, None, K // group, tn),
                             at(lambda i, j, kk, tg, lay: (lay, tg[i], 0, j))),
            ],
            # a skipped step keeps the last live tile's block, which is
            # written once it is left: no tile of rows that nobody reads
            # goes to HBM
            out_specs=pl.BlockSpec((tm, tn),
                                   at(lambda i, j, kk, tg, lay: (i, j))),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=backend.interpret(),
        name="grouped_mixed_gemm",
    )(tile_group, used_tiles, layer, x, codes, scales)


def grouped_mixed_gemm(x: jax.Array, qw: QuantizedWeight,
                       tile_group: jax.Array,
                       padded_group_sizes: jax.Array,
                       used_tiles: jax.Array, *, tile_m: int,
                       layer: Optional[jax.Array] = None) -> jax.Array:
    """``out[r] = x[r] @ dequant(qw[layer, tile_group[r // tile_m]])``.

    ``x (M, K)``: rows in the tile-aligned layout, M a multiple of
    ``tile_m``; ``qw``: int8 codes ``(E, K, N)``, or the layer stack
    ``(L, E, K, N)`` with ``layer`` an int32 scalar; ``tile_group
    (M / tile_m,)``: the expert of each tile; ``padded_group_sizes (E,)``:
    the layout's rows per expert (the fallback's ``ragged_dot`` needs them);
    ``used_tiles``: int32 scalar, tiles that hold rows (the rest are
    skipped).  Rows past an expert's real ones are the layout's zeros."""
    stacked = qw.codes.ndim == 4
    if stacked != (layer is not None):
        raise ValueError(
            f"grouped_mixed_gemm: codes {qw.codes.shape} "
            f"{'need' if stacked else 'take no'} layer index")
    M, K = x.shape
    E, Kw, N = qw.codes.shape[-3:]
    if K != Kw or K != qw.k_features:
        raise ValueError(f"x K={K} != weight K={qw.k_features}")
    tiles = pick_grouped_tiles(M, tile_m, K, N, qw.bits, qw.group,
                               x.dtype.itemsize)
    # chosen once per shape, while the caller's program is traced
    tracer.add_event("kernel/grouped_mixed_gemm_tiles", attrs={
        "e": E, "k": K, "n": N, "rows": M, "tile_m": tile_m,
        **({"tn": tiles.tn, "tk": tiles.tk, "k_tiles": K // tiles.tk,
            "m_tiles": M // tile_m,
            "steps_per_expert": (N // tiles.tn) * (K // tiles.tk),
            "grid_steps": tiles.grid_steps,
            "code_bytes_per_step": tiles.code_bytes_per_step}
           if tiles else {"fallback": 1})})
    if tiles is None:
        backend.warn_fallback(
            "grouped_mixed_gemm", f"bits={qw.bits}, M={M}, tile_m={tile_m}, "
            f"K={K}, N={N}, group={qw.group} do not tile")
        w = dequantize_gemm_weight(layer_of_stack(qw, layer)).astype(x.dtype)
        return jax.lax.ragged_dot(x, w, padded_group_sizes)
    codes, scales = qw.codes, qw.scales
    if layer is None:
        codes, scales, layer = codes[None], scales[None], jnp.int32(0)
    return _grouped_pallas(
        x, codes, scales, tile_group,
        jnp.reshape(used_tiles, (1,)).astype(jnp.int32),
        jnp.reshape(layer, (1,)).astype(jnp.int32), tiles, qw.group)
