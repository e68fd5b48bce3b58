"""Grouped W8A16 GEMM: ``out[r] = x[r] @ dequant(codes[g(r)], scales[g(r)])``.

The routed experts of a served MoE model, weight-only quantized: what
``grouped_matmul`` is to bf16 experts and ``mixed_gemm`` to one dense
projection.  Rows arrive in the tile-aligned grouped layout
(``grouped_matmul.tile_aligned_layout``): every M tile belongs to one expert,
and a scalar-prefetched ``tile_group`` steers the tile's code and scale
blocks to that expert's, so the body is ``mixed_gemm``'s dequantize walk (one
quantization group and one column chunk at a time, bf16 into the MXU, f32
accumulation) over one expert's matrix.

Three things differ from the dense kernel, all because experts are many and
small:

* **A grid step holds all of K.**  An expert matrix is a couple of megabytes
  (OLMoE: 2048 x 1024 = 2 MB of int8 codes), the size ``pick_gemm_tiles``
  wants a step to move, so the grid is (M tiles, N tiles) with no K axis.
  The scale block is then ``(K / group, tn)`` with its first dimension whole:
  the stored ``(…, K / group, N)`` array is read as it lies, with no reshaped
  copy of the scales.  Consecutive M tiles of one expert name the same
  blocks, so the pipeline fetches an expert's codes once however many tiles
  its rows fill.
* **Tiles past the rows are skipped.**  The layout always holds
  ``num_experts`` spare tiles; ``used_tiles`` (scalar-prefetched) tells the
  body where the rows end.  A skipped tile fetches nothing new (its block
  index is the last expert's) and computes nothing; its output rows are
  never read.
* **The layer is an index, not a slice.**  Codes and scales may be the whole
  stack ``(L, E, K, N)`` with ``layer`` scalar-prefetched: a ``pallas_call``
  cannot fuse a slice of its operand, so a layer scan that sliced the experts
  would write and read one layer's codes (0.4 GB for OLMoE) before each call.

``tile_m`` is the caller's (``moe/dropless.moe_tile_m``); ``tn`` comes from
``pick_gemm_tiles`` called with that ``tm``.  Shapes that do not tile fall
back to dequantize-then-``ragged_dot`` with ``backend.warn_fallback``.
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
                         column_chunks, dequantize_gemm_weight,
                         layer_of_stack, pick_gemm_tiles)


def pick_grouped_tiles(rows: int, tile_m: int, k: int, n: int, bits: int,
                       group: int, x_itemsize: int = 2
                       ) -> Optional[GemmTiles]:
    """``pick_gemm_tiles`` for ``rows`` laid out in M tiles of ``tile_m``:
    the widest int8 tile that holds all of K (see the module text) on rows
    that tile, or None.  (An expert width that is no multiple of 128, such
    as 1856 = 29 x 64, has no lane-aligned ``tn``: the quantizer stores such
    a width zero-padded to the next multiple, ``inference/quantization.py``.)"""
    if bits != 8 or rows % tile_m or tile_m % 16:
        return None
    return pick_gemm_tiles(rows, k, n, bits, group, x_itemsize, tm=tile_m,
                           whole_k=True)


def _kernel(tile_group_ref, used_ref, layer_ref, x_ref, c_ref, s_ref, o_ref,
            acc_ref, *, group: int):
    """One (tile_m, tn) output tile: the rows of one expert against all of
    K of its codes ``c_ref (K, tn)`` and scales ``s_ref (K / group, tn)``."""
    del tile_group_ref, layer_ref  # the index maps read them

    @pl.when(pl.program_id(0) < used_ref[0])
    def _compute():
        tn = o_ref.shape[1]
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # static loops: every slice is a static, tile-aligned window
        for gi in range(s_ref.shape[0]):
            x = x_ref[:, gi * group:(gi + 1) * group].astype(jnp.bfloat16)
            for cols in column_chunks(tn):
                c = c_ref[gi * group:(gi + 1) * group, cols]
                w = (c.astype(jnp.float32) * s_ref[gi:gi + 1, cols]
                     ).astype(jnp.bfloat16)
                acc_ref[:, cols] += jax.lax.dot_general(
                    x, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _grouped_pallas(x, codes, scales, tile_group, used_tiles, layer,
                    tiles: GemmTiles, group: int):
    """``codes (L, E, K, N)``, ``scales (L, E, K / group, N)``."""
    M, K = x.shape
    N = codes.shape[-1]
    tm, tn = tiles.tm, tiles.tn
    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // tm, N // tn),
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, j, tg, used, lay: (i, 0)),
                pl.BlockSpec((None, None, K, tn),
                             lambda i, j, tg, used, lay: (lay[0], tg[i], 0, j)),
                pl.BlockSpec((None, None, K // group, tn),
                             lambda i, j, tg, used, lay: (lay[0], tg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda i, j, tg, used, lay: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
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
        **({"tn": tiles.tn, "tk": tiles.tk,
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
