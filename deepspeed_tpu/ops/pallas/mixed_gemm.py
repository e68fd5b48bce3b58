"""Mixed-precision GEMM: quantized-weight × high-precision-activation matmul.

Reference: the CUTLASS mixed GEMM family backing weight-quantized inference
(``inference/v2/kernels/core_ops/cutlass_ops/mixed_gemm/``,
``deepspeed/inference/quantization`` W8A16/W4A16 paths). There the weight
stays int8/int4 in HBM and dequantizes in registers inside the GEMM.

TPU-native design: a Pallas kernel with grid (M/tm, N/tn, K/tk).  One grid
step streams a ``(tk, tn)`` tile of codes out of HBM (the scales of the
tile's whole K column, ``(K / group, tn)``, arrive once a column of tiles)
and walks the tile one quantization group and one column chunk at a time:
dequantize in VMEM with that group's scale row, feed the MXU in bfloat16,
accumulate in f32.  A tile is therefore several groups deep and thousands
of columns wide: a grid step costs about a third of a microsecond whatever
it moves, so it has to move megabytes.  ``pick_gemm_tiles`` is the one place that chooses ``(tm, tn,
tk)``, from the shapes the call can see: the whole padded M up to 512 rows
(the weights are read and dequantized once), the widest ``tn`` a VMEM budget
holds, and a ``tk`` of as many whole groups as make a step about 2 MB of
int8 codes.  int4 packs two K-rows per byte (codes shape (K/2, N)) and
unpacks with two arithmetic shifts in-kernel; fp6 packs four K-rows in three
byte-rows.  HBM traffic for the weight is K·N bytes (int8) or K·N/2 (int4)
instead of 2·K·N (bf16) — the same bandwidth win the reference gets, which
is what matters for memory-bound decode.

``QuantizedWeight`` is a pytree node (static bits/group), so stacked
per-layer weights shard under GSPMD like any other param leaf, and a
``lax.scan`` over them hands its body one layer's node.  That slice is not
free to this kernel: a ``pallas_call`` is a custom call, XLA cannot fuse the
slice of an operand into it, so the layer's codes are written out and read
again before every call (58.7 MB for one MLP projection of Mistral-7B: the
copies cost 2.2 times the GEMMs they fed, PERF.md S2a).  So the layer is an
index, not a slice: ``mixed_gemm(x, stack, layer)`` takes the codes ``(L, K,
N)`` and scales ``(L, K / group, N)`` whole with ``layer`` scalar-prefetched,
and the block index maps read the layer's tiles where they lie.  A 2-D weight
is the stack of one layer.  ``LayerOf`` is what a layer loop hands ``_lin`` in
place of the slice (``inference/v2/programs.py:serving_layers``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from ..quantizer import (minifloat_decode, minifloat_encode, minifloat_max,
                         pack_fp6, pack_int4, unpack_fp6, unpack_int4)
from . import backend
from .flash_attention import aligned_divisor


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedWeight:
    """Weight codes + per-(K-group, N) scales for ``x @ W``.

    codes: int8, (..., K, N) for bits=8, (..., K/2, N) for bits=4, or
    uint8 (..., 3K/4, N) for bits=6 (FP6 e3m2, 4 K-rows per 3 byte-rows)
    scales: f32, (..., K/group, N)
    """
    codes: jax.Array
    scales: jax.Array
    bits: int
    group: int
    k: int = 0  # true K (int4/fp6 pad K to the pack multiple)

    def __post_init__(self):
        if self.k == 0:
            if self.bits != 8:
                # int4/fp6 pack K with padding, so the code-row count only
                # bounds the true K (e.g. fp6 K=5 packs like K=8): inferring
                # would silently report the padded K
                raise ValueError(
                    f"QuantizedWeight(bits={self.bits}) requires the true K "
                    f"via k= (codes rows give only the padded K)")
            self.k = self.codes.shape[-2]

    @property
    def k_features(self) -> int:
        return self.k

    @property
    def out_features(self) -> int:
        return self.codes.shape[-1]

    def tree_flatten(self):
        return (self.codes, self.scales), (self.bits, self.group, self.k)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(leaves[0], leaves[1], *aux)


@dataclasses.dataclass(frozen=True)
class LayerOf:
    """One layer of a stacked :class:`QuantizedWeight` that a layer loop kept
    whole: the stack and the layer's index (an int32 scalar, traced), in the
    place of the slice a ``lax.scan`` over the stack would have copied."""
    stack: QuantizedWeight
    layer: jax.Array


def quantize_gemm_weight(w: jax.Array, bits: int = 8,
                         group: int = 256) -> QuantizedWeight:
    """Symmetric per-(K-group, column) quantization of ``w`` (..., K, N).
    ``bits=6`` stores FP6 e3m2 codes (reference: FP6 cuda_linear /
    fp_quantizer) — scales map each group's absmax to the fp6 max (28)."""
    assert bits in (8, 6, 4), bits
    *lead, K, N = w.shape
    if K % group != 0:
        if K > group and K % 128 == 0:
            # a width the kernels tile (896 = 7 x 128) under a group that
            # does not divide it: shrinking the group silently (to 224) would
            # send every GEMM over this width to the XLA fallback
            raise ValueError(
                f"quantize group {group} does not divide the width K = {K} "
                f"of a {tuple(w.shape)} weight; use a group that does "
                f"(128 divides every lane-aligned width)")
        # shrink the group to a divisor (odd K still works)
        group = aligned_divisor(K, group, 1) or K
    wf = w.astype(jnp.float32).reshape(*lead, K // group, group, N)
    if bits == 6:
        scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / minifloat_max(3, 2)
        scale = jnp.where(scale == 0.0, 1.0, scale)
        codes = minifloat_encode(wf / scale, 3, 2).reshape(*lead, K, N)
        if K % 4:  # pad zero K-rows to the 4-per-3-bytes pack multiple
            pad = [(0, 0)] * len(lead) + [(0, (-K) % 4), (0, 0)]
            codes = jnp.pad(codes, pad)
        # pack along K: move K last, pack, move back
        codes = jnp.moveaxis(pack_fp6(jnp.moveaxis(codes, -2, -1)), -1, -2)
        return QuantizedWeight(codes, scale[..., 0, :], bits, group, k=K)
    qmax = (1 << (bits - 1)) - 1
    scale = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / qmax
    scale = jnp.where(scale == 0.0, 1.0, scale)
    codes = jnp.clip(jnp.round(wf / scale), -qmax - 1, qmax)
    codes = codes.reshape(*lead, K, N).astype(jnp.int8)
    if bits == 4:
        if K % 2:  # pad a zero K-row so two codes always pack per byte
            pad = [(0, 0)] * len(lead) + [(0, 1), (0, 0)]
            codes = jnp.pad(codes, pad)
        codes = pack_int4(codes[..., 0::2, :], codes[..., 1::2, :])
    return QuantizedWeight(codes, scale[..., 0, :], bits, group, k=K)


# ---------------------------------------------------------------------------
# tile selection: one function, from the shapes a call can see
# ---------------------------------------------------------------------------

#: Scoped VMEM a ``mixed_gemm`` call asks the compiler for, and what the
#: picker may plan inside it.  The default scoped limit (16 MiB) is what
#: binds a tile of megabytes; a v5e core has 128 MiB.  The picker counts its
#: pipelined buffers, the accumulator and one chunk's temporaries, and leaves
#: the other half of the limit to what the compiler adds (int4 / fp6 unpack).
_VMEM_LIMIT = 64 << 20
_VMEM_BUDGET = _VMEM_LIMIT // 2
#: Rows and columns of the widest output tile.  Up to 512 rows a call reads
#: its weights once; more rows take another pass per M tile.
_MAX_TM = 512
_MAX_TN = 4096
#: Weights a grid step dequantizes (2 MB of int8 codes, 2.4 us of HBM time
#: against a third of a microsecond a step), and the fewest grid steps a
#: call is cut into: the first tile's fetch overlaps nothing.
_TILE_WEIGHTS = 2 << 20
_MIN_STEPS = 4
#: Columns dequantized at a time inside a grid step: bounds the temporaries.
_CHUNK_N = 512


def column_chunks(tn: int):
    """The column windows a grid step dequantizes one at a time: ``_CHUNK_N``
    wide, the last as wide as what is left; a tail of one lane tile (128)
    joins the window before it (a tile 640 or 2688 wide: Mosaic has no
    one-tile load of a scale row at a dynamic row)."""
    edges = list(range(0, tn, _CHUNK_N)) + [tn]
    if len(edges) > 2 and edges[-1] - edges[-2] <= 128:
        del edges[-2]
    return [slice(a, b) for a, b in zip(edges, edges[1:])]


def _code_rows(k_rows: int, bits: int) -> int:
    """Byte rows of codes that hold ``k_rows`` rows of K: int8 1:1, int4 two
    codes a byte, fp6 four codes in three bytes."""
    return {8: k_rows, 4: k_rows // 2, 6: k_rows // 4 * 3}[bits]


@dataclasses.dataclass(frozen=True)
class GemmTiles:
    """One ``mixed_gemm`` call's tiling, as :func:`pick_gemm_tiles` chose it."""
    tm: int
    tn: int
    tk: int
    grid_steps: int
    code_bytes_per_step: int


@functools.lru_cache(maxsize=None)
def pick_gemm_tiles(m: int, k: int, n: int, bits: int, group: int,
                    x_itemsize: int = 2, tm: Optional[int] = None
                    ) -> Optional[GemmTiles]:
    """The ``(tm, tn, tk)`` tile of an ``(m, k) @ (k, n)`` mixed GEMM (``m``
    already padded to the sublane multiple), or None when the shapes do not
    tile (→ the dequantize-then-matmul fallback).

    ``tm`` is all of ``m`` up to 512 rows: one pass over the weights, no tile
    of them dequantized twice.  ``tn`` is the widest lane-aligned divisor of
    ``n`` (or a small ``n`` whole) whose buffers fit the VMEM budget: a tile's
    rows are then long contiguous runs of HBM (tiles of equal bytes measured
    faster wide than deep, PERF.md).  ``tk`` is as many whole quantization
    groups, dividing ``k``, as keep a step at ``_TILE_WEIGHTS`` weights and
    the call at ``_MIN_STEPS`` steps or more.

    A caller whose rows already lie in M tiles (the grouped GEMM of MoE
    experts) passes its own ``tm``, a divisor of ``m``; the rest follows."""
    # int4 packs two codes per byte (group must be even); fp6 packs 4 K-rows
    # per 3 byte-rows (group must divide by 4, and the byte-row tile must be
    # sublane-aligned); int8 has no pack constraint
    if (k % group
            or (bits == 4 and group % 2)
            or (bits == 6 and (group % 4 or _code_rows(group, 6) % 8))
            or (group % 128 and group != k)):
        return None
    if tm is None:
        tm = m if m <= _MAX_TM else aligned_divisor(m, _MAX_TM)
    elif m % tm:
        return None
    tns = [d for d in range(min(n, _MAX_TN) // 128 * 128, 0, -128)
           if n % d == 0]
    if n <= 256 and n % 128:
        tns.insert(0, n)  # a full-dim block is legal whatever its width
    if tm is None or not tns:
        return None
    groups = k // group
    for tn in tns:  # widest first
        g = max([g for g in range(1, groups + 1)
                 if groups % g == 0 and g * group * tn <= _TILE_WEIGHTS
                 and (m // tm) * (n // tn) * (groups // g) >= _MIN_STEPS],
                default=1)
        tk = g * group
        codes = _code_rows(tk, bits) * tn
        chunk = min(tn, _CHUNK_N)
        vmem = (2 * codes + 2 * g * 8 * tn * 4  # a scale row pads to 8
                + 2 * tm * tk * x_itemsize + 2 * tm * tn * x_itemsize
                + tm * tn * 4
                # a group's chunk: widened codes, f32 product, bf16 MXU
                # operand, the partial product
                + group * chunk * 10 + tm * chunk * 4)
        if vmem <= _VMEM_BUDGET or tn == tns[-1]:
            return GemmTiles(tm, tn, tk, (m // tm) * (n // tn) * (k // tk),
                             codes)


def _unpack_int4(c):
    # byte row r holds K-rows 2r (lo), 2r+1 (hi).  Widen first: the chip's
    # compiler has no shifts on int8 vectors.
    c = c.astype(jnp.int32)
    lo, hi = (c << 28) >> 28, c >> 4  # arithmetic shifts sign-extend
    tk2, tn = c.shape
    return jnp.stack([lo, hi], axis=1).reshape(tk2 * 2, tn)


def _unpack_decode_fp6(c):
    """(3k, tn) packed bytes → (4k, tn) decoded fp6 values (in-kernel:
    shifts + masks + an exact power-of-two bitcast, no table gather)."""
    rows, tn = c.shape
    # a strided slice of a value (``b[0::3]``) lowers to a gather, which
    # the chip's compiler refuses; a reshape and an index do not
    b = c.astype(jnp.int32).reshape(rows // 3, 3, tn)
    b0, b1, b2 = b[:, 0], b[:, 1], b[:, 2]
    c0 = b0 & 63
    c1 = ((b0 >> 6) & 3) | ((b1 & 15) << 2)
    c2 = ((b1 >> 4) & 15) | ((b2 & 3) << 4)
    c3 = (b2 >> 2) & 63
    codes = jnp.stack([c0, c1, c2, c3], axis=1).reshape(rows // 3 * 4, tn)
    return minifloat_decode(codes, 3, 2)


def dequantize_walk(x_ref, c_ref, s_ref, o_ref, acc_ref, kk, nk, *,
                    bits: int, group: int):
    """One (tm, tn) output tile's step over k-tile ``kk`` of ``nk``, the one
    body of the dense and the grouped kernel: ``acc_ref (tm, tn) += x_ref
    (tm, tk) @ dequant(c_ref)``, zeroed at the first k-tile and written to
    ``o_ref`` at the last.  ``c_ref`` holds the code rows of a k-tile of
    whole quantization groups, ``s_ref`` the scales of the tile's whole K
    column ``(K / group, tn)``.  One group and one column chunk at a time:
    dequantize with the group's scale row, bfloat16 into the MXU, float32
    into ``acc_ref``.  (The caller reads ``kk`` and ``nk`` off the grid at
    the kernel's top level: the interpreter has no ``program_id`` inside a
    ``pl.when``.)"""
    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    tn = acc_ref.shape[1]
    rows = _code_rows(group, bits)
    g = c_ref.shape[0] // rows  # groups a k-tile
    first_group = kk * g
    # static loops: every slice of the codes is a static, tile-aligned window
    for gi in range(g):
        x = x_ref[:, gi * group:(gi + 1) * group].astype(jnp.bfloat16)
        scale_row = pl.ds(first_group + gi, 1)
        for cols in column_chunks(tn):
            c = c_ref[gi * rows:(gi + 1) * rows, cols]
            if bits == 4:
                c = _unpack_int4(c)
            if bits == 6:
                c = _unpack_decode_fp6(c)
            w = (c.astype(jnp.float32) * s_ref[scale_row, cols]
                 ).astype(jnp.bfloat16)
            acc_ref[:, cols] += jax.lax.dot_general(
                x, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _mixed_gemm_kernel(lay_ref, x_ref, c_ref, s_ref, o_ref, acc_ref, *,
                       bits: int, group: int):
    """``dequantize_walk`` over codes (rows of g groups, tn) of the layer the
    index maps chose."""
    del lay_ref  # the index maps read it
    dequantize_walk(x_ref, c_ref, s_ref, o_ref, acc_ref, pl.program_id(2),
                    pl.num_programs(2), bits=bits, group=group)


def _gemm_pallas(x2: jax.Array, qw: QuantizedWeight, layer: jax.Array,
                 tiles: GemmTiles):
    """``qw``: the layer stack, codes ``(L, rows of K, N)`` and scales
    ``(L, K / group, N)``, read in place at ``layer``."""
    M, K = x2.shape
    N = qw.out_features
    tm, tn, tk = tiles.tm, tiles.tn, tiles.tk
    kernel = functools.partial(_mixed_gemm_kernel, bits=qw.bits,
                               group=qw.group)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M // tm, N // tn, K // tk),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda i, j, kk, lay: (i, kk)),
                pl.BlockSpec((None, _code_rows(tk, qw.bits), tn),
                             lambda i, j, kk, lay: (lay[0], kk, j)),
                # the scales of the tile's whole K column, as they are stored
                # (a full dimension is always a legal block): the block index
                # does not move along kk, so a column is fetched once, and no
                # reshaped copy of the scales exists
                pl.BlockSpec((None, K // qw.group, tn),
                             lambda i, j, kk, lay: (lay[0], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, lay: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=backend.interpret(),
        name="mixed_gemm",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), x2, qw.codes, qw.scales)


def layer_of_stack(qw: QuantizedWeight, layer: Optional[jax.Array]
                   ) -> QuantizedWeight:
    """Layer ``layer`` of a stacked weight as XLA slices it (what the
    fallbacks and the backward dequantize); ``qw`` itself without a layer."""
    return qw if layer is None else jax.tree.map(lambda a: a[layer], qw)


def dequantize_gemm_weight(qw: QuantizedWeight) -> jax.Array:
    codes = qw.codes
    if qw.bits == 6:
        codes = jnp.moveaxis(unpack_fp6(jnp.moveaxis(codes, -2, -1)), -1, -2)
        vals = minifloat_decode(codes, 3, 2)[..., :qw.k_features, :]
        *lead, K, N = vals.shape
        v = vals.reshape(*lead, K // qw.group, qw.group, N)
        return (v * qw.scales[..., :, None, :]).reshape(*lead, K, N)
    if qw.bits == 4:
        lo, hi = unpack_int4(codes)
        # interleave: byte row r holds K-rows 2r (lo nibble), 2r+1 (hi)
        codes = jnp.stack([lo, hi], axis=-2).reshape(
            *qw.codes.shape[:-2], 2 * qw.codes.shape[-2], qw.out_features)
        codes = codes[..., :qw.k_features, :]  # drop odd-K zero padding
    *lead, K, N = codes.shape
    w = codes.astype(jnp.float32).reshape(*lead, K // qw.group, qw.group, N)
    return (w * qw.scales[..., :, None, :]).reshape(*lead, K, N)


def _int8_gemm_kernel(xc_ref, xs_ref, c_ref, s_ref, o_ref, acc_ref):
    """W8A8: int8×int8 → int32 on the MXU per k-tile, rescaled into an f32
    accumulator by (activation row scale) ⊗ (weight column scale)."""
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    i32 = jax.lax.dot_general(
        xc_ref[:], c_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)  # (tm, tn)
    # xs_ref block is (1, tm, 1): k-group leads as a batch dim so the tile's
    # last two dims stay Mosaic-legal (see the x-scale spec below)
    acc_ref[:] += i32.astype(jnp.float32) * xs_ref[0] * s_ref[0]

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _flatten_pad(x: jax.Array):
    """Shared GEMM prologue: collapse lead dims; the rows that pad M to the
    sublane multiple.  Returns (x2, lead, M, pad_m)."""
    *lead, K = x.shape
    M = 1
    for d in lead:
        M *= d
    return x.reshape(M, K), lead, M, (-M) % 8


def quantize_activations_rowwise(x2: jax.Array, group: int
                                 ) -> Tuple[jax.Array, jax.Array]:
    """Per-(row, K-group) symmetric int8 quantization of (M, K) activations
    — the dynamic-activation half of W8A8 (reference ZeroQuant-style
    token-wise activation quantization)."""
    M, K = x2.shape
    xg = x2.astype(jnp.float32).reshape(M, K // group, group)
    scale = jnp.max(jnp.abs(xg), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale == 0.0, 1.0, scale)
    codes = jnp.clip(jnp.round(xg / scale), -128, 127).astype(jnp.int8)
    return codes.reshape(M, K), scale[..., 0]  # (M, K), (M, K/group)


def int8_gemm(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """W8A8 ``quant(x) @ dequant-free(qw)``: activations quantize per
    (token, K-group) at runtime, the matmul runs int8×int8→int32 on the MXU
    and rescales per tile — HALF the MXU-input bandwidth of W8A16 and the
    int8 matmul throughput of v5e (the ROADMAP "int8 matmul paths" lever).

    ``qw`` must be bits=8 per-layer (K, N) codes with x's K matching.
    Falls back to the dequantize oracle off the tiling envelope."""
    if qw.bits != 8:
        raise ValueError(f"int8_gemm needs bits=8 weights, got {qw.bits}")
    if qw.codes.ndim != 2:
        raise ValueError("int8_gemm wants per-layer (K, N) codes; got "
                         f"{qw.codes.shape} — slice stacked layers via scan")
    K = x.shape[-1]
    if K != qw.k_features:
        raise ValueError(
            f"x K={K} != weight K={qw.k_features} — a partial product "
            f"would be silently wrong")
    N = qw.out_features
    x2, lead, M, pad_m = _flatten_pad(x)
    tm = aligned_divisor(M + pad_m, 256)
    tn = aligned_divisor(N, 256, 128)
    # int8 MXU tiles want lane-aligned k-tiles; no group==K escape here —
    # a misaligned single tile would pass interpret mode and fail Mosaic
    usable = (tm is not None and tn is not None and K % qw.group == 0
              and qw.group % 128 == 0)
    if not usable:
        backend.warn_fallback(
            "int8_gemm", f"M={M}, K={K}, N={N}, group={qw.group} do not tile "
            f"(tm={tm}, tn={tn}; the k-tile must be a multiple of 128)")
        out = (x2 @ dequantize_gemm_weight(qw).astype(x2.dtype))
        return out.reshape(*lead, N)
    xp = jnp.pad(x2, ((0, pad_m), (0, 0))) if pad_m else x2
    codes, scales = quantize_activations_rowwise(xp, qw.group)
    tk = qw.group
    grid = ((M + pad_m) // tm, N // tn, K // tk)
    out = pl.pallas_call(
        _int8_gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            # x scales ride as (K/group, M, 1): the k-group axis LEADS as a
            # batch dim so the block's last two dims are (tm, 1=full) —
            # a (tm, 1) block over (M, K/group) would put an unaligned,
            # non-full tile in the lane dim and fail Mosaic on real TPUs
            pl.BlockSpec((1, tm, 1), lambda i, j, kk: (kk, i, 0)),
            pl.BlockSpec((tk, tn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1, tn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pad_m, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret(),
        name="int8_gemm",
    )(codes, scales.T[:, :, None], qw.codes, qw.scales[:, None, :])
    if pad_m:
        out = out[:M]
    return out.reshape(*lead, N)


def mixed_gemm(x: jax.Array, qw: QuantizedWeight,
               layer: Optional[jax.Array] = None) -> jax.Array:
    """``x @ dequant(qw)`` with in-kernel dequantization.

    ``x``: (..., K); ``qw``: codes ``(K, N)``, or the layer stack ``(L, K,
    N)`` with ``layer`` an int32 scalar, which the kernel reads in place (a
    slice of the stack would be copied before the call: module text).  Falls
    back to the XLA dequant+matmul of the one layer when shapes do not tile
    (also the numeric oracle for tests).
    """
    stacked = qw.codes.ndim == 3
    if qw.codes.ndim not in (2, 3) or stacked != (layer is not None):
        raise ValueError(
            f"mixed_gemm: codes {qw.codes.shape} "
            f"{'need a' if stacked else 'take no'} layer index: (K, N) "
            f"codes alone, or the stack (L, K, N) with layer=")
    K = x.shape[-1]
    N = qw.out_features
    # ragged M (e.g. prefill with an odd token count) pads up to the sublane
    # multiple so the kernel path — the whole bandwidth win — is never lost
    # to an unlucky batch·seq product
    x2, lead, M, pad_m = _flatten_pad(x)
    tiles = pick_gemm_tiles(M + pad_m, K, N, qw.bits, qw.group,
                            x2.dtype.itemsize)
    # chosen once per shape, while the caller's program is traced: the ring
    # (``/debug/trace``) shows which of a server's GEMMs run on the kernel,
    # and on how many layers' codes in place (0: a 2-D operand)
    tracer.add_event("kernel/mixed_gemm_tiles", attrs={
        "m": M, "k": K, "n": N, "bits": qw.bits, "group": qw.group,
        "layers": qw.codes.shape[0] if stacked else 0,
        **(dataclasses.asdict(tiles) if tiles else {"fallback": 1})})
    if tiles is not None:
        xp = jnp.pad(x2, ((0, pad_m), (0, 0))) if pad_m else x2
        if not stacked:  # the stack of one layer
            qw, layer = jax.tree.map(lambda a: a[None], qw), jnp.int32(0)
        out = _gemm_pallas(xp, qw, layer, tiles)
        if pad_m:
            out = out[:M]
    else:
        backend.warn_fallback(
            "mixed_gemm", f"bits={qw.bits}, M={M}, K={K}, N={N}, "
            f"group={qw.group} do not tile")
        one = layer_of_stack(qw, layer)
        out = x2 @ dequantize_gemm_weight(one).astype(x2.dtype)
    return out.reshape(*lead, N)


# ---------------------------------------------------------------------------
# frozen-weight entry point: differentiable in x, never in the codes
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _frozen_gemm(bits, group, k, x, codes, scales, layer):
    return mixed_gemm(x, QuantizedWeight(codes, scales, bits, group, k),
                      layer)


def _frozen_gemm_fwd(bits, group, k, x, codes, scales, layer):
    return (_frozen_gemm(bits, group, k, x, codes, scales, layer),
            (codes, scales, layer))


def _frozen_gemm_bwd(bits, group, k, res, g):
    codes, scales, layer = res
    # cotangent flows to the activations only: dx = g @ W^T with W (the one
    # layer's) dequantized at the cotangent dtype.  The weight is frozen, so
    # its cotangents are structural zeros (float0 for the integer codes and
    # the layer index) — the backward never builds a dW buffer.
    qw = layer_of_stack(QuantizedWeight(codes, scales, bits, group, k), layer)
    gx = g @ jnp.swapaxes(dequantize_gemm_weight(qw).astype(g.dtype), -1, -2)
    return (gx, np.zeros(codes.shape, dtype=jax.dtypes.float0),
            jnp.zeros(scales.shape, scales.dtype),
            None if layer is None
            else np.zeros(jnp.shape(layer), dtype=jax.dtypes.float0))


_frozen_gemm.defvjp(_frozen_gemm_fwd, _frozen_gemm_bwd)


def mixed_gemm_frozen(x: jax.Array, qw: QuantizedWeight,
                      layer: Optional[jax.Array] = None) -> jax.Array:
    """:func:`mixed_gemm` for frozen weights inside a differentiated graph.

    ``pallas_call`` has no JVP rule, so the bare kernel breaks under
    ``jax.grad`` even when the weight itself needs no gradient (the LoRA
    base path: earlier layers' adapters still need the cotangent to flow
    *through* this matmul).  The custom VJP keeps the kernel forward and
    differentiates w.r.t. ``x`` only, via the dequant oracle — which is a
    training-only cost; inference traces never call it."""
    return _frozen_gemm(qw.bits, qw.group, qw.k, x, qw.codes, qw.scales,
                        layer)
