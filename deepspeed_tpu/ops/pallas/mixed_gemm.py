"""Mixed-precision GEMM: quantized-weight × high-precision-activation matmul.

Reference: the CUTLASS mixed GEMM family backing weight-quantized inference
(``inference/v2/kernels/core_ops/cutlass_ops/mixed_gemm/``,
``deepspeed/inference/quantization`` W8A16/W4A16 paths). There the weight
stays int8/int4 in HBM and dequantizes in registers inside the GEMM.

TPU-native design: a Pallas kernel with grid (M/tm, N/tn, K/tk) whose inner
step streams an int8 code tile + its per-group scale row out of HBM,
dequantizes in VMEM, and feeds the MXU in bfloat16 with an f32 accumulator.
The quantization group size along K equals the k-tile, so each grid step
reads exactly one (1, tn) scale row — no gather, no unaligned broadcast.
int4 packs two K-rows per byte (codes shape (K/2, N)) and unpacks with two
arithmetic shifts in-kernel. HBM traffic for the weight is K·N bytes (int8)
or K·N/2 (int4) instead of 2·K·N (bf16) — the same bandwidth win the
reference gets, which is what matters for memory-bound decode.

``QuantizedWeight`` is a pytree node (static bits/group), so stacked
per-layer weights slice transparently under ``lax.scan`` and shard under
GSPMD like any other param leaf.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def quantize_gemm_weight(w: jax.Array, bits: int = 8,
                         group: int = 256) -> QuantizedWeight:
    """Symmetric per-(K-group, column) quantization of ``w`` (..., K, N).
    ``bits=6`` stores FP6 e3m2 codes (reference: FP6 cuda_linear /
    fp_quantizer) — scales map each group's absmax to the fp6 max (28)."""
    assert bits in (8, 6, 4), bits
    *lead, K, N = w.shape
    if K % group != 0:  # shrink the group to a divisor (odd K still works)
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
# tile selection: heuristic default + autotuner override
# ---------------------------------------------------------------------------

#: (M_padded, N, K, bits) → (tm, tn), installed by the autotuner
#: (``autotuning.autotuner.tune_gemm_tiles``).  The heuristic in
#: ``_flatten_pad_tiles`` stays the default; an override only applies when it
#: tiles the problem legally, so a stale entry can never break a call.
_TILE_OVERRIDES: Dict[Tuple[int, int, int, int], Tuple[int, int]] = {}


def set_gemm_tiles(m: int, n: int, k: int, bits: int,
                   tm: int, tn: int) -> None:
    """Pin the (tm, tn) tiles for one (padded-M, N, K, bits) GEMM shape."""
    _TILE_OVERRIDES[(m, n, k, bits)] = (int(tm), int(tn))


def clear_gemm_tiles() -> None:
    _TILE_OVERRIDES.clear()


def _tile_legal(m: int, n: int, tm: int, tn: int) -> bool:
    return (tm > 0 and tn > 0 and m % tm == 0 and n % tn == 0
            and (tm % 8 == 0 or tm == m) and (tn % 128 == 0 or tn == n))


def gemm_tile_candidates(m: int, n: int, pad_m: int = 0
                         ) -> List[Tuple[int, int]]:
    """Legal (tm, tn) tile pairs for an (m+pad_m, K) × (K, n) problem —
    the autotuner's search space.  Every pair divides the padded M and N
    with Mosaic-legal alignment; the heuristic pick is always a member."""
    mp = m + pad_m
    tms = [d for d in (8, 16, 32, 64, 128, 256, 512) if mp % d == 0]
    if not tms:
        tms = [mp]
    tns = [d for d in (128, 256, 512) if n % d == 0] or [n]
    return [(tm, tn) for tm in tms for tn in tns]


def _apply_tile_override(mp: int, N: int, K: int, bits: int,
                         tm: Optional[int], tn: Optional[int]
                         ) -> Tuple[Optional[int], Optional[int]]:
    ov = _TILE_OVERRIDES.get((mp, N, K, bits))
    if ov is not None and _tile_legal(mp, N, ov[0], ov[1]):
        return ov
    return tm, tn


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


def _mixed_gemm_kernel(x_ref, c_ref, s_ref, o_ref, acc_ref, *, bits: int):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    c = c_ref[:]
    if bits == 4:
        c = _unpack_int4(c)
    if bits == 6:
        c = _unpack_decode_fp6(c)
    w = (c.astype(jnp.float32) * s_ref[0]).astype(jnp.bfloat16)
    x = x_ref[:].astype(jnp.bfloat16)
    acc_ref[:] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _flush():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _gemm_pallas(x2: jax.Array, qw: QuantizedWeight, tm: int, tn: int):
    M, K = x2.shape
    N = qw.out_features
    tk = qw.group
    grid = (M // tm, N // tn, K // tk)
    kernel = functools.partial(_mixed_gemm_kernel, bits=qw.bits)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk: (i, kk)),
            # code rows per k-tile: int8 1:1, int4 2 codes/byte, fp6 4:3
            pl.BlockSpec(({8: tk, 4: tk // 2, 6: tk // 4 * 3}[qw.bits], tn),
                         lambda i, j, kk: (kk, j)),
            # scales get a unit middle axis so every block dim is either
            # lane-aligned or covers the full array dim (Mosaic legality)
            pl.BlockSpec((1, 1, tn), lambda i, j, kk: (kk, 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x2.dtype),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=backend.interpret(),
        name="mixed_gemm",
    )(x2, qw.codes, qw.scales[:, None, :])


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


def _flatten_pad_tiles(x: jax.Array, N: int):
    """Shared GEMM prologue: collapse lead dims, pad M to the sublane
    multiple, pick (tm, tn) tiles.  Returns (x2, lead, M, pad_m, tm, tn);
    tm/tn are None when no aligned tiling exists (→ oracle fallback)."""
    *lead, K = x.shape
    M = 1
    for d in lead:
        M *= d
    x2 = x.reshape(M, K)
    pad_m = (-M) % 8
    tm = aligned_divisor(M + pad_m, 256)
    tn = aligned_divisor(N, 256, 128)
    return x2, lead, M, pad_m, tm, tn


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
    x2, lead, M, pad_m, tm, tn = _flatten_pad_tiles(x, N)
    tm, tn = _apply_tile_override(M + pad_m, N, K, qw.bits, tm, tn)
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


def mixed_gemm(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """``x @ dequant(qw)`` with in-kernel dequantization.

    ``x``: (..., K). Falls back to the XLA dequant+matmul when shapes do not
    tile (also the numeric oracle for tests).
    """
    if qw.codes.ndim != 2:
        raise ValueError("mixed_gemm wants per-layer (K, N) codes; got "
                         f"{qw.codes.shape} — slice stacked layers via scan")
    K = x.shape[-1]
    N = qw.out_features
    # ragged M (e.g. prefill with an odd token count) pads up to the sublane
    # multiple so the kernel path — the whole bandwidth win — is never lost
    # to an unlucky batch·seq product
    x2, lead, M, pad_m, tm, tn = _flatten_pad_tiles(x, N)
    tm, tn = _apply_tile_override(M + pad_m, N, K, qw.bits, tm, tn)
    # int4 packs two codes per byte (group must be even); fp6 packs 4 K-rows
    # per 3 byte-rows (group must divide by 4, and the byte-row tile must be
    # sublane-aligned); int8 has no pack constraint
    usable = (tm is not None and tn is not None and K % qw.group == 0
              and (qw.bits != 4 or qw.group % 2 == 0)
              and (qw.bits != 6 or (qw.group % 4 == 0
                                    and (qw.group // 4 * 3) % 8 == 0))
              and (qw.group % 128 == 0 or qw.group == K))
    if usable:
        xp = jnp.pad(x2, ((0, pad_m), (0, 0))) if pad_m else x2
        out = _gemm_pallas(xp, qw, tm, tn)
        if pad_m:
            out = out[:M]
    else:
        backend.warn_fallback(
            "mixed_gemm", f"bits={qw.bits}, M={M}, K={K}, N={N}, "
            f"group={qw.group} do not tile (tm={tm}, tn={tn})")
        out = x2 @ dequantize_gemm_weight(qw).astype(x2.dtype)
    return out.reshape(*lead, N)


# ---------------------------------------------------------------------------
# frozen-weight entry point: differentiable in x, never in the codes
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _frozen_gemm(bits, group, k, x, codes, scales):
    return mixed_gemm(x, QuantizedWeight(codes, scales, bits, group, k))


def _frozen_gemm_fwd(bits, group, k, x, codes, scales):
    return _frozen_gemm(bits, group, k, x, codes, scales), (codes, scales)


def _frozen_gemm_bwd(bits, group, k, res, g):
    codes, scales = res
    # cotangent flows to the activations only: dx = g @ W^T with W
    # dequantized at the cotangent dtype.  The weight is frozen, so its
    # cotangents are structural zeros (float0 for the integer codes) — the
    # backward never builds a dW buffer.
    w = dequantize_gemm_weight(QuantizedWeight(codes, scales, bits, group, k))
    gx = g @ jnp.swapaxes(w.astype(g.dtype), -1, -2)
    return (gx, np.zeros(codes.shape, dtype=jax.dtypes.float0),
            jnp.zeros(scales.shape, scales.dtype))


_frozen_gemm.defvjp(_frozen_gemm_fwd, _frozen_gemm_bwd)


def mixed_gemm_frozen(x: jax.Array, qw: QuantizedWeight) -> jax.Array:
    """:func:`mixed_gemm` for frozen weights inside a differentiated graph.

    ``pallas_call`` has no JVP rule, so the bare kernel breaks under
    ``jax.grad`` even when the weight itself needs no gradient (the LoRA
    base path: earlier layers' adapters still need the cotangent to flow
    *through* this matmul).  The custom VJP keeps the kernel forward and
    differentiates w.r.t. ``x`` only, via the dequant oracle — which is a
    training-only cost; inference traces never call it."""
    return _frozen_gemm(qw.bits, qw.group, qw.k, x, qw.codes, qw.scales)
