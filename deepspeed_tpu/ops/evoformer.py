"""DS4Science Evoformer attention — TPU-native.

Reference surface: ``deepspeed/ops/deepspeed4science/evoformer_attn.py:88``
(``DS4Sci_EvoformerAttention(Q, K, V, [bias1, bias2])``), backed there by
14.9k lines of CUTLASS fMHA kernels (``csrc/deepspeed4science/evoformer_attn``).
Semantics (verified against the reference unit test
``tests/unit/ops/deepspeed4science/test_DS4Sci_EvoformerAttention.py``):

    out = softmax(Q·Kᵀ / √D + bias1 + bias2) · V

with Q/K/V of shape ``(B, N, L, H, D)`` (MSA row/column attention: N = MSA
depth; triangle attention: N = L), ``bias1`` of shape ``(B, N, 1, 1, L)``
(per-key mask bias) and ``bias2`` of shape ``(B, 1, H, L, L)`` (pair bias,
shared across the N dimension). Gradients flow to all five inputs.

TPU-native design — two asymmetric passes instead of one kernel family:

* **Forward**: the Pallas flash kernel (``pallas/flash_attention.py``) with
  the two biases streamed per-tile (``bias_kv`` / ``bias_qk`` inputs) — the
  (L, L) score matrix never hits HBM, which is what makes deep Evoformer
  stacks fit. The (B, N) leading dims flatten into the kernel batch; bias2's
  broadcast over N is an index-map division, not a materialized repeat.
* **Backward**: a recompute ``lax.scan`` over N-chunks producing all five
  gradients in one fused pass. dBias2 = Σₙ dS is inherently O(L²) (it is the
  same size as the bias2 *input*), so a flash-style backward cannot beat
  O(L²) memory here; the scan bounds the peak at one chunk of dS while XLA
  fuses the einsum chain onto the MXU. This replaces the reference's
  atomics-based CUTLASS backward (``kernel_backward.h``) with
  compiler-scheduled accumulation.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .pallas import backend
from .pallas.flash_attention import (NUM_LANES, NUM_SUBLANES, _flash_fwd,
                                     aligned_divisor)


def _chunk_size(n: int, b: int, h: int, l_q: int, l_k: int,
                budget_bytes: int = 1 << 28) -> int:
    """Largest divisor of N whose per-chunk backward tiles fit the budget.

    Per N-row the backward materialises (B, H, Lq, Lk) float32 score-shaped
    tensors, and ~3 of them coexist (p, dp, ds) — budget all of them.
    """
    per_row = max(1, b * h * l_q * l_k * 4 * 3)
    cap = max(1, budget_bytes // per_row)
    for c in range(min(n, cap), 0, -1):
        if n % c == 0:
            return c
    return 1


def _fwd_impl(q, k, v, b1, b2, has_b1: bool, has_b2: bool):
    """Returns (out, lse) with out (B,N,L,H,D), lse (B,N,H,L) float32."""
    B, N, Lq, H, D = q.shape
    Lk = k.shape[2]
    sm_scale = 1.0 / math.sqrt(D)
    bq = aligned_divisor(Lq, 512)
    # the bias tiles put block_k in the minor (lane) dim, so on TPU it must
    # be lane-aligned (a full-dim block, n ≤ cap, is always legal)
    k_align = NUM_LANES if (has_b1 or has_b2) and not backend.interpret() \
        else NUM_SUBLANES
    bk = aligned_divisor(Lk, 512, k_align)
    if bq is not None and bk is not None and Lq >= 8 and Lk >= 8:
        qt = q.reshape(B * N, Lq, H, D).transpose(0, 2, 1, 3)
        kt = k.reshape(B * N, Lk, H, D).transpose(0, 2, 1, 3)
        vt = v.reshape(B * N, Lk, H, D).transpose(0, 2, 1, 3)
        bias_kv = None
        if has_b1:
            b1f = b1.reshape(B * N, Lk)
            bias_kv = jax.lax.broadcast_in_dim(
                b1f, (B * N, NUM_SUBLANES, Lk), (0, 2))
        bias_qk = b2.reshape(B, H, Lq, Lk) if has_b2 else None
        out, lse = _flash_fwd(qt, kt, vt, None, None, None, sm_scale,
                              causal=False, block_q=bq, block_k=bk,
                              bias_kv=bias_kv, bias_qk=bias_qk)
        out = out.transpose(0, 2, 1, 3).reshape(B, N, Lq, H, D)
        lse = lse.reshape(B, N, H, Lq)
        return out, lse
    # XLA fallback for kernel-unfriendly shapes (also the numeric oracle)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if has_b1:
        s = s + b1.astype(jnp.float32)  # (B,N,1,1,Lk) broadcasts
    if has_b2:
        s = s + b2.astype(jnp.float32)  # (B,1,H,Lq,Lk) broadcasts
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B,N,H,Lq)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p,
                     v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _evo_attention(q, k, v, b1, b2, has_b1: bool, has_b2: bool):
    out, _ = _fwd_impl(q, k, v, b1, b2, has_b1, has_b2)
    return out


def _evo_fwd(q, k, v, b1, b2, has_b1, has_b2):
    out, lse = _fwd_impl(q, k, v, b1, b2, has_b1, has_b2)
    return out, (q, k, v, b1, b2, out, lse)


def _evo_bwd(has_b1, has_b2, res, g):
    q, k, v, b1, b2, out, lse = res
    B, N, Lq, H, D = q.shape
    Lk = k.shape[2]
    sm_scale = 1.0 / math.sqrt(D)
    f32 = jnp.float32

    delta = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)  # (B,N,Lq,H)
    C = _chunk_size(N, B, H, Lq, Lk)
    nc = N // C

    def chunk(x):  # (B, N, ...) → (nc, B, C, ...)
        return x.reshape(B, nc, C, *x.shape[2:]).swapaxes(0, 1)

    xs = (chunk(q), chunk(k), chunk(v), chunk(g), chunk(lse), chunk(delta),
          chunk(b1.reshape(B, N, 1, 1, Lk)) if has_b1 else jnp.zeros((nc,)))
    b2f = b2.reshape(B, 1, H, Lq, Lk).astype(f32) if has_b2 else None

    def body(db2_acc, x):
        qc, kc, vc, gc, lsec, deltac, b1c = x
        s = jnp.einsum("bnqhd,bnkhd->bnhqk", qc.astype(f32),
                       kc.astype(f32)) * sm_scale
        if has_b1:
            s = s + b1c.astype(f32)
        if has_b2:
            s = s + b2f
        # lse = -inf marks fully-masked rows; their p must be 0, not inf
        lsee = lsec[..., None]  # (B,C,H,Lq,1)
        p = jnp.where(jnp.isfinite(lsee), jnp.exp(s - lsee), 0.0)
        gf = gc.astype(f32)
        dv = jnp.einsum("bnhqk,bnqhd->bnkhd", p, gf)
        dp = jnp.einsum("bnqhd,bnkhd->bnhqk", gf, vc.astype(f32))
        ds = p * (dp - deltac.transpose(0, 1, 3, 2)[..., None])  # (B,C,H,q,k)
        dq = jnp.einsum("bnhqk,bnkhd->bnqhd", ds, kc.astype(f32)) * sm_scale
        dk = jnp.einsum("bnhqk,bnqhd->bnkhd", ds, qc.astype(f32)) * sm_scale
        db1c = (jnp.sum(ds, axis=(2, 3))[:, :, None, None, :]
                if has_b1 else 0.0)
        if has_b2:
            db2_acc = db2_acc + jnp.sum(ds, axis=1)
        return db2_acc, (dq, dk, dv, db1c)

    db2_acc0 = jnp.zeros((B, H, Lq, Lk), f32) if has_b2 else jnp.zeros(())
    db2_acc, (dqs, dks, dvs, db1s) = jax.lax.scan(body, db2_acc0, xs)

    def unchunk(x, like):  # (nc, B, C, ...) → (B, N, ...)
        return x.swapaxes(0, 1).reshape(like.shape).astype(like.dtype)

    dq = unchunk(dqs, q)
    dk = unchunk(dks, k)
    dv = unchunk(dvs, v)
    db1 = (unchunk(db1s, b1.reshape(B, N, 1, 1, Lk)).reshape(b1.shape)
           if has_b1 else jnp.zeros_like(b1))
    db2 = (db2_acc[:, None].reshape(b2.shape).astype(b2.dtype)
           if has_b2 else jnp.zeros_like(b2))
    return dq, dk, dv, db1, db2


_evo_attention.defvjp(_evo_fwd, _evo_bwd)


def evoformer_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        biases: Sequence[Optional[jax.Array]] = ()):
    """``DS4Sci_EvoformerAttention`` equivalent (see module docstring).

    q/k/v: ``(B, N, L, H, D)``; ``biases`` holds up to two optional arrays —
    ``biases[0]`` with shape ``(B, N, 1, 1, L)`` (mask bias), ``biases[1]``
    with shape ``(B, 1, H, L, L)`` (pair bias). Differentiable in all inputs.
    """
    if q.ndim == 4:  # allow unbatched (N, L, H, D)
        out = evoformer_attention(q[None], k[None], v[None],
                                  [None if b is None else b[None]
                                   for b in biases])
        return out[0]
    if q.ndim != 5:
        raise ValueError(f"q must be (B, N, L, H, D), got {q.shape}")
    B, N, Lq, H, D = q.shape
    Lk = k.shape[2]
    biases = list(biases) + [None] * (2 - len(biases))
    if len(biases) > 2:
        raise ValueError("at most two biases (mask bias, pair bias)")
    b1, b2 = biases
    if b1 is not None and b1.shape != (B, N, 1, 1, Lk):
        raise ValueError(f"bias1 shape {b1.shape} != {(B, N, 1, 1, Lk)}")
    if b2 is not None and b2.shape != (B, 1, H, Lq, Lk):
        raise ValueError(f"bias2 shape {b2.shape} != {(B, 1, H, Lq, Lk)}")
    has_b1, has_b2 = b1 is not None, b2 is not None
    if not has_b1:
        b1 = jnp.zeros((0,), q.dtype)
    if not has_b2:
        b2 = jnp.zeros((0,), q.dtype)
    return _evo_attention(q, k, v, b1, b2, has_b1, has_b2)


# reference-compatible alias (deepspeed.ops.deepspeed4science)
DS4Sci_EvoformerAttention = evoformer_attention
