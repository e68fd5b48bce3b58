"""Ring attention — blockwise sequence/context parallelism over the ICI ring.

The reference has **no** ring attention (SURVEY.md §2.3: its long-context
answer is Ulysses + FPDT offload); this is the TPU-idiomatic complement: K/V
blocks rotate around the ``sp`` ring via ``lax.ppermute`` while each device
keeps its query block, combining partial attention with the online-softmax
(log-sum-exp) merge.  Memory per device is O(S/P · S/P) per step and
communication overlaps with the blockwise compute — the standard
blockwise-parallel-transformer / RingAttention construction.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from ..parallel.topology import get_topology

NEG_INF = -1e30


def _block_attention(q, k, v, q_offset, kv_offset, causal, sm_scale):
    """One (q_block × kv_block) attention tile with global-position masking.
    q: (B, Sq, H, D); k/v: (B, Sk, H, D). Returns (out_unnorm, m, l)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale  # (B,H,Sq,Sk)
    if causal:
        rows = q_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 0)
        cols = kv_offset + jax.lax.broadcasted_iota(jnp.int32, (Sq, Sk), 1)
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B,H,Sq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)  # (B,H,Sq)
    o = jnp.einsum("bhst,bthd->bshd", p, v.astype(jnp.float32))  # unnormalized
    return o, m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True) -> jax.Array:
    """Drop-in AttentionFn. q/k/v: (B, S, H, D) with S sharded over 'sp'."""
    topo = get_topology()
    sp = topo.size("sp")
    if sp == 1:
        from ..ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal)

    B, S, H, D = q.shape
    KV = k.shape[2]
    if KV != H:  # expand GQA for simplicity of the rotating buffers
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    sm_scale = 1.0 / math.sqrt(D)
    s_local = S // sp

    def local(q, k, v):
        n = axis_size("sp")
        me = jax.lax.axis_index("sp")
        q_offset = me * s_local
        perm = [(i, (i + 1) % n) for i in range(n)]

        def step(carry, i):
            o_acc, m_acc, l_acc, k_cur, v_cur = carry
            # the chunk we currently hold started at rank (me - i) % n
            src = jnp.mod(me - i, n)
            kv_offset = src * s_local
            o_b, m_b, l_b = _block_attention(q, k_cur, v_cur, q_offset,
                                             kv_offset, causal, sm_scale)
            # online-softmax merge (out kept unnormalized)
            m_new = jnp.maximum(m_acc, m_b)
            a1 = jnp.exp(m_acc - m_new)
            a2 = jnp.exp(m_b - m_new)
            o_new = o_acc * a1.transpose(0, 2, 1)[..., None] + \
                o_b * a2.transpose(0, 2, 1)[..., None]
            l_new = l_acc * a1 + l_b * a2
            # rotate kv to the next device (skipped on the last step's output
            # but kept unconditional: one extra permute overlaps with exit)
            k_nxt = jax.lax.ppermute(k_cur, "sp", perm)
            v_nxt = jax.lax.ppermute(v_cur, "sp", perm)
            return (o_new, m_new, l_new, k_nxt, v_nxt), None

        o0 = jnp.zeros(q.shape[:1] + (q.shape[1], H, D), jnp.float32)
        m0 = jnp.full((q.shape[0], H, q.shape[1]), NEG_INF, jnp.float32)
        l0 = jnp.zeros((q.shape[0], H, q.shape[1]), jnp.float32)
        (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v),
                                          jnp.arange(n))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        out = o / l_safe.transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype)

    spec = P(("dp", "fsdp"), "sp", None, None)
    return shard_map(local, mesh=topo.mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
