"""Ulysses sequence parallelism (all-to-all head↔sequence re-partition).

Capability analogue of the reference's DeepSpeed-Ulysses
(``deepspeed/sequence/layer.py`` — ``single_all_to_all:241``,
``_SeqAllToAll:297``, ``DistributedAttention:351``): activations arrive
sharded on the *sequence* axis; an all-to-all over the ``sp`` mesh axis
re-shards them on the *heads* axis so each device computes full-sequence
attention for a subset of heads, then a second all-to-all restores sequence
sharding.  Communication volume per device is O(S·h/P) per tensor — the
property that lets Ulysses hit >1M-token contexts.

TPU-native: expressed with ``shard_map`` + ``lax.all_to_all`` lowered onto the
ICI torus; the inner attention is the Pallas flash kernel.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size as _axis_size
from jax.sharding import PartitionSpec as P

from ..parallel.topology import get_topology


def min_kv_replication(heads: int, kv_heads: int, sp: int) -> int:
    """Smallest kv-head replication factor that makes the all-to-all legal.

    The head→sequence a2a needs KV' % sp == 0 and the GQA kernel needs
    H % KV' == 0. The reference sidesteps replication with uneven per-rank
    head counts (``sequence/layer.py:131``); static XLA shapes forbid that,
    but replicating to lcm(KV, sp) instead of to H cuts KV a2a traffic by
    H·gcd(KV, sp)/(KV·sp) (e.g. 4× for KV=8, sp=16, H=64)."""
    rep = sp // math.gcd(kv_heads, sp)
    if (heads // kv_heads) % rep == 0:
        return rep
    return heads // kv_heads  # fall back to full query-head expansion


def _inner_attention(q, k, v, causal):
    from ..ops.pallas.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=causal)


def ulysses_attention_bound(q: jax.Array, k: jax.Array, v: jax.Array,
                            causal: bool = True, attn_fn=None,
                            axis: str = "sp") -> jax.Array:
    """Ulysses body for callers ALREADY inside a shard_map binding ``axis``
    (e.g. the pipeline's stage shard_map — pp × sp composition): per-device
    q (B_l, S/sp, H, D) → head↔seq all-to-all → full-sequence attention on
    H/sp local heads → inverse all-to-all."""
    sp = _axis_size(axis)
    inner = attn_fn or _inner_attention
    H = q.shape[2]
    KV = k.shape[2]
    if H % sp != 0:
        raise ValueError(f"ulysses requires heads({H}) % sp({sp}) == 0")
    if KV % sp != 0:
        rep = min_kv_replication(H, KV, sp)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    a2a = partial(jax.lax.all_to_all, axis_name=axis, tiled=True)
    q = a2a(q, split_axis=2, concat_axis=1)
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)
    o = inner(q, k, v, causal=causal)
    # back: heads gathered, sequence re-sharded
    return a2a(o, split_axis=1, concat_axis=2)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True,
                      attn_fn=None) -> jax.Array:
    """Drop-in AttentionFn. q: (B, S, H, D) with S sharded over mesh 'sp'.

    Requires H % sp == 0.  GQA kv heads not divisible by sp are replicated
    by the *minimal* factor (lcm with sp — ``min_kv_replication``), then the
    post-a2a attention runs grouped-query on the local head subset.
    """
    topo = get_topology()
    sp = topo.size("sp")
    if sp == 1:
        return _inner_attention(q, k, v, causal) if attn_fn is None \
            else attn_fn(q, k, v, causal=causal)

    spec = P(("dp", "fsdp"), "sp", None, None)
    return shard_map(partial(ulysses_attention_bound, causal=causal,
                             attn_fn=attn_fn),
                     mesh=topo.mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
