"""Latent (MLA) attention over a SELECTED set of keys in a paged pool, and the
learned indexer (DSA) that selects them: what ``paged_attention.py`` is to
K/V heads, for a model whose cache holds one latent a token
(``models/latent_sparse.py``).

The pool is ``(L, num_blocks, block_size, W)``: a token's ``kv_lora_rank``
values of latent after its norm, then its ``qk_rope_head_dim`` values of
rotated key, zero-padded to ``W``, the next multiple of the 128 lanes (576 →
640 for the published sizes: the device tiles a last dimension of 576 to 640
anyway, and an aligned row is what a block fetch and a lane-aligned slice of
the value part need).  Queries arrive ABSORBED: ``q_lat (…, heads, W)`` is
``[q_nope W_kvb^K | q_rope | 0]``, so a score is one dot product of width
``W`` against a pool row and the value is the row's first ``kv_lora_rank``
values; nothing per head is ever expanded from the pool.  The indexer's pool
is ``(L_full, num_blocks, block_size, index_head_dim)``.

Four named scopes, which a device trace is reduced by:

``dsa_index_scores``  ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``:
                      bfloat16 operands, float32 products and sums.
``dsa_topk``          the ``index_topk`` largest visible scores of a query.
                      For the queries of a prefill chunk a THRESHOLD: the
                      k-th largest score by a 32-step bisection on the
                      scores' bits (exact; 0.54 ms for 512 x 16,768 scores
                      on a v5e, where ``lax.top_k`` takes 7.1 ms), and the
                      selection is the mask of the scores above it and of
                      as many at it, lowest positions first, as make k; for
                      one query a row the same mask (``select_rows_mask``)
                      for the masked decode path, or a row ``lax.top_k``
                      (the same keys, the same ties: ``select_rows``), whose
                      indices the gathered decode path reads by.
``latent_attention_decode``   one query a row over the row's selected keys.
                      MASKED, since PR 56: the row's context is streamed
                      through its table by the paged decode kernel below
                      (``_decode_full_kernel``, here named as this scope)
                      and the keys the row did not pick are masked out of
                      the scores; a row that is no row of one token in this
                      step is neither fetched nor multiplied.  Three and a
                      half times the gather's bytes at nine times its rate:
                      12 live rows of 4k-17k take 0.21 ms a layer on a v5e
                      where the gather of 16 x 2,048 rows of 640 takes 0.58
                      (0.8 inside the step program; PERF.md section 5).
                      GATHERED (``index_topk`` x W values a row, whatever
                      the context) where the static shapes say so,
                      ``decode_gathers``: under a table past
                      ``_MASKED_UP_TO`` times the picks, and on a pool the
                      kernel's DMAs cannot slice.
``latent_attention_prefill``  the rows of two tokens and more, cut into
                      tiles of ``TILE_Q`` queries of one row: a tile reads
                      its row's keys up to its last query's position, in
                      chunks, under the selection's mask, with an online
                      softmax.  MASKED, not gathered: a gather of 2,048 keys
                      a query moves 2.4 MB a query (6.2 ms for 128 queries on
                      a v5e, gather-bound), where the masked pass over 8,192
                      keys takes 1.3 ms for the same 128: the keys of a row
                      are fetched once for many queries, not once a query.
                      Past about 32k of context the gathered form would win.

The indexer and the top-k are XLA under these scopes.  The prefill path is
ONE Pallas kernel (``_prefill_kernel``, named as its scope:
what ``paged_attention._prefill_kernel`` is for K/V heads, at one KV head, a
group of all the heads, keys ``W`` wide and values the same rows' first
``kv_lora_rank`` lanes).  The pool stays in HBM; the tiles, the block table
and the rows' starts ride in as scalars.  A tile's queries are worked an
ITEM at a time (``PrefillPick.sq`` queries, 64 at the served shapes): the
item's key chunks (1,024 keys: sixteen blocks fetched through the table,
double-buffered, the next item's first chunk and queries under this item's
last) are read only up to the chunk that holds the item's last query, each
meets the item's rows a ROW BLOCK at a time (``qb`` queries x heads = 512
rows: scores, weights and both products of a block never leave VMEM), and
the running maximum, sum and accumulator of all the item's rows stay in VMEM
scratch for the whole key loop and are written out once, each query to its
token, by DMA.  The selection rides in as what it does to a score (float32:
0 for a picked key, ``_NEG`` for any other, so ``score + bias`` IS the masked
score), a ``(queries, chunk)`` piece at a time, and is spread over the heads
in VMEM.  The precisions are the XLA body's (``_latent_prefill_xla``, which
the kernel gives way to where a pool's shapes forbid its DMAs, and which the
tests hold it to): operands as stored, float32 products and sums, the scale
on the float32 scores, the weights rounded to the pool's type for ``p . v``.
Alone on a v5e at the served shapes a chunk of a tile takes 107 us where the
MXU needs 98 (PERF.md section 6, PR 46); the XLA body took 145.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend
from .paged_attention import _across, _layer_operand

LANES = 128
#: queries of one row a prefill tile holds
TILE_Q = 128
#: keys a prefill tile reads at a time (the largest power of two up to this
#: that divides the longest context)
_MAX_KEY_CHUNK = 1024
_NEG = -1e30


def pool_width(latent: int, rope: int) -> int:
    """Values a token takes in the latent pool: ``latent + rope`` rounded up
    to the lanes."""
    return -(-(latent + rope) // LANES) * LANES


def key_chunk(s_max: int) -> int:
    kc = _MAX_KEY_CHUNK
    while s_max % kc:
        kc //= 2
    return kc


class Tiles(NamedTuple):
    """The prefill rows of a mixed step cut into tiles of ``TILE_Q`` queries:
    tile ``i < n`` holds ``cnt[i]`` tokens of row ``row[i]`` from its
    ``off[i]``-th of this step on."""
    row: jax.Array
    off: jax.Array
    cnt: jax.Array
    n: jax.Array


def prefill_tiles(chunk_len: jax.Array, budget: int, tq: int = TILE_Q
                  ) -> Tiles:
    """Tiles of the rows that hold two tokens and more (``budget``: the
    step's token budget; static bound ``budget // tq + rows`` tiles)."""
    rows = chunk_len.shape[0]
    n = jnp.where(chunk_len >= 2, -(-chunk_len // tq), 0)
    ends = jnp.cumsum(n)
    w = jnp.arange(budget // tq + rows, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(ends, w, side="right"), 0, rows - 1
                   ).astype(jnp.int32)
    off = (w - (ends - n)[row]) * tq
    cnt = jnp.clip(chunk_len[row] - off, 0, tq)
    return Tiles(row, off.astype(jnp.int32), cnt.astype(jnp.int32),
                 ends[-1].astype(jnp.int32))


# ---------------------------------------------------------------------------
# the indexer
# ---------------------------------------------------------------------------


def index_scores(q: jax.Array, w: jax.Array, k: jax.Array) -> jax.Array:
    """``q (N, J, D)``, ``w (N, J)`` float32, ``k (S, D)`` or ``(N, S, D)`` →
    ``(N, S)`` float32: each head's ``relu(q . k)`` under its weight, summed
    over the heads.  The products of the stored (bfloat16) operands are exact
    in float32; the weighting and the sum are float32 on the VPU."""
    with jax.named_scope("dsa_index_scores"):
        eq = "njd,sd->njs" if k.ndim == 2 else "njd,nsd->njs"
        s = jnp.einsum(eq, q, k, preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[:, :, None],
                       axis=1)


def _order_key(x: jax.Array) -> jax.Array:
    """float32 → uint32 whose unsigned order is the floats' order."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def kth_largest_key(keys: jax.Array, k: int) -> jax.Array:
    """``keys (N, S)`` uint32 → ``(N,)``: each row's k-th largest, found bit
    by bit from the top: the largest value that at least k elements reach."""
    def bit(i, lo):
        cand = lo | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, lo)

    return jax.lax.fori_loop(0, 32, bit,
                             jnp.zeros(keys.shape[0], jnp.uint32))


def topk_mask(scores: jax.Array, k: int) -> jax.Array:
    """``scores (N, S)`` with ``-inf`` where a key is not visible → bool
    ``(N, S)``: the k largest visible scores of each row (every visible key
    where there are fewer).  Scores that tie at the k-th go to the lower
    positions, as ``lax.top_k`` breaks a tie: exactly k keys are picked."""
    with jax.named_scope("dsa_topk"):
        keys = _order_key(scores)
        thr = kth_largest_key(keys, k)[:, None]
        above, tied = keys > thr, keys == thr
        left = k - jnp.sum(above, axis=1, keepdims=True)
        pick = above | (tied & (jnp.cumsum(tied, axis=1) <= left))
        return pick & (scores > -jnp.inf)


def _row_scores(q: jax.Array, w: jax.Array, index_pool: jax.Array,
                layer: jax.Array, tables: jax.Array, positions: jax.Array,
                active: jax.Array) -> jax.Array:
    """One query a row: ``q (R, J, D)``, ``w (R, J)``, the row's keys read
    through ``tables (R, blocks)`` from ``index_pool`` at ``layer``; the query
    of row ``r`` sits at ``positions[r]`` and sees keys ``<=`` it.  → ``(R,
    S)`` float32 scores, ``-inf`` where the row sees no key."""
    R, blocks = tables.shape
    bs, D = index_pool.shape[2], index_pool.shape[3]
    keys = index_pool[layer, tables].reshape(R, blocks * bs, D)
    scores = index_scores(q, w, keys)
    seen = (jnp.arange(blocks * bs)[None] <= positions[:, None]) \
        & active[:, None]
    with jax.named_scope("dsa_topk"):
        return jnp.where(seen, scores, -jnp.inf)


def select_rows(q: jax.Array, w: jax.Array, index_pool: jax.Array,
                layer: jax.Array, tables: jax.Array, positions: jax.Array,
                active: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """The picks of one query a row (``_row_scores``'s arguments) as the
    gather reads them → ``(idx (R, k) int32`` positions in the row's
    sequence, ``ok (R, k)`` which of them are real picks: all ``k`` once the
    row has ``k`` keys)."""
    scores = _row_scores(q, w, index_pool, layer, tables, positions, active)
    with jax.named_scope("dsa_topk"):
        vals, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), vals > -jnp.inf


def select_rows_mask(q: jax.Array, w: jax.Array, index_pool: jax.Array,
                     layer: jax.Array, tables: jax.Array,
                     positions: jax.Array, active: jax.Array, k: int
                     ) -> jax.Array:
    """``select_rows``'s picks as the masked decode kernel reads them → bool
    ``(R, S)``: the same set, key for key (``topk_mask``)."""
    return topk_mask(
        _row_scores(q, w, index_pool, layer, tables, positions, active), k)


def select_tiles(q: jax.Array, w: jax.Array, index_pool: jax.Array,
                 layer: jax.Array, tables: jax.Array, tiles: Tiles,
                 q_start: jax.Array, chunk_start: jax.Array, k: int,
                 tq: int = TILE_Q) -> jax.Array:
    """The queries of a mixed step's prefill rows: ``q (T, J, D)``, ``w (T,
    J)`` flat, a row's tokens from ``q_start`` on at positions
    ``chunk_start`` on.  → bool ``(T, S)``: the keys each query attends over
    (nothing for a token no tile holds)."""
    T, J, D = q.shape
    blocks = tables.shape[1]
    bs = index_pool.shape[2]
    S = blocks * bs
    qp = jnp.pad(q, ((0, tq), (0, 0), (0, 0)))
    wp = jnp.pad(w, ((0, tq), (0, 0)))
    slot = jnp.arange(tq)
    key_pos = jnp.arange(S)[None]

    def tile(i, buf):
        s, off, cnt = tiles.row[i], tiles.off[i], tiles.cnt[i]
        t0, p0 = q_start[s] + off, chunk_start[s] + off
        keys = index_pool[layer, tables[s]].reshape(S, D)
        sc = index_scores(
            jax.lax.dynamic_slice(qp, (t0, 0, 0), (tq, J, D)),
            jax.lax.dynamic_slice(wp, (t0, 0), (tq, J)), keys)
        held = (slot < cnt)[:, None]
        sc = jnp.where(held & (key_pos <= (p0 + slot)[:, None]), sc, -jnp.inf)
        old = jax.lax.dynamic_slice(buf, (t0, 0), (tq, S))
        return jax.lax.dynamic_update_slice(
            buf, jnp.where(held, sc, old), (t0, 0))

    buf = jax.lax.fori_loop(0, tiles.n, tile,
                            jnp.full((T + tq, S), -jnp.inf, jnp.float32))
    return topk_mask(buf[:T], k)


# ---------------------------------------------------------------------------
# attention over the selection
# ---------------------------------------------------------------------------


def latent_decode_attention(q_lat: jax.Array, pool: jax.Array,
                            layer: jax.Array, tables: jax.Array,
                            idx: jax.Array, ok: jax.Array, *, scale: float,
                            latent: int) -> jax.Array:
    """One query a row over the row's selected keys, GATHERED: ``q_lat (R, H,
    W)`` absorbed, ``idx (R, k)`` the keys' positions in the row's sequence
    (``ok``: which are picks) → ``(R, H, latent)`` float32, the weighted sum
    of the keys' latents (a row without a pick: zeros).  What
    ``latent_decode_attention_masked`` gives way to (``decode_gathers``) and
    what the tests hold it to; one ring event a traced call,
    ``kernel/latent_attention_decode_tiles``, with the reason."""
    bs = pool.shape[2]
    why = decode_gathers(tables.shape[1], pool, latent, idx.shape[1])
    tracer.add_event("kernel/latent_attention_decode_tiles", attrs={
        **_decode_event(q_lat, tables, bs, idx.shape[1]),
        "form": "gathered, xla", **({why: 1} if why else {})})
    with jax.named_scope("latent_attention_decode"):
        blk = jnp.take_along_axis(tables, idx // bs, axis=1)
        g = pool[layer, blk, idx % bs]  # (R, k, W)
        s = jnp.einsum("rhw,rkw->rhk", q_lat, g,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(ok[:, None, :], s, _NEG)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(ok[:, None, :], jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("rhk,rkc->rhc", p.astype(g.dtype), g[..., :latent],
                       preferred_element_type=jnp.float32)
        return o / jnp.where(l == 0.0, 1.0, l)


# ---------------------------------------------------------------------------
# one query a row over the row's WHOLE context (a model without an indexer)
# ---------------------------------------------------------------------------

#: keys a fetch of the full decode kernel holds at most (1.3 MB of bfloat16
#: at a pool width of 640), and the fetches held in VMEM (the one multiplied
#: and those under way behind it: ``paged_attention.pick_decode_tiles``)
_FULL_FETCH_KEYS, _FULL_SLOTS = 1024, 3


def _fetch_blocks(blocks: int, bs: int) -> int:
    """Blocks a fetch of the decode kernel holds."""
    return max(1, min(blocks, _FULL_FETCH_KEYS // bs))


def _no_block_fetch(pool: jax.Array, latent: int) -> bool:
    """Whether Mosaic's DMAs cannot slice this pool a block at a time: the
    pool's rows and their value part in whole lanes, a block in whole sublane
    groups."""
    bs, W = pool.shape[2:]
    return not backend.interpret() and bool(
        W % LANES or latent % LANES or bs % (32 // pool.dtype.itemsize))


def _decode_full_xla(q_lat, pool, layer, tables, context_lens, *,
                     scale: float, latent: int):
    """``latent_decode_attention_full`` as XLA: a scan over the table's
    columns with an online softmax (what the kernel gives way to where the
    pool's shapes forbid its fetches, and what the tests hold it to)."""
    R, H, _ = q_lat.shape
    bs = pool.shape[2]

    def column(carry, j):
        acc, m, l = carry
        g = pool[layer, tables[:, j]]  # (R, bs, W)
        s = jnp.einsum("rhw,rkw->rhk", q_lat, g,
                       preferred_element_type=jnp.float32) * scale
        seen = (j * bs + jnp.arange(bs))[None, None, :] \
            < context_lens[:, None, None]
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("rhk,rkc->rhc", p.astype(g.dtype), g[..., :latent],
                        preferred_element_type=jnp.float32)
        return (acc * alpha + pv, m_new, l), None

    (acc, _, l), _ = jax.lax.scan(
        column, (jnp.zeros((R, H, latent), jnp.float32),
                 jnp.full((R, H, 1), _NEG, jnp.float32),
                 jnp.zeros((R, H, 1), jnp.float32)),
        jnp.arange(tables.shape[1]))
    return acc / jnp.where(l == 0.0, 1.0, l)


def _decode_full_kernel(layer_ref, tables_ref, ctx_ref,  # scalar prefetch
                        q_ref, pool_hbm,  # the queries, the pool in HBM
                        *rest,  # [the selection,] the output, the scratch
                        scale: float, latent: int, masked: bool = False):
    """``paged_attention._decode_kernel`` at one key head ``W`` wide whose
    values are the keys' first ``latent`` lanes: the rows walked as ONE list
    of (row, fetch), a fetch up to ``kb`` consecutive blocks of a row's table
    (one score slab), the DMA slots ``slots`` deep ACROSS rows.

    ``masked``: the rows PICKED their keys, and the selection rides in behind
    the pool as what it does to a score (``(rows, fetches, keys a fetch)``
    float32: 0 for a picked key, ``_NEG`` for any other, the keys past the
    context among them).  A fetch may then hold no key of its row's, so the
    running maximum starts finite and the weights are taken against a bound
    above ``_NEG`` (``_prefill_kernel``'s two)."""
    bias_ref = rest[0] if masked else None
    o_ref, rows_ref, k_buf, copy_sems = rest[masked:]
    rows, H, W = q_ref.shape
    slots, kb, BS, _ = k_buf.shape
    n = kb * BS
    layer = layer_ref[0]

    # -- the rows, one column of ``rows_ref`` each: the blocks [0, end) the
    # row's query (at ctx - 1) sees a key of, and the next row that has a
    # context (``rows``: none; that column reads 0, rows)
    def add_row(i, live):
        s = rows - 1 - i
        ctx = ctx_ref[s]
        rows_ref[0, s] = jax.lax.div(ctx + BS - 1, BS)
        rows_ref[1, s] = live
        return jnp.where(ctx > 0, s, live)

    rows_ref[0, rows] = 0
    rows_ref[1, rows] = rows
    live = jax.lax.fori_loop(0, rows, add_row, jnp.int32(rows))

    def copy(slot, c, blk):
        return pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                     k_buf.at[slot, c],
                                     copy_sems.at[slot, c])

    def fetch(g, s, j):
        """Start the DMAs of the ``g``-th fetch, row ``s``'s blocks ``j`` to
        ``j + kb`` that the row has (past the last row: none), and → the
        fetch after it."""
        slot = jax.lax.rem(g, slots)
        end = rows_ref[0, s]

        def one(c, _):
            copy(slot, c, tables_ref[s, j + c]).start()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(kb, end - j), one, 0)
        more = j + kb < end
        return jnp.where(more, s, rows_ref[1, s]), jnp.where(more, j + kb, 0)

    def await_fetch(slot, held):
        def one(c, _):
            copy(slot, c, 0).wait()  # a wait reads the size alone
            return 0

        jax.lax.fori_loop(0, held, one, 0)

    # a block the row lacks is not fetched: what its slot held before must be
    # finite where p = 0 meets it
    k_buf[...] = jnp.zeros(k_buf.shape, k_buf.dtype)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)

    ahead = jax.lax.fori_loop(
        0, slots - 1, lambda g, at: fetch(g, *at), (live, jnp.int32(0)))

    def row(s, carry):
        ctx, end = ctx_ref[s], rows_ref[0, s]
        q = q_ref[s].astype(k_buf.dtype)

        def step(i, carry):
            acc, m, l, g, *ahead = carry
            j = i * kb
            slot = jax.lax.rem(g, slots)
            ahead = fetch(g + slots - 1, *ahead)
            await_fetch(slot, jnp.minimum(kb, end - j))
            keys = k_buf[slot].reshape(n, W)
            scores = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                # a key the row did not pick lands on ``_NEG`` exactly
                scores = scores + bias_ref[s, pl.ds(i, 1), :]
            else:
                scores = jnp.where(j * BS + col < ctx, scores, -jnp.inf)
            # unmasked, every fetch holds a key the row sees and m_new is
            # finite; a fetch without a PICK leaves m_new where it was, and
            # against a bound above ``_NEG`` every weight of it is zero
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - (jnp.maximum(m_new, 0.1 * _NEG) if masked
                                  else m_new))
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(keys.dtype), keys[:, :latent],
                         preferred_element_type=jnp.float32)
            return (acc * alpha + pv, m_new, l, g + 1, *ahead)

        acc, _, l, *carry = jax.lax.fori_loop(
            0, jax.lax.div(end + kb - 1, kb), step,
            (jnp.zeros((H, latent), jnp.float32),
             jnp.full((H, 1), _NEG if masked else -jnp.inf, jnp.float32),
             jnp.zeros((H, 1), jnp.float32), *carry))
        # a row without a context ran no step, one without a pick summed
        # nothing: zero
        o_ref[s] = acc / jnp.where(l == 0.0, 1.0, l)
        return tuple(carry)

    jax.lax.fori_loop(0, rows, row, (jnp.int32(0), *ahead))


def _decode_call(q_lat, pool, layer, tables, context_lens, bias=None, *,
                 kb: int, scale: float, latent: int, interpret: bool):
    """The paged decode kernel's call: over a row's whole context, or
    (``bias``: ``_decode_full_kernel``'s selection) over the keys it picked,
    under the name of its scope."""
    R, H, W = q_lat.shape
    bs = pool.shape[2]
    masked = bias is not None
    selection = [bias] if masked else []
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((R, H, W), lambda i, *_: (0, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
                  *(pl.BlockSpec(b.shape, lambda i, *_: (0, 0, 0))
                    for b in selection)],
        out_specs=pl.BlockSpec((R, H, latent), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((2, R + 1), jnp.int32),
            pltpu.VMEM((_FULL_SLOTS, kb, bs, W), pool.dtype),
            pltpu.SemaphoreType.DMA((_FULL_SLOTS, kb)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_decode_full_kernel, scale=scale, latent=latent,
                          masked=masked),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H, latent), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="latent_attention_decode" if masked
        else "latent_attention_decode_full",
    )(_layer_operand(layer), tables, context_lens, q_lat, pool, *selection)


@functools.partial(jax.jit, static_argnames=("kb", "scale", "latent",
                                             "interpret"))
def _decode_full_pallas(q_lat, pool, layer, tables, context_lens, *, kb: int,
                        scale: float, latent: int, interpret: bool):
    """The kernel's call, under a jit of its own: the step program's latent
    layers trace and lower it once."""
    return _decode_call(q_lat, pool, layer, tables, context_lens, kb=kb,
                        scale=scale, latent=latent, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("kb", "scale", "latent",
                                             "interpret"))
def _decode_masked_pallas(q_lat, pool, layer, tables, mask, context_lens, *,
                          kb: int, scale: float, latent: int,
                          interpret: bool):
    """The kernel's call over the keys ``mask (R, S)`` picks, under a jit of
    its own; the selection is turned into the kernel's operand here, so that
    the scope's time is the path's whole cost: a fetch's piece of a row in
    whole lanes, the keys a row cannot see and the slots past the table
    among the unpicked."""
    R, S = mask.shape
    n = kb * pool.shape[2]
    fetches = -(-S // n)
    seen = jnp.arange(S)[None, :] < context_lens[:, None]
    bias = jnp.where(jnp.pad(mask & seen, ((0, 0), (0, fetches * n - S))),
                     0.0, _NEG).astype(jnp.float32).reshape(R, fetches, n)
    return _decode_call(q_lat, pool, layer, tables, context_lens, bias,
                        kb=kb, scale=scale, latent=latent,
                        interpret=interpret)


def latent_decode_attention_full(q_lat: jax.Array, pool: jax.Array,
                                 layer: jax.Array, tables: jax.Array,
                                 context_lens: jax.Array, *, scale: float,
                                 latent: int) -> jax.Array:
    """One query a row over the row's WHOLE context, streamed through its
    table: ``q_lat (R, H, W)`` absorbed; ``context_lens (R,)`` INCLUDES the
    row's own token (its entry already written; 0: the row takes no step,
    reads nothing and comes out zero) → ``(R, H, latent)`` float32, the
    weighted sum of the keys' latents.  One ring event a traced call,
    ``kernel/latent_attention_decode_full_tiles`` (``kb`` blocks a fetch,
    ``slots`` fetches held; or ``fallback=1`` where the pool's shapes are no
    whole lanes and sublane groups)."""
    R, H, W = q_lat.shape
    bs = pool.shape[2]
    blocks = tables.shape[1]
    kb = _fetch_blocks(blocks, bs)
    fallback = _no_block_fetch(pool, latent)
    tracer.add_event("kernel/latent_attention_decode_full_tiles", attrs={
        "rows": R, "heads": H, "w": W, "block": bs, "s_max": blocks * bs,
        **({"fallback": 1} if fallback else
           {"kb": kb, "slots": _FULL_SLOTS, "form": "absorbed, pallas"})})
    with jax.named_scope("latent_attention_decode_full"):
        if fallback:
            backend.warn_fallback(
                "latent_decode_attention_full",
                f"pool width {W} or latent {latent} is not a multiple of "
                f"{LANES}, or block_size={bs} is not whole sublane groups "
                f"(Mosaic DMA slice alignment)")
            return _decode_full_xla(q_lat, pool, layer, tables, context_lens,
                                    scale=scale, latent=latent)
        return _decode_full_pallas(
            q_lat, pool, layer, tables, context_lens.astype(jnp.int32),
            kb=kb, scale=scale, latent=latent,
            interpret=backend.interpret())


# ---------------------------------------------------------------------------
# the same kernel under a pick's mask (the rows of one token of a model WITH
# an indexer)
# ---------------------------------------------------------------------------

#: the keys of a row's table over the keys it picks, up to which the rows of
#: one token read their picks through the kernel: it streams a row's whole
#: context (1.8-2.0 us a fetch of 1,024 keys on a v5e) where the gather moves
#: ``index_topk`` rows whatever the context (0.58 ms for 16 x 2,048).  Timed
#: alone at 2,048 picks the two cross at 27k keys a table when every row
#: stands at its table's end and at about 43k when the rows are spread over
#: a quarter of it to the whole (PERF.md section 5, PR 56)
_MASKED_UP_TO = 16


def decode_gathers(blocks: int, pool: jax.Array, latent: int, k: int) -> str:
    """Why the rows of one token of a model that picks ``k`` keys GATHER them
    (``latent_decode_attention``), by the static shapes alone, or ``""``
    where they read them through the kernel under the pick's mask
    (``latent_decode_attention_masked``): ``"fallback"`` on a pool the kernel
    cannot fetch (or a fetch's piece of the selection in no whole lanes),
    ``"past_crossing"`` under a table so wide that the ``k`` gathered rows
    are the fewer bytes by more than the kernel's rate makes up."""
    bs = pool.shape[2]
    if _no_block_fetch(pool, latent) or (
            not backend.interpret() and _fetch_blocks(blocks, bs) * bs % LANES):
        return "fallback"
    return "past_crossing" if blocks * bs > _MASKED_UP_TO * k else ""


def _decode_event(q_lat, tables, bs: int, k: int) -> dict:
    R, H, W = q_lat.shape
    return {"rows": R, "heads": H, "w": W, "block": bs,
            "s_max": tables.shape[1] * bs, "k": k}


def latent_decode_attention_masked(q_lat: jax.Array, pool: jax.Array,
                                   layer: jax.Array, tables: jax.Array,
                                   mask: jax.Array, context_lens: jax.Array,
                                   *, scale: float, latent: int, k: int
                                   ) -> jax.Array:
    """``latent_decode_attention`` through the paged decode kernel: a row's
    context is streamed through its table as ``latent_decode_attention_full``
    streams it (``context_lens (R,)`` as there: 0 for a row that takes no
    step) and the keys outside ``mask (R, S)``, the row's ``k`` picks
    (``select_rows_mask``), are masked out of the scores → ``(R, H,
    latent)`` float32.  One ring event a traced call,
    ``kernel/latent_attention_decode_tiles`` (``kb`` blocks a fetch, ``slots``
    fetches held)."""
    bs = pool.shape[2]
    kb = _fetch_blocks(tables.shape[1], bs)
    tracer.add_event("kernel/latent_attention_decode_tiles", attrs={
        **_decode_event(q_lat, tables, bs, k), "form": "masked, pallas",
        "kb": kb, "slots": _FULL_SLOTS})
    with jax.named_scope("latent_attention_decode"):
        return _decode_masked_pallas(
            q_lat, pool, layer, tables, mask,
            context_lens.astype(jnp.int32), kb=kb, scale=scale,
            latent=latent, interpret=backend.interpret())


def _latent_prefill_xla(q_lat: jax.Array, pool: jax.Array, layer: jax.Array,
                        tables: jax.Array, mask: jax.Array, tiles: Tiles,
                        q_start: jax.Array, chunk_start: jax.Array, *,
                        scale: float, latent: int, tq: int = TILE_Q
                        ) -> jax.Array:
    """``latent_prefill_attention`` as XLA: what the kernel gives way to where
    the pool's shapes forbid its fetches, and what the tests hold it to.  A
    tile gathers its row's whole table and meets it a chunk at a time; a
    tile's scores and sums are arrays of the program."""
    T, H, W = q_lat.shape
    S = tables.shape[1] * pool.shape[2]
    kc = key_chunk(S)
    qp = jnp.pad(q_lat, ((0, tq), (0, 0), (0, 0)))
    mp = jnp.pad(mask, ((0, tq), (0, 0)))
    slot = jnp.arange(tq)

    def tile(i, out):
        s, off, cnt = tiles.row[i], tiles.off[i], tiles.cnt[i]
        t0, p0 = q_start[s] + off, chunk_start[s] + off
        q = jax.lax.dynamic_slice(qp, (t0, 0, 0), (tq, H, W)
                                  ).reshape(tq * H, W)
        sel = jax.lax.dynamic_slice(mp, (t0, 0), (tq, S))
        keys = pool[layer, tables[s]].reshape(S, W)

        def chunk(j, carry):
            acc, m, l = carry
            k = jax.lax.dynamic_slice(keys, (j * kc, 0), (kc, W))
            on = jax.lax.dynamic_slice(sel, (0, j * kc), (tq, kc))
            on = jnp.broadcast_to(on[:, None, :], (tq, H, kc)
                                  ).reshape(tq * H, kc)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(on, sc, _NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(on, jnp.exp(sc - m_new), 0.0)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jnp.dot(p.astype(k.dtype), k[:, :latent],
                         preferred_element_type=jnp.float32)
            return acc * alpha + pv, m_new, l

        acc, _, l = jax.lax.fori_loop(
            0, (p0 + cnt + kc - 1) // kc, chunk,
            (jnp.zeros((tq * H, latent), jnp.float32),
             jnp.full((tq * H, 1), _NEG, jnp.float32),
             jnp.zeros((tq * H, 1), jnp.float32)))
        o = (acc / jnp.where(l == 0.0, 1.0, l)).reshape(tq, H, latent)
        old = jax.lax.dynamic_slice(out, (t0, 0, 0), (tq, H, latent))
        return jax.lax.dynamic_update_slice(
            out, jnp.where((slot < cnt)[:, None, None], o, old), (t0, 0, 0))

    out = jax.lax.fori_loop(
        0, tiles.n, tile, jnp.zeros((T + tq, H, latent), jnp.float32))
    return out[:T]


@dataclasses.dataclass(frozen=True)
class PrefillPick:
    """The static tiling of one ``latent_prefill_attention`` kernel call: a
    tile's queries are worked ``sq`` at a time (an ITEM: one fetch of a key
    chunk serves them all, and their sums stay in VMEM for the item's whole
    key loop), an item's rows meet a chunk ``qb`` queries x heads at a time,
    and a chunk is ``kb`` blocks of the pool."""
    sq: int
    qb: int
    kb: int


#: queries an item holds; rows (queries x heads) of one pair of products;
#: keys a fetch.  Measured on the chip at the cell's shapes (PERF.md
#: section 6, PR 46)
_ITEM_Q, _BLOCK_ROWS = 64, 512


def pick_prefill(heads: int, block_size: int, blocks: int, tq: int = TILE_Q
                 ) -> PrefillPick:
    """The one picker, of the call's static shapes: row blocks of about
    ``_BLOCK_ROWS`` rows, items of ``_ITEM_Q`` queries in whole row blocks (no
    more than a tile), and as many blocks a fetch as ``_MAX_KEY_CHUNK`` keys
    hold, a power of two that divides the table (so no fetch runs past it)."""
    qb = max(1, min(tq, _BLOCK_ROWS // heads))
    sq = max(qb, min(tq, _ITEM_Q) // qb * qb)
    kb = 1
    while kb * 2 * block_size <= _MAX_KEY_CHUNK and blocks % (kb * 2) == 0:
        kb *= 2
    return PrefillPick(sq, qb, kb)


def _prefill_kernel(layer_ref, tables_ref, q_start_ref, chunk_start_ref,
                    row_ref, off_ref, cnt_ref, n_ref,  # scalar prefetch
                    q_hbm, bias_hbm, pool_hbm, zeros_hbm,  # in HBM
                    o_hbm,  # the output, ``zeros_hbm``'s buffer
                    items_ref, q_buf, k_buf, bias_buf, m_ref, l_ref, acc_ref,
                    o_buf, q_sem, k_sem, bias_sem, o_sem,  # scratch
                    *, pick: PrefillPick, tq: int, scale: float, latent: int):
    del zeros_hbm
    T, H, W = q_hbm.shape
    _, kb, bs, _ = k_buf.shape
    sq, qb = pick.sq, pick.qb
    kc, rb = kb * bs, qb * H
    lanes = bias_buf.shape[-1]
    layer = layer_ref[0]

    # -- the items, in tile order, one column of ``items_ref`` each: (row,
    # first token, queries held, key chunks up to the one that holds the
    # item's last query)
    def add_tile(w, n_items):
        s, off, cnt = row_ref[w], off_ref[w], cnt_ref[w]

        def add(u, at):
            left = cnt - u * sq
            held = jnp.minimum(left, sq)

            @pl.when(left > 0)
            def _add():
                items_ref[0, at] = s
                items_ref[1, at] = q_start_ref[s] + off + u * sq
                items_ref[2, at] = held
                items_ref[3, at] = jax.lax.div(
                    chunk_start_ref[s] + off + u * sq + held + kc - 1, kc)

            return at + (left > 0).astype(jnp.int32)

        return jax.lax.fori_loop(0, pl.cdiv(tq, sq), add, n_items)

    n_items = jax.lax.fori_loop(0, n_ref[0], add_tile, jnp.int32(0))

    def window(it):
        """An item's ``sq`` flat tokens, held inside the step's: its queries
        sit ``shift`` slots into them."""
        t0 = items_ref[1, it]
        w0 = jnp.minimum(t0, T - sq)
        return w0, t0 - w0, items_ref[2, it]

    def q_copy(it, slot):
        return pltpu.make_async_copy(q_hbm.at[pl.ds(window(it)[0], sq)],
                                     q_buf.at[slot], q_sem.at[slot])

    def key_copy(it, j, slot, c):
        blk = tables_ref[items_ref[0, it], j * kb + c]
        return pltpu.make_async_copy(pool_hbm.at[layer, blk],
                                     k_buf.at[slot, c], k_sem.at[slot])

    def bias_copy(it, j, slot):
        return pltpu.make_async_copy(
            bias_hbm.at[pl.ds(window(it)[0], sq), j], bias_buf.at[slot],
            bias_sem.at[slot])

    def fetch(it, j, slot):
        """Start the DMAs of item ``it``'s ``j``-th key chunk, through the
        row's table, and of its queries' piece of the selection."""
        def one(c, _):
            key_copy(it, j, slot, c).start()
            return 0

        jax.lax.fori_loop(0, kb, one, 0)
        bias_copy(it, j, slot).start()

    def await_fetch(slot):
        def one(c, _):
            key_copy(0, 0, slot, c).wait()  # a wait reads the size alone
            return 0

        jax.lax.fori_loop(0, kb, one, 0)
        bias_copy(0, 0, slot).wait()

    def out_copies(it, start: bool):
        """An item's queries, each to its token of the output: the slots
        that hold none of its queries are written nowhere."""
        w0, shift, cnt = window(it)

        def one(i, _):
            @pl.when((i >= shift) & (i < shift + cnt))
            def _held():
                dma = pltpu.make_async_copy(o_buf.at[i], o_hbm.at[w0 + i],
                                            o_sem.at[0])
                dma.start() if start else dma.wait()

            return 0

        jax.lax.fori_loop(0, sq, one, 0)

    def spread(slot, b):
        """Row block ``b``'s piece of the selection, one value a (query, key),
        over the heads: ``(qb x H, kc)``."""
        return jnp.concatenate([
            jnp.concatenate([
                jnp.broadcast_to(bias_buf[slot, b * qb + i, pl.ds(c, 1), :],
                                 (H, lanes)) for c in range(kc // lanes)],
                axis=1) for i in range(qb)], axis=0)

    def run_item(it, g):
        """One item against its row's keys; ``g`` counts the fetches (the DMA
        slots alternate)."""
        _, shift, cnt = window(it)
        n_chunks = items_ref[3, it]
        q_slot = jax.lax.rem(it, 2)
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def step(j, g):
            slot = jax.lax.rem(g, 2)
            more = j + 1 < n_chunks
            another = it + 1 < n_items

            # the next chunk, or the next item's first and its queries
            @pl.when(more | another)
            def _prefetch():
                fetch(jnp.where(more, it, it + 1), jnp.where(more, j + 1, 0),
                      1 - slot)

            @pl.when(jnp.logical_not(more) & another)
            def _next_queries():
                q_copy(it + 1, 1 - q_slot).start()

            await_fetch(slot)

            @pl.when(j == 0)
            def _queries():
                q_copy(it, q_slot).wait()

            def block(b, _):
                # a row block that holds a query of the item
                @pl.when(((b + 1) * qb > shift) & (b * qb < shift + cnt))
                def _held():
                    q = q_buf[q_slot, pl.ds(b * qb, qb)].reshape(rb, W)
                    k = k_buf[slot].reshape(kc, W)
                    # operands as they are stored, float32 products, the
                    # scale on the float32 scores; a key the query did not
                    # pick lands on ``_NEG`` exactly
                    sc = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32
                    ) * scale + spread(slot, b)
                    m_prev = m_ref[b]
                    m_new = jnp.maximum(
                        m_prev, jnp.max(sc, axis=1, keepdims=True))
                    alpha = jnp.exp(m_prev - m_new)
                    # a row with no key yet has ``m_new`` at ``_NEG``:
                    # against a bound above it every weight is zero
                    p = jnp.exp(sc - _across(jnp.maximum(m_new, 0.1 * _NEG),
                                             kc))
                    l_ref[b] = l_ref[b] * alpha + jnp.sum(
                        p, axis=1, keepdims=True)
                    pv = jnp.dot(p.astype(k.dtype), k[:, :latent],
                                 preferred_element_type=jnp.float32)
                    acc_ref[b] = acc_ref[b] * _across(alpha, latent) + pv
                    m_ref[b] = m_new

                return 0

            jax.lax.fori_loop(0, sq // qb, block, 0)
            return g + 1

        g = jax.lax.fori_loop(0, n_chunks, step, g)

        # the item before's output has had this item's key loop to leave
        @pl.when(it > 0)
        def _written():
            out_copies(it - 1, start=False)

        def finish(b, _):
            l = l_ref[b][:, :1]
            o_buf[pl.ds(b * qb, qb)] = (
                acc_ref[b] / jnp.where(l == 0.0, 1.0, l)
            ).reshape(qb, H, latent)
            return 0

        jax.lax.fori_loop(0, sq // qb, finish, 0)
        out_copies(it, start=True)
        return g

    @pl.when(n_items > 0)
    def _start_first():
        fetch(0, 0, 0)
        q_copy(0, 0).start()

    jax.lax.fori_loop(0, n_items, run_item, jnp.int32(0))

    @pl.when(n_items > 0)
    def _written():
        out_copies(n_items - 1, start=False)


@functools.partial(jax.jit, static_argnames=("pick", "tq", "scale", "latent",
                                             "interpret"))
def _prefill_pallas(q_lat, pool, layer, tables, mask, tiles: Tiles, q_start,
                    chunk_start, *, pick: PrefillPick, tq: int, scale: float,
                    latent: int, interpret: bool):
    """The kernel's call, under a jit of its own: the step program's nine
    layers trace and lower it once (as ``paged_attention._prefill_pallas``)."""
    T, H, W = q_lat.shape
    bs = pool.shape[2]
    S = tables.shape[1] * bs
    sq, qb, kc = pick.sq, pick.qb, pick.kb * bs
    lanes = math.gcd(kc, LANES)
    pad = max(sq - T, 0)  # an item's window of tokens lies inside the step's
    # the selection as what a score is moved by, a chunk's piece of a query
    # in whole (8, 128) tiles: any token and any chunk is a slice a DMA makes
    bias = jnp.where(jnp.pad(mask, ((0, pad), (0, 0))), 0.0, _NEG
                     ).astype(jnp.float32).reshape(
                         T + pad, S // kc, kc // lanes, lanes)
    anywhere = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(1,),
        in_specs=[anywhere] * 4,
        out_specs=anywhere,
        scratch_shapes=[
            pltpu.SMEM((4, tiles.row.shape[0] * pl.cdiv(tq, sq)), jnp.int32),
            pltpu.VMEM((2, sq, H, W), q_lat.dtype),
            pltpu.VMEM((2, pick.kb, bs, W), pool.dtype),
            pltpu.VMEM((2, sq, kc // lanes, lanes), jnp.float32),
            pltpu.VMEM((sq // qb, qb * H, LANES), jnp.float32),
            pltpu.VMEM((sq // qb, qb * H, LANES), jnp.float32),
            pltpu.VMEM((sq // qb, qb * H, latent), jnp.float32),
            pltpu.VMEM((sq, H, latent), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, pick=pick, tq=tq, scale=scale,
                          latent=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T + pad, H, latent), jnp.float32),
        # a token no item holds comes out zero
        input_output_aliases={11: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="latent_attention_prefill",
    )(_layer_operand(layer), tables, q_start, chunk_start,
      tiles.row, tiles.off, tiles.cnt, tiles.n.reshape(1),
      jnp.pad(q_lat, ((0, pad), (0, 0), (0, 0))), bias, pool,
      jnp.zeros((T + pad, H, latent), jnp.float32))
    return out[:T] if pad else out


def latent_prefill_attention(q_lat: jax.Array, pool: jax.Array,
                             layer: jax.Array, tables: jax.Array,
                             mask: jax.Array, tiles: Tiles,
                             q_start: jax.Array, chunk_start: jax.Array, *,
                             scale: float, latent: int, tq: int = TILE_Q
                             ) -> jax.Array:
    """The prefill rows' queries ``q_lat (T, H, W)`` (absorbed, flat) over the
    keys ``mask (T, S)`` selects for each → ``(T, H, latent)`` float32; a
    token no tile holds comes out zero.  A tile's ``tq x H`` rows meet the
    row's keys a chunk at a time, up to the chunk that holds the tile's last
    query (see the module text)."""
    T, H, W = q_lat.shape
    blocks = tables.shape[1]
    bs = pool.shape[2]
    S = blocks * bs
    pick = pick_prefill(H, bs, blocks, tq)
    kc = pick.kb * bs
    # what Mosaic's DMAs slice: whole lanes of a pool row and of a chunk's
    # piece of the selection, whole sublane groups of a block
    fallback = not backend.interpret() and bool(
        W % LANES or kc % LANES or bs % (32 // pool.dtype.itemsize))
    # once a traced call, as ``kernel/paged_attention_prefill_tiles``
    tracer.add_event("kernel/latent_attention_prefill_tiles", attrs={
        "t": T, "heads": H, "w": W, "tq": tq, "s_max": S,
        **({"fallback": 1, "key_chunk": key_chunk(S),
            "form": "absorbed, masked"} if fallback else
           {"key_chunk": kc, "form": "absorbed, masked, pallas",
            "sq": pick.sq, "qb": pick.qb, "kb": pick.kb})})
    with jax.named_scope("latent_attention_prefill"):
        if fallback:
            backend.warn_fallback(
                "latent_prefill_attention",
                f"pool width {W} or key chunk {kc} is not a multiple of "
                f"{LANES}, or block_size={bs} is not whole sublane groups "
                f"(Mosaic DMA slice alignment)")
            return _latent_prefill_xla(
                q_lat, pool, layer, tables, mask, tiles, q_start,
                chunk_start, scale=scale, latent=latent, tq=tq)
        return _prefill_pallas(
            q_lat, pool, layer, tables, mask, tiles, q_start, chunk_start,
            pick=pick, tq=tq, scale=scale, latent=latent,
            interpret=backend.interpret())


def rows_as_mask(idx: jax.Array, ok: jax.Array, s_max: int) -> jax.Array:
    """``select_rows``'s picks as a bool mask ``(R, s_max)`` (tooling)."""
    R = idx.shape[0]
    return jnp.zeros((R, s_max), bool).at[
        jnp.arange(R)[:, None], idx].max(ok)


def pack_mask(mask: jax.Array) -> jax.Array:
    """bool ``(N, S)`` → int32 ``(N, ceil(S / 32))``, bit ``s % 32`` of word
    ``s // 32`` (tooling: what rides out of a tapped step program)."""
    N, S = mask.shape
    words = -(-S // 32)
    bits = jnp.pad(mask, ((0, 0), (0, words * 32 - S))).reshape(N, words, 32)
    packed = jnp.sum(bits.astype(jnp.uint32)
                     << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                     dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)
