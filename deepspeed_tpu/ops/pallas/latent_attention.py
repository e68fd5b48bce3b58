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
                      one query a row ``lax.top_k`` (the same keys, the same
                      ties), whose indices the decode path gathers by.
``latent_attention_decode``   one query a row: the row's selected keys are
                      gathered from the pool (``index_topk`` x W values) and
                      attended over.
``latent_attention_prefill``  the rows of two tokens and more, cut into
                      tiles of ``TILE_Q`` queries of one row: a tile reads
                      its row's keys up to its last query's position, in
                      chunks, under the selection's mask, with an online
                      softmax.  MASKED, not gathered: a gather of 2,048 keys
                      a query moves 2.4 MB a query (6.2 ms for 128 queries on
                      a v5e, gather-bound), where the masked pass over 8,192
                      keys takes 1.4 ms for the same 128: the keys of a row
                      are fetched once a tile, not once a query.  Past about
                      32k of context the gathered form would win.

All of it is XLA under these scopes: the chip's compiler fuses the mask, the
scale and the exponentials round the two matrix products of a chunk, and the
measured tile runs at over half the MXU's peak (PERF.md section 6, PR 40).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ...observability.trace import tracer

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


def select_rows(q: jax.Array, w: jax.Array, index_pool: jax.Array,
                layer: jax.Array, tables: jax.Array, positions: jax.Array,
                active: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """One query a row: ``q (R, J, D)``, ``w (R, J)``, the row's keys read
    through ``tables (R, blocks)`` from ``index_pool`` at ``layer``; the query
    of row ``r`` sits at ``positions[r]`` and sees keys ``<=`` it.  → ``(idx
    (R, k) int32`` positions in the row's sequence, ``ok (R, k)`` which of
    them are real picks: all ``k`` once the row has ``k`` keys)."""
    R, blocks = tables.shape
    bs, D = index_pool.shape[2], index_pool.shape[3]
    keys = index_pool[layer, tables].reshape(R, blocks * bs, D)
    scores = index_scores(q, w, keys)
    seen = (jnp.arange(blocks * bs)[None] <= positions[:, None]) \
        & active[:, None]
    with jax.named_scope("dsa_topk"):
        vals, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    return idx.astype(jnp.int32), vals > -jnp.inf


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
    """One query a row over the row's selected keys: ``q_lat (R, H, W)``
    absorbed, ``idx (R, k)`` the keys' positions in the row's sequence (``ok``:
    which are picks) → ``(R, H, latent)`` float32, the weighted sum of the
    keys' latents (a row without a pick: zeros)."""
    bs = pool.shape[2]
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
    query."""
    T, H, W = q_lat.shape
    blocks = tables.shape[1]
    bs = pool.shape[2]
    S = blocks * bs
    kc = key_chunk(S)
    # once a traced call, as ``kernel/paged_attention_prefill_tiles``
    tracer.add_event("kernel/latent_attention_prefill_tiles", attrs={
        "t": T, "heads": H, "w": W, "tq": tq, "key_chunk": kc, "s_max": S,
        "form": "absorbed, masked"})
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

    with jax.named_scope("latent_attention_prefill"):
        out = jax.lax.fori_loop(
            0, tiles.n, tile, jnp.zeros((T + tq, H, latent), jnp.float32))
        return out[:T]


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
