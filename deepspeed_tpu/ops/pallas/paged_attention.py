"""Paged-KV attention Pallas kernels: decode and ragged (chunked) prefill.

Capability analogue of the reference's blocked/ragged attention kernels
(``inference/v2/kernels/ragged_ops/blocked_flash`` and
``linear_blocked_kv_rotary``): queries attend over their sequence's chain of
KV blocks, indexed through a block table — the continuous-batching hot loop.

Both kernels take the K/V pools whole, ``(L, num_blocks, block_size, KV, D)``,
and the layer as one more scalar-prefetch operand: a block is fetched as
``k_hbm.at[layer, blk]``, so a step program's layer scan never slices a layer
out of the pool for them (a Pallas call cannot fuse its operand's slice).
The block table arrives via scalar prefetch (SMEM) so each step can DMA the
right KV blocks HBM→VMEM while computing the previous ones (slots two deep
in the prefill kernel, three in the decode kernel); online softmax across
fetches.

**Decode** (``paged_decode_attention``): one query token a sequence.  The
kernel walks the step's rows as ONE list of (row, fetch) inside a grid step
(the queries and the output of 32-64 rows are 0.25-0.5 MB and sit in VMEM
whole; past 8 MiB the rows are walked in spans, as the prefill kernel's
tokens): a fetch is up to ``kb`` consecutive entries of a row's table
(``DecodeTiles``, ``pick_decode_tiles``: 2,048 (token, KV head) pairs, 0.5 MB
of bfloat16 each of K and V), waited together and multiplied as one
``(H, kb x block x KV)`` score slab, and the DMA slots are three deep ACROSS
rows: while a row's last fetch is multiplied, the first of the next row that
has a context (and the one after it) is under way, so the HBM stream does not
stop at a row's end.  A row without a context costs a zero-trip loop and a
store of zeros; a fetch holds only entries ``[first, nblocks)`` of its own
row's table, and every copy started is waited before the call ends.  Where
``head_dim`` is one lane tile (128: every served model) the pools are read
through a view of a block as its ``block x KV`` (token, KV head) rows, a
bitcast under XLA's tiled layouts, so a block lands in VMEM as the
``(rows, D)`` operand both products take, whatever ``KV``: nothing is cast,
transposed or regrouped.  The score columns are token-major (column ``c`` is
token ``c // KV``, KV head ``c % KV``; ``p . v`` is indifferent to the
order), a query head keeps the columns of its own KV head (the match is
computed once a call), and the operands go to the MXU in the cache's dtype
with float32 scores, sums and softmax state, the weights rounded to the
cache's dtype for ``p . v``, exactly as in the prefill kernel below.

**Prefill** (``paged_prefill_attention``): the step's queries flat,
``(T, H, D)``, as the layer produced them; per row of the block table where
its tokens begin in the flat array (``q_start``), their first position
(``chunk_start``) and how many there are (``chunk_len``).  The kernel walks
the tokens there are: it cuts each row's chunk into query tiles
(``PrefillTiles``: tiles of 128 for the body of a chunk and one tile of 8 or
128 for what is left, so a decode row riding in a mixed step costs 8 query
slots and a row without tokens a scalar compare), lists the tiles in SMEM,
and runs them in order inside a grid step, the first fetch of the next tile
started in the last step of this one.  A tile's K/V blocks are fetched once
for all its queries, up to four at a time (256 keys), regrouped by KV head in
VMEM, and multiplied **KV head by KV head**: the ``(queries x group, D)`` slab
of a KV head's query heads meets that head's ``(keys, D)``, so nothing is
multiplied to be masked away, whatever the share of query heads a KV head
(Mistral 4, Mellum2 8, OLMoE 1).  The operands go to the MXU in the dtype they
are stored in (bfloat16 products are exact in the float32 accumulator), the
``1/sqrt(D)`` scale is applied to the float32 scores, the softmax state is
float32, and the weights are rounded to the cache's dtype for ``p . v`` (as
the served models' own attention does); float32 operands stay float32.  A
grid step holds a span of the queries and of the output in VMEM: all of them
while they are at most 8 MiB each (4 MiB at the cells' 512 tokens of 32
heads: one grid step), else spans of that size, a row that crosses a span's
end being cut there, so the token budget has no ceiling the VMEM sets.  A
token no row holds comes out zero.

Both take a static ``window`` (0: none): a query at position ``p`` then reads
keys ``p - window < j <= p`` only.  The block loop starts at the first block
the row's (the prefill kernel: the tile's) oldest query can see, so a block
table's entries behind the window are never read (the engine frees those
blocks while the sequence runs), and the mask adds ``pos > q_pos - window``
per query.  With ``window=0`` each kernel traces what it always did.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend


_LANES = 128  # a lane tile; the prefill kernel's running max and sum are
# kept replicated across it

#: the most a grid step's block of the queries (and of the output) may hold;
#: Pallas keeps two of each in VMEM, beside about 10 MiB of scratch
_SPAN_BYTES = 8 << 20


def _block_copies(pools, bufs, sems, layer, slot, c, blk) -> list:
    """The two DMAs (K, V) of block ``blk`` of ``layer`` from the pools in
    HBM into place ``c`` of ``slot`` of their VMEM buffers."""
    return [pltpu.make_async_copy(hbm.at[layer, blk], buf.at[slot, c],
                                  sems.at[slot, kv, c])
            for kv, (hbm, buf) in enumerate(zip(pools, bufs))]


def _layer_operand(layer) -> jax.Array:
    """The layer as the kernels prefetch it: int32 ``(1,)`` in SMEM."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _note_window(kind: str, window: int, fallback: bool) -> None:
    """One ring event a traced call of a windowed layer, as
    ``kernel/mixed_gemm_tiles``: which kernel, its window, that the block
    loop's first block is computed from the row (not the constant 0), or
    that the call gave way to XLA."""
    if window:
        tracer.add_event("kernel/paged_attention_window", attrs={
            "kind": kind, "window": window,
            **({"fallback": 1} if fallback else {"first_block_static": 0})})


def _decode_attention_xla(q, k_cache, v_cache, layer, block_tables,
                          context_lens, window: int = 0):
    """Blockwise decode fallback for kernel-unfriendly shapes: a lax.scan
    over the block-table columns with online softmax.  Peak temp memory is
    O(S·KV·block_size), NOT O(S·S_max) — the r3 verdict's "gather path
    memory" bound: the old version materialized every sequence's whole
    gathered cache at once, punishing at serving scale."""
    S, H, D = q.shape
    _, NB, BS, KV, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    rep = H // KV
    # grouped-head layout: contracting per KV head keeps the per-step
    # working set at O(S·KV·BS·D) — a jnp.repeat of K/V would inflate it
    # rep× and undo the bound this fallback exists to provide
    qf = (q.astype(jnp.float32) * (1.0 / math.sqrt(D))
          ).reshape(S, KV, rep, D)

    def block_step(carry, j):
        acc, m, l = carry
        blk = block_tables[:, j]                      # (S,)
        k = k_cache[layer, blk].astype(jnp.float32)   # (S, BS, KV, D)
        v = v_cache[layer, blk].astype(jnp.float32)
        scores = jnp.einsum("skrd,stkd->skrt", qf, k)  # (S, KV, rep, BS)
        scores = scores.reshape(S, H, BS)
        pos = j * BS + jnp.arange(BS)[None, None, :]
        seen = pos < context_lens[:, None, None]
        if window:  # the query sits at context_lens - 1
            seen &= pos >= context_lens[:, None, None] - window
        scores = jnp.where(seen, scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        pv = jnp.einsum("skrt,stkd->skrd", p.reshape(S, KV, rep, BS), v)
        acc_new = acc * alpha + pv.reshape(S, H, D)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((S, H, D), jnp.float32)
    m0 = jnp.full((S, H, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((S, H, 1), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(block_step, (acc0, m0, l0),
                                  jnp.arange(max_blocks))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    # a fully-masked row has every p = exp(-1e30 - -1e30) = 1, so it holds
    # the MEAN of gathered V, not zeros — zero ctx=0 rows explicitly
    out = jnp.where(context_lens[:, None, None] > 0, out, 0.0)
    return out.astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class DecodeTiles:
    """The static tiling of one ``paged_decode_attention`` call: ``kb`` K/V
    blocks a fetch (one score slab), ``slots`` fetches held in VMEM (the one
    multiplied and those under way behind it), ``span`` rows a grid step."""
    kb: int
    slots: int
    span: int


#: (token, KV head) pairs a fetch holds at most: the columns of a score slab
_FETCH_ROWS = 2048


def pick_decode_tiles(rows: int, heads: int, kv: int, d: int, block_size: int,
                      dtype) -> DecodeTiles:
    """The decode kernel's picker, of the call's static shapes (beside
    ``pick_prefill_tiles``): the blocks a fetch that hold ``_FETCH_ROWS``
    (token, KV head) pairs, 16 at most (at blocks of 64: 2 at OLMoE's 16 KV
    heads, 4 at Mistral's 8, 8 at Mellum2's 4, 16 at Nemotron-3's 2: 0.5 MB
    of bfloat16 each of K and V whatever the model, so the loop's fixed cost
    and the MXU's operand loads are paid once for as many columns); three
    slots, so two fetches are under way behind the one multiplied (3 MB of
    VMEM in bfloat16, 6 in float32); the span all ``rows`` rows while their
    queries are at most ``_SPAN_BYTES``, else the whole sublane groups of
    rows that are.  Measured on the chip at the four served shapes
    (``scripts/prefill_attention_alone.py --decode-tiles``, PERF.md section
    5): half as many blocks a fetch cost Nemotron-3's rows 7 % and Mellum2's
    up to 4 %, a quarter as many 28-36 %, and Mistral's and OLMoE's nothing;
    twice as many cost those two 1-2 %; two slots cost 2-22 %, four bought
    nothing."""
    kb = max(1, min(16, _FETCH_ROWS // (block_size * kv)))
    a_row = heads * d * jnp.dtype(dtype).itemsize
    span = rows if rows * a_row <= _SPAN_BYTES else max(
        8, _SPAN_BYTES // a_row // 8 * 8)
    return DecodeTiles(kb, 3, span)


def _decode_kernel(layer_ref, tables_ref, ctx_ref,  # scalar prefetch (SMEM)
                   q_ref, k_hbm, v_hbm,  # the span's queries, the pools in HBM
                   o_ref,  # the span's output
                   rows_ref, k_buf, v_buf, copy_sems,  # scratch
                   *, block_size: int, kv: int, spans: int, window: int = 0):
    rows, H, D = q_ref.shape
    slots, kb = k_buf.shape[:2]
    BS, KV, group = block_size, kv, H // kv
    n = kb * BS * KV
    layer = layer_ref[0]
    scale = 1.0 / math.sqrt(D)
    # the span's first row
    base = pl.program_id(0) * rows if spans > 1 else 0

    # -- the span's rows, one column of ``rows_ref`` each: the K/V blocks
    # [first, end) the row's query (at ctx - 1) sees a key of, and the next
    # row that has a context (``rows``: none; that column reads 0, 0, rows).
    def add_row(i, live):
        s = rows - 1 - i
        ctx = ctx_ref[base + s]
        rows_ref[0, s] = (jax.lax.div(jnp.maximum(ctx - window, 0), BS)
                          if window else 0)
        rows_ref[1, s] = jax.lax.div(ctx + BS - 1, BS)
        rows_ref[2, s] = live
        return jnp.where(ctx > 0, s, live)

    for i, x in enumerate((0, 0, rows)):
        rows_ref[i, rows] = x
    live = jax.lax.fori_loop(0, rows, add_row, jnp.int32(rows))

    copies = functools.partial(_block_copies, (k_hbm, v_hbm), (k_buf, v_buf),
                               copy_sems, layer)

    def fetch(g, s, j):
        """Start the DMAs of the ``g``-th fetch of the span, row ``s``'s
        blocks ``j`` to ``j + kb`` that the row has (past the last row:
        none), and → the fetch after it: the row's next ``kb`` blocks, or the
        first of the next row that has a context."""
        slot = jax.lax.rem(g, slots)
        end = rows_ref[1, s]

        def one(c, _):
            for dma in copies(slot, c, tables_ref[base + s, j + c]):
                dma.start()
            return 0

        jax.lax.fori_loop(0, jnp.minimum(kb, end - j), one, 0)
        more = j + kb < end
        s = jnp.where(more, s, rows_ref[2, s])
        return s, jnp.where(more, j + kb, rows_ref[0, s])

    def await_fetch(slot, held):
        def one(c, _):
            for dma in copies(slot, c, 0):  # a wait reads the size alone
                dma.wait()
            return 0

        jax.lax.fori_loop(0, held, one, 0)

    # a block the row lacks is not fetched: what its slot held before must be
    # finite where p = 0 meets it, and after this only a row's own blocks are
    v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
    # column c of a fetch's keys: token c // KV of it, KV head c % KV; query
    # head h reads KV head h // group alone
    col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
    own = (jax.lax.rem(col, KV)
           == jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (H, n), 0),
                          group))
    col_pos = jax.lax.div(col, KV)

    # the first fetches, ``slots - 1`` of them under way before any product
    ahead = jax.lax.fori_loop(
        0, slots - 1, lambda g, at: fetch(g, *at), (live, rows_ref[0, live]))

    def row(s, carry):
        ctx, first, end = ctx_ref[base + s], rows_ref[0, s], rows_ref[1, s]
        q = q_ref[s].astype(k_buf.dtype)

        def step(i, carry):
            acc, m, l, g, *ahead = carry
            j = first + i * kb
            slot = jax.lax.rem(g, slots)
            ahead = fetch(g + slots - 1, *ahead)
            await_fetch(slot, jnp.minimum(kb, end - j))
            scores = jax.lax.dot_general(
                q, k_buf[slot].reshape(n, D), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            pos = j * BS + col_pos
            keep = own & (pos < ctx)
            if window:
                keep &= pos >= ctx - window
            scores = jnp.where(keep, scores, -jnp.inf)
            # every fetch holds a key each head sees: m_new is finite
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v_buf.dtype), v_buf[slot].reshape(n, D),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            return (acc * alpha + pv, m_new, l, g + 1, *ahead)

        acc, _, l, *carry = jax.lax.fori_loop(
            0, jax.lax.div(end - first + kb - 1, kb), step,
            (jnp.zeros((H, D), jnp.float32),
             jnp.full((H, 1), -jnp.inf, jnp.float32),
             jnp.zeros((H, 1), jnp.float32), *carry))
        # a row without a context ran no step: zero
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
        return tuple(carry)

    jax.lax.fori_loop(0, rows, row, (jnp.int32(0), *ahead))


def paged_decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                           layer: jax.Array, block_tables: jax.Array,
                           context_lens: jax.Array, window: int = 0
                           ) -> jax.Array:
    """q: (max_seqs, H, D) — one decode token per sequence.
    k/v_cache: the whole pools, (L, num_blocks, block_size, KV, D); layer:
    int32 scalar (traced in a layer scan), the pool's layer to read;
    block_tables: (max_seqs, max_blocks) int32; context_lens: (max_seqs,)
    int32.  Context length INCLUDES the current token (its KV already
    written); a row whose context is 0 reads nothing (its table need not be
    valid) and comes out zero.  ``window`` (static; 0: none): the sliding
    window of the layer.  The rows are walked as one list with the K/V
    fetches of ``pick_decode_tiles`` in flight across rows (see the module
    text); one ring event a traced call,
    ``kernel/paged_attention_decode_tiles``, says what was picked, in which
    dtype the products' operands are, and whether the call gave way to the
    blockwise XLA path (``fallback``: shapes Mosaic's DMA cannot slice)."""
    S, H, D = q.shape
    _, NB, BS, KV, _ = k_cache.shape

    # Mosaic DMA slices need the lane dim 128-aligned and sublanes 8-aligned;
    # small-model shapes fall back to the (correct, slower) XLA gather path.
    fallback = not backend.interpret() and (D % 128 != 0 or BS % 8 != 0)
    tiles = pick_decode_tiles(S, H, KV, D, BS, q.dtype)
    # once a traced call, as ``kernel/paged_attention_prefill_tiles``
    tracer.add_event("kernel/paged_attention_decode_tiles", attrs={
        "rows": S, "heads": H, "kv": KV, "d": D, "block": BS,
        "window": window,
        **({"fallback": 1} if fallback else
           {"kb": tiles.kb, "slots": tiles.slots,
            "operand_dtype": jnp.dtype(k_cache.dtype).name})})
    if fallback:
        backend.warn_fallback(
            "paged_decode_attention",
            f"head_dim={D} is not a multiple of 128 or block_size={BS} not "
            f"a multiple of 8 (Mosaic DMA slice alignment)")
        return _decode_attention_xla(q, k_cache, v_cache, layer,
                                     block_tables, context_lens, window)

    return _decode_pallas(q, k_cache, v_cache, _layer_operand(layer),
                          block_tables, context_lens, tiles=tiles,
                          window=window, interpret=backend.interpret())


@functools.partial(jax.jit, static_argnames=("tiles", "window", "interpret"))
def _decode_pallas(q, k_cache, v_cache, layer, block_tables, context_lens, *,
                   tiles: DecodeTiles, window: int, interpret: bool):
    """The kernel's call, under a jit of its own (as ``_prefill_pallas``): a
    step program whose layers call it alike traces and lowers it once."""
    S, H, D = q.shape
    L, NB, BS, KV, _ = k_cache.shape
    span = tiles.span
    spans = pl.cdiv(S, span)
    if S % span:  # whole spans: the rows added have no context
        pad = spans * span - S
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)))
        context_lens = jnp.pad(context_lens, (0, pad))
    block = (BS, KV, D)
    if D == _LANES:
        # a block's (token, KV head) pairs as its rows: with one lane tile a
        # row XLA's tiled layouts of the two shapes are byte for byte the
        # same, so the view is a bitcast (no pass over a pool:
        # tests/test_tpu_compile.py), and a block lands in VMEM as the
        # (rows, D) operand the products take, whatever KV
        block = (BS * KV, D)
        k_cache, v_cache = (x.reshape(L, NB, *block)
                            for x in (k_cache, v_cache))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(spans,),
        in_specs=[
            pl.BlockSpec((span, H, D), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((span, H, D), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((3, span + 1), jnp.int32),
            pltpu.VMEM((tiles.slots, tiles.kb, *block), k_cache.dtype),
            pltpu.VMEM((tiles.slots, tiles.kb, *block), v_cache.dtype),
            pltpu.SemaphoreType.DMA((tiles.slots, 2, tiles.kb)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=BS, kv=KV, spans=spans,
                          **({"window": window} if window else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="paged_attention_decode",
    )(layer, block_tables, context_lens, q, k_cache, v_cache)
    return out[:S] if S % span else out


# ---------------------------------------------------------------------------
# ragged prefill (chunked) over paged KV
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PrefillTiles:
    """The static tiling of one ``paged_prefill_attention`` call.

    ``span``: the flat tokens a grid step holds (its block of the queries and
    of the output); a row's tokens inside a span are cut into tiles of
    ``big`` queries and, for what is left, ONE tile of ``small`` if that
    holds it, else of ``big``: a row's K/V blocks are fetched once for up to
    ``big`` queries and a decode row riding in a mixed step costs ``small``
    query slots.  ``kb``: K/V blocks a fetch."""
    small: int
    big: int
    kb: int
    span: int

    def slots(self, chunk_len, q_start=None) -> np.ndarray:
        """Query slots the kernel multiplies for each row (host arrays):
        ``chunk_len`` rounded up to the row's tiles.  ``q_start`` None: the
        rows lie end to end, as the mixed step's do."""
        n = np.asarray(chunk_len, np.int64)
        lo = np.cumsum(n) - n if q_start is None else np.asarray(q_start)
        slots = np.zeros_like(n)
        for base in range(0, int((lo + n).max(initial=0)), self.span):
            held = np.clip(np.minimum(lo + n, base + self.span)
                           - np.maximum(lo, base), 0, None)
            left = held % self.big
            slots += held - left + np.where(left > self.small, self.big,
                                            (left > 0) * self.small)
        return slots


def pick_prefill_tiles(t: int, heads: int, kv: int, d: int, block_size: int,
                       dtype) -> PrefillTiles:
    """The one picker, of the call's static shapes (as ``pick_gemm_tiles``
    and ``moe_tile_m``): tiles of 8 queries (a sublane group: what a decode
    row riding in a mixed step costs) for single tokens and tails of up to 8,
    128 for the body of a chunk and longer tails, each cut to the span in
    whole groups of 8; up to four blocks a fetch, 256 keys at most (the row
    maximum and sum of a step's scores are lane reductions, cheaper a key the
    more keys a step holds); the span all ``t`` tokens while their queries
    are at most ``_SPAN_BYTES``, else the whole tiles of 128 that are.
    Measured on the chip at the serving cells' shapes
    (``scripts/prefill_attention_alone.py``, PERF.md section 5); a third
    size, 32, bought nothing there and every size is a body the step program
    traces at each start (0.4 s of ``setup_s`` a size a call site)."""
    del kv  # no served share of query heads a KV head asked for its own tiles
    a_token = heads * d * jnp.dtype(dtype).itemsize
    span = t if t * a_token <= _SPAN_BYTES else max(
        128, _SPAN_BYTES // a_token // 128 * 128)
    budget = span if span < 8 else span // 8 * 8  # whole sublane groups
    return PrefillTiles(min(8, budget), min(128, budget),
                        max(1, min(4, 256 // block_size)), span)


def _token_rows(t: int, q_start, chunk_start, chunk_len):
    """Each of ``t`` flat tokens' row, position and whether any row holds it
    (row ``s`` holds tokens ``q_start[s] <= i < q_start[s] + chunk_len[s]``)."""
    tok = jnp.arange(t)[:, None]
    held = (tok >= q_start[None]) & (tok < (q_start + chunk_len)[None])
    row = jnp.argmax(held, axis=1)
    return row, chunk_start[row] + jnp.arange(t) - q_start[row], held.any(1)


def _prefill_attention_xla(q, k_cache, v_cache, layer, block_tables, q_start,
                           chunk_start, chunk_len, window: int = 0):
    """Blockwise prefill fallback on the kernel's flat operands.  A lax.scan
    over block-table columns with online softmax: peak temp memory is
    O(T·block_size), never O(T·S_max) (the r3 "bound the gather path"
    item)."""
    T, H, D = q.shape
    _, NB, BS, KV, _ = k_cache.shape
    max_blocks = block_tables.shape[1]
    rep = H // KV
    row, q_pos, held = _token_rows(T, q_start, chunk_start, chunk_len)
    # grouped heads: contract per KV head (see _decode_attention_xla)
    qf = q.astype(jnp.float32).reshape(T, KV, rep, D)
    scale = 1.0 / math.sqrt(D)

    def block_step(carry, j):
        acc, m, l = carry
        blk = block_tables[row, j]                    # (T,)
        k = k_cache[layer, blk].astype(jnp.float32)   # (T, BS, KV, D)
        v = v_cache[layer, blk].astype(jnp.float32)
        scores = jnp.einsum("qkrd,qtkd->qkrt", qf, k).reshape(T, H, BS)
        t_pos = j * BS + jnp.arange(BS)[None, None, :]
        valid = (t_pos <= q_pos[:, None, None]) & held[:, None, None]
        if window:
            valid &= t_pos > q_pos[:, None, None] - window
        scores = jnp.where(valid, scores * scale, -1e30)
        m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + p.sum(-1, keepdims=True)
        pv = jnp.einsum("qkrt,qtkd->qkrd", p.reshape(T, KV, rep, BS), v)
        return (acc * alpha + pv.reshape(T, H, D), m_new, l_new), None

    acc0 = jnp.zeros((T, H, D), jnp.float32)
    m0 = jnp.full((T, H, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((T, H, 1), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(block_step, (acc0, m0, l0),
                                  jnp.arange(max_blocks))
    # a token no row holds had p = 1 everywhere → the mean of gathered V, not
    # zeros; zero it explicitly so callers can rely on it
    return jnp.where(held[:, None, None], acc / l, 0.0).astype(q.dtype)


def _across(x, n: int):
    """``x (..., _LANES)``, every lane of a row the same → ``(..., n)``."""
    if n % _LANES:
        return x[..., :1]
    return jnp.tile(x, (1,) * (x.ndim - 1) + (n // _LANES,))


def _prefill_kernel(layer_ref, tables_ref, q_start_ref, chunk_start_ref,
                    chunk_len_ref,  # scalar prefetch (SMEM)
                    q_ref, k_hbm, v_hbm,  # the span's queries, the pools in HBM
                    o_ref,  # the span's output
                    tiles_ref, k_buf, v_buf, copy_sems, kt_ref, vt_ref,
                    qt_ref, m_ref, l_ref, acc_ref,  # scratch
                    *, tiles: PrefillTiles, group: int, spans: int,
                    window: int = 0):
    span, H, D = q_ref.shape
    if k_buf.ndim == 4:  # ONE K/V head: a block's tokens are its rows
        (_, kb, BS, _), KV = k_buf.shape, 1
    else:
        _, kb, BS, KV, _ = k_buf.shape
    S = chunk_len_ref.shape[0]
    kbs = kb * BS
    small, big = tiles.small, tiles.big
    layer = layer_ref[0]
    scale = 1.0 / math.sqrt(D)
    # the span's first flat token
    base = pl.program_id(0) * span if spans > 1 else 0

    # -- the span's tiles, in row order, one column of ``tiles_ref`` each:
    # (row, first query of the row, queries held, first and last K/V block
    # its queries see, whether the tile is a ``big`` one).  A row without
    # tokens in the span adds none.
    def add_row(s, n_tiles):
        n, start = chunk_len_ref[s], chunk_start_ref[s]
        if spans > 1:  # the row's tokens that lie in this span
            lo = q_start_ref[s]
            skip = jnp.maximum(base - lo, 0)  # those in the spans before
            n = jnp.maximum(jnp.minimum(lo + n, base + span) - lo - skip, 0)
        body = jax.lax.div(n, big)  # operands are never negative

        def add(at, off, cnt, cls):
            if spans > 1:
                off = skip + off
            oldest = start + off
            # the first block the tile's oldest query sees a key of; each
            # query's own band is the mask's
            first = (jax.lax.div(jnp.maximum(oldest - window + 1, 0), BS)
                     if window else 0)
            for i, x in enumerate((s, off, cnt, first,
                                   jax.lax.div(oldest + cnt - 1, BS), cls)):
                tiles_ref[i, at] = x

        def add_body(i, at):
            add(at, i * big, big, 1)
            return at + 1

        at = jax.lax.fori_loop(0, body, add_body, n_tiles)
        left = n - body * big

        @pl.when(left > 0)
        def _add_tail():
            add(at, body * big, left, (left > small).astype(jnp.int32))

        return at + (left > 0).astype(jnp.int32)

    n_tiles = jax.lax.fori_loop(0, S, add_row, jnp.int32(0))

    copies = functools.partial(_block_copies, (k_hbm, v_hbm), (k_buf, v_buf),
                               copy_sems, layer)

    def fetch(w, step, slot):
        """Start the DMAs of tile ``w``'s ``step``-th ``kb`` blocks into
        ``slot``.  Past the tile's last block the last is fetched again: its
        keys then sit at positions no query of the tile sees, and what the
        mask drops is finite."""
        s, first, last = tiles_ref[0, w], tiles_ref[3, w], tiles_ref[4, w]

        def one(c, _):
            blk = tables_ref[s, jnp.minimum(first + step * kb + c, last)]
            for dma in copies(slot, c, blk):
                dma.start()
            return 0

        jax.lax.fori_loop(0, kb, one, 0)

    def await_fetch(slot):
        def one(c, _):
            for dma in copies(slot, c, 0):  # a wait reads the size alone
                dma.wait()
            return 0

        jax.lax.fori_loop(0, kb, one, 0)

    def run_tile(w, g, tq: int):
        """One tile of ``tq`` queries against its row's blocks, KV head by KV
        head; ``g`` counts the fetches (the DMA slots alternate)."""
        rows = group * tq
        s, off, cnt, first, last = (tiles_ref[i, w] for i in range(5))
        n_steps = jax.lax.div(last - first + kb, kb)
        # the tile's window of the span's queries, held inside the span:
        # the tile's queries sit ``shift`` slots into it
        tok0 = q_start_ref[s] + off
        if spans > 1:
            tok0 -= base
        w0 = jnp.minimum(tok0, span - tq)
        shift = tok0 - w0
        # (tq, H, D) → (KV, group * tq, D): row r of a KV head's slab is
        # query r % tq of one of the head's ``group`` query heads
        qt_ref[:, :rows] = jnp.swapaxes(q_ref[pl.ds(w0, tq)], 0, 1).reshape(
            KV, rows, D)
        m_ref[:, :rows] = jnp.full((KV, rows, _LANES), -jnp.inf, jnp.float32)
        l_ref[:, :rows] = jnp.zeros((KV, rows, _LANES), jnp.float32)
        acc_ref[:, :rows] = jnp.zeros((KV, rows, D), jnp.float32)
        # heads multiplied together overlap one's softmax with the next's
        # products: all of a small tile's, four of a large one's (and one
        # batched product is traced once, however many heads it holds)
        hb = KV if rows <= 64 else math.gcd(KV, 4)
        slot_q = jax.lax.rem(
            jax.lax.broadcasted_iota(jnp.int32, (rows, kbs), 0), tq)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, kbs), 1)
        held = (slot_q >= shift) & (slot_q < shift + cnt)
        q_abs = chunk_start_ref[s] + off + slot_q - shift

        def step(i, g):
            slot = jax.lax.rem(g, 2)

            # the blocks of the next step, or of the next tile's first
            more = i + 1 < n_steps

            @pl.when(more | (w + 1 < n_tiles))
            def _prefetch():
                fetch(jnp.where(more, w, w + 1), jnp.where(more, i + 1, 0),
                      1 - slot)

            await_fetch(slot)
            # (keys, KV, D) → (KV, keys, D): each KV head's keys together
            if KV == 1:
                kt_ref[...] = k_buf[slot].reshape(1, kbs, D)
                vt_ref[...] = v_buf[slot].reshape(1, kbs, D)
            else:
                kt_ref[...] = jnp.swapaxes(
                    k_buf[slot].reshape(kbs, KV, D), 0, 1)
                vt_ref[...] = jnp.swapaxes(
                    v_buf[slot].reshape(kbs, KV, D), 0, 1)
            pos = (first + i * kb) * BS + col
            keep = held & (pos <= q_abs)
            if window:
                keep &= pos > q_abs - window
            bias = jnp.where(keep, 0.0, -jnp.inf)

            def heads(j, _):
                """``hb`` KV heads from ``j * hb`` on, as one batched product:
                operands in the dtype they are stored in, float32 products
                (exact for bfloat16), the scale on the float32 scores."""
                hs = pl.ds(j * hb, hb)
                scores = jax.lax.dot_general(
                    qt_ref[hs, :rows], kt_ref[hs],
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * scale + bias
                m_prev = m_ref[hs, :rows]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(scores, axis=2, keepdims=True))
                # a slot that holds no query of the tile has every column
                # masked → m_new stays -inf and exp(-inf - -inf) is NaN;
                # rescaling against 0 instead keeps it at zero
                m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                alpha = jnp.exp(m_prev - m_safe)
                p = jnp.exp(scores - _across(m_safe, kbs))
                l_ref[hs, :rows] = l_ref[hs, :rows] * alpha + jnp.sum(
                    p, axis=2, keepdims=True)
                # the weights in the cache's dtype, as the models' own
                # attention rounds them; float32 operands stay float32
                pv = jax.lax.dot_general(
                    p.astype(vt_ref.dtype), vt_ref[hs],
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
                acc_ref[hs, :rows] = (acc_ref[hs, :rows] * _across(alpha, D)
                                      + pv)
                m_ref[hs, :rows] = m_new
                return 0

            jax.lax.fori_loop(0, KV // hb, heads, 0)
            return g + 1

        g = jax.lax.fori_loop(0, n_steps, step, g)
        l = l_ref[:, :rows, :1]
        out = acc_ref[:, :rows] / jnp.where(l == 0.0, 1.0, l)
        out = jnp.swapaxes(out.astype(o_ref.dtype).reshape(H, tq, D), 0, 1)
        at = jax.lax.broadcasted_iota(jnp.int32, (tq, H, D), 0)
        o_ref[pl.ds(w0, tq)] = jnp.where(
            (at >= shift) & (at < shift + cnt), out, o_ref[pl.ds(w0, tq)])
        return g

    # a token no tile holds comes out zero
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n_tiles > 0)
    def _start_first():
        fetch(0, 0, 0)

    def run(w, g):
        if small == big:  # a budget under two sublane groups: one size
            return run_tile(w, g, big)
        return jax.lax.cond(tiles_ref[5, w] == 1,
                            functools.partial(run_tile, w, tq=big),
                            functools.partial(run_tile, w, tq=small), g)

    jax.lax.fori_loop(0, n_tiles, run, jnp.int32(0))


def paged_prefill_attention(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, layer: jax.Array,
                            block_tables: jax.Array, q_start: jax.Array,
                            chunk_start: jax.Array, chunk_len: jax.Array,
                            window: int = 0) -> jax.Array:
    """Ragged chunked-prefill attention over paged KV (the reference's
    ragged-batch ``blocked_flash`` kernel, ``inference/v2/kernels/
    ragged_ops/``): the step's queries as the layer produced them, flat.

    q: (T, H, D), the step's tokens; row ``s`` of the block table holds the
    ``chunk_len[s]`` tokens from ``q_start[s]`` on (rows do not overlap; they
    may leave gaps), at positions ``chunk_start[s]`` on of its sequence (the
    tokens already in the cache); the chunk's own KV must already be written
    to the cache.  ``k_cache``/``v_cache``: the whole pools (L, num_blocks,
    block_size, KV, D), read at ``layer`` (int32 scalar, traced in a layer
    scan).  Returns (T, H, D); a token no row holds comes out zero.

    Causal within the sequence: a query at position p sees cache positions
    ≤ p (with ``window``, static: and > p less the window; see the module
    text).  Work follows the rows (see the module text): nothing is laid out
    by (row, budget) and nothing of that size exists.
    """
    T, H, D = q.shape
    _, NB, BS, KV, _ = k_cache.shape
    group = H // KV

    fallback = not backend.interpret() and (D % 128 != 0 or BS % 8 != 0)
    _note_window("prefill", window, fallback)
    tiles = pick_prefill_tiles(T, H, KV, D, BS, q.dtype)
    # once a traced call, as ``kernel/mixed_gemm_tiles``
    tracer.add_event("kernel/paged_attention_prefill_tiles", attrs={
        "t": T, "heads": H, "kv": KV, "d": D, "block": BS, "window": window,
        **({"fallback": 1} if fallback else
           {"tq": "/".join(map(str, sorted({tiles.small, tiles.big}))),
            "kb": tiles.kb, "grid_steps": pl.cdiv(T, tiles.span)})})
    if fallback:
        backend.warn_fallback(
            "paged_prefill_attention",
            f"head_dim={D} is not a multiple of 128 or block_size={BS} not "
            f"a multiple of 8 (Mosaic DMA slice alignment)")
        return _prefill_attention_xla(q, k_cache, v_cache, layer,
                                      block_tables, q_start, chunk_start,
                                      chunk_len, window)
    return _prefill_pallas(q, k_cache, v_cache, _layer_operand(layer),
                           block_tables, q_start, chunk_start, chunk_len,
                           tiles=tiles, window=window,
                           interpret=backend.interpret())


@functools.partial(jax.jit, static_argnames=("tiles", "window", "interpret"))
def _prefill_pallas(q, k_cache, v_cache, layer, block_tables, q_start,
                    chunk_start, chunk_len, *, tiles: PrefillTiles,
                    window: int, interpret: bool):
    """The kernel's call, under a jit of its own: a step program whose layers
    call it alike (Mellum2's three window layers a period) traces and lowers
    it once, and tracing is what a served program pays at every start."""
    T, H, D = q.shape
    _, NB, BS, KV, _ = k_cache.shape
    group = H // KV
    S = chunk_len.shape[0]
    span, big, kbs = tiles.span, tiles.big, tiles.kb * BS
    spans = pl.cdiv(T, span)
    rows = group * big
    if T % span:  # whole spans: the tokens added are no row's
        q = jnp.pad(q, ((0, spans * span - T), (0, 0), (0, 0)))
    block = (BS, KV, D)
    if KV == 1:
        # one K/V head (20 query heads on it: AI21-Jamba2): Mosaic's DMA
        # cannot slice a pool whose second-minor dimension is 1 (its tile
        # pads it to 2), so a block's tokens are viewed as its rows; the
        # view is a bitcast, as the decode kernel's
        block = (BS, D)
        k_cache, v_cache = (x.reshape(x.shape[0], NB, *block)
                            for x in (k_cache, v_cache))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(spans,),
        in_specs=[
            pl.BlockSpec((span, H, D), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((span, H, D), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            # a row adds a tile of the largest size per ``big`` tokens it
            # holds of the span, and one more
            pltpu.SMEM((6, S + span // big), jnp.int32),
            pltpu.VMEM((2, tiles.kb, *block), k_cache.dtype),
            pltpu.VMEM((2, tiles.kb, *block), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2, tiles.kb)),
            pltpu.VMEM((KV, kbs, D), k_cache.dtype),
            pltpu.VMEM((KV, kbs, D), v_cache.dtype),
            pltpu.VMEM((KV, rows, D), q.dtype),
            pltpu.VMEM((KV, rows, _LANES), jnp.float32),
            pltpu.VMEM((KV, rows, _LANES), jnp.float32),
            pltpu.VMEM((KV, rows, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, tiles=tiles, group=group,
                          spans=spans,
                          **({"window": window} if window else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="paged_attention_prefill",
    )(layer, block_tables, q_start, chunk_start, chunk_len, q, k_cache,
      v_cache)
    return out[:T] if T % span else out
