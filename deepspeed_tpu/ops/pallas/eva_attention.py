"""EVA attention over two paged pools, and the summariser between them.

A layer of an EVA model (``models/eva.py``) keeps two pools: the WINDOW pool,
``(L, blocks, block_size, H, D)`` K and V by token, of which a sequence holds
the blocks of its current tumbling window only, and the SUMMARY pool of the
same geometry, one entry a chunk of every window the sequence has closed.
Each has its own block table: the window's is indexed by logical block
(``position // block_size``: the entries of closed windows are stale and
never read), the summaries' by ``entry // block_size``.

**Attention** (``eva_prefill_attention``, ``eva_decode_attention``): ONE
kernel body, ``paged_attention.py``'s prefill kernel with a second source.  A
tile of queries (all of one window: the scheduler ends a row's chunk at the
window's edge) walks the blocks of its window up to its newest query, causal,
and then the blocks of its row's summaries, every entry of a closed window
visible, with the one running maximum, sum and accumulator in VMEM across
both: one softmax over the union, which two calls of a kernel that
normalises inside could not give.  The decode step hands it one query a row,
laid eight slots apart (a sublane group: what a decode row riding in a mixed
step costs there too).

**The summariser** (``eva_summarize``): for each row whose window the step
just completed, the window's K and V are read once from the window pool,
block by block, and ``window // chunk`` summaries a head written to the
row's next blocks of the summary pool, in place.  A row that closes nothing
costs a scalar compare.

Shapes off the (8, 128) tiling (the tier-1 tests' toy heads) run the
``_xla`` twins outside interpret mode, and leave ``fallback`` in their ring
event.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...models.eva import summarize
from ...observability.trace import tracer
from . import backend
from .paged_attention import (_LANES, PrefillTiles, _across, _layer_operand,
                              _token_rows, pick_prefill_tiles)

#: query slots a decode row takes: a sublane group
DECODE_SLOTS = 8


def check_geometry(window: int, chunk: int, block_size: int) -> None:
    """What both tables' indexing rests on: a window starts on a block's
    edge, a block holds whole chunks, and a window's summaries fill whole
    blocks."""
    if window % block_size or block_size % chunk \
            or (window // chunk) % block_size:
        raise ValueError(
            f"EVA attention over paged pools needs eva_window ({window}) in "
            f"whole blocks of {block_size}, a block in whole chunks of "
            f"{chunk}, and a window's {window // chunk} summaries in whole "
            f"blocks")


def _misaligned(d: int, block_size: int) -> bool:
    """Mosaic DMA slices need whole lanes and sublane groups."""
    return not backend.interpret() and (d % 128 != 0 or block_size % 8 != 0)


# ---------------------------------------------------------------------------
# attention: one softmax over a window's keys and the summaries behind it
# ---------------------------------------------------------------------------


def _attention_xla(q, k_win, v_win, k_sum, v_sum, layer, win_tables,
                   sum_tables, q_start, chunk_start, chunk_len, *,
                   window: int, chunk: int):
    """The kernel's twin on its flat operands: a scan over the blocks of each
    token's window, then over its row's summary blocks, one running softmax
    through both."""
    T, H, D = q.shape
    BS = k_win.shape[2]
    row, q_pos, held = _token_rows(T, q_start, chunk_start, chunk_len)
    win = q_pos // window
    first = win * (window // BS)  # the window's first logical block
    n_sum = win * (window // chunk)  # summaries a token sees
    qf = q.astype(jnp.float32) / math.sqrt(D)

    def walk(carry, table, k_pool, v_pool, at, visible):
        def block_step(carry, j):
            acc, m, l = carry
            blk = table[row, at(j)]
            k = k_pool[layer, blk].astype(jnp.float32)  # (T, BS, H, D)
            v = v_pool[layer, blk].astype(jnp.float32)
            scores = jnp.einsum("qhd,qthd->qht", qf, k)
            seen = visible(j * BS + jnp.arange(BS)[None, None, :])
            scores = jnp.where(seen & held[:, None, None], scores, -1e30)
            m_new = jnp.maximum(m, scores.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
            return (acc * alpha + jnp.einsum("qht,qthd->qhd", p, v), m_new,
                    l * alpha + p.sum(-1, keepdims=True)), None

        return block_step

    carry = (jnp.zeros((T, H, D), jnp.float32),
             jnp.full((T, H, 1), -1e30, jnp.float32),
             jnp.zeros((T, H, 1), jnp.float32))
    in_window = (q_pos % window)[:, None, None]
    carry, _ = jax.lax.scan(
        walk(carry, win_tables, k_win, v_win, lambda j: first + j,
             lambda off: off <= in_window), carry,
        jnp.arange(window // BS))
    carry, _ = jax.lax.scan(
        walk(carry, sum_tables, k_sum, v_sum, lambda j: j,
             lambda e: e < n_sum[:, None, None]), carry,
        jnp.arange(sum_tables.shape[1]))
    acc, _, l = carry
    return jnp.where(held[:, None, None], acc / jnp.where(l == 0, 1.0, l),
                     0.0).astype(q.dtype)


def _attention_kernel(layer_ref, win_tables_ref, sum_tables_ref, q_start_ref,
                      chunk_start_ref, chunk_len_ref,  # scalar prefetch
                      q_ref, k_win, v_win, k_sum, v_sum,  # queries; the pools
                      o_ref,
                      tiles_ref, k_buf, v_buf, copy_sems, kt_ref, vt_ref,
                      qt_ref, m_ref, l_ref, acc_ref,  # scratch
                      *, tiles: PrefillTiles, window: int, chunk: int):
    """``paged_attention._prefill_kernel`` for one span of queries, one K/V
    head a query head, and two sources a tile: the steps of its window's
    blocks, then the steps of its summaries' (see the module text)."""
    span, H, D = q_ref.shape
    _, kb, BS, _, _ = k_buf.shape
    S = chunk_len_ref.shape[0]
    kbs = kb * BS
    small, big = tiles.small, tiles.big
    layer = layer_ref[0]
    scale = 1.0 / math.sqrt(D)

    # -- the tiles, in row order, a column of ``tiles_ref`` each: (row, first
    # query of the row, queries held, the window's first block and the block
    # of the tile's newest query, whether the tile is a ``big`` one, the
    # summaries its queries see)
    def add_row(s, n_tiles):
        n, start = chunk_len_ref[s], chunk_start_ref[s]
        body = jax.lax.div(n, big)
        win = jax.lax.div(start, window)  # the chunk lies in one window

        def add(at, off, cnt, cls):
            for i, x in enumerate((s, off, cnt, win * (window // BS),
                                   jax.lax.div(start + off + cnt - 1, BS),
                                   cls, win * (window // chunk))):
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

    def copies(k_hbm, v_hbm, slot, c, blk):
        return [pltpu.make_async_copy(hbm.at[layer, blk], buf.at[slot, c],
                                      copy_sems.at[slot, kv, c])
                for hbm, buf, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))]

    def window_steps(w):
        return jax.lax.div(tiles_ref[4, w] - tiles_ref[3, w] + kb, kb)

    def summary_blocks(w):
        return jax.lax.div(tiles_ref[6, w] + BS - 1, BS)

    def fetch(w, step, slot):
        """Start the DMAs of tile ``w``'s ``step``-th ``kb`` blocks into
        ``slot``: of its window while ``step`` counts those, then of its
        summaries.  Past a source's last block the last is fetched again:
        what it holds then sits where the mask sees nothing."""
        s, first, last = tiles_ref[0, w], tiles_ref[3, w], tiles_ref[4, w]
        nw = window_steps(w)

        @pl.when(step < nw)
        def _window():
            def one(c, _):
                blk = win_tables_ref[s, jnp.minimum(first + step * kb + c,
                                                    last)]
                for dma in copies(k_win, v_win, slot, c, blk):
                    dma.start()
                return 0

            jax.lax.fori_loop(0, kb, one, 0)

        @pl.when(step >= nw)
        def _summaries():
            top = summary_blocks(w) - 1

            def one(c, _):
                blk = sum_tables_ref[s, jnp.minimum((step - nw) * kb + c,
                                                    top)]
                for dma in copies(k_sum, v_sum, slot, c, blk):
                    dma.start()
                return 0

            jax.lax.fori_loop(0, kb, one, 0)

    def await_fetch(slot):
        def one(c, _):
            for dma in copies(k_win, v_win, slot, c, 0):  # the size alone
                dma.wait()
            return 0

        jax.lax.fori_loop(0, kb, one, 0)

    def run_tile(w, g, tq: int):
        """One tile of ``tq`` queries, head by head; ``g`` counts the fetches
        (the DMA slots alternate)."""
        s, off, cnt, first = (tiles_ref[i, w] for i in range(4))
        n_sum = tiles_ref[6, w]
        nw = window_steps(w)
        n_steps = nw + jax.lax.div(summary_blocks(w) + kb - 1, kb)
        tok0 = q_start_ref[s] + off
        w0 = jnp.minimum(tok0, span - tq)
        shift = tok0 - w0
        # (tq, H, D) -> (H, tq, D)
        qt_ref[:, :tq] = jnp.swapaxes(q_ref[pl.ds(w0, tq)], 0, 1)
        m_ref[:, :tq] = jnp.full((H, tq, _LANES), -jnp.inf, jnp.float32)
        l_ref[:, :tq] = jnp.zeros((H, tq, _LANES), jnp.float32)
        acc_ref[:, :tq] = jnp.zeros((H, tq, D), jnp.float32)
        hb = H if tq <= 64 else math.gcd(H, 4)
        slot_q = jax.lax.broadcasted_iota(jnp.int32, (tq, kbs), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tq, kbs), 1)
        held = (slot_q >= shift) & (slot_q < shift + cnt)
        q_abs = chunk_start_ref[s] + off + slot_q - shift

        def step(i, g):
            slot = jax.lax.rem(g, 2)
            more = i + 1 < n_steps

            @pl.when(more | (w + 1 < n_tiles))
            def _prefetch():
                fetch(jnp.where(more, w, w + 1), jnp.where(more, i + 1, 0),
                      1 - slot)

            await_fetch(slot)
            kt_ref[...] = jnp.swapaxes(k_buf[slot].reshape(kbs, H, D), 0, 1)
            vt_ref[...] = jnp.swapaxes(v_buf[slot].reshape(kbs, H, D), 0, 1)
            # a window step's columns are positions, a summary step's entries
            in_window = i < nw
            at = jnp.where(in_window, (first + i * kb) * BS,
                           (i - nw) * kbs) + col
            keep = held & (at < jnp.where(in_window, q_abs + 1, n_sum))
            bias = jnp.where(keep, 0.0, -jnp.inf)

            def heads(j, _):
                hs = pl.ds(j * hb, hb)
                scores = jax.lax.dot_general(
                    qt_ref[hs, :tq], kt_ref[hs],
                    (((2,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32) * scale + bias
                m_prev = m_ref[hs, :tq]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(scores, axis=2, keepdims=True))
                m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
                alpha = jnp.exp(m_prev - m_safe)
                p = jnp.exp(scores - _across(m_safe, kbs))
                l_ref[hs, :tq] = l_ref[hs, :tq] * alpha + jnp.sum(
                    p, axis=2, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(vt_ref.dtype), vt_ref[hs],
                    (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32)
                acc_ref[hs, :tq] = (acc_ref[hs, :tq] * _across(alpha, D)
                                    + pv)
                m_ref[hs, :tq] = m_new
                return 0

            jax.lax.fori_loop(0, H // hb, heads, 0)
            return g + 1

        g = jax.lax.fori_loop(0, n_steps, step, g)
        l = l_ref[:, :tq, :1]
        out = acc_ref[:, :tq] / jnp.where(l == 0.0, 1.0, l)
        out = jnp.swapaxes(out.astype(o_ref.dtype), 0, 1)
        slot = jax.lax.broadcasted_iota(jnp.int32, (tq, H, D), 0)
        o_ref[pl.ds(w0, tq)] = jnp.where(
            (slot >= shift) & (slot < shift + cnt), out, o_ref[pl.ds(w0, tq)])
        return g

    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(n_tiles > 0)
    def _start_first():
        fetch(0, 0, 0)

    def run(w, g):
        if small == big:
            return run_tile(w, g, big)
        return jax.lax.cond(tiles_ref[5, w] == 1,
                            functools.partial(run_tile, w, tq=big),
                            functools.partial(run_tile, w, tq=small), g)

    jax.lax.fori_loop(0, n_tiles, run, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("tiles", "window", "chunk",
                                             "name", "interpret"))
def _attention_pallas(q, k_win, v_win, k_sum, v_sum, layer, win_tables,
                      sum_tables, q_start, chunk_start, chunk_len, *,
                      tiles: PrefillTiles, window: int, chunk: int, name: str,
                      interpret: bool):
    """The kernel's call, under a jit of its own (a layer scan's body traces
    it once)."""
    T, H, D = q.shape
    BS = k_win.shape[2]
    S = chunk_len.shape[0]
    big, kbs = tiles.big, tiles.kb * BS
    pool = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(1,),
        in_specs=[pl.BlockSpec((T, H, D), lambda i, *_: (0, 0, 0)),
                  pool, pool, pool, pool],
        out_specs=pl.BlockSpec((T, H, D), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((7, S + T // big), jnp.int32),
            pltpu.VMEM((2, tiles.kb, BS, H, D), k_win.dtype),
            pltpu.VMEM((2, tiles.kb, BS, H, D), v_win.dtype),
            pltpu.SemaphoreType.DMA((2, 2, tiles.kb)),
            pltpu.VMEM((H, kbs, D), k_win.dtype),
            pltpu.VMEM((H, kbs, D), v_win.dtype),
            pltpu.VMEM((H, big, D), q.dtype),
            pltpu.VMEM((H, big, _LANES), jnp.float32),
            pltpu.VMEM((H, big, _LANES), jnp.float32),
            pltpu.VMEM((H, big, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_attention_kernel, tiles=tiles, window=window,
                          chunk=chunk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name=name,
    )(layer, win_tables, sum_tables, q_start, chunk_start, chunk_len, q,
      k_win, v_win, k_sum, v_sum)


def prefill_tiles(t: int, heads: int, d: int, block_size: int, dtype
                  ) -> PrefillTiles:
    """The mixed step's tiling: ``paged_attention``'s picker on one span (a
    step's queries of 32 heads are 4 MiB at 512 tokens; a budget whose
    queries pass the picker's span is not served yet)."""
    tiles = pick_prefill_tiles(t, heads, heads, d, block_size, dtype)
    if tiles.span != t:
        raise NotImplementedError(
            f"EVA prefill attention holds a step's queries in one span: "
            f"{t} tokens of {heads} heads pass it")
    return tiles


def _attend(kind, q, pools, layer, win_tables, sum_tables, q_start,
            chunk_start, chunk_len, tiles, window, chunk):
    T, H, D = q.shape
    BS = pools[0].shape[2]
    fallback = _misaligned(D, BS)
    tracer.add_event("kernel/eva_attention_tiles", attrs={
        "kind": kind, "t": T, "heads": H, "d": D, "block": BS,
        "window": window, "chunk": chunk,
        **({"fallback": 1} if fallback else
           {"tq": "/".join(map(str, sorted({tiles.small, tiles.big}))),
            "kb": tiles.kb})})
    if fallback:
        backend.warn_fallback(
            f"eva_attention_{kind}",
            f"head_dim={D} is not a multiple of 128 or block_size={BS} not "
            f"a multiple of 8 (Mosaic DMA slice alignment)")
        return _attention_xla(q, *pools, layer, win_tables, sum_tables,
                              q_start, chunk_start, chunk_len, window=window,
                              chunk=chunk)
    return _attention_pallas(q, *pools, _layer_operand(layer), win_tables,
                             sum_tables, q_start, chunk_start, chunk_len,
                             tiles=tiles, window=window, chunk=chunk,
                             name=f"eva_attention_{kind}",
                             interpret=backend.interpret())


def eva_prefill_attention(q, k_win, v_win, k_sum, v_sum, layer, win_tables,
                          sum_tables, q_start, chunk_start, chunk_len, *,
                          window: int, chunk: int):
    """A mixed step's queries, flat ``(T, H, D)`` as
    ``paged_prefill_attention`` takes them (row ``s`` holds ``chunk_len[s]``
    tokens from ``q_start[s]`` on, at positions ``chunk_start[s]`` on, all in
    ONE window; their K and V already written) → ``(T, H, D)``; a token no
    row holds comes out zero."""
    T, H, D = q.shape
    tiles = prefill_tiles(T, H, D, k_win.shape[2], q.dtype)
    return _attend("prefill", q, (k_win, v_win, k_sum, v_sum), layer,
                   win_tables, sum_tables, q_start, chunk_start, chunk_len,
                   tiles, window, chunk)


def eva_decode_attention(q, k_win, v_win, k_sum, v_sum, layer, win_tables,
                         sum_tables, positions, active, *, window: int,
                         chunk: int):
    """One query a row, ``q (R, H, D)`` at ``positions (R,)`` (its K and V
    written); rows not ``active`` come out zero.  The kernel above on the
    queries laid ``DECODE_SLOTS`` apart, in tiles of that many."""
    R, H, D = q.shape
    n = DECODE_SLOTS
    BS = k_win.shape[2]
    flat = jnp.zeros((R, n, H, D), q.dtype).at[:, 0].set(q).reshape(
        R * n, H, D)
    tiles = PrefillTiles(n, n, max(1, min(4, 256 // BS)), R * n)
    out = _attend("decode", flat, (k_win, v_win, k_sum, v_sum), layer,
                  win_tables, sum_tables, jnp.arange(R, dtype=jnp.int32) * n,
                  positions, active.astype(jnp.int32), tiles, window, chunk)
    return out.reshape(R, n, H, D)[:, 0]


# ---------------------------------------------------------------------------
# the summariser: a closed window's K and V -> its chunks' summaries
# ---------------------------------------------------------------------------


def _summarize_xla(k_win, v_win, k_sum, v_sum, layer, win_tables, sum_tables,
                   closing, phi, mu, *, window: int, chunk: int):
    """The kernel's twin: every row's window gathered (a row that closes
    nothing: window 0, written to the scratch block)."""
    R = closing.shape[0]
    _, NS, BS, H, D = k_sum.shape
    nb, sb = window // BS, window // chunk // BS
    w = jnp.maximum(closing, 0)
    blocks = jnp.take_along_axis(
        win_tables, w[:, None] * nb + jnp.arange(nb)[None], 1)
    ks, vs = summarize(k_win[layer, blocks].reshape(R, window, H, D),
                       v_win[layer, blocks].reshape(R, window, H, D),
                       phi, mu, chunk)
    out = jnp.where(closing[:, None] >= 0, jnp.take_along_axis(
        sum_tables, w[:, None] * sb + jnp.arange(sb)[None], 1), NS - 1)
    return (k_sum.at[layer, out].set(
                ks.reshape(R, sb, BS, H, D).astype(k_sum.dtype)),
            v_sum.at[layer, out].set(
                vs.reshape(R, sb, BS, H, D).astype(v_sum.dtype)))


def _summarize_kernel(layer_ref, win_tables_ref, sum_tables_ref, closing_ref,
                      phi_ref, mu_ref, k_win, v_win, k_sum_in, v_sum_in,
                      k_sum, v_sum,  # the summary pool, in place
                      k_buf, v_buf, ko_buf, vo_buf, sems,
                      *, window: int, chunk: int):
    del k_sum_in, v_sum_in  # aliased to the outputs
    BS, H, D = k_buf.shape
    r = pl.program_id(0)
    layer, w = layer_ref[0], closing_ref[r]
    nb, sb = window // BS, window // chunk // BS
    per = BS // chunk  # summaries a window block gives
    scale = 1.0 / math.sqrt(D)

    @pl.when(w >= 0)
    def _close():
        phi = phi_ref[...].astype(jnp.float32)
        mu = mu_ref[...].astype(jnp.float32)

        def one_block(i, ob):
            """Window block ``i`` of the ``chunk`` that fill summary block
            ``ob``: ``per`` summaries into the block's buffer."""
            blk = win_tables_ref[r, w * nb + ob * chunk + i]
            fetch = [pltpu.make_async_copy(hbm.at[layer, blk], buf,
                                           sems.at[j])
                     for j, (hbm, buf) in enumerate(((k_win, k_buf),
                                                     (v_win, v_buf)))]
            for dma in fetch:
                dma.start()
            for dma in fetch:
                dma.wait()
            k = k_buf[...].astype(jnp.float32).reshape(per, chunk, H, D)
            v = v_buf[...].astype(jnp.float32).reshape(per, chunk, H, D)
            # a chunk's tokens one at a time: slices of a leading dimension
            scores = [jnp.sum(k[:, c] * phi, axis=-1, keepdims=True) * scale
                      for c in range(chunk)]  # (per, H, 1) each
            top = functools.reduce(jnp.maximum, scores)
            e = [jnp.exp(s - top) for s in scores]
            den = functools.reduce(jnp.add, e)
            vs = functools.reduce(
                jnp.add, [e[c] * v[:, c] for c in range(chunk)]) / den
            ks = functools.reduce(
                jnp.add, [k[:, c] for c in range(chunk)]) / chunk + mu
            ko_buf[pl.ds(i * per, per)] = ks.astype(ko_buf.dtype)
            vo_buf[pl.ds(i * per, per)] = vs.astype(vo_buf.dtype)
            return ob

        for ob in range(sb):
            jax.lax.fori_loop(0, chunk, one_block, ob)
            out = sum_tables_ref[r, w * sb + ob]
            store = [pltpu.make_async_copy(buf, hbm.at[layer, out],
                                           sems.at[j])
                     for j, (buf, hbm) in enumerate(((ko_buf, k_sum),
                                                     (vo_buf, v_sum)))]
            for dma in store:
                dma.start()
            for dma in store:
                dma.wait()


@functools.partial(jax.jit, static_argnames=("window", "chunk", "interpret"))
def _summarize_pallas(k_win, v_win, k_sum, v_sum, layer, win_tables,
                      sum_tables, closing, phi, mu, *, window: int,
                      chunk: int, interpret: bool):
    R = closing.shape[0]
    _, _, BS, H, D = k_win.shape
    pool = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    vec = pl.BlockSpec((H, D), lambda r, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(R,),
        in_specs=[vec, vec, pool, pool, pool, pool],
        out_specs=[pool, pool],
        scratch_shapes=[
            pltpu.VMEM((BS, H, D), k_win.dtype),
            pltpu.VMEM((BS, H, D), v_win.dtype),
            pltpu.VMEM((BS, H, D), k_sum.dtype),
            pltpu.VMEM((BS, H, D), v_sum.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_summarize_kernel, window=window, chunk=chunk),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_sum.shape, k_sum.dtype),
                   jax.ShapeDtypeStruct(v_sum.shape, v_sum.dtype)],
        # operands: 4 prefetched, phi, mu, k_win, v_win, then the two pools
        input_output_aliases={8: 0, 9: 1},
        interpret=interpret,
        name="eva_summarize",
    )(layer, win_tables, sum_tables, closing, phi, mu, k_win, v_win, k_sum,
      v_sum)


def eva_summarize(k_win, v_win, k_sum, v_sum, layer, win_tables, sum_tables,
                  closing, phi, mu, *, window: int, chunk: int):
    """For each row ``r`` with ``closing[r] = w >= 0`` (the window the step
    completed; -1: none): the window's K and V, read from the window pool
    through ``win_tables``, → its ``window // chunk`` summaries (``phi``,
    ``mu``: the layer's ``(H, D)`` vectors), written to the summary pool at
    the row's entries ``w * (window // chunk)`` on, through ``sum_tables``
    → ``(k_sum, v_sum)``."""
    _, _, BS, H, D = k_win.shape
    fallback = _misaligned(D, BS)
    tracer.add_event("kernel/eva_summarize_tiles", attrs={
        "heads": H, "d": D, "block": BS, "window": window, "chunk": chunk,
        **({"fallback": 1} if fallback else
           {"blocks_read": window // BS,
            "blocks_written": window // chunk // BS})})
    if fallback:
        backend.warn_fallback(
            "eva_summarize",
            f"head_dim={D} is not a multiple of 128 or block_size={BS} not "
            f"a multiple of 8 (Mosaic DMA slice alignment)")
        return _summarize_xla(k_win, v_win, k_sum, v_sum, layer, win_tables,
                              sum_tables, closing, phi, mu, window=window,
                              chunk=chunk)
    return _summarize_pallas(k_win, v_win, k_sum, v_sum,
                             _layer_operand(layer), win_tables, sum_tables,
                             closing, phi, mu, window=window, chunk=chunk,
                             interpret=backend.interpret())
