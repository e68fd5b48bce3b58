"""Mamba-1 (selective scan) state updates of a served model: the recurrence

    h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c] + delta_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

with ONE DECAY A (CHANNEL, STATE) PAIR (``ops/pallas/ssm.py`` holds the other
recurrence, Mamba-2's, one decay a head, which is what lets that file write a
chunk as masked products on the MXU; here no product form exists and the work
is elementwise, on the VPU and the EUP).  The per-sequence state ``ssm
(layers, slots + 1, N, d_inner)`` float32 lies with THE CHANNELS ON THE LANES
and is read and written in place by ``(layer, slot)``.  Two entry points:

``selective_decode_update``  one token a slot, every slot of the layer in one
    pass, the state aliased in place: a grid step a slot, the slot's ``(N,
    d_inner)`` block read, stepped and written back.
``selective_scan``  rows of two tokens and more lying end to end in one flat
    ``(T, d_inner)`` batch.  The flat tokens are cut into blocks of ``CHUNK``
    (aligned to the batch, so the blocks are plain ``BlockSpec`` tiles), and
    each scanned row into the SEGMENTS its tokens make with those blocks; a
    grid step walks one segment token by token with the row's state held in
    VMEM: read from its slot at the row's first segment (zeros for a row that
    starts a sequence), carried in scratch between a row's segments, written
    back once at its last.  A row's state crosses HBM twice and no ``(T,
    d_inner, N)`` array exists anywhere.  ``B_t`` and ``C_t`` have to lie
    along the sublanes, broadcast over the lanes; they arrive transposed
    ``(N, T)`` and a block's ``CHUNK`` columns are spread into a VMEM table
    once a block.

Both are Pallas kernels on the chip where the shapes tile (``d_inner`` whole
lane tiles, ``N`` whole sublane tiles), and in interpret mode when a test
calls ``_scan_pallas`` / ``_decode_pallas`` itself; the entry points take the
XLA formulation of the same mathematics on the CPU by design (``xla=1`` in
the ring event) and on the chip for shapes that do not tile (``fallback=1``).
The scan's XLA formulation is a ``lax.scan`` over single tokens: the tests'
oracle's form and the CPU's path, not what the chip runs.  Each traced call
leaves ``kernel/selective_scan`` / ``kernel/selective_decode_update``; both
run under the caller's scope ``sel_scan``.  ``selective_recurrence`` is the
single-row oracle.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend

#: tokens a block of the flat batch (the lanes of the transposed B and C)
CHUNK = 128
_LANES = 128
_F32 = jnp.float32


def selective_recurrence(x, delta, A, B, C, D, state):
    """One row, one token at a time: ``x, delta (T, d_inner)`` (``delta``
    after its softplus), ``A (N, d_inner)`` negative, ``B, C (T, N)``, ``D
    (d_inner,)``, ``state (N, d_inner)`` → ``(y (T, d_inner), final state)``,
    float32.  The tests' oracle; no served program calls it."""
    def step(h, inp):
        x_t, d_t, B_t, C_t = inp
        h = jnp.exp(d_t[None] * A) * h + (d_t * x_t)[None] * B_t[:, None]
        return h, jnp.sum(h * C_t[:, None], axis=0) + D * x_t

    h, y = lax.scan(step, state.astype(_F32),
                    (x.astype(_F32), delta.astype(_F32), B.astype(_F32),
                     C.astype(_F32)))
    return y, h


def tiles(N: int, di: int) -> bool:
    """Whether the kernels' blocks tile: channels whole lane tiles, the
    state's rows whole sublane tiles."""
    return di % _LANES == 0 and N % 8 == 0


def scan_pieces(row_start, row_len, chunk: int = CHUNK):
    """Segments a row of ``row_len`` tokens from ``row_start`` makes with the
    batch's blocks of ``chunk`` (NumPy or JAX arrays; 0 for an empty row):
    what the engine's counters call the scan's pieces."""
    last = (row_start + row_len - 1) // chunk
    return (row_len > 0) * (last - row_start // chunk + 1)


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------


def _decode_kernel(meta, s_ref, d_ref, u_ref, bb_ref, cc_ref, a_ref, y_ref,
                   o_ref):
    """One slot: ``s_ref (N, d_inner)`` its state; ``d_ref, u_ref (1,
    d_inner)``: ``delta`` and ``delta x``; ``bb_ref, cc_ref (N, 128)``: ``B``
    and ``C`` along the sublanes; ``a_ref (N, d_inner)``; ``meta``: the
    layer, then whether each slot takes the step, then whether it starts."""
    N, di = s_ref.shape
    r = pl.program_id(0)
    slots = pl.num_programs(0)
    active, fresh = meta[1 + r] > 0, meta[1 + slots + r] > 0
    bb, cc = bb_ref[...], cc_ref[...]

    def tile(j, _):  # a loop: an unrolled body is traced at every start
        ln = pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES)
        old = s_ref[:, ln]
        new = (jnp.exp(d_ref[:, ln] * a_ref[:, ln])
               * jnp.where(fresh, 0.0, old) + u_ref[:, ln] * bb)
        y_ref[:, ln] = jnp.sum(new * cc, axis=0, keepdims=True)
        o_ref[:, ln] = jnp.where(active, new, old)
        return 0

    lax.fori_loop(0, di // _LANES, tile, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_pallas(ssm, layer, delta, u, B, C, A, active, fresh,
                   interpret=False):
    """The kernel's call, under a jit of its own: a step program whose
    layers call it alike traces it once, and tracing is what a served
    program pays at every start."""
    _, S1, N, di = ssm.shape
    meta = jnp.concatenate([jnp.reshape(layer, (1,)).astype(jnp.int32),
                            active.astype(jnp.int32),
                            fresh.astype(jnp.int32)])

    def by_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda r, meta: (r,) + (0,) * len(block))

    def state():
        return pl.BlockSpec((None, None, N, di),
                            lambda r, meta: (meta[0], r, 0, 0))

    def spread(v):  # (S1, N) -> (S1, N, 128): along the sublanes
        return jnp.broadcast_to(v.astype(_F32)[:, :, None], (S1, N, _LANES))

    y, ssm = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S1,),
            in_specs=[state(), by_slot(1, di), by_slot(1, di),
                      by_slot(N, _LANES), by_slot(N, _LANES),
                      pl.BlockSpec((N, di), lambda r, meta: (0, 0))],
            out_specs=[by_slot(1, di), state()]),
        out_shape=[jax.ShapeDtypeStruct((S1, 1, di), _F32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={1: 1},  # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret, name="selective_decode_update",
    )(meta, ssm, delta.reshape(S1, 1, di), u.reshape(S1, 1, di), spread(B),
      spread(C), A)
    return y.reshape(S1, di), ssm


def _decode_update_xla(ssm, layer, delta, u, B, C, A, active, fresh):
    old = lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    start = jnp.where(fresh[:, None, None], 0.0, old)
    new = (jnp.exp(delta[:, None, :] * A[None]) * start
           + u[:, None, :] * B.astype(_F32)[:, :, None])
    y = jnp.sum(new * C.astype(_F32)[:, :, None], axis=1)
    kept = jnp.where(active[:, None, None], new, old)
    return y, lax.dynamic_update_index_in_dim(ssm, kept, layer, 0)


def selective_decode_update(ssm: jax.Array, layer: jax.Array, x: jax.Array,
                            delta: jax.Array, A: jax.Array, B: jax.Array,
                            C: jax.Array, D: jax.Array, active: jax.Array,
                            fresh: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One recurrence step on every slot of ``ssm[layer]``, in place.

    ``ssm (L, S1, N, d_inner)`` float32; ``x, delta (S1, d_inner)``, ``B, C
    (S1, N)``: one token a slot, in slot order; ``A (N, d_inner)``; ``active
    (S1,)``: the slots that take the step (the others keep their state, and
    their ``y`` is never read); ``fresh (S1,)``: the slots whose token starts
    a sequence, which start from zeros whatever the slot held.
    → ``(y (S1, d_inner) float32, ssm)``."""
    S1, di = x.shape
    N = B.shape[-1]
    ok = tiles(N, di)
    use_kernel = ok and not backend.interpret()
    fell_back = not backend.interpret() and not ok
    tracer.add_event("kernel/selective_decode_update", attrs={
        "rows": S1, "d_inner": di, "n": N, "layers": ssm.shape[0],
        "in_place": 1, **({} if use_kernel else
                          {"fallback": 1} if fell_back else {"xla": 1})})
    if fell_back:
        backend.warn_fallback("selective_decode_update",
                              f"d_inner={di}, N={N} do not tile")
    xf, delta = x.astype(_F32), delta.astype(_F32)
    update = _decode_pallas if use_kernel else _decode_update_xla
    with jax.named_scope("selective_decode_update"):
        y, ssm = update(ssm, layer, delta, delta * xf, B, C, A, active, fresh)
        return y + D[None] * xf, ssm


# ---------------------------------------------------------------------------
# rows of many tokens
# ---------------------------------------------------------------------------


def _first_past(ends, at):
    """``searchsorted(ends, at, side="right")`` for sorted ``ends (R,)``,
    clipped to a row: as one comparison and a sum (a binary search is a
    loop, 34 us a layer on the chip where this is under one)."""
    return jnp.minimum(jnp.sum(ends[None, :] <= at[:, None], axis=1),
                       ends.shape[0] - 1).astype(jnp.int32)


def _segments(T_blocks: int, G: int, row_start, row_len, slots, fresh,
              scanned, layer):
    """The scan's walk as ``G`` segments in token order, one int32 array for
    the kernel's scalar memory: seven fields of ``G`` (the batch block; the
    segment's first token in it and the one past its last; 1 to read the
    state from the slot, 2 to start from zeros, 0 to go on from the scratch;
    whether to write the slot after it; the slot; whether the block is new
    to the walk), then the layer.  Segments past the walk's end are empty and
    name the walk's last block, so nothing is fetched for them."""
    i32 = jnp.int32
    Q = CHUNK
    start, end = row_start.astype(i32), (row_start + row_len).astype(i32)
    first = start // Q
    pieces = jnp.where(scanned, scan_pieces(start, row_len.astype(i32)), 0)
    ends = jnp.cumsum(pieces)
    total = ends[-1]
    g = jnp.arange(G, dtype=i32)
    live = g < total
    at = jnp.minimum(g, jnp.maximum(total - 1, 0))  # dead: the last live one
    row = _first_past(ends, at)
    k = at - (ends[row] - pieces[row])
    blk = jnp.clip(first[row] + k, 0, T_blocks - 1)
    t0 = jnp.maximum(start[row], blk * Q) - blk * Q
    t1 = jnp.minimum(end[row], (blk + 1) * Q) - blk * Q
    mode = jnp.where(k == 0, jnp.where(fresh[row], 2, 1), 0)
    store = k == pieces[row] - 1
    new_blk = jnp.concatenate([jnp.ones((1,), bool), blk[1:] != blk[:-1]])
    dead = ~live
    fields = [jnp.where(total > 0, blk, 0),
              jnp.where(dead, 0, t0), jnp.where(dead, 0, t1),
              jnp.where(dead, 0, mode), jnp.where(dead, 0, store),
              slots[row], jnp.where(dead, 0, new_blk)]
    return jnp.concatenate([f.astype(i32) for f in fields]
                           + [jnp.reshape(layer, (1,)).astype(i32)])


def _scan_kernel(meta, ssm_in, d_ref, u_ref, bt_ref, ct_ref, a_ref, y_ref,
                 ssm_out, h_scr, bb_scr, cc_scr, sem, *, G: int, width: int):
    """One segment: ``d_ref, u_ref (CHUNK, d_inner)``: the block's ``delta``
    and ``delta x``; ``bt_ref, ct_ref (N, CHUNK)``; ``a_ref (N, d_inner)``;
    ``h_scr (N, d_inner)``: the row's state between its segments; ``bb_scr,
    cc_scr (CHUNK, N, 128)``: the block's ``B`` and ``C`` along the
    sublanes."""
    N, di = h_scr.shape
    g = pl.program_id(0)
    t0, t1 = meta[G + g], meta[2 * G + g]
    mode, store = meta[3 * G + g], meta[4 * G + g]
    slot, new_blk = meta[5 * G + g], meta[6 * G + g]
    lay = meta[7 * G]

    @pl.when(new_blk == 1)
    def _spread():
        bt, ct = bt_ref[...], ct_ref[...]

        def eight(q, _):  # column q * 8 + s brought to lane s, then spread
            shift = (CHUNK - q * 8) % CHUNK
            b8, c8 = (pltpu.roll(v, shift, 1) for v in (bt, ct))
            for s in range(8):
                bb_scr[q * 8 + s] = jnp.broadcast_to(b8[:, s:s + 1],
                                                     (N, _LANES))
                cc_scr[q * 8 + s] = jnp.broadcast_to(c8[:, s:s + 1],
                                                     (N, _LANES))
            return 0

        lax.fori_loop(0, CHUNK // 8, eight, 0)

    @pl.when(mode == 1)
    def _load():
        copy = pltpu.make_async_copy(ssm_in.at[lay, slot], h_scr, sem)
        copy.start()
        copy.wait()

    @pl.when(mode == 2)
    def _zero():
        h_scr[...] = jnp.zeros_like(h_scr)

    @pl.when(t1 > t0)
    def _walk():
        # Mosaic loads no single row at a dynamic sublane: the tokens are
        # walked eight at a time, each group's tiles loaded whole and its
        # rows taken statically; a token of the group outside the segment
        # has delta 0 and input 0 (the state stands) and keeps the y it had.
        # The channels are walked ``width`` at a time (a loop, not an
        # unrolled body: every operation here is traced at each start)
        per = width // _LANES
        sub = lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)

        def channels(j, _):
            lanes = [pl.ds(pl.multiple_of(j * width + i * _LANES, _LANES),
                           _LANES) for i in range(per)]
            a = [a_ref[:, ln] for ln in lanes]

            def group(q, hs):
                base = pl.multiple_of(q * 8, 8)
                rows = pl.ds(base, 8)
                ok = (base + sub >= t0) & (base + sub < t1)
                d = [jnp.where(ok, d_ref[rows, ln], 0.0) for ln in lanes]
                u = [jnp.where(ok, u_ref[rows, ln], 0.0) for ln in lanes]
                y = [y_ref[rows, ln] for ln in lanes]
                hs = list(hs)
                for s in range(8):
                    bb, cc = bb_scr[base + s], cc_scr[base + s]
                    mine = ok & (sub == s)
                    for i in range(per):
                        hs[i] = (jnp.exp(d[i][s:s + 1] * a[i]) * hs[i]
                                 + u[i][s:s + 1] * bb)
                        y[i] = jnp.where(
                            mine, jnp.sum(hs[i] * cc, axis=0, keepdims=True),
                            y[i])
                for ln, y_i in zip(lanes, y):
                    y_ref[rows, ln] = y_i
                return tuple(hs)

            hs = lax.fori_loop(t0 // 8, (t1 + 7) // 8, group,
                               tuple(h_scr[:, ln] for ln in lanes))
            for ln, h in zip(lanes, hs):
                h_scr[:, ln] = h
            return 0

        lax.fori_loop(0, di // width, channels, 0)

    @pl.when(store == 1)
    def _store():
        copy = pltpu.make_async_copy(h_scr, ssm_out.at[lay, slot], sem)
        copy.start()
        copy.wait()


def _lane_width(di: int) -> int:
    """Channels whose state a token's walk holds in registers: four lane
    tiles where they divide ``d_inner``, else one."""
    return 4 * _LANES if di % (4 * _LANES) == 0 else _LANES


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_pallas(ssm, layer, delta, u, B, C, A, row_start, row_len, slots,
                 fresh, scanned, interpret=False):
    """(Under a jit of its own, as ``_decode_pallas``.)  → ``(y (T, d_inner)`` float32, DEFINED ONLY on the scanned rows'
    tokens; ssm)``."""
    T, di = delta.shape
    N = B.shape[-1]
    Q = CHUNK
    Tp = -(-T // Q) * Q
    blocks = Tp // Q
    # a scanned row has two tokens at least, and a row's segments are one
    # more than the block edges it crosses
    G = blocks + max(1, min(row_len.shape[0], T // 2))
    meta = _segments(blocks, G, row_start, row_len, slots, fresh, scanned,
                     layer)

    def pad(a):
        return jnp.pad(a, ((0, Tp - T), (0, 0))) if Tp > T else a

    def by_block(g, meta):
        return (meta[g], 0)

    def by_block_t(g, meta):
        return (0, meta[g])

    whole = pl.BlockSpec(memory_space=pl.ANY)
    y, ssm = pl.pallas_call(
        functools.partial(_scan_kernel, G=G, width=_lane_width(di)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(G,),
            in_specs=[whole, pl.BlockSpec((Q, di), by_block),
                      pl.BlockSpec((Q, di), by_block),
                      pl.BlockSpec((N, Q), by_block_t),
                      pl.BlockSpec((N, Q), by_block_t),
                      pl.BlockSpec((N, di), lambda g, meta: (0, 0))],
            out_specs=[pl.BlockSpec((Q, di), by_block), whole],
            scratch_shapes=[pltpu.VMEM((N, di), _F32),
                            pltpu.VMEM((Q, N, _LANES), _F32),
                            pltpu.VMEM((Q, N, _LANES), _F32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=[jax.ShapeDtypeStruct((Tp, di), _F32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={1: 1},  # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=96 << 20),
        interpret=interpret, name="selective_scan",
    )(meta, ssm, pad(delta), pad(u), pad(B.astype(_F32)).T,
      pad(C.astype(_F32)).T, A)
    return y[:T], ssm


def _token_rows(T: int, row_start, row_len, scanned):
    """→ (each flat token's row, whether a scanned row holds it)."""
    t = jnp.arange(T)
    row = _first_past(row_start + row_len, t)
    return row, scanned[row] & (t >= row_start[row]) \
        & (t < row_start[row] + row_len[row])


def _scan_xla(ssm, layer, delta, u, B, C, A, row_start, row_len, slots,
              fresh, scanned):
    """The same walk as a ``lax.scan`` over the flat tokens: a scanned row's
    first token takes the slot's state (or zeros), its last writes it."""
    t = jnp.arange(delta.shape[0])
    row, inside = _token_rows(delta.shape[0], row_start, row_len, scanned)
    first = inside & (t == row_start[row])
    last = inside & (t == row_start[row] + row_len[row] - 1)
    states = lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)

    def step(carry, inp):
        states, h = carry
        d_t, u_t, B_t, C_t, r, is_first, is_last = inp
        h = jnp.where(is_first,
                      jnp.where(fresh[r], 0.0, states[slots[r]]), h)
        h = jnp.exp(d_t[None] * A) * h + u_t[None] * B_t[:, None]
        states = lax.cond(is_last, lambda s: s.at[slots[r]].set(h),
                          lambda s: s, states)
        return (states, h), jnp.sum(h * C_t[:, None], axis=0)

    (states, _), y = lax.scan(
        step, (states, jnp.zeros_like(states[0])),
        (delta, u, B.astype(_F32), C.astype(_F32), row, first, last))
    return y, lax.dynamic_update_index_in_dim(ssm, states, layer, 0)


def selective_scan(ssm: jax.Array, layer: jax.Array, x: jax.Array,
                   delta: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                   D: jax.Array, row_start: jax.Array, row_len: jax.Array,
                   slots: jax.Array, fresh: jax.Array, scanned: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """The rows ``scanned`` marks, through the recurrence.

    ``x, delta (T, d_inner)`` (``delta`` float32 after its softplus), ``B, C
    (T, N)``: the step's tokens flat, each row's end to end from
    ``row_start[r]`` for ``row_len[r]`` tokens, the rows in order; ``A (N,
    d_inner)``; ``slots (R,)``: where each row's state lives in
    ``ssm[layer]``; ``fresh (R,)``: the rows that start a sequence (zeros,
    not the slot); ``scanned (R,)``: the rows this call walks (a mixed step
    leaves its rows of one token to ``selective_decode_update``).
    → ``(y (T, d_inner) float32, zero outside the scanned rows; ssm with the
    scanned rows' final states written at their slots)``."""
    T, di = x.shape
    N = B.shape[-1]
    ok = tiles(N, di)
    use_kernel = ok and not backend.interpret()
    fell_back = not backend.interpret() and not ok
    tracer.add_event("kernel/selective_scan", attrs={
        "t": T, "chunk": CHUNK, "d_inner": di, "n": N,
        "rows": row_len.shape[0], "layers": ssm.shape[0],
        **({"lane_width": _lane_width(di)} if use_kernel else
           {"fallback": 1} if fell_back else {"xla": 1})})
    if fell_back:
        backend.warn_fallback("selective_scan",
                              f"d_inner={di}, N={N} do not tile")
    xf, delta = x.astype(_F32), delta.astype(_F32)
    walk = _scan_pallas if use_kernel else _scan_xla
    with jax.named_scope("selective_scan"):
        y, ssm = walk(ssm, layer, delta, delta * xf, B, C, A, row_start,
                      row_len, slots, fresh, scanned)
        _, inside = _token_rows(T, row_start, row_len, scanned)
        return jnp.where(inside[:, None], y + D[None] * xf, 0.0), ssm
