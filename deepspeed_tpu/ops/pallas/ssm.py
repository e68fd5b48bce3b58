"""Mamba-2 (SSD) state updates of a served model (which recurrence: ONE
DECAY A HEAD; Mamba-1's, one decay a (channel, state) pair, is
``ops/pallas/selective_scan.py``): the recurrence

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]

over the per-sequence state ``ssm (layers, slots + 1, H, P, N)`` float32 that
the v2 engine keeps beside the paged K/V (``inference/v2/programs.py``), read
and written in place by ``(layer, slot)``.  Two entry points:

``ssm_decode_update``  one token a slot, every slot of the layer in ONE dense
    pass (a slot is a row of the engine's table, so the rows already lie in
    slot order and nothing is gathered): the layer's states are read once
    and written once, which is all the HBM traffic the step needs.
``ssd_chunk_scan``  rows of many tokens (a chunk of prefill, a training
    sequence) lying end to end in one flat ``(T, ...)`` batch: the chunked
    SSD form, chunk ``Q``.  Each row is cut into pieces of at most ``Q``
    tokens from ITS start, and the pieces are walked in order: inside a
    piece the masked ``C B^T`` products with the decay (batched MXU
    products), between the pieces of a row the carried ``(H, P, N)`` state,
    at a row's first piece the state of its slot (zeros for a row that
    starts a sequence), after every piece the write-back.  The walk is a
    ``while`` over the pieces there are, not over the most there could be,
    so a step of 60 decode rows and two prompts walks a handful.

``ssm_decode_update`` is a Pallas kernel on the chip: one grid step a slot,
the slot's ``(H, P, N)`` state block read, stepped and written back to the
same block (``input_output_aliases``), so a layer's states cross HBM twice
and no more (the XLA formulation reads them twice and writes them once: its
reduction for ``y`` and its update do not fuse).  ``x`` has to multiply the
state along the sublanes (``P``) and arrives with ``P`` on the lanes; Mosaic
has no such relayout, so the kernel spreads it with the MXU: ``x^T (outer)
ones`` is exact for bfloat16 ``x``.  On the CPU (the interpreter) and for
shapes that do not tile it is the XLA formulation of the same mathematics.
``ssd_chunk_scan`` is an XLA formulation (batched products a chunk, no scan
over single tokens).  Both run under the caller's scope ``ssm_scan``; each
traced call leaves a ring event (``kernel/ssm_decode_update``,
``kernel/ssd_chunk_scan_tiles``; ``xla=1`` for an XLA formulation by design,
``fallback=1`` where a kernel gave way), which ``PERF.md`` section 6 accounts
for with the chip's numbers.  ``ssm_recurrence`` is the single-token
recurrence itself, the oracle of the tests.
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

_HI = lax.Precision.HIGHEST


def _heads(g_arr: jax.Array, heads: int) -> jax.Array:
    """``(..., G, N)`` → ``(..., H, N)``: head ``h`` reads group ``h // (H / G)``."""
    return jnp.repeat(g_arr, heads // g_arr.shape[-2], axis=-2)


def ssm_recurrence(x, dt, A, B, C, D, state):
    """The recurrence one token at a time (``lax.scan``): ``x (T, H, P)``,
    ``dt (T, H)`` after its softplus, ``A (H,)`` negative, ``B, C (T, G, N)``,
    ``D (H,)``, ``state (H, P, N)`` → ``(y (T, H, P), final state)``, float32.
    The tests' oracle; no served program calls it."""
    H = x.shape[1]
    f32 = jnp.float32

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * x_t)[:, :, None] * _heads(B_t, H)[:, None, :])
        y = jnp.einsum("hpn,hn->hp", S, _heads(C_t, H), precision=_HI)
        return S, y + D[:, None] * x_t

    S, y = lax.scan(step, state.astype(f32),
                    (x.astype(f32), dt.astype(f32), B.astype(f32),
                     C.astype(f32)))
    return y, S


def _decode_kernel(lay_ref, s_ref, x_ref, decay_ref, b_ref, c_ref, flag_ref,
                   y_ref, o_ref, *, exact: bool):
    """One slot: ``s_ref (H, P, N)`` the state; ``x_ref (1, H * P)`` the
    token's ``x``; ``decay_ref``, ``b_ref``, ``c_ref`` ``(H, N)``: ``exp(dt
    A)`` spread over the lanes, ``dt B`` and ``C`` by head; ``flag_ref (1,
    128)``: lane 0 whether the slot starts a sequence, lane 1 whether it
    takes the step."""
    del lay_ref  # the index maps read it
    H, P, N = s_ref.shape
    old = s_ref[...]
    fresh, active = flag_ref[0:1, 0:1], flag_ref[0:1, 1:2]
    # x spread along the lanes, P on the sublanes: x^T (outer) ones on the MXU
    rows = jnp.concatenate(
        [x_ref[...], jnp.zeros((15, H * P), x_ref.dtype)], axis=0)
    spread = lax.dot_general(
        rows, jnp.ones((16, N), x_ref.dtype), (((0,), (0,)), ((), ())),
        precision=_HI if exact else None,
        preferred_element_type=jnp.float32).reshape(H, P, N)
    new = (old * (1.0 - fresh)[None] * decay_ref[...][:, None, :]
           + spread * b_ref[...][:, None, :])
    y_ref[...] = jnp.sum(new * c_ref[...][:, None, :], axis=-1)
    o_ref[...] = jnp.where(active[None] > 0.0, new, old)


def _decode_pallas(ssm, layer, x, decay, dtb, c, flags):
    _, S1, H, P, N = ssm.shape

    def by_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda r, lay: (r,) + (0,) * len(block))

    def state():
        return pl.BlockSpec((None, None, H, P, N),
                            lambda r, lay: (lay[0], r, 0, 0, 0))

    return pl.pallas_call(
        functools.partial(_decode_kernel, exact=x.dtype == jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S1,),
            in_specs=[state(), by_slot(1, H * P), by_slot(H, N),
                      by_slot(H, N), by_slot(H, N), by_slot(1, 128)],
            out_specs=[by_slot(H, P), state()]),
        out_shape=[jax.ShapeDtypeStruct((S1, H, P), jnp.float32),
                   jax.ShapeDtypeStruct(ssm.shape, ssm.dtype)],
        input_output_aliases={1: 1},  # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=backend.interpret(),
        name="ssm_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), ssm,
      x.reshape(S1, 1, H * P), decay, dtb, c, flags)


def decode_update_tiles(H: int, P: int, N: int) -> bool:
    """Whether the kernel's blocks tile: the state's lanes whole tiles, a
    head's rows whole sublane tiles, ``x`` flat a whole number of tiles."""
    return N % 128 == 0 and P % 8 == 0 and (H * P) % 128 == 0


def ssm_decode_update(ssm: jax.Array, layer: jax.Array, x: jax.Array,
                      dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                      D: jax.Array, active: jax.Array, fresh: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """One recurrence step on every slot of ``ssm[layer]``, in place.

    ``ssm (L, S1, H, P, N)`` float32; ``x (S1, H, P)``, ``dt (S1, H)``
    float32, ``B, C (S1, G, N)``: one token a slot, in slot order; ``active
    (S1,)``: the slots that take the step (the others keep their state, and
    their ``y`` is never read); ``fresh (S1,)``: the slots whose token starts
    a sequence, which start from zeros whatever the slot held.
    → ``(y (S1, H, P) float32, ssm)``.  The kernel on the chip where the
    shapes tile; the XLA formulation on the CPU by design, and on the chip as
    a fallback that only shapes that do not tile take."""
    S1, H, P = x.shape
    N = B.shape[-1]
    tiles = decode_update_tiles(H, P, N)
    use_kernel = tiles and not backend.interpret()
    fell_back = not backend.interpret() and not tiles
    tracer.add_event("kernel/ssm_decode_update", attrs={
        "rows": S1, "heads": H, "p": P, "n": N, "layers": ssm.shape[0],
        "in_place": 1, **({} if use_kernel else
                          {"fallback": 1} if fell_back else {"xla": 1})})
    if fell_back:
        backend.warn_fallback("ssm_decode_update",
                              f"H={H}, P={P}, N={N} do not tile")
    update = _decode_update_kernel if use_kernel else _decode_update_xla
    with jax.named_scope("ssm_decode_update"):
        return update(ssm, layer, x, dt, A, B, C, D, active, fresh)


def _decode_update_kernel(ssm, layer, x, dt, A, B, C, D, active, fresh):
    S1, H, _ = x.shape
    N = B.shape[-1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.broadcast_to(jnp.exp(dt * A)[:, :, None], (S1, H, N))
    flags = jnp.zeros((S1, 1, 128), f32).at[:, 0, 0].set(
        fresh.astype(f32)).at[:, 0, 1].set(active.astype(f32))
    y, ssm = _decode_pallas(
        ssm, layer, x, decay, dt[:, :, None] * _heads(B.astype(f32), H),
        _heads(C.astype(f32), H), flags)
    return y + D[None, :, None] * x.astype(f32), ssm


def _decode_update_xla(ssm, layer, x, dt, A, B, C, D, active, fresh):
    H = x.shape[1]
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    old = lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    start = jnp.where(fresh[:, None, None, None], 0.0, old)
    new = (start * jnp.exp(dt * A)[:, :, None, None]
           + (dt[:, :, None] * xf)[..., None]
           * _heads(B.astype(f32), H)[:, :, None, :])
    y = jnp.sum(new * _heads(C.astype(f32), H)[:, :, None, :], axis=-1)
    y = y + D[None, :, None] * xf
    kept = jnp.where(active[:, None, None, None], new, old)
    return y, lax.dynamic_update_index_in_dim(ssm, kept, layer, 0)


def ssd_chunk_scan(ssm: jax.Array, layer: jax.Array, x: jax.Array,
                   dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
                   D: jax.Array, row_start: jax.Array, row_len: jax.Array,
                   slots: jax.Array, fresh: jax.Array, scanned: jax.Array,
                   chunk: int) -> Tuple[jax.Array, jax.Array]:
    """The rows ``scanned`` marks, through the recurrence, chunk by chunk.

    ``x (T, H, P)``, ``dt (T, H)`` float32 after its softplus, ``B, C (T, G,
    N)``: the step's tokens flat, each row's end to end from ``row_start[r]``
    for ``row_len[r]`` tokens; ``slots (R,)``: where each row's state lives in
    ``ssm[layer]``; ``fresh (R,)``: the rows that start a sequence (zeros, not
    the slot); ``scanned (R,)``: the rows this call walks (a mixed step leaves
    its rows of one token to ``ssm_decode_update``).
    → ``(y (T, H, P) float32, zero outside the scanned rows; ssm with the
    scanned rows' final states written at their slots)``."""
    T, H, P = x.shape
    G, N = B.shape[-2:]
    Q = chunk
    tracer.add_event("kernel/ssd_chunk_scan_tiles", attrs={
        "t": T, "chunk": Q, "heads": H, "p": P, "n": N, "groups": G,
        "grid_steps": -(-T // Q) + row_len.shape[0], "xla": 1})
    f32, dt_c = jnp.float32, x.dtype
    pieces = jnp.where(scanned, -(-row_len // Q), 0).astype(jnp.int32)
    ends = jnp.cumsum(pieces)

    def pad(a):  # a window of Q tokens may start at any token
        return jnp.pad(a, ((0, Q),) + ((0, 0),) * (a.ndim - 1))

    xp, dtp, Bp, Cp = pad(x), pad(dt.astype(f32)), pad(B), pad(C)
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    hg = H // G

    def piece(i, carry):
        y_all, ssm, S = carry
        row = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        k = i - (ends[row] - pieces[row])
        start = row_start[row] + k * Q
        n = jnp.minimum(Q, row_len[row] - k * Q)
        live = jnp.arange(Q) < n
        xw = lax.dynamic_slice_in_dim(xp, start, Q)
        Bw = lax.dynamic_slice_in_dim(Bp, start, Q)
        Cw = lax.dynamic_slice_in_dim(Cp, start, Q)
        # a token past the piece has dt 0: no decay, no input, and the
        # cumulative decay stays where the piece's last token left it
        dtw = jnp.where(live[:, None], lax.dynamic_slice_in_dim(dtp, start, Q),
                        0.0)
        slot = slots[row]
        S_in = jnp.where(
            k == 0,
            jnp.where(fresh[row], 0.0, ssm[layer, slot]),
            S)
        cs = jnp.cumsum(dtw * A, axis=0)  # (Q, H), inclusive, <= 0
        # inside the piece: y_t += sum_{s <= t} exp(cs_t - cs_s) (C_t.B_s) dt_s x_s
        cb = jnp.einsum("tgn,sgn->gts", Cw, Bw,
                        preferred_element_type=f32)  # (G, Q, Q)
        decay = jnp.exp(jnp.where(tri[None], cs.T[:, :, None] - cs.T[:, None, :],
                                  -jnp.inf))  # (H, Q, Q)
        w = (decay * dtw.T[:, None, :]).reshape(G, hg, Q, Q) * cb[:, None]
        y = jnp.einsum("hts,shp->thp", w.reshape(H, Q, Q).astype(dt_c), xw,
                       preferred_element_type=f32)
        # from the state the piece starts with (by group: B and C are a
        # group's, the state a head's), and D's skip
        y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
            "tgn,ghpn->tghp", Cw.astype(f32), S_in.reshape(G, hg, P, N),
            precision=_HI).reshape(Q, H, P)
        y = y + D[None, :, None] * xw.astype(f32)
        # the state the piece ends with
        left = jnp.exp(cs[-1][None] - cs) * dtw  # (Q, H)
        S_out = (jnp.exp(cs[-1])[:, None, None] * S_in
                 + jnp.einsum(
                     "sghp,sgn->ghpn",
                     (left[:, :, None] * xw.astype(f32)).reshape(Q, G, hg, P),
                     Bw.astype(f32), precision=_HI).reshape(H, P, N))
        seen = lax.dynamic_slice_in_dim(y_all, start, Q)
        y_all = lax.dynamic_update_slice_in_dim(
            y_all, jnp.where(live[:, None, None], y, seen), start, 0)
        ssm = ssm.at[layer, slot].set(S_out)
        return y_all, ssm, S_out

    with jax.named_scope("ssd_chunk_scan"):
        y_all, ssm, _ = lax.fori_loop(
            0, ends[-1], piece,
            (jnp.zeros((T + Q, H, P), f32), ssm, jnp.zeros((H, P, N), f32)))
    return y_all[:T], ssm
