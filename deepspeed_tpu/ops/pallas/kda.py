"""Kimi Delta Attention (KDA) state updates of a served model: the recurrence

    S_t[h] = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1}[h] + b_t k_t v_t^T
    o_t[h] = S_t[h]^T q_t

(a head ``h``; ``S (d_k, d_v)``, the gate ``a_t`` in (0, 1) a CHANNEL of
``d_k``, ``b_t`` a scalar, the decay BEFORE the delta correction) over the
per-sequence state ``kda (layers, slots + 1, H, d_k, d_v)`` float32 that the
v2 engine keeps beside the latent pool (``inference/v2/programs.py``), read
and written in place by ``(layer, slot)``: what ``ssm.py`` is to Mamba-2's
decay.  With ``S~ = Diag(a_t) S_{t-1}`` a step is ``u = b_t (v_t - S~^T
k_t)``, ``S_t = S~ + k_t u^T``.  Two entry points:

``kda_decode_update``  one token a slot, every slot of the layer in ONE dense
    pass (a slot is a row of the engine's table): the layer's states are read
    once and written once.  A Pallas kernel on the chip after
    ``ssm._decode_kernel``: one grid step a slot, the slot's ``(H, d_k,
    d_v)`` block read, stepped and written back to the same block
    (``input_output_aliases``).  ``a``, ``k`` and ``q`` multiply the state
    along the sublanes (``d_k``) and arrive with ``d_k`` on the lanes; the
    kernel spreads them with the MXU, ``x^T (outer) ones``, which is exact
    for the three bfloat16 pieces a float32 is cut into outside (the decay
    compounds over thousands of steps: one bfloat16 piece of it would not
    do).  On the CPU (the interpreter) by design, and for shapes that do not
    tile, the XLA formulation of the same mathematics.
``kda_chunk_scan``  rows of many tokens lying end to end in one flat ``(T,
    ...)`` batch, each cut into pieces of at most ``chunk`` tokens from ITS
    start and the pieces walked in order (``ssm.ssd_chunk_scan``'s walk: a
    ``while`` over the pieces there are).  Inside a piece the WY / UT form
    with the cumulative gate ``g_t = sum_{i <= t} log a_i``:

        A[t, s]  = sum_d k_t[d] k_s[d] exp(g_t[d] - g_s[d])      s <  t
        Aq[t, s] = sum_d q_t[d] k_s[d] exp(g_t[d] - g_s[d])      s <= t
        U = (I + Diag(b) A)^-1 Diag(b) (V - (K * exp(g)) S_0)   (blocked
            forward substitution, ``_solve_unit_lower``)
        O = (Q * exp(g)) S_0 + Aq U
        S_C = exp(g_C) * S_0 + (K * exp(g_C - g))^T U

    Every exponent is a difference ``g_t - g_s`` with ``s <= t`` or ``g_t``
    itself, so none is positive whatever the gate (a product ``exp(g_t) x
    exp(-g_s)`` on the MXU overflows once a channel decays by e^88 inside a
    piece; the pairwise form is a pass of the VPU over ``(chunk, chunk,
    d_k)`` a head).  An XLA formulation, every product at the highest
    precision.

Both run under the caller's scopes ``kda_decode_update`` / ``kda_chunk_scan``;
each traced call leaves a ring event (``kernel/kda_decode_update``,
``kernel/kda_chunk_scan_tiles``; ``xla=1`` for an XLA formulation by design,
``fallback=1`` where the kernel gave way).  ``kda_recurrence`` is the
recurrence a token at a time, the oracle of the tests.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend

_HI = lax.Precision.HIGHEST
#: float32 as this many bfloat16 pieces (8 + 8 + 8 bits of mantissa)
_PIECES = 3


def kda_recurrence(q, k, v, log_a, b, state):
    """The recurrence one token at a time (``lax.scan``): ``q, k, log_a (T,
    H, d_k)``, ``v (T, H, d_v)``, ``b (T, H)``, ``state (H, d_k, d_v)`` →
    ``(o (T, H, d_v), final state)``, float32.  The tests' oracle; no served
    program calls it."""
    f32 = jnp.float32

    def step(S, inp):
        q_t, k_t, v_t, la_t, b_t = inp
        S = S * jnp.exp(la_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t,
                                             precision=_HI))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=_HI)

    S, o = lax.scan(step, state.astype(f32),
                    tuple(x.astype(f32) for x in (q, k, v, log_a, b)))
    return o, S


# ---------------------------------------------------------------------------
# one token a slot
# ---------------------------------------------------------------------------


def _decode_kernel(lay_ref, s_ref, akq_ref, v_ref, b_ref, flag_ref, o_ref,
                   new_ref):
    """One slot: ``s_ref (H, d_k, d_v)`` the state; ``akq_ref (16, H x d_k)``
    bfloat16: the pieces of ``a``, ``k`` and ``q`` (row ``3 i + j``: piece
    ``i`` of the ``j``-th of them); ``v_ref``, ``b_ref (H, d_v)``: the token's
    ``v`` and ``b`` spread over the lanes; ``flag_ref (1, 128)``: lane 0
    whether the slot starts a sequence, lane 1 whether it takes the step."""
    del lay_ref  # the index maps read it
    H, dk, dv = s_ref.shape
    old = s_ref[...]
    fresh, active = flag_ref[0:1, 0:1], flag_ref[0:1, 1:2]
    # a, k and q spread along the lanes, d_k on the sublanes, in ONE product
    # on the MXU: column block j of the right operand sums the rows 3 i + j
    row = lax.broadcasted_iota(jnp.int32, (16, 3 * dv), 0)
    col = lax.broadcasted_iota(jnp.int32, (16, 3 * dv), 1)
    pick = ((row < 3 * _PIECES) & (lax.rem(row, 3) == lax.div(col, dv))
            ).astype(akq_ref.dtype)
    spread = lax.dot_general(akq_ref[...], pick, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    a = spread[:, :dv].reshape(H, dk, dv)
    k = spread[:, dv:2 * dv].reshape(H, dk, dv)
    q = spread[:, 2 * dv:].reshape(H, dk, dv)
    decayed = old * (1.0 - fresh)[None] * a
    u = b_ref[...] * (v_ref[...] - jnp.sum(decayed * k, axis=1))
    new = decayed + k * u[:, None, :]
    o_ref[...] = jnp.sum(new * q, axis=1)
    new_ref[...] = jnp.where(active[None] > 0.0, new, old)


def _decode_pallas(kda, layer, akq, v, b, flags, *, interpret: bool):
    _, S1, H, dk, dv = kda.shape

    def by_slot(*block):
        return pl.BlockSpec((None,) + block,
                            lambda r, lay: (r,) + (0,) * len(block))

    def state():
        return pl.BlockSpec((None, None, H, dk, dv),
                            lambda r, lay: (lay[0], r, 0, 0, 0))

    return pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(S1,),
            in_specs=[state(), by_slot(16, H * dk), by_slot(H, dv),
                      by_slot(H, dv), by_slot(1, 128)],
            out_specs=[by_slot(H, dv), state()]),
        out_shape=[jax.ShapeDtypeStruct((S1, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(kda.shape, kda.dtype)],
        input_output_aliases={1: 1},  # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="kda_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), kda, akq, v, b, flags)


def decode_update_tiles(H: int, dk: int, dv: int) -> bool:
    """Whether the kernel's blocks tile: the state's lanes whole tiles, a
    head's rows whole sublane tiles."""
    return dv % 128 == 0 and dk % 8 == 0 and (H * dk) % 128 == 0


def _pieces(x: jax.Array) -> jax.Array:
    """float32 ``(S1, ...)`` → its ``_PIECES`` bfloat16 pieces ``(S1,
    _PIECES, ...)``, which sum to it to 2^-24 of its size."""
    out, rest = [], x
    for _ in range(_PIECES):
        # (``reduce_precision``, not a cast to bfloat16 and back: XLA drops
        # such a pair where it may keep excess precision, and the rest would
        # then be zero)
        piece = lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        out.append(piece.astype(jnp.bfloat16))
        rest = rest - piece
    return jnp.stack(out, axis=1)


def decode_operands(q, k, v, log_a, b, active, fresh):
    """The kernel's operands a slot, made once outside it: → ``(akq (S1, 16,
    H x d_k)`` bfloat16, ``v (S1, H, d_v)``, ``b`` over the lanes, ``flags
    (S1, 1, 128))``."""
    S1, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    three = jnp.stack([jnp.exp(log_a.astype(f32)), k.astype(f32),
                       q.astype(f32)], axis=1).reshape(S1, 3, H * dk)
    # row 3 i + j: piece i of the j-th of (a, k, q)
    akq = _pieces(three).reshape(S1, 3 * _PIECES, H * dk)
    akq = jnp.pad(akq, ((0, 0), (0, 16 - 3 * _PIECES), (0, 0)))
    flags = jnp.zeros((S1, 1, 128), f32).at[:, 0, 0].set(
        fresh.astype(f32)).at[:, 0, 1].set(active.astype(f32))
    return (akq, v.astype(f32),
            jnp.broadcast_to(b.astype(f32)[:, :, None], (S1, H, dv)), flags)


def kda_decode_update(kda: jax.Array, layer: jax.Array, q: jax.Array,
                      k: jax.Array, v: jax.Array, log_a: jax.Array,
                      b: jax.Array, active: jax.Array, fresh: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """One recurrence step on every slot of ``kda[layer]``, in place.

    ``kda (L, S1, H, d_k, d_v)`` float32; ``q, k, log_a (S1, H, d_k)``, ``v
    (S1, H, d_v)``, ``b (S1, H)`` float32: one token a slot, in slot order;
    ``active (S1,)``: the slots that take the step (the others keep their
    state, and their ``o`` is never read); ``fresh (S1,)``: the slots whose
    token starts a sequence, which start from zeros whatever the slot held.
    → ``(o (S1, H, d_v) float32, kda)``.  The kernel on the chip where the
    shapes tile; the XLA formulation on the CPU by design, and on the chip as
    a fallback that only shapes that do not tile take."""
    S1, H, dk = q.shape
    dv = v.shape[-1]
    tiles = decode_update_tiles(H, dk, dv)
    use_kernel = tiles and not backend.interpret()
    fell_back = not backend.interpret() and not tiles
    tracer.add_event("kernel/kda_decode_update", attrs={
        "rows": S1, "heads": H, "dk": dk, "dv": dv, "layers": kda.shape[0],
        "in_place": 1, **({} if use_kernel else
                          {"fallback": 1} if fell_back else {"xla": 1})})
    if fell_back:
        backend.warn_fallback("kda_decode_update",
                              f"H={H}, d_k={dk}, d_v={dv} do not tile")
    with jax.named_scope("kda_decode_update"):
        if use_kernel:
            return _decode_pallas(
                kda, layer, *decode_operands(q, k, v, log_a, b, active,
                                             fresh), interpret=False)
        return _decode_update_xla(kda, layer, q, k, v, log_a, b, active,
                                  fresh)


def _decode_update_xla(kda, layer, q, k, v, log_a, b, active, fresh):
    f32 = jnp.float32
    q, k, v, log_a, b = (x.astype(f32) for x in (q, k, v, log_a, b))
    old = lax.dynamic_index_in_dim(kda, layer, 0, keepdims=False)
    start = jnp.where(fresh[:, None, None, None], 0.0, old)
    decayed = start * jnp.exp(log_a)[..., None]
    u = b[..., None] * (v - jnp.sum(decayed * k[..., None], axis=2))
    new = decayed + k[..., None] * u[:, :, None, :]
    o = jnp.sum(new * q[..., None], axis=2)
    kept = jnp.where(active[:, None, None, None], new, old)
    return o, lax.dynamic_update_index_in_dim(kda, kept, layer, 0)


# ---------------------------------------------------------------------------
# rows of many tokens
# ---------------------------------------------------------------------------


#: rows of a diagonal block of the triangular system, inverted a row at a time
_SOLVE_BLOCK = 16


def _solve_unit_lower(N, rhs):
    """``(I + N) U = rhs`` for strictly lower ``N (H, Q, Q)`` and ``rhs (H,
    Q, d_v)``, by blocked forward substitution: the diagonal blocks of
    ``_SOLVE_BLOCK`` rows inverted together a row at a time, then a block of
    ``U`` after the other, every product at the highest precision.  (A
    triangular solve of the library multiplies its blocks at the default
    precision on the chip; the inverse as the nilpotent ``N``'s powers, ``(I -
    N)(I + N^2)(I + N^4)...``, is exact on paper and loses every digit once a
    piece's keys are alike, as they are behind an attention layer: ``N^32``
    then holds binomials of 1e17 that have to cancel.)"""
    H, Q, _ = N.shape
    B = _SOLVE_BLOCK if Q % _SOLVE_BLOCK == 0 else Q
    blocks = [slice(i, i + B) for i in range(0, Q, B)]
    diag = jnp.stack([N[:, at, at] for at in blocks], axis=1)  # (H, nb, B, B)
    eye = jnp.eye(B, dtype=N.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:2] + (B,))]
    for t in range(1, B):  # row t of (I + diag)^-1 from the rows above it
        rows.append(eye[t] - jnp.einsum(
            "hns,hnsr->hnr", diag[:, :, t, :t], jnp.stack(rows, axis=2),
            precision=_HI))
    inv = jnp.stack(rows, axis=2)
    U = None
    for i, at in enumerate(blocks):
        r = rhs[:, at]
        if i:
            r = r - jnp.einsum("hts,hsv->htv", N[:, at, :at.start], U,
                               precision=_HI)
        u = jnp.einsum("hts,hsv->htv", inv[:, i], r, precision=_HI)
        U = u if U is None else jnp.concatenate([U, u], axis=1)
    return U


def chunk_piece(q, k, v, log_a, b, S_in):
    """One piece of ``Q`` tokens of one row by the module text's form: ``q,
    k, log_a (Q, H, d_k)``, ``v (Q, H, d_v)``, ``b (Q, H)`` float32 (a token
    past the piece: ``log_a`` 0 and ``b`` 0), ``S_in (H, d_k, d_v)`` →
    ``(o (Q, H, d_v), the state after the piece)``."""
    Q = q.shape[0]
    g = jnp.cumsum(log_a, axis=0)  # (Q, H, dk), inclusive, <= 0
    # pairwise decays: no exponent is positive (see the module text)
    low = jnp.tril(jnp.ones((Q, Q), bool))
    diff = jnp.where(low[:, :, None, None], g[:, None] - g[None, :], -jnp.inf)
    pair = jnp.exp(diff) * k[None]  # (t, s, H, dk): exp(g_t - g_s) k_s
    A = jnp.sum(pair * k[:, None], axis=-1)  # (t, s, H)
    Aq = jnp.sum(pair * q[:, None], axis=-1)
    strict = jnp.tril(jnp.ones((Q, Q), bool), -1)[:, :, None]
    eg = jnp.exp(g)
    from_state = jnp.einsum("thk,hkv->thv", k * eg, S_in, precision=_HI)
    rhs = b[:, :, None] * (v - from_state)  # (Q, H, dv)
    U = _solve_unit_lower(
        jnp.moveaxis(b[:, None, :] * jnp.where(strict, A, 0.0), 2, 0),
        jnp.moveaxis(rhs, 1, 0))  # (H, Q, dv)
    o = (jnp.einsum("thk,hkv->thv", q * eg, S_in, precision=_HI)
         + jnp.einsum("tsh,hsv->thv", Aq, U, precision=_HI))
    left = k * jnp.exp(g[-1][None] - g)  # exp(g_C - g_s) k_s
    S_out = (eg[-1][:, :, None] * S_in
             + jnp.einsum("shk,hsv->hkv", left, U, precision=_HI))
    return o, S_out


def kda_chunk_scan(kda: jax.Array, layer: jax.Array, q: jax.Array,
                   k: jax.Array, v: jax.Array, log_a: jax.Array, b: jax.Array,
                   row_start: jax.Array, row_len: jax.Array,
                   slots: jax.Array, fresh: jax.Array, scanned: jax.Array,
                   chunk: int) -> Tuple[jax.Array, jax.Array]:
    """The rows ``scanned`` marks, through the recurrence, piece by piece.

    ``q, k, log_a (T, H, d_k)``, ``v (T, H, d_v)``, ``b (T, H)`` float32: the
    step's tokens flat, each row's end to end from ``row_start[r]`` for
    ``row_len[r]`` tokens; ``slots (R,)``: where each row's state lives in
    ``kda[layer]``; ``fresh (R,)``: the rows that start a sequence (zeros,
    not the slot); ``scanned (R,)``: the rows this call walks (a mixed step
    leaves its rows of one token to ``kda_decode_update``).
    → ``(o (T, H, d_v) float32, zero outside the scanned rows; kda with the
    scanned rows' final states written at their slots)``."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    Q = chunk
    tracer.add_event("kernel/kda_chunk_scan_tiles", attrs={
        "t": T, "chunk": Q, "heads": H, "dk": dk, "dv": dv,
        "grid_steps": -(-T // Q) + row_len.shape[0], "xla": 1})
    f32 = jnp.float32
    pieces = jnp.where(scanned, -(-row_len // Q), 0).astype(jnp.int32)
    ends = jnp.cumsum(pieces)

    def pad(a):  # a window of Q tokens may start at any token
        return jnp.pad(a.astype(f32), ((0, Q),) + ((0, 0),) * (a.ndim - 1))

    qp, kp, vp, lap, bp = pad(q), pad(k), pad(v), pad(log_a), pad(b)

    def piece(i, carry):
        o_all, kda, S = carry
        row = jnp.searchsorted(ends, i, side="right").astype(jnp.int32)
        j = i - (ends[row] - pieces[row])
        start = row_start[row] + j * Q
        n = jnp.minimum(Q, row_len[row] - j * Q)
        live = jnp.arange(Q) < n

        def window(a):
            return lax.dynamic_slice_in_dim(a, start, Q)

        # a token past the piece neither decays nor writes: the cumulative
        # gate stays where the piece's last token left it
        la = jnp.where(live[:, None, None], window(lap), 0.0)
        bw = jnp.where(live[:, None], window(bp), 0.0)
        slot = slots[row]
        S_in = jnp.where(j == 0,
                         jnp.where(fresh[row], 0.0, kda[layer, slot]), S)
        o, S_out = chunk_piece(window(qp), window(kp), window(vp), la, bw,
                               S_in)
        seen = lax.dynamic_slice_in_dim(o_all, start, Q)
        o_all = lax.dynamic_update_slice_in_dim(
            o_all, jnp.where(live[:, None, None], o, seen), start, 0)
        kda = kda.at[layer, slot].set(S_out)
        return o_all, kda, S_out

    with jax.named_scope("kda_chunk_scan"):
        o_all, kda, _ = lax.fori_loop(
            0, ends[-1], piece,
            (jnp.zeros((T + Q, H, dv), f32), kda,
             jnp.zeros((H, dk, dv), f32)))
    return o_all[:T], kda
