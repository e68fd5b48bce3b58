"""Fused (flash) attention Pallas kernels, forward + backward.

TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, the inference attention in
``csrc/transformer/inference`` and the CUTLASS evoformer kernels) and for the
block-sparse attention package (``deepspeed/ops/sparse_attention/matmul.py``):
one kernel computes softmax(QKᵀ)V with online (streaming) softmax so the S×S
score matrix never materializes in HBM — O(S) memory instead of O(S²).

Design (classic FlashAttention-2 schedule on the MXU):
* grid = (batch, heads, q_blocks, steps); TPU executes the innermost
  dimension sequentially, so the running max/denominator/accumulator live in
  VMEM scratch across a q block's steps.  A step is one tile of the q block's
  WALK over its kv blocks: the walk ends at the last kv block the band keeps
  for the row and is as long as the band's widest row (``_band_tiles``, from
  static shapes: 3 steps under a window of 2,048 at blocks of 1,024, every
  kv block where nothing cuts), so a window layer has no dead step but the
  few of its first rows.  A row's dead steps come FIRST (the causal
  triangle of a full layer keeps its own: a row's kv blocks above the
  diagonal) and their index maps name the row's first live block, so nothing
  is fetched for them, the first live step finds its blocks there, and the
  next row's blocks are fetched behind the row's last tile;
* the band's element mask is built only in a tile the band's edge cuts, and
  only from the bound that cuts it (``_tile_cuts``: scalar compares on the
  grid indices choose between bodies of one function, one for each pair
  (cut by the causal bound, cut by the window's far bound) that the static
  shapes can give: a full layer has two, Trinity's window layers three:
  interior, diagonal, far edge).  A tile that lies wholly inside the band,
  and every tile of a call without a band, runs without a band mask.
  Segment ids, where given, are masked in every body;
* GQA: kv block index maps ``h → h * kv_heads // heads`` so grouped heads
  read the same K/V without materializing repeats;
* segment ids (packed sequences) are masked in-kernel: q ids ride along
  lanes as (B, S, 128) tiles, kv ids along sublanes as (B, 8, S) — the
  layout the TPU vector unit can compare without relayouts;
* arbitrary block-sparse masks: a scalar-prefetched (nq, nk) table gates
  each tile, so fully-masked tiles cost nothing (the reference's
  `sparse_attention` layouts — fixed/bigbird/longformer — compile to this);
* backward = two kernels (dkdv: grid over kv blocks, each walking the q
  blocks of its column of the band once a query head of the group; dq: the
  forward's grid) using the saved logsumexp, in the standard recompute
  formulation; a masked ``p`` is selected to 0 AFTER ``exp(s - lse)``, so the
  scores themselves are not selected on;
* precision: every dot multiplies its operands in the dtype the caller handed
  in and sums in float32.  q, k, v and dO go to the MXU as they are read; the
  probabilities ``p`` and ``ds`` are computed in float32 and rounded to the
  other operand's dtype immediately before the dot that consumes them.  The
  scores, the softmax (max, sum, ``exp``), ``lse``, ``delta``, the biases,
  the masks and every accumulator are float32 whatever the input
  (``operand_dtype`` on the ``kernel/flash_attention_tiles`` event names the
  dots' dtype).  Mosaic's float32 x float32 dot at default precision is one
  bfloat16 pass on the v5e as well (it rounds both operands on their way to
  the MXU), so bfloat16 callers get the same bits either way; what the rule
  buys them is VMEM: no float32 copy of a block exists, and past one lane
  tile 16-bit blocks fit at 1024 where float32 ones are held to 512;
* CPU fallback: interpreter mode (tests), or the XLA einsum path for odd
  shapes.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
NUM_LANES = 128
NUM_SUBLANES = 8


def aligned_divisor(n: int, cap: int, align: int = NUM_SUBLANES):
    """Largest divisor of ``n`` ≤ ``cap`` that is a multiple of ``align``;
    ``n`` itself when ``n ≤ cap`` (a full-dim block is always legal — Mosaic
    pads it). None when no aligned divisor exists (caller should fall back).
    """
    if n <= cap:
        return n
    for d in range(cap - cap % align, align - 1, -align):
        if n % d == 0:
            return d
    return None


def _band_mask(s_shape, q_start, k_start, by_causal: bool, by_window: bool,
               window: int):
    """Causal/sliding-window keep-mask for one (bq, bk) tile, built from the
    bounds that cut the tile (``_tile_cuts``): ``by_causal`` keeps keys up to
    the query, ``by_window`` keys in (query-window, ...; a bound that cuts
    nothing of the tile would compare all true.  None when neither cuts.
    ``window > 0`` implies the causal upper bound even when
    ``causal=False``."""
    if not (by_causal or by_window):
        return None
    rows = q_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 0)
    cols = k_start + jax.lax.broadcasted_iota(jnp.int32, s_shape, 1)
    keep = None
    if by_causal:
        keep = cols <= rows
    if by_window:
        inside = cols > rows - window
        keep = inside if keep is None else inside & keep
    return keep


def _tile_in_band(q_start, k_start, block_q: int, block_k: int,
                  causal: bool, window: int):
    """Static predicate: does this tile intersect the kept band?"""
    ok = True
    if causal or window > 0:
        ok = q_start + block_q - 1 >= k_start
    if window > 0:
        ok = ok & (k_start + block_k - 1 >= q_start - window + 1)
    return ok


def _tile_cuts(q_start, k_start, block_q: int, block_k: int, causal: bool,
               window: int):
    """Which of the band's two bounds cut the tile (mask some element of
    it) → (by_causal, by_window): its last key is later than its first
    query; under a window, its first key is outside the window of its last
    query.  A live tile that neither cuts lies wholly inside the band."""
    by_causal = by_window = False
    if causal or window > 0:
        by_causal = k_start + block_k - 1 > q_start
    if window > 0:
        by_window = k_start < q_start + block_q - window
    return by_causal, by_window


def _at_least(x, lo: int):
    """max(x, lo): of plain ints while a call is traced, of a grid index in
    an index map or a kernel."""
    return max(x, lo) if isinstance(x, int) else jnp.maximum(x, lo)


def _at_most(x, hi):
    return min(x, hi) if isinstance(x, int) else jnp.minimum(x, hi)


def _kv_block_in_band(iq, step, steps: int, block_q: int, block_k: int,
                      nk: int, causal: bool, window: int):
    """Step ``step`` of q block ``iq``'s walk of ``steps`` steps over its kv
    blocks → ``(ik, held)``.  The walk ENDS at the last kv block
    ``_tile_in_band`` keeps for the row, so ``ik`` counts up to it; ``held``
    is ``ik`` held to the row's live blocks inside ``[0, nk)``, which is what
    the index maps name.  A row that keeps fewer blocks than the widest (the
    causal triangle's, a window's first) has its dead steps FIRST: they
    compute nothing, name the row's first live block, which the first live
    step then finds fetched, and the walk's last step is a live tile, behind
    whose compute the next row's blocks arrive."""
    first, last = 0, nk - 1
    if causal or window > 0:
        last = _at_most((iq * block_q + block_q - 1) // block_k, last)
    if window > 0:
        first = _at_least(iq * block_q - window + 1, 0) // block_k
    ik = last - (steps - 1) + step
    return ik, _at_most(_at_least(ik, first), nk - 1)


def _q_block_in_band(ik, step, steps: int, block_q: int, block_k: int,
                     nq: int, causal: bool, window: int):
    """``_kv_block_in_band`` for the kernel that walks the q blocks of kv
    block ``ik``'s column: → ``(iq, held)``."""
    first, last = 0, nq - 1
    if causal or window > 0:
        first = ik * block_k // block_q
    if window > 0:
        last = _at_most((ik * block_k + block_k + window - 2) // block_q,
                        last)
    iq = last - (steps - 1) + step
    return iq, _at_most(_at_least(iq, first), nq - 1)


def _band_tiles(nq: int, nk: int, block_q: int, block_k: int, causal: bool,
                window: int) -> dict:
    """What the static shapes say of a call's tiles: ``kv_steps`` / ``q_steps``
    (the most tiles ``_tile_in_band`` keeps in a row / a column: the length
    of the forward's and dQ's / of the dK/dV kernel's walk), ``live_tiles``
    and, of those, ``edge_tiles`` (the ones ``_tile_cuts`` says build a
    mask), each a head; ``bodies``: the (by_causal, by_window) pairs that
    occur among the live tiles, one body of a kernel each."""
    q0 = np.arange(nq)[:, None] * block_q
    k0 = np.arange(nk)[None, :] * block_k
    live, by_causal, by_window = (
        np.broadcast_to(x, (nq, nk)) for x in (
            _tile_in_band(q0, k0, block_q, block_k, causal, window),
            *_tile_cuts(q0, k0, block_q, block_k, causal, window)))
    return {"kv_steps": max(int(live.sum(axis=1).max()), 1),
            "q_steps": max(int(live.sum(axis=0).max()), 1),
            "live_tiles": int(live.sum()),
            "edge_tiles": int((live & (by_causal | by_window)).sum()),
            "bodies": tuple(sorted({(bool(a), bool(b)) for a, b in zip(
                by_causal[live], by_window[live])}))}


def _for_live_tile(live, cuts, bodies, tile) -> None:
    """Run ``tile(by_causal, by_window)`` where the step's tile is live, in
    the body that builds the compares of the bounds that cut it and no other:
    none where the whole tile lies inside the band (and wherever the call
    has no band).  ``bodies`` are the pairs the call's shapes can give."""
    def says(cut, want: bool):  # a grid index's compare, or a plain bool
        if isinstance(cut, bool):
            return cut == want
        return cut if want else jnp.logical_not(cut)

    by_causal, by_window = cuts
    for a, b in bodies:
        pl.when(live & says(by_causal, a) & says(by_window, b))(
            functools.partial(tile, a, b))


def _seg_mask(q_seg_tile, k_seg_tile, block_k: int):
    """(block_q, NUM_LANES) q ids + (1, block_k) kv ids → (bq, bk) keep-mask.

    q ids are lane-broadcast copies, so tiling them along lanes yields the
    (bq, bk) matrix without any transpose/relayout (block_k % 128 == 0 on
    TPU; interpret mode takes the 1-lane broadcast path for small test
    blocks)."""
    if block_k % NUM_LANES == 0:
        qs = jnp.tile(q_seg_tile, (1, block_k // NUM_LANES))  # (bq, bk)
    else:  # interpret-mode (CPU test) path for unaligned tiny blocks
        qs = q_seg_tile[:, :1]
    return jnp.equal(qs, k_seg_tile)


def _unpack(refs, has_mask: bool, has_seg: bool, n_io: int,
            has_b1: bool = False, has_b2: bool = False):
    """Split the kernel's positional refs into
    (mask_tab, q_seg, k_seg, b1, b2, io).

    The additive biases b1/b2 are FORWARD-ONLY: ``_flash_attention_bhsd``'s
    custom VJP never threads them, and the backward kernels must not accept
    them — recomputing p = exp(s - lse) with a bias-less s against a biased
    lse would be silently wrong. The bias backward lives in
    ``ops/evoformer.py`` (its own VJP, recompute scan)."""
    idx = 0
    mask_tab = q_seg = k_seg = b1 = b2 = None
    if has_mask:
        mask_tab = refs[0]
        idx = 1
    if has_seg:
        q_seg, k_seg = refs[idx], refs[idx + 1]
        idx += 2
    if has_b1:
        b1 = refs[idx]
        idx += 1
    if has_b2:
        b2 = refs[idx]
        idx += 1
    io = refs[idx:]
    assert len(io) == n_io, (len(io), n_io, has_mask, has_seg)
    return mask_tab, q_seg, k_seg, b1, b2, io


def _scores(q_ref, k_ref, q_seg_ref, k_seg_ref, q_start, k_start, sm_scale,
            window, block_k, has_seg, by_causal: bool, by_window: bool):
    """QKᵀ·scale of a tile and its element keep-mask (band ∧ segments) →
    ``(s, keep)``.  ``by_causal`` / ``by_window`` say which of the band's
    bounds cut the tile (``_tile_cuts``): an interior tile builds no band
    mask.  ``keep`` is None when nothing masks
    at the element level; ``s`` comes back as computed, the caller selects
    (the forward on ``s`` for its maximum and on ``p``, the backward kernels
    on ``p`` alone).  Shared by the forward and both backward kernels so mask
    semantics can never desynchronize between passes."""
    s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    keep = _band_mask(s.shape, q_start, k_start, by_causal, by_window,
                      window)
    if has_seg:
        sm = _seg_mask(q_seg_ref[0], k_seg_ref[0, :1], block_k)
        keep = sm if keep is None else keep & sm
    return s, keep


def _masked_p(s, lse, keep):
    """The backward kernels' probabilities: ``exp(s - lse)`` with the masked
    elements selected to 0 (whatever ``exp`` made of them: a row whose
    ``lse`` is -inf has no kept element)."""
    p = jnp.exp(s - lse)  # (bq, bk)
    return p if keep is None else jnp.where(keep, p, 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, sm_scale: float, causal: bool, block_q: int,
                block_k: int, nk: int, kv_steps: int, window: int,
                bodies: tuple,
                has_mask: bool, has_seg: bool, has_b1: bool = False,
                has_b2: bool = False):
    # grid: (B, H, nq, steps) — the innermost dim walks the q block's live kv
    # blocks (``_kv_block_in_band``)
    mask_tab, q_seg_ref, k_seg_ref, b1_ref, b2_ref, io = _unpack(
        refs, has_mask, has_seg, 8, has_b1, has_b2)
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = io
    iq, step = pl.program_id(2), pl.program_id(3)
    ik, held = _kv_block_in_band(iq, step, kv_steps, block_q, block_k, nk,
                                 causal, window)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k

    live = _tile_in_band(q_start, k_start, block_q, block_k, causal, window)
    if causal or window > 0:
        live = live & (ik >= 0) & (ik < nk)
    if has_mask:
        live = live & (mask_tab[iq, held] != 0)

    def tile(by_causal: bool, by_window: bool):
        v = v_ref[0, 0]  # (bk, d)
        s, keep = _scores(q_ref, k_ref, q_seg_ref, k_seg_ref, q_start,
                          k_start, sm_scale, window, block_k, has_seg,
                          by_causal, by_window)  # (bq, bk)
        # additive attention biases (evoformer pair/mask biases): a per-key
        # row bias broadcast over queries and a full (bq, bk) tile
        if has_b1:
            s = s + b1_ref[0, :1].astype(jnp.float32)  # (1, bk) → rows
        if has_b2:
            s = s + b2_ref[0, 0].astype(jnp.float32)  # (bq, bk)
        if keep is not None:
            s = jnp.where(keep, s, DEFAULT_MASK_VALUE)

        m_prev = m_ref[:]  # (bq, 1)
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)  # DEFAULT_MASK_VALUE exp underflows,
            # but fully-masked rows would otherwise get exp(MASK - MASK) = 1
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)

        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new
        l_ref[:] = l_new

    _for_live_tile(live, _tile_cuts(q_start, k_start, block_q, block_k,
                                    causal, window), bodies, tile)

    @pl.when(step == kv_steps - 1)
    def _finalize():
        l = l_ref[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:] + jnp.log(l_safe)  # (bq, 1)
        lse_ref[0, 0] = jnp.where(l == 0.0, -jnp.inf, lse)


def _kernel_name(which: str, window: int, kv_len: int) -> str:
    """A kernel's name in the compiled program and the trace:
    ``flash_attention_<which>``, with ``_band`` behind it where the band CUTS
    something (0 < window < the keys' length), so that a trace tells a window
    layer's calls from a full layer's in one program.  A window that cuts
    nothing keeps the plain name."""
    return f"flash_attention_{which}" + ("_band" if 0 < window < kv_len
                                         else "")


def _pallas_call(name, kernel, grid, in_specs, out_specs, out_shape,
                 scratch_shapes, mask_tab, inputs):
    """Dispatch with or without the scalar-prefetched block-mask table;
    ``name`` is the kernel's name in the compiled program and the trace."""
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))
    if mask_tab is not None:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes)
        return pl.pallas_call(kernel, grid_spec=grid_spec,
                              out_shape=out_shape, compiler_params=params,
                              interpret=backend.interpret(),
                              name=name)(mask_tab, *inputs)
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch_shapes,
        compiler_params=params,
        interpret=backend.interpret(), name=name)(*inputs)


def _flash_fwd(q, k, v, q_seg, k_seg, mask_tab, sm_scale, causal, block_q,
               block_k, window=0, bias_kv=None,
               bias_qk=None) -> Tuple[jax.Array, jax.Array]:
    B, H, S, D = q.shape
    Dv = v.shape[3]  # the value width: D, or its own (latent attention)
    KV = k.shape[1]
    Skv = k.shape[2]
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Skv, block_k)
    group = H // KV
    has_seg = q_seg is not None
    has_b1 = bias_kv is not None
    has_b2 = bias_qk is not None

    tiles = _band_tiles(nq, nk, block_q, block_k, causal, window)
    grid = (B, H, nq, tiles["kv_steps"])

    def kv_at(iq, step):  # the kv block a step of the walk names
        return _kv_block_in_band(iq, step, tiles["kv_steps"], block_q,
                                 block_k, nk, causal, window)[1]

    def kv_rows(b, h, iq, step, *_):  # a block of this head's kv rows
        return (b, h // group, kv_at(iq, step), 0)

    def own_q_rows(b, h, iq, step, *_):  # the q block's rows of this head
        return (b, h, iq, 0)

    in_specs = []
    inputs = []
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda b, h, iq, step, *_: (b, iq, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, block_k),
                         lambda b, h, iq, step, *_: (b, 0, kv_at(iq, step))),
        ]
        inputs += [q_seg, k_seg]
    if has_b1:  # per-key bias, (B, NUM_SUBLANES, Skv) lane layout
        in_specs += [pl.BlockSpec(
            (1, NUM_SUBLANES, block_k),
            lambda b, h, iq, step, *_: (b, 0, kv_at(iq, step)))]
        inputs += [bias_kv]
    if has_b2:  # full (q, k) bias, batch-broadcast (e.g. pair bias over MSA)
        b2_rep = B // bias_qk.shape[0]
        in_specs += [pl.BlockSpec(
            (1, 1, block_q, block_k),
            lambda b, h, iq, step, *_: (b // b2_rep, h, iq,
                                     kv_at(iq, step)))]
        inputs += [bias_qk]
    in_specs += [
        pl.BlockSpec((1, 1, block_q, D), own_q_rows),
        pl.BlockSpec((1, 1, block_k, D), kv_rows),
        pl.BlockSpec((1, 1, block_k, Dv), kv_rows),
    ]
    inputs += [q, k, v]
    out, lse = _pallas_call(
        _kernel_name("fwd", window, Skv),
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          kv_steps=tiles["kv_steps"], window=window,
                          bodies=tiles["bodies"],
                          has_mask=mask_tab is not None, has_seg=has_seg,
                          has_b1=has_b1, has_b2=has_b2),
        grid, in_specs,
        [
            pl.BlockSpec((1, 1, block_q, Dv), own_q_rows),
            pl.BlockSpec((1, 1, block_q, 1), own_q_rows),
        ],
        [
            jax.ShapeDtypeStruct((B, H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        [
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        mask_tab, inputs)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dkdv_kernel(*refs, sm_scale, causal, block_q, block_k, nq: int,
                     q_steps: int, window: int, bodies: tuple,
                     has_mask: bool, has_seg: bool):
    # grid: (B, KV, nk, group*q_steps) — the innermost dim walks, for every
    # query head in this kv head's group, the q blocks of the kv block's
    # column of the band (``_q_block_in_band``), accumulating straight into
    # the per-KV-head dk/dv (no (B, H, S, D) f32 intermediate).
    mask_tab, q_seg_ref, k_seg_ref, _, _, io = _unpack(
        refs, has_mask, has_seg, 10)
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dk_acc, dv_acc) = io
    ik, iqg = pl.program_id(2), pl.program_id(3)
    iq, held = _q_block_in_band(ik, iqg % q_steps, q_steps, block_q, block_k,
                                nq, causal, window)

    @pl.when(iqg == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    live = _tile_in_band(q_start, k_start, block_q, block_k, causal, window)
    if causal or window > 0:
        live = live & (iq >= 0) & (iq < nq)
    if has_mask:
        live = live & (mask_tab[held, ik] != 0)

    def tile(by_causal: bool, by_window: bool):
        q = q_ref[0, 0]  # (bq, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0]  # (bq, d)
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]  # (bq, 1)

        s, keep = _scores(q_ref, k_ref, q_seg_ref, k_seg_ref, q_start,
                          k_start, sm_scale, window, block_k, has_seg,
                          by_causal, by_window)
        p = _masked_p(s, lse, keep)  # (bq, bk)

        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale  # (bq, bk)
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _for_live_tile(live, _tile_cuts(q_start, k_start, block_q, block_k,
                                    causal, window), bodies, tile)

    @pl.when(iqg == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, nk: int,
                   kv_steps: int, window: int, bodies: tuple,
                   has_mask: bool, has_seg: bool):
    # grid: the forward's, (B, H, nq, steps)
    mask_tab, q_seg_ref, k_seg_ref, _, _, io = _unpack(
        refs, has_mask, has_seg, 8)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc = io
    iq, step = pl.program_id(2), pl.program_id(3)
    ik, held = _kv_block_in_band(iq, step, kv_steps, block_q, block_k, nk,
                                 causal, window)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = ik * block_k
    live = _tile_in_band(q_start, k_start, block_q, block_k, causal, window)
    if causal or window > 0:
        live = live & (ik >= 0) & (ik < nk)
    if has_mask:
        live = live & (mask_tab[iq, held] != 0)

    def tile(by_causal: bool, by_window: bool):
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]  # (bq, 1)
        delta = delta_ref[0, 0]  # (bq, 1)

        s, keep = _scores(q_ref, k_ref, q_seg_ref, k_seg_ref, q_start,
                          k_start, sm_scale, window, block_k, has_seg,
                          by_causal, by_window)
        p = _masked_p(s, lse, keep)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    _for_live_tile(live, _tile_cuts(q_start, k_start, block_q, block_k,
                                    causal, window), bodies, tile)

    @pl.when(step == kv_steps - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[:].astype(dq_ref.dtype)


def _tiles_event(q, v, block_q: int, block_k: int, which: str = "",
                 tiles: Optional[dict] = None) -> None:
    """The ``kernel/flash_attention_tiles`` ring event of one pass of a
    traced call: the widths, the blocks, the dots' dtype, and what the static
    shapes say of the walk (``steps`` a q block, for the dK/dV kernel a kv
    block and a query head; ``live_tiles`` and ``edge_tiles`` a head); with
    no ``tiles``, the event of a call that fell back."""
    attrs = {"d_qk": q.shape[-1], "d_v": v.shape[-1], "block_q": block_q,
             "block_k": block_k, "operand_dtype": jnp.dtype(q.dtype).name}
    if tiles is None:
        attrs["fallback"] = 1
    else:
        attrs.update({
            "pass": which,
            "steps": tiles["q_steps" if which == "dkdv" else "kv_steps"],
            "live_tiles": tiles["live_tiles"],
            "edge_tiles": tiles["edge_tiles"]})
    tracer.add_event("kernel/flash_attention_tiles", attrs=attrs)


def _flash_bwd(sm_scale, causal, block_q, block_k, window, res, g):
    q, k, v, q_seg, k_seg, mask_tab, out, lse = res
    B, H, S, D = q.shape
    Dv = v.shape[3]
    KV = k.shape[1]
    Skv = k.shape[2]
    group = H // KV
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(Skv, block_k)
    has_seg = q_seg is not None
    tiles = _band_tiles(nq, nk, block_q, block_k, causal, window)
    q_steps = tiles["q_steps"]

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (B, H, S, 1)
    for which in ("dkdv", "dq"):  # once a traced backward, as the forward's
        _tiles_event(q, v, block_q, block_k, which, tiles)

    # dk, dv: one pass per kv block; the innermost grid dim walks the q
    # blocks of the kv block's column once a query head of the group, so GQA
    # groups accumulate directly into the (B, KV, Skv, D) result — no
    # (B, H, Skv, D) f32 intermediate.
    def q_at(ik, iqg):  # the q block a step of the walk names
        return _q_block_in_band(ik, iqg % q_steps, q_steps, block_q,
                                block_k, nq, causal, window)[1]

    def q_rows(b, kv, ik, iqg, *_):  # a block of this head's q rows
        return (b, kv * group + iqg // q_steps, q_at(ik, iqg), 0)

    in_specs = []
    inputs = []
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda b, kv, ik, iqg, *_: (b, q_at(ik, iqg), 0)),
            pl.BlockSpec((1, NUM_SUBLANES, block_k),
                         lambda b, kv, ik, iqg, *_: (b, 0, ik)),
        ]
        inputs += [q_seg, k_seg]
    in_specs += [
        pl.BlockSpec((1, 1, block_q, D), q_rows),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, kv, ik, iqg, *_: (b, kv, ik, 0)),
        pl.BlockSpec((1, 1, block_k, Dv),
                     lambda b, kv, ik, iqg, *_: (b, kv, ik, 0)),
        pl.BlockSpec((1, 1, block_q, Dv), q_rows),
        pl.BlockSpec((1, 1, block_q, 1), q_rows),
        pl.BlockSpec((1, 1, block_q, 1), q_rows),
    ]
    inputs += [q, k, v, g, lse, delta]
    dk, dv = _pallas_call(
        _kernel_name("bwd_dkv", window, Skv),
        functools.partial(_bwd_dkdv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          q_steps=q_steps, window=window,
                          bodies=tiles["bodies"],
                          has_mask=mask_tab is not None, has_seg=has_seg),
        (B, KV, nk, group * q_steps), in_specs,
        [
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, kv, ik, iqg, *_: (b, kv, ik, 0)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, kv, ik, iqg, *_: (b, kv, ik, 0)),
        ],
        [
            jax.ShapeDtypeStruct((B, KV, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, KV, Skv, Dv), v.dtype),
        ],
        [
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        mask_tab, inputs)

    def kv_at(iq, step):  # the kv block a step of the walk names
        return _kv_block_in_band(iq, step, tiles["kv_steps"], block_q,
                                 block_k, nk, causal, window)[1]

    def kv_rows(b, h, iq, step, *_):  # a block of this head's kv rows
        return (b, h // group, kv_at(iq, step), 0)

    def own_q_rows(b, h, iq, step, *_):  # the q block's rows of this head
        return (b, h, iq, 0)

    in_specs = []
    inputs = []
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, block_q, NUM_LANES),
                         lambda b, h, iq, step, *_: (b, iq, 0)),
            pl.BlockSpec((1, NUM_SUBLANES, block_k),
                         lambda b, h, iq, step, *_: (b, 0, kv_at(iq, step))),
        ]
        inputs += [q_seg, k_seg]
    in_specs += [
        pl.BlockSpec((1, 1, block_q, D), own_q_rows),
        pl.BlockSpec((1, 1, block_k, D), kv_rows),
        pl.BlockSpec((1, 1, block_k, Dv), kv_rows),
        pl.BlockSpec((1, 1, block_q, Dv), own_q_rows),
        pl.BlockSpec((1, 1, block_q, 1), own_q_rows),
        pl.BlockSpec((1, 1, block_q, 1), own_q_rows),
    ]
    inputs += [q, k, v, g, lse, delta]
    dq = _pallas_call(
        _kernel_name("bwd_dq", window, Skv),
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          kv_steps=tiles["kv_steps"], window=window,
                          bodies=tiles["bodies"],
                          has_mask=mask_tab is not None, has_seg=has_seg),
        (B, H, nq, tiles["kv_steps"]), in_specs,
        pl.BlockSpec((1, 1, block_q, D), own_q_rows),
        jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        [pltpu.VMEM((block_q, D), jnp.float32)],
        mask_tab, inputs)

    return dq, dk, dv, None, None, None


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_attention_bhsd(q, k, v, q_seg, k_seg, mask_tab,
                          sm_scale, causal, block_q, block_k, window):
    out, _ = _flash_fwd(q, k, v, q_seg, k_seg, mask_tab, sm_scale, causal,
                        block_q, block_k, window)
    return out


def _fwd_rule(q, k, v, q_seg, k_seg, mask_tab, sm_scale, causal, block_q,
              block_k, window):
    out, lse = _flash_fwd(q, k, v, q_seg, k_seg, mask_tab, sm_scale, causal,
                          block_q, block_k, window)
    return out, (q, k, v, q_seg, k_seg, mask_tab, out, lse)


_flash_attention_bhsd.defvjp(
    _fwd_rule,
    lambda sm_scale, causal, block_q, block_k, window, res, g: _flash_bwd(
        sm_scale, causal, block_q, block_k, window, res, g))


def _mesh_specs(B: int, H: int, KV: int):
    """How a (B, S, H, D) attention call splits over the engine's mesh:
    batch rows over (dp, fsdp), heads over tp.  None when there is no mesh
    of several devices, or the call is already inside a ``shard_map``.  An
    axis that does not divide its dimension stays whole (replicated work,
    never a wrong answer); the sequence is never split here."""
    from jax.sharding import PartitionSpec as P

    from ...parallel import topology

    if (not topology.topology_initialized()
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    topo = topology.get_topology()
    if topo.world_size == 1:
        return None
    batch = ("dp", "fsdp") if B % topo.dp_world_size == 0 else None
    tp = topo.size("tp")
    heads = "tp" if tp > 1 and H % tp == 0 and KV % tp == 0 else None
    return (topo.mesh, P(batch, None, heads, None), P(batch, None))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    segment_ids=None, window: int = 0,
                    block_mask=None) -> jax.Array:
    """Fused attention. q: (B, S, H, D); k: (B, S, KV, D), v: (B, S, KV, Dv)
    with KV | H.  ``Dv`` may differ from ``D`` (latent attention's expanded
    form: a query-key width of 192 beside a value width of 128); the output,
    ``dO`` and ``dV`` are ``Dv`` wide and ``v`` is never padded to ``D``.

    Differentiable (custom VJP); supports causal masking, GQA, sliding-
    window (``window`` > 0 keeps keys in (query-window, query]), packed-
    sequence ``segment_ids`` ((B, S) int32, masked in-kernel), and arbitrary
    block-sparse ``block_mask`` ((S/block_q, S/block_k) bool/int — tiles
    where the mask is 0 are skipped entirely; the reference's
    ``deepspeed.ops.sparse_attention`` layouts lower to this). All masks
    compose. Falls back to the XLA einsum path when shapes don't fit the
    kernel constraints (tiny/unaligned sequence lengths).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(D)
    if not backend.interpret():
        specs = _mesh_specs(B, H, KV)
        if specs is not None:
            # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
            # shard_map"): on a mesh each device runs the kernel on its own
            # batch rows and heads; inside, the manual axes stop a second wrap
            mesh, qspec, bspec = specs
            local = functools.partial(
                flash_attention, causal=causal, sm_scale=sm_scale,
                block_q=block_q, block_k=block_k, window=window,
                block_mask=block_mask)
            if segment_ids is None:
                return jax.shard_map(
                    local, mesh=mesh, in_specs=(qspec, qspec, qspec),
                    out_specs=qspec, check_vma=False)(q, k, v)
            return jax.shard_map(
                lambda q_, k_, v_, seg: local(q_, k_, v_, segment_ids=seg),
                mesh=mesh, in_specs=(qspec, qspec, qspec, bspec),
                out_specs=qspec, check_vma=False)(q, k, v, segment_ids)

    def pick_block(n: int, cap: int) -> int:
        # small windows waste MXU work in huge tiles: shrink the cap toward
        # the band width (never raise it above the caller's request)
        if 0 < window < cap:
            cap = min(cap, max(128, window // 128 * 128))
        # a query-key width past one lane tile (latent attention's 192 is
        # held as 256): at 1024 x 1024 the dK/dV kernel's float32 tiles (the
        # (bq, bk) scores, p, dp and ds, and the dk / dv accumulators) beside
        # float32 blocks pass the 16 MB of scoped VMEM; 512 fits.  The blocks
        # are in the caller's dtype, and 16-bit ones fit at 1024: a quarter
        # of the grid steps, each with its fixed cost and its rescaling of
        # the accumulators
        if D > NUM_LANES and jnp.dtype(q.dtype).itemsize > 2:
            cap = min(cap, 512)
        # largest sublane-aligned divisor, so raising the default can never
        # push a previously-fused shape onto the O(S²) fallback (e.g.
        # S=1536: divisor 768, not min()=1024 → unusable); when none exists
        # return cap and let the usable-gate fall back
        return aligned_divisor(n, cap) or cap

    if block_mask is None:
        # block sizes are free parameters without a mask table; with one,
        # the table's granularity pins them
        block_q = pick_block(S, block_q)
        block_k = pick_block(k.shape[1], block_k)
    usable = (S % block_q == 0 and k.shape[1] % block_k == 0 and H % KV == 0)
    if segment_ids is not None:
        # the in-kernel lane-tiling needs 128-aligned kv blocks on TPU
        usable = usable and (block_k % NUM_LANES == 0
                             or backend.interpret())
    if block_mask is not None:
        nq, nk = pl.cdiv(S, block_q), pl.cdiv(k.shape[1], block_k)
        if block_mask.shape != (nq, nk):
            raise ValueError(
                f"block_mask shape {block_mask.shape} != grid ({nq}, {nk}) "
                f"for S={S}, block_q={block_q}, block_k={block_k}")
    # chosen once per shape, while the caller's program is traced
    if not usable:
        _tiles_event(q, v, block_q, block_k)
        backend.warn_fallback(
            "flash_attention",
            f"S={S}, Skv={k.shape[1]}, H={H}, KV={KV} do not tile into "
            f"block_q={block_q}, block_k={block_k}"
            + (" (segment ids need 128-aligned kv blocks)"
               if segment_ids is not None else ""))
        return _reference_attention(q, k, v, causal=causal, window=window,
                                    segment_ids=segment_ids,
                                    block_mask=block_mask, block_q=block_q,
                                    block_k=block_k, sm_scale=sm_scale)

    q_seg3 = k_seg3 = None
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        q_seg3 = jax.lax.broadcast_in_dim(seg, (B, S, NUM_LANES), (0, 1))
        k_seg3 = jax.lax.broadcast_in_dim(seg, (B, NUM_SUBLANES, S), (0, 2))
    mask_tab = None
    if block_mask is not None:
        mask_tab = jnp.asarray(block_mask, jnp.int32)

    # kernel layout is (B, H, S, D)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    _tiles_event(q, v, block_q, block_k, "fwd", _band_tiles(
        S // block_q, k.shape[1] // block_k, block_q, block_k, causal,
        window))
    out = _flash_attention_bhsd(qt, kt, vt, q_seg3, k_seg3, mask_tab,
                                sm_scale, causal, block_q, block_k, window)
    return out.transpose(0, 2, 1, 3)


def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None):
    """Pure-XLA reference for numeric tests."""
    from ...models.transformer import xla_attention

    return xla_attention(q, k, v, causal=causal)


def _reference_attention(q, k, v, causal: bool, window: int, segment_ids,
                         block_mask, block_q: int, block_k: int,
                         sm_scale: Optional[float] = None):
    """XLA einsum path implementing the full mask algebra (band ∧ segments ∧
    block mask) — the fallback for kernel-unfriendly shapes and the numeric
    oracle for the kernel tests."""
    B, S, H, D = q.shape
    Skv = k.shape[1]
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(Skv)[None, :]
    keep = jnp.ones((S, Skv), bool)
    if window > 0:
        keep = (cols > rows - window) & (cols <= rows)
    elif causal:
        keep = rows >= cols
    if block_mask is not None:
        bm = jnp.asarray(block_mask) != 0
        elem = jnp.repeat(jnp.repeat(bm, block_q, axis=0), block_k, axis=1)
        keep = keep & elem[:S, :Skv]
    keep = jnp.broadcast_to(keep[None], (B, S, Skv))
    if segment_ids is not None:
        keep = keep & (segment_ids[:, :, None] == segment_ids[:, None, :])
    logits = jnp.where(keep[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    # fully-masked rows: softmax over all -1e30 gives uniform; zero them
    any_keep = jnp.any(keep, axis=-1)[:, None, :, None]
    probs = jnp.where(any_keep, probs, 0.0)
    return jnp.einsum("bhst,bthd->bshd", probs,
                      v.astype(jnp.float32)).astype(q.dtype)


def _windowed_reference(q, k, v, causal: bool, window: int,
                        sm_scale: Optional[float] = None):
    """Back-compat alias for the banded reference path."""
    return _reference_attention(q, k, v, causal=causal, window=window,
                                segment_ids=None, block_mask=None,
                                block_q=1, block_k=1, sm_scale=sm_scale)
