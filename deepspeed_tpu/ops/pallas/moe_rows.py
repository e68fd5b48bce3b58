"""The routed experts' rows into the grouped layout: ``out[r] = x[src[r]]``.

What carries a step's tokens into the tile-aligned grouped layout
(``moe/dropless.py``, scope ``moe_dispatch``).  The parent wrote it as a
scatter, ``zeros((M_pad, H)).at[positions].set(repeat(x, k))``, which the TPU
walks one row after another (0.1 us a row: 0.40 ms for a mixed step's 4,096
assignments at 2,304 columns, a fifth of what the HBM allows).  A decode
step's few assignments (under ``_MIN_LIVE``) stay that scatter: it costs them
less than making ``src`` does (three decode cells lost 1-3 % of their tokens
a second to the gather: PERF.md section 6, PR 52).  A mixed step's are a
GATHER under ``src`` (each layout row's token, -1 for none), in one of two
forms, chosen once per shape while the caller's program is traced:

* **the kernel** (``moe_rows``): a grid step owns one block of output rows
  and makes it on the MXU as ``onehot(src)^T . x``: the step's tokens ``x``
  (2-6 MB) stay in VMEM, the block's one-hot ``(tokens, rows)`` is built from
  the block's ``src`` with one compare, and a bf16 one-hot times bf16 rows
  summed in float32 is the row itself, exactly; a row without a source is an
  all-zero column of the one-hot and comes out zero.  Blocks past
  ``used_tiles`` tiles of ``tile_m`` rows compute nothing and are parked on
  the last block that held rows, so they are NEVER WRITTEN: a caller reads
  them through a mask, as it reads the grouped GEMMs' rows past
  ``used_tiles``.  The work grows with tokens x rows, so it is the form of a
  served step (at most ``_MAX_TOKENS`` tokens of a 16-bit type whose columns
  are whole lanes); 141 us where the scatter took 396 (PERF.md section 6,
  PR 52).  A sum over ALL tokens would carry one token's Inf or NaN into
  every row (0 x Inf), so the kernel reads the tokens through a copy in
  which a value that is not finite is 0: a row's output still depends on its
  own token alone.
* **XLA's gather**, ``where(src >= 0, x[src], 0)``: off the chip, in float32
  (the MXU's float32 product is one bf16 pass: not the row), and for more
  tokens than the kernel's rule takes (a trained step); 206 us at the same
  shape.

Moving each row by a DMA of its own under the prefetched ``src`` (ISSUE 52's
first design) is correct and slower than either: a DMA costs the scalar core
46 ns to start and to wait for, 275 us for the same 4,096 rows (PERF.md
section 6, PR 52).  The combine's ``ys[positions]`` stays the XLA gather it
was: its source is a layout's worth of rows, not a step's tokens.

The backward is gathers in XLA through ``inverse`` (the rows of ``out`` that
read each row of ``x``), as ``moe/dropless._rows_in_bwd``: XLA would transpose
the gather into a scatter-add, which the TPU serialises.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...observability.trace import tracer
from . import backend

_LANES = 128
#: the most rows a grid step owns (the pipeline's two output blocks: 6 MB at
#: 6,144 columns) and the most tokens the one-hot is summed over (a served
#: step has 512; the product's work grows with tokens x rows)
_MAX_BLOCK_ROWS, _MAX_TOKENS = 256, 1024
_VMEM_LIMIT = 64 * 1024 * 1024
_CLEAN_ROWS = 16  # one packed sublane tile of the tokens at a time
#: fewer assignments than this (a decode step's: 128-384 in the served cells)
#: are scattered as the parent scattered them: the walk costs 0.1 us an
#: assignment, 32-43 us a call, where the gather's fixed cost (the argsort
#: and a dozen small operations for ``src``, then the kernel's 25 us) is more
_MIN_LIVE = 1024


def _rows_xla(x, src):
    return jnp.where(src[:, None] >= 0, x[jnp.maximum(src, 0)], 0)


def block_rows(rows: int, tile_m: int, tokens: int, h: int, dtype) -> int:
    """Rows a grid step of the kernel owns: as many whole tiles as divide
    ``rows`` and stay within ``_MAX_BLOCK_ROWS`` (one tile where a tile is
    larger), or 0 where the kernel does not apply: columns that are no whole
    lanes, rows or tokens that are no whole packed sublane tiles, more tokens
    than ``_MAX_TOKENS``, a type the one-hot product does not reproduce."""
    dtype = jnp.dtype(dtype)
    if (h % _LANES or rows % tile_m or tokens % _CLEAN_ROWS
            or tokens > _MAX_TOKENS
            or not (jnp.issubdtype(dtype, jnp.floating)
                    and dtype.itemsize == 2)):
        return 0
    tiles = rows // tile_m
    best = 0
    for d in range(1, tiles + 1):
        r = d * tile_m
        if r > max(_MAX_BLOCK_ROWS, tile_m):
            break
        if tiles % d == 0 and r % 16 == 0:
            best = r
    return best


def _kernel(used_ref, src_ref, x_ref, o_ref, clean_ref, *, rows: int):
    t = pl.program_id(0)
    tokens = x_ref.shape[0]

    @pl.when(t == 0)
    def _clean():
        # the sum below runs over every token: one that is not finite must
        # not reach the other tokens' rows
        def some(i, _):
            at = pl.ds(pl.multiple_of(i * _CLEAN_ROWS, _CLEAN_ROWS),
                       _CLEAN_ROWS)
            x = x_ref[at, :].astype(jnp.float32)
            clean_ref[at, :] = jnp.where(jnp.abs(x) < jnp.inf, x, 0.0
                                         ).astype(clean_ref.dtype)
            return 0

        jax.lax.fori_loop(0, tokens // _CLEAN_ROWS, some, 0)

    @pl.when(t < used_ref[0])  # blocks that hold rows
    def _block():
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (tokens, rows), 0)
                  == src_ref[0]).astype(clean_ref.dtype)
        o_ref[...] = jax.lax.dot_general(
            onehot, clean_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "rows", "interpret"))
def _rows_pallas(x, src, used_tiles, *, tile_m: int, rows: int,
                 interpret: bool):
    """The kernel's call, under a jit of its own: a step program whose layers
    call it alike traces and lowers it once."""
    M, (N, H) = src.shape[0], x.shape
    used = -(-(jnp.reshape(used_tiles, (1,)).astype(jnp.int32) * tile_m)
             // rows)

    def held(t, used):
        # a block past the rows names the last block that held rows: the
        # pipeline moves nothing for it
        return jnp.minimum(t, jnp.maximum(used[0] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // rows,),
        in_specs=[
            pl.BlockSpec((1, 1, rows), lambda t, used: (held(t, used), 0, 0)),
            pl.BlockSpec((N, H), lambda t, used: (0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, H), lambda t, used: (held(t, used), 0)),
        scratch_shapes=[pltpu.VMEM((N, H), x.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, H), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows",
    )(used, src.astype(jnp.int32).reshape(M // rows, 1, rows), x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rows(x, src, used_tiles, inverse, rows: int, tile_m: int, block: int):
    """The layout's rows in the form the caller chose: ``src`` None, the
    parent's scatter through ``inverse``; else a gather under ``src``, the
    kernel's at blocks of ``block`` rows or (0) XLA's."""
    if src is None:
        k = inverse.shape[1]
        return jnp.zeros((rows, x.shape[1]), x.dtype).at[
            jnp.where(inverse >= 0, inverse, rows).reshape(-1)].set(
                jnp.repeat(x, k, axis=0), mode="drop")
    if not block:
        return _rows_xla(x, src)
    return _rows_pallas(x, src, used_tiles, tile_m=tile_m, rows=block,
                        interpret=backend.interpret())


def _rows_fwd(x, src, used_tiles, inverse, rows, tile_m, block):
    return (_rows(x, src, used_tiles, inverse, rows, tile_m, block),
            (inverse, x.shape[0]))


def _rows_bwd(rows, tile_m, block, res, dout):
    inverse, n = res
    acc = jnp.zeros((n, dout.shape[1]), jnp.float32)
    for j in range(inverse.shape[1]):
        at = inverse[:, j]
        acc = acc + jnp.where(at[:, None] >= 0,
                              dout[jnp.maximum(at, 0)].astype(jnp.float32), 0)
    return acc.astype(dout.dtype), None, None, None


_rows.defvjp(_rows_fwd, _rows_bwd)


def gather_rows(x: jax.Array, inverse: jax.Array, used_tiles: jax.Array, *,
                rows: int, tile_m: int,
                sources: Callable[[], jax.Array]) -> jax.Array:
    """The step's tokens ``x (N, H)`` in the grouped layout: ``out (rows,
    H)`` with ``out[inverse[n, j]] = x[n]`` for each of a token's ``k``
    assignments that has a row (``inverse (N, k)`` int32, -1: none) and zero
    where no assignment lies, over the first ``used_tiles`` tiles of
    ``tile_m`` rows; the rows of later tiles are NEVER WRITTEN by the kernel
    (read them through a mask).  ``sources() -> (rows,)`` int32 is the same
    map read the other way, the assignment ``n k + j`` at each row (-1:
    none); it is called, while the caller's program is traced, only where
    the rows are gathered.  The backward is ``k`` gathers through
    ``inverse``."""
    N, H = x.shape
    live = inverse.size
    if live < _MIN_LIVE:
        form, block = "scatter", 0
    else:
        block = 0 if backend.interpret() else block_rows(
            rows, tile_m, N, H, x.dtype)
        form = ("pallas" if block else
                "xla" if backend.interpret() else "fallback")
    # chosen once per shape, while the caller's program is traced
    tracer.add_event("kernel/moe_rows", attrs={
        "rows": rows, "h": H, "tile_m": tile_m, "tokens": N, "live": live,
        form: 1})
    src = None
    if form != "scatter":  # the token at each row
        src = sources()
        src = jnp.where(src >= 0, src // inverse.shape[1], -1)
    return _rows(x, src, used_tiles, inverse, rows, tile_m, block)
