"""``ssm_hybrid.conv_ragged``, the mixed step's causal conv of the three
models with a mixer conv (Nemotron-3's ``ssm_conv``, Kimi-Linear's
``kda_conv``, Jamba2's ``sel_conv``), against the form it had before PR 59
(``served_kinds.conv_ragged_gather``: two gathers of all ``T`` rows a tap):
the step's tokens read in one pass through shifted slices, a row's first
``K - 1`` outputs laid over it by a one-hot product.  The same values in the same order:
BIT-identical on every real token and on the kept columns, in float32 and in
bfloat16, whatever the rows' lengths."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from served_kinds import conv_ragged_gather  # noqa: E402

from deepspeed_tpu.models.ssm_hybrid import conv_ragged  # noqa: E402

C = 40

#: name -> (tokens a row (0: an unused row), rows that start a sequence (zero
#: kept columns), T, the row that padding tokens name)
LAYOUTS = {
    "rows of one token": ([1] * 8, (), 8, 0),
    "one long row": ([64], (), 64, 0),
    "a row of 499 behind 13 of one, padded": ([1] * 13 + [499] + [0] * 18,
                                              (), 544, 31),
    "rows shorter than K - 1": ([2, 1, 2, 3, 1, 0, 2, 0], (), 16, 0),
    "fresh rows beside continued ones": ([5, 1, 9, 1, 2, 0], (0, 3, 4), 24,
                                         5),
}


def i32(a):
    return jnp.asarray(a, jnp.int32)


def operands(lens, fresh, T, pad_row, K, dtype, seed=0):
    """→ ``conv_ragged``'s arguments for rows of ``lens`` tokens lying end to
    end as the engine lays them (``q_start = cumsum(n) - n``, so an unused
    row starts where the real tokens end), and the real tokens' mask."""
    n = np.asarray(lens, np.int32)
    R = len(n)
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (T, C)).astype(dtype)
    kept = jax.random.normal(jax.random.fold_in(key, 1),
                             (R, K - 1, C)).astype(dtype)
    kept = kept.at[jnp.asarray(fresh, jnp.int32)].set(0)
    p = {"conv_w": jax.random.normal(jax.random.fold_in(key, 2), (K, C)),
         "conv_b": jax.random.normal(jax.random.fold_in(key, 3), (C,))}
    start = np.cumsum(n) - n
    valid = np.arange(T) < n.sum()
    row = np.where(valid, np.searchsorted(np.cumsum(n), np.arange(T),
                                          "right"), pad_row)
    row = np.minimum(row, R - 1)
    meta = tuple(i32(a) for a in (row, np.arange(T) - start[row], start, n))
    return (x, kept, p) + meta, valid


def identical(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and \
        bool((a.view(np.uint8) == b.view(np.uint8)).all())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("K", [2, 4])
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_equals_the_gather_form_bit_for_bit(layout, K, dtype):
    args, valid = operands(*LAYOUTS[layout], K, jnp.dtype(dtype))
    got, got_kept = jax.jit(conv_ragged)(*args)
    want, want_kept = jax.jit(conv_ragged_gather)(*args)
    assert identical(got[valid], want[valid])
    assert identical(got_kept, want_kept)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())  # padding too


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("K", [2, 4])
@pytest.mark.parametrize("cut", [1, 2, 3, 17])
def test_a_row_split_over_two_steps_equals_one_step(cut, K, dtype):
    """A row of 40 tokens in one step, and the same row as ``cut`` tokens
    then the rest from the kept columns the first step left (beside another
    row, so the row does not start the second step's batch)."""
    (x, kept, p, *_), _ = operands([40], (0,), 40, 0, K, jnp.dtype(dtype))

    def step(xs, kept, lens):
        n = np.asarray(lens, np.int32)
        start = np.cumsum(n) - n
        row = np.repeat(np.arange(len(n)), n)
        return jax.jit(conv_ragged)(
            xs, kept, p, i32(row), i32(np.arange(len(row)) - start[row]),
            i32(start), i32(n))

    whole, whole_kept = step(x, kept, [40])
    other = jax.random.normal(jax.random.PRNGKey(7), (3, C)).astype(x.dtype)
    both = jnp.concatenate([kept, jnp.ones_like(kept)])
    a, a_kept = step(jnp.concatenate([other, x[:cut]]), both[::-1],
                     [3, cut])
    b, b_kept = step(jnp.concatenate([other, x[cut:]]), a_kept, [3, 40 - cut])
    assert identical(jnp.concatenate([a[3:], b[3:]]), whole)
    assert identical(b_kept[1:], whole_kept)


def test_reads_the_steps_tokens_once():
    """No gather is left in the traced function (the rows it moves, it moves
    by one-hot products); the form before it made six of all ``T`` rows."""
    args, _ = operands(*LAYOUTS["one long row"], 4, jnp.bfloat16)

    def gathers(fn):
        return [e.outvars[0].aval.shape
                for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                if e.primitive.name == "gather"]

    assert gathers(conv_ragged) == []
    assert gathers(conv_ragged_gather).count((64, C)) == 6
