"""The masked latent prefill kernel (ISSUE 46; interpret mode here) against
the XLA body it replaced, ``_latent_prefill_xla``, and against a dense
float32 softmax over the keys each query picked, over the ways a mixed step
lays its prefill rows out.  Every case runs on operands of one shape, with a
tile of 16 queries cut into items of 8 and row blocks of 4, so that the one
program holds every loop of the kernel: two items a tile, two row blocks an
item, three key chunks of four blocks a row.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import latent_attention as la

T, R, H, LATENT, ROPE = 48, 4, 4, 64, 32
BS, BLOCKS, NB, L, LAYER = 8, 12, 60, 2, 1
TQ, TOPK, SCALE = 16, 20, 0.11
S = BLOCKS * BS
W = la.pool_width(LATENT, ROPE)

#: name → (chunk_start, chunk_len, q_start or None: end to end) by row
CASES = {
    # (a) one row of 21 tokens: a whole tile, then a tile of 5 (one item, and
    # of its two row blocks the second holds one query)
    "a-tile-with-cnt-below-tq": ([0], [21], None),
    # (b) rows of 19 and 23 tokens behind a single token: the second row's
    # tiles start at tokens 20 and 36, and its first at position 3
    "b-two-rows-off-the-tile-grid": ([7, 30, 3], [1, 19, 23], None),
    # (c) 26 tokens from position 37 on: the context ends at 63, inside the
    # second chunk of 32 keys and inside the eighth block
    "c-chunk-start-inside-a-chunk": ([37], [26], None),
    # (d) a row from position 0: its first 20 queries see fewer than
    # ``TOPK`` keys, and pick every one of them
    "d-fewer-keys-than-topk": ([0, 50], [30, 18], None),
    # (e) token 2's selection is emptied; a single-token row and the tokens
    # behind the rows are no tile's
    "e-empty-mask-and-unheld-tokens": ([12, 5, 40], [17, 1, 9], [0, 20, 30]),
    # (f) single tokens only: no tile
    "f-no-tile": ([9, 14, 70, 2], [1, 1, 1, 0], None),
}


@pytest.fixture
def small_items(monkeypatch):
    monkeypatch.setattr(la, "_ITEM_Q", 8)
    monkeypatch.setattr(la, "_BLOCK_ROWS", 16)


def _case(name, dtype):
    rng = np.random.default_rng(sum(map(ord, name)))
    cs, cl, qs = CASES[name]
    cs, cl = (np.pad(np.asarray(x, np.int32), (0, R - len(x)))
              for x in (cs, cl))
    qs = (np.cumsum(cl) - cl if qs is None
          else np.pad(np.asarray(qs), (0, R - len(qs)))).astype(np.int32)
    assert (qs + cl).max() <= T and (cs + cl).max() <= S
    tables = rng.permutation(NB)[:R * BLOCKS].reshape(R, BLOCKS)
    pool = rng.standard_normal((L, NB, BS, W)).astype(np.float32)
    pool[..., LATENT + ROPE:] = 0.0
    q = rng.standard_normal((T, H, W)).astype(np.float32)
    # what ``select_tiles`` hands out: the TOPK best visible keys of a
    # held query of a row of two tokens and more, nothing for the others
    scores = np.full((T, S), -np.inf, np.float32)
    for s in range(R):
        for i in range(cl[s] if cl[s] >= 2 else 0):
            seen = cs[s] + i + 1
            scores[qs[s] + i, :seen] = rng.standard_normal(seen)
    mask = np.array(la.topk_mask(jnp.asarray(scores), TOPK))
    if name.startswith("e-"):
        mask[2] = False
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(mask),
            jnp.asarray(qs), jnp.asarray(cs), jnp.asarray(cl))


def _dense(q, pool, tables, mask, qs, cs, cl):
    """float32 softmax of every held query over the keys it picked."""
    q, pool = np.asarray(q, np.float32), np.asarray(pool, np.float32)
    out = np.zeros((T, H, LATENT), np.float32)
    for s in range(R):
        keys = pool[LAYER, np.asarray(tables)[s]].reshape(S, W)
        for t in range(qs[s], qs[s] + (cl[s] if cl[s] >= 2 else 0)):
            on = np.asarray(mask)[t]
            if not on.any():
                continue
            sc = q[t] @ keys[on].T * SCALE
            p = np.exp(sc - sc.max(-1, keepdims=True))
            out[t] = (p / p.sum(-1, keepdims=True)) @ keys[on, :LATENT]
    return out


@functools.partial(jax.jit, static_argnames="fn")
def _run(q, pool, tables, mask, qs, cs, cl, *, fn):
    tiles = la.prefill_tiles(cl, T, TQ)
    return getattr(la, fn)(q, pool, jnp.int32(LAYER), tables, mask, tiles, qs,
                           cs, scale=SCALE, latent=LATENT, tq=TQ), tiles.n


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_xla_body_and_dense_softmax(name, dtype, small_items):
    args = _case(name, jnp.dtype(dtype))
    q, pool, tables, mask, qs, cs, cl = args
    got, n = _run(*args, fn="latent_prefill_attention")
    want, _ = _run(*args, fn="_latent_prefill_xla")
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (T, H, LATENT) and got.dtype == np.float32
    assert int(n) == sum(-(-int(c) // TQ) for c in np.asarray(cl) if c >= 2)
    # the same products of the same operands, summed in another order
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    dense = _dense(*map(np.asarray, (q.astype(jnp.float32),
                                     pool.astype(jnp.float32), tables, mask,
                                     qs, cs, cl)))
    np.testing.assert_allclose(got, dense, atol=tol, rtol=tol)
    held = np.zeros(T, bool)
    for s in range(R):
        if cl[s] >= 2:
            held[int(qs[s]):int(qs[s] + cl[s])] = True
    assert not got[~held].any()  # a token no tile holds comes out zero
    if name.startswith("e-"):
        assert held[2] and not got[2].any()
    if name.startswith("d-"):
        assert int(mask[int(qs[0]) + 4].sum()) == 5 < TOPK


@pytest.mark.parametrize("on_chip", [False, True])
def test_event_says_what_engaged(on_chip, small_items, monkeypatch):
    """The ring event of a traced call: the kernel's tiling, or that the
    pool's shapes (here a key chunk of 32, no whole lanes) forbid its DMAs
    where Mosaic would have to make them."""
    from deepspeed_tpu.ops.pallas import backend
    monkeypatch.setattr(backend, "interpret", lambda: not on_chip)
    tracer.clear()
    out, _ = jax.eval_shape(
        functools.partial(_run.__wrapped__, fn="latent_prefill_attention"),
        *_case("a-tile-with-cnt-below-tq", jnp.float32))
    assert out.shape == (T, H, LATENT) and out.dtype == jnp.float32
    event, = [s.attrs for s in tracer.spans()
              if s.name == "kernel/latent_attention_prefill_tiles"]
    if on_chip:
        assert event["fallback"] == 1 and event["form"] == "absorbed, masked"
    else:
        assert "fallback" not in event
        assert event["form"] == "absorbed, masked, pallas"
        assert (event["sq"], event["qb"], event["kb"], event["key_chunk"]
                ) == (8, 4, 4, 32)


def test_picker_at_the_cell():
    """GLM-5.2's mixed step: 64 heads, blocks of 64 keys, 272 a row."""
    pick = la.pick_prefill(64, 64, 272)
    assert pick.kb * 64 == la.key_chunk(272 * 64) == 1024
    assert pick.sq % pick.qb == 0 and la.TILE_Q % pick.sq == 0
    assert pick.qb * 64 == la._BLOCK_ROWS
    # a table no power of two divides: one block a fetch
    assert la.pick_prefill(64, 64, 17).kb == 1
