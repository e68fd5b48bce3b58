"""The paged latent decode kernel under a pick's mask (ISSUE 56; interpret
mode here) against the gather it replaced, ``latent_decode_attention``, over
the ways a step's rows of one token meet their picks.  Every case runs on
operands of one shape: tables of 11 blocks of 8 keys read 4 blocks a fetch,
so that a row's context is up to three fetches, the last of three blocks.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.pallas import latent_attention as la

R, H, LATENT, ROPE = 6, 4, 64, 32
BS, BLOCKS, NB, L, LAYER = 8, 11, 80, 2, 1
TOPK, SCALE, FETCH = 12, 0.11, 32
S = BLOCKS * BS
W = la.pool_width(LATENT, ROPE)

#: name → a row's (context, the keys it may pick among: (from, to) runs);
#: the rows the list does not reach take no step
CASES = {
    # (a) contexts shorter than ``TOPK``: every visible key is picked
    "a-fewer-keys-than-topk": [(5, [(0, 5)]), (11, [(0, 11)]), (1, [(0, 1)])],
    # (b) three fetches a row, picks in the first and the last alone; in the
    # last alone (the running maximum is still ``_NEG`` after two fetches);
    # in the second alone
    "b-fetches-without-a-pick": [(88, [(3, 9), (70, 88)]), (85, [(66, 85)]),
                                 (70, [(33, 60)])],
    # (c) a row with a context and NO pick between rows that have some; a
    # row whose mask is set and whose context is 0 (no row of one token in
    # this step: the program hands it context 0 whatever its picks say)
    "c-no-pick-and-not-single": [(40, [(0, 40)]), (30, []), (0, [(0, 20)]),
                                 (64, [(0, 64)])],
    # (d) contexts that end inside a block, inside the last, partial fetch
    # and on a fetch's edge; picks anywhere
    "d-picks-anywhere": [(88, [(0, 88)]), (67, [(0, 67)]), (32, [(0, 32)]),
                         (33, [(0, 33)]), (9, [(0, 9)]), (81, [(0, 81)])],
}


@pytest.fixture
def small_fetches(monkeypatch):
    monkeypatch.setattr(la, "_FULL_FETCH_KEYS", FETCH)


def _case(name, dtype):
    rng = np.random.default_rng(sum(map(ord, name)))
    rows = CASES[name] + [(0, [])] * (R - len(CASES[name]))
    ctx = np.asarray([c for c, _ in rows], np.int32)
    scores = np.full((R, S), -np.inf, np.float32)
    for r, (_, runs) in enumerate(rows):
        for a, b in runs:
            scores[r, a:b] = rng.standard_normal(b - a)
    tables = rng.permutation(NB)[:R * BLOCKS].reshape(R, BLOCKS)
    pool = rng.standard_normal((L, NB, BS, W)).astype(np.float32)
    pool[..., LATENT + ROPE:] = 0.0
    q = rng.standard_normal((R, H, W)).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(scores),
            jnp.asarray(ctx))


@jax.jit
def _both(q, pool, tables, scores, ctx):
    kw = dict(scale=SCALE, latent=LATENT)
    vals, idx = jax.lax.top_k(scores, TOPK)
    # the gather knows no context: a row that takes no step has no pick
    ok = (vals > -jnp.inf) & (ctx > 0)[:, None]
    want = la.latent_decode_attention(q, pool, jnp.int32(LAYER), tables, idx,
                                      ok, **kw)
    mask = la.topk_mask(scores, TOPK)
    got = la.latent_decode_attention_masked(
        q, pool, jnp.int32(LAYER), tables, mask, ctx, k=TOPK, **kw)
    return got, want, mask


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_masked_kernel_matches_the_gather(name, dtype, small_fetches):
    args = _case(name, jnp.dtype(dtype))
    got, want, mask = map(np.asarray, _both(*args))
    ctx = np.asarray(args[-1])
    assert got.shape == (R, H, LATENT) and got.dtype == np.float32
    assert np.isfinite(got).all()
    # the same products of the same operands, summed in another order
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
    picked = mask.sum(axis=1)
    for r, (c, runs) in enumerate(CASES[name]):
        assert picked[r] == min(TOPK, sum(b - a for a, b in runs))
        if not c or not runs:  # no step, or a context and no pick: zero
            assert not got[r].any()
        else:
            assert got[r].any()
    assert not got[len(CASES[name]):].any()
    if name.startswith("b-"):
        # fetches of 32 keys: the picks' fetches, a row
        held = [sorted({int(s) // FETCH for s in np.flatnonzero(mask[r])})
                for r in range(3)]
        assert held == [[0, 2], [2], [1]] and (ctx[:3] > 2 * FETCH).all()
    if name.startswith("c-"):
        assert mask[2].any() and ctx[2] == 0


def test_rows_mask_is_the_gathers_set():
    """``select_rows_mask`` against ``select_rows`` on one query a row whose
    scores TIE at the k-th (the index pool holds a key many times over): the
    same set, the ties to the lower positions; a row that is not active and
    a row with fewer keys than k among them."""
    rng = np.random.default_rng(5)
    J, D, k = 2, 16, 12
    keys = rng.standard_normal((1, NB, BS, D)).astype(np.float32)
    keys[0, :, 2:6] = keys[0, :1, 2:3]  # half of every block: ONE key
    tables = jnp.asarray(rng.permutation(NB)[:R * BLOCKS].reshape(R, BLOCKS),
                         jnp.int32)
    q = jnp.asarray(rng.standard_normal((R, J, D)), jnp.float32)
    w = jnp.asarray(np.abs(rng.standard_normal((R, J))), jnp.float32)
    positions = jnp.asarray([87, 40, 5, 63, 70, 20], jnp.int32)
    active = jnp.asarray([True, True, True, True, False, True])
    args = (q, w, jnp.asarray(keys), jnp.int32(0), tables, positions, active,
            k)
    idx, ok = map(np.asarray, la.select_rows(*args))
    mask = np.asarray(la.select_rows_mask(*args))
    want = np.asarray(la.rows_as_mask(jnp.asarray(idx), jnp.asarray(ok), S))
    assert mask.shape == (R, S) and (mask == want).all()
    assert mask.sum(axis=1).tolist() == [k, k, 6, k, 0, k]
    # the tie is real: at least one row's k-th score is held by more keys
    # than the pick takes of them
    scores = np.asarray(la._row_scores(*args[:-1]))
    kth = np.sort(scores, axis=1)[:, -k]
    tied = [(scores[r] == kth[r]).sum() > (mask[r] & (scores[r] == kth[r])
                                            ).sum() for r in (0, 1, 3, 5)]
    assert any(tied)


@pytest.mark.parametrize("blocks, on_chip, form", [
    (BLOCKS, False, "masked, pallas"),
    # a table past ``_MASKED_UP_TO`` times the picks: the gather
    (la._MASKED_UP_TO * TOPK // BS + 1, False, "past_crossing"),
    # a latent of 64 values and a fetch of 32 keys are no whole lanes:
    # Mosaic could slice neither the pool's value part nor the selection
    (BLOCKS, True, "fallback")])
def test_event_says_what_engaged(blocks, on_chip, form, small_fetches,
                                 monkeypatch):
    """The ring event of a traced call on both sides of the crossing, as the
    step program chooses (``decode_gathers``, by the static shapes alone)."""
    monkeypatch.setattr(backend, "interpret", lambda: not on_chip)
    sds = jax.ShapeDtypeStruct
    q, pool = sds((R, H, W), jnp.float32), sds((L, NB, BS, W), jnp.float32)
    tables = sds((R, blocks), jnp.int32)
    why = la.decode_gathers(blocks, pool, LATENT, TOPK)
    kw = dict(scale=SCALE, latent=LATENT)
    tracer.clear()
    if why:
        out = jax.eval_shape(
            functools.partial(la.latent_decode_attention, **kw), q, pool,
            sds((), jnp.int32), tables, sds((R, TOPK), jnp.int32),
            sds((R, TOPK), bool))
    else:
        out = jax.eval_shape(
            functools.partial(la.latent_decode_attention_masked, k=TOPK,
                              **kw), q, pool, sds((), jnp.int32), tables,
            sds((R, blocks * BS), bool), sds((R,), jnp.int32))
    assert out.shape == (R, H, LATENT) and out.dtype == jnp.float32
    event, = [s.attrs for s in tracer.spans()
              if s.name == "kernel/latent_attention_decode_tiles"]
    assert (event["rows"], event["heads"], event["w"], event["block"],
            event["s_max"], event["k"]) == (R, H, W, BS, blocks * BS, TOPK)
    if why:
        assert why == form and event["form"] == "gathered, xla"
        assert event[why] == 1
    else:
        assert event["form"] == form and "fallback" not in event
        assert (event["kb"], event["slots"]) == (FETCH // BS, la._FULL_SLOTS)


def test_chooser_at_the_cell():
    """GLM-5.2's serving cell: tables of 272 blocks of 64 keys under 2,048
    picks read through the kernel, 16 blocks a fetch; the same picks under a
    table of 64k keys are gathered."""
    pool = jax.ShapeDtypeStruct((9, 4353, 64, 640), jnp.bfloat16)
    assert la.decode_gathers(272, pool, 512, 2048) == ""
    assert la._fetch_blocks(272, 64) == 16
    assert la.decode_gathers(1024, pool, 512, 2048) == "past_crossing"
    # every visible key is picked: nothing to gather by
    assert la.decode_gathers(16, pool, 512, 1024) == ""
