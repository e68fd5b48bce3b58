"""The ragged prefill attention kernel on the flat ``(T, H, D)`` queries
(ISSUE 32): the kernel (interpret mode here) and the blockwise XLA fallback
against a dense reference, over the ways a step lays its rows out, the shares
of query heads a KV head, and the sliding windows.  The operands keep one
shape a (group, window) so that a program is traced once and the row mixes
run on it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import paged_attention as pa

T, S, H, D, BS, MB, NB, L, LAYER = 300, 32, 8, 32, 16, 20, 700, 2, 1
Q = 9  # the verify step's positions a row


def _rows(name):
    """→ (chunk_start, chunk_len, q_start or None: end to end) of a mix."""
    rng = np.random.default_rng(len(name))
    ctx = rng.integers(1, MB * BS - Q - 1, S)
    if name == "decode-rows-only":
        return ctx - 1, np.ones(S, int), None
    if name == "one-long-chunk":  # two tiles of 128 and a tail in a third
        return [20], [290], None
    if name == "30-short-rows-beside-a-chunk":
        return list(ctx[:30] - 1) + [50], [1] * 30 + [200], None
    if name == "empty-rows-between":
        return ([5, 0, 40, 0, 0, 100, 0, 7],
                [3, 0, 10, 0, 0, 40, 0, 1], None)
    if name == "verify-gapped":  # row s from token s * Q on, up to Q tokens
        return ctx[:S] - 1, rng.integers(0, Q + 1, S), np.arange(S) * Q
    if name == "full-budget":  # every one of the T tokens is some row's
        return list(ctx[:20] - 1) + [12], [1] * 20 + [280], None
    if name == "padding-at-the-tail":
        return list(ctx[:5] - 1) + [0, 64], [1] * 5 + [33, 9], None
    raise KeyError(name)


MIXES = ["decode-rows-only", "one-long-chunk", "30-short-rows-beside-a-chunk",
         "empty-rows-between", "verify-gapped", "full-budget",
         "padding-at-the-tail"]


def _case(name, kv, dtype=np.float32, poison_behind=0):
    """A pool of ``L`` layers, a table of disjoint random blocks a row, flat
    queries.  With ``poison_behind`` (a window) every table entry behind a
    row's band points at a block of NaN, as the engine's freed blocks may."""
    rng = np.random.default_rng(sum(map(ord, name)) + kv)
    cs, cl, qs = _rows(name)
    cs, cl = (np.pad(np.asarray(x, np.int32), (0, S - len(x)))
              for x in (cs, cl))
    qs = (np.cumsum(cl) - cl if qs is None else qs).astype(np.int32)
    assert (qs + cl).max() <= T and (cs + cl).max() <= MB * BS
    ids = rng.permutation(NB - 1)  # the last block is the poisoned one
    nblk = -(-(cs + cl) // BS) * (cl > 0)
    tables = np.zeros((S, MB), np.int32)
    at = 0
    for r, n in enumerate(nblk):
        tables[r, :n] = ids[at:at + n]
        at += n
    k, v = (rng.standard_normal((L, NB, BS, kv, D)).astype(np.float32)
            for _ in range(2))
    q = rng.standard_normal((T, H, D)).astype(np.float32)
    dead = tables.copy()
    if poison_behind:
        k[:, NB - 1], v[:, NB - 1] = np.nan, np.nan
        for r in range(S):  # behind the chunk's oldest query's window
            dead[r, :max(int(cs[r]) - poison_behind + 1, 0) // BS] = NB - 1
    cast = lambda x: np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
    return dict(q=cast(q), k=cast(k), v=cast(v), tables=tables, dead=dead,
                qs=qs, cs=cs, cl=cl, dtype=dtype)


def _dense(c, window):
    """Reference, float32: each row's keys gathered in order, an explicit
    causal band; a token no row holds is zero."""
    want = np.zeros_like(c["q"])
    for r in np.nonzero(c["cl"])[0]:
        n = int(c["cs"][r] + c["cl"][r])
        blocks = c["tables"][r, :-(-n // BS)]
        ks, vs = (np.repeat(x[LAYER][blocks].reshape(-1, *x.shape[-2:])[:n],
                            H // x.shape[-2], 1) for x in (c["k"], c["v"]))
        sl = slice(c["qs"][r], c["qs"][r] + c["cl"][r])
        q_pos = c["cs"][r] + np.arange(c["cl"][r])
        s = np.einsum("qhd,thd->hqt", c["q"][sl], ks) / np.sqrt(D)
        j = np.arange(n)[None, :]
        seen = j <= q_pos[:, None]
        if window:
            seen &= q_pos[:, None] - j < window
        s = np.where(seen[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want[sl] = np.einsum("hqt,thd->qhd", p / p.sum(-1, keepdims=True), vs)
    return want


def _held(c):
    """Which of the T flat tokens some row holds."""
    held = np.zeros(T, bool)
    for r in range(S):
        held[c["qs"][r]:c["qs"][r] + c["cl"][r]] = True
    return held


@functools.lru_cache(maxsize=None)
def _jitted(impl: str, window: int):
    fn = {"pallas": pa.paged_prefill_attention,
          "xla": pa._prefill_attention_xla}[impl]
    return jax.jit(functools.partial(fn, window=window))


def _run(c, impl, window, tables="tables"):
    dt = c["dtype"]
    return np.asarray(_jitted(impl, window)(
        jnp.asarray(c["q"], dt), jnp.asarray(c["k"], dt),
        jnp.asarray(c["v"], dt), jnp.int32(LAYER), jnp.asarray(c[tables]),
        jnp.asarray(c["qs"]), jnp.asarray(c["cs"]), jnp.asarray(c["cl"])
    ).astype(jnp.float32))


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_flat_prefill_matches_dense(impl, group, mix):
    """Kernel and fallback equal the dense reference on every token a row
    holds, and give zero for every token none holds."""
    c = _case(mix, H // group)
    got = _run(c, impl, 0)
    np.testing.assert_allclose(got, _dense(c, 0), atol=2e-5, rtol=2e-5)
    held = _held(c)
    assert not got[~held].any()
    if mix == "full-budget":
        assert held.all()
    if mix in ("padding-at-the-tail", "verify-gapped"):
        assert not held.all()


@pytest.mark.parametrize("window", [8, 11, 16, 40])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_flat_prefill_reads_the_band_only(group, window):
    """Windows that are (8, 16) and are not (11, 40) multiples of the block:
    every table entry behind a row's band points at a block of NaN, so a tile
    has to start at the first block its oldest query sees; decode rows, a
    chunk over several tiles and short chunks in one call.  The fallback
    gathers every entry and masks: its dead entries point at live blocks, as
    the engine leaves them."""
    c = _case("30-short-rows-beside-a-chunk", H // group,
              poison_behind=window)
    want = _dense(c, window)
    np.testing.assert_allclose(_run(c, "pallas", window, "dead"), want,
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(_run(c, "xla", window), want, atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("window", [0, 24])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_flat_prefill_bfloat16_operands(group, window):
    """bfloat16 queries and cache: products in float32 (exact), the weights
    rounded to bfloat16 before ``p . v``, the output rounded to bfloat16.
    Against the float32 reference on the same (rounded) operands the worst
    element stays under 2e-2 (the output's own rounding is 2^-9 of values up
    to about 3; measured 4e-3 to 7e-3) and the mean under 2e-3 (measured
    2e-4 to 4e-4)."""
    c = _case("30-short-rows-beside-a-chunk", H // group, jnp.bfloat16)
    err = np.abs(_run(c, "pallas", window) - _dense(c, window))
    assert err.max() < 2e-2 and err.mean() < 2e-3, (err.max(), err.mean())


def test_flat_prefill_ignores_what_no_row_holds():
    """Padding tokens' queries are whatever the layer computed for them: NaN
    there reaches no row's output, and their own output is zero."""
    c = _case("padding-at-the-tail", 2)
    held = _held(c)
    want = _dense(c, 0)
    c["q"] = np.where(held[:, None, None], c["q"], np.nan)
    for impl in ("pallas", "xla"):
        got = _run(c, impl, 0)
        assert np.isfinite(got).all() and not got[~held].any(), impl
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("span", [128, 72])
@pytest.mark.parametrize("mix", MIXES)
def test_flat_prefill_in_spans(monkeypatch, mix, span):
    """A budget whose queries pass what a grid step may hold is walked in
    spans, here of 128 tokens (tiles of 128: T = 300 is two spans and a
    padded third) and of 72 (tiles of 8 and 72): a row that crosses a span's
    end is cut there, a row wholly in another span adds no tile, and every
    token comes out as from the one span."""
    picked = pa.pick_prefill_tiles(span, H, H // 4, D, BS, jnp.float32)
    assert (picked.big, picked.span) == (min(span, 128), span)
    monkeypatch.setattr(pa, "pick_prefill_tiles", lambda *_: picked)
    c = _case(mix, H // 4)
    for window, tables in ((0, "tables"), (11, "dead")):
        if window:
            c = _case(mix, H // 4, poison_behind=window)
        fn = jax.jit(functools.partial(pa.paged_prefill_attention,
                                       window=window))
        got = np.asarray(fn(*map(jnp.asarray, (
            c["q"], c["k"], c["v"], np.int32(LAYER), c[tables], c["qs"],
            c["cs"], c["cl"]))))
        np.testing.assert_allclose(got, _dense(c, window), atol=2e-5,
                                   rtol=2e-5)
        assert not got[~_held(c)].any()


@pytest.mark.parametrize("t,small,big", [(512, 8, 128), (160, 8, 128),
                                         (20, 8, 16), (4, 4, 4)])
def test_tiles_follow_the_budget(t, small, big):
    tiles = pa.pick_prefill_tiles(t, 32, 8, 128, 64, jnp.bfloat16)
    assert tiles == pa.PrefillTiles(small, big, 4, t)
    assert pa.pick_prefill_tiles(t, 32, 8, 128, 256, jnp.bfloat16).kb == 1


@pytest.mark.parametrize("t,heads,dtype,span", [
    (1024, 32, jnp.bfloat16, 1024), (2048, 32, jnp.bfloat16, 1024),
    (8192, 32, jnp.bfloat16, 1024), (8192, 16, jnp.bfloat16, 2048),
    (1000, 32, jnp.float32, 512), (600, 1024, jnp.float32, 128)])
def test_span_follows_the_bytes(t, heads, dtype, span):
    """A grid step holds all the queries while they are at most 8 MiB, else
    the whole tiles of 128 that are (one at least)."""
    tiles = pa.pick_prefill_tiles(t, heads, 8, 128, 64, dtype)
    assert tiles == pa.PrefillTiles(8, 128, 4, span)


def test_q_slots_round_a_row_up_to_its_tiles():
    """A row's tokens in tiles of 128, and what is left in ONE tile: of 8 if
    that holds it, else of 128; no tokens, no slots.  Rows that lie end to
    end in spans of 256: a row is cut where a span ends."""
    tiles = pa.PrefillTiles(8, 128, 4, 4096)
    n = np.asarray([0, 1, 8, 9, 32, 128, 129, 137, 161, 498])
    np.testing.assert_array_equal(
        tiles.slots(n), [0, 8, 8, 128, 128, 128, 136, 256, 256, 512])
    # a mixed step of 30 decode rows beside a chunk of 482: 752 slots
    assert tiles.slots(np.asarray([1] * 30 + [482])).sum() == 30 * 8 + 512
    # the verify step's layout: row s from token s * 9 on
    np.testing.assert_array_equal(
        tiles.slots([3, 0, 9], q_start=[0, 9, 18]), [8, 0, 128])
    spans = pa.PrefillTiles(8, 128, 4, 256)
    # tokens 0-9 | 10-299: 246 in the first span, 44 in the second | 300
    np.testing.assert_array_equal(
        spans.slots([10, 290, 1]), [128, 128 + 128 + 128, 8])


@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_twenty_heads_on_one_kv_head(monkeypatch, impl, dtype):
    """20 query heads on ONE K/V head (AI21-Jamba2's attention mixers: a
    group that is no multiple of the sublane tile of 8, where every other
    model has 1, 4, 8 or 16): the kernel neither pads nor falls back; with
    one K/V head it views a block's tokens as its rows (on the chip the DMA
    cannot slice a second-minor dimension of 1) and multiplies a slab of 20
    x the tile's queries.  Decode rows, a chunk over several tiles and short
    chunks in one call, against the dense formulation."""
    import sys

    from deepspeed_tpu.observability.trace import tracer

    monkeypatch.setattr(sys.modules[__name__], "H", 20)
    c = _case("30-short-rows-beside-a-chunk", 1, dtype)
    assert c["q"].shape == (T, 20, D) and c["k"].shape[3] == 1
    tracer.clear()
    err = np.abs(_run(c, impl, 0) - _dense(c, 0))
    if dtype is np.float32:
        assert err.max() < 2e-5
    else:  # the bounds of ``test_flat_prefill_bfloat16_operands``
        assert err.max() < 2e-2 and err.mean() < 2e-3, (err.max(),
                                                         err.mean())
    if impl == "pallas":
        (event,) = [s.attrs for s in tracer.spans()
                    if s.name == "kernel/paged_attention_prefill_tiles"][-1:]
        assert "fallback" not in event and (event["heads"], event["kv"]) \
            == (20, 1)
