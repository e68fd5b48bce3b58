"""Two steps in flight (ISSUE 50: a decode step behind a decode step; ISSUE
54: a step of either kind behind either kind, ``engine._step_impl``): a step
whose successor needs nothing of it but the token ids has that successor
called BEFORE its own tokens are fetched, the ids handed over on the device
through a row map (``engine._promise``).  Held here, one tiny model a served
kind: the tokens are those of an engine that never goes ahead (and of the
float32 reference); what happens to the engine between two calls drops exactly
the tokens it should and leaks nothing; a request put between two calls joins
the step after; a step goes ahead only where an arrival could not have joined
it anyway (or, as since ISSUE 50, a decode step behind a decode step); the
table and the picks' descriptors are the called step's own when its program
is called; and every way of leaving or changing an engine copes with a
program under way.

To drive an engine that never goes ahead: ``eng._may_go_ahead = lambda *a:
False`` (the gate is one method; there is no option).  Every case that claims
identity asserts ``ahead_steps > 0`` (decode steps) or ``mixed_ahead_steps >
0`` beside it."""

import os
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import tracer

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from served_kinds import TINY_KINDS  # noqa: E402
from test_inference_v2 import _greedy_reference  # noqa: E402

_V2 = dict(max_tokens_per_step=24, max_seqs=4, block_size=8, num_blocks=96,
           max_blocks_per_seq=16, dtype="float32")
# (prompt tokens, budget): decode across a block's edge, across a window's
# (tiny-evabyte: 32 tokens; tiny-mellum2: 8), a prompt of several chunks, a
# short budget that ends a decode phase early
_REQUESTS = ((5, 30), (40, 20), (27, 9), (3, 41))
_KINDS = sorted(TINY_KINDS)


@pytest.fixture(scope="module", params=_KINDS)
def built(request):
    preset, over = TINY_KINDS[request.param]
    cfg = tfm.get_config(preset, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return request.param, cfg, params, V2Config(**{**_V2, **over})


def _engine(built, never=False, **over):
    _, cfg, params, v2 = built
    eng = InferenceEngineV2(cfg, params, V2Config(
        **{**{f: getattr(v2, f) for f in _V2}, **over}))
    if never:
        eng._may_go_ahead = lambda *a: False
    return eng


def _prompt(n, seed=0):
    return np.random.default_rng([n, seed]).integers(1, 200, n).tolist()


def _put_all(eng, sampled):
    """``_REQUESTS``; ``sampled``: with pinned seeds, a pinned temperature on
    two rows, the step's on one and greedy on one."""
    temps = (0.8, None, 0.0, 1.1) if sampled else (None,) * 4
    return [eng.put(_prompt(n), budget, temperature=t, seed=17 + i)
            for i, ((n, budget), t) in enumerate(zip(_REQUESTS, temps))]


def _run(eng, temperature=0.0, between=None):
    """Step until nothing is left, a program under way included → the tokens
    by uid, and every step's output in order."""
    served, steps = {}, []
    while eng.running or eng.waiting or eng._ahead is not None:
        out = eng.step(temperature=temperature)
        steps.append(out)
        for uid, toks in out.items():
            served.setdefault(uid, []).extend(toks)
        if between is not None:
            between(eng, out, len(steps))
    return served, steps


# -- (a) the tokens ---------------------------------------------------------


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_serves_what_an_engine_that_never_goes_ahead_serves(devices, built,
                                                            sampled):
    tracer.clear()
    eng = _engine(built)
    uids = _put_all(eng, sampled)
    served, steps = _run(eng, temperature=0.7 if sampled else 0.0)
    spans = [s.attrs for s in tracer.spans(name="engine/step")]
    never = _engine(built, never=True)
    _put_all(never, sampled)
    want, want_steps = _run(never, temperature=0.7 if sampled else 0.0)
    assert served == want and steps == want_steps
    assert [len(served[u]) for u in uids] == [b for _, b in _REQUESTS]
    assert never.ahead_steps == never.mixed_ahead_steps == 0
    assert eng.ahead_dropped == 0
    # four requests over four rows: every step but the first found its
    # program under way while all four ran, the mixed steps too; with a row
    # free a decode step behind a decode step still does, and the last
    # phase's first does not
    decode = [a for a in spans if a["kind"] == "decode"]
    mixed = [a for a in spans if a["kind"] == "mixed"]
    assert eng.ahead_steps == sum(a["ahead"] for a in decode) >= 25
    assert eng.ahead_steps >= 0.9 * len(decode)
    assert eng.mixed_ahead_steps == sum(a["ahead"] for a in mixed) \
        == len(mixed) - 1 >= 2
    assert all(a["ahead"] for a in spans[1:] if a["running"] == 4)
    assert eng.fast_steps == never.fast_steps == len(decode)
    assert eng.drained() and never.drained()
    for a in decode:  # a program a step, a copy a program
        assert a["h2d_copies"] == 1 and a["h2d_bytes"] == \
            eng._decode_layout.size * 4
        assert (a["staged"] == "ahead") == bool(a["ahead"])


def test_serves_what_the_float32_reference_serves(devices):
    """The dense model against the plain uncached forward, greedy."""
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngineV2(cfg, params, V2Config(**_V2))
    uids = _put_all(eng, False)
    served, _ = _run(eng)
    assert eng.ahead_steps >= 25
    for uid, (n, budget) in zip(uids, _REQUESTS):
        assert served[uid] == _greedy_reference(cfg, params, _prompt(n),
                                                budget)


# -- (b) a cancel and a stop token between two calls ------------------------


def test_a_row_retired_between_two_calls_loses_its_ahead_token(devices,
                                                               built):
    """A ``cancel`` and a stop token (the broker's: ``cancel`` of the request
    that just emitted one) find the next program under way: the call that
    fetches it returns the other rows' tokens, drops exactly the retired
    rows', counts them, and every block is free after the drain."""
    tracer.clear()
    eng = _engine(built)
    uids = _put_all(eng, True)
    gone = {}

    def between(eng, out, n):
        if eng._ahead is None:
            return
        if n >= 8 and uids[3] not in gone:  # a client went away
            assert uids[3] in eng.running
            eng.cancel(uids[3])
            gone[uids[3]] = n
        elif n >= 14 and uids[0] not in gone:  # what it just emitted stops it
            assert uids[0] in out and uids[0] in eng.running
            eng.cancel(uids[0])
            gone[uids[0]] = n

    served, steps = _run(eng, temperature=0.7, between=between)
    assert sorted(gone) == sorted([uids[0], uids[3]])
    spans = [s.attrs for s in tracer.spans(name="engine/step")]
    for uid, n in gone.items():
        # the step after: fetched whole, that row's token dropped, the others'
        # returned
        a = spans[n]
        assert (a["kind"], a["ahead"], a["ahead_dropped"]) == ("decode", 1, 1)
        assert uid not in steps[n] and steps[n]
        assert a["emitted"] == len(steps[n]) == a["tokens"]
    assert sum(a.get("ahead_dropped", 0) for a in spans) == 2 == \
        eng.ahead_dropped
    assert eng.drained() and not eng.running and eng._ahead is None
    # the rows that stayed were served what an engine serves that retires the
    # same rows at the same steps and never goes ahead
    never = _engine(built, never=True)
    assert _put_all(never, True) == uids
    want, _ = _run(never, temperature=0.7, between=lambda e, out, n: [
        e.cancel(u) for u, at in gone.items() if at == n])
    assert served == want and never.ahead_dropped == 0


def test_every_row_cancelled_leaves_a_program_nobody_waits_for(devices,
                                                               built):
    """All rows cancelled with a program under way: nothing runs, every block
    is back at once (the broker goes idle here); the program is fetched and
    dropped by the next ``step``, which serves a request put since from the
    step after."""
    eng = _engine(built)
    uids = _put_all(eng, False)
    while eng._prefilling or eng.waiting or eng._ahead is None:
        eng.step()
    live = len(eng.running)
    for uid in uids:
        eng.cancel(uid)
    assert eng._ahead is not None and not eng.running and eng.drained()
    uid = eng.put(_prompt(6), 4)
    assert eng.step() == {} and eng._ahead is None  # the program's step
    assert eng.ahead_dropped == live
    served, _ = _run(eng)
    assert list(served) == [uid] and len(served[uid]) == 4
    assert eng.drained()


# -- (c) a put between two calls --------------------------------------------


def test_a_request_put_between_two_calls_waits_one_step(devices, built):
    def drive(eng):
        first = eng.put(_prompt(9), 12)
        steps = [eng.step() for _ in range(3)]
        under_way = eng._ahead is not None
        late = eng.put(_prompt(7), 5)
        steps += [eng.step(), eng.step()]
        return first, late, under_way, steps + _run(eng)[1]

    tracer.clear()
    eng = _engine(built)
    first, late, under_way, steps = drive(eng)
    assert under_way
    # the program under way: a decode step of the old row; the step after:
    # the mixed step that admits the new one
    assert list(steps[3]) == [first] and sorted(steps[4]) == [first, late]
    kinds = [(s.attrs["kind"], s.attrs.get("ahead"), s.attrs.get("ahead_next"))
             for s in tracer.spans(name="engine/step")]
    assert kinds[3:5] == [("decode", 1, 0), ("mixed", 0, 0)]
    # an engine that never goes ahead admits it a step earlier and serves
    # both the same tokens
    never = _engine(built, never=True)
    _, _, under_way, want = drive(never)
    assert not under_way and sorted(want[3]) == [first, late]
    for uid in (first, late):
        assert [s[uid][0] for s in steps if uid in s] == \
            [s[uid][0] for s in want if uid in s]
    assert eng.drained()


# -- (d) when a step goes ahead ---------------------------------------------


def _step_kinds():
    return [(a["kind"], a["running"], a["ahead"], a["ahead_next"],
             a.get("staged"))
            for a in (s.attrs for s in tracer.spans(name="engine/step"))]


def test_with_every_slot_taken_a_step_goes_ahead_of_a_budget_and_a_prefill(
        devices, built):
    """Four requests over four rows: an arrival could join no step, so every
    step calls its successor before its own fetch, whatever both are: a mixed
    step behind a mixed step (a prompt of two chunks prefills), a decode step
    behind the mixed step that ends the prefill, and the step behind the one
    that hands a row its last token (the row holds its slot until that
    token's fetch).  Once a slot is free only a decode step behind a decode
    step goes ahead, as since ISSUE 50, a row at its budget or not."""
    tracer.clear()
    eng = _engine(built)
    for n, budget in ((5, 4), (6, 9), (30, 6), (7, 9)):
        eng.put(_prompt(n), budget)
    _run(eng)
    assert _step_kinds() == [
        ("mixed", 0, 0, 1, None),  # (the fourth joins the step called here)
        ("mixed", 4, 1, 1, None),  # two prompts prefill
        ("mixed", 4, 1, 1, None),  # the last chunk
        ("decode", 4, 1, 1, "ahead"),  # the first row's fourth token
        ("decode", 3, 1, 1, "ahead"), ("decode", 3, 1, 1, "ahead"),
        ("decode", 3, 1, 1, "ahead"),  # the third row's sixth
        ("decode", 2, 1, 1, "ahead"),
        ("decode", 2, 1, 1, "ahead"),  # the second row's ninth
        ("decode", 1, 1, 1, "ahead"), ("decode", 1, 1, 0, "ahead")]
    programs = [(s.attrs["kind"], s.attrs["step"], s.attrs["behind"])
                for s in tracer.spans(name="engine/program")]
    assert programs == [("mixed", 1, 0), ("mixed", 2, 1), ("mixed", 3, 1)] + [
        ("decode", n, 1) for n in range(4, 12)]
    assert (eng.mixed_ahead_steps, eng.ahead_steps) == (2, 8)
    assert eng.drained()


def test_with_a_free_slot_and_nothing_waiting_a_mixed_step_does_not_go_ahead(
        devices, built):
    """Two requests over four rows: a request arriving now could join the
    next step, so neither the mixed step nor the decode step behind it is
    called ahead (that one starts from the staged buffer); a decode step
    behind a decode step is, the one behind a row's last token too."""
    tracer.clear()
    eng = _engine(built)
    eng.put(_prompt(5), 4)
    eng.put(_prompt(6), 9)
    _run(eng)
    assert _step_kinds() == [
        ("mixed", 0, 0, 0, None),
        ("decode", 2, 0, 1, "used"), ("decode", 2, 1, 1, "ahead"),
        ("decode", 2, 1, 1, "ahead"),  # the first row's fourth token
        ("decode", 1, 1, 1, "ahead"), ("decode", 1, 1, 1, "ahead"),
        ("decode", 1, 1, 1, "ahead"), ("decode", 1, 1, 1, "ahead"),
        ("decode", 1, 1, 0, "ahead")]
    assert (eng.mixed_ahead_steps, eng.ahead_steps) == (0, 7)
    assert eng.drained()


def test_a_request_that_capacity_keeps_out_lets_the_steps_go_ahead(devices,
                                                                   built):
    """Five requests over four rows: the fifth waits in the engine (every
    step is a mixed step) and an arrival would wait behind it, so the steps
    go ahead; the four end in one step, whose successor would be the fifth
    alone, which can take no slot before their fetch: nothing is called ahead
    there, and nothing was released early.  A caller that hands ``step`` its
    keys never goes ahead."""
    tracer.clear()
    eng = _engine(built)
    uids = [eng.put(_prompt(4 + i), 6) for i in range(5)]
    served, _ = _run(eng)
    got = [(kind, running, ahead, nxt) for kind, running, ahead, nxt, _
           in _step_kinds()]
    assert got[:7] == [("mixed", 0, 0, 1)] + [("mixed", 4, 1, 1)] * 4 + [
        ("mixed", 4, 1, 0), ("mixed", 0, 0, 0)]
    assert [len(served[u]) for u in uids] == [6] * 5
    assert eng.mixed_ahead_steps == 5 and eng.drained()
    keyed = _engine(built)
    for i in range(4):
        keyed.put(_prompt(5 + i), 8)
    key = jax.random.PRNGKey(3)
    while keyed.running or keyed.waiting:
        key, sub = jax.random.split(key)
        keyed.step(rng=sub)
        assert keyed._ahead is None
    assert keyed.fast_steps == 7
    assert keyed.ahead_steps == keyed.mixed_ahead_steps == 0


def test_no_step_goes_ahead_under_speculation(devices):
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngineV2(cfg, params, V2Config(
        **{**_V2, "spec_mode": "self_draft", "spec_k": 2}))
    eng.put(_prompt(5), 12)
    while eng.running or eng.waiting:
        eng.step()
        assert eng._ahead is None
    assert eng.spec_steps > 0 and eng.ahead_steps == 0 and eng.drained()


# -- (e) the table at the call ----------------------------------------------


def test_the_table_is_the_called_steps_own(devices, built):
    """A wrapper round ``_decode_fwd`` like the benchmark's taps reads
    ``table.ctx`` / ``active`` / ``seq_at`` at the call: they describe the
    step that is called (each row's position is the one its logits belong
    to), also where that step is called before its predecessor's tokens are
    on the host; ``hist`` and ``next_tok`` follow when the tokens arrive."""
    eng = _engine(built)
    uids = _put_all(eng, False)
    real, seen = eng._decode_fwd, []

    def tapped(params, caches, token_ids, position_ids, tables, context_lens,
               *rest):
        t = eng.table
        rows = np.nonzero(t.active)[0]
        seen.append({t.seq_at[int(r)].uid: int(t.ctx[r]) for r in rows})
        # the program's own inputs say the same
        np.testing.assert_array_equal(np.asarray(position_ids)[rows],
                                      t.ctx[rows])
        np.testing.assert_array_equal(np.asarray(context_lens),
                                      (t.ctx + 1) * t.active)
        return real(params, caches, token_ids, position_ids, tables,
                    context_lens, *rest)

    eng._decode_fwd = tapped
    served, _ = _run(eng)
    assert eng.ahead_steps >= 25 and len(seen) == eng.fast_steps
    # every token a request got from a decode step was filed once, under the
    # position of the token it follows: each position up to the last but one
    # (its first tokens come from mixed steps, while others prefill)
    for uid, (n, budget) in zip(uids, _REQUESTS):
        filed = [s[uid] for s in seen if uid in s]
        assert filed == list(range(filed[0], n + budget - 1))
        assert n <= filed[0] <= n + 4 and len(served[uid]) == budget


# -- (f) leaving or changing an engine with a program under way -------------


def _under_way(built, kind="decode", **over):
    """An engine with a program of ``kind`` under way (four rows of four:
    the mixed steps go ahead too)."""
    eng = _engine(built, **over)
    uids = _put_all(eng, False)
    while eng._ahead is None or eng._ahead.kind != kind:
        eng.step()
    return eng, uids


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_generate_all_takes_over_a_program_under_way(devices, built, kind):
    eng, uids = _under_way(built, kind)
    got = eng.generate_all()
    want_eng = _engine(built, never=True)
    want_uids = _put_all(want_eng, False)
    want = want_eng.generate_all()
    assert [got[u] for u in uids] == [want[u] for u in want_uids]
    assert eng.mixed_ahead_steps + eng.ahead_steps > 0
    assert eng._ahead is None and eng.drained()
    with pytest.raises(RuntimeError, match="under way"):
        busy, _ = _under_way(built, kind)
        busy._burst_decode(2)


@pytest.mark.parametrize("kind", ["decode", "mixed"])
def test_close_and_swap_params_leave_a_program_under_way_alone(devices,
                                                               built, kind):
    """``close`` is the pager's alone, and ``swap_params`` is a drained
    engine's (its rows were cancelled): neither waits for the program, which
    holds the weights it was called with; the next ``step`` fetches it,
    drops its tokens and counts them, and serves what is put since."""
    eng, uids = _under_way(built, kind)
    eng.close()
    assert eng._ahead is not None
    served, _ = _run(eng)  # (closing is no end)
    assert sorted(served) == uids and eng.drained()
    assert eng.ahead_dropped == 0
    eng, uids = _under_way(built, kind)
    # the rows that get a token from the program under way: all of a decode
    # program's, of a mixed program's those whose prompt ends in it
    owed = sum(seq.seen_tokens + n >= seq.cur_len
               for seq, n in eng._ahead.picks) or len(eng.running)
    for uid in uids:
        eng.cancel(uid)
    assert eng.drained() and eng._ahead is not None
    eng.swap_params(eng.params)
    uid = eng.put(_prompt(6), 4)
    assert eng.step() == {} and eng.ahead_dropped == owed
    assert len(_run(eng)[0][uid]) == 4 and eng.drained()


def test_the_prefix_cache_and_the_pager_read_behind_a_program_under_way(
        devices, tmp_path):
    """Rows retired under a program under way donate their blocks; the cache
    demotes, exports and promotes them while that program still writes one
    slot behind a retired row's context, and requests that come back to the
    same prompts are served what a cold engine serves them."""
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    v2 = {**_V2, "num_blocks": 32, "enable_prefix_cache": True,
          "kv_host_pool_bytes": 1 << 20}
    eng = InferenceEngineV2(cfg, params, V2Config(**v2))
    prompts = [_prompt(33, seed=s) for s in range(3)]
    uids = [eng.put(p, 20) for p in prompts]
    while eng._ahead is None or eng.table.gen.max() < 9:
        eng.step()
    for uid in uids[:2]:
        eng.cancel(uid)  # donates full blocks; the program is under way
    # the first cancel left the pool short of a sequence's headroom: blocks
    # were demoted (read from the host) with the program under way
    assert eng._ahead is not None and eng.prefix_stats()["demotions"] >= 1
    payload = eng.export_prefix(prompts[1])  # reads a pool from the host
    assert payload is not None
    _run(eng)
    assert eng.ahead_dropped == 2
    # fill the pool so that cached blocks are demoted, then come back
    again = [eng.put(p, 6) for p in prompts] + [eng.put(_prompt(40, 9), 6)]
    served, _ = _run(eng)
    cold = InferenceEngineV2(cfg, params, V2Config(**_V2))
    cold._may_go_ahead = lambda *a: False
    cold_uids = [cold.put(p, 6) for p in prompts] + [cold.put(_prompt(40, 9),
                                                              6)]
    want, _ = _run(cold)
    assert [served[u] for u in again] == [want[u] for u in cold_uids]
    stats = eng.prefix_stats()
    assert stats["hits"] >= 2 and stats["promotions"] >= 1
    assert eng.ahead_steps > 0
    other = InferenceEngineV2(cfg, params, V2Config(**v2))
    assert other.import_prefix(payload) == 32
    eng.close()
