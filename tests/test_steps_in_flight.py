"""A step of either kind called behind a step of either kind (ISSUE 54), under
a loop like the broker's: more requests than rows, each put ``strict`` as soon
as the engine takes it, so the table stays full, prompts of several chunks
prefill beside rows that decode, rows end (two in one step) and the admission
that replaces a row joins one step on.  One tiny model a served kind
(``tests/test_decode_ahead.py`` holds the rule itself and the decode step's
cases).

The comparison is an engine that never goes ahead and follows the SAME
schedule: it is handed each request at the step whose program the first one
put it in (one later where a program was under way at the ``put``), is made
to give it the same row of the table (a sampled row draws under its step's
key, its seed and its row), and cancels what the first one cancelled at the
same steps.  Then every step of the two serves the same tokens, greedy and
sampled."""

import os
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import AdmissionError
from deepspeed_tpu.observability.trace import tracer

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from test_decode_ahead import _engine, _prompt, built  # noqa: E402,F401

# (prompt tokens, budget), nine over four rows: two that end in one step, a
# prompt of three chunks, budgets that end phases early, a prompt across a
# window's edge (tiny-evabyte: 32 tokens; tiny-mellum2: 8)
_REQUESTS = ((5, 10), (6, 10), (60, 7), (27, 9), (3, 12), (50, 5), (8, 6),
             (31, 8), (4, 9))


def _serve(eng, sampled=False, follow=None, watch=None):
    """The broker's loop over ``_REQUESTS`` → (tokens by uid, every step's
    output, the schedule).  ``watch(eng, n, out)`` → uids to cancel before
    step ``n`` (``out``: the step before's tokens); ``follow``: another run's
    schedule to keep to (``puts``: request → step; ``rows``: uid → row;
    ``cancels``: step → uids)."""
    queue = list(enumerate(_REQUESTS))
    served, steps, out = {}, [], {}
    puts, rows, cancels = {}, {}, {}
    free = (eng.kv.slots._free if eng.kv.slots is not None
            else eng.table._free)
    n = 0
    while queue or eng.running or eng.waiting or eng._ahead is not None:
        gone = (watch(eng, n, out) if watch else
                follow["cancels"].get(n, ()) if follow else ())
        for uid in gone:
            # (put and cancelled round one step of the first run: this one
            # has not seen it yet)
            assert uid > eng._uid or eng.cancel(uid)
            cancels.setdefault(n, []).append(uid)
        while queue:
            i, (length, budget) = queue[0]
            if follow is not None and follow["puts"][i] > n:
                break
            try:
                eng.put(_prompt(length, i), budget, strict=True, seed=17 + i,
                        temperature=0.8 if sampled and i % 2 else None)
            except AdmissionError:
                assert follow is None
                break
            queue.pop(0)
            puts[i] = n + (eng._ahead is not None)
            if eng._uid in gone:
                assert eng.cancel(eng._uid)
        if follow is not None:  # the rows the first run gave, in FIFO order
            for seq in reversed(eng.waiting):
                if follow["rows"][seq.uid] in free:
                    free.remove(follow["rows"][seq.uid])
                    free.append(follow["rows"][seq.uid])
        out = eng.step(temperature=0.7 if sampled else 0.0)
        for uid, row in eng.table.row_of.items():
            rows.setdefault(uid, row)
        steps.append(out)
        for uid, toks in out.items():
            served.setdefault(uid, []).extend(toks)
        n += 1
        assert n < 400
    return served, steps, dict(puts=puts, rows=rows, cancels=cancels)


def _programs():
    return [(s.attrs["kind"], s.attrs["behind"])
            for s in tracer.spans(name="engine/program")]


@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
def test_a_full_table_serves_what_an_engine_that_never_goes_ahead_serves(
        devices, built, sampled):
    tracer.clear()
    eng = _engine(built)
    served, steps, schedule = _serve(eng, sampled)
    programs = _programs()
    never = _engine(built, never=True)
    want, want_steps, _ = _serve(never, sampled, follow=schedule)
    assert steps == want_steps and served == want
    assert [len(served[u]) for u in sorted(served)] == \
        [budget for _, budget in _REQUESTS]
    assert eng.drained() and never.drained() and eng.ahead_dropped == 0
    assert never.ahead_steps == never.mixed_ahead_steps == 0
    # every transition: each kind behind each, and nearly every program
    for kind, behind in (("mixed", "mixed"), ("mixed", "decode"),
                         ("decode", "mixed"), ("decode", "decode")):
        assert any(a == (kind, 1) and b[0] == behind
                   for b, a in zip(programs, programs[1:])), (kind, behind)
    assert sum(b for _, b in programs) >= len(programs) - 3
    assert eng.mixed_ahead_steps >= 8 and eng.ahead_steps >= 12
    # an admission found the step called and joined the one after
    assert any(at > 0 for at in schedule["puts"].values())


def test_a_row_retired_under_a_mixed_program_loses_what_it_was_owed(devices,
                                                                   built):
    """A ``cancel`` and a stop token between two calls with a MIXED program
    under way, of a row that program still prefills, of a row whose prompt
    ends in it, and of a row that decodes in it (what it just emitted stops
    it): the next call fetches the program whole, drops the token of each row
    that was owed one (two of the three) and counts it, gives every other row
    its own; the slots given back are taken again by the admissions behind,
    and everything drains."""
    todo = {"prefilling": None, "last chunk": None, "stopped": None}

    def watch(eng, n, out):
        run = eng._ahead
        if run is None or run.kind != "mixed" or n < 2:
            return ()
        for seq, chunk in run.picks:
            ends = seq.seen_tokens + chunk >= seq.cur_len
            case = ("stopped" if seq.in_decode and seq.uid in out
                    else "last chunk" if ends and not seq.in_decode
                    else "prefilling" if not ends else None)
            # one a step, each of another request, the rest left to run on
            if case and todo[case] is None and seq.uid not in todo.values():
                todo[case] = seq.uid
                return (seq.uid,)
        return ()

    tracer.clear()
    eng = _engine(built)
    served, steps, schedule = _serve(eng, watch=watch)
    assert all(todo.values()), todo
    spans = [s.attrs for s in tracer.spans(name="engine/step")]
    for n, (uid,) in schedule["cancels"].items():
        a = spans[n]  # the step that fetched the program under way
        owed = uid != todo["prefilling"]
        assert (a["kind"], a["ahead"], a["ahead_dropped"]) == \
            ("mixed", 1, int(owed))
        assert uid not in steps[n]
    assert eng.ahead_dropped == 2 and eng.drained() and not eng.running
    # a row given back was taken again (of a state model: its slot)
    rows = schedule["rows"]
    assert any(rows[u] == rows[gone] for gone in todo.values()
               for u in rows if u > gone)
    # greedy: a mixed program samples a row under its place among the picks,
    # which a row cancelled before the call moves and one cancelled behind it
    # does not
    never = _engine(built, never=True)
    want, want_steps, _ = _serve(never, follow=schedule)
    assert steps == want_steps and served == want
    assert never.ahead_dropped == 0 and never.drained()


def test_a_tap_reads_the_called_steps_own_descriptors(devices, built):
    """``benchmark/logit_tap.py`` over a run that goes ahead: at the call of
    ``_fwd`` the picks' descriptors are that step's own (``seen_tokens`` where
    its chunk begins, ``cur_len`` with the place of a token still under way),
    at the call of ``_decode_fwd`` the table is, so the tap files the same
    logits under the same positions as on an engine that never goes ahead."""
    from benchmark.logit_tap import LogitTap

    cfg = built[1]  # (a model with several output heads files them all)
    eng = _engine(built)
    tap = LogitTap(eng)
    served, _, schedule = _serve(eng)
    tap.remove()
    assert eng.mixed_ahead_steps >= 8 and eng.ahead_steps >= 12
    never = _engine(built, never=True)
    want_tap = LogitTap(never)
    want, _, _ = _serve(never, follow=schedule)
    want_tap.remove()
    assert served == want and sorted(tap.logits) == sorted(want_tap.logits)
    for i, (length, budget) in enumerate(_REQUESTS):
        got, ref = tap.logits[i + 1], want_tap.logits[i + 1]
        # a token a position from the prompt's last on, each filed once
        assert [p for p, _ in got] == [p for p, _ in ref] == \
            list(range(length - 1, length + budget - 1))
        for (_, a), (_, b) in zip(got, ref):
            np.testing.assert_array_equal(a, b)
        # and the token served is the argmax of what was filed
        assert [int(a[:cfg.vocab_size].argmax()) for _, a in got] == \
            served[i + 1]


def test_the_prefix_cache_and_the_pager_read_behind_a_mixed_program(devices):
    """Rows retired while a mixed program that still prefills them is under
    way donate the blocks they have filled (the program writes behind them);
    the cache demotes and exports those while it runs, and requests that come
    back to the same prompts are served what a cold engine serves them."""
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.models import transformer as tfm
    from test_decode_ahead import _V2, _run

    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    v2 = {**_V2, "max_seqs": 3, "num_blocks": 32,
          "enable_prefix_cache": True, "kv_host_pool_bytes": 1 << 20}
    eng = InferenceEngineV2(cfg, params, V2Config(**v2))
    prompts = [_prompt(60, seed=s) for s in range(3)]
    uids = [eng.put(p, 12) for p in prompts]

    def second_prefills_under_a_mixed_program():
        seq = eng.running.get(uids[1])
        return (eng._ahead is not None and eng._ahead.kind == "mixed"
                and seq is not None and seq.seen_tokens >= 16
                and not seq.in_decode)

    while not second_prefills_under_a_mixed_program():
        eng.step()
    for uid in uids[:2]:
        eng.cancel(uid)  # donates full blocks; the program is under way
    assert eng._ahead is not None and eng.prefix_stats()["demotions"] >= 1
    payload = eng.export_prefix(prompts[1])  # reads a pool from the host
    assert payload is not None
    _run(eng)
    assert eng.mixed_ahead_steps > 0
    again = [eng.put(p, 6) for p in prompts]
    served, _ = _run(eng)
    cold = InferenceEngineV2(cfg, params, V2Config(**{**_V2, "max_seqs": 3}))
    cold._may_go_ahead = lambda *a: False
    cold_uids = [cold.put(p, 6) for p in prompts]
    want, _ = _run(cold)
    assert [served[u] for u in again] == [want[u] for u in cold_uids]
    stats = eng.prefix_stats()
    assert stats["hits"] >= 2 and stats["promotions"] >= 1
    other = InferenceEngineV2(cfg, params, V2Config(**v2))
    assert other.import_prefix(payload) >= 16
    eng.close()
