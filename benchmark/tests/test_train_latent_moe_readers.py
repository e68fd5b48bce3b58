"""``train_moe_gemm_roofline_pct`` and the flash kernels' shares on hand-made
observations and traces (ISSUE 44): the grouped GEMM's calls are credited
with the rows they were handed, in the steps that were traced whole.  No
chip, no program: ``kernel_time.whole_steps`` over a ``trace_reduce.Trace``
built here, ``latent_moe_flops``' readers over what it returns."""

import json
import os

import pytest

from benchmark import kernel_time, trace_reduce
from benchmark import latent_moe_flops as lm
from benchmark.drivers import train_latent_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "deepseek-v2-lite-ep8-train.json")) as f:
    MODEL = train_latent_moe.model_of(json.load(f))
with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]
PROGRAM = "jit_train_step"
LAYERS = 5  # routed: 6 layers less the leading dense one
FWD, DLHS, DRHS = lm.GROUPED


def floor_s(rows, hit=8.0):
    """The least time of one call over ``rows`` local rows."""
    flops, nbytes = lm.grouped_call(MODEL, rows, hit)
    return max(flops / PEAKS["bf16_flops_per_s"],
               nbytes / PEAKS["hbm_bytes_per_s"])


def step(fetch, seconds, rounds=LAYERS, remat=1):
    """A whole execution as ``kernel_time.whole_steps`` gives it: ``rounds``
    rounds over the routed layers (5: one each), each 3 forward calls,
    ``remat`` x 3 rematerialised, 3 dlhs and 3 drhs; ``seconds`` of kernel
    time spread over the calls evenly."""
    calls = {FWD: 3 * rounds * (1 + remat), DLHS: 3 * rounds,
             DRHS: 3 * rounds}
    total = sum(calls.values())
    return {"chip": 0, "seconds": 0.7, "fetch": fetch,
            "kernel_calls": {f"{PROGRAM}/{k}": n for k, n in calls.items()},
            "kernel_s": {f"{PROGRAM}/{k}": seconds * n / total
                         for k, n in calls.items()}}


def observed(steps, step_rows, traced_from=0, mean_rows=None, window=None):
    """``step_rows``: every step's ``moe_local_rows`` in the order fetched;
    ``mean_rows``: the whole window's mean (what PR 42's reader credited)."""
    counters = [{"moe_local_rows": float(r), "moe_experts_hit": 8.0,
                 "moe_rows_max": r / 6.0, "moe_aux_loss": 5.0}
                for r in step_rows]
    mean = dict(counters[0], moe_local_rows=float(
        mean_rows if mean_rows is not None
        else sum(step_rows) / len(step_rows)))
    by_name = dict(window or {"busy_s": 3.0, "kernel_s": {},
                              "kernel_calls": {}, "scope_s": {}},
                   steps=steps)
    return {"model": MODEL, "device": {"peaks": PEAKS},
            "trace": {"by_name": by_name},
            "train": {"rows": 2, "seq_len": 8192, "counters": mean,
                      "step_counters": counters, "traced_from": traced_from}}


def test_one_round_or_two_read_the_same():
    """(a) The same rows and the same kernel time, walked in one round a
    layer or with a second round in two of the layers: a second round adds
    calls and no rows."""
    seconds = 12 * LAYERS * floor_s(10_000) / 0.8
    one = lm.grouped_roofline(observed([step(0, seconds)], [10_000]))
    two = lm.grouped_roofline(observed([step(0, seconds, rounds=LAYERS + 2)],
                                       [10_000]))
    assert lm.grouped_passes(step(0, seconds)) == 12
    assert lm.grouped_passes(step(0, seconds, rounds=LAYERS + 2)) == 12
    assert one == pytest.approx(80.0) and two == pytest.approx(one)
    # PR 42's count went up with the calls: 72 + 12 of them here
    twice = lm.grouped_roofline(observed([step(0, seconds, remat=2)],
                                         [10_000]))
    assert lm.grouped_passes(step(0, seconds, remat=2)) == 15
    assert twice == pytest.approx(100.0)  # a second remat IS more work


def test_the_share_follows_the_traced_steps_rows():
    """(b) A window whose mean is 1.3 x the traced steps' rows reads what the
    traced steps give; the steps are found through the fetch's ordinal."""
    rows = [13_000] * 3 + [9_000, 10_000, 11_000] + [13_265] * 34
    mean = sum(rows) / len(rows)
    assert mean == pytest.approx(1.3 * 10_000, rel=1e-3)
    seconds = [12 * LAYERS * floor_s(r) / 0.75 for r in rows[3:6]]
    obs = observed([step(i, s) for i, s in enumerate(seconds)], rows,
                   traced_from=3)
    assert [lm.counters_of(obs, s)["moe_local_rows"]
            for s in lm.whole_steps(obs)] == [9_000, 10_000, 11_000]
    assert lm.grouped_roofline(obs) == pytest.approx(75.0)
    # the window's mean would have read 1.3 x that
    assert 12 * LAYERS * 3 * floor_s(mean) / sum(seconds) * 100 \
        == pytest.approx(75.0 * mean / 10_000, rel=1e-3)
    # a step whose fetch the trace cannot place counts on neither side
    lost = dict(step(None, 1.0))
    assert lm.grouped_roofline(observed(
        [step(i, s) for i, s in enumerate(seconds)] + [lost], rows,
        traced_from=3)) == pytest.approx(75.0)
    # without the steps' own counters there is nothing to read
    obs["train"].pop("step_counters")
    assert lm.grouped_roofline(obs) is None


CALL = ('%{}.{} = bf16[22528,1408]{{1,0}} custom-call(bf16[22528,2048]{{1,0}}'
        ' %x), custom_call_target="tpu_custom_call"')


def _event(name, start, dur):
    return trace_reduce.Event(name, start, start + dur)


def traced(cut_ns=0):
    """A window of 3 ms: three executions of 0.9 ms back to back from 0.1 ms,
    each with four kernel calls of 100 us (two grouped, a dlhs, a flash
    forward); a fourth execution begins at 2.8 ms and the window's edge cuts
    it and, by ``cut_ns``, its first kernel call.  A fetch ends 10 us after
    each execution."""
    ops, mods, host = [], [], [_event(trace_reduce.WINDOW, 0, 3_000_000)]
    for i in range(4):
        t0 = 100_000 + 900_000 * i
        mods.append(_event(f"{PROGRAM}(77)", t0, 900_000))
        for j, name in enumerate((FWD, FWD, DLHS, lm.FLASH_FWD)):
            start = t0 + 200_000 * j + (100_000 + cut_ns if i == 3 else 0)
            ops.append(_event(CALL.format(name, 10 * i + j), start, 100_000))
        host.append(_event(train_latent_moe.FETCH, t0 + 500_000, 410_000))
    mods.append(_event("jit_place(3)", 50_000, 20_000))
    return trace_reduce.Trace({0: ops}, {0: mods}, host)


@pytest.mark.parametrize("cut_ns", [0, 60_000])
def test_a_call_the_edge_cuts_counts_on_neither_side(cut_ns):
    """(c) The fourth execution's first call lies inside the window whole
    (``cut_ns`` 0) or is cut by its edge: the window's reduction counts it as
    a call either way, and part of its time; the whole steps hold neither."""
    trace = traced(cut_ns)
    window = kernel_time.reduce(trace)
    assert window["kernel_calls"][f"{PROGRAM}/{FWD}"] == 7
    assert window["kernel_s"][f"{PROGRAM}/{FWD}"] == pytest.approx(
        700e-6 - cut_ns / 1e9)
    steps = kernel_time.whole_steps(trace, PROGRAM, train_latent_moe.FETCH)
    assert [s["fetch"] for s in steps] == [0, 1, 2]
    assert all(s["seconds"] == pytest.approx(900e-6) for s in steps)
    for s in steps:
        assert s["kernel_calls"] == {f"{PROGRAM}/{FWD}": 2,
                                     f"{PROGRAM}/{DLHS}": 1,
                                     f"{PROGRAM}/{lm.FLASH_FWD}": 1}
        assert s["kernel_s"][f"{PROGRAM}/{FWD}"] == pytest.approx(200e-6)
    # the readers: the flash forward's three whole calls, not the window's
    obs = observed(steps, [10_000] * 4, window=window)
    fwd, _ = lm.flash_call_flops(MODEL, 2, 8192)
    assert lm.flash_roofline(obs, backward=False) == pytest.approx(
        100 * 3 * fwd / PEAKS["bf16_flops_per_s"] / 300e-6)
    assert lm.flash_roofline(obs, backward=True) is None
    assert lm.grouped_roofline(obs) == pytest.approx(
        100 * LAYERS * 9 * floor_s(10_000) / 300e-6)
    # the share of busy time stays the window's
    assert lm.busy_share(obs, names=lm.GROUPED) == pytest.approx(
        100 * (900e-6 + 100e-6 - cut_ns / 1e9) / window["busy_s"])


def test_what_the_profiler_cut_is_not_a_whole_step():
    """The profiler starts and stops inside the window span, while a step
    runs: the chip's first and last executions are what it recorded of them
    (here 0.9 ms of more, and the window's edge has nothing to cut)."""
    trace = traced()
    trace = trace_reduce.Trace(
        trace.device_ops,
        {0: [m for m in trace.device_modules[0] if "place" not in m.name]},
        [s if s.name != trace_reduce.WINDOW
         else _event(trace_reduce.WINDOW, 0, 4_000_000)
         for s in trace.host_spans])
    steps = kernel_time.whole_steps(trace, PROGRAM, train_latent_moe.FETCH)
    assert [s["fetch"] for s in steps] == [1, 2]
    window = kernel_time.reduce(trace)  # the window's own count holds all 4
    assert window["kernel_calls"][f"{PROGRAM}/{lm.FLASH_FWD}"] == 4


def test_two_executions_behind_one_fetch_are_not_placed():
    """The host fell a step behind (the profiler's start): two executions end
    before one fetch does, and neither is given its counters."""
    trace = traced()
    late = [s for s in trace.host_spans if s.name != train_latent_moe.FETCH]
    late += [_event(train_latent_moe.FETCH, 150_000, 1_800_000),
             _event(train_latent_moe.FETCH, 1_960_000, 850_000)]
    steps = kernel_time.whole_steps(
        trace_reduce.Trace(trace.device_ops, trace.device_modules, late),
        PROGRAM, train_latent_moe.FETCH)
    assert [s["fetch"] for s in steps] == [None, None, 1]
    assert kernel_time.whole_steps(
        trace_reduce.Trace({}, {}, []), PROGRAM, "x") == []


@pytest.mark.parametrize("slack", [1.0, 1.25, 2.0])
@pytest.mark.parametrize("second_rounds", [0, 1, 3])
@pytest.mark.parametrize("mean_over_traced", [0.7, 1.0, 1.3])
def test_at_the_floor_it_reads_100_and_nothing_reads_over(
        slack, second_rounds, mean_over_traced):
    """(d) Every call at exactly its floor reads 100.0; slower calls, second
    rounds and a window whose mean rows are not the traced steps' read
    100 / slack, never over."""
    rows = [9_500, 10_400, 12_288]
    steps = [step(i, slack * 12 * LAYERS * floor_s(r),
                  rounds=LAYERS + second_rounds)
             for i, r in enumerate(rows)]
    obs = observed(steps, [11_000] * 4 + rows + [11_000] * 60, traced_from=4,
                   mean_rows=mean_over_traced * sum(rows) / 3)
    assert lm.grouped_roofline(obs) == pytest.approx(100.0 / slack)
    assert lm.grouped_roofline(obs) <= 100.0 + 1e-9
