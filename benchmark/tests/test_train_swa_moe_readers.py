"""The flash kernels' shares by kind of layer, the gate's share and the two
counters of ``trinity-train-16k`` on hand-made observations and traces: a
banded call is told from a full one by its name, each is credited with the
pairs of its own band, and a program that has neither the names nor the scope
reads nothing.  No chip, no program: ``kernel_time`` over a
``trace_reduce.Trace`` built here, ``swa_moe_train_flops``' readers over what
it returns."""

import importlib.util
import json
import os

import pytest

from benchmark import kernel_time, trace_reduce
from benchmark import swa_moe_train_flops as sm
from benchmark.drivers import train_swa_moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "trinity-mini-ep8-train.json")) as f:
    MODEL = train_swa_moe.model_of(json.load(f))
with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
    PEAKS = json.load(f)["TPU v5 lite"]
PROGRAM = "jit_step_compat"
SEQ = 16384
NAMES = {("sliding", False): sm.FLASH["sliding"][0],
         ("sliding", True): sm.FLASH["sliding"][1],
         ("full", False): sm.FLASH["full"][0],
         ("full", True): sm.FLASH["full"][1]}


def reader(name):
    """``benchmark/layer_metrics/<name>.py``'s ``read``, found as
    ``run.load_module`` finds it."""
    path = os.path.join(ROOT, "benchmark", "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def floor_s(kind, backward):
    fwd, bwd = sm.flash_call_flops(MODEL, kind, 1, SEQ)
    return (bwd if backward else fwd) / PEAKS["bf16_flops_per_s"]


def step(slack=1.0, remat=1):
    """One whole execution: four window layers and a full one, each layer's
    forward run ``1 + remat`` times and its backward once, every call at
    ``slack`` x its floor."""
    calls, seconds = {}, {}
    for kind, layers in (("sliding", 4), ("full", 1)):
        fwd, (dkv, dq) = sm.FLASH[kind]
        calls[fwd] = layers * (1 + remat)
        seconds[fwd] = calls[fwd] * slack * floor_s(kind, False)
        calls[dkv] = calls[dq] = layers
        # the two kernels of one backward share its time, unevenly
        seconds[dkv] = layers * slack * floor_s(kind, True) * 0.6
        seconds[dq] = layers * slack * floor_s(kind, True) * 0.4
    return {"chip": 0, "seconds": 0.7, "fetch": 0,
            "kernel_calls": {f"{PROGRAM}/{k}": n for k, n in calls.items()},
            "kernel_s": {f"{PROGRAM}/{k}": s for k, s in seconds.items()}}


def observed(steps, window=None, model=MODEL, counters=None):
    by_name = dict(window or {"busy_s": 3.0, "kernel_s": {},
                              "kernel_calls": {}, "scope_s": {}},
                   steps=steps)
    return {"model": model, "device": {"peaks": PEAKS},
            "trace": {"by_name": by_name},
            "train": {"rows": 1, "seq_len": SEQ,
                      "counters": counters or {}}}


READERS = {("sliding", False): "swa_flash_fwd_roofline_pct",
           ("sliding", True): "swa_flash_bwd_roofline_pct",
           ("full", False): "full_flash_fwd_roofline_pct",
           ("full", True): "full_flash_bwd_roofline_pct"}


@pytest.mark.parametrize("kind,backward", list(READERS))
@pytest.mark.parametrize("slack,remat", [(1.0, 1), (1.6, 1), (2.0, 0),
                                         (1.25, 2)])
def test_a_call_at_its_floor_reads_100_and_none_reads_over(kind, backward,
                                                           slack, remat):
    """Each kind's calls against the pairs of its OWN band: at the floor 100,
    slower 100 / slack, however often the forward is rematerialised."""
    read = reader(READERS[kind, backward])
    obs = observed([step(slack, remat), step(slack, remat)])
    assert read(obs) == pytest.approx(100.0 / slack)
    assert read(obs) <= 100.0 + 1e-9


def test_the_band_is_counted_as_the_band():
    """A window layer's call is credited with 2,048 keys a query, not the
    triangle: a quarter of the full layer's pairs at 16,384 (4.27 times
    fewer), and a full layer's time never enters a window layer's share."""
    band, full = sm.pairs(MODEL, "sliding", SEQ), sm.pairs(MODEL, "full", SEQ)
    assert band == 2048 * 2049 / 2 + (SEQ - 2048) * 2048
    assert full == SEQ * (SEQ + 1) / 2
    assert full / band == pytest.approx(4.267, abs=1e-3)
    # a window that cuts nothing is the triangle
    assert sm.pairs(MODEL, "sliding", 2048) == 2048 * 2049 / 2
    slow_full = step()
    key = f"{PROGRAM}/{sm.FLASH['full'][0]}"
    slow_full["kernel_s"][key] *= 3
    obs = observed([slow_full])
    assert reader("swa_flash_fwd_roofline_pct")(obs) == pytest.approx(100.0)
    assert reader("full_flash_fwd_roofline_pct")(obs) == pytest.approx(
        100.0 / 3)


def test_a_program_without_the_names_reads_nothing():
    """The parent's program names every flash call alike and has no gate: the
    banded readers and the gate's find nothing, and nothing is raised; nor
    for an observation without a trace, a model or counters."""
    plain = step()
    for k in list(plain["kernel_s"]):
        if k.endswith("_band"):
            for d in (plain["kernel_s"], plain["kernel_calls"]):
                d[k[:-5]] = d.get(k[:-5], 0) + d.pop(k)
    obs = observed([plain])
    assert reader("swa_flash_fwd_roofline_pct")(obs) is None
    assert reader("swa_flash_bwd_roofline_pct")(obs) is None
    assert reader("attn_gate_busy_pct")(obs) is None
    for name in list(READERS.values()) + [
            "gqa_flash_busy_pct", "attn_gate_busy_pct",
            "train_moe_load_max_over_mean", "train_moe_bias_abs_max"]:
        for empty in ({}, {"trace": None}, {"train": {}},
                      observed([], model={"hidden_size": 1})):
            assert reader(name)(empty) is None, name


CALL = ('%{}.{} = bf16[1,32,16384,128]{{3,2,1,0}} custom-call('
        'bf16[1,32,16384,128]{{3,2,1,0}} %x), '
        'custom_call_target="tpu_custom_call"')
MUL = ('%{} = bf16[16384,4096]{{1,0}} fusion(bf16[16384,4096]{{1,0}} %a), '
       'kind=kLoop')
HLO = """HloModule jit_step_compat, entry_computation_layout={()->()}
  %fusion.7 = bf16[16384,4096]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step_compat)/jit(main)/layers/attn/attn_gate/mul" source_file="x.py"}
  %fusion.8 = bf16[16384,4096]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step_compat)/jit(main)/transpose(jvp(layers))/attn/attn_gate/dot_general" source_file="x.py"}
  %fusion.9 = bf16[16384,4096]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step_compat)/jit(main)/layers/attn/qk_norm/mul" source_file="x.py"}
  %fusion.10 = bf16[16384,2048]{1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(step_compat)/jit(main)/layers/attn/dot_general" source_file="x.py"}
"""


def _event(name, start, dur):
    return trace_reduce.Event(name, start, start + dur)


def traced():
    """A window of 3.2 ms: four executions of 700 us, each a banded forward
    (100 us), a plain forward (200 us), two operations of the gate's scope
    (25 us each, one of them in the backward), the q/k norm's and an
    attention projection (50 us each).  The chip's first and last execution
    are what the profiler cut (``kernel_time.whole_steps``)."""
    ops, mods, host = [], [], [_event(trace_reduce.WINDOW, 0, 3_200_000)]
    for i in range(4):
        t0 = 100_000 + 750_000 * i
        mods.append(_event(f"{PROGRAM}(9)", t0, 700_000))
        ops += [_event(CALL.format(NAMES["sliding", False], i), t0, 100_000),
                _event(CALL.format(NAMES["full", False], i), t0 + 100_000,
                       200_000),
                _event(MUL.format("fusion.7"), t0 + 300_000, 25_000),
                _event(MUL.format("fusion.8"), t0 + 325_000, 25_000),
                _event(MUL.format("fusion.9"), t0 + 350_000, 50_000),
                _event(MUL.format("fusion.10"), t0 + 400_000, 50_000)]
        host.append(_event(train_swa_moe.FETCH, t0 + 300_000, 410_000))
    return trace_reduce.Trace({0: ops}, {0: mods}, host)


def test_the_gate_and_the_six_kernels_off_a_trace():
    """``kernel_time.reduce`` under the driver's scopes: the gate's
    operations count under ``attn_gate`` though they lie inside ``attn``, and
    the banded and the plain kernel are two names."""
    trace = traced()
    window = kernel_time.reduce(trace, {
        PROGRAM: kernel_time.scopes_of_text(HLO, train_swa_moe.SCOPES)})
    assert window["busy_s"] == pytest.approx(4 * 450e-6)
    assert window["scope_s"][f"{PROGRAM}/attn_gate"] == pytest.approx(200e-6)
    assert window["scope_s"][f"{PROGRAM}/qk_norm"] == pytest.approx(200e-6)
    assert window["scope_s"][f"{PROGRAM}/attn"] == pytest.approx(200e-6)
    steps = kernel_time.whole_steps(trace, PROGRAM, train_swa_moe.FETCH)
    assert [s["fetch"] for s in steps] == [1, 2]
    obs = observed(steps, window=window)
    assert reader("attn_gate_busy_pct")(obs) == pytest.approx(
        100 * 200 / 1800)
    assert reader("gqa_flash_busy_pct")(obs) == pytest.approx(
        100 * 1200 / 1800)
    fwd = sm.flash_call_flops(MODEL, "sliding", 1, SEQ)[0]
    assert reader("swa_flash_fwd_roofline_pct")(obs) == pytest.approx(
        100 * 2 * fwd / PEAKS["bf16_flops_per_s"] / 200e-6)
    assert reader("swa_flash_bwd_roofline_pct")(obs) is None


def test_the_two_counters():
    obs = observed([], counters={"moe_local_rows": 16000.0,
                                 "moe_load_max_over_mean": 1.11,
                                 "moe_bias_abs_max": 0.071})
    assert reader("train_moe_load_max_over_mean")(obs) == 1.11
    assert reader("train_moe_bias_abs_max")(obs) == 0.071
    import numpy as np

    counts = np.array([[10, 30, 20, 20], [20, 20, 20, 20]])
    assert train_swa_moe.load_of(counts) == pytest.approx((1.5 + 1.0) / 2)
