"""The OLMoE cell's driver, reference, readers and reduction, on the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import olmoe_rehearsal as rehearsal  # noqa: E402

from benchmark import kernel_time, moe_flops, trace_reduce  # noqa: E402
from benchmark.layer_metrics import (moe_dispatch_busy_pct,  # noqa: E402
                                     moe_gemm_busy_pct, moe_gemm_roofline_pct)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("olmoe")))


def test_serve_moe_driver(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_serve_moe_driver_traced(copy, monkeypatch):
    """The traced path: the profiler started and stopped, the step programs'
    scopes read off their compiled text, every reader called.  The CPU has no
    device plane, so the trace that is reduced is the recorded TPU one."""
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_router_check_sees_a_bf16_router():
    rehearsal.check_router_has_teeth()


def test_kernel_time_on_the_recorded_trace():
    """Three steps of a one-layer trainer, recorded before the kernels had
    names of their own (PR 23: all three of flash attention's are ``attn``):
    nine calls in the one program; busy and kernel time are
    ``trace_reduce``'s."""
    trace = trace_reduce.load(rehearsal.FIXTURE)
    by_name = kernel_time.reduce(trace)
    assert by_name["busy_s"] == pytest.approx(
        trace_reduce.reduce(trace)["busy_s"])
    assert set(by_name["kernel_s"]) == {"jit_step_compat/attn"}
    assert sum(by_name["kernel_s"].values()) == pytest.approx(
        trace_reduce.reduce(trace)["pallas_s"])
    assert by_name["kernel_calls"]["jit_step_compat/attn"] == 9
    assert by_name["scope_s"] == {}
    assert kernel_time.kernel_name("grouped_mixed_gemm.12") \
        == "grouped_mixed_gemm"


HLO = '''HloModule jit_decode_step, entry_computation_layout={()->f32[]}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%fc, metadata={op_name="jit(decode_step)/jit(main)/while/body/moe_dispatch/scatter" source_file="x.py" source_line=1}
  ROOT %sort.2 = f32[8]{0} sort(f32[8]{0} %p), metadata={op_name="jit(decode_step)/jit(main)/while/body/moe_route/top_k"}
  %add.1 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b), metadata={op_name="jit(decode_step)/jit(main)/add"}
'''


def _event(name, start, dur):
    return trace_reduce.Event(name, start, start + dur)


def synthetic_obs():
    """A window of 1 ms on one chip: one decode step whose layer runs three
    grouped GEMMs of 100 us, a dispatch fusion, a router sort and an add."""
    call = ('%grouped_mixed_gemm.{} = bf16[1280,1024]{{1,0}} custom-call('
            'bf16[1280,2048]{{1,0}} %x), custom_call_target="tpu_custom_call"')
    ops = [_event(call.format(i), 100_000 * (i + 1), 100_000)
           for i in range(3)]
    ops += [_event("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p)", 450_000,
                   30_000),
            _event("%sort.2 = f32[8]{0} sort(f32[8]{0} %p)", 500_000, 20_000),
            _event("%add.1 = f32[8]{0} add(f32[8]{0} %a)", 600_000, 50_000)]
    trace = trace_reduce.Trace(
        {0: ops}, {0: [_event("jit_decode_step(1)", 50_000, 900_000)]},
        [_event(trace_reduce.WINDOW, 0, 1_000_000)])
    scope_of = {"jit_decode_step": kernel_time.scopes_of_text(
        HLO, ("moe_route", "moe_dispatch", "moe_combine"))}
    model = {"hidden_size": 2048, "intermediate_size": 1024,
             "num_experts": 64}
    return {"trace": {"by_name": kernel_time.reduce(trace, scope_of)},
            "model": model,
            "engine": {"weight_bits": 8, "weight_group": 256},
            "device": {"peaks": {"hbm_bytes_per_s": 819e9}},
            "window": {"t_open": 0.0, "t_close": 1.0},
            "spans": [{"name": "engine/step", "t_start": 0.1, "t_end": 0.2,
                       "attrs": {"kind": "decode", "moe_rows": 256,
                                 "moe_rows_padded": 1280,
                                 "moe_experts_hit": 63.0,
                                 "moe_rows_max": 11}}]}


def test_device_trace_readers_on_a_synthetic_trace():
    obs = synthetic_obs()
    by_name = obs["trace"]["by_name"]
    assert by_name["busy_s"] == pytest.approx(400e-6)
    assert by_name["scope_s"] == {
        "jit_decode_step/moe_dispatch": pytest.approx(30e-6),
        "jit_decode_step/moe_route": pytest.approx(20e-6)}
    assert moe_gemm_busy_pct.read(obs) == pytest.approx(75.0)
    assert moe_dispatch_busy_pct.read(obs) == pytest.approx(12.5)
    # one layer-step: 63 experts' codes and scales and 256 rows in and out
    weights = 63 * 3 * (2048 * 1024 + 8 * 1024 * 4)
    acts = 3 * 256 * (2048 + 1024) * 2
    assert moe_flops.grouped_gemm_bytes(obs["model"], 256, 63.0, 8, 256) \
        == weights + acts
    assert moe_gemm_roofline_pct.read(obs) == pytest.approx(
        100 * (weights + acts) / 819e9 / 300e-6)
    assert moe_flops.grouped_gemm_flops(obs["model"], 4096) \
        == 3 * 2 * 4096 * 2048 * 1024


def test_readers_read_nothing_from_an_older_program():
    """A program without the spans, or a driver without the reduction by
    name, leaves the metrics out and does not raise."""
    from benchmark.layer_metrics import moe_experts_hit_pct, moe_pad_rows_pct

    obs = {"spans": [{"name": "engine/step", "t_start": 0.1, "t_end": 0.2,
                      "attrs": {"kind": "decode"}}],
           "trace": {"busy_s": 1.0}, "window": {"t_open": 0.0,
                                                "t_close": 1.0}}
    for reader in (moe_gemm_busy_pct, moe_gemm_roofline_pct,
                   moe_dispatch_busy_pct, moe_pad_rows_pct,
                   moe_experts_hit_pct):
        assert reader.read(obs) is None
