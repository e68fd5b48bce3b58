import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
E = tr.Event


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert tr.total(tr.clip([(0, 4), (6, 9)], 2, 7)) == 3


def test_names_from_hlo_text():
    text = ('%attn.42 = (bf16[2,4,512,128]{3,2,1,0:T(8,128)(2,1)S(1)}, '
            'f32[2,4,512,1]{3,2,1,0:T(8,128)}) custom-call(bf16[2] %x), '
            'custom_call_target="tpu_custom_call"')
    assert tr.describe(text) == tr.Op("attn.42", "custom-call",
                                      "bf16[2,4,512,128]", True, False)
    assert tr.describe("not hlo") == tr.Op("not hlo", "", "", False, False)
    loop = tr.describe(
        '%while.5 = (s32[]{:T(128)}, bf16[8]{0}) while((s32[]) %t), body=%b')
    assert (loop.name, loop.code, loop.container) == ("while.5", "while", True)
    assert tr.describe(
        '%all-gather-done.3 = bf16[8]{0} all-gather-done((bf16[2]) %s)'
    ).collective
    assert tr.describe(
        '%all-reduce-scatter.1 = f32[4]{0} fusion(f32[16]{0} %g), kind=kCustom'
    ).collective
    assert tr.module_name("jit_fwd(16188141146180184629)") == "jit_fwd"


def synthetic() -> tr.Trace:
    """One chip, a window of 100 us.  A ``while`` from 10 to 60 encloses a
    matmul fusion (10-30), a Pallas call (30-40) and a collective (40-60);
    a copy runs beside the collective's first half (40-50).  Idle: 0-10
    before the first call, 60-100 after it: 60-70 still inside the
    benchmark's span round the call, 70-100 outside every span."""
    ops = [
        E("%while.1 = (s32[]) while((s32[]) %t), body=%b", 10e3, 60e3),
        E("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kOutput",
          10e3, 30e3),
        E('%attn.1 = bf16[8]{0} custom-call(bf16[8]{0} %q), '
          'custom_call_target="tpu_custom_call"', 30e3, 40e3),
        E("%all-gather.1 = bf16[32]{0} all-gather(bf16[8]{0} %w)",
          40e3, 60e3),
        E("%copy.1 = bf16[8]{0} copy(bf16[8]{0} %c)", 40e3, 50e3),
    ]
    return tr.Trace(
        device_ops={0: ops},
        device_modules={0: [E("jit_step(123)", 10e3, 60e3)]},
        host_spans=[E(tr.WINDOW, 0.0, 100e3),
                    E("bench/train_batch", 5e3, 70e3)])


def test_reduce_synthetic():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s"] == pytest.approx(50e-6)
    assert r["pallas_s"] == pytest.approx(10e-6)
    assert r["collective_s"] == pytest.approx(20e-6)
    assert r["collective_exposed_s"] == pytest.approx(10e-6)
    # the enclosing while is nobody's time; the leaves are named
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["jit_step/fusion.1 fusion bf16[8]"] == pytest.approx(20e-6)
    assert ops["jit_step/attn.1 custom-call bf16[8] pallas"] == pytest.approx(10e-6)
    assert not any("while" in k for k in ops)
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["bench/train_batch"] == pytest.approx(15e-6)
    assert gaps["between calls"] == pytest.approx(35e-6)
    assert r["longest_gap_s"] == pytest.approx(40e-6)


def test_gap_pieces_named_by_position_in_the_enclosing_span():
    spans = [E("bench/engine.step", 0, 100), E("bench/_decode_fwd", 30, 40)]
    got = dict(tr._host_pieces((10, 90), spans))
    assert got == {"bench/engine.step:pre": 20, "bench/_decode_fwd": 10,
                   "bench/engine.step:post": 50}


def test_no_window_or_no_device_op_reduces_to_nothing():
    t = synthetic()
    assert tr.reduce(tr.Trace(t.device_ops, t.device_modules, [])) is None
    assert tr.reduce(tr.Trace({}, {}, t.host_spans)) is None


def test_recorded_v5e_trace():
    """``fixture_v5e.xplane.pb.gz`` (``record_fixture.py``, a TPU v5e, PR 23),
    read by hand with ``ProfileData``: a window span of 15,993.220 us; three
    runs of ``jit_step_compat`` of 124.3, 125.2 and 124.8 us; 816 operations
    whose leaves sum to 320.348 us; 4,944.8 us from the end of the first
    program to the start of the second, while the host fetched the loss,
    slept 2 ms and placed the next batch."""
    trace = tr.load(FIXTURE)
    assert len(trace.device_ops[0]) == 816
    assert [tr.module_name(m.name) for m in trace.device_modules[0]] == \
        ["jit_step_compat"] * 3
    r = tr.reduce(trace)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(15993.220e-6)
    assert r["busy_s"] == pytest.approx(320.348e-6)
    assert 100 * r["busy_s"] / r["window_s"] == pytest.approx(2.003, abs=1e-3)
    assert r["device_ops"][0] == ["jit_step_compat/fusion.6 fusion bf16[512,256]",
                                  pytest.approx(32.009e-6)]
    assert r["pallas_s"] == pytest.approx(52.454e-6)  # three attn.N kernels
    assert sum("pallas" in name for name, _ in r["device_ops"]) == 3
    assert r["collective_s"] == 0.0
    # the longest gap: from the first program's last operation to the
    # second program's first, a little more than between the programs
    assert r["longest_gap_s"] == pytest.approx(5037.11e-6)
    assert r["longest_gap_s"] > 4944.8e-6
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert set(gaps) == {"between calls", "bench/train_batch",
                         "bench/fetch_loss",
                         "under 2 us (device turn-round)"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
