"""The per-layer metrics that read the program's sub-spans (ISSUE 24), each
over a hand-made ``obs``: a value, ``None`` over nothing (a program from
before the sub-spans records none of the attributes), and spans outside the
window left out as the driver leaves them out."""

import json
import os

import pytest

from benchmark import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
T_OPEN, T_CLOSE = 100.0, 110.0


def span(name, t_start, t_end, **attrs):
    return {"name": name, "t_start": t_start, "t_end": t_end, "attrs": attrs}


def step(kind, t_start, ms, device_ms, tokens=32, budget=512):
    return span("engine/step", t_start, t_start + ms / 1e3, kind=kind,
                device_ms=device_ms, tokens=tokens, budget=budget)


def obs_of(spans):
    """What ``drivers/serve.py`` hands the readers: the spans that ended
    inside the window."""
    return {"window": {"t_open": T_OPEN, "t_close": T_CLOSE,
                       "seconds": T_CLOSE - T_OPEN},
            "spans": [s for s in spans if T_OPEN <= s["t_end"] < T_CLOSE]}


SPANS = [
    step("decode", 99.0, 90.0, 80.0),  # ended before the window opened
    step("decode", 100.0, 92.0, 85.0),  # host 7
    step("decode", 101.0, 94.0, 85.0),  # host 9
    step("decode", 102.0, 100.0, 89.0),  # host 11
    step("mixed", 103.0, 210.0, 200.0, tokens=512),  # host 10, fill 100 %
    step("mixed", 104.0, 214.0, 200.0, tokens=128),  # host 14, fill 25 %
    step("mixed", 109.9, 220.0, 200.0, tokens=1),  # ends after the close
    span("engine/step", 105.0, 105.001, kind="mixed", tokens=0,
         budget=512),  # scheduled nothing: no device_ms, no chunk paid
    span("request/prefill", 100.0, 100.5),
    span("request/prefill", 100.0, 102.5),
    span("request/prefill", 90.0, 99.0),
    span("request/first_write", 101.0, 101.002),
    span("request/first_write", 101.0, 101.004),
    span("broker/turn", 100.5, 100.501, next="step"),
    span("broker/turn", 100.6, 100.603, next="step"),
    span("broker/turn", 100.7, 100.750, next="idle"),  # not a turn to a step
]
WANT = {
    "decode_host_ms_p50": 9.0,
    "mixed_host_ms_p50": 12.0,
    "mixed_step_fill_pct": 100.0 * (1.0 + 0.25) / 2,
    "prefill_wait_p90_ms": 500.0 + 0.9 * 2000.0,
    "loop_turn_ms_p50": 2.0,
    "first_write_p90_ms": 2.0 + 0.9 * 2.0,
    "loop_not_waiting_pct": 100.0 * (1.0 - 0.659 / 10.0),
}


def reader(name):
    return run.load_module(os.path.join(ROOT, "benchmark"), "layer_metrics",
                           name, "metric").read


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_over_a_hand_made_window(name):
    read = reader(name)
    assert read(obs_of(SPANS)) == pytest.approx(WANT[name], rel=1e-9)
    assert read(obs_of([])) is None
    # the parent program: the same spans without what this PR records
    old = [span(s["name"], s["t_start"], s["t_end"],
                **{k: v for k, v in s["attrs"].items() if k == "kind"})
           for s in SPANS if s["name"] in ("engine/step", "request/prefill")]
    if name == "prefill_wait_p90_ms":  # request/prefill was already there
        assert read(obs_of(old)) == pytest.approx(WANT[name])
    else:
        assert read(obs_of(old)) is None


def test_every_new_entry_finds_its_file_and_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entries = {m["name"]: m for m in spec["per_layer"]}
    # appended in ISSUE 24's order; later PRs append theirs behind them
    assert [m["name"] for m in spec["per_layer"] if m["name"] in WANT] == [
        "decode_host_ms_p50", "mixed_host_ms_p50", "mixed_step_fill_pct",
        "prefill_wait_p90_ms", "loop_turn_ms_p50", "first_write_p90_ms",
        "loop_not_waiting_pct"]
    layers = {m["layer"] for m in spec["per_layer"] if m["name"] not in WANT}
    for name in WANT:
        m = entries[name]
        assert callable(reader(name))
        assert m["source"] == "program_span" and m["layer"] in layers
        moved = next(e for e in spec["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(moved["workloads"])
        for cell in m["workloads"]:
            assert m in run.metrics_of(spec, "per_layer", cell)
