"""``benchmark/tools/knee_sweep.py``: one run of the sweep rehearsed on the
CPU at the tiny preset (the tests' bypass of the TPU check), and the
arithmetic it adds to the cell's readers."""

import argparse
import json

import pytest

from benchmark import run
from benchmark.tools import knee_sweep

from test_harness import copy, fake_device  # noqa: F401  (a fixture)


def test_one_run_of_the_sweep(copy, monkeypatch, capsys):  # noqa: F811
    monkeypatch.setattr(knee_sweep, "ROOT", copy)
    monkeypatch.setattr(run.run_cell, "__defaults__",
                        (fake_device, run.ROOT))
    args = argparse.Namespace(workload="t-open", rate=6.0, seed=5,
                              seconds=3.0, trace=0, set=["schedule_seed=4"],
                              set_check=["window_sequences=2"])
    assert knee_sweep.one(args) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["rate"] == 6.0 and row["schedule_seed"] == 4
    assert row["correct"] and row["failed"] == 0
    assert 8 <= row["requests"] <= 40  # 6 a second for 3 s, Gamma(0.5)
    assert row["ttft_p90_ms"] >= row["ttft_p50_ms"] > 0
    assert 0 < row["mixed_gap_share_pct"] <= 100
    assert 0 < row["blocks_reserved_mean_pct"] \
        <= row["blocks_reserved_max_pct"] <= 100
    assert 0 < row["rows_mean"] <= 4 and 0 <= row["rows_full_pct"] <= 100
    assert row["prefilled_vs_due_pct"] > 50
    assert isinstance(row["sustained"], bool)
    assert 0 <= row["checks"]["worst_margin"][0] <= 0.5
    # the committed traffic file was read, not written
    with open(f"{copy}/benchmark/traffic/tiny-open.json") as f:
        assert json.load(f)["rate_per_s"] == 4.0


def span(name, t0, t1):
    return {"name": name, "t_start": t0, "t_end": t1, "attrs": {}}


def test_blocks_reserved_from_spans_and_records():
    """Two requests of 3 and 5 blocks (block 8), resident 101-104 and
    103-109 of a window 100-110 over 15 usable blocks, behind a warm-up
    request that came before the records."""
    spans = [span("request/queue", 90.0, 90.5),
             span("request/prefill", 90.5, 91.0),
             span("request/decode", 91.0, 92.0),
             span("request/queue", 100.5, 101.0),
             span("request/prefill", 101.0, 102.0),
             span("request/decode", 102.0, 104.0),
             span("request/queue", 102.5, 103.0),
             span("request/prefill", 103.0, 105.0),
             span("request/decode", 105.0, 109.0)]
    obs = {"window": {"t_open": 100.0, "t_close": 110.0},
           "requests": [{"sent": 102.5, "n_prompt": 30, "asked": 4},
                        {"sent": 100.5, "n_prompt": 20, "asked": 4}]}
    config = {"engine": {"v2": {"block_size": 8, "num_blocks": 16}}}
    got = knee_sweep.blocks_reserved(spans, obs, config)
    assert got["blocks_reserved_max_pct"] == pytest.approx(100 * 8 / 15)
    assert got["blocks_reserved_mean_pct"] == pytest.approx(
        100 * (3 * 3 + 5 * 6) / 10 / 15)
    short = knee_sweep.blocks_reserved(spans[:3], obs, config)
    assert short == {"blocks_reserved_mean_pct": None,
                     "blocks_reserved_max_pct": None}


def test_rates_and_substitutions():
    assert knee_sweep.parse_rates("3.0:5.0:0.5") == [3.0, 3.5, 4.0, 4.5, 5.0]
    assert knee_sweep.parse_rates("6.4,7") == [6.4, 7.0]
    assert knee_sweep.parse_set(["schedule_seed=3", "ramp_s=8.5"]) == {
        "schedule_seed": 3, "ramp_s": 8.5}
