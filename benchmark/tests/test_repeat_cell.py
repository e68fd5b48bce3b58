"""``benchmark/tools/repeat_cell.py``: one run of it rehearsed on the CPU at
the tiny preset (the tests' bypass of the TPU check), and what it reads out
of an observation."""

import argparse
import json

import pytest

from benchmark import run
from benchmark.tools import repeat_cell

from test_harness import copy, fake_device  # noqa: F401  (a fixture)


def test_one_run_keeps_what_shows_a_stall(copy, monkeypatch, capsys):  # noqa: F811
    monkeypatch.setattr(run.run_cell, "__defaults__", (fake_device, copy))
    args = argparse.Namespace(workload="t-closed", one=5, seconds=3.0)
    assert repeat_cell.one(args) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    tool = row["tool"]
    assert row["correct"] and row["failed"] == 0
    assert set(row["metrics"]) >= {"serve_out_tokens_per_s", "setup_s"}
    # the tokens the tool counts second by second are the metric's tokens
    assert sum(tool["tokens_by_second"]) == tool["tokens"]
    assert tool["tokens"] == pytest.approx(
        3.0 * row["metrics"]["serve_out_tokens_per_s"]["value"], abs=0.5)
    assert tool["steps"] > 2 and tool["step_gap_ms_p50"] > 0
    assert len(tool["tokens_by_second"]) == 3


def obs_with_a_stall():
    """Ten tokens a second for ten seconds, but for seconds 5 and 6."""
    times = [100.05 + 0.1 * i for i in range(100)
             if not 5.0 <= 0.05 + 0.1 * i < 7.0]
    return {"window": {"t_open": 100.0, "t_close": 110.0}, "setup_s": 3.0,
            "requests": [{"token_times": times, "status": "ok",
                          "done": 109.0, "n_prompt": 10}],
            "spans": [{"name": "engine/step", "t_start": t} for t in times]}


def test_a_stall_shows_in_every_reading():
    s = repeat_cell.summary(obs_with_a_stall(), [(104.9, 1.9), (50.0, 0.1)])
    assert s["overruns_in_window"] == [[4.9, 1.9]]
    assert s["overruns_before"] == 1
    assert s["tokens"] == 80 and s["tokens_by_second"][5:7] == [0, 0]
    assert len(s["silences"]) == 1
    offset, seconds = s["silences"][0]
    assert offset == pytest.approx(4.95) and seconds == pytest.approx(2.1)
    assert s["step_gaps_longest_ms"][0] == [pytest.approx(4.95),
                                            pytest.approx(2100.0)]
    assert s["step_gap_ms_p50"] == pytest.approx(100.0)


def test_the_spread_is_the_contracts():
    # statistics.quantiles(n=4) of 1..6: 1.75 and 5.25, median 3.5
    assert repeat_cell.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)
    assert repeat_cell.spread([5.0, 5.0, 5.0]) == 0.0
