"""The open-loop Mistral cell's own tests (ISSUE 36) in the tier-1 run, which
collects only ``tests/``: ``benchmark/tests/test_doc_prefill_loaded.py``
whole (the schedule ``doc-prefill-loaded`` sends, every name
``BENCHMARK.json`` lists found, ``mixed_gap_share_pct`` over hand-made
spans, what ``correct`` sees of an altered token and of the control),
imported as ``tests/test_mellum2_cell.py`` imports its rehearsal.  One test
is replaced: the count of the cell's per-layer metrics, which that file
fixes at PR 36's sixteen and PR 37 raised by six (a file under
``benchmark/`` is a ``benchmark`` PR's to edit)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
from test_doc_prefill_loaded import *  # noqa: E402,F401,F403  (its tests)
from test_doc_prefill_loaded import CELL, run  # noqa: E402

WAITED_FOR = ("mixed_host_wait_ms_mean", "loop_turn_wait_ms_mean",
              "device_starved_pct", "step_interval_p90_ms",
              "attn_q_fill_pct", "step_h2d_copies_max")


def test_the_new_cell_reports_what_the_retired_one_reported(spec):  # noqa: F811
    assert {m["name"] for m in run.metrics_of(spec, "end_to_end", CELL)} \
        == {"ttft_p90_ms", "itl_p90_ms", "setup_s"}
    per_layer = [m["name"] for m in run.metrics_of(spec, "per_layer", CELL)]
    # the retired cell's fifteen, PR 36's one, and PR 37's six behind them
    assert len(per_layer) == 22 and per_layer[15] == "mixed_gap_share_pct"
    assert tuple(per_layer[16:]) == WAITED_FOR
    alone = [m["name"] for m in spec["per_layer"]
             if m.get("workloads") == [CELL]]
    assert len(alone) == 7
