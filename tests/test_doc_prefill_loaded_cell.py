"""The open-loop Mistral cell's own tests (ISSUE 36) in the tier-1 run, which
collects only ``tests/``: ``benchmark/tests/test_doc_prefill_loaded.py``
whole (the schedule ``doc-prefill-loaded`` sends, every name
``BENCHMARK.json`` lists found, ``mixed_gap_share_pct`` over hand-made
spans, what ``correct`` sees of an altered token and of the control),
imported as ``tests/test_mellum2_cell.py`` imports its rehearsal.  One test
is replaced: the count of the cell's per-layer metrics, which that file
fixes at PR 36's sixteen, PR 37 raised by six and PR 53 by four (a file
under ``benchmark/`` is a ``benchmark`` PR's to edit); and a second, for the same
reason: ``test_every_listed_name_is_found`` fixes the benchmark at seven
cells, PR 40 added the eighth and PR 42 the ninth."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
from test_doc_prefill_loaded import *  # noqa: E402,F401,F403  (its tests)
from test_doc_prefill_loaded import CELL, run  # noqa: E402
from test_doc_prefill_loaded import \
    test_every_listed_name_is_found as _seven_cells  # noqa: E402

WAITED_FOR = ("mixed_host_wait_ms_mean", "loop_turn_wait_ms_mean",
              "device_starved_pct", "step_interval_p90_ms",
              "attn_q_fill_pct", "step_h2d_copies_max")
# PR 53's account of the device's queue, the four that every serving cell has
UNQUEUED = ("device_unqueued_pct", "unqueued_post_pct", "unqueued_pre_pct",
            "unqueued_turn_pct")


def test_the_new_cell_reports_what_the_retired_one_reported(spec):  # noqa: F811
    assert {m["name"] for m in run.metrics_of(spec, "end_to_end", CELL)} \
        == {"ttft_p90_ms", "itl_p90_ms", "setup_s"}
    per_layer = [m["name"] for m in run.metrics_of(spec, "per_layer", CELL)]
    # the retired cell's fifteen, PR 36's one, PR 37's six behind them and
    # PR 53's four behind those
    assert len(per_layer) == 26 and per_layer[15] == "mixed_gap_share_pct"
    assert tuple(per_layer[16:]) == WAITED_FOR + UNQUEUED
    alone = [m["name"] for m in spec["per_layer"]
             if m.get("workloads") == [CELL]]
    assert len(alone) == 7


def _without(spec, cell):
    """``spec`` less one cell: off every list, and the metrics it alone
    reported gone."""
    import copy

    less = copy.deepcopy(spec)
    less["workloads"] = [w for w in less["workloads"] if w["name"] != cell]
    for m in less["end_to_end"] + less["per_layer"]:
        if cell in m.get("workloads", ()):
            m["workloads"].remove(cell)
    less["per_layer"] = [m for m in less["per_layer"]
                         if m.get("workloads") != []]
    return less


def test_every_listed_name_is_found(spec):  # noqa: F811
    """The file's own test on the benchmark less the cells PR 40 and PR 42
    added (its count of seven cells holds of those), and the eighth and the
    ninth cell's names found as it finds the others': seven again, the new
    ones among them; the third training cell is listed behind the two."""
    spec = _without(spec, "jamba2-doc-long-sat")  # PR 58's, the thirteenth
    spec = _without(spec, "trinity-train-16k")  # PR 55's, the twelfth
    spec = _without(spec, "kimilinear-reason-sat")  # PR 51's, the eleventh
    spec_9 = _without(spec, "evabyte-doc-bytes-sat")  # PR 48's, the tenth
    less = _without(spec_9, "dsv2lite-train-8k")  # PR 42's, the ninth
    _seven_cells(_without(less, "glm52-ctx8k-sat"))
    _seven_cells(_without(less, "olmoe-decode-sat"))
    _seven_cells(_without(_without(spec_9, "glm52-ctx8k-sat"),
                          "olmoe-decode-sat"))
    # and the tenth cell's names, found as the others': seven with it
    _seven_cells(_without(_without(_without(spec, "dsv2lite-train-8k"),
                                   "glm52-ctx8k-sat"), "olmoe-decode-sat"))
    assert len(spec["workloads"]) == 10
    tokens = next(m for m in spec["end_to_end"]
                  if m["name"] == "train_tokens_per_s")
    assert tokens["workloads"] == ["train-1chip", "zero3-4chip",
                                   "dsv2lite-train-8k"]


def test_mixed_gap_share_is_listed_for_every_serving_cell(spec):  # noqa: F811
    """The file's test with the count of serving cells as it is now (five
    there; PR 40 added the sixth, PR 48 the seventh, PR 51 the eighth, PR 58
    the ninth)."""
    entry = next(m for m in spec["per_layer"]
                 if m["name"] == "mixed_gap_share_pct")
    itl = next(m for m in spec["end_to_end"] if m["name"] == "itl_p90_ms")
    assert (entry["moves"], entry["source"], entry["layer"]) == (
        "itl_p90_ms", "program_span", "scheduler")
    assert sorted(entry["workloads"]) == sorted(itl["workloads"])
    assert len(entry["workloads"]) == 9
