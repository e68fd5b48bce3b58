"""The benchmark's EvaByte cell rehearsed in the tier-1 run (which collects
only ``tests/``): driver ``serve_eva`` at the ``tiny-evabyte`` preset through
``run.run_cell``, ``correct`` decided by
``benchmark/reference/eva_byte_decoder`` on the engine's own step-program
logits (all heads, through the tap that donates the pools) and on the served
bytes' margins; then the comparison itself, held to seeing every wrong
reading.  A later PR that breaks the cell's driver, reference, tap or readers
fails here."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import evabyte_rehearsal as rehearsal  # noqa: E402
import evabyte_wrong_programs as wrong  # noqa: E402

from benchmark import eva_flops, trace_reduce  # noqa: E402
from benchmark.reference import eva_byte_decoder as reference  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("evabyte")))


def test_evabyte_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_evabyte_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


@pytest.fixture(scope="module")
def read():
    return wrong.readings(rehearsal.CONFIG, 11, (
        *reference.FAULTS, *reference.ROUNDINGS))


@pytest.mark.parametrize("name", ["right", *reference.ROUNDINGS,
                                  *reference.FAULTS])
def test_the_comparison_sees_wrong_programs(read, name):
    """The rehearsal's limits (float32: 1e-4 on the median row, 1e-3 on the
    worst) pass the right program, and fail every reading of the mathematics
    the other way on the median row; the stream's bfloat16 rounding, which
    the chip's limits allow for, is far over float32's."""
    check = rehearsal.CONFIG["check"]
    median, worst, chunk_median, chunk_worst = read[name]
    if name == "right":
        assert median <= check["logit_tol_median"] \
            and worst <= check["logit_tol"]
        assert chunk_median <= check["summary_tol_median"] \
            and chunk_worst <= check["summary_tol"]
    else:
        assert median > check["logit_tol_median"], (name, median)
    # what the summary pool holds is compared directly, and sees every
    # reading that changes a summary (the heads' maps change none, and the
    # stream's rounding reaches them from the second layer on)
    if name in ("weighted_key", "rf_norm", "no_mu", "uniform_pool",
                "rope_half", "no_unit_offset"):
        assert chunk_median > 100 * check["summary_tol_median"], name


def test_the_cell_is_in_the_benchmark():
    """One configuration, one cell, its traffic to the letter, and the
    yardsticks' arithmetic at the published sizes."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell, = [w for w in spec["workloads"]
             if w["name"] == "evabyte-doc-bytes-sat"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("evabyte-6.5b-w8", "doc-bytes-sat", 1)
    assert len(spec["workloads"]) == 13  # PR 51: the eleventh; PR 55, 58
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "doc-bytes-sat.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"]) == ("closed", 6)
    assert traffic["prompt_tokens"] == {"median": 8192, "sigma": 0.4,
                                        "min": 4096, "max": 12288}
    assert traffic["output_tokens"] == {"median": 2048, "sigma": 0.5,
                                        "min": 1024, "max": 4096}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-6.5b-w8.json")) as f:
        config = json.load(f)
    assert config["reduced"] == [] and config["engine"]["v2"]["max_seqs"] == 4
    model = {"hidden_size": 4096, "window_size": 2048, "chunk_size": 16,
             "num_hidden_layers": 32}
    # a token's K and V over 32 layers: 512 KiB; a window a row: 1 GiB
    assert eva_flops.key_bytes(model, 32) == 512 * 1024
    assert eva_flops.key_bytes(model, 32 * 2048) == 1 << 30
    assert eva_flops.pair_flops(model, 1) == 32 * 4 * 128
    assert eva_flops.window_bytes(model, 1) == (2048 + 128) * 512 * 1024
