"""The benchmark's OLMoE cell rehearsed in the tier-1 run (which collects
only ``tests/``): driver ``serve_moe`` at the ``tiny-olmoe`` preset through
``run.run_cell``, ``correct`` decided by ``benchmark/reference/moe_decoder``
on the engine's own step-program logits.  A later PR that breaks the cell's
driver, reference or readers fails here.  The same rehearsal, and the
readers' unit tests, are in ``benchmark/tests/test_serve_moe.py``."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import olmoe_rehearsal as rehearsal  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("olmoe")))


def test_olmoe_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_olmoe_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_router_check_sees_a_bf16_router():
    """The driver's direct router comparison passes on the program's float32
    ``route`` and fails on one whose logits are rounded to bfloat16."""
    rehearsal.check_router_has_teeth()
