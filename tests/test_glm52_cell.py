"""The benchmark's GLM-5.2 cell rehearsed in the tier-1 run (which collects
only ``tests/``): driver ``serve_latent_moe`` at the first five layers of the
``tiny-glm52`` preset through ``run.run_cell``, W8A16, ``correct`` decided by
``benchmark/reference/latent_sparse_moe_decoder`` on the engine's own
step-program logits (through the tap that reads the picks and the experts),
by ``check_indexer`` and ``check_router``, a prompt chunked three times among
them, and by both pools' blocks all free after the drain.  A later PR that
breaks the cell's driver, reference, tap or readers fails here."""

import copy as _copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import glm52_rehearsal as rehearsal  # noqa: E402

from benchmark import dsa_flops, trace_reduce  # noqa: E402
from benchmark.drivers import serve_latent_moe  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("glm52")))


def test_glm52_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_glm52_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_the_yardstick():
    """``benchmark/dsa_flops.py`` at the published sizes: what the indexer
    and the attention over its picks require."""
    cfg, model = serve_latent_moe.program_config(rehearsal.PUBLISHED)
    assert (dsa_flops.picking_layers(model), dsa_flops.routed_layers(model)
            ) == (3, 8)
    assert dsa_flops.entry_values(model) == 576
    assert dsa_flops.index_flops(model, 1000) == 2.0 * 1000 * 32 * 128
    assert dsa_flops.index_bytes(model, 1000) == 1000 * 128 * 2
    # q . k over 256, p . v over 256, a head
    assert dsa_flops.attention_flops(model, 10) == 2.0 * 10 * 64 * 512
    assert dsa_flops.attention_bytes(model, 2048) == 2048 * 576 * 2


@pytest.mark.parametrize("edit,says", [
    (lambda c: c.update(num_experts_per_tok=8), "num_experts_per_tok"),
    (lambda c: c.update(routed_scaling_factor=1.0), "routed_scaling_factor"),
    (lambda c: c.update(kv_lora_rank=64), "kv_lora_rank"),
    (lambda c: c.update(index_topk=32), "index_topk"),
    (lambda c: c.update(index_topk_freq=2), "indexer_types"),
    (lambda c: c.update(attention_bias=True), "attention_bias"),
    (lambda c: c["as_run"].update(first_layer=1), "indexer_types"),
    (lambda c: c["as_run"].update(first_expert=0), "first_expert"),
    (lambda c: c["rope_parameters"].update(rope_type="yarn"),
     "rope_parameters"),
])
def test_program_config_refuses_what_the_program_does_not_compute(edit, says):
    config = _copy.deepcopy(rehearsal.CONFIG)
    cfg, model = serve_latent_moe.program_config(config)
    assert cfg.num_layers == 5 and model["intermediate_size"] == 128
    assert model["num_experts"] == 4 and model["n_routed_experts"] == 16
    edit(config)
    with pytest.raises(ValueError, match=says):
        serve_latent_moe.program_config(config)
