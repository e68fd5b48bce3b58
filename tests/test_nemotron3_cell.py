"""The benchmark's Nemotron-3-Nano cell rehearsed in the tier-1 run (which
collects only ``tests/``): driver ``serve_ssm_moe`` at the first four layers
of the ``tiny-nemotron3`` preset through ``run.run_cell``, W8A16, ``correct``
decided by ``benchmark/reference/ssm_moe_decoder`` on the engine's own
step-program logits (through the tap that donates the pools and the state),
by the reference's router margin, a prompt chunked three times among them,
and by the state slots all free after the drain.  A later PR that breaks the
cell's driver, reference, tap or readers fails here."""

import copy as _copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import nemotron3_rehearsal as rehearsal  # noqa: E402

from benchmark import ssm_flops, trace_reduce  # noqa: E402
from benchmark.drivers import serve_ssm_moe  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("nemotron3")))


def test_nemotron3_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_nemotron3_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_published_configuration_is_the_programs():
    """The cell's file against the program's preset: every published key,
    the pattern as run a contiguous run of the published one."""
    cfg, model = serve_ssm_moe.program_config(rehearsal.PUBLISHED)
    assert cfg.num_layers == 16 and cfg.num_experts == 128
    assert "".join(cfg.mixer_pattern) == "EMEMEM*EMEMEMEM*"
    assert model["hybrid_override_pattern"] == "EMEMEM*EMEMEMEM*"
    assert (cfg.layers_of("M"), cfg.layers_of("E"), cfg.layers_of("*")) == \
        (7, 7, 2)
    from deepspeed_tpu.models import transformer as tfm

    assert tfm.get_config(rehearsal.PUBLISHED["preset"]).num_params() == 31_577_940_288  # 31.58 B as published
    assert ssm_flops.state_bytes(model) == 2_097_152
    assert ssm_flops.stored_expert_width(model) == 1920


@pytest.mark.parametrize("edit,says", [
    (lambda c: c.update(num_experts_per_tok=8), "num_experts_per_tok"),
    (lambda c: c.update(routed_scaling_factor=1.0), "routed_scaling_factor"),
    (lambda c: c.update(n_groups=4), "n_groups"),
    (lambda c: c.update(mlp_hidden_act="silu"), "mlp_hidden_act"),
    (lambda c: c.update(attention_bias=True), "attention_bias"),
    (lambda c: c["as_run"].update(first_layer=1), "hybrid_override_pattern"),
])
def test_program_config_refuses_what_the_program_does_not_compute(edit, says):
    config = _copy.deepcopy(rehearsal.CONFIG)
    cfg, model = serve_ssm_moe.program_config(config)
    assert cfg.num_layers == 4 and model["intermediate_size"] == 192
    edit(config)
    with pytest.raises(ValueError, match=says):
        serve_ssm_moe.program_config(config)
