"""The benchmark's DeepSeek-V2-Lite training cell rehearsed in the tier-1 run
(which collects only ``tests/``): its files against the program's preset and
the catalog row, driver ``train_latent_moe`` at the ``tiny-dsv2lite`` preset
through ``run.run_cell`` with the device check stubbed, ``correct`` decided by
``benchmark/reference/latent_moe_trainer.py`` on the engine's first step and
on the gradient of its loss function, a named fault in the reference's place
coming out not correct, and the yardstick's arithmetic at the published
sizes.  A later PR that breaks the cell's driver, reference or readers fails
here.  ``benchmark/tests/test_train_latent_moe_readers.py`` (PR 44: the
grouped GEMM's roofline share and the flash kernels' on hand-made traces, 33
cases, no chip) is imported whole, as ``tests/test_doc_prefill_loaded_cell.py``
imports its file: the claims of ISSUE 45 and after rest on that yardstick."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import dsv2lite_rehearsal as rehearsal  # noqa: E402
from test_train_latent_moe_readers import *  # noqa: E402,F401,F403

from benchmark import latent_moe_flops, trace_reduce  # noqa: E402
from benchmark.drivers import train_latent_moe  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = rehearsal.PUBLISHED


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_files_agree_with_the_preset_and_the_catalog(spec):
    entry = next(c for c in spec["configs"]
                 if c["name"] == "deepseek-v2-lite-ep8-train")
    cell = next(w for w in spec["workloads"]
                if w["name"] == rehearsal.REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        entry["name"], "steps-8192", 1)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "steps-8192.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["seq_len"], traffic["rows"]) == (
        "steps", 8192, 2)
    # the preset as the file runs it is the file's published keys and cuts
    cfg = tfm.get_config(CONFIG["preset"], **dict(
        CONFIG["overrides"],
        mlp_layer_types=tuple(CONFIG["overrides"]["mlp_layer_types"])))
    train_latent_moe.check_program(CONFIG, cfg)
    assert cfg.num_params() == CONFIG["as_run"]["parameters"] == 635_466_752
    assert (cfg.experts_held, cfg.num_experts, cfg.moe_top_k) == (8, 64, 6)
    # the floors: a dense layer and at least four routed, 8 experts, an
    # eighth of the vocabulary; no width in reduced
    as_run = CONFIG["as_run"]
    assert as_run["mlp_layer_types"].count("sparse") >= 4
    assert as_run["n_routed_experts"] >= 8
    assert as_run["vocab_size"] * 8 >= CONFIG["vocab_size"]
    if os.path.isfile(CATALOG):  # every number of the row, under its key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "DeepSeek-V2-Lite")
        assert row["source_url"] == CONFIG["source"]
        for key, value in row["config"].items():
            assert CONFIG[key] == value, key


def test_a_changed_width_is_refused():
    import copy

    for edit, says in (
            (lambda c: c.update(v_head_dim=192), "v_head_dim"),
            (lambda c: c.update(num_experts_per_tok=8), "num_experts_per_tok"),
            (lambda c: c.update(norm_topk_prob=True), "norm_topk_prob"),
            (lambda c: c["rope_scaling"].update(factor=32), "rope_scaling"),
            (lambda c: c.update(attention_bias=True), "attention_bias")):
        config = copy.deepcopy(CONFIG)
        edit(config)
        cfg = tfm.get_config(config["preset"], **dict(
            config["overrides"],
            mlp_layer_types=tuple(config["overrides"]["mlp_layer_types"])))
        with pytest.raises(ValueError, match=says):
            train_latent_moe.check_program(config, cfg)


def test_the_yardstick():
    """``benchmark/latent_moe_flops.py`` at the published sizes: ISSUE 42's
    arithmetic (13.76 M of attention a layer, 2.5 GFLOP a token trained)."""
    model = train_latent_moe.model_of(CONFIG)
    assert latent_moe_flops.attention_params(model) == 13_762_560
    assert latent_moe_flops.expert_params(model) == 3 * 2048 * 1408
    parts = latent_moe_flops.matmul_params_per_token(model, 0.75)
    assert parts["dense_mlp"] == 3 * 2048 * 10944
    assert parts["shared_experts"] == 5 * 2 * 3 * 2048 * 1408
    assert parts["routed_experts"] == 5 * 0.75 * 3 * 2048 * 1408
    assert parts["head"] == 2048 * 12800
    per_token = latent_moe_flops.train_flops_per_token(model, 8192, 0.75)
    assert 2.5e9 < per_token < 2.56e9
    fwd, bwd = latent_moe_flops.flash_call_flops(model, 2, 8192)
    pairs = 2 * 16 * 8192 * 8193 / 2
    assert fwd == pairs * 2 * (192 + 128)
    assert bwd == pairs * 2 * (3 * 192 + 2 * 128)
    flops, nbytes = latent_moe_flops.grouped_call(model, 12288, 8)
    assert flops == 2.0 * 12288 * 2048 * 1408
    assert nbytes == 2.0 * (12288 * (2048 + 1408) + 8 * 2048 * 1408)
    # nothing to read: no reading, and nothing raised (the parent's program)
    assert latent_moe_flops.flash_roofline({}, backward=True) is None
    assert latent_moe_flops.grouped_roofline({"trace": None}) is None
    assert latent_moe_flops.busy_share({}, names=("x",)) is None


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("dsv2lite")))


def test_dsv2lite_cell_rehearsal(copy):
    result = rehearsal.rehearse(copy)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert all(v <= limit for v, limit in result["checks"].values())


def test_dsv2lite_cell_rehearsal_traced(copy, monkeypatch):
    """The per-layer line: the counter's reader finds the step's counters;
    the device-trace readers need a TPU's trace of the new kernels, find
    none in the recorded one and are left out."""
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    result = rehearsal.rehearse(copy, trace=True)
    m = result["metrics"]
    assert result["correct"]
    # 2 of 8 experts held, a near-uniform router: about a quarter
    assert 10 < m["train_moe_local_rows_pct"]["value"] < 40
    assert m["train_compiles_in_window"]["value"] == 0
    assert m["train_step_ms_p50"]["value"] > 0 and m["mfu_pct"]["value"] > 0
    for name in ("mla_flash_busy_pct", "mla_flash_fwd_roofline_pct",
                 "mla_flash_bwd_roofline_pct", "train_moe_gemm_busy_pct",
                 "train_moe_gemm_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace


def test_a_fault_in_the_reference_is_not_correct(tmp_path):
    """The shared experts left out of the reference: the balance loss and
    the gradients of every stack move past their limits."""
    root = rehearsal.make_copy(str(tmp_path), faults=["no_shared"])
    result = rehearsal.rehearse(root, seconds=0.3)
    assert not result["correct"]
    over = {k for k, (v, limit) in result["checks"].items() if v > limit}
    assert {"aux_rel", "grad_norm_rel", "grad_norm_rel.shared_experts",
            "grad_one_less_cos.attention"} <= over


def test_a_state_left_unchanged_is_not_correct(tmp_path):
    """A trainer whose update never lands (the comparison is handed the
    parameters from before the first step as those after it): every stack's
    change reads 1 against the reference's AdamW step, and nothing else
    moves: loss, balance loss and gradients are all made before the update."""
    root = rehearsal.make_copy(str(tmp_path), faults=["state_unchanged"])
    result = rehearsal.rehearse(root, seconds=0.3)
    assert not result["correct"]
    over = {k for k, (v, limit) in result["checks"].items() if v > limit}
    assert over == {f"update_{what}.{stack}"
                    for what in ("norm_rel", "one_less_cos")
                    for stack in train_latent_moe.STACKS}
    assert all(result["checks"][k][0] == 1.0 for k in over)


def test_another_optimizer_than_the_reference_steps_is_refused():
    import copy

    for edit, says in (
            (lambda ds: ds["optimizer"]["params"].update(weight_decay=0.1),
             "weight_decay 0.1"),
            (lambda ds: ds.update(gradient_clipping=1.0),
             "gradient_clipping"),
            (lambda ds: ds["optimizer"].update(type="Lion"), "Lion")):
        config = copy.deepcopy(CONFIG)
        edit(config["engine"]["deepspeed"])
        with pytest.raises(ValueError, match=says):
            train_latent_moe.optimizer_of(config)
    assert train_latent_moe.optimizer_of(CONFIG) == {
        "lr": 2e-4, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
