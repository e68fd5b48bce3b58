"""The benchmark's Trinity-Mini training cell rehearsed in the tier-1 run
(which collects only ``tests/``): its files against the program's preset and
the catalog row, driver ``train_swa_moe`` at the ``tiny-trinity`` preset
through ``run.run_cell`` with the device check stubbed, ``correct`` decided by
``benchmark/reference/gated_swa_moe_trainer.py`` on the engine's first step,
on the gradient of its loss function and on the routers' biases after the
rule, a named fault in the reference's place coming out not correct, and the
yardstick's arithmetic at the published sizes against ISSUE 55's figures.
``benchmark/tests/test_train_swa_moe_readers.py`` (the flash kernels' shares
by kind of layer, the gate's scope and the two counters on hand-made traces,
no chip) is imported whole, as ``tests/test_dsv2lite_cell.py`` imports its
file."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import trinity_rehearsal as rehearsal  # noqa: E402
from test_train_swa_moe_readers import *  # noqa: E402,F401,F403

from benchmark import swa_moe_train_flops, trace_reduce  # noqa: E402
from benchmark.drivers import train_swa_moe  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = rehearsal.PUBLISHED


def _program_config(config):
    over = dict(config["overrides"])
    for key in ("layer_types", "mlp_layer_types"):
        over[key] = tuple(over[key])
    return tfm.get_config(config["preset"], **over)


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_files_agree_with_the_preset_and_the_catalog(spec):
    entry = next(c for c in spec["configs"]
                 if c["name"] == "trinity-mini-ep8-train")
    cell = next(w for w in spec["workloads"]
                if w["name"] == rehearsal.REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        entry["name"], "steps-16384", 1)
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "steps-16384.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["seq_len"], traffic["rows"]) == (
        "steps", 16384, 1)
    # the preset as the file runs it is the file's published keys and cuts
    cfg = _program_config(CONFIG)
    train_swa_moe.check_program(CONFIG, cfg)
    as_run = CONFIG["as_run"]
    assert cfg.num_params() == as_run["parameters"]
    assert (cfg.num_experts, cfg.moe_top_k) == (128, 8)
    assert cfg.experts_held == as_run["num_experts"]
    # the floors: a dense layer and one whole period of routed ones (three
    # window layers to one full), 8 experts or more, an eighth of the
    # vocabulary; no width in reduced
    kinds = [t.split("_")[0] for t in as_run["layer_types"]]
    assert as_run["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (kinds[1:].count("sliding"), kinds[1:].count("full")) == (3, 1)
    assert as_run["num_experts"] in (8, 16)
    assert as_run["vocab_size"] * 8 == CONFIG["vocab_size"]
    # every cell the benchmark had is still there, one of them on four chips
    assert len(spec["workloads"]) == 13  # PR 58 added the thirteenth
    assert [w["chips"] for w in spec["workloads"]].count(4) == 1
    if os.path.isfile(CATALOG):  # every number of the row, under its key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["source_url"] == CONFIG["source"]
        for key, value in row["config"].items():
            assert CONFIG[key] == value, key


def test_every_assumed_item_has_its_reason():
    for key in ("bias_rule", "counts", "rope", "mup", "window_edge",
                "groups", "router_bias_init", "optimizer", "norms"):
        assert len(CONFIG["assumed"][key]) > 40, key
    factors = CONFIG["assumed"]["norm_factors"]
    assert factors["value"] and len(factors["why"]) > 400
    assert "eight" in CONFIG["deployment"] or "sixteen" in CONFIG[
        "deployment"]


def test_a_changed_width_is_refused():
    import copy

    for edit, says in (
            (lambda c: c.update(head_dim=64), "head_dim"),
            (lambda c: c.update(num_experts_per_tok=6), "num_experts_per_tok"),
            (lambda c: c.update(route_norm=False), "route_norm"),
            (lambda c: c.update(route_scale=1.0), "route_scale"),
            (lambda c: c.update(sliding_window=4096), "sliding_window"),
            (lambda c: c.update(moe_intermediate_size=2048),
             "moe_intermediate_size"),
            (lambda c: c.update(n_group=2), "n_group"),
            (lambda c: c.update(rope_scaling={"factor": 2}), "rope_scaling"),
            (lambda c: c["as_run"].update(first_layer=0), "layer_types")):
        config = copy.deepcopy(CONFIG)
        edit(config)
        with pytest.raises(ValueError, match=says):
            train_swa_moe.check_program(config, _program_config(config))


def test_the_yardstick():
    """``benchmark/swa_moe_train_flops.py`` at the published sizes against
    ISSUE 55's paragraph, figure by figure (TF forward at 1 x 16,384)."""
    model = train_swa_moe.model_of(CONFIG)
    assert swa_moe_train_flops.attention_params(model) == 27_262_976
    assert swa_moe_train_flops.expert_params(model) == 3 * 2048 * 1024
    held = model["experts_held"]
    local = 8 * held / 128  # a token's assignments that fall on the share
    fwd = swa_moe_train_flops.forward_flops(model, 16384, local)
    tf = {k: v / 1e12 for k, v in fwd.items()}
    assert tf["attention"] == pytest.approx(4.47, abs=0.005)
    assert tf["scores_sliding"] / 4 == pytest.approx(0.52, abs=0.005)
    assert tf["scores_full"] == pytest.approx(2.2, abs=0.005)
    assert tf["scores_sliding"] + tf["scores_full"] == pytest.approx(
        4.26, abs=0.005)
    assert tf["dense_mlp"] == pytest.approx(1.24, abs=0.005)
    assert tf["shared_experts"] == pytest.approx(4 * 0.21, abs=0.02)
    assert tf["routed_experts"] == pytest.approx(4 * 0.21 * held / 16,
                                                 abs=0.02)
    assert tf["router"] == pytest.approx(0.03, abs=0.005)
    assert tf["head"] == pytest.approx(1.68, abs=0.005)
    if held == 16:
        assert sum(tf.values()) == pytest.approx(13.3, abs=0.05)
    # with no band the scores would be 11 of 20
    full = 5 * tf["scores_full"]
    assert full == pytest.approx(11.0, abs=0.01)
    per_token = swa_moe_train_flops.train_flops_per_token(model, 16384, local)
    assert per_token == pytest.approx(
        (6 * sum(v for k, v in fwd.items() if not k.startswith("scores"))
         / 2 + 3 * (fwd["scores_sliding"] + fwd["scores_full"])) / 16384)
    a, b = swa_moe_train_flops.flash_call_flops(model, "sliding", 1, 16384)
    pairs = 32 * (2048 * 2049 / 2 + 14336 * 2048)
    assert (a, b) == (pairs * 2 * 256, pairs * 2 * 640)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("trinity")))


def test_trinity_cell_rehearsal(copy):
    result = rehearsal.rehearse(copy)
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert all(v <= limit for v, limit in result["checks"].values())
    # the rule ran on the counts the step reported, to the last bit
    assert result["checks"]["bias_rule_diff"][0] < 1e-7
    assert result["checks"]["bias_moments"] == [0.0, 0]


def test_trinity_cell_rehearsal_traced(copy, monkeypatch):
    """The per-layer line: the counters' readers find the step's counters;
    the device-trace readers need a TPU's trace of the new kernels, find none
    in the recorded one and are left out."""
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    result = rehearsal.rehearse(copy, trace=True)
    m = result["metrics"]
    assert result["correct"]
    # 4 of 16 experts held, a near-uniform router: about a quarter
    assert 10 < m["train_moe_local_rows_pct"]["value"] < 40
    assert 1.0 < m["train_moe_load_max_over_mean"]["value"] < 4.0
    # seeded at 0.02 x a normal draw over 64 biases, moved by 0.001 a step
    assert 0.02 < m["train_moe_bias_abs_max"]["value"] < 0.2
    assert m["train_compiles_in_window"]["value"] == 0
    assert m["train_step_ms_p50"]["value"] > 0 and m["mfu_pct"]["value"] > 0
    for name in ("swa_flash_fwd_roofline_pct", "swa_flash_bwd_roofline_pct",
                 "full_flash_fwd_roofline_pct", "full_flash_bwd_roofline_pct",
                 "gqa_flash_busy_pct", "attn_gate_busy_pct",
                 "train_moe_gemm_busy_pct", "train_moe_gemm_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace


@pytest.mark.parametrize("fault,over", [
    ("no_gate", {"grad_one_less_cos.attention", "grad_one_less_cos.head",
                 "update_one_less_cos.attention"}),
    ("rule_uncentred", {"bias_rule_diff"}),
    ("bias_left", {"bias_rule_diff", "bias_entries_differ"})])
def test_a_fault_in_the_reference_is_not_correct(tmp_path, fault, over):
    root = rehearsal.make_copy(str(tmp_path), faults=[fault])
    result = rehearsal.rehearse(root, seconds=0.3)
    assert not result["correct"]
    assert over <= {k for k, (v, limit) in result["checks"].items()
                    if v > limit}


def test_a_state_left_unchanged_is_not_correct(tmp_path):
    """A trainer whose update never lands: every stack's change reads 1
    against the reference's AdamW step, and the bias is where it was."""
    root = rehearsal.make_copy(str(tmp_path), faults=["state_unchanged"])
    result = rehearsal.rehearse(root, seconds=0.3)
    assert not result["correct"]
    over = {k for k, (v, limit) in result["checks"].items() if v > limit}
    assert {f"update_{what}.{stack}"
            for what in ("norm_rel", "one_less_cos")
            for stack in train_swa_moe.STACKS} | {"bias_rule_diff"} <= over
