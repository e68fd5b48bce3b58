"""The ``train_swa_moe`` driver rehearsed at the program's ``tiny-trinity``
preset through ``run.run_cell``: a temporary copy of the benchmark to which a
tiny configuration, a tiny traffic mix and a cell are added, as
``dsv2lite_rehearsal.py`` does for ``train_latent_moe``.  Used by
``tests/test_trinity_cell.py`` (the repository's tier-1 run collects only
``tests/``)."""

import copy
import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-trinity"
REAL = "trinity-train-16k"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "trinity-mini-ep8-train.json")) as f:
    PUBLISHED = json.load(f)
_TYPES = ["dense", "sparse", "sparse", "sparse", "sparse"]
_KINDS = ["sliding_attention", "sliding_attention", "full_attention",
          "sliding_attention", "sliding_attention"]
_PROGRAM = copy.deepcopy(PUBLISHED["program"])
_PROGRAM["implied"].update(num_experts=16, moe_shared_size=48)
CONFIG = {
    # the tiny preset's sizes under the published keys
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 5,
    "num_dense_layers": 1, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "max_position_embeddings": 256,
    "moe_intermediate_size": 48, "num_experts": 16, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "hidden_act": "silu", "rope_theta": 10000.0,
    "rope_scaling": None, "sliding_window": 8, "mup_enabled": True,
    "load_balance_coeff": 0.001, "layer_types": _KINDS, "n_group": 1,
    "topk_group": 1, "num_expert_groups": 1, "num_limited_groups": 1,
    "model_type": "afmoe",
    "name": "tiny-trinity-train",
    "source": "the repository's tiny-trinity preset",
    "driver": "train_swa_moe", "preset": "tiny-trinity",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                  "layer_types": [k.split("_")[0] for k in _KINDS],
                  "mlp_layer_types": _TYPES},
    "reduced": ["num_experts"],
    "as_run": {"num_hidden_layers": 5, "first_layer": 0,
               "num_dense_layers": 1, "num_experts": 4, "first_expert": 4,
               "vocab_size": 256, "layer_types": _KINDS,
               "mlp_layer_types": _TYPES},
    "program": _PROGRAM,
    "assumed": {"norm_factors": {"value": {"ln1_post": 0.5, "q_norm": 1.0},
                                 "why": "rehearsal"}},
    "engine": {"loss_tile": 64, "deepspeed": {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 0.001}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 1000000}},
    # at toy widths a bf16 rounding is a larger share of a gradient than at
    # the published ones, and 128 tokens over 16 experts put many counts
    # within a rounding of their mean: the bounds are loose here, the chip's
    # are in the published file
    "check": {"loss_rel_tol": 0.005, "grad_norm_rel_tol": 0.05,
              "stack_norm_rel_tol": 0.1, "stack_one_less_cos_max": 0.06,
              "router_prob_tol": 1e-4, "router_rows_differ_max": 0.001,
              "update_norm_rel_tol": 0.3, "update_one_less_cos_max": 0.3,
              "bias_rule_tol": 1e-7, "bias_entries_differ_max": 0.5,
              "counts_moved_max": 0.1},
}
# under the tests' eight virtual devices a step is eight times these rows, and
# the flash kernel runs in the interpreter, a second a step: short rows, and
# a trace that starts with the window (the loop starts it between two steps,
# and a window of two steps has no second gap)
TRAFFIC = {"loop": "steps", "seq_len": 64, "rows": 1, "warmup_steps": 2,
           "in_flight": 2, "trace_after_s": 0.0, "trace_seconds": 0.6}


def make_copy(root: str, faults=()) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-trinity`` wherever
    ``trinity-train-16k`` is listed; ``faults``: the named faults the
    reference is to carry (``check.reference_faults``)."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config = copy.deepcopy(CONFIG)
    if faults:
        config["check"]["reference_faults"] = list(faults)
    for rel, content in (("configs/tiny-trinity-train.json", config),
                         ("traffic/tiny-steps.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-trinity-train", "source": CONFIG["source"],
        "reduced": CONFIG["reduced"],
        "file": "benchmark/configs/tiny-trinity-train.json",
        "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-trinity-train",
                              "traffic": "tiny-steps", "chips": 1,
                              "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if REAL in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def fake_device(chips):
    """The tests' bypass of the TPU check; the command has none."""
    return {"platform": "cpu", "kind": "cpu", "count": chips,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def rehearse(root: str, trace: bool = False, seconds: float = 1.5) -> dict:
    return run.run_cell(CELL, seed=2147480055, seconds=seconds, trace=trace,
                        device_check=fake_device, root=root)
