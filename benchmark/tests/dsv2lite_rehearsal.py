"""The ``train_latent_moe`` driver rehearsed at the program's ``tiny-dsv2lite``
preset through ``run.run_cell``: a temporary copy of the benchmark to which a
tiny configuration, a tiny traffic mix and a cell are added, as
``glm52_rehearsal.py`` does for ``serve_latent_moe``.  Used by
``tests/test_dsv2lite_cell.py`` (the repository's tier-1 run collects only
``tests/``)."""

import copy
import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-dsv2lite"
REAL = "dsv2lite-train-8k"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "deepseek-v2-lite-ep8-train.json")) as f:
    PUBLISHED = json.load(f)
_PROGRAM = copy.deepcopy(PUBLISHED["program"])
_PROGRAM["implied"].update(num_experts=8, moe_shared_size=96,
                           attn_impl="xla")
_TYPES = ["dense", "sparse", "sparse", "sparse"]
CONFIG = {
    # the tiny preset's sizes under the published keys
    "hidden_size": 64, "intermediate_size": 160, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 4, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "max_position_embeddings": 256, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "moe_intermediate_size": 48, "n_routed_experts": 8,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "norm_topk_prob": False,
    "routed_scaling_factor": 1.0, "scoring_func": "softmax", "seq_aux": True,
    "hidden_act": "silu", "attention_bias": False, "first_k_dense_replace": 1,
    "model_type": "deepseek_v2", "rope_theta": 10000.0,
    "rope_scaling": {"beta_fast": 4.0, "beta_slow": 1.0, "factor": 4.0,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "name": "tiny-dsv2lite-train",
    "source": "the repository's tiny-dsv2lite preset",
    "driver": "train_latent_moe", "preset": "tiny-dsv2lite",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                  "mlp_layer_types": _TYPES},
    "reduced": ["n_routed_experts"],
    "as_run": {"num_hidden_layers": 4, "n_routed_experts": 2,
               "first_expert": 2, "vocab_size": 256,
               "mlp_layer_types": _TYPES},
    "program": _PROGRAM,
    "assumed": {"aux_loss_alpha": {"value": 0.01, "why": "the preset's"}},
    "engine": {"loss_tile": 64, "deepspeed": {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 0.001}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 1000000}},
    # at toy widths a bf16 rounding is a larger share of a gradient than at
    # the published ones: the bounds are loose here, the chip's are in the
    # published file
    "check": {"loss_rel_tol": 0.005, "aux_rel_tol": 0.02,
              "grad_norm_rel_tol": 0.05, "stack_norm_rel_tol": 0.1,
              "stack_one_less_cos_max": 0.02, "router_prob_tol": 1e-4,
              "router_rows_differ_max": 0.001,
              "update_norm_rel_tol": 0.3, "update_one_less_cos_max": 0.3},
}
TRAFFIC = {"loop": "steps", "seq_len": 128, "rows": 2, "warmup_steps": 2,
           "in_flight": 2, "trace_after_s": 0.3, "trace_seconds": 0.6}


def make_copy(root: str, faults=()) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-dsv2lite`` wherever
    ``dsv2lite-train-8k`` is listed; ``faults``: the named faults the
    reference is to carry (``check.reference_faults``)."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config = copy.deepcopy(CONFIG)
    if faults:
        config["check"]["reference_faults"] = list(faults)
    for rel, content in (("configs/tiny-dsv2lite-train.json", config),
                         ("traffic/tiny-steps.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-dsv2lite-train", "source": CONFIG["source"],
        "reduced": CONFIG["reduced"],
        "file": "benchmark/configs/tiny-dsv2lite-train.json",
        "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-dsv2lite-train",
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
    return run.run_cell(CELL, seed=2147480021, seconds=seconds, trace=trace,
                        device_check=fake_device, root=root)
