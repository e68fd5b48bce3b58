"""The ``serve_ssm_moe`` driver rehearsed at the program's ``tiny-nemotron3``
preset (the first four layers of its pattern, ``MEM*``) through
``run.run_cell``: a temporary copy of the benchmark to which a tiny
configuration, a tiny traffic mix and a cell are added, as
``mellum2_rehearsal.py`` does for ``serve_swa_moe``.  Shared by
``benchmark/tests/test_serve_ssm_moe.py`` and ``tests/test_nemotron3_cell.py``
(the repository's tier-1 run collects only ``tests/``)."""

import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-nemotron3"
REAL = "nemotron3-chat-wide-sat"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "nemotron3-nano-30b-w8.json")) as f:
    PUBLISHED = json.load(f)
CONFIG = {
    # the tiny preset's sizes under the published keys
    "hidden_size": 192, "vocab_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "num_hidden_layers": 9,
    "norm_eps": 1e-5, "layer_norm_epsilon": 1e-5,
    "tie_word_embeddings": False, "max_position_embeddings": 512,
    "rope_theta": 10000.0, "intermediate_size": 192,
    "moe_intermediate_size": 192, "moe_shared_expert_intermediate_size": 128,
    "n_routed_experts": 8, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "mamba_num_heads": 8, "mamba_head_dim": 16,
    "n_groups": 2, "ssm_state_size": 32, "conv_kernel": 4, "chunk_size": 16,
    "mlp_hidden_act": "relu2", "hybrid_override_pattern": "MEM*EMEME",
    "model_type": "nemotron_h", "attention_bias": False, "mlp_bias": False,
    "use_bias": False, "mamba_proj_bias": False, "sliding_window": None,
    "name": "tiny-nemotron3-w8",
    "source": "the repository's tiny-nemotron3 preset",
    "driver": "serve_ssm_moe", "preset": "tiny-nemotron3",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                  "num_layers": 4, "mixer_pattern": "MEM*"},
    "reduced": ["num_hidden_layers"],
    "as_run": {"num_hidden_layers": 4, "first_layer": 0,
               "hybrid_override_pattern": "MEM*"},
    "program": PUBLISHED["program"],
    # group 256: at these widths (192, 128) every group is then all of K,
    # which the kernels tile; the chip's group is 128
    "engine": {"weight_bits": 8, "weight_group": 256,
               "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 96,
                      "max_blocks_per_seq": 16, "dtype": "bfloat16",
                      "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0},
               "state": {"ssm": "float32", "conv": "bfloat16"}},
    # at toy widths (8 experts, top 3) a router tie that flips in bf16 swaps
    # a third of a token's routed output: the bounds are loose here, the
    # chip's are in benchmark/configs/nemotron3-nano-30b-w8.json
    "check": {"margin": 0.5, "reference_len": 96, "window_sequences": 3,
              "warmup_prompt": 75, "warmup_tokens": 6,
              "logit_prompts": [75, 40, 9], "logit_tokens": 12,
              "logit_pad": 32, "logit_tol_median": 0.1, "logit_tol": 0.3,
              "agree_min": 0.7, "served_min": 0.8, "state_tol": 0.15,
              "state_low_bits_min": 0.5, "router_tol": 1e-4},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 30, "sigma": 0.6, "min": 5, "max": 70},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0, "schedule_seed": 1}


def make_copy(root: str) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-nemotron3`` wherever
    ``nemotron3-chat-wide-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-nemotron3-w8.json", CONFIG),
                         ("traffic/tiny-wide.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-nemotron3-w8", "source": CONFIG["source"],
        "reduced": ["num_hidden_layers"],
        "file": "benchmark/configs/tiny-nemotron3-w8.json",
        "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-nemotron3-w8",
                              "traffic": "tiny-wide", "chips": 1,
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


def rehearse(root: str, trace: bool = False) -> dict:
    return run.run_cell(CELL, seed=2147480021, seconds=3.0, trace=trace,
                        device_check=fake_device, root=root)


def check_untraced(result: dict) -> None:
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 5
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def check_traced(result: dict) -> None:
    """The per-layer line of the rehearsed cell: the program-span metrics
    are read from the engine's own step spans; the device-trace ones need a
    TPU's trace and are left out on the CPU."""
    m = result["metrics"]
    assert result["correct"]
    assert 0 < m["state_slots_used_pct"]["value"] <= 100
    assert 0 < m["mixed_step_share_pct"]["value"] < 100
    assert m["serve_compiles_in_window"]["value"] == 0
    # the readers that take their sizes from what the driver observed
    assert 0 < m["moe_experts_hit_pct"]["value"] <= 100
    assert 0 <= m["moe_pad_rows_pct"]["value"] < 100
    assert m["mixed_step_ms_p50.tps"]["value"] > 0
    assert m["mixed_host_ms_p50.tps"]["value"] > 0
    assert 0 < m["mixed_step_fill_pct.tps"]["value"] <= 100
    for name in ("ssm_busy_pct", "ssm_decode_roofline_pct",
                 "ssd_scan_roofline_pct", "moe_shared_busy_pct",
                 "moe_gemm_e128_roofline_pct",
                 "moe_gemm_e128_mixed_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace
