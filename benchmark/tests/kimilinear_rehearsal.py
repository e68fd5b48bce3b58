"""The ``serve_linear_latent_moe`` driver rehearsed at the program's
``tiny-kimi-linear`` preset through ``run.run_cell``: a temporary copy of the
benchmark to which a tiny configuration, a tiny traffic mix and a cell are
added, as ``glm52_rehearsal.py`` does for ``serve_latent_moe``.  Run in tier 1
by ``tests/test_kimilinear_cell.py`` (the repository's tier-1 run collects
only ``tests/``)."""

import copy
import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-kimilinear"
REAL = "kimilinear-reason-sat"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "kimi-linear-48b-ep8-w8.json")) as f:
    PUBLISHED = json.load(f)
_PROGRAM = copy.deepcopy(PUBLISHED["program"])
_PROGRAM["implied"].update(num_experts=16, moe_shared_size=128,
                           kda_gate_rank=16, kda_chunk_size=8)
CONFIG = {
    # the tiny preset's sizes under the published keys
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 32, "num_hidden_layers": 10,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "model_max_length": 512, "rope_theta": 10000.0, "rope_scaling": None,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 24,
    "qk_rope_head_dim": 8, "v_head_dim": 32, "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 10], "kda_layers": [1, 2, 3, 5, 6, 7, 9],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "moe_intermediate_size": 128, "num_experts": 16,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "routed_scaling_factor": 2.446, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "num_nextn_predict_layers": 0,
    "hidden_act": "silu", "model_type": "kimi_linear",
    "name": "tiny-kimi-linear-w8",
    "source": "the repository's tiny-kimi-linear preset",
    "driver": "serve_linear_latent_moe", "preset": "tiny-kimi-linear",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16"},
    "reduced": ["num_experts"],
    "as_run": {"num_experts": 4, "first_expert": 4},
    "program": _PROGRAM,
    # group 128: at these widths every group is then all of K, which the
    # kernels tile
    "engine": {"weight_bits": 8, "weight_group": 128,
               "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 65,
                      "max_blocks_per_seq": 16, "dtype": "bfloat16",
                      "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0},
               "pools": {"latent": {"dtype": "bfloat16", "width": 128},
                         "kda": {"dtype": "float32", "width": 16},
                         "conv": {"dtype": "bfloat16", "width": 192}}},
    # at toy widths (a hidden width of 128, 4 experts of 16 held) bfloat16
    # moves a row by a quarter of a logit's spread over ten layers and one
    # flip of an expert is a large share of a token's output: the bounds
    # are loose here, the chip's are in the published file
    "check": {"margin": 0.5, "reference_len": 96, "window_sequences": 2,
              "warmup_prompt": 40, "warmup_tokens": 6,
              "logit_prompts": [75, 40, 9], "logit_tokens": 10,
              "logit_pad": 32, "logit_tol_median": 0.4, "logit_tol": 0.8,
              "state_tol": 0.02, "state_tol_deep": 0.3,
              "state_low_bits_min": 0.5, "kda_tol": 1e-3, "agree_min": 0.6,
              "served_min": 0.4, "router_tol": 1e-4,
              "decode_prompt": 21, "decode_tokens": 12, "conv_tol": 0.02},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 20, "max": 70},
           "output_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 20},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0, "schedule_seed": 1,
           "start_gap_s": 0.01}


def make_copy(root: str) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-kimilinear`` wherever
    ``kimilinear-reason-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-kimi-linear-w8.json", CONFIG),
                         ("traffic/tiny-reason.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-kimi-linear-w8", "source": CONFIG["source"],
        "reduced": CONFIG["reduced"],
        "file": "benchmark/configs/tiny-kimi-linear-w8.json",
        "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-kimi-linear-w8",
                              "traffic": "tiny-reason", "chips": 1,
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
    return run.run_cell(CELL, seed=2147480051, seconds=3.0, trace=trace,
                        device_check=fake_device, root=root)


def check_untraced(result: dict) -> None:
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def check_traced(result: dict) -> None:
    """The per-layer line of the rehearsed cell: the program-span metrics
    are read from the engine's own step spans; the device-trace ones need a
    TPU's trace and are left out on the CPU."""
    m = result["metrics"]
    assert result["correct"]
    assert 0 < m["kda_latent_pool_used_pct"]["value"] <= 100
    assert 0 < m["state_slots_used_pct"]["value"] <= 100
    # 4 of 16 experts held, a near-uniform router: about a quarter
    assert 10 < m["moe_local_rows_pct"]["value"] < 40
    assert 0 < m["mixed_step_share_pct"]["value"] <= 100
    assert m["serve_compiles_in_window"]["value"] == 0
    assert m["step_h2d_copies_max"]["value"] == 1
    assert 0 < m["attn_q_fill_pct"]["value"] <= 100
    for name in ("kda_busy_pct", "kda_decode_roofline_pct",
                 "kda_chunk_roofline_pct", "latent_full_decode_roofline_pct",
                 "moe_gemm_e32_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace
