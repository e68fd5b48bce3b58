"""The ``serve_latent_moe`` driver rehearsed at the program's ``tiny-glm52``
preset (its first five layers: the dense layer that picks and one period of
shared shared shared full) through ``run.run_cell``: a temporary copy of the
benchmark to which a tiny configuration, a tiny traffic mix and a cell are
added, as ``nemotron3_rehearsal.py`` does for ``serve_ssm_moe``.  Shared by
``benchmark/tests/test_serve_latent_moe.py`` and ``tests/test_glm52_cell.py``
(the repository's tier-1 run collects only ``tests/``)."""

import copy
import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-glm52"
REAL = "glm52-ctx8k-sat"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "glm-5.2-ep16-w8.json")) as f:
    PUBLISHED = json.load(f)
_PROGRAM = copy.deepcopy(PUBLISHED["program"])
_PROGRAM["implied"].update(num_experts=16, moe_shared_size=128)
_TYPES = {"indexer_types": ["full", "shared", "shared", "shared", "full",
                            "shared", "shared", "shared", "full"],
          "mlp_layer_types": ["dense"] + ["sparse"] * 8}
CONFIG = {
    # the tiny preset's sizes under the published keys
    "hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 24, "num_hidden_layers": 9,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "max_position_embeddings": 512, "q_lora_rank": 64, "kv_lora_rank": 32,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 32,
    "index_topk": 16, "index_n_heads": 4, "index_head_dim": 16,
    "index_topk_freq": 4, "index_skip_topk_offset": 1,
    "index_topk_pattern": None, "moe_intermediate_size": 128,
    "n_routed_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "hidden_act": "silu",
    "attention_bias": False, "model_type": "glm_moe_dsa",
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    **_TYPES,
    "name": "tiny-glm52-w8",
    "source": "the repository's tiny-glm52 preset",
    "driver": "serve_latent_moe", "preset": "tiny-glm52",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16",
                  "num_layers": 5,
                  "indexer_types": _TYPES["indexer_types"][:5],
                  "mlp_layer_types": _TYPES["mlp_layer_types"][:5]},
    "reduced": ["num_hidden_layers", "n_routed_experts"],
    "as_run": {"num_hidden_layers": 5, "first_layer": 0,
               "n_routed_experts": 4, "first_expert": 4,
               "indexer_types": _TYPES["indexer_types"][:5],
               "mlp_layer_types": _TYPES["mlp_layer_types"][:5]},
    "program": _PROGRAM,
    # group 256: at these widths every group is then all of K, which the
    # kernels tile; the chip's group is 128
    "engine": {"weight_bits": 8, "weight_group": 256,
               "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 65,
                      "max_blocks_per_seq": 16, "dtype": "bfloat16",
                      "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0},
               "pools": {"latent": {"dtype": "bfloat16", "width": 128},
                         "index": {"dtype": "bfloat16", "width": 16}}},
    # at toy widths (16 keys of a few dozen, 4 experts of 16 held) one flip
    # of a pick or of an expert is a large share of a token's output: the
    # bounds are loose here, the chip's are in the published file
    "check": {"margin": 0.5, "reference_len": 96, "window_sequences": 2,
              "warmup_prompt": 40, "warmup_tokens": 6,
              "logit_prompts": [75, 40, 9], "logit_tokens": 10,
              "logit_filler": 100,
              "logit_pad": 32, "logit_tol_median": 0.15, "logit_tol": 0.4,
              "agree_min": 0.7, "served_min": 0.5, "index_tol": 1e-4,
              "select_band": 0.25, "select_agree_min": 0.8,
              "router_tol": 1e-4},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 40, "sigma": 0.6, "min": 33, "max": 80},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0, "schedule_seed": 1,
           "start_gap_s": 0.01}


def make_copy(root: str) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-glm52`` wherever
    ``glm52-ctx8k-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-glm52-w8.json", CONFIG),
                         ("traffic/tiny-longctx.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-glm52-w8", "source": CONFIG["source"],
        "reduced": CONFIG["reduced"],
        "file": "benchmark/configs/tiny-glm52-w8.json", "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-glm52-w8",
                              "traffic": "tiny-longctx", "chips": 1,
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
    assert 0 < m["latent_pool_used_pct"]["value"] <= 100
    assert 0 < m["dsa_keys_read_vs_full_pct"]["value"] <= 100
    # 4 of 16 experts held, a near-uniform router: about a quarter
    assert 10 < m["moe_local_rows_pct"]["value"] < 40
    assert 0 < m["mixed_step_share_pct"]["value"] <= 100
    assert m["serve_compiles_in_window"]["value"] == 0
    assert m["mixed_step_ms_p50.tps"]["value"] > 0
    assert 0 < m["attn_q_fill_pct"]["value"] <= 100
    for name in ("dsa_index_busy_pct", "dsa_index_roofline_pct",
                 "latent_attn_busy_pct", "latent_prefill_roofline_pct",
                 "latent_decode_roofline_pct",
                 "moe_gemm_e16_mixed_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace
