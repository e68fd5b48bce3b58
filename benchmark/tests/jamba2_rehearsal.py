"""The ``serve_selective`` driver rehearsed at the program's ``tiny-jamba2``
preset (8 layers: a Mamba-1 mixer or attention, and a dense FFN each) through
``run.run_cell``: a temporary copy of the benchmark to which a tiny
configuration, a tiny traffic mix and a cell are added, as
``nemotron3_rehearsal.py`` does for ``serve_ssm_moe``.  Shared by
``benchmark/tests/test_serve_selective.py`` and ``tests/test_jamba2_cell.py``
(the repository's tier-1 run collects only ``tests/``)."""

import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-jamba2"
REAL = "jamba2-doc-long-sat"
with open(os.path.join(ROOT, "benchmark", "configs",
                       "jamba2-3b-bf16.json")) as f:
    PUBLISHED = json.load(f)
with open(os.path.join(ROOT, "benchmark", "traffic",
                       "doc-long-sat.json")) as f:
    PUBLISHED_TRAFFIC = json.load(f)
CONFIG = {
    # the tiny preset's sizes under the published keys
    "attn_layer_offset": 2, "attn_layer_period": 4, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 512,
    "model_type": "jamba", "num_attention_heads": 4, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 8,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-6, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 256,
    "name": "tiny-jamba2-bf16",
    "source": "the repository's tiny-jamba2 preset",
    "driver": "serve_selective", "preset": "tiny-jamba2",
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16"},
    "reduced": [],
    "engine": {"weight_bits": 0, "weight_group": 0,
               "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 96,
                      "max_blocks_per_seq": 24, "dtype": "bfloat16",
                      "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0},
               "state": {"ssm": "float32", "conv": "bfloat16"}},
    # bfloat16 activations at toy widths against the float32 reference: the
    # bounds are loose here, the chip's are in jamba2-3b-bf16.json
    "check": {"warmup_prompt": 75, "warmup_tokens": 6,
              "window_sequences": 3, "tap_sequences": 6,
              "tap_long_min": 30, "tap_longest": 150, "logit_pad": 32,
              "logit_tol_median": 0.3, "logit_tol": 0.8, "state_tol": 0.3,
              "state_low_bits_min": 0.5, "mixed_step_share_min": 0.5},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 70, "sigma": 0.6, "min": 20,
                             "max": 150},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 3, "max": 16},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0, "schedule_seed": 1,
           "start_gap_s": 0.01}


def make_copy(root: str) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-jamba2`` wherever
    ``jamba2-doc-long-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-jamba2-bf16.json", CONFIG),
                         ("traffic/tiny-doc.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-jamba2-bf16", "source": CONFIG["source"],
        "reduced": [], "file": "benchmark/configs/tiny-jamba2-bf16.json",
        "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-jamba2-bf16",
                              "traffic": "tiny-doc", "chips": 1,
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
    return run.run_cell(CELL, seed=2147480021, seconds=4.0, trace=trace,
                        device_check=fake_device, root=root)


def check_untraced(result: dict) -> None:
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 5
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    checks = result["checks"]
    assert checks["window_sequences"][0] == 3
    assert checks["window_long_prompt"][0] >= 30
    assert checks["state_low_bits"][0] > 0.9
    assert set(checks) == {
        "window_sequences", "window_long_prompt", "logit_rows_not_finite",
        "logit_median", "logit_worst", "state_worst", "state_low_bits",
        "state_types_as_stated", "kv_blocks_free", "state_slots_free",
        "failed_requests", "answers_not_max_tokens", "kernel_fallbacks",
        "mixed_step_share"}


def check_traced(result: dict) -> None:
    """The per-layer line of the rehearsed cell: the program-span metrics
    are read from the engine's own step spans; the device-trace ones need a
    TPU's trace and are left out on the CPU."""
    m = result["metrics"]
    assert result["correct"], result["checks"]
    assert 0 < m["state_slots_used_pct"]["value"] <= 100
    assert 0 < m["kv_pool_used_pct"]["value"] <= 100
    assert 0 < m["mixed_step_share_pct"]["value"] <= 100
    assert m["serve_compiles_in_window"]["value"] == 0
    assert m["mixed_step_ms_p50.tps"]["value"] > 0
    assert 0 < m["mixed_step_fill_pct.tps"]["value"] <= 100
    assert 0 < m["attn_q_fill_pct"]["value"] <= 100
    for name in ("sel_busy_pct", "sel_scan_roofline_pct",
                 "sel_decode_roofline_pct", "dense_ffn_busy_pct",
                 "attn_busy_pct"):
        assert name not in m  # no TPU kernel in a CPU trace
