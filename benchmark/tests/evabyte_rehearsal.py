"""The ``serve_eva`` driver rehearsed at the program's ``tiny-evabyte``
preset through ``run.run_cell``: a temporary copy of the benchmark to which
a tiny configuration, a tiny traffic mix and a cell are added, as
``mellum2_rehearsal.py`` does for ``serve_swa_moe``.  Run in tier 1 by
``tests/test_evabyte_cell.py`` (the repository's tier-1 run collects only
``tests/``)."""

import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-evabyte"
REAL = "evabyte-doc-bytes-sat"
CONFIG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 4,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "max_position_embeddings": 512, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 4,
    "num_hidden_layers": 2, "num_key_value_heads": 4, "num_pred_heads": 2,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000.0,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 32,
    "name": "tiny-evabyte-w8", "source": "the repository's tiny-evabyte preset",
    "driver": "serve_eva", "preset": "tiny-evabyte", "reduced": [],
    "as_run": {},
    "program": {
        "published": {"window_size": "eva_window", "chunk_size": "eva_chunk",
                      "num_pred_heads": "num_pred_heads",
                      "fp32_logits": "fp32_logits"},
        "implied": {"norm": "gemma_rmsnorm", "position": "rope",
                    "qk_norm": False, "num_experts": 0,
                    "activation": "silu"},
        "must_be_off": ["attention_bias", "rope_scaling", "fp32_ln"]},
    # float32 and plain weights, where the chip's cell is bfloat16 over int8
    # codes: at toy widths the top two of 320 random logits lie closer than
    # bf16 rounds, and which requests end inside the window is the machine's
    # load's to decide; in float32 every served byte IS head 0's first
    "overrides": {"dtype": "float32", "param_dtype": "float32"},
    "engine": {"weight_bits": 0, "weight_group": 128,
               "v2": {"max_tokens_per_step": 24, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 17,
                      "num_window_blocks": 17, "max_blocks_per_seq": 16,
                      "dtype": "float32", "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0}},
    "check": {"margin": 0.5, "reference_len": 128, "window_sequences": 3,
              "warmup_prompt": 40, "warmup_tokens": 6,
              "logit_prompts": [60, 75, 9, 30], "logit_tokens": 40,
              "logit_tol_median": 1e-4, "logit_tol": 1e-3,
              "summary_layers": [0, 1], "summary_tol_median": 1e-5,
              "summary_tol": 1e-4},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 50, "sigma": 0.4, "min": 20, "max": 90},
           "output_tokens": {"median": 16, "sigma": 0.5, "min": 4, "max": 36},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0,
           "start_gap_s": 0.01}


def make_copy(root: str, config=CONFIG) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-evabyte`` wherever
    ``evabyte-doc-bytes-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-evabyte-w8.json", config),
                         ("traffic/tiny-bytes.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-evabyte-w8", "source": config["source"], "reduced": [],
        "file": "benchmark/configs/tiny-evabyte-w8.json", "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-evabyte-w8",
                              "traffic": "tiny-bytes", "chips": 1,
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
    return run.run_cell(CELL, seed=2147480017, seconds=3.0, trace=trace,
                        device_check=fake_device, root=root)


def check_untraced(result: dict) -> None:
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 3
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def check_traced(result: dict) -> None:
    """The per-layer line of the rehearsed cell: the program-span metrics of
    the two pools and of what the mechanism saves are read from the engine's
    own step spans; the device-trace ones need a TPU's trace and are left
    out on the CPU."""
    m = result["metrics"]
    assert result["correct"]
    assert 0 < m["eva_keys_read_vs_full_pct"]["value"] < 100
    assert 0 < m["eva_window_pool_used_pct"]["value"] <= 100
    assert 0 < m["eva_summary_pool_used_pct"]["value"] <= 100
    assert 0 < m["attn_q_fill_pct"]["value"] <= 100
    assert m["serve_compiles_in_window"]["value"] == 0
    assert m["step_h2d_copies_max"]["value"] == 1
    for name in ("eva_attn_busy_pct", "eva_decode_roofline_pct",
                 "eva_prefill_roofline_pct", "eva_summary_roofline_pct"):
        assert name not in m  # no TPU kernel in a CPU trace
