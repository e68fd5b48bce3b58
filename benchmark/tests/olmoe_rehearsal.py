"""The ``serve_moe`` driver rehearsed at the program's ``tiny-olmoe`` preset
through ``run.run_cell``: a temporary copy of the benchmark to which a tiny
configuration, a tiny traffic mix and a cell are added, as
``test_harness.py`` does for the other drivers.  Shared by
``benchmark/tests/test_serve_moe.py`` and ``tests/test_olmoe_cell.py`` (the
repository's tier-1 run collects only ``tests/``)."""

import json
import os
import shutil

from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
FIXTURE = os.path.join(HERE, "fixture_v5e.xplane.pb.gz")
CELL = "t-olmoe"
CONFIG = {
    "hidden_size": 128, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "tie_word_embeddings": False, "max_position_embeddings": 128,
    "model_type": "olmoe", "num_experts": 8, "num_experts_per_tok": 2,
    "norm_topk_prob": False, "attention_bias": False, "clip_qkv": None,
    "name": "tiny-olmoe-w8", "source": "the repository's tiny-olmoe preset",
    "driver": "serve_moe", "preset": "tiny-olmoe", "reduced": [],
    "as_run": {},
    "program": {"published": {"num_experts": "num_experts",
                              "num_experts_per_tok": "moe_top_k",
                              "norm_topk_prob": "moe_norm_topk"},
                "implied": {"qk_norm": True, "norm": "rmsnorm"},
                "must_be_off": ["attention_bias", "clip_qkv"]},
    "overrides": {"dtype": "bfloat16", "param_dtype": "bfloat16"},
    "engine": {"weight_bits": 8, "weight_group": 128,
               "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                      "block_size": 8, "num_blocks": 64,
                      "max_blocks_per_seq": 16, "dtype": "bfloat16",
                      "quantize_bits": 0},
               "serving": {"num_replicas": 1, "max_queue": 64,
                           "drain_timeout_s": 30.0}},
    # at toy widths (8 experts, top 2) a router tie that flips in bf16 swaps
    # a third of a token's expert output: the bounds are loose here, the
    # chip's are in benchmark/configs/olmoe-1b-7b-w8.json
    "check": {"margin": 0.5, "reference_len": 96, "window_sequences": 3,
              "warmup_prompt": 40, "warmup_tokens": 6,
              "logit_prompts": [40, 17, 9], "logit_tokens": 18,
              "logit_tol_median": 0.15, "logit_tol": 1.0,
              "router_layer": 1, "router_tol": 1e-4},
}
TRAFFIC = {"loop": "closed", "clients": 6,
           "prompt_tokens": {"median": 24, "sigma": 0.5, "min": 6, "max": 60},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0}


def make_copy(root: str) -> str:
    """A checkout at ``root`` with the benchmark, the tiny configuration and
    traffic as new files and the cell ``t-olmoe`` wherever
    ``olmoe-decode-sat`` is listed."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for rel, content in (("configs/tiny-olmoe-w8.json", CONFIG),
                         ("traffic/tiny-closed.json", TRAFFIC)):
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": "tiny-olmoe-w8", "source": CONFIG["source"], "reduced": [],
        "file": "benchmark/configs/tiny-olmoe-w8.json", "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny-olmoe-w8",
                              "traffic": "tiny-closed", "chips": 1,
                              "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmoe-decode-sat" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def fake_device(chips):
    """The tests' bypass of the TPU check; the command has none."""
    return {"platform": "cpu", "kind": "cpu", "count": chips,
            "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}


def rehearse(root: str, trace: bool = False) -> dict:
    return run.run_cell(CELL, seed=2147480011, seconds=3.0, trace=trace,
                        device_check=fake_device, root=root)


def check_untraced(result: dict) -> None:
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 5
    assert set(result["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())


def check_traced(result: dict) -> None:
    """The per-layer line of the rehearsed cell: the program-span metrics of
    the routed experts are read from the engine's own step spans; the
    device-trace ones need a TPU's trace and are left out on the CPU."""
    m = result["metrics"]
    assert result["correct"]
    assert 0 < m["moe_experts_hit_pct"]["value"] <= 100
    # tile 16: 4 rows x top-2 = 8 assignments on (1 + 8) x 16 rows
    assert abs(m["moe_pad_rows_pct"]["value"] - 100 * (1 - 8 / 144)) < 1e-6
    assert m["decode_rows_mean"]["value"] > 1
    assert m["serve_compiles_in_window"]["value"] == 0
    assert "moe_gemm_roofline_pct" not in m  # no TPU kernel in a CPU trace


def router_check(route=None) -> dict:
    """``serve_moe.check_router`` on the tiny configuration's own weights,
    with the program's ``route`` or, to show the check's teeth, another."""
    import numpy as np

    from benchmark.drivers import serve_moe
    from deepspeed_tpu.moe import dropless

    cfg, model = serve_moe.program_config(CONFIG)
    params = serve_moe.make_params(cfg, 7, 8, 128)
    rng = np.random.default_rng(7)
    tapped = [(rng.integers(1, cfg.vocab_size, 40).tolist(),
               rng.integers(1, cfg.vocab_size, 18).tolist(), [])]
    real = dropless.route
    if route is not None:
        dropless.route = route
    try:
        return serve_moe.check_router(params, model, cfg, tapped,
                                      CONFIG["check"], print)
    finally:
        dropless.route = real


def bf16_route(x2, router, cfg):
    """A router whose logits are rounded to bfloat16: what the published
    model states in float32, done in the activations' precision."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.dropless import Routing

    # reduce_precision, not a cast: under jit the compiler may keep the
    # excess precision of a bfloat16 round trip (it does on the CPU)
    logits = jax.lax.reduce_precision(
        x2.astype(jnp.float32) @ router.astype(jnp.float32), 8, 7)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.moe_top_k)
    return Routing(weights, experts.astype(jnp.int32), probs, logits)


def check_router_has_teeth() -> None:
    right, wrong = router_check(), router_check(bf16_route)
    assert right["ok"] and right["prob_rel"] < 1e-5
    assert not wrong["ok"] and wrong["prob_rel"] > 10 * CONFIG["check"][
        "router_tol"]
