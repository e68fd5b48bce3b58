"""The benchmark's Kimi-Linear cell rehearsed in the tier-1 run (which
collects only ``tests/``): driver ``serve_linear_latent_moe`` at the
``tiny-kimi-linear`` preset through ``run.run_cell``, W8A16, ``correct``
decided by ``benchmark/reference/linear_latent_moe_decoder`` on the engine's
own step-program logits (through the tap that reads the experts), on the KDA
state its slots hold, by ``check_router`` and the served tokens' margins;
then the configuration file against the program's preset, the traffic as the
issue names it, the readers on a recorded reduction and the yardstick's
arithmetic.  A later PR that breaks the cell's driver, reference, tap or
readers fails here."""

import copy as _copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import kimilinear_rehearsal as rehearsal  # noqa: E402
import kimilinear_wrong_programs as wrong  # noqa: E402

from benchmark import kda_flops, run, trace_reduce  # noqa: E402
from benchmark.drivers import serve_linear_latent_moe as drv  # noqa: E402
from benchmark.reference import linear_latent_moe_decoder as reference  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("kimilinear")))


def test_kimilinear_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_kimilinear_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


@pytest.fixture(scope="module")
def read():
    config = _copy.deepcopy(rehearsal.CONFIG)
    # float32 and plain weights: what differs from the reference is then the
    # order of the sums, and every wrong program stands clear of it
    config["overrides"] = {"dtype": "float32", "param_dtype": "float32"}
    config["engine"]["weight_bits"] = 0
    config["engine"]["v2"]["dtype"] = "float32"
    return wrong.readings(config, 11, reference.FAULTS, served=False)


@pytest.mark.parametrize("name", ("right",) + reference.FAULTS)
def test_the_comparison_sees_wrong_programs(read, name):
    """In float32 the right program reads 1e-5 on the logits and 1e-6 on the
    state; every named wrong program fails one of the two by a factor of a
    hundred.  The two faults of a step's edge lose every 512 tokens in the
    logit sample (``tests/test_kimi_linear.py`` reads them at the tiny
    engine's budget) and between decode steps in what the served decode-only
    program left in its slot: that comparison has to see them."""
    got = read[name]
    if name == "right":
        assert got["median"] < 2e-4 and got["worst"] < 2e-4, got
        assert got["state"] < 1e-4, got
        assert got["decode_state"] < 1e-4 and got["decode_conv"] < 1e-4, got
        assert got["decode_conv_late"] > 0.1, got
        assert got["decode_steps"][0] >= 11 and got["decode_steps"][1] > 0
    elif name in ("state_lost", "conv_lost"):
        assert got["decode_state"] > 1e-2, got
    else:
        assert got["median"] > 1e-2 or got["state"] > 1e-2, got
    if name in wrong.NOT_OF_THE_STATE and name != "q_unscaled":
        # (the first KDA layer's state reads nothing of these; the deeper
        # ones read the layers before them)
        assert got["median"] > 1e-2, got


def test_the_cell_is_in_the_benchmark():
    """One configuration, one cell, its traffic to the letter."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell, = [w for w in spec["workloads"]
             if w["name"] == "kimilinear-reason-sat"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("kimi-linear-48b-ep8-w8", "reason-sat", 1)
    # PR 55 added the twelfth cell and the eleventh configuration, PR 58
    # the thirteenth and the twelfth
    assert len(spec["workloads"]) == 13 and len(spec["configs"]) == 12
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    entry, = [c for c in spec["configs"]
              if c["name"] == "kimi-linear-48b-ep8-w8"]
    assert entry["reduced"] == ["num_experts", "vocab_size"]
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "reason-sat.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"]) == ("closed", 60)
    assert traffic["prompt_tokens"] == {"median": 1024, "sigma": 0.7,
                                        "min": 256, "max": 4096}
    assert traffic["output_tokens"] == {"median": 2048, "sigma": 0.5,
                                        "min": 1024, "max": 4096}
    assert traffic["schedule_seed"] == 1 and traffic["start_gap_s"] == 0.01
    assert traffic["sharing"].startswith("none")
    # every per-layer metric this cell reports has its reader
    for m in run.metrics_of(spec, "per_layer", "kimilinear-reason-sat"):
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "layer_metrics", m["name"] + ".py")), m["name"]


def test_the_configuration_file_is_the_programs():
    """``kimi-linear-48b-ep8-w8.json`` against the preset: every published
    width, all 27 layers, ``reduced`` = the experts held and the vocabulary,
    the deployment, every assumed size, the sizes as compiled."""
    config = rehearsal.PUBLISHED
    cfg, model = drv.program_config(config)
    assert (cfg.num_layers, cfg.experts_held, cfg.num_experts,
            cfg.vocab_size, cfg.moe_first_expert) == (27, 32, 256, 20480, 0)
    assert (cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim,
            cfg.kv_lora_rank, cfg.expert_width, cfg.intermediate_size,
            cfg.moe_top_k) == (2304, 32, 128, 512, 1024, 9216, 8)
    assert config["reduced"] == ["num_experts", "vocab_size"]
    assert config["as_run"]["num_hidden_layers"] == 27
    assert "EIGHT" in config["deployment"]
    assert {"a_gate_rank", "b_gate_form", "c_q_scale", "d_conv", "e_state",
            "f_head_dim", "g_nope", "weights"} <= set(config["assumed"])
    v2 = config["engine"]["v2"]
    assert (v2["max_seqs"], v2["max_tokens_per_step"], v2["block_size"],
            v2["num_blocks"]) == (48, 512, 64, 48 * 128 + 1)
    run_ = config["as_run"]
    assert 13.2e9 < run_["arguments_bytes"] < 13.4e9
    assert run_["arguments_bytes"] + run_["mixed_step_temp_bytes"] < 14.5e9
    assert cfg.num_params() == 7_240_459_136
    assert model["linear_attn_config"]["kda_layers"][:4] == [1, 2, 3, 5]


@pytest.mark.parametrize("edit,says", [
    (lambda c: c.update(num_experts_per_token=8), "num_experts_per_token"),
    (lambda c: c.update(routed_scaling_factor=1.0), "routed_scaling_factor"),
    (lambda c: c.update(kv_lora_rank=64), "kv_lora_rank"),
    (lambda c: c.update(q_lora_rank=1536), "q_lora_rank"),
    (lambda c: c["linear_attn_config"].update(head_dim=64),
     "linear_attn_config.head_dim"),
    (lambda c: c["linear_attn_config"].update(
        kda_layers=[1, 2, 3], full_attn_layers=[4, 5, 6, 7, 8, 9, 10]),
     "kda_layers"),
    (lambda c: c.update(first_k_dense_replace=2), "first_k_dense_replace"),
    (lambda c: c.update(num_shared_experts=2), "num_shared_experts"),
    (lambda c: c["as_run"].update(first_expert=0), "first_expert"),
])
def test_program_config_refuses_what_the_program_does_not_compute(edit, says):
    config = _copy.deepcopy(rehearsal.CONFIG)
    cfg, model = drv.program_config(config)
    assert cfg.num_layers == 10 and model["intermediate_size"] == 128
    assert model["num_experts"] == 4
    edit(config)
    with pytest.raises(ValueError, match=says):
        drv.program_config(config)


def test_the_yardstick():
    """``benchmark/kda_flops.py`` at the published sizes, against hand
    arithmetic."""
    _, model = drv.program_config(rehearsal.PUBLISHED)
    assert kda_flops.state_bytes(model) == 32 * 128 * 128 * 4 == 2 << 20
    assert (kda_flops.kda_layers(model), kda_flops.latent_layers(model),
            kda_flops.routed_layers(model)) == (20, 7, 26)
    # 48 rows: 4 MiB a row a layer, read and written
    assert kda_flops.decode_update_bytes(model, 48) == 48 * (4 << 20)
    assert kda_flops.decode_update_flops(model, 1) == 6 * 32 * 128 * 128
    # a row of 128 tokens in two pieces of 64: 128 x 65 / 2 pairs
    assert kda_flops.scan_flops(model, 128, 2) == \
        6 * 128 * 32 * 128 * 128 + (128 * 65 / 2) * 32 * (4 * 128 + 4 * 128)
    assert kda_flops.scan_bytes(model, 128, 1) == 2 * (2 << 20) + 128 * (
        3 * 4096 * 2 + 4096 * 4 + 32 * 4 + 4096 * 4)
    assert kda_flops.entry_values(model) == 576
    assert kda_flops.attention_bytes(model, 1000) == 1000 * 576 * 2
    # q . k over 192, p . v over 128, a head
    assert kda_flops.attention_flops(model, 10) == 2.0 * 10 * 32 * 320


def _obs(model, **by_name):
    """What a traced run observes, by hand: one decode and one mixed step's
    spans and a reduction in which every kernel took the time named."""
    def step(kind, **attrs):
        return {"name": "engine/step", "t_start": 1.0, "t_end": 1.01,
                "attrs": {"kind": kind, **attrs}}

    spans = [
        step("decode", kda_state_bytes=48 * 20 * (4 << 20),
             latent_keys_single=48 * 3000 * 7, moe_experts_hit=20.0,
             moe_assignments_local=48 * 26, moe_assignments=48 * 8 * 26,
             moe_rows=384, blocks_used_latent=3072, state_slots_used=48),
        step("mixed", kda_state_bytes=3 * 20 * (4 << 20),
             latent_keys_single=2 * 3000 * 7, kda_scan_rows=1,
             kda_scan_tokens=510, kda_scan_pieces=8, moe_experts_hit=30.0,
             moe_assignments_local=512 * 26, moe_assignments=512 * 8 * 26,
             moe_rows=4096, blocks_used_latent=3072, state_slots_used=48)]
    return {"spans": spans, "model": model,
            "window": {"t_open": 0.0, "t_close": 10.0},
            "engine": {"weight_bits": 8, "weight_group": 128,
                       "v2": {"num_blocks": 6145, "max_seqs": 48}},
            "device": {"peaks": {"bf16_flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9}},
            "trace": {"by_name": {"busy_s": 1.0, **by_name}}}


def test_the_readers_on_a_recorded_reduction():
    """Each new reader on a reduction written by hand: ten decode steps and
    two mixed steps in the trace (their grouped-GEMM calls say so), every
    kernel at twice its least time reads 50 %; without the spans or the
    scopes (the parent's program) each reads nothing."""
    from benchmark.layer_metrics import (kda_busy_pct,
                                         kda_chunk_roofline_pct,
                                         kda_decode_roofline_pct,
                                         kda_latent_pool_used_pct,
                                         latent_full_decode_roofline_pct,
                                         moe_gemm_e32_roofline_pct)
    from benchmark import moe_flops

    _, model = drv.program_config(rehearsal.PUBLISHED)
    hbm, mxu = 819e9, 197e12
    state_s = 48 * 20 * (4 << 20) / hbm  # a decode step's states
    scan_s = 20 * max(kda_flops.scan_bytes(model, 510, 1) / hbm,
                      kda_flops.scan_flops(model, 510, 8) / mxu)
    keys_d, keys_m = 48 * 3000 * 7, 2 * 3000 * 7
    attn_s = 10 * keys_d * 1152 / hbm + 2 * keys_m * 1152 / hbm
    gemm_s = moe_flops.grouped_gemm_bytes(model, 48, 20.0, 8, 128) / hbm
    obs = _obs(
        model,
        kernel_calls={"jit_decode_step/grouped_mixed_gemm": 10 * 3 * 26,
                      "jit_mixed_step/grouped_mixed_gemm": 2 * 3 * 26},
        kernel_s={"jit_decode_step/grouped_mixed_gemm": 2 * 10 * 26 * gemm_s,
                  "jit_decode_step/kda_decode_update": 2 * 10 * state_s},
        scope_s={"jit_decode_step/kda_decode_update": 2 * 10 * state_s,
                 "jit_mixed_step/kda_chunk_scan": 2 * 2 * scan_s,
                 "jit_decode_step/latent_attention_decode_full":
                     1.5 * attn_s,
                 "jit_mixed_step/latent_attention_decode_full":
                     0.5 * attn_s,
                 "jit_decode_step/kda_conv": 0.01})
    assert kda_decode_roofline_pct.read(obs) == pytest.approx(50.0)
    assert kda_chunk_roofline_pct.read(obs) == pytest.approx(50.0)
    assert latent_full_decode_roofline_pct.read(obs) == pytest.approx(50.0)
    assert moe_gemm_e32_roofline_pct.read(obs) == pytest.approx(50.0)
    assert kda_busy_pct.read(obs) == pytest.approx(
        100 * (20 * state_s + 4 * scan_s + 0.01))
    assert kda_latent_pool_used_pct.read(obs) == pytest.approx(50.0)
    # the parent: no such span, no such scope, no such configuration
    bare = _obs({}, kernel_calls={}, kernel_s={}, scope_s={})
    bare["spans"] = []
    for reader in (kda_busy_pct, kda_decode_roofline_pct,
                   kda_chunk_roofline_pct, latent_full_decode_roofline_pct,
                   moe_gemm_e32_roofline_pct, kda_latent_pool_used_pct):
        assert reader.read(bare) is None
        assert reader.read({}) is None
