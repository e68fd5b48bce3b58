"""The benchmark's Jamba2 cell rehearsed in the tier-1 run (which collects
only ``tests/``): driver ``serve_selective`` at the ``tiny-jamba2`` preset
through ``run.run_cell`` with a stub device, ``correct`` decided on the
logits and the states the WINDOW's own mixed steps produced against
``benchmark/reference/selective_ssm_decoder``; the configuration's sizes
against its ``sizing``; the traffic file's parameters; the new readers.  A
later PR that breaks the cell's driver, reference, tap or readers fails
here."""

import copy as _copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
import jamba2_rehearsal as rehearsal  # noqa: E402
import test_serve_selective as readers  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from benchmark.drivers import serve_selective  # noqa: E402


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("jamba2")))


def test_jamba2_cell_rehearsal(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_jamba2_cell_rehearsal_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_published_configuration_is_the_catalogs_and_the_programs():
    """Every key of the catalog's row, unchanged; ``reduced`` empty; the
    program's preset computes what the file states; the sizes add up to the
    file's ``sizing``."""
    conf = rehearsal.PUBLISHED
    row = {"attn_layer_offset": 7, "attn_layer_period": 14,
           "expert_layer_offset": 1, "expert_layer_period": 2,
           "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 8192, "mamba_conv_bias": True,
           "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
           "mamba_expand": 2, "mamba_proj_bias": False,
           "max_position_embeddings": 262144, "model_type": "jamba",
           "num_attention_heads": 20, "num_experts": 1,
           "num_experts_per_tok": 1, "num_hidden_layers": 28,
           "num_key_value_heads": 1, "num_logits_to_keep": 1,
           "rms_norm_eps": 1e-06, "sliding_window": None,
           "tie_word_embeddings": True, "use_mamba_kernels": True,
           "vocab_size": 65536}
    assert {k: conf[k] for k in row} == row and conf["reduced"] == []
    cfg, model = serve_selective.program_config(conf)
    assert (cfg.layers_of("S"), cfg.layers_of("*"), cfg.layers_of("F")) == \
        (26, 2, 28)
    assert model["num_hidden_layers"] == 28
    eng, v2 = conf["engine"], conf["engine"]["v2"]
    assert eng["weight_bits"] == 0 and v2["dtype"] == "bfloat16"
    assert v2["num_blocks"] == v2["max_seqs"] * v2["max_blocks_per_seq"] \
        == 16640
    assert v2["max_blocks_per_seq"] * v2["block_size"] == 32768 + 512
    size = eng["sizing_bytes"]
    assert size["weights"] == 2 * cfg.num_params() == 6_058_674_944
    per_seq = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert size["state"] == (v2["max_seqs"] + 1) * per_seq
    assert size["kv"] == 2 * v2["num_blocks"] * 64 * 1 * 128 * 2 * 2
    assert size["arguments"] == size["weights"] + size["state"] + size["kv"]
    assert 0.46 < size["arguments"] / 16e9 < 0.48  # of peaks.json's HBM
    # what memory_analysis() gave for the compiled step programs
    run = conf["as_run"]
    assert 0 <= run["arguments_bytes"] - size["arguments"] < 0.02e9
    assert run["arguments_bytes"] + run["mixed_step_temp_bytes"] < 14.5e9


def test_traffic_is_the_issues():
    t = rehearsal.PUBLISHED_TRAFFIC
    assert (t["loop"], t["clients"]) == ("closed", 40)
    assert t["prompt_tokens"] == {"median": 8192, "sigma": 0.7, "min": 2048,
                                  "max": 32768}
    assert t["output_tokens"] == {"median": 256, "sigma": 0.6, "min": 64,
                                  "max": 512}
    assert (t["schedule_seed"], t["start_gap_s"], t["lead_s"],
            t["request_timeout_s"], t["trace_after_s"],
            t["trace_seconds"]) == (1, 0.01, 1.5, 240.0, 3.0, 4.0)
    # the longest prompt and the longest answer fit a row's table
    v2 = rehearsal.PUBLISHED["engine"]["v2"]
    assert 32768 + 512 <= v2["max_blocks_per_seq"] * v2["block_size"]


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert len(spec["workloads"]) == 13 and len(spec["configs"]) == 12
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
    cell = spec["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("jamba2-doc-long-sat", "jamba2-3b-bf16", "doc-long-sat", 1)
    assert spec["configs"][-1]["reduced"] == []
    mine = {m["name"]: m for m in spec["per_layer"]
            if m.get("workloads") == ["jamba2-doc-long-sat"]}
    assert set(mine) == {"sel_busy_pct", "sel_scan_roofline_pct",
                         "sel_decode_roofline_pct", "dense_ffn_busy_pct",
                         "kv_pool_used_pct"}
    assert mine["sel_decode_roofline_pct"]["moves"] == "itl_p90_ms"
    listed = {m["name"] for m in spec["per_layer"] + spec["end_to_end"]
              if "jamba2-doc-long-sat" in m.get("workloads", ())}
    both = {m["name"] for m in spec["per_layer"]
            if {"mellum2-code-sat", "glm52-ctx8k-sat"}
            <= set(m.get("workloads", ()))}
    # the every-step-is-mixed set, less the routed experts' two
    assert both - listed == {"moe_gemm_busy_pct", "moe_dispatch_busy_pct"}
    assert {"serve_out_tokens_per_s", "itl_p90_ms", "state_slots_used_pct",
            "attn_busy_pct"} <= listed
    assert "decode_rows_mean" not in listed


def test_new_readers():
    readers.test_the_yardstick()
    readers.test_readers_read_the_new_spans_and_scopes()
    readers.test_readers_leave_out_what_a_program_without_the_layers_lacks()


@pytest.mark.parametrize("edit,says", [
    (lambda c: c.update(mamba_dt_rank=16), "mamba_dt_rank"),
    (lambda c: c.update(attn_layer_offset=1), "layer order"),
    (lambda c: c.update(mamba_expand=4), "mamba_expand"),
    (lambda c: c.update(tie_word_embeddings=False), "tie_word_embeddings"),
    (lambda c: c.update(mamba_proj_bias=True), "mamba_proj_bias"),
    (lambda c: c.update(num_experts=16), "dense"),
    (lambda c: c["engine"].update(weight_bits=8), "weight_bits"),
])
def test_program_config_refuses_what_the_program_does_not_compute(edit, says):
    config = _copy.deepcopy(rehearsal.CONFIG)
    cfg, model = serve_selective.program_config(config)
    assert cfg.num_layers == 16 and model["num_hidden_layers"] == 8
    edit(config)
    with pytest.raises(ValueError, match=says):
        cfg, _ = serve_selective.program_config(config)
        serve_selective.make_params(cfg, 0, config["engine"]["weight_bits"])
