"""The GLM-5.2 cell's driver, reference, readers and arithmetic, on the
CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import glm52_rehearsal as rehearsal  # noqa: E402

from benchmark import trace_reduce  # noqa: E402
from benchmark.layer_metrics import (dsa_index_busy_pct,  # noqa: E402
                                     dsa_index_roofline_pct,
                                     dsa_keys_read_vs_full_pct,
                                     latent_attn_busy_pct,
                                     latent_decode_roofline_pct,
                                     latent_pool_used_pct,
                                     latent_prefill_roofline_pct,
                                     moe_gemm_e16_mixed_roofline_pct,
                                     moe_local_rows_pct)

MODEL = {"hidden_size": 6144, "intermediate_size": 2048,
         "num_attention_heads": 64, "kv_lora_rank": 512,
         "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
         "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
         "indexer_types": ["full"] + ["shared", "shared", "shared",
                                      "full"] * 2,
         "mlp_layer_types": ["dense"] + ["sparse"] * 8, "num_experts": 16}
READERS = (dsa_index_busy_pct, dsa_index_roofline_pct, latent_attn_busy_pct,
           latent_prefill_roofline_pct, latent_decode_roofline_pct,
           dsa_keys_read_vs_full_pct, latent_pool_used_pct,
           moe_local_rows_pct, moe_gemm_e16_mixed_roofline_pct)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("glm52")))


def test_serve_latent_moe_driver(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_serve_latent_moe_driver_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def _obs(program_is_latent: bool):
    attrs = {"kind": "mixed", "tokens": 512, "moe_rows": 4096,
             "moe_experts_hit": 16.0}
    if program_is_latent:
        attrs.update(
            dsa_keys_visible=9 * 512 * 8000, dsa_keys_selected=9 * 512 * 2048,
            dsa_selected_single=9 * 12 * 2048,
            dsa_selected_prefill=9 * 500 * 2048,
            latent_keys_single=9 * 12 * 2048, latent_keys_prefill=9 * 8000,
            dsa_index_pairs=3 * 512 * 8000, dsa_index_keys=3 * 100000,
            latent_blocks_used=2000, moe_assignments=8 * 4096,
            moe_assignments_local=8 * 256)
    steps = [{"name": "engine/step", "t_start": 0.0, "t_end": 0.05,
              "attrs": attrs}]
    scopes = {"jit_mixed_step/latent_attention_prefill": 0.30,
              "jit_mixed_step/latent_attention_decode": 0.10,
              "jit_mixed_step/dsa_index_scores": 0.03,
              "jit_mixed_step/dsa_topk": 0.02} if program_is_latent else \
        {"jit_mixed_step/moe_dispatch": 0.01}
    return {"spans": steps, "model": dict(MODEL) if program_is_latent else
            {"hidden_size": 6144, "intermediate_size": 2048},
            "engine": {"weight_bits": 8, "weight_group": 128,
                       "v2": {"max_seqs": 16, "num_blocks": 4353}},
            "device": {"peaks": {"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12}},
            "trace": {"by_name": {
                "busy_s": 1.0, "scope_s": scopes,
                "kernel_s": {"jit_mixed_step/grouped_mixed_gemm": 0.1},
                "kernel_calls": {"jit_mixed_step/grouped_mixed_gemm":
                                 240.0}}}}


def test_readers_read_the_new_spans_and_scopes():
    obs = _obs(True)
    assert dsa_index_busy_pct.read(obs) == pytest.approx(5.0)
    assert latent_attn_busy_pct.read(obs) == pytest.approx(40.0)
    assert dsa_keys_read_vs_full_pct.read(obs) == pytest.approx(25.6)
    assert latent_pool_used_pct.read(obs) == pytest.approx(
        100 * 2000 / 4352)
    assert moe_local_rows_pct.read(obs) == pytest.approx(6.25)
    # 240 calls = 3 matrices x 8 routed layers x 10 mixed steps
    flops = 2.0 * 3 * 512 * 8000 * 32 * 128
    assert dsa_index_roofline_pct.read(obs) == pytest.approx(
        100 * 10 * flops / 197e12 / 0.03)
    for reader in (latent_prefill_roofline_pct, latent_decode_roofline_pct,
                   moe_gemm_e16_mixed_roofline_pct):
        assert 0 < reader.read(obs) < 100


def test_readers_leave_out_what_a_program_without_latent_layers_lacks():
    """The parent's program under this benchmark (a traced run of an older
    cell, or this cell's files over a checkout that lacks the model): no
    ``dsa_*`` or ``latent_*`` scope, no counter: nothing to read, nothing
    raised."""
    obs = _obs(False)
    for reader in READERS:
        assert reader.read(obs) is None
    for reader in READERS:
        assert reader.read({"spans": [], "trace": None, "model": {},
                            "engine": {"v2": {"max_seqs": 1}}}) is None
