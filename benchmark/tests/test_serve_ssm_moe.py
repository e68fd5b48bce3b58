"""The Nemotron-3-Nano cell's driver, reference, readers and arithmetic, on
the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import nemotron3_rehearsal as rehearsal  # noqa: E402

from benchmark import ssm_flops, trace_reduce  # noqa: E402
from benchmark.layer_metrics import (moe_gemm_e128_mixed_roofline_pct,  # noqa: E402
                                     moe_gemm_e128_roofline_pct,
                                     moe_shared_busy_pct,
                                     ssd_scan_roofline_pct, ssm_busy_pct,
                                     ssm_decode_roofline_pct,
                                     state_slots_used_pct)

MODEL = {"hidden_size": 2688, "moe_intermediate_size": 1856,
         "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
         "n_groups": 8, "hybrid_override_pattern": "EMEMEM*EMEMEMEM*"}
READERS = (ssm_busy_pct, ssm_decode_roofline_pct, ssd_scan_roofline_pct,
           moe_shared_busy_pct, state_slots_used_pct,
           moe_gemm_e128_roofline_pct, moe_gemm_e128_mixed_roofline_pct)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("nemotron3")))


def test_serve_ssm_moe_driver(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_serve_ssm_moe_driver_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_the_yardstick():
    assert ssm_flops.state_bytes(MODEL) == 2_097_152
    assert ssm_flops.decode_update_bytes(MODEL, 64) == 64 * 2 * 2_097_152
    assert (ssm_flops.mamba_layers(MODEL), ssm_flops.moe_layers(MODEL)) == \
        (7, 7)
    assert ssm_flops.stored_expert_width(MODEL) == 1920
    # operations at the published width, bytes at the stored one
    assert ssm_flops.grouped_gemm_flops(MODEL, 384) == \
        2 * 2.0 * 384 * 2688 * 1856
    codes = 2 * (2688 * 1920 + 21 * 1920 * 4 / 2 + 15 * 2688 * 4 / 2)
    acts = 384 * 2 * (2688 + 1920) * 2
    assert ssm_flops.grouped_gemm_bytes(MODEL, 384, 100.0, 8, 128) == \
        pytest.approx(100 * codes + acts)
    # a piece of 128 tokens: the state twice a token, 8,256 causal pairs
    assert ssm_flops.scan_flops(MODEL, 128, 1) == \
        4.0 * 128 * 64 * 64 * 128 + 2.0 * 8256 * (8 * 128 + 64 * 64)


def _obs(program_has_ssm: bool):
    steps = [{"name": "engine/step", "t_start": 0.0, "t_end": 0.03, "attrs": {
        "kind": kind, "moe_rows": rows, "moe_experts_hit": 120.0,
        **({"state_slots_used": 48, "ssm_state_bytes": 60 * 7 * 2 * 2_097_152,
            "ssm_scan_rows": 2, "ssm_scan_tokens": 400, "ssm_scan_pieces": 4}
           if program_has_ssm else {})}}
        for kind, rows in (("decode", 384), ("mixed", 3072))]
    scopes = {"jit_decode_step/ssm_decode_update": 0.010,
              "jit_decode_step/ssm_in_proj": 0.004,
              "jit_mixed_step/ssd_chunk_scan": 0.020,
              "jit_mixed_step/moe_shared": 0.002} if program_has_ssm else \
        {"jit_decode_step/moe_dispatch": 0.01}
    return {"spans": steps, "model": dict(MODEL),
            "engine": {"weight_bits": 8, "weight_group": 128,
                       "v2": {"max_seqs": 64}},
            "device": {"peaks": {"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12}},
            "trace": {"by_name": {
                "busy_s": 0.5, "scope_s": scopes,
                "kernel_s": {"jit_decode_step/grouped_mixed_gemm": 0.2,
                             "jit_mixed_step/grouped_mixed_gemm": 0.1},
                "kernel_calls": {"jit_decode_step/grouped_mixed_gemm": 140.0,
                                 "jit_mixed_step/grouped_mixed_gemm": 28.0}}}}


def test_readers_read_the_new_spans_and_scopes():
    obs = _obs(True)
    assert ssm_busy_pct.read(obs) == pytest.approx(100 * 0.034 / 0.5)
    assert moe_shared_busy_pct.read(obs) == pytest.approx(0.4)
    assert state_slots_used_pct.read(obs) == pytest.approx(75.0)
    # 140 calls = 2 matrices x 7 layers x 10 decode steps
    per_step = 60 * 7 * 2 * 2_097_152 + 7 * ssm_flops.mamba_projection_bytes(
        MODEL, 8, 128)
    assert ssm_decode_roofline_pct.read(obs) == pytest.approx(
        100 * 10 * per_step / 819e9 / 0.014)
    assert 0 < ssd_scan_roofline_pct.read(obs) < 100
    assert 0 < moe_gemm_e128_roofline_pct.read(obs) < 100
    assert 0 < moe_gemm_e128_mixed_roofline_pct.read(obs) < 100


def test_readers_leave_out_what_a_program_without_state_layers_lacks():
    """The parent's program under this benchmark (a traced run of an older
    cell, or this cell's files over a checkout that lacks the model): no
    ``ssm_*`` scope, no state counter: nothing to read, nothing raised."""
    obs = _obs(False)
    del obs["model"]["moe_intermediate_size"]
    for reader in READERS:
        assert reader.read(obs) is None
    for reader in READERS:
        assert reader.read({"spans": [], "trace": None, "model": {},
                            "engine": {"v2": {"max_seqs": 1}}}) is None
