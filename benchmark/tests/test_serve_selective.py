"""The Jamba2 cell's driver, reference, readers and arithmetic, on the CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import jamba2_rehearsal as rehearsal  # noqa: E402

from benchmark import selective_flops, trace_reduce  # noqa: E402
from benchmark.layer_metrics import (dense_ffn_busy_pct,  # noqa: E402
                                     kv_pool_used_pct, sel_busy_pct,
                                     sel_decode_roofline_pct,
                                     sel_scan_roofline_pct)

MODEL = {k: rehearsal.PUBLISHED[k] for k in (
    "num_hidden_layers", "attn_layer_period", "attn_layer_offset",
    "hidden_size", "mamba_expand", "mamba_d_state", "mamba_dt_rank")}
READERS = (sel_busy_pct, sel_scan_roofline_pct, sel_decode_roofline_pct,
           dense_ffn_busy_pct, kv_pool_used_pct)
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("jamba2")))


def test_serve_selective_driver(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_serve_selective_driver_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_the_yardstick():
    """What a Mamba-1 layer requires, from the published sizes."""
    assert selective_flops.d_inner(MODEL) == 5120
    assert selective_flops.state_bytes(MODEL) == 327_680
    assert selective_flops.mamba_layers(MODEL) == 26
    # x, z in and y out in bfloat16; 160 + 16 + 16 float32
    assert selective_flops.token_bytes(MODEL) == 3 * 5120 * 2 + 192 * 4
    assert selective_flops.scan_flops(MODEL, 512) == 9.0 * 512 * 81_920
    assert selective_flops.scan_bytes(MODEL, 499, 1) == \
        2 * 327_680 + 499 * 31_488
    assert selective_flops.decode_update_bytes(MODEL, 16) == \
        16 * (2 * 327_680 + 31_488)
    # the table has no vector-unit peak: bytes decide (19.98 against 1.87 us)
    least = selective_flops.least_s(selective_flops.scan_flops(MODEL, 499),
                                    selective_flops.scan_bytes(MODEL, 499, 1),
                                    PEAKS)
    assert least == pytest.approx(selective_flops.scan_bytes(MODEL, 499, 1)
                                  / 819e9)
    # what the issue's arithmetic says no array may hold: (T, d_inner, N)
    assert 512 * 5120 * 16 * 4 == 167_772_160


def _obs(has_mamba: bool):
    row = 2 * 26 * 327_680
    steps = [{"name": "engine/step", "t_start": 0.0, "t_end": 0.03, "attrs": {
        "kind": "mixed",
        **({"state_slots_used": 30, "ssm_state_bytes": 14 * row,
            "ssm_scan_rows": 1, "ssm_scan_tokens": 499, "ssm_scan_pieces": 4,
            "kv_blocks_used": 4000} if has_mamba else {})}}
        for _ in range(3)]
    scopes = {"jit_mixed_step/selective_scan": 0.040,
              "jit_mixed_step/selective_decode_update": 0.013,
              "jit_mixed_step/sel_in_proj": 0.05,
              "jit_mixed_step/sel_x_proj": 0.02,
              "jit_mixed_step/dense_ffn": 0.15} if has_mamba else \
        {"jit_mixed_step/moe_dispatch": 0.01}
    kernels = {"jit_mixed_step/selective_scan": 0.040,
               "jit_mixed_step/selective_decode_update": 0.013} \
        if has_mamba else {"jit_mixed_step/mixed_gemm": 0.1}
    return {"spans": steps, "model": dict(MODEL),
            "engine": {"weight_bits": 0, "weight_group": 0,
                       "v2": {"max_seqs": 32,
                              "num_blocks": 16640 if has_mamba else 0}},
            "device": {"peaks": dict(PEAKS)},
            "trace": {"by_name": {
                "busy_s": 0.5, "scope_s": scopes, "kernel_s": kernels,
                "kernel_calls": {k: 260.0 for k in kernels}}}}


def test_readers_read_the_new_spans_and_scopes():
    obs = _obs(True)
    assert sel_busy_pct.read(obs) == pytest.approx(100 * 0.123 / 0.5)
    assert dense_ffn_busy_pct.read(obs) == pytest.approx(30.0)
    assert kv_pool_used_pct.read(obs) == pytest.approx(100 * 4000 / 16639)
    # 260 calls = 26 layers x 10 mixed steps
    assert sel_scan_roofline_pct.read(obs) == pytest.approx(
        100 * 260 * selective_flops.scan_bytes(MODEL, 499, 1) / 819e9 / 0.040)
    assert sel_decode_roofline_pct.read(obs) == pytest.approx(
        100 * 260 * selective_flops.decode_update_bytes(MODEL, 13) / 819e9
        / 0.013)
    for reader in (sel_scan_roofline_pct, sel_decode_roofline_pct):
        assert 0 < reader.read(obs) < 100


def test_readers_leave_out_what_a_program_without_the_layers_lacks():
    """The parent's program under this benchmark (a traced run of an older
    cell, or this cell's files over a checkout that lacks the model): no
    ``sel_*`` scope, no counter: nothing to read, nothing raised."""
    obs = _obs(False)
    for reader in READERS:
        assert reader.read(obs) is None
    for reader in READERS:
        assert reader.read({"spans": [], "trace": None, "model": {},
                            "engine": {"v2": {"max_seqs": 1}}}) is None
