"""The Mellum2 cell's driver, reference, tap, readers and reduction, on the
CPU."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import mellum2_rehearsal as rehearsal  # noqa: E402

from benchmark import attn_flops, kernel_time, moe_flops, trace_reduce  # noqa: E402
from benchmark.drivers import serve_swa_moe  # noqa: E402
from benchmark.layer_metrics import (attn_busy_pct,  # noqa: E402
                                     global_pool_used_pct,
                                     kv_read_vs_full_pct,
                                     moe_gemm_mixed_roofline_pct,
                                     prefill_attn_roofline_pct,
                                     window_pool_used_pct)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return rehearsal.make_copy(str(tmp_path_factory.mktemp("mellum2")))


def test_serve_swa_moe_driver(copy):
    rehearsal.check_untraced(rehearsal.rehearse(copy))


def test_serve_swa_moe_driver_traced(copy, monkeypatch):
    recorded = trace_reduce.load(rehearsal.FIXTURE)
    monkeypatch.setattr(trace_reduce, "load", lambda path: recorded)
    rehearsal.check_traced(rehearsal.rehearse(copy, trace=True))


def test_an_altered_token_is_not_correct(copy, monkeypatch):
    """The margin the rehearsal holds has teeth: every fifth token a step
    hands to its requests is another token (the engine's own state keeps the
    right one); no request fails, and ``correct`` is false."""
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2

    step, count = InferenceEngineV2.step, [0]

    def altered(self, *args, **kwargs):
        out = step(self, *args, **kwargs)
        for tokens in out.values():
            for i in range(len(tokens)):
                count[0] += 1
                if count[0] % 5 == 0:
                    tokens[i] = (int(tokens[i]) + 100) % 255 + 1
        return out

    monkeypatch.setattr(InferenceEngineV2, "step", altered)
    result = rehearsal.rehearse(copy)
    assert result["failed"] == 0 and result["attempted"] > 5
    assert not result["correct"]


@pytest.mark.parametrize("edit,says", [
    (lambda c: c["rope_parameters"]["full_attention"].update(factor=8.0),
     "rope_parameters.full_attention.factor"),
    (lambda c: c["rope_parameters"]["sliding_attention"].update(
        rope_type="yarn"), "rope_parameters.sliding_attention.rope_type"),
    (lambda c: c.update(sliding_window=16), "sliding_window"),
    (lambda c: c.update(layer_types=["full_attention"] * 8), "layer_types"),
    (lambda c: c.update(norm_topk_prob=False), "norm_topk_prob"),
    (lambda c: c.update(moe_intermediate_size=256), "moe_intermediate_size"),
])
def test_program_config_refuses_what_the_program_does_not_compute(edit, says):
    import copy as _copy

    config = _copy.deepcopy(rehearsal.CONFIG)
    cfg, model = serve_swa_moe.program_config(config)
    assert cfg.num_layers == 4 and model["intermediate_size"] == 128
    assert model["layer_types"] == rehearsal.CONFIG["layer_types"][:4]
    edit(config)
    with pytest.raises(ValueError, match=says):
        serve_swa_moe.program_config(config)


def _event(name, start, dur):
    return trace_reduce.Event(name, start, start + dur)


def synthetic_obs():
    """A window of 4 ms on one chip: one mixed step of a two-layer model,
    each layer a prefill attention call of 100 us and three grouped GEMMs of
    400 us, and 100 us of something else."""
    attn = ('%paged_attention_prefill.{} = bf16[32,512,32,128]{{3,2,1,0}} '
            'custom-call(s32[1]{{0}} %l), custom_call_target="tpu_custom_call"')
    gemm = ('%grouped_mixed_gemm.{} = bf16[12288,896]{{1,0}} custom-call('
            'bf16[12288,2304]{{1,0}} %x), custom_call_target="tpu_custom_call"')
    ops, t = [], 100_000
    for layer in range(2):
        ops.append(_event(attn.format(layer), t, 100_000))
        t += 100_000
        for i in range(3):
            ops.append(_event(gemm.format(3 * layer + i), t, 400_000))
            t += 400_000
    ops.append(_event("%add.1 = f32[8]{0} add(f32[8]{0} %a)", t, 100_000))
    trace = trace_reduce.Trace(
        {0: ops}, {0: [_event("jit_mixed_step(1)", 50_000, 3_900_000)]},
        [_event(trace_reduce.WINDOW, 0, 4_000_000)])
    model = {"hidden_size": 2304, "intermediate_size": 896,
             "num_attention_heads": 32, "num_key_value_heads": 4,
             "head_dim": 128, "num_hidden_layers": 2, "num_experts": 64}
    step = {"kind": "mixed", "moe_rows": 4096, "moe_rows_padded": 12288,
            "moe_experts_hit": 64.0, "moe_rows_max": 80,
            "kv_blocks_read": 300, "kv_blocks_full": 500,
            "kv_query_keys": 2_000_000, "window_blocks_freed": 3,
            "blocks_used_global": 1500, "blocks_used_window": 400}
    return {"trace": {"by_name": kernel_time.reduce(trace, {})},
            "model": model,
            "engine": {"weight_bits": 8, "weight_group": 128,
                       "v2": {"block_size": 64, "num_blocks": 3001,
                              "num_window_blocks": 801}},
            "device": {"peaks": {"hbm_bytes_per_s": 819e9,
                                 "bf16_flops_per_s": 197e12}},
            "window": {"t_open": 0.0, "t_close": 1.0},
            "spans": [{"name": "engine/step", "t_start": 0.1, "t_end": 0.2,
                       "attrs": step}]}


def test_readers_on_a_synthetic_trace():
    obs = synthetic_obs()
    assert kv_read_vs_full_pct.read(obs) == pytest.approx(60.0)
    assert global_pool_used_pct.read(obs) == pytest.approx(50.0)
    assert window_pool_used_pct.read(obs) == pytest.approx(50.0)
    assert attn_busy_pct.read(obs) == pytest.approx(100 * 200 / 2700)
    # one step, both layers: 2e6 (query, key) pairs x 32 heads x 128 x 4
    flops = 4 * 2_000_000 * 32 * 128
    assert attn_flops.attention_flops(obs["model"], 2_000_000) == flops
    assert attn_flops.block_bytes(obs["model"], 64) == 131072
    assert flops / 197e12 > 300 * 131072 / 819e9  # the MXU's is the larger
    assert prefill_attn_roofline_pct.read(obs) == pytest.approx(
        100 * flops / 197e12 / 200e-6)
    per_layer = max(
        moe_flops.grouped_gemm_bytes(obs["model"], 4096, 64.0, 8, 128)
        / 819e9, moe_flops.grouped_gemm_flops(obs["model"], 4096) / 197e12)
    assert moe_gemm_mixed_roofline_pct.read(obs) == pytest.approx(
        100 * 2 * per_layer / 2400e-6)
    assert 0 < moe_gemm_mixed_roofline_pct.read(obs) < 100


def test_readers_read_nothing_from_an_older_program():
    """A program without the counters (the parent), a model without window
    layers, or a driver without the reduction by name, leaves the metrics
    out and does not raise."""
    obs = {"spans": [{"name": "engine/step", "t_start": 0.1, "t_end": 0.2,
                      "attrs": {"kind": "mixed", "moe_rows": 4096,
                                "moe_experts_hit": 64.0}}],
           "trace": {"busy_s": 1.0}, "engine": {"v2": {"num_blocks": 416}},
           "window": {"t_open": 0.0, "t_close": 1.0}}
    for reader in (kv_read_vs_full_pct, global_pool_used_pct,
                   window_pool_used_pct, attn_busy_pct,
                   prefill_attn_roofline_pct, moe_gemm_mixed_roofline_pct):
        assert reader.read(obs) is None
    obs["trace"] = None
    assert attn_busy_pct.read(obs) is None
