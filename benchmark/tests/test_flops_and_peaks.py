import json
import os
import types

import pytest

from benchmark import flops, run

HERE = os.path.dirname(os.path.abspath(__file__))
MISTRAL = dict(hidden_size=4096, intermediate_size=14336,
               num_attention_heads=32, num_key_value_heads=8,
               vocab_size=32000, sliding_window=4096)


@pytest.mark.parametrize("layers, gflop", [(2, 3.5), (16, 22.5)])
def test_required_flops_a_token(layers, gflop):
    """The worked figures of ISSUE 23: 6 x (layers + head) and causal
    attention at 2,048 under the window."""
    model = dict(MISTRAL, num_hidden_layers=layers)
    assert flops.train_flops_per_token(model, 2048) / 1e9 == \
        pytest.approx(gflop, abs=0.05)


def test_head_and_layers_counted_embedding_not():
    model = dict(MISTRAL, num_hidden_layers=2)
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.matmul_params(model) == 2 * layer + 4096 * 32000
    assert flops.matmul_params(model, with_head=False) == 2 * layer


def test_window_caps_attended_keys():
    assert flops.mean_attended_keys(2048, 4096) == 1024.5
    assert flops.mean_attended_keys(8192, 4096) == \
        (4096 * 4097 / 2 + 4096 * 4096) / 8192


def test_v5e_peaks():
    with open(os.path.join(os.path.dirname(HERE), "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["int8_ops_per_s"],
            v5e["hbm_bytes_per_s"], v5e["ici_bits_per_s"]) == \
        (197e12, 393e12, 819e9, 1600e9)


def _devices(platform, kind, n):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("platform, kind, n, why", [
    ("cpu", "cpu", 1, "needs a TPU"),
    ("tpu", "TPU v9 imagined", 1, "not in benchmark/peaks.json"),
    ("tpu", "TPU v5 lite", 4, "asks for 1 chip"),
])
def test_wrong_device_is_an_error(monkeypatch, platform, kind, n, why):
    import jax

    monkeypatch.setattr(jax, "devices", lambda: _devices(platform, kind, n))
    with pytest.raises(run.BenchmarkError, match=why):
        run.require_device(1)


def test_command_without_a_tpu_prints_no_result(capsys):
    """The command itself, unbypassed, on this machine's CPU."""
    rc = run.main(["--workload", "train-1chip", "--seed", "0",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "needs a TPU" in out.err
