"""Every driver rehearsed at a tiny preset through ``run.py``'s own path, in a
temporary copy of the benchmark to which the test adds what a later PR would
add: configurations, traffic mixes, a per-layer metric and cells, as new files
and new entries, with no file that was there edited."""

import json
import os
import shutil

import pytest

from benchmark import run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "sliding_window": 0,
        "tie_word_embeddings": False, "max_position_embeddings": 128,
        "source": "the repository's tiny preset", "preset": "tiny",
        "reduced": [], "as_run": {}}
LENGTHS = {"prompt_tokens": {"median": 24, "sigma": 0.5, "min": 6, "max": 60},
           "output_tokens": {"median": 8, "sigma": 0.5, "min": 2, "max": 16},
           "lead_s": 1.0, "ramp_s": 1.0, "request_timeout_s": 60.0,
           "trace_after_s": 0.5, "trace_seconds": 1.0}
NEW_FILES = {
    "configs/tiny-train.json": dict(
        TINY, name="tiny-train", driver="train",
        overrides={"tie_embeddings": False, "param_dtype": "bfloat16"},
        engine={"loss_tile": 32, "deepspeed": {
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
            "steps_per_print": 10 ** 6}}),
    "configs/tiny-w8.json": dict(
        TINY, name="tiny-w8", driver="serve",
        overrides={"tie_embeddings": False, "dtype": "bfloat16",
                   "param_dtype": "bfloat16"},
        engine={"weight_bits": 8, "weight_group": 256,
                "v2": {"max_tokens_per_step": 32, "max_seqs": 4,
                       "block_size": 8, "num_blocks": 64,
                       "max_blocks_per_seq": 16, "dtype": "bfloat16",
                       "quantize_bits": 0},
                "serving": {"num_replicas": 1, "max_queue": 64,
                            "drain_timeout_s": 30.0}},
        check={"margin": 0.5, "reference_len": 96, "reference_pad": 32,
               "window_sequences": 3,
               "warmup_prompt": 40, "warmup_tokens": 6}),
    "traffic/steps-64.json": {
        "loop": "steps", "seq_len": 64, "warmup_steps": 2, "in_flight": 2,
        "loss_rel_tol": 2e-2, "trace_after_s": 0.5, "trace_seconds": 1.0},
    "traffic/tiny-closed.json": dict(LENGTHS, loop="closed", clients=6),
    "traffic/tiny-open.json": dict(LENGTHS, loop="open", rate_per_s=4.0,
                                   arrival_shape=0.5, max_in_flight=32),
}
NEW_READER = '''"""A per-layer metric a later PR adds: a new file, a new entry."""


def read(obs):
    return float(len(obs["spans"]))
'''
CELLS = {"t-train": ("tiny-train", "steps-64", "train-1chip"),
         "t-closed": ("tiny-w8", "tiny-closed", "chat-decode-sat"),
         "t-open": ("tiny-w8", "tiny-open", "doc-prefill-loaded")}


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    for rel, content in NEW_FILES.items():
        with open(os.path.join(root, "benchmark", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "program_spans_count.py"), "w") as f:
        f.write(NEW_READER)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for name in ("tiny-train", "tiny-w8"):
        spec["configs"].append({
            "name": name, "source": TINY["source"], "reduced": [],
            "file": f"benchmark/configs/{name}.json", "why": "rehearsal"})
    for cell, (config, traffic, like) in CELLS.items():
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "rehearsal"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "program_spans_count", "unit": "count", "better": "higher",
        "source": "program_span", "layer": "scheduler",
        "moves": "itl_p90_ms", "workloads": ["t-closed"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    yield root
    for path, content in before.items():  # nothing that was there changed
        with open(path, "rb") as fh:
            assert fh.read() == content, path


def fake_device(chips):
    """The tests' bypass of the TPU check; the command has none."""
    return {"platform": "cpu", "kind": "cpu", "count": chips,
            "peaks": {"bf16_flops_per_s": 1e12}}


def rehearse(copy, cell, trace=False):
    return run.run_cell(cell, seed=3, seconds=3.0, trace=trace,
                        device_check=fake_device, root=copy)


def test_train_driver(copy):
    r = rehearse(copy, "t-train")
    assert r["correct"] and r["attempted"] > 3 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert set(r) == {"correct", "attempted", "failed", "metrics", "device"}


def test_serve_driver_closed_loop(copy):
    r = rehearse(copy, "t-closed")
    assert r["correct"] and r["attempted"] > 10 and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_out_tokens_per_s", "itl_p90_ms",
                                 "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_serve_driver_open_loop(copy):
    r = rehearse(copy, "t-open")
    assert r["correct"] and r["attempted"] >= 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_ms", "itl_p90_ms", "setup_s"}


def test_traced_run_reads_the_added_metric(copy, monkeypatch):
    """The traced path on the CPU: spans round the program's calls, the
    profiler started and stopped, every reader called.  The CPU has no device
    plane, so the reduction is that of the recorded TPU trace."""
    recorded = trace_reduce.reduce_file(
        os.path.join(HERE, "fixture_v5e.xplane.pb.gz"))
    monkeypatch.setattr(trace_reduce, "reduce_file", lambda path: recorded)
    r = rehearse(copy, "t-closed", trace=True)
    assert r["correct"]
    assert r["metrics"]["program_spans_count"]["value"] > 10
    assert r["metrics"]["decode_rows_mean"]["value"] > 1
    assert r["metrics"]["serve_compiles_in_window"]["value"] == 0
    assert r["device"]["busy_s"] > 0 and r["device"]["window_s"] > 0
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert "ttft_p90_ms" not in r["metrics"]  # traced: per-layer only


@pytest.mark.parametrize("edit, message", [
    (lambda s: s["workloads"].__setitem__(0, dict(s["workloads"][0],
                                                  traffic="no-such-mix")),
     "traffic mix 'no-such-mix'"),
    (lambda s: s["end_to_end"].append({"name": "no_such_metric",
                                       "unit": "s"}), "no_such_metric"),
])
def test_a_name_that_cannot_be_found_fails_loudly(copy, tmp_path, edit,
                                                  message):
    root = str(tmp_path / "broken")
    shutil.copytree(copy, root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    edit(spec)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with pytest.raises(run.BenchmarkError, match=message):
        run.run_cell(spec["workloads"][0]["name"], 0, 1.0, False,
                     fake_device, root)
    with pytest.raises(run.BenchmarkError, match="not in BENCHMARK.json"):
        run.run_cell("no-such-cell", 0, 1.0, False, fake_device, root)
