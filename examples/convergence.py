"""Convergence sanity run: train a preset to a target loss on real text.

Capability analogue of the reference's model-level sanity tier
(``tests/model/`` — BingBertSquad / Megatron runs that assert a real model
reaches a real loss, not just that kernels are numerically consistent).

Corpus: byte-level LM over the English documentation/license text shipped
inside the installed site-packages (deterministic file order) — real text
with zero network egress, packed into an mmap indexed dataset
(``data_sampling.indexed_dataset``). The loss floor of byte-level English
makes the target meaningful: an untrained model sits at ln(256) ≈ 5.55.

Usage:
    python examples/convergence.py --preset tiny --steps 150 --seq 128 \
        --target 3.5 --out CONVERGENCE.json        # CPU-scale smoke
    python examples/convergence.py --preset gpt2-125m --steps 400 \
        --seq 1024 --target 2.6                    # real-chip tier
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_corpus(seq_len: int, max_bytes: int = 4 << 20,
                 out_dir: str = None) -> "MMapIndexedDataset":
    """Byte-level samples of seq_len+1 from site-packages documentation."""
    from deepspeed_tpu.runtime.data_pipeline.data_sampling import (
        MMapIndexedDataset, MMapIndexedDatasetBuilder)

    out_dir = out_dir or tempfile.mkdtemp(prefix="dstpu_corpus_")
    prefix = os.path.join(out_dir, f"bytes_s{seq_len}")
    if MMapIndexedDataset.exists(prefix):
        return MMapIndexedDataset(prefix)
    roots = [os.path.dirname(os.path.dirname(np.__file__))]
    files = []
    for root in roots:
        for pat in ("**/*.md", "**/*.rst", "**/*.txt"):
            files.extend(glob.glob(os.path.join(root, pat), recursive=True))
    files = sorted(set(files))
    buf = bytearray()
    for f in files:
        if len(buf) >= max_bytes:
            break
        try:
            with open(f, "rb") as fh:
                data = fh.read(max_bytes - len(buf))
        except OSError:
            continue
        # keep printable-ish text only
        buf.extend(bytes(b if 9 <= b < 127 else 32 for b in data))
    if len(buf) < (seq_len + 1) * 64:
        raise RuntimeError(f"corpus too small: {len(buf)} bytes")
    arr = np.frombuffer(bytes(buf), np.uint8)
    b = MMapIndexedDatasetBuilder(prefix, dtype=np.uint8)
    step = seq_len + 1
    for i in range(0, len(arr) - step, step):
        b.add_item(arr[i:i + step])
    b.end_document()
    b.finalize()
    return MMapIndexedDataset(prefix)


def run(preset: str, steps: int, seq: int, target: float,
        micro_batch: int = 2, lr: float = 3e-3, out: str = None,
        log_every: int = 10) -> dict:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.runtime.engine import ModelSpec

    cfg = tfm.get_config(preset, vocab_size=256, max_seq_len=seq)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    spec = ModelSpec(
        params=params,
        loss_fn=lambda p, b, rng: tfm.loss_fn(p, b, cfg),
        param_axes=tfm.param_axes(cfg))
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": micro_batch,
        "optimizer": {"type": "adamw",
                      "params": {"lr": lr, "weight_decay": 0.1}},
        "scheduler": {"type": "WarmupCosineLR",
                      "params": {"total_num_steps": steps,
                                 "warmup_num_steps": max(steps // 20, 5)}},
        "gradient_clipping": 1.0,
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10 ** 9,
    })
    ds = build_corpus(seq)
    order = np.random.default_rng(0).permutation(len(ds))
    bs = engine.train_batch_size
    losses = []
    t0 = time.time()
    for step in range(steps):
        idx = order[(step * bs) % (len(ds) - bs):][:bs]
        x = np.stack([np.asarray(ds[int(i)][:seq], np.int32) for i in idx])
        y = np.stack([np.asarray(ds[int(i)][1:seq + 1], np.int32)
                      for i in idx])
        m = engine.train_batch({"input_ids": x, "labels": y})
        if step % log_every == 0 or step == steps - 1:
            losses.append([step, float(m["loss"])])
            print(f"step {step:4d} loss {losses[-1][1]:.4f}", flush=True)
    result = {
        "preset": preset, "steps": steps, "seq": seq,
        "initial_loss": losses[0][1], "final_loss": losses[-1][1],
        "target": target, "passed": losses[-1][1] <= target,
        "wall_s": round(time.time() - t0, 1),
        "curve": losses,
    }
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", default="tiny")
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--target", type=float, default=3.5)
    p.add_argument("--micro_batch", type=int, default=2)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--out", default=None)
    p.add_argument("--device", default="auto", choices=["auto", "cpu"],
                   help="cpu pins the CPU backend via jax.config, with "
                        "--cpu_devices virtual devices")
    p.add_argument("--cpu_devices", type=int, default=8)
    args = p.parse_args()
    if args.device == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.cpu_devices}").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    r = run(args.preset, args.steps, args.seq, args.target,
            micro_batch=args.micro_batch, lr=args.lr, out=args.out)
    print(json.dumps({k: v for k, v in r.items() if k != "curve"}))
    return 0 if r["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
