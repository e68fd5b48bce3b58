"""Generic training driver for the example configs.

Usage (single host):
    python examples/train.py --config examples/gpt2_125m_zero1.json --steps 50
Pod launch:
    dstpu --hostfile /job/hostfile examples/train.py -- \
        --config examples/llama3_8b_zero3.json

The JSON files carry BOTH the framework config (everything
``deepspeed_tpu.initialize`` understands) and a ``"model"`` section naming a
preset from ``models/transformer.PRESETS`` with optional overrides — the
five configs mirror BASELINE.md's ladder.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# allow running from a source checkout without installation
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--seq", type=int, default=None)
    p.add_argument("--synthetic-vocab", type=int, default=None)
    args = p.parse_args()

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.runtime.engine import ModelSpec
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    with open(args.config) as f:
        raw = json.load(f)
    model_cfg_dict = raw.pop("model")
    preset = model_cfg_dict.pop("preset")
    seq = args.seq or model_cfg_dict.pop("train_seq_len", 2048)
    tile_size = model_cfg_dict.pop("loss_tile_size", 0)
    cfg = tfm.get_config(preset, **model_cfg_dict)

    print(f"model: {preset} ({cfg.num_params() / 1e6:.0f}M params), seq {seq}")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    if tile_size:
        from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

        def loss_fn(p_, b, r):
            return tiled_loss_fn(p_, b, cfg, tile_size=tile_size)
    else:
        def loss_fn(p_, b, r):
            return tfm.loss_fn(p_, b, cfg)

    spec = ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg))
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=raw)

    rng = np.random.default_rng(0)
    vocab = args.synthetic_vocab or cfg.vocab_size
    batch = {"input_ids": rng.integers(
        0, vocab, size=(engine.train_batch_size, seq)).astype(np.int32)}

    t0 = time.perf_counter()
    for step in range(args.steps):
        metrics = engine.train_batch(batch)
    engine.accelerator.synchronize()
    dt = (time.perf_counter() - t0) / args.steps
    toks = engine.train_batch_size * seq / dt
    print(f"done: loss={metrics['loss']:.4f} step={dt * 1e3:.0f}ms "
          f"tokens/s={toks:.0f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
