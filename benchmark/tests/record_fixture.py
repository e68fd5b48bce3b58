#!/usr/bin/env python3
"""How ``fixture_v5e.xplane.pb.gz`` was recorded (on a TPU v5e, PR 23):

    python benchmark/tests/record_fixture.py chiprun_out/fixture_v5e.xplane.pb.gz

Three optimizer steps of a one-layer model (hidden 256, two heads of 128,
sequences of 256, so flash attention runs as a Pallas kernel), each followed
by a fetch and a sleep of 2 ms, inside the benchmark's window span.  The
expected numbers in ``test_trace_reduce.py`` were read from this file by hand
(PERF.md, PR 23).
"""

import glob
import gzip
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(out: str) -> None:
    import jax
    import numpy as np

    import deepspeed_tpu
    from benchmark import common
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.runtime.engine import ModelSpec

    cfg = tfm.get_config("mistral-7b", num_layers=1, hidden_size=256,
                         intermediate_size=512, num_heads=2, num_kv_heads=1,
                         vocab_size=512, param_dtype="bfloat16",
                         sliding_window=128)
    params = jax.jit(lambda k: tfm.init_params(k, cfg))(jax.random.PRNGKey(0))
    spec = ModelSpec(loss_fn=lambda p, b, r: tfm.loss_fn(p, b, cfg),
                     params=params, param_axes=tfm.param_axes(cfg))
    engine, *_ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 10 ** 6})
    rng = np.random.default_rng(0)

    def batch():
        return {"input_ids": rng.integers(
            0, cfg.vocab_size, size=(2, 256)).astype(np.int32)}

    for _ in range(2):
        float(engine.train_batch(batch())["loss"])
    session = common.TraceSession(print)
    session.start()
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench/train_batch"):
            o = engine.train_batch(engine.place_batch(batch()))
        with jax.profiler.TraceAnnotation("bench/fetch_loss"):
            float(o["loss"])
        time.sleep(0.002)
    session.stop()
    pb = glob.glob(os.path.join(session.dir, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    with open(pb, "rb") as f, gzip.open(out, "wb", 9) as g:
        g.write(f.read())
    print(f"{out}: {os.path.getsize(pb)} bytes raw, "
          f"{os.path.getsize(out)} gzipped")
    print(session.reduce())


if __name__ == "__main__":
    main(sys.argv[1])
