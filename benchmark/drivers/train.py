"""Driver ``train``: optimizer steps back to back through
``deepspeed_tpu.initialize`` → ``engine.train_batch``, on every chip the cell
has.

The builder is a copy of ``chip_smoke.py:build_trainer`` with two changes:
the parameters are made under ``zero.Init`` (a jit with the engine's own
shardings as ``out_shardings``), so that a sharded model is never whole on one
chip, and the caller's copy is dropped once the engine has taken its own.

Traffic (``loop: steps``): a fresh batch of seeded uniform tokens every step,
through ``engine.place_batch``.  The host keeps at most ``in_flight`` steps
ahead of the device: each iteration ends in a device-to-host fetch of the loss
of the step dispatched ``in_flight`` iterations earlier, which is also the
barrier the step times are taken on.  The window ends in the fetch of the last
step's loss.
"""

from __future__ import annotations

import gc
import time
from collections import deque
from typing import Any, Callable, Dict, Mapping

import numpy as np

from benchmark import common, flops
from benchmark.reference import dense_decoder as reference


def make_batch(seed: int, step: int, rows: int, seq_len: int, vocab: int
               ) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng([seed, step])
    return {"input_ids": rng.integers(0, vocab, size=(rows, seq_len),
                                      dtype=np.int32)}


def build(config: Mapping[str, Any], seed: int):
    """→ (program config, published sizes as run, params on the mesh, the
    ``ModelSpec`` that holds them, the engine's config dict, the topology)."""
    import jax

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.runtime.config import load_config
    from deepspeed_tpu.runtime.engine import ModelSpec
    from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

    topology.reset_topology()
    cfg, model = common.program_config(config)
    ds = config["engine"]["deepspeed"]
    ds_cfg = load_config(ds)
    stage = ds_cfg.zero_optimization.stage
    topo = MeshTopology.from_config(ds_cfg.mesh)
    with zero.Init(topo, stage=stage) as init:
        params = init.init_sharded(lambda k: tfm.init_params(k, cfg),
                                   tfm.param_axes(cfg),
                                   jax.random.PRNGKey(seed))
    tile = config["engine"]["loss_tile"]

    def loss_fn(p, b, r):
        return tiled_loss_fn(p, b, cfg, tile_size=tile)

    spec = ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg))
    return cfg, model, params, spec, ds, topo


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] != "steps":
        raise ValueError(f"driver train runs loop 'steps', not "
                         f"{traffic['loop']!r}")
    import jax

    import deepspeed_tpu

    compiles = common.start_jax(log)

    cfg, model, params, spec, ds, topo = build(config, seed)
    jax.block_until_ready(params)
    seq_len = traffic["seq_len"]
    log(f"{config['name']}: {cfg.num_layers} layers, "
        f"{cfg.num_params() / 1e9:.3f} B parameters, made on the device")

    # the reference's loss on the first batch, before the engine's state
    # fills the chips: same parameters, float32, a layer at a time
    rows = (ds["train_micro_batch_size_per_gpu"]
            * ds.get("gradient_accumulation_steps", 1) * topo.dp_world_size)
    first = make_batch(seed, 0, rows, seq_len, cfg.vocab_size)
    t0 = time.monotonic()
    ref_loss = reference.next_token_loss(params, model, first["input_ids"])
    log(f"reference loss {ref_loss:.6f} ({time.monotonic() - t0:.1f}s)")

    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=ds,
                                               topo=topo)
    spec.params = None  # the caller's copy goes; the engine has its own
    del params
    gc.collect()
    if engine.train_batch_size != rows:
        raise ValueError(f"batch rows {rows} != {engine.train_batch_size}")
    tokens_per_step = rows * seq_len

    # warm-up: the first step compiles, the second must not
    losses = [float(engine.train_batch(engine.place_batch(first))["loss"])]
    log(f"first step done, loss {losses[0]:.6f}")
    for i in range(1, 1 + traffic["warmup_steps"]):
        losses.append(float(engine.train_batch(engine.place_batch(
            make_batch(seed, i, rows, seq_len, cfg.vocab_size)))["loss"]))
    step0 = len(losses)

    session = common.TraceSession(log) if trace else None
    train_batch, place_batch = engine.train_batch, engine.place_batch
    if trace:
        train_batch = common.annotated(train_batch, "bench/train_batch")
        place_batch = common.annotated(place_batch, "bench/place_batch")
    in_flight = traffic["in_flight"]
    pending: deque = deque()
    done_times = []
    trace_from = traffic["trace_after_s"] if session else float("inf")
    trace_to = float("inf")  # set when the profiler starts
    t_open = time.monotonic()
    setup_s = t_open - t_ready
    log(f"window opens; set-up {setup_s:.1f}s")
    step = step0
    while True:
        now = time.monotonic() - t_open
        if now >= trace_from:  # between two steps, on this thread
            session.start()
            trace_from, trace_to = float("inf"), now + traffic["trace_seconds"]
        elif now >= trace_to:
            session.stop()
            trace_to = float("inf")
        out = train_batch(place_batch(
            make_batch(seed, step, rows, seq_len, cfg.vocab_size)))
        pending.append(out)
        step += 1
        if len(pending) > in_flight:
            with jax.profiler.TraceAnnotation("bench/fetch_loss"):
                losses.append(float(pending.popleft()["loss"]))
            done_times.append(time.monotonic())
        if time.monotonic() - t_open >= seconds:
            break
    while pending:  # the window ends in the fetch of the last step's loss
        losses.append(float(pending.popleft()["loss"]))
        done_times.append(time.monotonic())
    t_close = time.monotonic()
    if trace_to != float("inf"):  # the window was shorter than the trace
        session.stop()
    steps = step - step0
    peak = common.memory_peak_bytes()
    programs = engine._train_step._cache_size()
    log(f"window closed: {steps} steps in {t_close - t_open:.2f}s, "
        f"peak {peak / 1e9:.2f} GB, {programs} train program(s)")

    # correct: the engine's first-step loss is the reference's on the same
    # parameters and batch; every loss is finite; one compiled train program.
    # The tolerance is the traffic file's (3e-4 relative at the real size,
    # 0.003 of a loss near 10.9).  The engine computes in bf16 with float32
    # accumulation, the reference in float32: on the chip the two differed by
    # 6.5e-7 to 4.6e-5 in 29 runs (PERF.md, PR 23), so 3e-4 is six times the
    # worst seen.  A wrong mask, a missing layer or a shift off by one moves
    # the loss by 5e-3 or more at these sizes.
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    finite = bool(np.isfinite(losses).all())
    correct = rel < traffic["loss_rel_tol"] and finite and programs == 1
    log(f"first-step loss {losses[0]:.6f} against reference {ref_loss:.6f}: "
        f"relative difference {rel:.2e} (allowed {traffic['loss_rel_tol']}), "
        f"finite {finite}")

    return {
        "correct": correct,
        "attempted": steps,
        "failed": 0 if finite else int((~np.isfinite(losses)).sum()),
        "setup_s": setup_s,
        "window": {"t_open": t_open, "t_close": t_close,
                   "seconds": t_close - t_open},
        "train": {"steps": steps, "tokens_per_step": tokens_per_step,
                  "done_times": done_times, "in_flight": in_flight,
                  "flops_per_token": flops.train_flops_per_token(
                      model, seq_len)},
        "compiles_in_window": compiles.between(t_open, t_close),
        "memory_peak_bytes": peak,
        "device": dict(device),
        "chips": cell["chips"],
        "trace": session.reduce() if session else None,
        "spans": [], "requests": [],
    }
