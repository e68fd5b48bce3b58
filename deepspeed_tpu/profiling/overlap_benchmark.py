"""Overlap/fusion evidence benchmarks.

Three of this framework's parity rows are "by design" claims — Domino-style
TP comm/compute overlap (``deepspeed/runtime/domino``), DeepCompile
(``deepspeed/compile``), SuperOffload's host-offload overlap — delegated to
XLA's latency-hiding scheduler, fusion passes, and async dispatch. A claim
delegated to a compiler must be *measured*, not asserted; this module is the
measurement (the round-1 review's "assert it with a profile" item).

* :func:`tp_overlap_report` — times a TP-sharded Megatron MLP chain three
  ways (full step, compute-only, collectives-only). Overlap efficiency =
  fraction of the cheaper leg that XLA's scheduler hid behind the other.
* :func:`offload_overlap_report` — times optimizer steps with the host
  offload's async write-behind on vs. blocked (``OffloadedOptimizer``
  drains its swap queue every step), the SuperOffload dataflow evidence.
* :func:`fusion_report` — compiles a function and reports jaxpr-ops →
  HLO-instruction/fusion counts + buffer sizes: the DeepCompile-role
  evidence that the whole step lowers to one fused program.

Run as ``python -m deepspeed_tpu.profiling.overlap_benchmark`` on a pod (or
a virtual mesh for plumbing checks) to print a JSON report.
"""

from __future__ import annotations

import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.topology import get_topology
from .comms_benchmark import bench_fn as _time_it


def tp_overlap_report(hidden: int = 1024, layers: int = 8, batch: int = 8,
                      seq: int = 512, steps: int = 10,
                      dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Megatron MLP chain on the tp axis: col-parallel in, row-parallel out,
    psum per layer. Compares the real step against its two decomposed legs.
    """
    topo = get_topology()
    tp = topo.size("tp")
    H, F = hidden, hidden * 4
    key = jax.random.PRNGKey(0)
    # GLOBAL weight shapes; the shard_map in_specs slice F over tp so each
    # device holds the Megatron F/tp partition
    w1 = jax.random.normal(key, (layers, H, F), dtype) / np.sqrt(H)
    w2 = jax.random.normal(key, (layers, F, H), dtype) / np.sqrt(F)
    x = jax.random.normal(key, (batch, seq, H), dtype)

    def chain(x, w1, w2, comm: bool, compute: bool):
        def layer(h, w):
            a, b = w
            if compute:
                y = jax.nn.gelu(h @ a) @ b
            else:
                y = jnp.broadcast_to(h[..., :1], h.shape[:-1] + (b.shape[-1],))
            if comm:
                y = lax.psum(y, "tp")
            return y.astype(h.dtype), None

        out, _ = lax.scan(layer, x, (w1, w2))
        return out

    def run(comm, compute):
        f = shard_map(
            lambda x, w1, w2: chain(x, w1, w2, comm, compute),
            mesh=topo.mesh,
            in_specs=(P(), P(None, None, "tp"), P(None, "tp", None)),
            out_specs=P(), check_vma=False)
        return _time_it(jax.jit(f), x, w1, w2, steps=steps)

    t_full = run(comm=True, compute=True)
    t_compute = run(comm=False, compute=True)
    t_comm = run(comm=True, compute=False)
    hidden_leg = min(t_compute, t_comm)
    overlap = 0.0
    if hidden_leg > 0:
        overlap = max(0.0, min(1.0, (t_compute + t_comm - t_full) / hidden_leg))
    return {"tp": tp, "t_full_ms": t_full * 1e3,
            "t_compute_ms": t_compute * 1e3, "t_comm_ms": t_comm * 1e3,
            "overlap_efficiency": overlap}


def offload_overlap_report(param_mb: float = 32.0, steps: int = 6,
                           swap_dir: Optional[str] = None) -> Dict[str, Any]:
    """Write-behind NVMe paging vs. drained-every-step optimizer offload.

    The async path's win is the device/host computing step N while step
    N-1's optimizer moments page out through the AIO library —
    SuperOffload's dataflow and ZeRO-Infinity's pipeline_write. Blocking
    mode waits the AIO queue empty after every step.
    """
    import optax

    from ..runtime.config import OffloadOptimizerConfig
    from ..runtime.zero.offload import OffloadedOptimizer

    n = int(param_mb * 1e6 / 4)
    params = {"w": jnp.zeros((n,), jnp.float32)}
    grads = {"w": jnp.ones((n,), jnp.float32)}
    swap_dir = swap_dir or "/tmp/dstpu_overlap_bench"

    def run(blocking: bool) -> float:
        # separate dir per mode: the async run's trailing writes must never
        # land inside the blocking run's timed region
        opt = OffloadedOptimizer(
            optax.adam(1e-3), params,
            OffloadOptimizerConfig(
                device="nvme",
                nvme_path=f"{swap_dir}/{'block' if blocking else 'async'}"))

        def one_step():
            out = opt.step(grads)
            if blocking:
                opt.drain()  # defeat the write-behind on purpose
            jax.block_until_ready(out)
            return out

        t = _time_it(one_step, steps=steps, warmup=1)
        opt.drain()  # drain in-flight writes before teardown
        return t

    t_async = run(blocking=False)
    t_block = run(blocking=True)
    return {"param_mb": param_mb, "t_async_ms": t_async * 1e3,
            "t_blocking_ms": t_block * 1e3,
            "speedup": t_block / t_async if t_async > 0 else 1.0}


def dpu_overlap_report(steps: int = 8, num_layers: int = 2,
                       hidden: int = 256) -> Dict[str, Any]:
    """Delayed-parameter-update overlap: step time of the offloaded engine
    with ``delayed_update`` on vs. off.

    With DPU the device computes batch N's gradients while the host applies
    batch N-1's update — wall-clock ≈ max(device, host) instead of their sum
    (reference: superoffload_stage3.py / pipelined_optimizer_swapper.py:52).
    On a CPU-only test mesh device and host share cores, so the ratio ~1;
    on TPU this measures the real overlap win.
    """
    import deepspeed_tpu
    from ..models import transformer as tfm
    from ..runtime.engine import ModelSpec

    def build(delayed: bool):
        cfg = tfm.get_config("tiny", num_layers=num_layers,
                             hidden_size=hidden, intermediate_size=2 * hidden)
        params = tfm.init_params(jax.random.PRNGKey(0), cfg)
        spec = ModelSpec(
            loss_fn=lambda p, b, r: tfm.loss_fn(p, b, cfg), params=params,
            param_axes=tfm.param_axes(cfg))
        engine, *_ = deepspeed_tpu.initialize(model=spec, config={
            "train_micro_batch_size_per_gpu": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"offload_optimizer": {
                "device": "cpu", "delayed_update": delayed}},
            "steps_per_print": 10_000,
        })
        return engine

    def time_engine(engine) -> float:
        batch = {"input_ids": np.zeros(
            (engine.train_batch_size, 64), np.int32)}
        engine.train_batch(batch)  # compile
        import time as _t

        t0 = _t.perf_counter()
        for _ in range(steps):
            engine.train_batch(batch)
        engine.flush_delayed_update()
        jax.block_until_ready(engine.state.params)
        return (_t.perf_counter() - t0) / steps

    t_serial = time_engine(build(delayed=False))
    t_dpu = time_engine(build(delayed=True))
    return {"t_serial_ms": t_serial * 1e3, "t_dpu_ms": t_dpu * 1e3,
            "speedup": t_serial / t_dpu if t_dpu > 0 else 1.0}


def fusion_report(fn: Callable, *args,
                  static_argnums=()) -> Dict[str, Any]:
    """jaxpr-ops → compiled-HLO shape of a function: instruction count,
    fusion count, and buffer sizes. Low instructions-per-jaxpr-op and high
    fusion share = the compiler is doing DeepCompile's job."""
    jaxpr = jax.make_jaxpr(fn, static_argnums=static_argnums)(*args)
    n_eqns = len(jaxpr.eqns)
    compiled = jax.jit(fn, static_argnums=static_argnums).lower(*args).compile()
    hlo = compiled.as_text()
    lines = [ln.strip() for ln in hlo.splitlines()]
    n_instr = sum(1 for ln in lines if " = " in ln)
    n_fusion = sum(1 for ln in lines if " = " in ln and "fusion(" in ln)
    report = {"jaxpr_eqns": n_eqns, "hlo_instructions": n_instr,
              "hlo_fusions": n_fusion}
    ma = compiled.memory_analysis()
    if ma is not None:
        report["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        report["argument_bytes"] = int(getattr(ma, "argument_size_in_bytes", 0))
    return report


def default_fusion_subject() -> Dict[str, Any]:
    """A realistic train-step subject for the fusion report: tiny llama-style
    model, loss + grads in one program."""
    from ..models import transformer as tfm

    cfg = tfm.get_config("tiny", num_layers=2, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"input_ids": np.zeros((2, 32), np.int32)}

    def step(p):
        return jax.grad(lambda p_: tfm.loss_fn(p_, batch, cfg)[0])(p)

    return fusion_report(step, params)


def main() -> int:
    from ..parallel.topology import MeshTopology, set_topology
    from ..runtime.config import MeshConfig

    try:
        get_topology()
    except RuntimeError:  # standalone CLI: tp over every visible device
        set_topology(MeshTopology.from_config(
            MeshConfig(tensor_parallel_size=len(jax.devices()))))
    report = {
        "tp_overlap": tp_overlap_report(),
        "offload_overlap": offload_overlap_report(),
        "dpu_overlap": dpu_overlap_report(),
        "train_step_fusion": default_fusion_subject(),
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
    }
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
