"""Eager collective micro-benchmarks.

Capability analogue of the reference's comms benchmark suite (referred from
``benchmarks/README.md`` to DeepSpeedExamples' comm benchmarks) + the timed
half of ``CommsLogger``: run each collective at a sweep of sizes across the
mesh, record wall-clock + algorithmic/bus bandwidth into the shared logger.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..comm import comm as dcomm
from ..parallel.topology import MeshTopology


def bench_fn(fn, *args, steps: int = 10, warmup: int = 2) -> float:
    """Shared timing loop for the profiling suite: warmup (includes
    compile), then mean wall-time over ``steps`` with a trailing
    block_until_ready."""
    out = None
    for _ in range(max(1, warmup)):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def _bench_op(op_name: str, fn, x, n_iters: int = 10) -> float:
    return bench_fn(fn, x, steps=n_iters)


def run_comms_benchmark(topo: MeshTopology, axis: str = "dp",
                        sizes_mb: Sequence[float] = (1, 4, 16, 64),
                        n_iters: int = 10,
                        dtype=jnp.bfloat16) -> List[Dict]:
    """Benchmark all_reduce / all_gather / reduce_scatter / all_to_all over
    ``axis``.  Returns one record per (op, size) and feeds the CommsLogger's
    timed sink (algbw = payload/time, busbw per the standard ring formulas)."""
    mesh = topo.mesh
    n = topo.size(axis)
    logger = dcomm.get_comms_logger()
    results = []
    if n <= 1:
        return results

    for mb in sizes_mb:
        elems = int(mb * 2**20 / jnp.dtype(dtype).itemsize)
        # divisible by n (sharding), n*n (all_to_all reshape) and 128 (lanes)
        quantum = n * n * 128
        elems = max(quantum, elems // quantum * quantum)
        x = jnp.ones((elems,), dtype)

        ops = {
            "all_reduce": (
                shard_map(lambda v: jax.lax.psum(v, axis), mesh=mesh,
                          in_specs=P(None), out_specs=P(None), check_vma=False),
                2.0 * (n - 1) / n),
            "all_gather": (
                shard_map(lambda v: jax.lax.all_gather(v, axis, tiled=True),
                          mesh=mesh, in_specs=P(axis), out_specs=P(None),
                          check_vma=False),
                (n - 1) / n),
            "reduce_scatter": (
                shard_map(lambda v: jax.lax.psum_scatter(v, axis, tiled=True),
                          mesh=mesh, in_specs=P(None), out_specs=P(axis),
                          check_vma=False),
                (n - 1) / n),
            "all_to_all": (
                shard_map(lambda v: jax.lax.all_to_all(
                    v.reshape(n, -1), axis, split_axis=0, concat_axis=0,
                    tiled=False).reshape(-1),
                    mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                    check_vma=False),
                (n - 1) / n),
        }
        for name, (fn, bus_factor) in ops.items():
            dt = _bench_op(name, jax.jit(fn), x, n_iters)
            nbytes = x.nbytes
            algbw = nbytes / dt / 1e9
            rec = {"op": name, "axis": axis, "size_mb": round(nbytes / 2**20, 2),
                   "time_ms": round(dt * 1e3, 3), "algbw_GBps": round(algbw, 2),
                   "busbw_GBps": round(algbw * bus_factor, 2)}
            logger.record_timed(f"{name}@{axis}", nbytes, dt)
            results.append(rec)
    return results
