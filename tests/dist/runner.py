"""Multi-process distributed test runner — the repo's ``DistributedExec``.

Capability analogue of the reference's test harness
(``/root/reference/tests/unit/common.py:139 DistributedExec``), which spawns
N real torch.distributed processes with a file-store rendezvous.  Here each
worker is a real OS process that rendezvouses through
``jax.distributed.initialize`` (local coordinator over TCP, gloo CPU
collectives) — exercising the process tier of ``comm/comm.py``, the
launcher's env contract (``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/
``PROCESS_ID``), and cross-process device arrays, none of which the
in-process 8-virtual-device mesh can reach.

Workers are named functions in ``tests.dist.workers``; each writes a JSON
result file that the parent collects and compares rank-wise.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import tempfile
from typing import Any, Dict, List, Optional

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def free_port() -> int:
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_distributed(worker: str, nprocs: int = 2, local_devices: int = 2,
                    args: Optional[Dict[str, Any]] = None,
                    timeout: float = 420.0) -> List[Dict[str, Any]]:
    """Spawn ``nprocs`` worker processes, each with ``local_devices`` virtual
    CPU devices, rendezvoused via a local coordinator.  Returns the per-rank
    results (rank order).  Raises with the failing ranks' stderr tails on any
    worker failure — a hung worker is killed at ``timeout``."""
    port = free_port()
    outdir = tempfile.mkdtemp(prefix="dstpu_dist_")
    procs = []
    for r in range(nprocs):
        env = dict(
            os.environ,
            # the launcher env contract consumed by comm.init_distributed
            COORDINATOR_ADDRESS=f"localhost:{port}",
            NUM_PROCESSES=str(nprocs),
            PROCESS_ID=str(r),
            XLA_FLAGS=f"--xla_force_host_platform_device_count={local_devices}",
            DSTPU_ACCELERATOR="cpu",
            # persistent compile cache: reruns and the N-1 follower processes
            # skip recompiling the same tiny programs (file store is
            # concurrent-writer safe)
            JAX_COMPILATION_CACHE_DIR=os.path.join(_REPO_ROOT,
                                                   ".jax_cache_tests"),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
        )
        # workers pin the platform themselves, via jax.config
        env.pop("JAX_PLATFORMS", None)
        out_path = os.path.join(outdir, f"rank{r}.json")
        log_path = os.path.join(outdir, f"rank{r}.log")
        log_f = open(log_path, "w")
        p = subprocess.Popen(
            [sys.executable, "-m", "tests.dist.worker_main", worker,
             "--out", out_path, "--args", json.dumps(args or {})],
            cwd=_REPO_ROOT, stdout=log_f, stderr=subprocess.STDOUT, env=env)
        procs.append((r, out_path, log_path, log_f, p))

    failures = []
    try:
        for r, out_path, log_path, log_f, p in procs:
            try:
                rc = p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                failures.append((r, "TIMEOUT (killed)"))
                continue
            if rc != 0:
                failures.append((r, f"rc={rc}"))
    finally:
        for r, _, _, log_f, p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
            log_f.close()

    results: List[Dict[str, Any]] = []
    for r, out_path, log_path, _, p in procs:
        if os.path.exists(out_path):
            with open(out_path) as f:
                res = json.load(f)
            if not res.get("ok"):
                failures.append((r, res.get("error", "worker error")))
            results.append(res)
        else:
            results.append({"ok": False, "rank": r, "error": "no result file"})
    if failures:
        detail = []
        for r, why in failures:
            tail = ""
            log_path = procs[r][2]
            if os.path.exists(log_path):
                with open(log_path) as f:
                    tail = "".join(f.readlines()[-25:])
            detail.append(f"--- rank {r}: {why}\n{tail}")
        raise AssertionError(
            f"distributed worker {worker!r} failed on "
            f"{[r for r, _ in failures]}:\n" + "\n".join(detail))
    return sorted(results, key=lambda x: x["rank"])
