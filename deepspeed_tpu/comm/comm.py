"""Communication facade.

Capability analogue of the reference's ``deepspeed/comm/comm.py`` (the
torch.distributed-compatible facade + ``timed_op`` logging wrapper) built on
XLA collectives.  Two tiers:

* **process tier** — multi-host control plane: ``init_distributed`` wraps
  ``jax.distributed.initialize`` (the NCCL/MPI-rendezvous equivalent is the
  coordinator service over DCN); ``barrier``/``broadcast_host_value`` use
  ``jax.experimental.multihost_utils``.

* **device tier** — collectives *by mesh-axis name*, usable inside
  ``jit``/``shard_map``: ``all_reduce → lax.psum``, ``all_gather``,
  ``reduce_scatter → lax.psum_scatter``, ``all_to_all``, ``ppermute``.
  XLA lowers these onto ICI within a slice and DCN across slices.

Every device-tier op reports to the ``CommsLogger`` (reference:
``utils/comms_logging.py`` + ``comm/comm.py:106 timed_op``).  Inside a traced
program wall-clock timing is meaningless, so the logger records op counts and
message volumes at trace time; eager microbenchmarks live in
``profiling/comms_benchmark.py``.
"""

from __future__ import annotations

import os
from enum import Enum
from typing import Any, Dict, Optional, Sequence, Union

from ..utils.logging import logger
from .comms_logger import CommsLogger

_initialized = False
_comms_logger = CommsLogger()


class ReduceOp(Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PROD = "prod"


# ---------------------------------------------------------------------------
# process tier
# ---------------------------------------------------------------------------


def init_distributed(dist_backend: Optional[str] = None,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     auto_mpi_discovery: bool = True,
                     timeout: Optional[int] = None,
                     verbose: bool = True) -> None:
    """Rendezvous.  Reference: ``comm/comm.py:792 init_distributed``.

    Single-process (the common TPU-VM case, and all unit tests): no-op beyond
    marking initialized.  Multi-process: ``jax.distributed.initialize`` using
    explicit args or the standard env vars
    (``COORDINATOR_ADDRESS``/``NUM_PROCESSES``/``PROCESS_ID``; cloud TPU pods
    auto-discover via metadata when no args are given).
    """
    global _initialized
    if _initialized:
        return
    import jax

    coordinator_address = coordinator_address or os.environ.get("COORDINATOR_ADDRESS")
    if num_processes is None and "NUM_PROCESSES" in os.environ:
        num_processes = int(os.environ["NUM_PROCESSES"])
    if process_id is None and "PROCESS_ID" in os.environ:
        process_id = int(os.environ["PROCESS_ID"])
    if process_id is None:
        # launcher-backend rank sources (reference: multinode_runner backends
        # hand rank through their own fabric): Slurm srun, Open MPI, hydra
        # (MPICH/IMPI) — and pdsh, which can only broadcast one command, so
        # rank = index of this host in DSTPU_HOSTS
        for var in ("SLURM_PROCID", "OMPI_COMM_WORLD_RANK", "PMI_RANK"):
            if var in os.environ:
                process_id = int(os.environ[var])
                break
        else:
            if "DSTPU_HOSTS" in os.environ:
                import socket

                names = os.environ["DSTPU_HOSTS"].split(",")
                hostname = socket.gethostname()
                short = hostname.split(".")[0]
                for i, h in enumerate(names):
                    if h in (hostname, short) or h.split(".")[0] == short:
                        process_id = i
                        break
                else:
                    raise RuntimeError(
                        f"cannot derive PROCESS_ID: hostname {hostname!r} "
                        f"matches no entry of DSTPU_HOSTS={names} — use "
                        f"resolvable hostnames in the host list (IPs and ssh "
                        f"aliases cannot be matched) or export PROCESS_ID")

    want_multiprocess = (coordinator_address is not None
                         or os.environ.get("DSTPU_MULTIPROCESS", "0") == "1")
    if want_multiprocess:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        if verbose:
            logger.info(
                f"jax.distributed initialized: process {jax.process_index()}"
                f"/{jax.process_count()}, {jax.local_device_count()} local devices")
    elif verbose:
        logger.info(
            f"single-process distributed context: {jax.device_count()} devices")
    _initialized = True


def is_initialized() -> bool:
    return _initialized


def get_rank() -> int:
    """Process rank.  Note the unit difference from the reference: torch.dist
    has one rank per *device*; JAX has one process per *host* controlling
    ``jax.local_device_count()`` devices.  ``get_rank``/``get_world_size`` are
    both process-level; use ``get_global_device_count`` for chip counts."""
    import jax

    return jax.process_index()


def get_world_size() -> int:
    """Process count (matches ``get_rank`` units)."""
    import jax

    return jax.process_count()


def get_global_device_count() -> int:
    import jax

    return jax.device_count()


def get_local_rank() -> int:
    return int(os.environ.get("LOCAL_RANK", 0))


def get_local_world_size() -> int:
    import jax

    return jax.local_device_count()


def barrier(name: str = "barrier") -> None:
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def broadcast_host_value(value: Any, is_source: Optional[bool] = None) -> Any:
    """Broadcast a host-side pytree from process 0 (reference: broadcast of
    rank-0 state; here via ``multihost_utils.broadcast_one_to_all``)."""
    import jax

    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(value, is_source=is_source)


# ---------------------------------------------------------------------------
# device tier — named-axis collectives (use inside jit / shard_map)
# ---------------------------------------------------------------------------

AxisName = Union[str, Sequence[str]]


def _log(op: str, x, axis: AxisName) -> None:
    if _comms_logger.enabled:
        _comms_logger.record_traced(op, x, axis)


def all_reduce(x, axis_name: AxisName, op: ReduceOp = ReduceOp.SUM):
    """Reference: ``comm/comm.py:645 all_reduce`` → ``lax.psum`` family."""
    import jax.lax as lax

    _log("all_reduce", x, axis_name)
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        out = lax.psum(x, axis_name)
        if op == ReduceOp.AVG:
            out = out / axis_size(axis_name)
        return out
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis_name)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis_name)
    if op == ReduceOp.PROD:
        # no pprod primitive: gather the per-shard values and reduce locally
        # (sign-correct for negatives/zeros, unlike exp∘psum∘log)
        gathered = lax.all_gather(x, axis_name, axis=0, tiled=False)
        import jax.numpy as jnp

        return jnp.prod(gathered, axis=0)
    raise ValueError(f"unsupported reduce op {op}")


def all_gather(x, axis_name: AxisName, axis: int = 0, tiled: bool = True):
    """Reference: ``all_gather_into_tensor`` (comm/comm.py:314)."""
    import jax.lax as lax

    _log("all_gather", x, axis_name)
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: AxisName, scatter_axis: int = 0, tiled: bool = True):
    """Reference: ``reduce_scatter_tensor`` (comm/comm.py:297) → psum_scatter."""
    import jax.lax as lax

    _log("reduce_scatter", x, axis_name)
    return lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=tiled)


def all_to_all(x, axis_name: AxisName, split_axis: int, concat_axis: int, tiled: bool = True):
    """Reference: ``all_to_all_single`` (comm/comm.py:348).  The workhorse of
    Ulysses sequence parallelism and MoE expert dispatch."""
    import jax.lax as lax

    _log("all_to_all", x, axis_name)
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def ppermute(x, axis_name: AxisName, perm: Sequence):
    """Ring/neighbour exchange — pipeline activations, ring attention."""
    import jax.lax as lax

    _log("ppermute", x, axis_name)
    return lax.ppermute(x, axis_name, perm=list(perm))


def axis_index(axis_name: AxisName):
    import jax.lax as lax

    return lax.axis_index(axis_name)


def axis_size(axis_name: AxisName) -> int:
    import math

    from jax.lax import axis_size as _axis_size

    if isinstance(axis_name, str):
        return _axis_size(axis_name)
    return math.prod(_axis_size(a) for a in axis_name)


# ---------------------------------------------------------------------------
# comms logging (reference: comm/comm.py configure/log_summary)
# ---------------------------------------------------------------------------


def configure(enabled: Optional[bool] = None, verbose: Optional[bool] = None,
              prof_all: Optional[bool] = None,
              prof_ops: Optional[Sequence[str]] = None) -> None:
    _comms_logger.configure(enabled=enabled, verbose=verbose, prof_all=prof_all,
                            prof_ops=prof_ops)


def get_comms_logger() -> CommsLogger:
    return _comms_logger


def log_summary() -> str:
    return _comms_logger.log_summary()
