"""Accelerator selection.

Reference: ``accelerator/real_accelerator.py:52 get_accelerator`` — env-var
override (``DS_ACCELERATOR``) plus auto-detection, cached per process.

There is no fallback: a backend that fails to initialise raises, and the CPU
is chosen only when asked for (``DSTPU_ACCELERATOR=cpu`` or
``JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import Accelerator

_accelerator: Optional[Accelerator] = None


def set_accelerator(accel: Accelerator) -> None:
    global _accelerator
    _accelerator = accel


def get_accelerator() -> Accelerator:
    global _accelerator
    if _accelerator is not None:
        return _accelerator

    import jax

    from .tpu_accelerator import CPUAccelerator, TPUAccelerator

    override = os.environ.get("DSTPU_ACCELERATOR", os.environ.get("DS_ACCELERATOR"))
    if override not in (None, "", "cpu", "tpu"):
        raise ValueError(
            f"DSTPU_ACCELERATOR={override!r}: expected 'cpu' or 'tpu'")
    # A backend error (no chip, chip held by another process) propagates.
    backend = override or jax.default_backend()
    if backend == "cpu":
        _accelerator = CPUAccelerator()
    elif backend == "tpu":
        _accelerator = TPUAccelerator()
    else:
        raise RuntimeError(
            f"unsupported JAX backend {backend!r}: deepspeed_tpu runs on "
            f"'tpu', or on 'cpu' when asked (DSTPU_ACCELERATOR=cpu / "
            f"JAX_PLATFORMS=cpu)")
    return _accelerator
