"""Where the Pallas kernels run.

One switch between Mosaic and the Pallas interpreter, so a test steers one
place (``monkeypatch.setattr(backend, "interpret", lambda: False)``), and one
warning for a kernel that gives way to an XLA reference on a TPU.  Callers
reach both through the module (``backend.interpret()``), never by importing
the names.
"""

from __future__ import annotations

import jax

from ...utils.logging import warning_once


def interpret() -> bool:
    """True on the host CPU: kernels run in the Pallas interpreter there."""
    return jax.default_backend() == "cpu"


def warn_fallback(kernel: str, why: str) -> None:
    """Log, once per (kernel, reason), that ``kernel`` gave way to its XLA
    reference on a device that could have run it."""
    if not interpret():
        warning_once(
            f"{kernel}: falling back to the XLA reference path: {why}")
