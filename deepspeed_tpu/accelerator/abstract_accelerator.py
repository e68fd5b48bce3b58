"""Accelerator abstraction.

Capability analogue of the reference's ``accelerator/abstract_accelerator.py``
(``DeepSpeedAccelerator``, ~80 abstract methods): one interface the whole
runtime is written against.  On JAX the device model is simpler (no streams/
events — XLA handles async dispatch), so the surface is the meaningful subset:
device identity/count, memory stats, synchronization, RNG, dtype support,
communication-backend name, and the named-op registry (the op-builder role).
"""

from __future__ import annotations

import abc
import functools
from typing import Any, Dict, List, Optional


@functools.lru_cache(maxsize=None)
def _sentinel_fn(device):
    """Cached per-device jitted no-op whose fetched result drains the queue."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda: jnp.zeros((), jnp.int32),
                   out_shardings=jax.sharding.SingleDeviceSharding(device))


class Accelerator(abc.ABC):
    """One instance per process; see ``real_accelerator.get_accelerator()``."""

    _name: str = "abstract"

    # --- identity -----------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    @abc.abstractmethod
    def platform(self) -> str:
        """jax platform string: 'tpu' | 'cpu' | 'gpu'."""

    @abc.abstractmethod
    def device_count(self) -> int:
        """Local (process-visible) device count."""

    @abc.abstractmethod
    def global_device_count(self) -> int:
        ...

    def is_available(self) -> bool:
        return self.device_count() > 0

    # --- devices ------------------------------------------------------
    def devices(self) -> List[Any]:
        import jax

        return [d for d in jax.local_devices() if d.platform == self.platform()]

    def current_device(self) -> Any:
        return self.devices()[0]

    # --- sync / memory ------------------------------------------------
    def synchronize(self, device_index: Optional[int] = None) -> None:
        import jax

        # effects_barrier only awaits *effectful* computations; draining all
        # in-flight work (the cudaDeviceSynchronize analogue) needs PJRT's
        # per-device synchronize_all_activity.  An invalid device_index must
        # fail loudly, so only the missing-method case falls back.
        devs = jax.local_devices()
        if device_index is not None:
            devs = [devs[device_index]]
        try:
            for d in devs:
                d.synchronize_all_activity()
        except (AttributeError, NotImplementedError):
            jax.effects_barrier()
        # A device→host fetch of a sentinel computation enqueued last drains
        # the (in-order) compute stream even where the synchronize call acks
        # early.  A failed fetch is a failed device: it raises.
        for d in devs:
            jax.device_get(_sentinel_fn(d)())

    def memory_stats(self, device_index: int = 0) -> Dict[str, int]:
        """Allocator statistics as the backend reports them.  The CPU backend
        reports none (``memory_stats()`` is ``None`` there) → ``{}``; any
        error from the backend propagates."""
        return dict(self.devices()[device_index].memory_stats() or {})

    def memory_allocated(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("peak_bytes_in_use", 0)

    def total_memory(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index: int = 0) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def empty_cache(self) -> None:  # XLA manages memory; parity no-op
        pass

    # --- RNG ----------------------------------------------------------
    def default_rng(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    # --- dtype support ------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def is_fp8_supported(self) -> bool:
        return False

    def preferred_dtype(self) -> str:
        return "bfloat16"

    # --- comm ---------------------------------------------------------
    @abc.abstractmethod
    def communication_backend_name(self) -> str:
        """'ici' for intra-slice XLA collectives, 'gloo'-like cpu ring, etc."""

    def supports_dcn(self) -> bool:
        return False

    # --- ops (op-builder role) ----------------------------------------
    def create_op_builder(self, op_name: str):
        from ..ops.op_registry import get_op_builder

        return get_op_builder(op_name, self.platform())

    # --- misc ---------------------------------------------------------
    def range_push(self, name: str):
        import jax

        return jax.named_scope(name)

    def range_pop(self) -> None:
        pass

    def device_kind(self) -> str:
        return self.devices()[0].device_kind

    @abc.abstractmethod
    def peak_tflops(self, dtype: str = "bfloat16") -> float:
        """Per-chip peak for MFU accounting; a device without a recorded
        peak raises."""
