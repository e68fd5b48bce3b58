"""TPU accelerator (the native platform) and a CPU fallback for tests.

Reference counterpart: ``accelerator/real_accelerator.py`` +
``accelerator/cuda_accelerator.py`` — here the real backend is TPU/XLA.
"""

from __future__ import annotations

from typing import Optional

from .abstract_accelerator import Accelerator

# Peak dense bf16 TFLOPS per chip, for MFU accounting, keyed by a substring
# of ``device_kind`` (source: Google Cloud TPU documentation, per-chip specs).
# A kind that is not listed is an error, never a default.
_TPU_PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,  # trillium
    "v6e": 918.0,
}


class TPUAccelerator(Accelerator):
    _name = "tpu"

    def platform(self) -> str:
        return "tpu"

    def device_count(self) -> int:
        return len(self.devices())

    def global_device_count(self) -> int:
        import jax

        return jax.device_count()

    def communication_backend_name(self) -> str:
        return "xla-ici"

    def supports_dcn(self) -> bool:
        return True

    def is_fp8_supported(self) -> bool:
        # v5p onward have int8/fp8-friendly paths; report conservatively.
        kind = self.device_kind().lower()
        return any(k in kind for k in ("v5p", "v6"))

    def peak_tflops(self, dtype: str = "bfloat16") -> float:
        kind = self.device_kind().lower()
        for key, tflops in _TPU_PEAK_TFLOPS.items():
            if key in kind:
                return tflops * (2.0 if dtype in ("int8", "fp8") else 1.0)
        raise KeyError(
            f"no peak TFLOP/s recorded for device_kind "
            f"{self.device_kind()!r}; add it to _TPU_PEAK_TFLOPS with its "
            f"source")


class CPUAccelerator(Accelerator):
    """Host-CPU backend — used by the unit-test mesh
    (``--xla_force_host_platform_device_count=N``) and by offload targets."""

    _name = "cpu"

    def platform(self) -> str:
        return "cpu"

    def device_count(self) -> int:
        import jax

        return len([d for d in jax.local_devices() if d.platform == "cpu"])

    def global_device_count(self) -> int:
        import jax

        return len([d for d in jax.devices() if d.platform == "cpu"])

    def communication_backend_name(self) -> str:
        return "xla-host"

    def preferred_dtype(self) -> str:
        return "float32"

    def peak_tflops(self, dtype: str = "bfloat16") -> float:
        raise NotImplementedError(
            "the host CPU has no recorded peak: utilisation is a device "
            "metric and is not computed on a CPU run")
