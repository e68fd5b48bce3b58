"""Environment / compatibility report (``dstpu_report``).

Capability analogue of the reference's ``ds_report`` (``env_report.py:188``):
prints platform, device inventory, memory, and the op compatibility matrix.
"""

from __future__ import annotations

import shutil
import sys


def main() -> int:
    import jax

    from . import __version__
    from .accelerator import get_accelerator
    from .ops.op_registry import available_ops, _ensure_builtin_ops, _REGISTRY

    accel = get_accelerator()
    print("-" * 60)
    print(f"deepspeed_tpu {__version__} environment report")
    print("-" * 60)
    print(f"jax version ............ {jax.__version__}")
    print(f"default backend ........ {jax.default_backend()}")
    print(f"platform ............... {accel.platform()}")
    print(f"device kind ............ {accel.device_kind()}")
    print(f"local devices .......... {accel.device_count()}")
    print(f"global devices ......... {accel.global_device_count()}")
    print(f"process count .......... {jax.process_count()}")
    if accel.platform() == "tpu":
        print(f"peak bf16 TFLOPS/chip .. {accel.peak_tflops():.0f}")
    mem = accel.total_memory()
    if mem:
        print(f"HBM per chip ........... {mem / 2**30:.1f} GiB")
    print(f"g++ .................... {shutil.which('g++') or 'NOT FOUND'}")
    print("-" * 60)
    print("op compatibility:")
    _ensure_builtin_ops()
    avail = available_ops()
    for name, entry in sorted(_REGISTRY.items()):
        ok = "[OK]  " if name in avail else "[MISS]"
        print(f"  {ok} {name:<18} {entry.description}")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
