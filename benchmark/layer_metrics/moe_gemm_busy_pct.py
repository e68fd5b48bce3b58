"""Kernels: share of the device's busy time inside the grouped W8A16 GEMM of
the routed experts (the Pallas kernel named ``grouped_mixed_gemm``, in every
step program), from the traced window's reduction by kernel name."""

KERNEL = "grouped_mixed_gemm"


def by_name(obs):
    """The traced run's reduction by kernel and scope name, or None (a
    program or driver from before it)."""
    return (obs.get("trace") or {}).get("by_name")


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"]:
        return None
    inside = [s for k, s in t["kernel_s"].items()
              if k.rsplit("/", 1)[-1] == KERNEL]
    return 100.0 * sum(inside) / t["busy_s"] if inside else None
