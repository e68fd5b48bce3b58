"""Kernels: share of the device's busy time in the shared expert that every
row passes beside the routed ones (scope ``moe_shared``: two ``mixed_gemm``
calls and relu squared), in every step program."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

SCOPE = "moe_shared"


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"] or not t["scope_s"]:
        return None
    inside = sum(s for k, s in t["scope_s"].items()
                 if k.rsplit("/", 1)[-1] == SCOPE)
    return 100.0 * inside / t["busy_s"] if inside else None
