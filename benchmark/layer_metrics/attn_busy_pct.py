"""Kernels: share of the device's busy time inside the two paged attention
kernels (the Pallas kernels named ``paged_attention_decode`` and
``paged_attention_prefill``, in every step program), from the traced
window's reduction by kernel name."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

KERNELS = ("paged_attention_decode", "paged_attention_prefill")


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"]:
        return None
    inside = [s for k, s in t["kernel_s"].items()
              if k.rsplit("/", 1)[-1] in KERNELS]
    return 100.0 * sum(inside) / t["busy_s"] if inside else None
