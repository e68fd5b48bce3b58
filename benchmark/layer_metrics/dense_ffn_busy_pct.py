"""Kernels: share of the device's busy time inside the dense gated FFN that
follows every mixer (the scope ``dense_ffn``), in every step program, from
the traced window's reduction by scope name."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"] or not t["scope_s"]:
        return None
    inside = sum(s for key, s in t["scope_s"].items()
                 if key.rsplit("/", 1)[1] == "dense_ffn")
    return 100.0 * inside / t["busy_s"] if inside else None
