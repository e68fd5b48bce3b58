"""Jitted steps: share of the device's busy time in what surrounds the
expert GEMMs of a routed FFN: the scopes ``moe_route`` (router, softmax,
top-k), ``moe_dispatch`` (layout, scatter into expert order) and
``moe_combine`` (gather back, weighted sum), in every step program, by the
scope each traced operation carries in the compiled program."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

SCOPES = ("moe_route", "moe_dispatch", "moe_combine")


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"] or not t["scope_s"]:
        return None
    inside = sum(s for k, s in t["scope_s"].items()
                 if k.rsplit("/", 1)[-1] in SCOPES)
    return 100.0 * inside / t["busy_s"]
