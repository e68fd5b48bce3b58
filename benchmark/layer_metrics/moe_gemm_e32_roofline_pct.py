"""Kernels: the grouped W8A16 GEMM's share of its HBM roofline in the pure
decode steps of a layer that holds a SHARE of its experts (32 of 256): the
codes and scales of the held experts that got a row, once, and the LOCAL
assignments' activations at the HBM rate (``benchmark/moe_flops.py`` on the
spans' ``moe_experts_hit`` and ``moe_assignments_local``, a routed layer's
mean), over the device time of ``jit_decode_step/grouped_mixed_gemm``.  At
1.5 rows an expert the kernel is bound by the codes it streams."""

from benchmark import kda_flops, moe_flops
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL

PROGRAM = "jit_decode_step"


def read(obs):
    got = kda_flops.traced(obs)
    steps = [a for a in kda_flops.kda_steps(obs, "decode")
             if a.get("moe_assignments_local") is not None
             and "moe_experts_hit" in a]
    if got is None or not steps:
        return None
    t, model, peaks = got
    key = f"{PROGRAM}/{KERNEL}"
    if not t["kernel_s"].get(key):
        return None
    eng = obs["engine"]
    layers = kda_flops.routed_layers(model)
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    local = sum(a["moe_assignments_local"] for a in steps) / len(steps) \
        / layers
    least_s = moe_flops.grouped_gemm_bytes(
        model, local, hit, eng["weight_bits"], eng["weight_group"]
    ) / peaks["hbm_bytes_per_s"]
    calls = t["kernel_calls"][key] / 3.0  # three GEMMs a layer a step
    return 100.0 * calls * least_s / t["kernel_s"][key]
