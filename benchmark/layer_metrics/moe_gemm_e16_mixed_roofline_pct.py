"""Kernels: the grouped W8A16 GEMM's share of its roofline in the mixed steps
of a layer that holds a SHARE of its experts (16 of 256): the codes and
scales of the held experts that got a row, once, and the LOCAL assignments'
activations at the HBM rate, or the local assignments' operations at the
bfloat16 peak, whichever is larger (``benchmark/moe_flops.py`` on the spans'
``moe_experts_hit`` and ``moe_assignments_local``, a routed layer's mean),
over the device time of ``jit_mixed_step/grouped_mixed_gemm``."""

from benchmark import dsa_flops, moe_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL, by_name

PROGRAM = "jit_mixed_step"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if "moe_assignments_local" in s["attrs"]]
    key = f"{PROGRAM}/{KERNEL}"
    if not t or not steps or not t["kernel_s"].get(key):
        return None
    model, eng, peaks = obs["model"], obs["engine"], obs["device"]["peaks"]
    layers = dsa_flops.routed_layers(model)
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    local = sum(a["moe_assignments_local"] for a in steps) / len(steps) \
        / layers
    least_s = max(
        moe_flops.grouped_gemm_bytes(model, local, hit, eng["weight_bits"],
                                     eng["weight_group"])
        / peaks["hbm_bytes_per_s"],
        moe_flops.grouped_gemm_flops(model, local)
        / peaks["bf16_flops_per_s"])
    calls = t["kernel_calls"][key] / 3.0  # three GEMMs a layer a step
    return 100.0 * calls * least_s / t["kernel_s"][key]
