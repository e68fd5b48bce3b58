"""Kernels: the grouped W8A16 GEMM's share of its roofline in the mixed
steps: ``moe_gemm_roofline_pct``'s arithmetic on ``jit_mixed_step``, where a
step's 512 tokens are 4,096 assignments and the kernel may be bound by the
MXU as well as by the codes it streams.  The least time is the larger of the
bytes (``moe_flops.grouped_gemm_bytes``: codes and scales of the experts
that got a row, the assignments' activations) at the HBM rate and the
operations (``grouped_gemm_flops``) at the bfloat16 peak; the time taken is
the device time of ``jit_mixed_step/grouped_mixed_gemm`` in the traced
window."""

from benchmark import moe_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL, by_name

PROGRAM = "jit_mixed_step"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if "moe_experts_hit" in s["attrs"]]
    key = f"{PROGRAM}/{KERNEL}"
    if not t or not steps or not t["kernel_s"].get(key):
        return None
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    eng, peaks = obs["engine"], obs["device"]["peaks"]
    rows = steps[0]["moe_rows"]  # static: the step program's assignments
    least_s = max(
        moe_flops.grouped_gemm_bytes(obs["model"], rows, hit,
                                     eng["weight_bits"], eng["weight_group"])
        / peaks["hbm_bytes_per_s"],
        moe_flops.grouped_gemm_flops(obs["model"], rows)
        / peaks["bf16_flops_per_s"])
    layers = t["kernel_calls"][key] / len(moe_flops.expert_matrices(
        obs["model"]))  # three calls a layer a step
    return 100.0 * layers * least_s / t["kernel_s"][key]
