"""Kernels: the grouped W8A16 GEMM's share of its HBM roofline in the pure
decode steps of a model with two expert matrices (ungated) whose width is
stored padded: ``moe_gemm_roofline_pct``'s arithmetic with
``benchmark/ssm_flops.py``'s bytes (the codes and scales of the experts that
got a row at the STORED width 1920, the assignments' activations).  At 3
rows an expert over 128 experts the kernel is bound by the codes it
streams."""

from benchmark import ssm_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL, by_name

PROGRAM = "jit_decode_step"


def share(obs, program: str, kind: str, with_mxu: bool):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind=kind)
             if "moe_experts_hit" in s["attrs"]]
    key = f"{program}/{KERNEL}"
    if not t or not steps or not t["kernel_s"].get(key) \
            or "moe_intermediate_size" not in obs["model"]:
        return None
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    eng, peaks = obs["engine"], obs["device"]["peaks"]
    rows = steps[0]["moe_rows"]  # static: the step program's assignments
    least_s = ssm_flops.grouped_gemm_bytes(
        obs["model"], rows, hit, eng["weight_bits"], eng["weight_group"]
    ) / peaks["hbm_bytes_per_s"]
    if with_mxu:
        least_s = max(least_s, ssm_flops.grouped_gemm_flops(
            obs["model"], rows) / peaks["bf16_flops_per_s"])
    layers = t["kernel_calls"][key] / len(ssm_flops.expert_matrices(
        obs["model"]))  # two calls a layer a step
    return 100.0 * layers * least_s / t["kernel_s"][key]


def read(obs):
    return share(obs, PROGRAM, "decode", with_mxu=False)
