"""Kernels: the grouped W8A16 GEMM's share of its HBM roofline in the pure
decode steps.  The least time the chip could take is the bytes the calls must
read and write (``benchmark/moe_flops.py``: codes and scales of the experts
that got a row, from the steps' ``moe_experts_hit``, and the assignments'
activations) at ``peaks.json``'s HBM rate; the time they took is the device
time of ``jit_decode_step/grouped_mixed_gemm`` in the traced window.  At 4
rows an expert the kernel is bound by the codes it streams, not by the MXU."""

from benchmark import moe_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL, by_name

PROGRAM = "jit_decode_step"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="decode")
             if "moe_experts_hit" in s["attrs"]]
    key = f"{PROGRAM}/{KERNEL}"
    if not t or not steps or not t["kernel_s"].get(key):
        return None
    hit = sum(a["moe_experts_hit"] for a in steps) / len(steps)
    eng = obs["engine"]
    per_layer = moe_flops.grouped_gemm_bytes(
        obs["model"], steps[0]["moe_rows"], hit, eng["weight_bits"],
        eng["weight_group"])
    layers = t["kernel_calls"][key] / len(moe_flops.expert_matrices(
        obs["model"]))  # three calls a layer a step
    least_s = layers * per_layer / obs["device"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / t["kernel_s"][key]
