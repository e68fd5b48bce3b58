"""Kernels: the chunked scan's share of its roofline in the mixed steps.  The
least time is the larger of its bytes at the HBM rate and its operations at
the bfloat16 peak (``benchmark/ssm_flops.py``: ``scan_bytes``, ``scan_flops``
on the rows of two tokens and more that the steps' spans count:
``ssm_scan_rows``, ``ssm_scan_tokens``, ``ssm_scan_pieces``); the time taken
is the device time of ``jit_mixed_step/ssd_chunk_scan`` (a kernel of that
name, or the scope while it is an XLA formulation) in the traced window."""

from benchmark import ssm_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import by_name
from benchmark.layer_metrics.ssm_decode_roofline_pct import steps_traced

PROGRAM = "jit_mixed_step"
NAME = "ssd_chunk_scan"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if "ssm_scan_tokens" in s["attrs"]]
    if not t or not steps:
        return None
    key = f"{PROGRAM}/{NAME}"
    taken = t["scope_s"].get(key) or t["kernel_s"].get(key)
    n = steps_traced(t, obs["model"], PROGRAM)
    if not taken or not n:
        return None
    model, peaks = obs["model"], obs["device"]["peaks"]
    mean = {k: sum(a[k] for a in steps) / len(steps)
            for k in ("ssm_scan_rows", "ssm_scan_tokens", "ssm_scan_pieces")}
    least_s = max(
        ssm_flops.scan_bytes(model, mean["ssm_scan_tokens"],
                             mean["ssm_scan_rows"])
        / peaks["hbm_bytes_per_s"],
        ssm_flops.scan_flops(model, mean["ssm_scan_tokens"],
                             mean["ssm_scan_pieces"])
        / peaks["bf16_flops_per_s"])
    return 100.0 * n * ssm_flops.mamba_layers(model) * least_s / taken
