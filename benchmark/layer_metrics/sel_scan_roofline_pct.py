"""Kernels: the selective scan's share of its roofline in the mixed steps.
The least time is the larger of its bytes at ``peaks.json``'s HBM rate and
its operations at the bfloat16 peak (``benchmark/selective_flops.py``:
``scan_bytes``, ``scan_flops`` on the rows of two tokens and more that the
steps' spans count: ``ssm_scan_rows``, ``ssm_scan_tokens``); the time taken
is the device time of the kernel ``jit_mixed_step/selective_scan`` in the
traced window.  The table gives no vector-unit peak and the recurrence runs
on the vector unit, so the share reads against HBM in effect.  The steps in
the traced window are counted from the kernel's calls, one a Mamba layer."""

from benchmark import selective_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

KEY = "jit_mixed_step/selective_scan"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if "ssm_scan_tokens" in s["attrs"]]
    if not t or not steps:
        return None
    taken, calls = t["kernel_s"].get(KEY), t["kernel_calls"].get(KEY)
    if not taken or not calls:
        return None
    model, peaks = obs["model"], obs["device"]["peaks"]
    tokens = sum(a["ssm_scan_tokens"] for a in steps) / len(steps)
    rows = sum(a["ssm_scan_rows"] for a in steps) / len(steps)
    least = selective_flops.least_s(
        selective_flops.scan_flops(model, tokens),
        selective_flops.scan_bytes(model, tokens, rows), peaks)
    return 100.0 * calls * least / taken
