"""Kernels: the chunked KDA form's share of its roofline in the mixed steps.
The least time is the larger of its bytes at the HBM rate and its operations
at the bfloat16 peak (``benchmark/kda_flops.py``: ``scan_bytes``,
``scan_flops`` on the rows of two tokens and more that the steps' spans
count: ``kda_scan_rows``, ``kda_scan_tokens``, ``kda_scan_pieces``); the time
taken is the device time under ``jit_mixed_step/kda_chunk_scan`` (a kernel
of that name, or the scope while it is an XLA formulation) in the traced
window."""

from benchmark import kda_flops

PROGRAM = "jit_mixed_step"
SCOPES = ("kda_chunk_scan",)
COUNTS = ("kda_scan_rows", "kda_scan_tokens", "kda_scan_pieces")


def read(obs):
    got = kda_flops.traced(obs)
    steps = [a for a in kda_flops.kda_steps(obs, "mixed")
             if COUNTS[1] in a]
    if got is None or not steps:
        return None
    t, model, peaks = got
    taken = kda_flops.scope_seconds(t, SCOPES, PROGRAM)
    n = kda_flops.steps_traced(t, model, PROGRAM)
    if not taken or not n:
        return None
    rows, tokens, pieces = (sum(a[k] for a in steps) / len(steps)
                            for k in COUNTS)
    least_s = max(
        kda_flops.scan_bytes(model, tokens, rows) / peaks["hbm_bytes_per_s"],
        kda_flops.scan_flops(model, tokens, pieces)
        / peaks["bf16_flops_per_s"])
    return 100.0 * n * kda_flops.kda_layers(model) * least_s / taken
