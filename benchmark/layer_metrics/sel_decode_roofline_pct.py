"""Kernels: the selective decode update's share of its HBM roofline: the
bytes its live rows must move (``benchmark/selective_flops.py``: each row of
one token's state read and written, its token's inputs and output) at
``peaks.json``'s HBM rate over the device time of the kernel
``selective_decode_update`` in the traced window, in every step program (a
mixed step's rows of one token go through it beside the chunk's scan).  The
rows of one token a step are read off the steps' spans: the rows that moved
state (``ssm_state_bytes`` over a row's bytes) less the rows the scan walked
(``ssm_scan_rows``)."""

from benchmark import selective_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

KERNEL = "selective_decode_update"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step")
             if "ssm_state_bytes" in s["attrs"]]
    if not t or not steps:
        return None
    taken = sum(s for k, s in t["kernel_s"].items()
                if k.rsplit("/", 1)[-1] == KERNEL)
    calls = sum(n for k, n in t["kernel_calls"].items()
                if k.rsplit("/", 1)[-1] == KERNEL)
    if not taken or not calls:
        return None
    model = obs["model"]
    row = 2 * selective_flops.mamba_layers(model) \
        * selective_flops.state_bytes(model)
    single = sum(a["ssm_state_bytes"] / row - a.get("ssm_scan_rows", 0)
                 for a in steps) / len(steps)
    least = selective_flops.decode_update_bytes(model, single) \
        / obs["device"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * calls * least / taken
