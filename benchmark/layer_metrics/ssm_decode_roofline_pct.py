"""Kernels: the state-space layers' share of their HBM roofline in the pure
decode steps.  The least time a step could take in them is the bytes they
must move (``benchmark/ssm_flops.py``: every running row's state read and
written, ``ssm_state_bytes`` of the steps' spans, and each Mamba layer's
quantized projections read once) at ``peaks.json``'s HBM rate; the time
taken is the device time of ``jit_decode_step``'s ``ssm_*`` scopes and state
update in the traced window.  The steps in the traced window are counted
from the grouped GEMM's calls there (two a MoE layer a step)."""

from benchmark import ssm_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import KERNEL, by_name
from benchmark.layer_metrics.ssm_busy_pct import ssm_seconds

PROGRAM = "jit_decode_step"


def steps_traced(t, model, program):
    """Steps of ``program`` inside the traced window."""
    calls = t["kernel_calls"].get(f"{program}/{KERNEL}", 0)
    return calls / (len(ssm_flops.expert_matrices(model))
                    * ssm_flops.moe_layers(model))


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="decode")
             if "ssm_state_bytes" in s["attrs"]]
    if not t or not steps or not t["scope_s"]:
        return None
    taken = ssm_seconds(t, PROGRAM)
    n = steps_traced(t, obs["model"], PROGRAM)
    if not taken or not n:
        return None
    eng = obs["engine"]
    per_step = (sum(a["ssm_state_bytes"] for a in steps) / len(steps)
                + ssm_flops.mamba_layers(obs["model"])
                * ssm_flops.mamba_projection_bytes(
                    obs["model"], eng["weight_bits"], eng["weight_group"]))
    least_s = n * per_step / obs["device"]["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / taken
