"""Kernels: the KDA decode update's share of its HBM roofline in the pure
decode steps.  The least time a step could take in it is the bytes it must
move (``benchmark/kda_flops.py``: every running row's state read and written
in every KDA layer, ``kda_state_bytes`` of the steps' spans) at
``peaks.json``'s HBM rate, or its operations at the bfloat16 peak where that
is more; the time taken is the device time under ``kda_decode_update`` (the
kernel of that name) in ``jit_decode_step`` in the traced window."""

from benchmark import kda_flops

PROGRAM = "jit_decode_step"
SCOPES = ("kda_decode_update",)


def read(obs):
    got = kda_flops.traced(obs)
    steps = kda_flops.kda_steps(obs, "decode")
    if got is None or not steps:
        return None
    t, model, peaks = got
    taken = kda_flops.scope_seconds(t, SCOPES, PROGRAM)
    n = kda_flops.steps_traced(t, model, PROGRAM)
    if not taken or not n:
        return None
    state = sum(a["kda_state_bytes"] for a in steps) / len(steps)
    rows = state / (2.0 * kda_flops.state_bytes(model))  # rows x layers
    least_s = max(state / peaks["hbm_bytes_per_s"],
                  kda_flops.decode_update_flops(model, rows)
                  / peaks["bf16_flops_per_s"])
    return 100.0 * n * least_s / taken
