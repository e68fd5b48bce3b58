"""Kernels: share of the device's busy time inside the KDA layers: the
``kda_*`` scopes (in projection, conv, the gate's inputs, the two state
updates ``kda_decode_update`` and ``kda_chunk_scan``, the output gate and
norm, out projection), in every step program, from the traced window's
reduction by kernel and scope name."""

from benchmark import kda_flops

SCOPES = ("kda_in_proj", "kda_conv", "kda_gate_in", "kda_decode_update",
          "kda_chunk_scan", "kda_gate_out", "kda_out_proj")


def read(obs):
    got = kda_flops.traced(obs)
    if got is None:
        return None
    t = got[0]
    inside = kda_flops.scope_seconds(t, SCOPES)
    return 100.0 * inside / t["busy_s"] if inside else None
