"""Kernels: share of the device's busy time inside the state-space layers:
the ``ssm_*`` scopes (in projection, conv, scan, gate and norm, out
projection) and the two state updates (``ssm_decode_update``,
``ssd_chunk_scan``: kernels by those names, or scopes while they are XLA
formulations), in every step program, from the traced window's reduction by
kernel and scope name."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
          "ssm_out_proj", "ssm_decode_update", "ssd_chunk_scan")


def ssm_seconds(t, program=None):
    """Device seconds of the state-space layers (of one step program): a
    kernel's call carries its scope, so the scopes hold everything once."""
    return sum(s for key, s in t["scope_s"].items()
               if key.rsplit("/", 1)[1] in SCOPES
               and program in (None, key.rsplit("/", 1)[0]))


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"] or not t["scope_s"]:
        return None
    inside = ssm_seconds(t)
    return 100.0 * inside / t["busy_s"] if inside else None
