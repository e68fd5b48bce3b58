"""Kernels: share of the device's busy time inside the Mamba-1 mixers: the
``sel_*`` scopes (in projection, conv, ``x_proj`` with its three norms and
``dt_proj``, scan, gate, out projection) and the two state updates under
``sel_scan`` (``selective_scan``, ``selective_decode_update``), in every step
program, from the traced window's reduction by kernel and scope name."""

from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

SCOPES = ("sel_in_proj", "sel_conv", "sel_x_proj", "sel_scan", "sel_gate",
          "sel_out_proj", "selective_scan", "selective_decode_update")


def read(obs):
    t = by_name(obs)
    if not t or not t["busy_s"] or not t["scope_s"]:
        return None
    inside = sum(s for key, s in t["scope_s"].items()
                 if key.rsplit("/", 1)[1] in SCOPES)
    return 100.0 * inside / t["busy_s"] if inside else None
