"""Kernels: the summariser (``eva_summarize``, called once a layer in both
step programs) against its roofline: a closed window's K and V read once and
its ``window / chunk`` summaries written, every layer, at the HBM peak
(``eva_windows_closed`` of a mean step, ``benchmark/eva_flops.py``), over the
kernel's device time in the traced steps of both kinds.  The time holds the
calls that found no row to close, which cost a scalar compare a row."""

from benchmark import eva_flops


def read(obs):
    return eva_flops.roofline_share(
        obs, eva_flops.SUMMARIZE, eva_flops.DECODE + eva_flops.MIXED,
        lambda model, peaks, mean: eva_flops.window_bytes(
            model, mean("eva_windows_closed")) / peaks["hbm_bytes_per_s"])
