"""Kernels: EVA attention's decode kernel (``eva_attention_decode``, the
decode step's one query a row) against its roofline: the keys and values a
row has to read (``eva_window_keys + eva_summary_keys``, 2 x 4096 values of 2
bytes each) at the HBM peak, ``benchmark/eva_flops.py``, over the kernel's
device time in the traced decode steps."""

from benchmark import eva_flops


def read(obs):
    return eva_flops.roofline_share(
        obs, "eva_attention_decode", eva_flops.DECODE,
        lambda model, peaks, mean: eva_flops.key_bytes(
            model, mean("eva_window_keys") + mean("eva_summary_keys"))
        / peaks["hbm_bytes_per_s"])
