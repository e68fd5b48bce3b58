"""Kernels: the grouped GEMM's calls (forward, dlhs, drhs) against their
roofline: the rows that were LOCAL (the steps' ``moe_local_rows``; the
layout's padding counts as nothing) x hidden x expert width, the larger of
FLOPs over the bf16 peak and bytes over the HBM rate a call
(``benchmark/latent_moe_flops.py``), over the kernels' device time."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.grouped_roofline(obs)
