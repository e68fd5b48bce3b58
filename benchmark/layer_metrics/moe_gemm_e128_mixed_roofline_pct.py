"""Kernels: the grouped W8A16 GEMM's share of its roofline in the mixed steps
of a model with two expert matrices whose width is stored padded
(``moe_gemm_e128_roofline_pct``'s arithmetic on ``jit_mixed_step``): a step's
512 tokens are 3,072 assignments over 128 experts, and the least time is the
larger of the bytes at the HBM rate and the operations, at the published
width, at the bfloat16 peak."""

from benchmark.layer_metrics.moe_gemm_e128_roofline_pct import share

PROGRAM = "jit_mixed_step"


def read(obs):
    return share(obs, PROGRAM, "mixed", with_mxu=True)
