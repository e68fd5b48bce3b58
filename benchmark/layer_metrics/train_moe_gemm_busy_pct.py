"""Kernels: share of the device's busy time inside the bf16 grouped GEMM of
the held experts, forward (``grouped_matmul``) and backward
(``grouped_matmul_dlhs``, ``grouped_matmul_drhs``), in the train step."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.busy_share(obs, names=lm.GROUPED)
