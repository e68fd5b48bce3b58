"""Kernels: share of the device's busy time inside the six flash attention
kernels of a stack of window and full layers (forward, dK/dV and dQ, each
with and without ``_band``), from the traced window's reduction by kernel
name."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.busy_share(obs, names=sm.ALL_FLASH)
