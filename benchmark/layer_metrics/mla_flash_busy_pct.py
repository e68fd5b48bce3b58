"""Kernels: share of the device's busy time inside the flash attention kernels
(forward, dK/dV and dQ) that latent attention's expanded form runs through at
a query-key width of 192 and a value width of 128, from the traced window's
reduction by kernel name."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.busy_share(obs, names=(lm.FLASH_FWD,) + lm.FLASH_BWD)
