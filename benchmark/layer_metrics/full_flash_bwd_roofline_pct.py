"""Kernels: the flash kernel's backward on the FULL layer against the MXU:
the causal pairs' S, dV, dP, dK and dQ products
(``benchmark/swa_moe_train_flops.py``) over the bf16 peak of ``peaks.json``,
over the device time of ``flash_attention_bwd_dkv`` and
``flash_attention_bwd_dq`` together in the traced window's whole steps."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.flash_roofline(obs, "full", backward=True)
