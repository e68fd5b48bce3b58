"""Kernels: the flash kernel's backward on the WINDOW layers against the MXU:
the band's pairs times the S, dV, dP, dK and dQ products
(``benchmark/swa_moe_train_flops.py``) over the bf16 peak of ``peaks.json``,
over the device time of ``flash_attention_bwd_dkv_band`` and
``flash_attention_bwd_dq_band`` together in the traced window's whole steps
(``kernel_time.whole_steps``)."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.flash_roofline(obs, "sliding", backward=True)
