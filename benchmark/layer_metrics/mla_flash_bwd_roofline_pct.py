"""Kernels: the flash kernel's backward at 192 / 128 against the MXU: the
causal pairs' S, dV, dP, dK and dQ products (``benchmark/latent_moe_flops.py``)
over the bf16 peak of ``peaks.json``, over the device time of
``flash_attention_bwd_dkv`` and ``flash_attention_bwd_dq`` together in the
traced window's whole steps (``kernel_time.whole_steps``)."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.flash_roofline(obs, backward=True)
