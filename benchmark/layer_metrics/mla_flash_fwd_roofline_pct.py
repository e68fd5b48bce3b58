"""Kernels: the flash kernel's forward calls at 192 / 128 against the MXU:
the causal pairs' QK^T and PV (``benchmark/latent_moe_flops.py``) over the
bf16 peak of ``peaks.json``, over the device time of ``flash_attention_fwd``
in the traced window's whole steps (``kernel_time.whole_steps``; the
rematerialised forward's calls included, each counted as a call)."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.flash_roofline(obs, backward=False)
