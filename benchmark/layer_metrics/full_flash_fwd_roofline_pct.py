"""Kernels: the flash kernel's forward calls on the FULL layer (128 wide,
grouped queries, the whole causal triangle of the trained length) against the
MXU: the causal pairs' QK^T and PV (``benchmark/swa_moe_train_flops.py``) over
the bf16 peak of ``peaks.json``, over the device time of
``flash_attention_fwd`` in the traced window's whole steps
(``kernel_time.whole_steps``; the rematerialised forward's calls included)."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.flash_roofline(obs, "full", backward=False)
