"""Kernels: the flash kernel's forward calls on the WINDOW layers (128 wide,
32 query heads over 4 K/V heads, a band of 2,048 keys) against the MXU: the
pairs IN THE BAND times QK^T and PV (``benchmark/swa_moe_train_flops.py``)
over the bf16 peak of ``peaks.json``, over the device time of
``flash_attention_fwd_band`` in the traced window's whole steps
(``kernel_time.whole_steps``; the rematerialised forward's calls included,
each counted as a call).  A program whose banded calls carry no name of their
own reads nothing."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.flash_roofline(obs, "sliding", backward=False)
