"""Kernels: EVA attention's prefill kernel (``eva_attention_prefill``, the
mixed step's chunks and the decode rows riding with them) against its
roofline: the (query, key) pairs under the one softmax (``eva_query_keys``)
at ``q . k`` and ``p . v`` a head (32 heads x 4 x 128 FLOPs a pair) and the
MXU's bfloat16 peak, ``benchmark/eva_flops.py``, over the kernel's device
time in the traced mixed steps."""

from benchmark import eva_flops


def read(obs):
    return eva_flops.roofline_share(
        obs, "eva_attention_prefill", eva_flops.MIXED,
        lambda model, peaks, mean: eva_flops.pair_flops(
            model, mean("eva_query_keys")) / peaks["bf16_flops_per_s"])
