"""Kernels: latent attention's prefill path (``latent_attention_prefill``:
the rows of two tokens and more) against its roofline: a row's picked keys
read once a layer (``latent_keys_prefill`` x 576 values) and the
mathematics' own ``q . k`` and ``p . v`` a picked pair a head
(``dsa_selected_prefill``), ``benchmark/dsa_flops.py``, over the device time
under the scope."""

from benchmark import dsa_flops


def read(obs):
    return dsa_flops.roofline_share(
        obs, ("latent_attention_prefill",), "dsa_selected_prefill",
        "latent_keys_prefill", dsa_flops.attention_flops,
        dsa_flops.attention_bytes)
