"""Kernels: latent attention's decode path (``latent_attention_decode``: the
rows of one token, in the decode step and riding in a mixed step) against
its roofline: ``index_topk`` picked keys of 576 values a row a layer
(``latent_keys_single``) and the mathematics' own ``q . k`` and ``p . v`` a
picked pair a head (``dsa_selected_single``), ``benchmark/dsa_flops.py``,
over the device time under the scope."""

from benchmark import dsa_flops


def read(obs):
    return dsa_flops.roofline_share(
        obs, ("latent_attention_decode",), "dsa_selected_single",
        "latent_keys_single", dsa_flops.attention_flops,
        dsa_flops.attention_bytes)
