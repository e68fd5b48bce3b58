"""Kernels: the indexer's scoring (``dsa_index_scores``) against its
roofline: the indexer keys of every row's visible context read once a step a
picking layer, and ``index_n_heads`` dot products of ``index_head_dim`` a
scored pair (``benchmark/dsa_flops.py`` on the spans' ``dsa_index_keys`` and
``dsa_index_pairs``), over the device time under the scope."""

from benchmark import dsa_flops


def read(obs):
    return dsa_flops.roofline_share(
        obs, ("dsa_index_scores",), "dsa_index_pairs", "dsa_index_keys",
        dsa_flops.index_flops, dsa_flops.index_bytes)
