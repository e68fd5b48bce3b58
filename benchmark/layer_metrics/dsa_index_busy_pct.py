"""Kernels: share of the device's busy time in the learned selection of keys:
the scopes ``dsa_index_scores`` (every visible key scored against the query's
indexer heads) and ``dsa_topk`` (the ``index_topk`` largest), in every step
program, on the layers that pick."""

from benchmark import dsa_flops

SCOPES = ("dsa_index_scores", "dsa_topk")


def read(obs):
    return dsa_flops.busy_share(obs, SCOPES)
