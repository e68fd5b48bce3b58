"""Jitted steps: share of the device's busy time in the shared experts every
token passes beside the routed ones (scope ``moe_shared``: one SwiGLU of 2 x
1,408), forward and backward."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.busy_share(obs, scopes=("moe_shared",))
