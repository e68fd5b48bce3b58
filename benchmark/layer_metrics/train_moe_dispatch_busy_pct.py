"""Jitted steps: share of the device's busy time in what surrounds the expert
GEMMs of a trained routed FFN, forward and backward: the scopes ``moe_route``
(float32 router, softmax, top-k), ``moe_dispatch`` (layout, gather into expert
order) and ``moe_combine`` (weighted scatter-add back), by the scope each
traced operation carries in the compiled train step."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.busy_share(obs, scopes=("moe_route", "moe_dispatch",
                                      "moe_combine"))
