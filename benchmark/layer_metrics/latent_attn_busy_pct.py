"""Kernels: share of the device's busy time in latent attention over the
selected keys: the scopes ``latent_attention_prefill`` (rows of two tokens
and more) and ``latent_attention_decode`` (rows of one), in every step
program, on every layer."""

from benchmark import dsa_flops

SCOPES = ("latent_attention_prefill", "latent_attention_decode")


def read(obs):
    return dsa_flops.busy_share(obs, SCOPES)
