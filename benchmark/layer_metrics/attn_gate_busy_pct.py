"""Jitted steps: share of the device's busy time under the scope of the gate
on the attention's output (``attn_gate``: its projection of the layer's normed
input, the sigmoid and the product, forward and backward): what the gate
costs beside the attention it gates.  A program without the scope reads
nothing."""

from benchmark import swa_moe_train_flops as sm


def read(obs):
    return sm.busy_share(obs, scopes=("attn_gate",))
