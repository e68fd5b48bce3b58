"""Kernels: share of the device's busy time in EVA attention and its
summariser: the scopes ``eva_attention_decode``, ``eva_attention_prefill``
and ``eva_summarize``, in both step programs, on every layer."""

from benchmark import dsa_flops

SCOPES = ("eva_attention_decode", "eva_attention_prefill", "eva_summarize")


def read(obs):
    if "window_size" not in (obs.get("model") or {}):
        return None
    return dsa_flops.busy_share(obs, SCOPES)
