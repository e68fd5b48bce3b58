"""Kernels: share of the device's busy time inside Pallas kernels
(``tpu_custom_call`` operations of the trace), all kernels together."""

from benchmark import stats


def read(obs):
    return stats.trace_share(obs, "pallas_s", "busy_s")
