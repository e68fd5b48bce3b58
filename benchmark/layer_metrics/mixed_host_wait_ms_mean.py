"""Scheduler: ``decode_host_wait_ms_mean``'s number (see it) over the mixed
steps: the mean time the engine thread was not running inside the host part
of a mixed step, the part of ``mixed_host_ms_p50`` that is waiting."""

from benchmark.layer_metrics.decode_host_wait_ms_mean import wait_ms


def read(obs):
    return wait_ms(obs, "mixed")
