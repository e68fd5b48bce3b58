"""Scheduler: median host work a mixed engine step carries (scheduling, the
batch build, eight host-to-device copies, the sampler's enqueue, the
bookkeeping): the step's duration less its ``device_ms``."""

from benchmark import stats
from benchmark.layer_metrics.decode_host_ms_p50 import host_ms


def read(obs):
    return stats.percentile(host_ms(obs, "mixed"), 50)
