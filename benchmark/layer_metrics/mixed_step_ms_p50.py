"""Jitted steps: median duration of a mixed engine step (``engine/step``
spans of kind ``mixed``): up to 512 tokens of prefill chunks and the decode
rows, the sampler and the fetch."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.durations_ms(
        stats.spans_named(obs, "engine/step", kind="mixed")), 50)
