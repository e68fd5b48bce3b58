"""Jitted steps: median duration of a pure-decode engine step, a host clock
round a step that ends in the fetch of its tokens (``engine/step`` spans of
kind ``decode``)."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.durations_ms(
        stats.spans_named(obs, "engine/step", kind="decode")), 50)
