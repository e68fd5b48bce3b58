"""Entry points: 90th percentile of the time from a request's first token at
the broker to its first SSE chunk flushed by the HTTP thread (the program's
``request/first_write`` spans, one a streamed request)."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.durations_ms(
        stats.spans_named(obs, "request/first_write")), 90)
