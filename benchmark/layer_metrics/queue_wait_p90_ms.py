"""Entry points: 90th percentile of the time a request waited in the broker's
admission queue, from the program's ``request/queue`` spans."""

from benchmark import stats


def read(obs):
    return stats.percentile(
        stats.durations_ms(stats.spans_named(obs, "request/queue")), 90)
