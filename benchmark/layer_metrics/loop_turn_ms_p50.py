"""Entry points: median of the broker loop's turn, the host work between one
engine step's return and the next one's call (token hand-off to the HTTP
threads, gauges, cancels, admission): the program's ``broker/turn`` spans
that ended in a step, not in an idle wait."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.durations_ms(
        stats.spans_named(obs, "broker/turn", next="step")), 50)
