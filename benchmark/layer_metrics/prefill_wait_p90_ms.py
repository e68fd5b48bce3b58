"""Scheduler: 90th percentile of the time from a request's admission to its
first token at the broker (the program's ``request/prefill`` spans): its own
chunks, and the other prompts' chunks it shares the mixed steps with."""

from benchmark import stats


def read(obs):
    return stats.percentile(
        stats.durations_ms(stats.spans_named(obs, "request/prefill")), 90)
