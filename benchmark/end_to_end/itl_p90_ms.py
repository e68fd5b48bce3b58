"""90th percentile of the gaps between consecutive streamed tokens at the
client, pooled over all requests of the window."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.token_gaps_ms(obs), 90)
