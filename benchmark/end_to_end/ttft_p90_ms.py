"""90th percentile of time to first token, from the moment the request was
due to the first streamed token at the client; a failed or refused request
counts as the worst."""

from benchmark import stats


def read(obs):
    return stats.percentile(stats.first_token_ms(obs), 90)
