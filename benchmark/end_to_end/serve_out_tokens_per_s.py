"""Output tokens that reached the HTTP clients inside the window over its
seconds."""

from benchmark import stats


def read(obs):
    if not obs["requests"]:
        return None
    return stats.tokens_in_window(obs) / obs["window"]["seconds"]
