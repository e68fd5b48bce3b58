"""Collectives: share of the traced window, on one chip, in which a
collective operation runs and no other operation does."""

from benchmark import stats


def read(obs):
    return stats.trace_share(obs, "collective_exposed_s", "window_s")
