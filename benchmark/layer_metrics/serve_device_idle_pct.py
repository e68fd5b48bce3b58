"""Device: share of the traced window in which no operation ran on the
device, averaged over the chips used."""

from benchmark import stats


def read(obs):
    busy = stats.trace_share(obs, "busy_s", "window_s")
    return None if busy is None else 100.0 - busy
