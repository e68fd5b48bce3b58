"""Jitted steps: median time from one finished optimizer step to the next,
on the host's clock at the fetch of each step's loss (the barrier)."""

from benchmark import stats


def read(obs):
    t = (obs.get("train") or {}).get("done_times") or []
    return stats.percentile(
        [(b - a) * 1e3 for a, b in zip(t, t[1:])], 50)
