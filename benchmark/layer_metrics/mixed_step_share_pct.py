"""Scheduler: share of the window's engine steps that were mixed steps
(prefill chunks, with decode rows riding along), by count."""

from benchmark import stats


def read(obs):
    steps = stats.spans_named(obs, "engine/step")
    if not steps:
        return None
    mixed = sum(s["attrs"].get("kind") == "mixed" for s in steps)
    return 100.0 * mixed / len(steps)
