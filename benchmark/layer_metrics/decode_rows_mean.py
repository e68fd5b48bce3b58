"""Scheduler: mean number of running rows over the pure-decode steps of the
window (``running`` on the program's ``engine/step`` spans of kind
``decode``)."""

from benchmark import stats


def read(obs):
    rows = [s["attrs"]["running"]
            for s in stats.spans_named(obs, "engine/step", kind="decode")]
    return sum(rows) / len(rows) if rows else None
