"""Scheduler: the keys the window's steps have to read, the rows' windows
and the summaries behind them, as a share of what full attention would read:
100 x sum (``eva_window_keys`` + ``eva_summary_keys``) / sum
``eva_keys_full`` of the program's ``engine/step`` spans (counted on the
host from the rows' positions).  What the mechanism saves, at the traffic's
contexts."""

from benchmark import stats


def eva_steps(obs):
    return [s["attrs"] for s in stats.spans_named(obs, "engine/step")
            if "eva_keys_full" in s["attrs"]]


def read(obs):
    steps = eva_steps(obs)
    full = sum(a["eva_keys_full"] for a in steps)
    if not full:
        return None
    return 100.0 * sum(a["eva_window_keys"] + a["eva_summary_keys"]
                       for a in steps) / full
