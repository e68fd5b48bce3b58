"""Scheduler: how full the window pool is: the mean over the window's steps
of ``blocks_used_window`` (the program's ``engine/step`` spans) over the
pool's size (``engine.v2.num_window_blocks`` less the scratch block).  A row
holds its current window's blocks and gives them back whole when it closes:
on average half a window."""

from benchmark.layer_metrics.eva_keys_read_vs_full_pct import eva_steps


def pool_share(obs, used_key, size_key):
    steps = eva_steps(obs)
    size = (obs.get("engine") or {}).get("v2", {}).get(size_key, 0) - 1
    if not steps or size <= 0:
        return None
    return 100.0 * sum(a[used_key] for a in steps) / len(steps) / size


def read(obs):
    return pool_share(obs, "blocks_used_window", "num_window_blocks")
