"""Scheduler: how full the global layers' K/V pool is, the mean over the
window's steps of ``blocks_used_global`` (the program's ``engine/step``
spans: blocks in use after the step) over the pool's size
(``engine.v2.num_blocks`` less the scratch block).  The global layers keep a
whole context, so this pool is what bounds the rows admitted."""

from benchmark.layer_metrics.kv_read_vs_full_pct import window_steps


def pool_share(obs, used_key, size_key):
    steps = window_steps(obs)
    size = (obs.get("engine") or {}).get("v2", {}).get(size_key, 0) - 1
    if not steps or size <= 0:
        return None
    return 100.0 * sum(a[used_key] for a in steps) / len(steps) / size


def read(obs):
    return pool_share(obs, "blocks_used_global", "num_blocks")
