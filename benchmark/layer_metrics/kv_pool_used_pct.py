"""Scheduler: how full the K/V pool of the attention layers is: the mean over
the window's steps of ``kv_blocks_used`` (the program's ``engine/step``
spans) over the pool's size (``engine.v2.num_blocks`` less the scratch
block).  With 1 KB of K/V a token the pool is sized for every row at the
longest context; what is used says how far the traffic's contexts fill it."""

from benchmark import stats


def read(obs):
    used = [s["attrs"]["kv_blocks_used"]
            for s in stats.spans_named(obs, "engine/step")
            if "kv_blocks_used" in s["attrs"]]
    size = (obs.get("engine") or {}).get("v2", {}).get("num_blocks", 0) - 1
    if not used or size <= 0:
        return None
    return 100.0 * sum(used) / len(used) / size
