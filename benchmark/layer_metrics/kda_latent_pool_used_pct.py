"""Scheduler: how full the latent pool of a model without an indexer is: the
mean over the window's steps of ``blocks_used_latent`` (the program's
``engine/step`` spans) over the pool's size (``engine.v2.num_blocks`` less
the scratch block).  Beside ``state_slots_used_pct``: which of the two
caches bounds the rows admitted."""

from benchmark import stats


def read(obs):
    used = [s["attrs"]["blocks_used_latent"]
            for s in stats.spans_named(obs, "engine/step")
            if "blocks_used_latent" in s["attrs"]] if "spans" in obs else []
    size = (obs.get("engine") or {}).get("v2", {}).get("num_blocks", 0) - 1
    if not used or size <= 0:
        return None
    return 100.0 * sum(used) / len(used) / size
