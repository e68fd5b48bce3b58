"""Scheduler: how full the latent pool is (and with it the indexer's: one
block table): the mean over the window's steps of ``latent_blocks_used`` (the
program's ``engine/step`` spans) over the pool's size
(``engine.v2.num_blocks`` less the scratch block).  The pool is what bounds
the rows admitted at these contexts."""

from benchmark.layer_metrics.dsa_keys_read_vs_full_pct import latent_steps


def read(obs):
    steps = latent_steps(obs)
    size = (obs.get("engine") or {}).get("v2", {}).get("num_blocks", 0) - 1
    if not steps or size <= 0:
        return None
    return 100.0 * sum(a["latent_blocks_used"] for a in steps) / len(steps) \
        / size
