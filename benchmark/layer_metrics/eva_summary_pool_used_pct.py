"""Scheduler: how full the summary pool is: the mean over the window's steps
of ``blocks_used_summary`` (the program's ``engine/step`` spans) over the
pool's size (``engine.v2.num_blocks`` less the scratch block).  It grows by
a window's summaries whenever a row closes one and shrinks only when the
sequence ends."""

from benchmark.layer_metrics.eva_window_pool_used_pct import pool_share


def read(obs):
    return pool_share(obs, "blocks_used_summary", "num_blocks")
