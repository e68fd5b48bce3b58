"""Scheduler: how full the window layers' K/V pool is, the mean over the
window's steps of ``blocks_used_window`` over the pool's size
(``engine.v2.num_window_blocks`` less the scratch block).  A row holds at
most a window and a chunk of it however long its context, and gives blocks
back as it runs."""

from benchmark.layer_metrics.global_pool_used_pct import pool_share


def read(obs):
    return pool_share(obs, "blocks_used_window", "num_window_blocks")
