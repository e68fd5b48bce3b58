"""Scheduler: the K/V blocks the window's steps had to read, as a share of
what full attention on every layer would have read: 100 x sum
``kv_blocks_read`` / sum ``kv_blocks_full`` of the program's ``engine/step``
spans (a model with sliding-window layers counts both on the host, from the
rows' context lengths, chunks and the layer pattern).  What the window layers
save, at the traffic's contexts."""

from benchmark import stats


def window_steps(obs):
    """The ``engine/step`` spans of a model with window layers that ran the
    device; none from a program or a model without the counters."""
    return [s["attrs"] for s in stats.spans_named(obs, "engine/step")
            if "kv_blocks_full" in s["attrs"]]


def read(obs):
    steps = window_steps(obs)
    full = sum(a["kv_blocks_full"] for a in steps)
    if not full:
        return None
    return 100.0 * sum(a["kv_blocks_read"] for a in steps) / full
