"""Scheduler: share of the grouped layout's rows that are padding, over the
pure decode steps of the window: 1 - sum ``moe_rows`` / sum
``moe_rows_padded`` of the program's ``engine/step`` spans (both static, of
the step program that ran: assignments, and the rows the tile-aligned layout
lays them out on)."""

from benchmark import stats


def read(obs):
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="decode")
             if "moe_rows_padded" in s["attrs"]]
    padded = sum(a["moe_rows_padded"] for a in steps)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(a["moe_rows"] for a in steps) / padded)
