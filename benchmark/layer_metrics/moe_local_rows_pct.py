"""Scheduler: the share of the routed layers' expert assignments that fell on
an expert THIS CHIP holds: 100 x sum ``moe_assignments_local`` (fetched with
the step's tokens) / sum ``moe_assignments`` of the program's ``engine/step``
spans.  With 16 of 256 experts held and a near-uniform router, 6.25."""

from benchmark import stats


def read(obs):
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step")
             if "moe_assignments_local" in s["attrs"]]
    made = sum(a["moe_assignments"] for a in steps)
    if not made:
        return None
    return 100.0 * sum(a["moe_assignments_local"] for a in steps) / made
