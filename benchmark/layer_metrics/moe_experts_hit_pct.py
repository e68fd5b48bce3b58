"""Scheduler: mean share of a layer's experts that got at least one row in a
pure decode step (``moe_experts_hit`` of the program's ``engine/step`` spans,
the mean over layers, fetched with the step's tokens, over the published
``num_experts``).  Near 100 the step reads every expert's codes; skewed
routing lowers it."""

from benchmark import stats


def read(obs):
    hit = [s["attrs"]["moe_experts_hit"]
           for s in stats.spans_named(obs, "engine/step", kind="decode")
           if "moe_experts_hit" in s["attrs"]]
    if not hit:
        return None
    return 100.0 * sum(hit) / len(hit) / obs["model"]["num_experts"]
