"""Router: the busiest expert's assignments over the mean expert's, mean over
the routed layers and the window's steps, from ``moe_expert_counts`` (all of
the router's experts, held here or not) in the metrics each step's loss is
fetched with: 1 is a balanced router; what the bias rule works against."""


def read(obs):
    c = (obs.get("train") or {}).get("counters") or {}
    return c.get("moe_load_max_over_mean")
