"""Router: the largest ``|router_bias|`` over the routed layers at the
window's end, read off the engine's parameters: with
``train_moe_load_max_over_mean``, whether the rule that moves the bias runs
and which way it drifts (seeded at 0.02 x a normal draw, moved by 0.001 a
step)."""


def read(obs):
    c = (obs.get("train") or {}).get("counters") or {}
    return c.get("moe_bias_abs_max")
