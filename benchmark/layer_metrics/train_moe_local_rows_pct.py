"""Router: the share of a routed layer's assignments that fell on an expert
THIS CHIP holds: 100 x the steps' mean ``moe_local_rows`` (out of the step's
metrics, fetched with the loss) over a replica's tokens x experts per token.  With 8 of
64 held and a near-uniform router, 12.5."""


def read(obs):
    t = obs.get("train") or {}
    c = t.get("counters")
    if not c or "model" not in obs:
        return None
    return 100.0 * c["moe_local_rows"] / (
        t["tokens_per_replica"] * obs["model"]["num_experts_per_tok"])
