"""Scheduler: the (query, key) pairs the window's steps attend over after the
selection, as a share of what full attention would: 100 x sum
``dsa_keys_selected`` / sum ``dsa_keys_visible`` of the program's
``engine/step`` spans (counted on the host from the rows' contexts, chunks
and ``index_topk``).  What the learned selection saves, at the traffic's
contexts."""

from benchmark import stats


def latent_steps(obs):
    return [s["attrs"] for s in stats.spans_named(obs, "engine/step")
            if "dsa_keys_visible" in s["attrs"]]


def read(obs):
    steps = latent_steps(obs)
    seen = sum(a["dsa_keys_visible"] for a in steps)
    if not seen:
        return None
    return 100.0 * sum(a["dsa_keys_selected"] for a in steps) / seen
