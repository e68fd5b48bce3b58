"""Scheduler: mean share of the per-sequence state slots that are taken
(``state_slots_used`` of the program's ``engine/step`` spans over the
engine's ``max_seqs``: a slot a row).  Near 100 the batch is as wide as the
state arrays allow; admission then waits for a slot, not for blocks."""

from benchmark import stats


def read(obs):
    used = [s["attrs"]["state_slots_used"]
            for s in stats.spans_named(obs, "engine/step")
            if "state_slots_used" in s["attrs"]]
    if not used:
        return None
    return 100.0 * sum(used) / len(used) / obs["engine"]["v2"]["max_seqs"]
