"""Kernels: share of the prefill attention kernel's query slots that hold a
token: 100 x the sum of ``tokens`` over the sum of ``attn_q_slots`` on the
program's ``engine/step`` spans of kind ``mixed``.  ``attn_q_slots`` is what
the kernel multiplies that step: each row's chunk rounded up to its query
tiles (a decode row riding in a mixed step costs a tile of 8 for one
token)."""

from benchmark import stats


def read(obs):
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if s["attrs"].get("attn_q_slots")]
    if not steps:
        return None
    return 100.0 * sum(a["tokens"] for a in steps) / sum(
        a["attn_q_slots"] for a in steps)
