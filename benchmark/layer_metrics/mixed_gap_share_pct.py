"""Scheduler: share of the window's streamed tokens that a mixed step
emitted: 100 x the sum of ``emitted`` on the program's ``engine/step`` spans
of kind ``mixed`` over the sum of ``emitted`` on all of them.

Why it exists.  ``itl_p90_ms`` is the ninth decile of the gaps between
streamed tokens, pooled, and a gap is as long as the step that ended it.  A
cell's steps are of two lengths (a decode step, 14-35 ms; a mixed step,
42-57 ms), so while more than a tenth of the gaps end in a mixed step the
decile IS a mixed step, and once fewer do it is a decode step: a fall of
30-40 ms that no step got faster for.  This number says how far a cell
stands from that edge at 10 % (PERF.md section 7); ``mixed_step_share_pct``
counts steps, and a mixed step carries fewer decoding rows than a decode
step, so it reads higher than this.

What it counts.  ``emitted`` is every token the step handed to its
requests: the decode rows' next tokens and the first token of each prompt
whose last chunk the step held.  A first token ends no gap, so the share of
*gaps* is a little lower than this: by under a hundredth of the tokens in
the decode cells (one first token a request of about 256), and by up to the
requests' share of the tokens where outputs are short
(``doc-prefill-loaded``: one in about 36).  Steps that emitted nothing (a
chunk in the middle of a prompt with no decode row beside it) count in
neither sum."""

from benchmark import stats


def read(obs):
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step")
             if "emitted" in s["attrs"]]
    total = sum(a["emitted"] for a in steps)
    if not total:
        return None
    mixed = sum(a["emitted"] for a in steps if a.get("kind") == "mixed")
    return 100.0 * mixed / total
