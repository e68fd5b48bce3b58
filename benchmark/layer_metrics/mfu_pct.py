"""Jitted steps: model FLOP/s utilisation.  Tokens a step over the median
step time (``train_step_ms_p50``, which the profiler's start and stop inside
the traced window do not move) times the FLOPs a token *requires*
(``benchmark/flops.py``: the matmuls of layers and head times 6, causal
attention at the trained length under the window, nothing recomputed) over
chips times the bf16 peak of ``peaks.json``."""

from benchmark import flops, stats


def read(obs):
    t = obs.get("train") or {}
    done = t.get("done_times") or []
    step_s = stats.percentile([b - a for a, b in zip(done, done[1:])], 50)
    if not step_s:
        return None
    return 100.0 * flops.mfu(t["tokens_per_step"] / step_s,
                             t["flops_per_token"], obs["chips"],
                             obs["device"]["peaks"]["bf16_flops_per_s"])
