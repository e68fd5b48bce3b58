"""Scheduler: how full the mixed steps are: 100 x the mean of ``tokens`` over
``budget`` on the program's ``engine/step`` spans of kind ``mixed`` that ran
the device (those with ``device_ms``; a step that scheduled nothing costs no
chunk).  A mixed step costs its whole chunk (``max_tokens_per_step``)
whatever it holds, so this is the share of that cost spent on tokens
somebody asked for."""

from benchmark import stats


def read(obs):
    fills = [s["attrs"]["tokens"] / s["attrs"]["budget"]
             for s in stats.spans_named(obs, "engine/step", kind="mixed")
             if "device_ms" in s["attrs"] and s["attrs"].get("budget")]
    return 100.0 * sum(fills) / len(fills) if fills else None
