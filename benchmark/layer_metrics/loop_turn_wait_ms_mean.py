"""Entry points: mean time the serving loop's thread spent NOT running in a
turn that ended in a step: the duration of the program's ``broker/turn``
spans with ``next="step"`` less their ``cpu_ms`` (the thread's CPU clock
over the span).  ``loop_turn_ms_p50`` is the turn, work and waiting.  A mean
for ``decode_host_wait_ms_mean``'s reason: the chip's host ticks its thread
clock at 10 ms, and a turn is one."""

from benchmark import stats


def read(obs):
    turns = [s for s in stats.spans_named(obs, "broker/turn", next="step")
             if "cpu_ms" in s["attrs"]]
    if not turns:
        return None
    return sum(d - s["attrs"]["cpu_ms"] for s, d in zip(
        turns, stats.durations_ms(turns))) / len(turns)
