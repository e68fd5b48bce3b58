"""Scheduler: mean time the engine thread spent NOT running inside the host
part of a pure-decode step: ``(pre_ms - pre_cpu_ms) + (post_ms -
post_cpu_ms)`` on the program's ``engine/step`` spans, the wall clock less
the thread's CPU clock from the step's entry to the call of its program and
from the fetch's return to the step's.  ``decode_host_ms_p50`` is the whole
host part, work and waiting; this is the waiting alone: for the interpreter
lock behind the HTTP threads the step before woke, for a lock, or
descheduled (the two clocks cannot tell which).

A mean over the window and not a median: the thread clock of the chip's
host advances in ticks of 10 ms (PERF.md section 6, PR 37), so one step's
CPU time reads 0 or 10 and only the sum over many steps is the thread's CPU
time (the ticks fall where they fall, a thousand steps a window)."""

from benchmark import stats


def wait_ms(obs, kind):
    """Mean over the steps of that kind that reached the device; a program
    whose steps carry no split (one from before it) gives nothing."""
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind=kind)
             if "pre_cpu_ms" in s["attrs"] and "post_cpu_ms" in s["attrs"]]
    if not steps:
        return None
    return sum((a["pre_ms"] - a["pre_cpu_ms"])
               + (a["post_ms"] - a["post_cpu_ms"]) for a in steps) / len(steps)


def read(obs):
    return wait_ms(obs, "decode")
