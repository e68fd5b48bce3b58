"""Scheduler: share of the window in which the device had no program to run
while there WAS work, on the program's own clock: 100 x (the sum of
``pre_ms`` and of ``post_ms`` over the ``engine/step`` spans that reached
the device + the durations of the ``broker/turn`` spans that ended
``next="step"``) / the window's seconds.  With one step in flight the
device has nothing from a fetch's return to the next program's call, and
both moments are the engine thread's; a turn that ended in an idle wait is
left out, so the time with nothing to run is not in it.
``serve_device_idle_pct`` (the device's trace) less this and less the
nothing-to-run share is what neither host clock sees: the launch and the
fetch's tail."""

from benchmark import stats


def read(obs):
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step")
             if "pre_ms" in s["attrs"] and "post_ms" in s["attrs"]]
    if not steps:
        return None
    turns = stats.durations_ms(
        stats.spans_named(obs, "broker/turn", next="step"))
    starved_ms = sum(a["pre_ms"] + a["post_ms"] for a in steps) + sum(turns)
    return 100.0 * starved_ms / 1e3 / obs["window"]["seconds"]
