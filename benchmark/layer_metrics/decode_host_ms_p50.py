"""Scheduler: median host work a pure-decode engine step carries: the step's
duration less the time the device was presumed busy in it (``device_ms`` on
the program's ``engine/step`` spans: first enqueue to the token fetch).  What
is left is scheduling, the host-to-device copies and the bookkeeping."""

from benchmark import stats


def host_ms(obs, kind):
    """Per step of that kind; a program that records no ``device_ms`` (one
    from before the sub-spans) gives nothing."""
    steps = [s for s in stats.spans_named(obs, "engine/step", kind=kind)
             if "device_ms" in s["attrs"]]
    return [d - s["attrs"]["device_ms"]
            for s, d in zip(steps, stats.durations_ms(steps))]


def read(obs):
    return stats.percentile(host_ms(obs, "decode"), 50)
