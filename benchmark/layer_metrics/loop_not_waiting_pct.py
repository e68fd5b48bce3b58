"""Scheduler: share of the whole window in which the serving loop's thread
was not between a step's first enqueue and its token fetch: 100 x (1 - the
sum of ``device_ms`` over the window's ``engine/step`` spans / the window's
seconds).  A host-loop metric from host clocks, with no profiler: the time
the loop spends scheduling, copying, bookkeeping, handing tokens over and
idling.  It is a lower bound on the device's idle share, not that share: it
cannot see the device idle inside dispatch-to-fetch (the launch, the fetch's
tail, gaps between programs); ``serve_device_idle_pct`` is the device's own
trace.  It holds while the loop keeps one step in flight, so that the
intervals do not overlap."""

from benchmark import stats


def read(obs):
    waiting_ms = [s["attrs"]["device_ms"]
                  for s in stats.spans_named(obs, "engine/step")
                  if "device_ms" in s["attrs"]]
    if not waiting_ms:
        return None
    return 100.0 * (1.0 - sum(waiting_ms) / 1e3 / obs["window"]["seconds"])
