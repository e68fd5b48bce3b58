"""Jitted steps: the program's own twin of ``itl_p90_ms``: the ninth decile
of the time from one device step's fetch return to the next one's, each
interval counted once for every token the step that ends it emitted.

The fetch's return is ``t_end - post_ms`` of an ``engine/step`` span that
reached the device; the intervals run between consecutive such spans.  A
row that decodes gets one token a step, so the gap before a token is the
interval its step ends, and pooling the intervals by ``emitted`` pools the
gaps as the client's metric does, but on the engine thread's clock: what
``itl_p90_ms`` reads over this number is the emission and the client, not
the steps.  A pair of steps with a ``broker/idle`` between them is left out
(the server had nothing to run: no row waited through it).  A first token
ends no gap but counts here (``mixed_gap_share_pct`` says how few they
are)."""

from benchmark import stats


def intervals(obs):
    """→ [(milliseconds, emitted of the step that ends it), ...]"""
    steps = sorted((s for s in stats.spans_named(obs, "engine/step")
                    if "post_ms" in s["attrs"]), key=lambda s: s["t_end"])
    fetched = [s["t_end"] - s["attrs"]["post_ms"] / 1e3 for s in steps]
    idle = [s["t_start"] for s in stats.spans_named(obs, "broker/idle")]
    return [((b - a) * 1e3, s["attrs"].get("emitted", 0))
            for a, b, s in zip(fetched, fetched[1:], steps[1:])
            if not any(a <= t < b for t in idle)]


def read(obs):
    return stats.percentile(
        [ms for ms, emitted in intervals(obs) for _ in range(emitted)], 90)
