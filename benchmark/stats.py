"""The arithmetic the metric readers share: what the window holds, and
percentiles over it.  ``obs`` is what a driver returns (see the drivers)."""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Mapping, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile; ``None`` over nothing."""
    values = list(values)
    return float(np.percentile(values, q)) if values else None


def in_window(obs: Mapping[str, Any], t: float) -> bool:
    return obs["window"]["t_open"] <= t < obs["window"]["t_close"]


def tokens_in_window(obs: Mapping[str, Any]) -> int:
    """Output tokens that reached a client inside the window."""
    return sum(in_window(obs, t) for r in obs["requests"]
               for t in r["token_times"])


def token_gaps_ms(obs: Mapping[str, Any]) -> List[float]:
    """Gaps between consecutive streamed tokens of one request, at the
    client, pooled over all requests; a gap belongs to the window when the
    later token arrived inside it."""
    return [(b - a) * 1e3 for r in obs["requests"]
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if in_window(obs, b)]


def first_token_ms(obs: Mapping[str, Any]) -> List[float]:
    """Time to first token of every request that was due inside the window,
    from when it was due to the first streamed token at the client.  A
    request that failed, was refused or timed out counts as the worst: the
    longest of everything seen, its own life included."""
    good, bad = [], []
    for r in obs["requests"]:
        if not in_window(obs, r["due"]):
            continue
        if r["status"] == "ok" and r["token_times"]:
            good.append((r["token_times"][0] - r["due"]) * 1e3)
        else:
            bad.append((r["done"] - r["due"]) * 1e3)
    worst = max(good + bad, default=0.0)
    return good + [worst] * len(bad)


def spans_named(obs: Mapping[str, Any], name: str, **attrs: Any
                ) -> List[Dict[str, Any]]:
    """The program's spans of that name that ended inside the window, with
    the given attribute values."""
    return [s for s in obs["spans"] if s["name"] == name
            and all(s["attrs"].get(k) == v for k, v in attrs.items())]


def durations_ms(spans: Iterable[Mapping[str, Any]]) -> List[float]:
    return [(s["t_end"] - s["t_start"]) * 1e3 for s in spans]


def trace_share(obs: Mapping[str, Any], part: str, whole: str
                ) -> Optional[float]:
    """``100 * trace[part] / trace[whole]`` of the reduced trace."""
    t = obs.get("trace")
    if not t or not t.get(whole):
        return None
    return 100.0 * t[part] / t[whole]
