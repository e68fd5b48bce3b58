"""The device's queue on the program's clock (ISSUE 53): when the engine had
called NO step program whose tokens it had not fetched yet, while there was
work, and what its host was in meanwhile.

The program records one ``engine/program`` span a call of a step program,
from the call (the ``t_start`` of its ``engine/dispatch``) to the fetch of
its tokens (the ``t_end`` of its ``engine/wait``); with two steps in flight
two of them overlap.  A program called while none was under way says how
long none had been (``unqueued_ms``, up to its call) and splits that at two
moments the engine thread passed: ``unqueued_post_ms`` (the fetch before to
its step's return), ``unqueued_turn_ms`` (to the next step's entry: the
caller's turn), ``unqueued_pre_ms`` (to the call).  One implementation, for
the seven readers of ``layer_metrics/`` and ``scripts/host_path_by_span.py``:
the unqueued time is ``[t0, t1)`` less the union of the program spans, less
what lies inside a ``broker/idle`` span (nothing to run is no starvation);
each of its gaps is split by the attributes of the program that ends it.

What it cannot see, and leaves out: a program that ends behind ``t1`` is in
no driver's list (``SpanCollector.finish`` hands over the spans that END in
the window), so what follows the last program's end cannot be told from a
program under way: the account ends there (``accounted_s``).  A gap whose
program does not say whose it was (the engine's first call; a call behind a
failed step) counts in the whole and in no part (``unqueued_s`` less the
three parts).  Spans of two engines in one process are not told apart: the
cells run one replica.  A program from before the span gives ``None``.

Imports nothing of the benchmark, so a script can load this file by its path
beside another checkout's package."""

from __future__ import annotations

from bisect import bisect_right
from typing import Any, Dict, Iterable, List, Mapping, Optional, Tuple

PROGRAM = "engine/program"
PARTS = ("post", "turn", "pre")

Interval = Tuple[float, float]


def programs(spans: Iterable[Mapping[str, Any]], **attrs: Any
             ) -> List[Mapping[str, Any]]:
    """The attributes of the programs whose tokens were fetched (one marked
    ``error`` was dropped: it has no fetch), with the given values."""
    return [s["attrs"] for s in spans if s["name"] == PROGRAM
            and not s["attrs"].get("error")
            and all(s["attrs"].get(k) == v for k, v in attrs.items())]


def share_pct(spans, of: Mapping[str, Any], having: Mapping[str, Any]
              ) -> Optional[float]:
    """Of the fetched programs with the attributes ``of``, the share that
    also has ``having``, in per cent; None over none."""
    whole = programs(spans, **of)
    if not whole:
        return None
    return 100.0 * len(programs(spans, **of, **having)) / len(whole)


def _merged(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of the intervals, as disjoint ones in order."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _inside(a: float, b: float, merged: List[Interval]) -> float:
    """Seconds of ``[a, b)`` that lie inside the disjoint ``merged``."""
    total = 0.0
    i = max(0, bisect_right(merged, (a, float("inf"))) - 1)
    while i < len(merged) and merged[i][0] < b:
        total += max(0.0, min(b, merged[i][1]) - max(a, merged[i][0]))
        i += 1
    return total


def unqueued(spans: Iterable[Mapping[str, Any]], t0: float, t1: float
             ) -> Optional[Dict[str, float]]:
    """The account of ``[t0, t1)``, in seconds: ``unqueued_s`` (no program
    called and unfetched, and no ``broker/idle``), its parts ``post_s``,
    ``turn_s``, ``pre_s``, ``nothing_to_run_s`` (the ``broker/idle`` spans),
    ``accounted_s`` (from ``t0`` to the last program's end) and the
    ``programs`` counted; None where no program span touches the interval."""
    spans = list(spans)
    called = [s for s in spans if s["name"] == PROGRAM and s["t_end"] > t0]
    inside = sum(s["t_start"] < t1 for s in called)
    if not inside:
        return None
    end = min(t1, max(s["t_end"] for s in called))

    def cut(a: float, b: float) -> Interval:
        return max(a, t0), min(b, end)

    idle = _merged(cut(s["t_start"], s["t_end"]) for s in spans
                   if s["name"] == "broker/idle")
    busy = _merged([cut(s["t_start"], s["t_end"]) for s in called] + idle)
    out = {"seconds": t1 - t0, "accounted_s": end - t0,
           "programs": float(inside),
           "nothing_to_run_s": sum(b - a for a, b in idle),
           "unqueued_s": (end - t0) - sum(b - a for a, b in busy)}
    out.update({f"{part}_s": 0.0 for part in PARTS})
    for s in called:
        at, attrs = s["t_start"], s["attrs"]
        if not attrs.get("unqueued_ms"):
            continue
        at -= attrs["unqueued_ms"] / 1e3
        for part in PARTS:  # in the order the engine thread passed them
            a, at = at, at + attrs[f"unqueued_{part}_ms"] / 1e3
            a, b = cut(a, at)
            if b > a:
                out[f"{part}_s"] += (b - a) - _inside(a, b, idle)
    return out


def of_window(obs: Mapping[str, Any]) -> Optional[Dict[str, float]]:
    """``unqueued`` over the window of a driver's observations."""
    w = obs["window"]
    return unqueued(obs["spans"], w["t_open"], w["t_close"])


def window_pct(obs: Mapping[str, Any], key: str) -> Optional[float]:
    """One number of the window's account over the window's seconds."""
    account = of_window(obs)
    if account is None:
        return None
    return 100.0 * account[key] / obs["window"]["seconds"]
