"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

What a trace of this installation holds (looked at by hand, PERF.md): one
plane ``/device:TPU:<n>`` a chip, with the lines ``XLA Modules`` (one event
a program run), ``XLA Ops`` (one event an HLO operation, named by its whole
HLO text, a ``while`` enclosing the operations of its body) and ``Async XLA
Ops``; and one plane ``/host:CPU`` with a line a thread, on which
``jax.profiler.TraceAnnotation`` spans appear under their own names.  Device
and host events share one clock, nanoseconds from the start of the trace.

The traced window is the host span named :data:`WINDOW`, which the drivers
open right after the profiler starts and close right before it stops; the
spans the benchmark wraps round calls into the program all start with
``bench/``.  Nothing here imports the program.
"""

from __future__ import annotations

import dataclasses
import functools
import gzip
import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: operations that only enclose others: their time is their children's
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)")
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
#: an idle gap shorter than this is the device's own turn-round between two
#: operations, not something the host did
MIN_GAP_NS = 2_000
#: outside every ``bench/`` span, a gap at least this long means the loop
#: that drives the device had nothing to run (the broker's idle wait is 5 ms)
NOTHING_RUNNING_NS = 10_000_000

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    """The few lines of a trace this file reads, as plain lists."""
    device_ops: Dict[int, List[Event]]  # chip -> events of "XLA Ops"
    device_modules: Dict[int, List[Event]]  # chip -> events of "XLA Modules"
    host_spans: List[Event]  # every host event named bench/...


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one) with nothing but JAX."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    (ops if line.name == "XLA Ops" else modules)[chip] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench/"):
                        host.append(Event(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return Trace(ops, modules, host)


# -- interval arithmetic -----------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The parts of the (disjoint, sorted) ``a`` that no interval of the
    (disjoint, sorted) ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, cur = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- names -------------------------------------------------------------------

_OP = re.compile(r"^%?(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"[a-z][a-z0-9]*\[[0-9,]*\]")


@dataclasses.dataclass(frozen=True)
class Op:
    """What an ``XLA Ops`` event's name says, worked out once a distinct
    name (a trace repeats a few thousand names some 100,000 times)."""
    name: str  # attn.42
    code: str  # custom-call; "" where the name is not HLO text
    shape: str  # first array of the result, without its layout
    pallas: bool
    collective: bool

    @property
    def container(self) -> bool:
        return self.code in CONTAINERS


@functools.lru_cache(maxsize=None)
def describe(text: str) -> Op:
    """``%attn.42 = (bf16[2,4,512,128]{..}, ..) custom-call(..),
    custom_call_target="tpu_custom_call"`` → ``Op("attn.42", "custom-call",
    "bf16[2,4,512,128]", pallas=True, collective=False)``.  Shapes hold no
    lower-case word before a bracket, so the first `` word(`` is the opcode.
    A name that is not HLO text (some runtimes give the bare name) is its own
    name, with no opcode and no shape."""
    m = _OP.match(text)
    if m is None:
        return Op(text, "", "", False, False)
    code = _OPCODE.search(text, m.end() - 1)
    code = code.group(1) if code else ""
    shape = _SHAPE.search(text, m.end())
    return Op(m.group(1), code, shape.group(0) if shape else "",
              PALLAS_MARK in text,
              bool(COLLECTIVE.match(code) or COLLECTIVE.match(m.group(1))))


def module_name(text: str) -> str:
    """``jit_fwd(16188141146180184629)`` → ``jit_fwd``."""
    return text.split("(", 1)[0]


# -- the reduction -----------------------------------------------------------


def _host_pieces(gap: Interval, spans: List[Event]) -> List[Tuple[str, float]]:
    """Cut an idle gap at the boundaries of the ``bench/`` host spans and
    name each piece by what the host was inside of: the innermost span, and
    for a span that has spans inside it, whether the piece lies before the
    first of them (``:pre``, inputs being prepared), after the last
    (``:post``, the fetch and the bookkeeping) or between."""
    lo, hi = gap
    over = [s for s in spans if s.start < hi and s.end > lo]
    cuts = sorted({lo, hi, *(t for s in over for t in (s.start, s.end)
                             if lo < t < hi)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [s for s in over if s.start <= mid < s.end]
        if not cover:
            name = ("nothing to run" if hi - lo >= NOTHING_RUNNING_NS
                    else "between calls")
        else:
            inner = min(cover, key=lambda s: s.end - s.start)
            name = inner.name
            kids = [s for s in spans if s is not inner
                    and inner.start <= s.start and s.end <= inner.end]
            if kids:
                first = min(k.start for k in kids)
                last = max(k.end for k in kids)
                name += (":pre" if mid < first else
                         ":post" if mid >= last else ":mid")
        out.append((name, b - a))
    return out


def reduce(trace: Trace, top: int = 10) -> Optional[dict]:
    """Busy and idle time, the operations that took most time, the share of
    busy time inside Pallas kernels, the time in which only a collective ran,
    and the idle gaps by what the host was doing.  Seconds, averaged over the
    chips that ran anything.  ``None`` when the trace has no window span or
    no device operation inside it."""
    windows = [s for s in trace.host_spans if s.name == WINDOW]
    if not windows:
        return None
    lo, hi = windows[0].start, windows[0].end
    spans = sorted((s for s in trace.host_spans if s.name != WINDOW),
                   key=lambda s: s.start)
    chips = [c for c, evs in trace.device_ops.items()
             if any(e.end > lo and e.start < hi for e in evs)]
    if not chips or hi <= lo:
        return None
    busy_ns = pallas_ns = collective_ns = exposed_ns = 0.0
    by_op: Dict[str, float] = defaultdict(float)
    by_gap: Dict[str, float] = defaultdict(float)
    longest_gap = 0.0
    for chip in chips:
        evs = [e for e in trace.device_ops[chip]
               if e.end > lo and e.start < hi]
        busy = union(clip(((e.start, e.end) for e in evs), lo, hi))
        busy_ns += total(busy)
        mods = sorted(trace.device_modules.get(chip, ()),
                      key=lambda m: m.start)
        starts = [m.start for m in mods]
        coll, rest = [], []
        for e in evs:
            op = describe(e.name)
            if op.container:
                continue
            a, b = max(e.start, lo), min(e.end, hi)
            i = bisect_right(starts, e.start) - 1
            mod = (module_name(mods[i].name)
                   if i >= 0 and mods[i].end >= e.start else "?")
            label = " ".join(filter(None, (
                f"{mod}/{op.name}", op.code, op.shape,
                "pallas" if op.pallas else "")))
            if op.pallas:
                pallas_ns += b - a
            by_op[label] += b - a
            (coll if op.collective else rest).append((a, b))
        coll_u, rest_u = union(coll), union(rest)
        collective_ns += total(coll_u)
        exposed_ns += total(subtract(coll_u, rest_u))
        for gap in subtract([(lo, hi)], busy):
            if gap[1] - gap[0] < MIN_GAP_NS:
                by_gap["under 2 us (device turn-round)"] += gap[1] - gap[0]
                continue
            longest_gap = max(longest_gap, gap[1] - gap[0])
            for name, ns in _host_pieces(gap, spans):
                by_gap[name] += ns
    n = len(chips)

    def rank(d: Dict[str, float]) -> List[List]:
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "pallas_s": pallas_ns / n / 1e9,
        "collective_s": collective_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "longest_gap_s": longest_gap / 1e9,
        "device_ops": rank(by_op),
        "idle_gaps": rank(by_gap),
    }


def reduce_file(path: str) -> Optional[dict]:
    return reduce(load(path))
