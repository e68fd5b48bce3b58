"""Device time by kernel name and by named scope, from a profiler trace.

``trace_reduce.reduce`` ranks operations and sums all Pallas kernels
together; a per-kernel metric (``<kernel>_busy_pct``, ``<kernel>_roofline``)
needs the time of one kernel, in one step program.  This file reduces the
same ``trace_reduce.Trace`` by name:

* a **kernel** is a Pallas ``custom-call`` whose instruction carries the
  kernel's own ``name=`` (``grouped_mixed_gemm.12`` → ``grouped_mixed_gemm``);
  its time and its calls are kept per step program (``jit_decode_step``,
  ``jit_mixed_step``: the ``XLA Modules`` event that encloses it);
* a **scope** is a ``jax.named_scope`` of the program.  The trace's events
  are named by their HLO text, which carries no scope; the compiled program's
  text does (``metadata={op_name=".../moe_dispatch/..."}``), so the caller
  hands in ``scope_of``: instruction name → scope, read off the compiled
  step programs by :func:`scopes_of_text`.  An instruction XLA fused from
  several scopes counts under the scope of the metadata it kept.

:func:`whole_steps` gives the same kernel times one EXECUTION of a step
program at a time, for a reader whose count of work differs from step to step
(``latent_moe_flops.grouped_roofline``: the rows a step's router handed its
kernels): only the executions that lie wholly inside the window, each with
the ordinal of the host's fetch that brought its metrics.

Nothing here imports the program.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Iterable, List, Mapping, Optional

from benchmark import trace_reduce

_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')
_SUFFIX = re.compile(r"\.\d+$")


def kernel_name(op_name: str) -> str:
    """``grouped_mixed_gemm.12`` → ``grouped_mixed_gemm``."""
    return _SUFFIX.sub("", op_name)


def scopes_of_text(hlo_text: str, scopes: Iterable[str]) -> Dict[str, str]:
    """Instruction name → the first of ``scopes`` that is a component of its
    ``op_name``, for every instruction of a compiled module's text that has
    one."""
    scopes = tuple(scopes)
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        parts = m.group(2).split("/")
        for s in scopes:
            if s in parts:
                out[m.group(1)] = s
                break
    return out


def reduce(trace: trace_reduce.Trace,
           scope_of: Optional[Mapping[str, Mapping[str, str]]] = None
           ) -> Optional[dict]:
    """Seconds inside the window span, averaged over the chips that ran
    anything: ``kernel_s`` and ``kernel_calls`` keyed ``<program>/<kernel>``,
    ``scope_s`` keyed ``<program>/<scope>`` (``scope_of``: program →
    instruction → scope), and ``busy_s`` as ``trace_reduce.reduce`` counts
    it.  None without a window or a device operation in it."""
    windows = [s for s in trace.host_spans if s.name == trace_reduce.WINDOW]
    if not windows:
        return None
    lo, hi = windows[0].start, windows[0].end
    chips = [c for c, evs in trace.device_ops.items()
             if any(e.end > lo and e.start < hi for e in evs)]
    if not chips or hi <= lo:
        return None
    scope_of = scope_of or {}
    kernel_ns: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    scope_ns: Dict[str, float] = defaultdict(float)
    busy_ns = 0.0
    for chip in chips:
        evs = [e for e in trace.device_ops[chip]
               if e.end > lo and e.start < hi]
        busy_ns += trace_reduce.total(trace_reduce.union(
            trace_reduce.clip(((e.start, e.end) for e in evs), lo, hi)))
        mods = sorted(trace.device_modules.get(chip, ()),
                      key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in evs:
            op = trace_reduce.describe(e.name)
            if op.container:
                continue
            i = bisect_right(starts, e.start) - 1
            mod = (trace_reduce.module_name(mods[i].name)
                   if i >= 0 and mods[i].end >= e.start else "?")
            ns = min(e.end, hi) - max(e.start, lo)
            if op.pallas:
                key = f"{mod}/{kernel_name(op.name)}"
                kernel_ns[key] += ns
                kernel_calls[key] += 1
            scope = scope_of.get(mod, {}).get(op.name)
            if scope is not None:
                scope_ns[f"{mod}/{scope}"] += ns
    n = len(chips)
    return {
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "kernel_calls": {k: v / n for k, v in kernel_calls.items()},
        "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()},
    }


def whole_steps(trace: trace_reduce.Trace, program: str, fetched: str
                ) -> List[dict]:
    """The executions of the step program ``program`` (its ``XLA Modules``
    events) that the trace holds WHOLE, a chip at a time, in order of time:
    inside the window span, and between two other executions of the chip.
    The profiler starts and stops while a step runs, INSIDE the window span
    (it is opened after the one and closed before the other), and the
    execution it cuts is an event like any other, from the first operation
    recorded or to the last (PR 44's traces: 650 ms and 2.25 ms among
    executions of 735); the chip's first and last event are those, and
    nothing else says so.  An execution that is cut is left out with
    everything in it: its kernels are neither called nor timed here, so a
    count of a step's work stands against the time of the same steps.  Each:

    * ``chip``, ``seconds`` (the execution's own);
    * ``kernel_s`` and ``kernel_calls`` of its Pallas kernels, keyed
      ``<program>/<kernel>`` as :func:`reduce` keys the window's;
    * ``fetch``: the ordinal, among the host spans named ``fetched`` that
      began inside the window (in order of time), of the first that ended
      after this execution did: where the host fetches every step's metrics
      in the order of the steps, the fetch that brought THIS step's.  None
      where no such span ends after it, or where two of these executions
      would share one (the host fell a whole step behind: whose counters
      that fetch brought cannot be said from the trace).
    """
    windows = [s for s in trace.host_spans if s.name == trace_reduce.WINDOW]
    if not windows:
        return []
    lo, hi = windows[0].start, windows[0].end
    ends = [s.end for s in sorted(
        (s for s in trace.host_spans
         if s.name == fetched and lo <= s.start < hi), key=lambda s: s.start)]
    out: List[dict] = []
    for chip in sorted(trace.device_modules):
        every = sorted(trace.device_modules[chip], key=lambda m: m.start)
        mods = [m for m in every[1:-1]
                if trace_reduce.module_name(m.name) == program
                and m.start >= lo and m.end <= hi]
        starts = [m.start for m in mods]
        steps = [{"chip": chip, "seconds": (m.end - m.start) / 1e9,
                  "kernel_s": {}, "kernel_calls": {},
                  "fetch": next((i for i, end in enumerate(ends)
                                 if end >= m.end), None)} for m in mods]
        for e in trace.device_ops.get(chip, ()):
            i = bisect_right(starts, e.start) - 1
            if i < 0 or e.end > mods[i].end:
                continue
            op = trace_reduce.describe(e.name)
            if op.pallas:
                key, step = f"{program}/{kernel_name(op.name)}", steps[i]
                step["kernel_s"][key] = (step["kernel_s"].get(key, 0.0)
                                         + (e.end - e.start) / 1e9)
                step["kernel_calls"][key] = \
                    step["kernel_calls"].get(key, 0) + 1
        shared = [s["fetch"] for s in steps]
        for s in steps:
            if shared.count(s["fetch"]) > 1:
                s["fetch"] = None
        out.extend(steps)
    return out
