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

Nothing here imports the program.
"""

from __future__ import annotations

import re
from bisect import bisect_right
from collections import defaultdict
from typing import Dict, Iterable, Mapping, Optional

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
