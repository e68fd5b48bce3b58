#!/usr/bin/env python3
"""Read kept traces of ``dsv2lite-train-8k`` again, without a chip: the
roofline shares by PR 42's count and by the count that stands, side by side
on the SAME traces, with what tells them apart.

    python3 benchmark/tools/read_traces.py chiprun_out/traces

Every pair ``<cell>.<seed>.xplane.pb.gz`` / ``.obs.json`` that
``keep_trace.py`` left is reduced by ``kernel_time`` as the driver reduces it
and read by ``latent_moe_flops``' readers; beside each reading stands what
PR 42's reader made of the same trace (:func:`parent_grouped`,
:func:`parent_flash`: every call the window touches, a clipped one too,
credited with the mean of the WHOLE window's counters).  One JSON line a
trace, then a table.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from statistics import mean

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import kernel_time, trace_reduce  # noqa: E402
from benchmark import latent_moe_flops as lm  # noqa: E402
from benchmark.drivers.train_latent_moe import FETCH  # noqa: E402


def parent_grouped(obs):
    """PR 42's ``grouped_roofline``: the window's calls x one call over the
    window's mean rows, over the window's (clipped) kernel time."""
    t, c = lm.by_name(obs), obs["train"]["counters"]
    seconds, calls = lm.kernel_seconds(t, lm.GROUPED)
    flops, nbytes = lm.grouped_call(obs["model"], c["moe_local_rows"],
                                    c["moe_experts_hit"])
    peaks = obs["device"]["peaks"]
    return 100.0 * calls * max(flops / peaks["bf16_flops_per_s"],
                               nbytes / peaks["hbm_bytes_per_s"]) / seconds


def between(obs, own_rows: bool):
    """Two counts between PR 42's and the one that stands, to say which fault
    carried how much: every CALL of the whole steps credited with the
    window's mean rows (the cut executions gone, nothing else), then with its
    own step's rows (a second round's calls still credited whole)."""
    peaks, least, seconds = obs["device"]["peaks"], 0.0, 0.0
    for step in lm.whole_steps(obs):
        c = lm.counters_of(obs, step)
        if c is None:
            continue
        c = c if own_rows else obs["train"]["counters"]
        flops, nbytes = lm.grouped_call(obs["model"], c["moe_local_rows"],
                                        c["moe_experts_hit"])
        s, calls = lm.kernel_seconds(step, lm.GROUPED)
        least += calls * max(flops / peaks["bf16_flops_per_s"],
                             nbytes / peaks["hbm_bytes_per_s"])
        seconds += s
    return 100.0 * least / seconds if seconds else None


def parent_flash(obs, backward):
    t, train = lm.by_name(obs), obs["train"]
    fwd, bwd = lm.flash_call_flops(obs["model"], train["rows"],
                                   train["seq_len"])
    if backward:
        seconds = lm.kernel_seconds(t, lm.FLASH_BWD)[0]
        calls = lm.kernel_seconds(t, lm.FLASH_BWD[1:])[1]
    else:
        seconds, calls = lm.kernel_seconds(t, (lm.FLASH_FWD,))
    return 100.0 * calls * (bwd if backward else fwd) \
        / obs["device"]["peaks"]["bf16_flops_per_s"] / seconds


def clipped_calls(trace, names):
    """Pallas calls of ``names`` that an edge of the window cuts."""
    w = next(s for s in trace.host_spans if s.name == trace_reduce.WINDOW)
    return sum(1 for evs in trace.device_ops.values() for e in evs
               if e.end > w.start and e.start < w.end
               and (e.start < w.start or e.end > w.end)
               and trace_reduce.describe(e.name).pallas
               and kernel_time.kernel_name(
                   trace_reduce.describe(e.name).name) in names)


def read(stem: str) -> dict:
    with open(f"{stem}.obs.json") as f:
        obs = json.load(f)
    trace = trace_reduce.load(f"{stem}.xplane.pb.gz")
    program = next(k.split("/")[0] for k in lm.by_name(obs)["kernel_s"])
    obs["trace"]["by_name"] = kernel_time.reduce(trace)
    obs["trace"]["by_name"]["steps"] = kernel_time.whole_steps(
        trace, program, FETCH)
    steps = lm.whole_steps(obs)
    w = next(s for s in trace.host_spans if s.name == trace_reduce.WINDOW)
    per_step = []
    for s in steps:
        c = lm.counters_of(obs, s) or {}
        seconds, calls = lm.kernel_seconds(s, lm.GROUPED)
        per_step.append({
            "step": None if s["fetch"] is None
            else obs["train"]["traced_from"] + s["fetch"],
            "step_ms": 1e3 * s["seconds"],
            "rows": c.get("moe_local_rows"), "rows_max": c.get("moe_rows_max"),
            "hit": c.get("moe_experts_hit"),
            "calls": [lm.kernel_seconds(s, (n,))[1] for n in lm.GROUPED],
            "rounds": lm.kernel_seconds(s, lm.GROUPED[1:2])[1] / 3,
            "passes": lm.grouped_passes(s),
            "grouped_ms": 1e3 * seconds,
            "call_us": 1e6 * seconds / calls if calls else None})
    mods = [m for ms in trace.device_modules.values() for m in ms
            if trace_reduce.module_name(m.name) == program
            and m.end > w.start and m.start < w.end]
    t = lm.by_name(obs)
    return {
        "trace": os.path.basename(stem),
        "window_s": (w.end - w.start) / 1e9,
        "executions_touched": len(mods), "whole": len(steps),
        "window_steps": obs["train"]["steps"],
        "window_mean": obs["train"]["counters"],
        "window_calls": lm.kernel_seconds(t, lm.GROUPED)[1],
        "window_grouped_ms": 1e3 * lm.kernel_seconds(t, lm.GROUPED)[0],
        "clipped_grouped_calls": clipped_calls(trace, lm.GROUPED),
        "clipped_flash_calls": clipped_calls(
            trace, (lm.FLASH_FWD,) + lm.FLASH_BWD),
        "steps": per_step,
        "grouped_busy_pct": lm.busy_share(obs, names=lm.GROUPED),
        "grouped_parent": parent_grouped(obs),
        "grouped_whole_steps_mean_rows": between(obs, False),
        "grouped_whole_steps_own_rows": between(obs, True),
        "grouped": lm.grouped_roofline(obs),
        "flash_fwd_parent": parent_flash(obs, False),
        "flash_fwd": lm.flash_roofline(obs, False),
        "flash_bwd_parent": parent_flash(obs, True),
        "flash_bwd": lm.flash_roofline(obs, True),
    }


def main(argv=None) -> int:
    directory = (argv or sys.argv[1:])[0]
    rows = [read(p[:-len(".obs.json")]) for p in sorted(
        glob.glob(os.path.join(directory, "*.obs.json")))]
    for r in rows:
        print(json.dumps(r))
    print("| trace | whole steps (of executions touched) | window's mean rows "
          "| traced steps' rows | calls a step (fwd/dlhs/drhs) | rounds "
          "| us a call | clipped calls | PR 42's count | whole steps only "
          "| and their own rows | this count "
          "| flash fwd (42 / now) | flash bwd (42 / now) |")
    print("|" + " --- |" * 14)
    for r in rows:
        st = [s for s in r["steps"] if s["rows"] is not None]
        print(f"| {r['trace']} | {r['whole']} ({r['executions_touched']}) "
              f"| {r['window_mean']['moe_local_rows']:.0f} "
              f"| {', '.join(format(s['rows'], '.0f') for s in st)} "
              f"| {', '.join('/'.join(format(c, '.0f') for c in s['calls']) for s in st)} "
              f"| {', '.join(format(s['rounds'], '.0f') for s in st)} "
              f"| {', '.join(format(s['call_us'], '.0f') for s in st)} "
              f"| {r['clipped_grouped_calls']} "
              f"| {r['grouped_parent']:.2f} "
              f"| {r['grouped_whole_steps_mean_rows']:.2f} "
              f"| {r['grouped_whole_steps_own_rows']:.2f} "
              f"| {r['grouped']:.2f} "
              f"| {r['flash_fwd_parent']:.2f} / {r['flash_fwd']:.2f} "
              f"| {r['flash_bwd_parent']:.2f} / {r['flash_bwd']:.2f} |")
    if rows:
        print("mean of this count:", mean(r["grouped"] for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
