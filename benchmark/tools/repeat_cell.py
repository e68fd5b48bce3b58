#!/usr/bin/env python3
"""One cell run several times in one call, each run a process of its own as
the driver's are, and what tells a stalled run from a steady one kept.

    python3 benchmark/tools/repeat_cell.py --out chiprun_out/repeat \\
        --workload glm52-ctx8k-sat --seconds 50 --seeds 11,11,12,13

A run is ``run.run_cell`` untraced, in a child of this script (which never
touches JAX), with two taps that change nothing the window times: what the
driver observed is read once the run is over, and a thread that only sleeps
20 ms at a time notes every sleep that overran by 50 ms or more (the whole
process off the CPU: PERF.md, PR 42's review round).  For a serving cell the
summary lays beside each run's metrics the tokens that reached the clients
second by second and the longest silences (no token to ANY client); for a
training cell the longest step-to-step gaps.  One JSON line a run goes to
``<out>/<workload>.runs.jsonl``, the logs to ``<out>/<workload>.<n>.log``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from statistics import median, quantiles

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def silences(times, t0: float, t1: float, least: float = 0.25):
    """The gaps of ``least`` seconds or more between consecutive events of
    ``times`` inside ``[t0, t1]``, longest first: [[offset, seconds], ...]."""
    ts = sorted(t for t in times if t0 <= t <= t1)
    edges = [t0] + ts + [t1]
    gaps = [[round(a - t0, 3), round(b - a, 3)]
            for a, b in zip(edges, edges[1:]) if b - a >= least]
    return sorted(gaps, key=lambda g: -g[1])[:8]


def summary(obs: dict, overruns) -> dict:
    """What of one run's observation says where its window's time went."""
    w = obs["window"]
    t0, t1 = w["t_open"], w["t_close"]
    out = {"setup_s": round(obs["setup_s"], 2),
           "overruns_in_window": [
               [round(t - t0, 3), round(s, 3)] for t, s in overruns
               if t0 <= t <= t1],
           "overruns_before": sum(1 for t, _ in overruns if t < t0)}
    if "requests" in obs:
        times = [t for r in obs["requests"] for t in r["token_times"]]
        inside = [t for t in times if t0 <= t < t1]
        per_s = [0] * int(round(t1 - t0))
        for t in inside:
            per_s[min(int(t - t0), len(per_s) - 1)] += 1
        done = [r for r in obs["requests"]
                if r["status"] == "ok" and t0 <= r["done"] < t1]
        out.update(tokens=len(inside), tokens_by_second=per_s,
                   silences=silences(times, t0, t1),
                   finished=len(done),
                   finished_prompt_tokens=sum(r["n_prompt"] for r in done))
    steps = sorted(s["t_start"] for s in obs.get("spans", [])
                   if s["name"] in ("engine/step", "train/step"))
    if len(steps) > 2:
        gaps = [b - a for a, b in zip(steps, steps[1:])]
        out.update(steps=len(steps),
                   step_gap_ms_p50=round(1e3 * median(gaps), 2),
                   step_gaps_longest_ms=[
                       [round(a - t0, 2), round(1e3 * (b - a), 1)]
                       for a, b in sorted(zip(steps, steps[1:]),
                                          key=lambda p: p[0] - p[1])[:6]])
    return out


def one(args) -> int:
    """The child: one run, its taps, its line."""
    from benchmark import run

    overruns, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            t = time.monotonic()
            time.sleep(0.02)
            late = time.monotonic() - t - 0.02
            if late >= 0.05:
                overruns.append((t, late))

    kept = {}
    load_module = run.load_module

    def keeping_module(here, directory, name, what):
        module = load_module(here, directory, name, what)
        if directory == "drivers":
            inner = module.run

            def observed(**kw):
                kept["obs"] = inner(**kw)
                return kept["obs"]

            module.run = observed
        return module

    run.load_module = keeping_module
    threading.Thread(target=watch, daemon=True, name="tool-watch").start()
    try:
        result = run.run_cell(args.workload, args.one, args.seconds, False)
    except run.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    finally:
        stop.set()
    result["tool"] = summary(kept["obs"], overruns)
    print(json.dumps(result), flush=True)
    return 0


def spread(values) -> float:
    """The contract's: quartile distance over the median."""
    q = quantiles(values, n=4)
    return (q[2] - q[0]) / median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "repeat"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", help="comma-separated, one run each, in order")
    ap.add_argument("--one", type=int, help="(the child's) one seed")
    ap.add_argument("--root", default=ROOT,
                    help="run the copy of the benchmark in this checkout")
    args = ap.parse_args(argv)
    if args.one is not None:
        return one(args)
    os.makedirs(args.out, exist_ok=True)
    script = os.path.join(os.path.abspath(args.root), "benchmark", "tools",
                          "repeat_cell.py")
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.monotonic()
        with open(os.path.join(args.out, f"{args.workload}.{n}.log"),
                  "w") as log:
            p = subprocess.run(
                [sys.executable, script, "--workload", args.workload,
                 "--seconds", str(args.seconds), "--one", str(seed)],
                cwd=os.path.abspath(args.root), stdout=subprocess.PIPE,
                stderr=log, text=True)
        wall = time.monotonic() - t
        lines = p.stdout.strip().splitlines()
        row = {"run": n, "seed": seed, "rc": p.returncode,
               "wall_s": round(wall, 1)}
        if p.returncode == 0 and lines:
            r = json.loads(lines[-1])
            row.update(correct=r["correct"], attempted=r["attempted"],
                       failed=r["failed"],
                       metrics={k: v["value"]
                                for k, v in r["metrics"].items()},
                       checks=r.get("checks"), tool=r["tool"],
                       memory_peak_bytes=r["device"]["memory_peak_bytes"])
        rows.append(row)
        with open(os.path.join(args.out, f"{args.workload}.runs.jsonl"),
                  "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps(row), flush=True)
    good = [r for r in rows if "metrics" in r]
    if len(good) >= 3:
        for name in good[0]["metrics"]:
            vals = [r["metrics"][name] for r in good]
            rest = vals[1:]  # the first run compiles
            print(f"{name}: {vals}; spread of all {100 * spread(vals):.3f} %"
                  + (f", without the first {100 * spread(rest):.3f} %"
                     if len(rest) >= 3 else ""), flush=True)
    return 0 if len(good) == len(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
