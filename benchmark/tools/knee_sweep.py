#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate the server sustains.

    python3 benchmark/tools/knee_sweep.py --workload doc-prefill-loaded \\
        --rates 3.0:10.0:0.5 --seeds 3600001001,3600001002 --seconds 50 \\
        --pick-schedule --out chiprun_out/knee

One run a rate and seed, each a process of its own (the chip belongs to one
process at a time; this parent never touches JAX).  A run is ``run.py``'s
own path (``run.run_cell``) over a temporary copy of the checkout's
``BENCHMARK.json`` and ``benchmark/`` in which the traffic file's keys given
with ``--set`` (``rate_per_s`` from ``--rates``) are substituted, so the
committed files are never edited and nothing but the rate differs from the
cell.  The driver's observation (client records, the program's spans) is
kept, and from it one row is printed: what the cell's own readers read, and
what decides whether the rate was *sustained*:

* no request failed or was refused;
* ``generator_lag_p99_ms`` under 50 (the generator kept its schedule);
* the prompt tokens whose prefill ended inside the window (first token in
  it) are at least 97 % of the prompt tokens due inside it;
* the median time to first token of the requests due in the window's last
  10 s is at most 1.5 times that of its first 10 s (no growing backlog).

The knee is the highest rate at which every run was sustained and below
which every rate was.  What sets it is read off the same row: the token
budget (``mixed_step_fill_pct`` near 100 with rows to spare), the rows
(``rows_full_pct``: steps with every sequence slot taken), or the blocks
(``blocks_reserved_max_pct`` near 100: admission reserves a request's whole
prompt and output, ``engine.put(strict=True)``).  The reservation is
rebuilt from the program's ``request/queue`` and ``request/decode`` spans
laid beside the client's records in order of submission.

``loop_not_waiting_pct`` stands in for the device's idle share, which only
a traced run reads (``--trace 1`` gives ``serve_device_idle_pct`` for the
rate; a traced run's tails are the tracer's too, so the table's are
untraced).  With ``--count-only`` nothing runs: the realised count and
prompt tokens a second of each ``schedule_seed`` are printed (with
``--pick-schedule`` only the one closest to nominal), which is how a traffic
file's seed is chosen.  ``--set-check control_bits=4`` makes a run the
control of ``correct`` (the reference at four bits in the program's place,
``reference/dense_control.py``: the run comes out as not correct).  Without
``--rates``
the cell runs as committed, once a seed: a set of runs with every number
above beside the end-to-end ones.  With ``--pick-schedule`` the sweep runs
each rate on the seed that realises it best: Gamma(0.5) arrivals realise a
nominal rate to within 8 % (one standard deviation at 300 requests), and a
sweep on one seed would read that luck as the server's.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402  (imports neither JAX nor the program)

COLUMNS = ("rate", "seed", "sustained", "requests", "failed", "ttft_p50_ms",
           "ttft_p90_ms", "itl_p90_ms", "mixed_step_share_pct",
           "mixed_gap_share_pct", "mixed_step_fill_pct", "mixed_step_ms_p50",
           "queue_wait_p90_ms", "prefill_wait_p90_ms", "generator_lag_p99_ms",
           "prefilled_vs_due_pct", "ttft_last_vs_first", "rows_mean",
           "rows_full_pct", "blocks_reserved_mean_pct",
           "blocks_reserved_max_pct", "loop_not_waiting_pct",
           "out_tokens_per_s", "prompt_tokens_per_s_due", "correct",
           "checks")


def parse_rates(text, traffic=None):
    if text is None:  # the file's own; a closed loop has none
        return [traffic.get("rate_per_s")]
    if ":" not in text:
        return [float(x) for x in text.split(",")]
    lo, hi, step = (float(x) for x in text.split(":"))
    n = int(round((hi - lo) / step))
    return [round(lo + i * step, 6) for i in range(n + 1)]


def parse_set(pairs):
    return {k: json.loads(v) for k, v in (p.split("=", 1) for p in pairs)}


def cell_files(root: str, workload: str):
    """→ (the cell's entry, the paths of its traffic and configuration
    files) in the checkout at ``root``, found as ``run.py`` finds them."""
    spec = run.load_json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    cell = run.by_name(spec["workloads"], workload, "workload")
    entry = run.by_name(spec["configs"], cell["config"], "configuration")
    return (cell,
            os.path.join(root, "benchmark", "traffic",
                         f"{cell['traffic']}.json"),
            os.path.join(root, entry["file"]))


def traffic_of(root: str, workload: str) -> dict:
    return run.load_json(cell_files(root, workload)[1], "traffic mix")


def make_copy(tmp: str, workload: str, changes: dict,
              check_changes: dict) -> str:
    """``BENCHMARK.json`` and ``benchmark/`` (without its tests) under
    ``tmp``, the cell's traffic file with ``changes`` applied and its
    configuration's ``check`` with ``check_changes``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    _, traffic_path, config_path = cell_files(tmp, workload)
    for path, key, new in ((traffic_path, None, changes),
                           (config_path, "check", check_changes)):
        if new:
            whole = run.load_json(path, "file")
            (whole[key] if key else whole).update(new)
            with open(path, "w") as f:
                json.dump(whole, f, indent=1)
    return tmp


# -- what one schedule sends (no server) -----------------------------------


def schedule(traffic: dict, seconds: float, seed: int = 0, vocab: int = 2):
    """→ (requests due in the window, their prompt tokens a second)."""
    from benchmark import loadgen

    sched = traffic.get("schedule_seed", seed)
    offsets = loadgen.gamma_arrivals(sched, traffic["rate_per_s"],
                                     traffic["arrival_shape"],
                                     -traffic["ramp_s"], seconds)
    due = [i for i, off in enumerate(offsets) if off >= 0.0]
    prompt = sum(len(loadgen.draw_request(seed, 0, i, traffic, vocab)
                     ["prompt"]) for i in due)
    return len(due), prompt / seconds


def schedule_deviation(traffic: dict, seconds: float, mean_prompt: float):
    """→ (realised count, prompt tokens a second, and how far each lies from
    nominal, in percent) of the traffic's own ``schedule_seed``."""
    n, tok = schedule(traffic, seconds)
    rate = traffic["rate_per_s"]
    return (n, tok, 100.0 * n / (rate * seconds) - 100.0,
            100.0 * tok / (rate * mean_prompt) - 100.0)


def pick_schedule(traffic: dict, seconds: float, tries: int) -> int:
    """The ``schedule_seed`` under ``tries`` whose realised count and prompt
    tokens a second in the window lie closest to nominal (the larger of the
    two deviations decides)."""
    mean_prompt = nominal_prompt_mean(traffic["prompt_tokens"])

    def worst(sched):
        dev = schedule_deviation(dict(traffic, schedule_seed=sched), seconds,
                                 mean_prompt)
        return max(abs(dev[2]), abs(dev[3]))

    return min(range(tries), key=worst)


def count_only(args) -> int:
    traffic = traffic_of(ROOT, args.workload)
    traffic.update(parse_set(args.set))
    if traffic["loop"] != "open":
        print(f"{args.workload}: a {traffic['loop']} loop has no schedule",
              file=sys.stderr)
        return 1
    mean_prompt = nominal_prompt_mean(traffic["prompt_tokens"])
    for rate in parse_rates(args.rates, traffic):
        t = dict(traffic, rate_per_s=rate)
        seeds = ([pick_schedule(t, args.seconds, args.schedule_seeds)]
                 if args.pick_schedule else range(args.schedule_seeds))
        for sched in seeds:
            n, tok, dev_n, dev_tok = schedule_deviation(
                dict(t, schedule_seed=sched), args.seconds, mean_prompt)
            print(json.dumps({
                "rate": rate, "schedule_seed": sched, "requests": n,
                "vs_nominal_pct": dev_n, "prompt_tokens_per_s": tok,
                "tokens_vs_nominal_pct": dev_tok}))
    return 0


def nominal_prompt_mean(spec: dict, n: int = 400_000) -> float:
    """The mean of the clipped, rounded lognormal ``loadgen.lognormal_length``
    draws from, by a large fixed sample (the clipping has no tidy closed
    form once rounded)."""
    import numpy as np

    x = spec["median"] * np.exp(
        spec["sigma"] * np.random.default_rng(0).standard_normal(n))
    return float(np.clip(np.round(x), spec["min"], spec["max"]).mean())


# -- one run, in a process of its own --------------------------------------


def one(args) -> int:
    from benchmark import stats
    from benchmark.drivers import serve

    changes = parse_set(args.set)
    if args.rate is not None:
        changes["rate_per_s"] = args.rate
    kept = {}
    load_module = run.load_module

    def keeping_spans(finish):
        def finish_keeping(self, t0, t1):
            spans = finish(self, t0, t1)
            kept["all_spans"] = list(self.spans.values())
            return spans
        return finish_keeping

    def keeping(here, directory, name, what):
        module = load_module(here, directory, name, what)
        if directory == "drivers":
            inner = module.run

            def outer(**kwargs):
                kept["obs"] = inner(**kwargs)
                return kept["obs"]
            module.run = outer
            # run.py loads a driver by path, as a module of its own: its
            # collector is not the imported package's
            if "SpanCollector" in vars(module):
                module.SpanCollector.finish = keeping_spans(
                    module.SpanCollector.finish)
        return module

    with tempfile.TemporaryDirectory(prefix="knee-") as tmp, \
            mock.patch.object(run, "load_module", keeping), \
            mock.patch.object(  # the drivers that import serve's collector
                serve.SpanCollector, "finish",
                keeping_spans(serve.SpanCollector.finish)):
        root = make_copy(tmp, args.workload, changes,
                         parse_set(args.set_check))
        traffic = traffic_of(root, args.workload)
        config = run.load_json(cell_files(root, args.workload)[2],
                               "configuration")
        result = run.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), root=root)
    obs = kept["obs"]

    def reader(directory, name):
        return load_module(os.path.join(ROOT, "benchmark"), directory, name,
                           "metric").read(obs)

    t_open, t_close = obs["window"]["t_open"], obs["window"]["t_close"]
    due = [r for r in obs["requests"] if t_open <= r["due"] < t_close]

    def first(r):
        return r["token_times"][0] if r["token_times"] else math.inf

    prefilled = sum(r["n_prompt"] for r in obs["requests"]
                    if t_open <= first(r) < t_close)
    due_tokens = sum(r["n_prompt"] for r in due)

    def ttft_median(t0, t1):
        values = [(first(r) - r["due"]) * 1e3 for r in due
                  if t0 <= r["due"] < t1]
        return statistics.median(values) if values else None

    head = ttft_median(t_open, t_open + 10.0)
    tail = ttft_median(t_close - 10.0, t_close)
    steps = [(s["t_end"],
              s["attrs"]["running"] + s["attrs"].get("waiting", 0))
             for s in stats.spans_named(obs, "engine/step")
             if "running" in s["attrs"]]
    rows = [n for _, n in steps]
    opening = [n for t_end, n in steps if t_end < t_open + 2.0]
    max_seqs = config["engine"]["v2"]["max_seqs"]
    row = {
        "rate": args.rate, "seed": args.seed, "requests": len(due),
        "failed": obs["failed"], "correct": result["correct"],
        "ttft_p50_ms": stats.percentile(stats.first_token_ms(obs), 50),
        "ttft_p90_ms": reader("end_to_end", "ttft_p90_ms"),
        "itl_p90_ms": reader("end_to_end", "itl_p90_ms"),
        "out_tokens_per_s": stats.tokens_in_window(obs) / args.seconds,
        "prompt_tokens_per_s_due": due_tokens / args.seconds,
        "prefilled_vs_due_pct": 100.0 * prefilled / max(due_tokens, 1),
        "ttft_last_vs_first": tail / head if head and tail else None,
        "rows_mean": statistics.fmean(rows) if rows else None,
        "rows_first_2s": statistics.fmean(opening) if opening else None,
        "rows_full_pct": (100.0 * sum(n >= max_seqs for n in rows)
                          / len(rows)) if rows else None,
        "setup_s": obs["setup_s"],
        "compiles_in_window": obs["compiles_in_window"],
        "memory_peak_bytes": obs["memory_peak_bytes"],
        "schedule_seed": traffic.get("schedule_seed"),
        "checks": result.get("checks"),
    }
    for name in ("mixed_step_share_pct", "mixed_gap_share_pct",
                 "mixed_step_fill_pct", "mixed_step_ms_p50",
                 "decode_step_ms_p50", "queue_wait_p90_ms",
                 "prefill_wait_p90_ms", "generator_lag_p99_ms",
                 "loop_not_waiting_pct"):
        row[name] = reader("layer_metrics", name)
    row.update(blocks_reserved(kept.get("all_spans", ()), obs, config))
    if args.trace:
        row["serve_device_idle_pct"] = reader("layer_metrics",
                                              "serve_device_idle_pct")
        row["breakdown"] = result.get("breakdown")
    row["sustained"] = bool(
        obs["failed"] == 0
        and (row["generator_lag_p99_ms"] or 0.0) < 50.0
        and row["prefilled_vs_due_pct"] >= 97.0
        and row["ttft_last_vs_first"] is not None
        and row["ttft_last_vs_first"] <= 1.5)
    print(json.dumps(row), flush=True)
    return 0


def blocks_reserved(spans, obs, config) -> dict:
    """Time-weighted mean and the maximum, over the window, of the KV blocks
    reserved by admitted requests, as a share of the usable blocks.  A
    request holds ``ceil((prompt + asked) / block_size)`` blocks from its
    admission (end of ``request/queue``) to its finish (end of
    ``request/decode``); the program's spans carry no length, so they are
    laid beside the client's records in order of submission (the counts must
    agree, else nothing is returned)."""
    v2 = config["engine"]["v2"]
    queues = sorted((s for s in spans if s["name"] == "request/queue"),
                    key=lambda s: s["t_start"])
    ends = {s["t_start"]: s["t_end"] for s in spans
            if s["name"] == "request/decode"}
    prefills = {s["t_start"]: s["t_end"] for s in spans
                if s["name"] == "request/prefill"}
    records = sorted(obs["requests"], key=lambda r: r["sent"])
    queues = queues[len(queues) - len(records):]  # the warm-up came first
    if len(queues) != len(records) or not records:
        return {"blocks_reserved_mean_pct": None,
                "blocks_reserved_max_pct": None}
    t_open, t_close = obs["window"]["t_open"], obs["window"]["t_close"]
    events = []
    for q, r in zip(queues, records):
        admit = q["t_end"]
        done = ends.get(prefills.get(admit))
        if done is None:
            continue
        need = -(-(r["n_prompt"] + r["asked"]) // v2["block_size"])
        events += [(admit, need), (done, -need)]
    events.sort()
    held, t_last, area, peak = 0, t_open, 0.0, 0
    for t, delta in events:
        if t > t_open:
            area += held * (min(t, t_close) - t_last)
            t_last = min(t, t_close)
        if t >= t_close:
            break
        held += delta
        if t >= t_open:
            peak = max(peak, held)
    area += held * (t_close - t_last)
    usable = v2["num_blocks"] - 1  # one scratch block
    return {"blocks_reserved_mean_pct":
                100.0 * area / (t_close - t_open) / usable,
            "blocks_reserved_max_pct": 100.0 * peak / usable}


# -- the sweep -------------------------------------------------------------


def sweep(args) -> int:
    os.makedirs(args.out, exist_ok=True)
    rows = []
    traffic = traffic_of(ROOT, args.workload)
    traffic.update(parse_set(args.set))
    for rate in parse_rates(args.rates, traffic):
        picked = []
        if args.pick_schedule:
            best = pick_schedule(dict(traffic, rate_per_s=rate), args.seconds,
                                 args.schedule_seeds)
            picked = [f"schedule_seed={best}"]
        for seed in (int(s) for s in args.seeds.split(",")):
            stem = os.path.join(args.out, f"{args.workload}_r{rate}_s{seed}")
            cmd = [sys.executable, os.path.abspath(__file__), "--one",
                   "--workload", args.workload,
                   "--seed", str(seed), "--seconds", str(args.seconds),
                   "--trace", str(args.trace)]
            if rate is not None:
                cmd += ["--rate", str(rate)]
            for pair in args.set + picked:
                cmd += ["--set", pair]
            for pair in args.set_check:
                cmd += ["--set-check", pair]
            with open(stem + ".err", "w") as err:
                done = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=err,
                                      cwd=ROOT, timeout=args.timeout)
            lines = done.stdout.decode().strip().splitlines()
            if done.returncode != 0 or not lines:
                print(f"rate {rate} seed {seed}: exit {done.returncode}; "
                      f"see {stem}.err", flush=True)
                continue
            row = json.loads(lines[-1])
            rows.append(row)
            with open(os.path.join(args.out, f"{args.workload}.jsonl"),
                      "a") as f:
                f.write(json.dumps(row) + "\n")
            print(" ".join(f"{c}={fmt(row.get(c))}" for c in COLUMNS),
                  flush=True)
    if args.rates is None:
        return 0  # a set of runs of the cell as committed, not a sweep
    by_rate = {}
    for row in rows:
        by_rate.setdefault(row["rate"], []).append(row["sustained"])
    knee = None
    for rate in sorted(by_rate):
        if not all(by_rate[rate]):
            break
        knee = rate
    print(f"knee: {knee} req/s (the highest rate with every run sustained "
          f"and none unsustained below it); rates run: {sorted(by_rate)}")
    return 0


def fmt(v):
    if isinstance(v, float):
        return f"{v:.4g}" if abs(v) < 1e4 else f"{v:.1f}"
    return str(v)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default=None,
                    help="lo:hi:step, or a comma-separated list; the "
                         "traffic file's own rate without it")
    ap.add_argument("--seeds", default="3600001001,3600001002")
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="another key of the traffic file")
    ap.add_argument("--set-check", action="append", default=[],
                    metavar="KEY=JSON",
                    help="a key of the configuration's check, e.g. "
                         "control_bits=4: the control of correct")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "knee"))
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--count-only", action="store_true")
    ap.add_argument("--pick-schedule", action="store_true",
                    help="for each rate, the schedule_seed that realises "
                         "the nominal load best (a sweep at one seed reads "
                         "the seed's luck at each rate, 8 %% of the load, "
                         "as the server's)")
    ap.add_argument("--schedule-seeds", type=int, default=64,
                    help="how many schedule seeds --pick-schedule and "
                         "--count-only try")
    ap.add_argument("--one", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--rate", type=float, help=argparse.SUPPRESS)
    ap.add_argument("--seed", type=int, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.count_only:
        return count_only(args)
    return one(args) if args.one else sweep(args)


if __name__ == "__main__":
    sys.exit(main())
