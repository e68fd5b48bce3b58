"""Where a closed-loop serving cell's plateau begins: the cell's driver run
once with a short ramp and a long window, and the tokens a second it
completed by 10 s bucket FROM THE LOAD'S START, beside the time each client's
first (cut) request ended.  ``ramp_s`` of the traffic file is the shortest
ramp after which the buckets are flat and every first request has ended
(PR 40 found GLM-5.2's so; PERF.md section 6).

    chiprun --timeout 1500 -- python scripts/ramp_sweep.py \\
        --workload jamba2-doc-long-sat --seed 5 [--ramp 6] [--seconds 170]

A measurement of the chip: the driver refuses a host without the cell's TPU.
The line is kept in ``chiprun_out/ramp_sweep.jsonl``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--ramp", type=float, default=6.0)
    ap.add_argument("--seconds", type=float, default=170.0)
    ap.add_argument("--bucket", type=float, default=10.0)
    args = ap.parse_args()
    spec = bench.load_json(os.path.join(ROOT, "BENCHMARK.json"), "benchmark")
    cell = bench.by_name(spec["workloads"], args.workload, "workload")
    entry = bench.by_name(spec["configs"], cell["config"], "configuration")
    config = bench.load_json(os.path.join(ROOT, entry["file"]), "config")
    traffic = bench.load_json(os.path.join(
        ROOT, "benchmark", "traffic", f"{cell['traffic']}.json"), "traffic")
    traffic["ramp_s"] = args.ramp
    driver = bench.load_module(os.path.join(ROOT, "benchmark"), "drivers",
                               config["driver"], "driver")
    device = bench.require_device(cell["chips"])
    obs = driver.run(cell=cell, config=config, traffic=traffic,
                     seed=args.seed, seconds=args.seconds, trace=False,
                     device=device, t_ready=time.monotonic(), log=bench.log)
    t_load = obs["window"]["t_open"] - args.ramp
    span = args.ramp + args.seconds
    buckets = [0] * int(span // args.bucket)
    for r in obs["requests"]:
        for t in r["token_times"]:
            i = int((t - t_load) // args.bucket)
            if 0 <= i < len(buckets):
                buckets[i] += 1
    firsts = sorted(round(r["done"] - t_load, 1) for r in obs["requests"]
                    if r["index"] == 0 and r["status"] != "pending")
    line = {"workload": args.workload, "seed": args.seed, "ramp": args.ramp,
            "seconds": args.seconds, "bucket_s": args.bucket,
            "tokens_per_s_by_bucket": [round(n / args.bucket, 1)
                                       for n in buckets],
            "first_requests_ended_s": firsts,
            "correct": obs["correct"], "failed": obs["failed"]}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "ramp_sweep.jsonl"),
              "a") as f:
        f.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
