#!/usr/bin/env python3
"""One traced run of a cell with its trace and its observation KEPT.

    python3 benchmark/tools/keep_trace.py --out chiprun_out/traces \\
        --workload dsv2lite-train-8k --seed 4400000001 --seconds 50

``run.run_cell`` itself, in this process, with two taps: the profiler's
``.xplane.pb`` is copied (gzipped) to ``<out>/<workload>.<seed>.xplane.pb.gz``
before the driver reduces and deletes it, what the driver observed goes to
``<out>/<workload>.<seed>.obs.json``, and with ``--text`` the compiled step
program's text (which the driver reads the scopes from) to ``.hlo.txt.gz``.  ``benchmark/tools/read_traces.py``
reads the pairs again, without a chip, so that two readers can be laid side
by side on the SAME traces.  The result's line is printed as ``run.py``
prints it.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import kernel_time, run, trace_reduce  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--text", action="store_true",
                    help="keep the compiled step program's text too")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    stem = os.path.join(args.out, f"{args.workload}.{args.seed}")
    load, load_module = trace_reduce.load, run.load_module
    scopes_of_text = kernel_time.scopes_of_text

    def keeping_text(hlo_text, scopes):
        with gzip.open(f"{stem}.hlo.txt.gz", "wt") as f:
            f.write(hlo_text)
        return scopes_of_text(hlo_text, scopes)

    def keeping_load(path):
        with open(path, "rb") as src, gzip.open(
                f"{stem}.xplane.pb.gz", "wb", compresslevel=6) as dst:
            shutil.copyfileobj(src, dst)
        return load(path)

    def keeping_module(here, directory, name, what):
        module = load_module(here, directory, name, what)
        if directory == "drivers":
            inner = module.run

            def observed(**kw):
                obs = inner(**kw)
                with open(f"{stem}.obs.json", "w") as f:
                    json.dump(obs, f, default=float)
                return obs

            module.run = observed
        return module

    trace_reduce.load, run.load_module = keeping_load, keeping_module
    if args.text:
        kernel_time.scopes_of_text = keeping_text
    try:
        result = run.run_cell(args.workload, args.seed, args.seconds, True)
    except run.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
