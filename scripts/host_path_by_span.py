"""One serving cell of the benchmark, traced, and beside its result line the
host path by span (PERF.md section 5): the median of every ``engine/*`` and
``broker/*`` span of the window by kind of step, in ms, with what a step
spends outside dispatch-to-fetch (its length less ``device_ms``) and the
``h2d_copies`` / ``h2d_bytes`` its spans carry.

    chiprun -- python scripts/host_path_by_span.py --workload chat-decode-sat \
        --seed 3400000001 [--root .bench_checkout/parent]

The cell runs through ``benchmark/run.py:run_cell`` of ``--root`` (default:
this checkout; another checkout's program and benchmark with it), whose
driver's observations are read here as the metric readers read them.  The
result line is the benchmark's own; the line before it is this script's and
also goes to ``chiprun_out/host_path_by_span.jsonl`` (appended).
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def by_span(spans) -> dict:
    """→ {kind of step: {span name: median ms, ...}}; ``broker/*`` spans
    carry no kind and go under ``"loop"``."""
    out: dict = {}
    for s in spans:
        name, attrs = s["name"], s["attrs"]
        if not name.startswith(("engine/", "broker/")):
            continue
        ms = (s["t_end"] - s["t_start"]) * 1e3
        kind = out.setdefault(attrs.get("kind", "loop"), {})
        kind.setdefault(name, []).append(ms)
        if name == "engine/step" and "device_ms" in attrs:
            kind.setdefault("host = step - device_ms", []).append(
                ms - attrs["device_ms"])
            for k in ("h2d_copies", "h2d_bytes"):
                if k in attrs:
                    kind.setdefault(k, []).append(attrs[k])
    return {kind: {"steps": len(v.get("engine/step", ())),
                   **{n: round(statistics.median(x), 3)
                      for n, x in sorted(v.items())}}
            for kind, v in sorted(out.items())}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--root", default=HERE)
    opts = ap.parse_args()
    root = os.path.abspath(opts.root)
    sys.path.insert(0, root)
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(root, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    seen = {}
    load = run.load_module

    def load_module(here, directory, name, what):
        module = load(here, directory, name, what)
        if directory != "drivers":
            return module

        class Driver:  # the driver, its observations kept
            @staticmethod
            def run(**kw):
                seen.update(module.run(**kw))
                return seen

        return Driver

    run.load_module = load_module
    result = run.run_cell(opts.workload, opts.seed, opts.seconds, True,
                          root=root)
    line = {"workload": opts.workload, "seed": opts.seed, "root": opts.root,
            "device": result["device"]["kind"],
            "by_span_ms_p50": by_span(seen["spans"]),
            "idle_gaps": result["breakdown"]["idle_gaps"]}
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "host_path_by_span.jsonl"),
              "a") as f:
        print(json.dumps(line), file=f)
    print(json.dumps(line), flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
