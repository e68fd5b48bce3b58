#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, on this machine.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is data that this file finds by name, and a name
it cannot find is an error:

* the cell is an entry of ``workloads`` in ``BENCHMARK.json``: a
  configuration, a traffic mix and the chips it needs;
* the configuration is the file its ``configs`` entry names
  (``benchmark/configs/<config>.json``): published sizes, what was cut, the
  engine's sizes, and the driver that runs it (``benchmark/drivers/<driver>.py``);
* the traffic mix is ``benchmark/traffic/<traffic>.json``, parameters that one
  general generator reads;
* a metric is a reader of its own, ``benchmark/end_to_end/<metric>.py`` or
  ``benchmark/layer_metrics/<metric>.py``, with one function ``read(obs)``
  over what the driver observed; a reader that finds nothing returns ``None``
  and the metric is left out.

The log goes to standard error; the last line of standard output is the one
JSON object of the contract.  Without a TPU of a kind that
``benchmark/peaks.json`` lists, or with another number of chips than the cell
asks for, the exit code is not 0 and no result is printed.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # the log's clock

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The cell cannot be run as asked; the message says why."""


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - T_PROCESS:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str, what: str) -> Any:
    if not os.path.isfile(path):
        raise BenchmarkError(f"{what}: no file {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def load_module(here: str, directory: str, name: str, what: str):
    """``benchmark/<directory>/<name>.py`` as a module, found by name."""
    path = os.path.join(here, directory, f"{name}.py")
    if not os.path.isfile(path):
        raise BenchmarkError(
            f"{what} {name!r}: no file benchmark/{directory}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{directory}.{name.replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchmarkError(
        f"{what} {name!r} is not in BENCHMARK.json "
        f"(has {[e['name'] for e in entries]})")


def metrics_of(spec: dict, group: str, cell: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def require_device(chips: int) -> dict:
    """The accelerator as JAX reports it, or an error: no TPU, a kind that
    ``peaks.json`` does not list, or another count than the cell's."""
    import jax

    devices = jax.devices()  # raises when the backend cannot start
    d = devices[0]
    peaks = load_json(os.path.join(HERE, "peaks.json"), "table of peaks")
    if d.platform != "tpu":
        raise BenchmarkError(
            f"needs a TPU; JAX found {d.platform!r} ({d.device_kind}); "
            f"a number from another platform is not a device metric")
    if d.device_kind not in peaks:
        raise BenchmarkError(
            f"device kind {d.device_kind!r} is not in benchmark/peaks.json")
    if len(devices) != chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s); JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "peaks": peaks[d.device_kind]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             device_check: Callable[[int], dict] = require_device,
             root: str = ROOT) -> dict:
    """Everything but the printing; returns the contract's object."""
    here = os.path.join(root, "benchmark")
    spec = load_json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    cell = by_name(spec["workloads"], workload, "workload")
    entry = by_name(spec["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(root, entry["file"]), "configuration")
    traffic = load_json(
        os.path.join(here, "traffic", f"{cell['traffic']}.json"),
        f"traffic mix {cell['traffic']!r}")
    wanted = metrics_of(spec, "per_layer" if trace else "end_to_end",
                        workload)
    directory = "layer_metrics" if trace else "end_to_end"
    readers = {m["name"]: load_module(here, directory, m["name"],
                                      "metric").read
               for m in wanted}
    driver = load_module(here, "drivers", config["driver"], "driver")

    device = device_check(cell["chips"])
    # Set-up is counted from here.  Importing JAX and starting the TPU's
    # runtime took 9.6 to 13.3 s in twelve runs of one call (PERF.md, PR 23),
    # is no work of the system under test, and its drift alone would pass
    # setup_s's bound; the log line says how long it took.
    t_ready = time.monotonic()
    log(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, {device['count']} x {device['kind']}, "
        f"seed {seed}, {seconds}s, trace {int(trace)}; the backend took "
        f"{t_ready - T_PROCESS:.1f}s to start, which set-up does not count")
    obs = driver.run(cell=cell, config=config, traffic=traffic, seed=seed,
                     seconds=seconds, trace=trace, device=device,
                     t_ready=t_ready, log=log)

    metrics: Dict[str, dict] = {}
    for m in wanted:
        value = readers[m["name"]](obs)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": obs["memory_peak_bytes"]}
    result = {"correct": bool(obs["correct"]), "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": dev}
    if trace:
        reduced: Optional[dict] = obs.get("trace")
        if reduced is None:
            raise BenchmarkError("the traced run read no device operation")
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if "checks" in obs:  # what ``correct`` compared: name -> [number, limit]
        result["checks"] = obs["checks"]
        for name, (value, limit) in obs["checks"].items():
            log(f"compared {name}: {value} (limit {limit})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
