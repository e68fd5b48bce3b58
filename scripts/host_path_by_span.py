"""One serving cell of the benchmark, traced, and beside its result line the
host path by span (PERF.md section 5): every ``engine/*`` and ``broker/*``
span of the window by kind of step, in ms, as ``[median, mean, mean cpu_ms,
mean less mean cpu_ms]`` (the span's length twice, its thread's CPU time, and
the time the thread was not running; a program from before ``cpu_ms`` gives
``[median, mean]``), with what a step spends outside dispatch-to-fetch (its
length less ``device_ms``), the two host parts of the step's own split in the
same form (``step:pre``, ``step:post``) and the ``h2d_copies`` / ``h2d_bytes``
its spans carry.  The CPU columns are means because the thread
clock of the chip's host advances in ticks of 10 ms: one span reads 0 or 10,
the sum over a window's spans is the thread's CPU time.  The program asks for
that clock on ``broker/turn`` alone (and ``engine/step`` reads it for its
split): to see WHICH phase holds a step's wait, open that phase's span with
``cpu=True`` in ``inference/v2/engine.py`` for the run (``engine/h2d``, say)
and its row gains the two CPU columns.  ``burst``: the mean ``streams`` and
``emitted`` of a ``broker/emit`` (the HTTP threads it wakes, and the tokens
it gave them) beside the mean wait of the device steps' host parts, and that
wait a stream.  ``staging`` (ISSUE 38): the share of the decode steps that
ran on the copy the step before staged (``staged`` = ``"used"``) or made
their own (``"fresh"``), and of the stagings (``engine/stage``, a row of the
table like any span) the share thrown away unused, by the kind of step that
found them; beside them (ISSUE 50) the share that found their program under
way (``ahead`` 1: ``staged`` = ``"ahead"``), the share that dispatched their
successor before their own fetch (``ahead_next`` 1) and the tokens computed
ahead and dropped (``ahead_dropped``, summed, and the steps that dropped
any).  ``behind`` (ISSUE 54): by kind of step, the share of the programs that
was called behind another, and of those the share that came ``late``.  And
``starved`` (ISSUE 53): the account of the device's queue that
``benchmark/program_queue.py`` makes of the ``engine/program`` spans, one
implementation with the readers ``device_unqueued_pct`` and
``unqueued_{post,turn,pre}_pct``: the share of the interval in which there
was work and the device held no program of it, its three parts, the time with
nothing to run (``broker/idle``), ``long_gap_turn_pct``, the turn part of
the gaps of 10 ms or more, which the device trace's reduction names
"nothing to run" whatever the loop was doing, and the gaps by length
(``gaps_by_length``: count, and per cent of the interval); over the window and over the
traced part of it, where the device's own idle share
(``serve_device_idle_pct``) stands beside them and what is left over
(``residual_pct``) is a step's launch, the fetch's tail and the programs that
came late.  A program from before ``engine/program`` gives ``{}``.

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
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


STEP_ATTRS = ("h2d_copies", "h2d_bytes")


def by_span(spans) -> dict:
    """→ {kind of step: {span name: [median ms, mean ms, mean ``cpu_ms``,
    mean ms less mean ``cpu_ms``], ...}}; the two host parts of a step's own
    split stand beside the spans in the same form (``step:pre``,
    ``step:post``), its other numbers (``h2d_copies``, ...) are medians;
    ``broker/*`` spans carry no kind and go under ``"loop"``."""
    out: dict = {}
    for s in spans:
        name, attrs = s["name"], s["attrs"]
        if not name.startswith(("engine/", "broker/")):
            continue
        ms = (s["t_end"] - s["t_start"]) * 1e3
        kind = out.setdefault(attrs.get("kind", "loop"), {})
        kind.setdefault(name, []).append((ms, attrs.get("cpu_ms")))
        if name == "engine/step" and "device_ms" in attrs:
            own = {"host = step - device_ms": ms - attrs["device_ms"],
                   **{k: attrs[k] for k in STEP_ATTRS if k in attrs}}
            if "pre_ms" in attrs:
                own["pre + device + post - step"] = (
                    attrs["pre_ms"] + attrs["device_ms"] + attrs["post_ms"]
                    - ms)
                for part in ("pre", "post"):
                    kind.setdefault(f"step:{part}", []).append(
                        (attrs[f"{part}_ms"], attrs[f"{part}_cpu_ms"]))
            for k, v in own.items():
                kind.setdefault(k, []).append(v)

    def row(values):
        if not isinstance(values[0], tuple):
            return round(statistics.median(values), 3)
        ms = [m for m, _ in values]
        cpu = [c for _, c in values if c is not None]
        cols = [statistics.median(ms), statistics.fmean(ms)]
        if len(cpu) == len(ms):
            cols += [statistics.fmean(cpu), cols[1] - statistics.fmean(cpu)]
        return [round(x, 3) for x in cols]

    return {kind: {"steps": len(v.get("engine/step", ())),
                   **{n: row(x) for n, x in sorted(v.items())}}
            for kind, v in sorted(out.items())}


def burst(spans) -> dict:
    """What a step's wait scales with, counted where it is caused: the mean
    ``streams`` (requests that got a token: each one's HTTP thread wakes) and
    ``emitted`` of the ``broker/emit`` spans, and by kind of step the mean
    wall less CPU of the host parts of the steps that reached the device,
    with that wait a stream.  Empty for a program from before those
    attributes."""
    emits = [s["attrs"] for s in spans
             if s["name"] == "broker/emit" and "streams" in s["attrs"]]
    waits: dict = {}
    for a in (s["attrs"] for s in spans if s["name"] == "engine/step"):
        if "pre_cpu_ms" in a:
            waits.setdefault(a["kind"], []).append(
                (a["pre_ms"] - a["pre_cpu_ms"])
                + (a["post_ms"] - a["post_cpu_ms"]))
    if not emits or not waits:
        return {}
    streams = statistics.fmean(a["streams"] for a in emits)
    out = {"emits": len(emits), "streams_mean": round(streams, 4),
           "emitted_mean": round(
               statistics.fmean(a["emitted"] for a in emits), 4)}
    for kind, ms in sorted(waits.items()):
        out[f"{kind}_wait_ms_mean"] = round(statistics.fmean(ms), 4)
        if streams:
            out[f"{kind}_wait_ms_a_stream"] = round(
                statistics.fmean(ms) / streams, 4)
    return out


def staging(spans) -> dict:
    """Whose copy the decode steps ran on, and what became of the stagings:
    the decode steps, the share of them that say ``staged`` = ``"used"`` and
    ``"fresh"``, the ``engine/stage`` spans, and the share of those that a
    later step dropped (``stage_discarded``), by that step's kind, with
    their bytes; and the shares that found their program under way and that
    dispatched their successor ahead, with the tokens dropped (a program from
    before ``ahead`` reads 0).  Empty for a program from before ``staged``."""
    steps = [s["attrs"] for s in spans if s["name"] == "engine/step"]
    dropped = [a for a in steps if a.get("stage_discarded")]
    steps = [a for a in steps if "staged" in a]  # (a mixed step says ``ahead``
    use = [a["staged"] for a in steps]           # too since ISSUE 54: below)
    if not use:
        return {}
    stagings = sum(s["name"] == "engine/stage" for s in spans)
    out = {"decode_steps": len(use),
           "used_pct": 100.0 * use.count("used") / len(use),
           "fresh_pct": 100.0 * use.count("fresh") / len(use),
           "ahead_pct": 100.0 * sum(a.get("ahead", 0) for a in steps)
           / len(use),
           "ahead_next_pct": 100.0 * sum(a.get("ahead_next", 0)
                                         for a in steps) / len(use),
           "ahead_dropped": sum(a.get("ahead_dropped", 0) for a in steps),
           "ahead_dropped_steps": sum(a.get("ahead_dropped", 0) > 0
                                      for a in steps),
           "stagings": stagings, "discarded": len(dropped),
           "discarded_bytes": sum(a["stage_bytes"] for a in dropped)}
    if stagings:
        out["discarded_pct"] = 100.0 * len(dropped) / stagings
    for kind in sorted({a["kind"] for a in dropped}):
        out[f"discarded_by_{kind}"] = sum(a["kind"] == kind for a in dropped)
    return {k: round(v, 4) for k, v in out.items()}


def _program_queue():
    """``benchmark/program_queue.py`` of THIS checkout, by its path: with
    ``--root`` the package ``benchmark`` is another checkout's, which may be
    from before the file (it imports nothing of the package)."""
    spec = importlib.util.spec_from_file_location(
        "program_queue", os.path.join(HERE, "benchmark", "program_queue.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def behind(spans) -> dict:
    """Two steps in flight, by kind of step (ISSUE 54): the programs fetched
    (``engine/program``), the share of them that was called BEHIND another,
    before that one's tokens were fetched (``behind`` 1), and of those the
    share whose predecessor had finished by then (``late`` 1: the device ran
    dry inside ``step``); beside them the steps that called their successor
    ahead (``ahead_next``) and the tokens dropped.  ``{}`` for a program from
    before the span."""
    q = _program_queue()
    out = {}
    for kind in sorted({a["kind"] for a in q.programs(spans)}):
        steps = [s["attrs"] for s in spans if s["name"] == "engine/step"
                 and s["attrs"]["kind"] == kind and "ahead" in s["attrs"]]
        out[kind] = {
            "programs": len(q.programs(spans, kind=kind)),
            "behind_pct": q.share_pct(spans, {"kind": kind}, {"behind": 1}),
            "late_pct": q.share_pct(spans, {"kind": kind, "behind": 1},
                                    {"late": 1}),
            "ahead_next_pct": 100.0 * sum(a["ahead_next"] for a in steps)
            / len(steps) if steps else None,
            "ahead_dropped": sum(a["ahead_dropped"] for a in steps)}
    return {kind: {k: v if v is None else round(v, 4) for k, v in d.items()}
            for kind, d in out.items()}


def starved(spans, t0: float, t1: float) -> dict:
    """The device's queue over ``[t0, t1)`` as ``program_queue.unqueued``
    accounts for it, in per cent of the interval; ``{}`` where no
    ``engine/program`` span touches it."""
    q = _program_queue().unqueued(spans, t0, t1)
    if q is None:
        return {}
    pct = 100.0 / q["seconds"]
    parts = q["post_s"] + q["turn_s"] + q["pre_s"]
    # the gaps before the programs called inside the interval, by length
    gaps = [s["attrs"] for s in spans if s["name"] == "engine/program"
            and t0 <= s["t_start"] < t1 and s["attrs"].get("unqueued_ms")]
    long_turn = sum(a["unqueued_turn_ms"] for a in gaps
                    if a["unqueued_ms"] >= 10.0) / 1e3
    by_length = {}
    for lo, hi in ((0, 2), (2, 5), (5, 10), (10, 50), (50, float("inf"))):
        ms = [a["unqueued_ms"] for a in gaps if lo <= a["unqueued_ms"] < hi]
        by_length[f"{lo}-{hi} ms"] = [len(ms), round(pct * sum(ms) / 1e3, 4)]
    out = {"seconds": q["seconds"], "accounted_s": q["accounted_s"],
           "programs": q["programs"], "unqueued_pct": pct * q["unqueued_s"],
           "post_pct": pct * q["post_s"], "turn_pct": pct * q["turn_s"],
           "pre_pct": pct * q["pre_s"],
           "nobodys_pct": pct * (q["unqueued_s"] - parts),
           "nothing_to_run_pct": pct * q["nothing_to_run_s"],
           "long_gap_turn_pct": pct * long_turn}
    return {**{k: round(v, 4) for k, v in out.items()},
            "gaps_by_length": by_length}  # [count, per cent of the interval]


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
    # when the profiler ran, on the spans' clock
    from benchmark import common
    traced, start, stop = [], common.TraceSession.start, \
        common.TraceSession.stop

    def started(self):
        start(self)
        traced.append(time.monotonic())

    def stopped(self):
        traced.append(time.monotonic())
        stop(self)

    common.TraceSession.start, common.TraceSession.stop = started, stopped
    result = run.run_cell(opts.workload, opts.seed, opts.seconds, True,
                          root=root)
    window = seen["window"]
    in_trace = starved(seen["spans"], *traced)
    idle = result["metrics"].get("serve_device_idle_pct", {}).get("value")
    if idle is not None and in_trace:
        # what the program's clock cannot see: the launch, the fetch's tail
        # and the programs that came late
        in_trace["device_idle_pct"] = round(idle, 4)
        left = idle - in_trace["unqueued_pct"] - in_trace["nothing_to_run_pct"]
        in_trace["residual_pct"] = round(left, 4)
        in_trace["residual_ms_a_program"] = round(
            left / 100.0 * in_trace["seconds"] / in_trace["programs"] * 1e3,
            4)
    line = {"workload": opts.workload, "seed": opts.seed, "root": opts.root,
            "device": result["device"]["kind"],
            "by_span_ms": by_span(seen["spans"]),
            "burst": burst(seen["spans"]),
            "staging": staging(seen["spans"]),
            "behind": behind(seen["spans"]),
            "starved": {"window": starved(seen["spans"], window["t_open"],
                                          window["t_close"]),
                        "traced": in_trace},
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
