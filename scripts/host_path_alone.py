"""A decode step's argument preparation alone, on the chip's host, beside N
threads that wake every step and write a few bytes to a socket as the
server's HTTP threads do (PERF.md section 5, "The host path by span"):
what it costs to bring a step's small host inputs to the device, four ways.

    chiprun -- python scripts/host_path_alone.py [--steps 300]

(a) the parent's path: six ``jnp.asarray`` (seven with a second table) and an
    eager ``jax.random.split`` unpacked in Python;
(b) one packed int32 buffer, one ``jax.device_put``, one jitted unpack
    program; the key was split off (eagerly, as in (a)) after the step
    before was called (``b_direct``, the engine's path: the same buffer
    handed to the unpack program as it is, so that the call makes the copy);
(c) NumPy arrays handed straight to the jitted step, the key split in a
    one-line jitted program (for the record: the copies then lie inside the
    step's dispatch);
(d) (b) with the table's fields laid out as views of the one buffer, so that
    packing copies nothing;
``b_then_wake``: (b) with the threads woken after the step is called;
and ``stage_then_wake`` (ISSUE 38, the engine's order since): ``b_direct``'s
pack and unpack call made with no thread awake (``stage_ms``), THEN the
wake, THEN what the step still does before its program (``prep_ms``): the
table's fields compared with the staged buffer's a field at a time as
``bytes``, which holds the interpreter lock throughout, and the call;
``stage_then_wake_packed`` is the same with the check ISSUE 38 first wrote,
a fresh pack compared with the staged buffer, whose ``np.zeros`` and
assignments let go of the lock; ``wake_then_call`` is the floor: the wake,
then the call on arguments that were on the device all along, nothing
staged, nothing checked.  ``to_device_ms`` is the time from the wake to the
start of the step program on the device, from a profile of ``--traced``
steps more (``b_direct``, ``b_then_wake``, the two ``stage_then_wake`` and
``wake_then_call``; not on a CPU).

The sizes are the serving cells' (rows x blocks a sequence): Mistral's and
OLMoE's 32 x 64, Nemotron's 64 x 24, Mellum2's 32 x 132 with two tables.  A
step is a stand-in program that reads every argument and whose result is
fetched; ``prep_ms`` runs from the threads' wake-up to the moment the step
program is called (the engine's ``engine/h2d``), ``call_ms`` is the call
itself (``engine/dispatch``).  Medians over ``--steps`` steps.

A measurement of the chip's host: without a TPU it stops before the first
run, unless ``--rehearse`` (a CPU rehearsal of the control flow, whose lines
say ``"backend": "cpu"`` and are kept nowhere).  The lines go to the output
and to ``chiprun_out/host_path_alone.jsonl``, the device's line first.
"""

import argparse
import bisect
import glob
import json
import os
import select
import shutil
import socket
import statistics
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SIZES = {  # name: rows, blocks a sequence, tables
    "mistral-32x64": (32, 64, 1),
    "nemotron-64x24": (64, 24, 1),
    "mellum2-32x132x2": (32, 132, 2),
}
THREADS = (0, 8, 32, 64)


def drain(readers) -> None:
    """The clients: a child process (forked before JAX is imported) that
    reads what the threads write until every socket closes."""
    open_ = list(readers)
    while open_:
        for s in select.select(open_, [], [])[0]:
            if not s.recv(65536):
                open_.remove(s)
    os._exit(0)


class Streams:
    """``n`` threads, each blocked on its queue; a step's ``wake`` hands each
    a token, which it writes as one SSE chunk to its socket."""

    def __init__(self, writers):
        import queue

        self.queues = [queue.SimpleQueue() for _ in writers]
        self.done = [0] * len(writers)
        self.threads = [threading.Thread(target=self._run, args=(i, w),
                                         daemon=True)
                        for i, w in enumerate(writers)]
        for t in self.threads:
            t.start()

    def _run(self, i, sock):
        q = self.queues[i]
        while True:
            tok = q.get()
            if tok is None:
                return
            data = b"data: " + json.dumps({
                "id": f"cmpl-{i}", "object": "text_completion",
                "choices": [{"index": 0, "text": "", "token": tok,
                             "finish_reason": None}]}).encode() + b"\n\n"
            sock.sendall(f"{len(data):X}\r\n".encode() + data + b"\r\n")
            self.done[i] += 1

    def wake(self, n, tok):
        for q in self.queues[:n]:
            q.put(tok)

    def settle(self, n, since, steps):
        """Wait until the first ``n`` threads wrote ``steps`` chunks more
        than ``since`` (a copy of ``done``)."""
        while any(d - d0 < steps for d, d0 in zip(self.done[:n], since)):
            time.sleep(0.0002)

    def stop(self):
        for q in self.queues:
            q.put(None)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--traced", type=int, default=60)
    ap.add_argument("--rehearse", action="store_true")
    opts = ap.parse_args()

    pairs = [socket.socketpair() for _ in range(max(THREADS))]
    if os.fork() == 0:
        for w, _ in pairs:
            w.close()
        drain([r for _, r in pairs])
    for _, r in pairs:
        r.close()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.inference.v2.ragged import (DecodeStateTable,
                                                   decode_layout)

    kind, backend = jax.devices()[0].device_kind, jax.default_backend()
    if backend != "tpu" and not opts.rehearse:
        sys.exit(f"host_path_alone: backend {backend!r}, device {kind!r}: "
                 f"this script measures the host of a TPU and nothing else")
    out = None
    if backend == "tpu":
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        out = open(os.path.join(ROOT, "chiprun_out", "host_path_alone.jsonl"),
                   "w")

    def say(line):
        for f in (sys.stdout, out):
            if f is not None:
                print(json.dumps(line), file=f, flush=True)

    say({"device": kind, "backend": backend, "cpus": os.cpu_count(),
         "steps": opts.steps,
         "switch_interval_ms": sys.getswitchinterval() * 1e3})
    streams = Streams([w for w, _ in pairs])

    @jax.jit
    def step(token_ids, position_ids, block_tables, context_lens, temps, rng,
             seeds):  # reads every argument; its result is fetched
        tables = block_tables if isinstance(block_tables, tuple) \
            else (block_tables,)
        return (token_ids + position_ids + context_lens + seeds
                + sum(t.sum(1) for t in tables)
                + temps.astype(jnp.int32) + rng[0].astype(jnp.int32))

    split = jax.jit(lambda key: tuple(jax.random.split(key)))

    def to_device(traced_run, n, wake_first):
        """Median ms from a step's wake to the start of its ``jit_step`` on
        the device, over a profiled run (host annotation and device events
        on one clock: ``benchmark/trace_reduce.py``)."""
        from benchmark import common, trace_reduce

        def wake(i):
            with jax.profiler.TraceAnnotation("bench/wake"):
                streams.wake(n, i)

        session = common.TraceSession(lambda msg: None)
        try:
            session.start()
            try:
                traced_run(wake)
            finally:
                session.stop()
            (path,) = glob.glob(os.path.join(
                session.dir, "plugins", "profile", "*", "*.xplane.pb"))
            trace = trace_reduce.load(path)
        finally:
            shutil.rmtree(session.dir, ignore_errors=True)
        wakes = sorted(e.start for e in trace.host_spans
                       if e.name == "bench/wake")
        runs = sorted(e.start for e in trace.device_modules.get(0, ())
                      if trace_reduce.module_name(e.name) == "jit_step")
        # each run against the last wake before it; where the run comes
        # first (``b_then_wake``), each wake against the last run before it
        if wake_first:
            ms = [(r - wakes[i - 1]) / 1e6 for r in runs
                  if (i := bisect.bisect_right(wakes, r))]
        else:
            ms = [(runs[i - 1] - w) / 1e6 for w in wakes
                  if (i := bisect.bisect_right(runs, w))]
        return round(statistics.median(ms), 4) if ms else None

    for name, (rows, blocks, tables) in SIZES.items():
        rs = np.random.default_rng(0)

        def fill(t):
            t.active[:] = True
            t.ctx[:] = rs.integers(1, blocks * 64 - 1, rows)
            t.next_tok[:] = rs.integers(1, 32000, rows)
            t.seed[:] = rs.integers(0, 1 << 30, rows)
            t.temp[::3] = 0.7
            t.block_tables[:] = rs.integers(0, 400, (rows, blocks))
            if t.win_tables is not None:
                t.win_tables[:] = rs.integers(0, 400, (rows, blocks))
            return t

        layout = decode_layout(rows, blocks, two_pools=tables == 2)
        plain = fill(DecodeStateTable(rows, blocks, 64, two_pools=tables == 2))
        unpack = programs.build_unpack(layout)
        held = layout.views(layout.new())  # (d): the table lives in it
        held["token_ids"][:] = plain.next_tok
        held["position_ids"][:] = plain.ctx
        held["seeds"][:] = plain.seed
        held["block_tables"][:] = plain.block_tables
        if tables == 2:
            held["win_tables"][:] = plain.win_tables
        state = {"key": jax.random.PRNGKey(0)}
        state["key"], state["next"] = jax.random.split(state["key"])

        def temps_of(t, temperature=0.0):
            return np.where(t.temp >= 0.0, t.temp,
                            np.float32(temperature)).astype(np.float32)

        def a():
            t = plain
            ctx_in = ((t.ctx + 1) * t.active).astype(np.int32)
            tabs = jnp.asarray(t.block_tables)
            if t.win_tables is not None:
                tabs = (tabs, jnp.asarray(t.win_tables))
            state["key"], rng = jax.random.split(state["key"])
            return (jnp.asarray(t.next_tok), jnp.asarray(t.ctx), tabs,
                    jnp.asarray(ctx_in), jnp.asarray(temps_of(t)), rng,
                    jnp.asarray(t.seed))

        def pack():
            buf = layout.new()
            t, v = plain, layout.views(buf)
            v["token_ids"][:] = t.next_tok
            v["position_ids"][:] = t.ctx
            np.multiply(t.ctx + 1, t.active, out=v["context_lens"])
            v["temps"][:] = temps_of(t)
            v["seeds"][:] = t.seed
            v["block_tables"][:] = t.block_tables
            if t.win_tables is not None:
                v["win_tables"][:] = t.win_tables
            return buf

        def unpacked(dev):
            f, rng = unpack(dev), state["next"]
            tabs = f["block_tables"]
            if tables == 2:
                tabs = (tabs, f["win_tables"])
            return (f["token_ids"], f["position_ids"], tabs,
                    f["context_lens"], f["temps"], rng, f["seeds"])

        def b():
            return unpacked(jax.device_put(pack()))

        def b_direct():
            return unpacked(pack())

        def c():
            t = plain
            ctx_in = ((t.ctx + 1) * t.active).astype(np.int32)
            tabs = t.block_tables if t.win_tables is None \
                else (t.block_tables, t.win_tables)
            state["key"], rng = split(state["key"])
            return (t.next_tok, t.ctx, tabs, ctx_in, temps_of(t), rng, t.seed)

        def d():
            v = held
            np.multiply(v["position_ids"] + 1, plain.active,
                        out=v["context_lens"])
            v["temps"][:] = temps_of(plain)
            return unpacked(jax.device_put(v["token_ids"].base))

        staged = {}

        def stage():  # with no thread awake: the step before's last act
            staged["buf"] = pack()
            staged["views"] = layout.views(staged["buf"])
            staged["args"] = unpacked(staged["buf"])

        def check():  # what is left before the program: the engine's compare
            t, v = plain, staged["views"]
            fields = {"token_ids": t.next_tok, "position_ids": t.ctx,
                      "context_lens": (t.ctx + 1) * t.active,
                      "temps": temps_of(t), "seeds": t.seed,
                      "block_tables": t.block_tables}
            if t.win_tables is not None:
                fields["win_tables"] = t.win_tables
            assert all(v[k].tobytes() == np.ascontiguousarray(
                x, v[k].dtype).tobytes() for k, x in fields.items())
            return staged["args"]

        def check_packed():  # the same by a second buffer
            assert pack().tobytes() == staged["buf"].tobytes()
            return staged["args"]

        # ``b_then_wake``: (b) with the threads woken AFTER the step is
        # called (the order of S5's lever 2), for what the threads cost
        variants = {"a": a, "b": b, "b_direct": b_direct, "c": c, "d": d,
                    "b_then_wake": b, "stage_then_wake": check,
                    "stage_then_wake_packed": check_packed,
                    "wake_then_call": lambda: staged["args"]}
        stage()
        for fn in variants.values():  # compile everything before timing
            np.asarray(step(*fn()))

        def run(label, fn, n, steps, wake):
            """``steps`` steps beside ``n`` threads → (stage, prep, call)
            ms a step; ``wake(i)`` wakes them, where the variant does."""
            rows, since = [], list(streams.done)
            for i in range(steps):
                t_s = time.perf_counter()
                if label.startswith("stage_then_wake"):
                    stage()
                t_w = time.perf_counter()
                if label != "b_then_wake":
                    wake(i)
                t0 = time.perf_counter()
                args = fn()
                t1 = time.perf_counter()
                res = step(*args)
                t2 = time.perf_counter()
                if label[0] in "bds":  # the next step's key, behind it
                    state["key"], state["next"] = jax.random.split(
                        state["key"])
                if label == "b_then_wake":
                    wake(i)
                np.asarray(res)
                streams.settle(n, since, i + 1)
                rows.append(((t_w - t_s) * 1e3, (t1 - t0) * 1e3,
                             (t2 - t1) * 1e3))
                # freeing a device array lets go of the interpreter lock:
                # here, with no thread awake, not where the next step
                # rebinds the names (the engine frees a step's fields where
                # the step returns)
                args = res = None
            return rows

        for n in THREADS:
            for label, fn in variants.items():
                rows = run(label, fn, n, opts.steps + 20,
                           lambda i: streams.wake(n, i))[20:]
                prep, call = [r[1] for r in rows], [r[2] for r in rows]
                q = statistics.quantiles(prep, n=10)
                line = {"size": name, "threads": n, "variant": label,
                        "prep_ms_p50": round(statistics.median(prep), 4),
                        "prep_ms_p90": round(q[8], 4),
                        "call_ms_p50": round(statistics.median(call), 4),
                        "prep_call_ms_p50": round(statistics.median(
                            p + c for p, c in zip(prep, call)), 4),
                        "buffer_bytes": layout.size * 4}
                if label.startswith("stage_then_wake"):
                    line["stage_ms_p50"] = round(
                        statistics.median(r[0] for r in rows), 4)
                if (label in ("b_direct", "b_then_wake", "stage_then_wake",
                              "stage_then_wake_packed", "wake_then_call")
                        and backend == "tpu" and opts.traced):
                    line["to_device_ms_p50"] = to_device(
                        lambda wake: run(label, fn, n, opts.traced, wake), n,
                        label != "b_then_wake")
                say(line)
    streams.stop()


if __name__ == "__main__":
    main()
