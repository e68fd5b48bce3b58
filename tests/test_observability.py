"""Observability tests: span tracer, flight recorder, Prometheus exposition,
request-timeline plumbing, /debug endpoints, crash dumps (ISSUE 9).

The load-bearing guarantees:

* tracing is host-side only — greedy outputs are token-identical with the
  tracer on vs off (and the tier-1 HLO/budget gates run with it on);
* a request's recorded queue → prefill → decode spans reconstruct its TTFT;
* ``/metrics`` passes a strict text-exposition parser (HELP/TYPE,
  histograms whose ``+Inf`` bucket equals ``_count``, labeled series);
* an injected hard-kill (``DSTPU_FAULTS``) leaves a flight-recorder dump.
"""

import glob
import http.client
import json
import logging
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.monitor.monitor import CSVMonitor
from deepspeed_tpu.observability import (DEFAULT_MS_BUCKETS,
                                         ExpositionBuilder, ExpositionError,
                                         FlightRecorder, Histogram, Tracer,
                                         load_dump, parse_exposition)
from deepspeed_tpu.observability import recorder as global_recorder
from deepspeed_tpu.observability import tracer as global_tracer
from deepspeed_tpu.observability.__main__ import render
from deepspeed_tpu.serving import (ReplicaPool, RequestBroker, ServingConfig,
                                   ServingMetrics, create_server)
from deepspeed_tpu.serving.metrics import _WindowRate
from deepspeed_tpu.utils.logging import logger, request_logger

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V2 = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
          max_blocks_per_seq=8, dtype="float32")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def ref_fn(tiny_model):
    cfg, params = tiny_model
    cache = {}

    def ref(prompt, n):
        key = (tuple(prompt), n)
        if key not in cache:
            seq = np.array([list(prompt)], np.int32)
            for _ in range(n):
                logits = tfm.forward(params, seq, cfg)
                nxt = np.asarray(logits[:, -1].argmax(-1)).astype(np.int32)
                seq = np.concatenate([seq, nxt[:, None]], axis=1)
            cache[key] = seq[0, len(prompt):].tolist()
        return cache[key]

    return ref


def _engine(tiny_model, **over):
    cfg, params = tiny_model
    return InferenceEngineV2(cfg, params, V2Config(**{**V2, **over}))


def _pool(tiny_model, scfg, **eng_over):
    cfg, params = tiny_model
    return ReplicaPool.build(
        lambda: InferenceEngineV2(cfg, params, V2Config(**{**V2, **eng_over})),
        scfg, metrics=ServingMetrics())


# ---------------------------------------------------------------------------
# tracer units
# ---------------------------------------------------------------------------


def test_span_parenting_and_ordering():
    tr = Tracer(enabled=True)
    with tr.span("outer", trace_id="r1") as outer:
        with tr.span("inner") as inner:
            pass  # closes first
    spans = tr.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    by_name = {s.name: s for s in spans}
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["inner"].trace_id == "r1"  # inherited from stack top
    assert by_name["outer"].parent_id is None
    assert outer.t_start <= inner.t_start <= inner.t_end <= outer.t_end


def test_retroactive_span_and_filtering():
    tr = Tracer(enabled=True)
    tr.add_span("phase", 1.0, 2.5, trace_id="rA")
    tr.add_span("phase", 3.0, 3.5, trace_id="rB")
    tr.add_event("kick", trace_id="rA")
    assert len(tr.spans(trace_id="rA")) == 2
    assert len(tr.spans(name="phase")) == 2
    (sp,) = tr.spans(trace_id="rB")
    assert sp.duration_s == pytest.approx(0.5)


def test_ring_is_bounded():
    tr = Tracer(capacity=16, enabled=True)
    for i in range(100):
        tr.add_event(f"e{i}")
    spans = tr.spans()
    assert len(spans) == 16
    assert spans[0].name == "e84"  # oldest surviving


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("x") as sp:
        assert sp is None
    assert tr.add_span("y", 0.0, 1.0) is None
    assert tr.add_event("z") is None
    assert tr.spans() == []


def test_env_switch_turns_off_ring_and_annotation(monkeypatch):
    """``DSTPU_TRACE=0`` is a no-op for both clocks: no span, and no
    profiler annotation is made."""
    from deepspeed_tpu.observability import trace as trace_mod

    made = []
    monkeypatch.setattr(trace_mod, "_annotate",
                        lambda name, attrs: made.append(name))
    monkeypatch.setenv("DSTPU_TRACE", "0")
    off = Tracer()
    assert not off.enabled
    off.end(off.begin("x", n=1), more=2)
    with off.span("y"):
        pass
    assert made == [] and off.spans() == []
    monkeypatch.setenv("DSTPU_TRACE", "1")
    on = Tracer()
    with on.span("y", n=1):
        pass
    on.add_span("retro", 0.0, 1.0)  # no live interval: ring only
    assert made == ["y"] and [s.name for s in on.spans()] == ["y", "retro"]


def test_end_closes_children_an_exception_left_open():
    """The phase that raised stays in the ring, closed with its parent and
    marked ``error``, its profiler annotation is left, and the stack is
    clean for the next span."""
    tr = Tracer(enabled=True)
    outer = tr.begin("outer")
    child = tr.begin("child-left-open")
    inner = tr.begin("grandchild-left-open")
    tr.end(outer, error=True)
    with tr.span("next") as nxt:
        assert nxt.parent_id is None  # the stack is clean again
    spans = tr.spans()
    assert [s.name for s in spans] == [
        "grandchild-left-open", "child-left-open", "outer", "next"]
    for sp in (inner, child):
        assert sp.attrs == {"error": True}
        assert sp.t_end == outer.t_end and sp.annotation is None
    assert child.parent_id == outer.span_id
    assert inner.parent_id == child.span_id


def test_live_span_lies_on_the_profilers_host_plane(devices, tmp_path):
    """A span opened and closed live is also a ``TraceAnnotation``: in a
    ``jax.profiler`` capture it is on the ``/host:CPU`` plane under its
    name, with its small scalar attributes, on the profiler's clock."""
    import jax.numpy as jnp

    tr = Tracer(enabled=True)
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("engine/step", kind="decode", step=7, blob="x" * 100):
            sp = tr.begin("engine/wait", kind="decode", step=7)
            jnp.ones(8).block_until_ready()
            tr.end(sp, late=1)
        tr.add_span("request/first_write", 0.0, 1.0)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in ("engine/step", "engine/wait",
                               "request/first_write"):
                    assert plane.name == "/host:CPU"
                    found[ev.name] = (ev.start_ns, ev.duration_ns,
                                      dict(ev.stats))
    assert set(found) == {"engine/step", "engine/wait"}  # not the retroactive
    (s0, sd, sattrs), (w0, wd, wattrs) = (found["engine/step"],
                                          found["engine/wait"])
    assert s0 <= w0 and w0 + wd <= s0 + sd  # nested on the profiler's clock
    assert sattrs == {"kind": "decode", "step": 7}  # small scalars only
    assert wattrs == {"kind": "decode", "step": 7}  # begin()'s, not end()'s
    # the ring has the same spans on time.monotonic, with every attribute
    ring = {s.name: s for s in tr.spans()}
    assert ring["engine/wait"].attrs["late"] == 1
    assert len(ring["engine/step"].attrs["blob"]) == 100


def test_chrome_trace_format():
    tr = Tracer(enabled=True)
    with tr.span("work", trace_id="r1", items=3):
        pass
    tr.add_event("instant")
    doc = json.loads(json.dumps(tr.to_chrome_trace()))  # must be valid JSON
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"  # process_name metadata
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert len(complete) == 1 and len(instants) == 1
    (x,) = complete
    assert x["name"] == "work" and x["dur"] >= 0 and x["ts"] >= 0
    assert x["args"]["items"] == 3 and x["args"]["trace_id"] == "r1"
    for e in events[1:]:  # every sample event carries the required keys
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)


# ---------------------------------------------------------------------------
# flight recorder units
# ---------------------------------------------------------------------------


def test_recorder_rings_and_dump_roundtrip(tmp_path):
    rec = FlightRecorder(max_requests=2, max_steps=2, max_events=2)
    for i in range(4):
        rec.record_request({"rid": f"r{i}", "spans": []})
        rec.record_step({"kind": "decode", "t_start": 0.0, "t_end": 0.01})
        rec.record_event("ev", i=i)
    snap = rec.snapshot()
    assert [r["rid"] for r in snap["requests"]] == ["r2", "r3"]  # bounded
    assert len(snap["steps"]) == 2 and len(snap["events"]) == 2
    path = rec.dump(path=str(tmp_path / "f.json"), reason="test")
    body = load_dump(path)
    assert body["meta"]["reason"] == "test"
    assert [r["rid"] for r in body["requests"]] == ["r2", "r3"]


def test_recorder_dump_without_destination_is_none(monkeypatch):
    monkeypatch.delenv("DSTPU_FLIGHT_DIR", raising=False)
    assert FlightRecorder().dump() is None  # no env, no path → no scatter


def test_dump_gc_keeps_newest(tmp_path, monkeypatch):
    """Dump-time GC: a crash-looping worker must not fill the disk — only
    the newest $DSTPU_FLIGHT_MAX_DUMPS flight_*.json survive."""
    monkeypatch.setenv("DSTPU_FLIGHT_MAX_DUMPS", "3")
    rec = FlightRecorder()
    rec.record_event("ev")
    paths = []
    for i in range(6):
        p = str(tmp_path / f"flight_{i}.json")
        rec.dump(path=p, reason=f"r{i}")
        os.utime(p, (i + 1, i + 1))  # deterministic mtime order
        paths.append(p)
    survivors = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("flight_"))
    assert survivors == ["flight_3.json", "flight_4.json", "flight_5.json"]
    # unrelated files are never touched, and GC failures never raise
    (tmp_path / "notes.txt").write_text("keep me")
    rec.dump(path=str(tmp_path / "flight_7.json"), reason="r7")
    assert (tmp_path / "notes.txt").exists()
    monkeypatch.setenv("DSTPU_FLIGHT_MAX_DUMPS", "0")  # 0 disables GC
    rec.dump(path=str(tmp_path / "flight_8.json"), reason="r8")
    assert len([f for f in os.listdir(tmp_path)
                if f.startswith("flight_")]) == 4


# ---------------------------------------------------------------------------
# prometheus exposition: builder + strict parser
# ---------------------------------------------------------------------------


def test_histogram_cumulative_buckets():
    h = Histogram((1.0, 10.0))
    for v in (0.5, 5.0, 5.0, 100.0):
        h.observe(v)
    assert h.cumulative() == [(1.0, 1), (10.0, 3), (float("inf"), 4)]
    assert h.count == 4 and h.sum == pytest.approx(110.5)


def test_builder_renders_parseable_exposition():
    b = ExpositionBuilder()
    b.counter("app_requests_total", "Requests.", 7)
    b.gauge("app_depth", "Depth.", 1.5)
    b.gauge_series("app_replica_up", "Per-replica.",
                   [({"replica": "r0"}, 1.0), ({"replica": "r1"}, 0.0)])
    h = Histogram((5.0,))
    h.observe(1.0)
    h.observe(9.0)
    b.histogram("app_latency_ms", "Latency.", h)
    fams = parse_exposition(b.render())
    assert fams["app_requests_total"]["type"] == "counter"
    assert len(fams["app_replica_up"]["samples"]) == 2
    hist = fams["app_latency_ms"]
    buckets = [s for s in hist["samples"] if s[0].endswith("_bucket")]
    assert [v for _, _, v in buckets] == [1.0, 2.0]  # cumulative


def test_builder_rejects_duplicates_and_bad_names():
    b = ExpositionBuilder()
    b.gauge("ok_name", "x.", 1)
    with pytest.raises(ValueError):
        b.gauge("ok_name", "again.", 2)
    with pytest.raises(ValueError):
        b.gauge("bad-name", "x.", 1)


@pytest.mark.parametrize("text,msg", [
    ("metric_no_type 1\n", "no # TYPE"),
    ("# HELP a x\n# TYPE a gauge\n# TYPE a gauge\na 1\n", "duplicate TYPE"),
    ("# HELP a x\n# TYPE a gauge\na 1\na 2\n", "duplicate series"),
    ("# HELP a x\n# TYPE a gauge\na{b='q'} 1\n", "malformed"),
    ("# HELP a x\n# TYPE a gauge\na one\n", "malformed sample value"),
    ("# HELP h x\n# TYPE h histogram\n"
     'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 1\nh_sum 1\nh_count 1\n',
     "decrease"),
    ("# HELP h x\n# TYPE h histogram\n"
     'h_bucket{le="1"} 1\nh_sum 1\nh_count 1\n', r"\+Inf"),
    ("# HELP h x\n# TYPE h histogram\n"
     'h_bucket{le="1"} 1\nh_bucket{le="+Inf"} 2\nh_sum 1\nh_count 3\n',
     "_count"),
])
def test_parser_rejects_malformed(text, msg):
    with pytest.raises(ExpositionError, match=msg):
        parse_exposition(text)


# ---------------------------------------------------------------------------
# serving metrics: sliding-window rates + SLO goodput + exposition
# ---------------------------------------------------------------------------


def test_window_rate_slides_and_decays():
    w = _WindowRate(window_s=10.0)
    for t in range(5):  # 1 event/s for 5s starting at t=1000
        w.add(1.0, 1000.0 + t)
    assert w.rate(1004.0) == pytest.approx(5 / 4.0)  # young process: elapsed
    # full window: the t=1000 event is exactly window_s old → excluded
    assert w.rate(1010.0) == pytest.approx(0.4)
    assert w.rate(1030.0) == 0.0                     # idle → decays to zero


def test_goodput_counts_only_within_deadline():
    clock = [1000.0]
    m = ServingMetrics(rate_window_s=10.0, now_fn=lambda: clock[0])
    m.record_finish("length", within_deadline=True)
    m.record_finish("length", within_deadline=False)  # completed, not goodput
    m.record_finish("deadline")
    snap = m.snapshot()
    assert snap["completed"] == 2
    assert snap["completed_in_slo"] == 1
    assert snap["deadline_missed"] == 1
    assert snap["goodput_rps"] == pytest.approx(1.0)  # 1 event / 1s floor
    clock[0] += 100.0  # idle: windowed rate decays, lifetime division never
    assert m.snapshot()["goodput_rps"] == 0.0


def test_tokens_per_s_is_windowed_not_lifetime():
    clock = [5000.0]
    m = ServingMetrics(rate_window_s=10.0, now_fn=lambda: clock[0])
    clock[0] += 1000.0  # long idle lifetime before the first token
    for _ in range(20):
        m.record_token(0.001)
    # lifetime division would give 20/1000 = 0.02; the window gives 20/1
    assert m.snapshot()["tokens_per_s"] == pytest.approx(20.0)


def test_metrics_exposition_is_strictly_valid():
    m = ServingMetrics()
    m.record_submit()
    m.record_admit(0.004)
    m.record_first_token(0.020)
    for _ in range(5):
        m.record_token(0.002)
    m.record_finish("length")
    m.set_gauges(1, 2, 0.25)
    m.set_replica_stats([
        {"name": "replica0", "healthy": 1.0, "queue_depth": 1.0,
         "running": 2.0, "outstanding_tokens": 30.0, "kv_utilization": 0.25},
        {"name": "replica1", "healthy": 0.0, "queue_depth": 0.0,
         "running": 0.0, "outstanding_tokens": 0.0, "kv_utilization": 0.0}])
    fams = parse_exposition(m.to_prometheus())
    assert fams["dstpu_serving_ttft_ms"]["type"] == "histogram"
    assert fams["dstpu_serving_tpot_ms"]["type"] == "histogram"
    assert fams["dstpu_serving_queue_wait_ms"]["type"] == "histogram"
    reps = fams["dstpu_serving_replica_kv_utilization"]["samples"]
    assert {lbl["replica"] for _, lbl, _ in reps} == {"replica0", "replica1"}
    # histogram _count agrees with the recorded observations
    tpot = dict((s[0], s[2]) for s in fams["dstpu_serving_tpot_ms"]["samples"]
                if s[0].endswith("_count"))
    assert tpot["dstpu_serving_tpot_ms_count"] == 5


def test_replica_gauges_carry_stale_label_for_dead_replicas():
    """A dead replica's stats accessors return last-known (frozen) values;
    its gauge series must say so via stale="true" instead of passing the
    frozen numbers off as live (ISSUE 13 satellite)."""
    m = ServingMetrics()
    m.set_replica_stats([
        {"name": "replica0", "healthy": 1.0, "queue_depth": 1.0,
         "stale": False},
        {"name": "replica1", "healthy": 0.0, "queue_depth": 3.0,
         "stale": True}])
    fams = parse_exposition(m.to_prometheus())  # mixed label sets parse
    by_replica = {lbl["replica"]: lbl for _, lbl, _ in
                  fams["dstpu_serving_replica_queue_depth"]["samples"]}
    assert "stale" not in by_replica["replica0"]
    assert by_replica["replica1"]["stale"] == "true"
    # "stale" is a label, never a gauge family of its own
    assert "dstpu_serving_replica_stale" not in fams


# ---------------------------------------------------------------------------
# monitor close (handle-leak satellite)
# ---------------------------------------------------------------------------


def test_csv_monitor_close_releases_handles(tmp_path):
    mon = CSVMonitor(str(tmp_path), job_name="job")
    mon.write_events([("a/b", 1.0, 0), ("c", 2.0, 0)])
    handles = [f for f, _ in mon._files.values()]
    assert len(handles) == 2 and all(not f.closed for f in handles)
    mon.close()
    assert all(f.closed for f in handles) and not mon._files
    mon.close()  # idempotent
    mon.write_events([("a/b", 3.0, 1)])  # reopens cleanly (append mode)
    mon.close()
    rows = (tmp_path / "job" / "a_b.csv").read_text().strip().splitlines()
    assert rows == ["step,a/b", "0,1.0", "1,3.0"]


def test_monitor_base_close_is_noop():
    from deepspeed_tpu.monitor.monitor import Monitor

    Monitor().close()  # the ABC default must not raise


# ---------------------------------------------------------------------------
# request-id log correlation
# ---------------------------------------------------------------------------


def test_request_logger_prefixes_rid():
    records = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = _Cap()
    logger.addHandler(h)  # logger.propagate is False: attach directly
    try:
        request_logger("req-42").info("hello")
        request_logger("req-43", uid=7).warning("moved")
    finally:
        logger.removeHandler(h)
    assert records == ["[rid=req-42] hello", "[rid=req-43 uid=7] moved"]


def test_broker_logs_carry_rid(devices, tiny_model):
    records = []

    class _Cap(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    h = _Cap()
    logger.addHandler(h)
    try:
        broker = RequestBroker(_engine(tiny_model), ServingConfig()).start()
        handle = broker.submit([1, 2, 3], max_new_tokens=4)
        assert len(handle.result(timeout=90)) == 4
        broker.stop(drain=True, timeout=60)
    finally:
        logger.removeHandler(h)
    rid_lines = [r for r in records if f"rid={handle.rid}" in r]
    # submit, admit, and finish must all be greppable by the one rid
    assert any("submitted" in r for r in rid_lines)
    assert any("admitted" in r for r in rid_lines)
    assert any("finished" in r for r in rid_lines)


# ---------------------------------------------------------------------------
# tracing through the serving lifecycle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("over", [
    {}, {"max_tokens_per_step": 8},
    {"spec_mode": "self_draft", "spec_k": 2}],
    ids=["decode-mixed", "chunked-prefill", "spec"])
def test_tracing_on_vs_off_token_identical(devices, tiny_model, ref_fn, over):
    """Tracing must change no compiled program and no order of host work
    that a token depends on: greedy serving outputs are token-identical
    with the tracer enabled (``DSTPU_TRACE`` unset or 1) and disabled
    (``DSTPU_TRACE=0``), through the decode, the mixed (one chunk and
    several) and the speculative step, each with its sub-spans."""
    prompts = [([5, 6, 7], 6), ([1, 2, 3, 4], 5), ([11, 12], 8),
               (list(range(1, 20)), 4)]
    outs = {}
    was_enabled = global_tracer.enabled
    try:
        for enabled in (True, False):
            global_tracer.enabled = enabled
            global_tracer.clear()
            broker = RequestBroker(_engine(tiny_model, **over),
                                   ServingConfig()).start()
            handles = [broker.submit(p, max_new_tokens=n)
                       for p, n in prompts]
            outs[enabled] = [h.result(timeout=120) for h in handles]
            broker.stop(drain=True, timeout=90)
            kinds = {s.attrs["kind"]
                     for s in global_tracer.spans(name="engine/wait")}
            assert kinds == (set() if not enabled else
                             {"mixed", "spec" if over.get("spec_mode")
                              else "decode"})
    finally:
        global_tracer.enabled = was_enabled
    assert outs[True] == outs[False]
    for (p, n), toks in zip(prompts, outs[True]):
        assert toks == ref_fn(p, n)


def test_request_timeline_reconstructs_ttft(devices, tiny_model):
    """Acceptance: the recorded queue→prefill spans sum to the request's
    TTFT, and the decode span completes the timeline to finish."""
    global_tracer.clear()
    broker = RequestBroker(_engine(tiny_model), ServingConfig()).start()
    handle = broker.submit([3, 1, 4, 1, 5], max_new_tokens=8)
    toks = handle.result(timeout=120)
    broker.stop(drain=True, timeout=60)
    assert len(toks) == 8

    tl = next(r for r in global_recorder.snapshot()["requests"]
              if r["rid"] == handle.rid)
    spans = {s["name"]: s for s in tl["spans"]}
    assert set(spans) == {"request/queue", "request/prefill", "request/decode"}
    q, p, d = (spans["request/queue"], spans["request/prefill"],
               spans["request/decode"])
    # contiguous, ordered phases
    assert q["t_start"] == tl["submit_ts"]
    assert q["t_end"] == p["t_start"] == tl["admit_ts"]
    assert p["t_end"] == d["t_start"] == tl["first_token_ts"]
    assert d["t_end"] == tl["finish_ts"]
    ttft_from_spans = ((q["t_end"] - q["t_start"])
                       + (p["t_end"] - p["t_start"])) * 1e3
    assert ttft_from_spans == pytest.approx(tl["ttft_ms"], rel=1e-6)
    assert tl["finish_reason"] == "length" and tl["tokens_out"] == 8

    # the tracer ring carries the same request trace + engine step spans
    names = {s.name for s in global_tracer.spans(trace_id=handle.rid)}
    assert {"request", "request/queue", "request/prefill",
            "request/decode", "request/submit"} <= names
    steps = global_tracer.spans(name="engine/step")
    assert steps and all(s.attrs.get("kind") in ("decode", "mixed", "spec")
                         for s in steps)


def test_engine_steps_recorded_with_batch_attrs(devices, tiny_model):
    eng = _engine(tiny_model)
    eng.put([1, 2, 3], max_new_tokens=3)
    since = time.monotonic()  # (the ring is bounded: no index holds)
    while eng.running or eng.waiting:
        eng.step()
    steps = [s for s in global_recorder.snapshot()["steps"]
             if s["t_start"] >= since]
    assert steps
    assert steps[0]["kind"] == "mixed"  # first step prefills
    for s in steps:
        assert {"kind", "t_start", "t_end", "running", "waiting",
                "emitted"} <= set(s)
        assert s["t_end"] >= s["t_start"]
    assert sum(s["emitted"] for s in steps) == 3


# ---------------------------------------------------------------------------
# /debug endpoints + /metrics E2E
# ---------------------------------------------------------------------------


@pytest.fixture()
def http_stack(devices, tiny_model):
    scfg = ServingConfig(num_replicas=2, max_queue=32,
                         metrics_interval_s=0.1)
    pool = _pool(tiny_model, scfg).start()
    srv = create_server(pool, pool.metrics, scfg)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv, pool, srv.server_port
    pool.shutdown()
    srv.shutdown()


def _get(port, path, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp, body


def test_debug_endpoints_and_metrics_e2e(http_stack):
    srv, pool, port = http_stack
    h = pool.submit([2, 7, 1, 8], max_new_tokens=6)
    assert len(h.result(timeout=120)) == 6
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:  # pump pushes replica stats async
        if pool.metrics.replica_stats:
            break
        time.sleep(0.05)

    resp, body = _get(port, "/metrics")
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/plain")
    fams = parse_exposition(body.decode())  # strict format oracle
    assert fams["dstpu_serving_ttft_ms"]["type"] == "histogram"
    assert {lbl["replica"] for _, lbl, _ in
            fams["dstpu_serving_replica_queue_depth"]["samples"]} \
        == {"replica0", "replica1"}

    resp, body = _get(port, "/debug/requests")
    assert resp.status == 200
    dump = json.loads(body)
    assert any(r["rid"] == h.rid for r in dump["requests"])
    assert dump["steps"], "engine steps missing from flight snapshot"

    resp, body = _get(port, "/debug/trace")
    assert resp.status == 200
    doc = json.loads(body)  # Perfetto JSON validity
    events = doc["traceEvents"]
    assert events[0]["ph"] == "M"
    cats = {e.get("cat") for e in events[1:]}
    assert h.rid in cats  # the request's spans are in the trace
    assert all({"name", "ph", "ts"} <= set(e) for e in events[1:])

    resp, body = _get(port, "/debug/profile?seconds=nope")
    assert resp.status == 400
    # a request served while the capture runs: its steps are in the profile
    late = threading.Timer(0.4, lambda: pool.submit(
        [3, 1, 4, 1], max_new_tokens=8).result(timeout=120))
    late.start()
    resp, body = _get(port, "/debug/profile?seconds=1.5")
    late.join()
    if resp.status == 200:  # profiler may be unavailable on some backends
        prof = json.loads(body)
        assert os.path.isdir(prof["profile_dir"])
        (path,) = glob.glob(os.path.join(prof["profile_dir"], "**",
                                         "*.xplane.pb"), recursive=True)
        names = {ev.name for plane in
                 jax.profiler.ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for ev in line.events}
        # the program's live spans, and no Python-function events (the
        # profiler's Python tracer is off: it slows what it shows)
        assert {"engine/step", "engine/dispatch", "engine/wait",
                "broker/turn", "broker/emit"} <= names
        assert not any(n.startswith("$") for n in names)
    else:
        assert resp.status == 503


def test_streamed_request_records_turns_and_first_write(http_stack):
    """The broker loop's turns (token hand-off, then admission) and, once a
    streamed request, the time from the broker's first token to the
    first SSE chunk written."""
    srv, pool, port = http_stack
    global_tracer.clear()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions", body=json.dumps(
        {"prompt": [2, 7, 1, 8], "max_tokens": 5, "stream": True}),
        headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200
    chunks = [line for line in resp.read().split(b"\n\n")
              if line.startswith(b"data: {")]
    conn.close()
    rid = json.loads(chunks[0][6:])["id"].removeprefix("cmpl-")
    assert len(chunks) == 6  # five tokens and the closing chunk

    (fw,) = global_tracer.spans(name="request/first_write")  # one a request
    assert fw.trace_id == rid
    tl = next(r for r in global_recorder.snapshot()["requests"]
              if r["rid"] == rid)
    assert fw.t_start == tl["first_token_ts"] and fw.t_end >= fw.t_start
    (prefill,) = global_tracer.spans(trace_id=rid, name="request/prefill")
    assert prefill.t_end == fw.t_start  # the TTFT sum's parts meet

    deadline = time.monotonic() + 30  # the loop goes idle after the reply
    while time.monotonic() < deadline:
        turns = global_tracer.spans(name="broker/turn")
        if turns and turns[-1].attrs["next"] == "idle":
            break
        time.sleep(0.01)
    to_step = [t for t in turns if t.attrs["next"] == "step"]
    assert len(to_step) >= 4  # five tokens: a step each, a turn between
    assert turns[-1].attrs["next"] == "idle"  # then nothing is left to run
    spans = global_tracer.spans()
    for t in to_step:
        kids = [s for s in spans if s.parent_id == t.span_id]
        assert [k.name for k in kids] == ["broker/emit", "broker/admit"]
        assert t.t_start <= kids[0].t_start and kids[-1].t_end <= t.t_end
        # no step runs inside a turn: it ends where the next step starts
        assert not any(s.name == "engine/step" and s.thread == t.thread
                       and s.t_start < t.t_end and s.t_end > t.t_start
                       for s in spans)
    admits = global_tracer.spans(name="broker/admit")
    # one idle span a quiet period, however often the wait woke: the one
    # this request ended (the other replica's is still open)
    (idle,) = global_tracer.spans(name="broker/idle")
    assert idle.t_end <= to_step[0].t_start
    assert [a.parent_id for a in admits][0] == idle.span_id


def test_broker_loop_closes_its_spans_when_it_stops_or_dies(devices,
                                                            tiny_model):
    """A loop that stops closes its open ``broker/idle``; one that dies in
    the token hand-off leaves ``broker/emit`` (marked ``error``) and its
    ``broker/turn`` in the ring: the flight dump shows the failing phase."""
    global_tracer.clear()
    broker = RequestBroker(_engine(tiny_model), ServingConfig()).start()
    assert len(broker.submit([1, 2, 3], max_new_tokens=3)
               .result(timeout=90)) == 3
    broker.stop(drain=True, timeout=60)
    idles = global_tracer.spans(name="broker/idle")
    assert idles and idles[-1].t_end is not None
    assert idles[-1].annotation is None

    global_tracer.clear()
    broker = RequestBroker(_engine(tiny_model), ServingConfig())

    def boom(out, now):
        raise RuntimeError("boom")

    broker._dispatch = boom
    handle = broker.start().submit([1, 2, 3], max_new_tokens=3)
    with pytest.raises(Exception):
        handle.result(timeout=90)
    broker.stop(drain=False, timeout=60)
    (emit,) = global_tracer.spans(name="broker/emit")
    (turn,) = global_tracer.spans(name="broker/turn")
    assert emit.attrs == {"error": True} and emit.parent_id == turn.span_id
    assert emit.t_end == turn.t_end and emit.annotation is None


def test_profile_endpoint_409_when_capture_in_flight(http_stack):
    """jax.profiler.trace is process-wide and not reentrant: a second
    overlapping /debug/profile must get a clean 409, never a mid-capture
    crash (ISSUE 13 satellite)."""
    srv, _pool_, port = http_stack
    assert srv.profile_lock.acquire(blocking=False)  # simulate a capture
    try:
        resp, body = _get(port, "/debug/profile?seconds=0.1")
        assert resp.status == 409
        err = json.loads(body)["error"]
        assert err["type"] == "profiler_busy"
        assert "busy" in err["message"]
    finally:
        srv.profile_lock.release()
    # bad-arg validation still runs before the lock is consulted
    resp, _ = _get(port, "/debug/profile?seconds=999")
    assert resp.status == 400


# ---------------------------------------------------------------------------
# flight dump on injected replica kill (subprocess)
# ---------------------------------------------------------------------------


def _child_main():
    """Serve a few requests with ``serving.step=exit@N`` armed: the engine
    thread hard-kills mid-step and the crash hook must leave a dump."""
    from deepspeed_tpu.serving.broker import RequestBroker as RB

    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    eng = InferenceEngineV2(cfg, params, V2Config(**V2))
    broker = RB(eng, ServingConfig()).start()
    h = broker.submit([1, 2, 3], max_new_tokens=32)
    list(h.tokens(timeout=120))
    sys.exit(3)  # only reachable if the kill never fired


def test_injected_kill_dumps_flight_recorder(tmp_path):
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", ""),
        "JAX_PLATFORMS": "cpu", "DSTPU_ACCELERATOR": "cpu",
        "DSTPU_FAULTS": "serving.step=exit@4",
        "DSTPU_FLIGHT_DIR": str(tmp_path),
    })
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "child"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 70, (
        f"expected injected-kill rc 70, got {proc.returncode}\n"
        f"stdout: {proc.stdout}\nstderr: {proc.stderr}")
    dumps = list(tmp_path.glob("flight_*.json"))
    assert dumps, "hard-kill left no flight-recorder dump"
    body = load_dump(str(dumps[0]))
    assert body["meta"]["reason"] == "fault_serving_step"
    # the replica died mid-request: steps were recorded, the request wasn't
    # finalized — exactly the postmortem shape we want
    assert len(body["steps"]) == 3  # kill fired entering the 4th step
    text = render(body)
    assert "flight dump" in text and "engine steps" in text


# ---------------------------------------------------------------------------
# CLI rendering
# ---------------------------------------------------------------------------


def test_cli_renders_dump(tmp_path, capsys):
    from deepspeed_tpu.observability.__main__ import main as cli_main

    rec = FlightRecorder()
    rec.record_request({
        "rid": "req-9", "uid": 1, "replica": "replica0",
        "submit_ts": 10.0, "admit_ts": 10.1, "first_token_ts": 10.3,
        "finish_ts": 10.9, "finish_reason": "length", "tokens_out": 8,
        "ttft_ms": 300.0,
        "spans": [{"name": "request/queue", "t_start": 10.0, "t_end": 10.1},
                  {"name": "request/prefill", "t_start": 10.1, "t_end": 10.3},
                  {"name": "request/decode", "t_start": 10.3, "t_end": 10.9}]})
    rec.record_step({"kind": "decode", "t_start": 0.0, "t_end": 0.004})
    rec.record_event("elastic/start_group", workers=2)
    path = rec.dump(path=str(tmp_path / "dump.json"), reason="manual")
    assert cli_main([path]) == 0
    out = capsys.readouterr().out
    assert "req-9" in out and "request/decode" in out
    assert "decode" in out and "elastic/start_group" in out
    assert "ttft=300.00ms" in out


if __name__ == "__main__" and "child" in sys.argv[1:]:
    _child_main()
