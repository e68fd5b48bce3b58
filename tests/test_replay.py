"""Workload traces + fleet-wide trace stitching (ISSUE 13, cut by ISSUE 60).

* workload schema: JSONL round-trip with hard schema errors, the
  inspector CLI, broker-side live capture (arrivals, prompts, budgets,
  cancels), and the fields a load generator's traffic file needs surviving
  capture -> file -> load;
* cross-process stitching: under the subprocess transport, worker-side
  ``engine/step`` spans and request spans arrive over the heartbeat
  channel and appear in the front's ``/debug/trace`` under the worker's
  own pid track; a mid-stream worker kill yields ONE request timeline
  (same trace id) spanning two worker pids;
* strict Perfetto schema validity of ``/debug/trace`` in both transports.

What a replay under chaos held of the fleet (a hard-killed worker loses no
token) is in ``tests/test_fleet.py``.
"""

import http.client
import json
import random
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability import replay as rp
from deepspeed_tpu.observability import tracer as global_tracer
from deepspeed_tpu.observability.__main__ import main as obs_main
from deepspeed_tpu.serving import ReplicaPool, ServingConfig, create_server

V2 = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
          max_blocks_per_seq=8, dtype="float32")
WORKER_ARGV = ["--model", "tiny", "--seed", "0", "--num_blocks", "64",
               "--max_tokens_per_step", "32", "--max_seqs", "4",
               "--block_size", "8", "--max_blocks_per_seq", "8"]


def wait_until(pred, timeout=30.0, interval=0.05, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def ref_fn(tiny_model):
    """Greedy continuation via the plain uncached forward — the oracle
    every stream (a failed-over one included) must match."""
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


# ---------------------------------------------------------------------------
# workload schema: JSONL round-trip
# ---------------------------------------------------------------------------


def _workload(seed, n):
    """``n`` requests over four shared 12-token templates with unique
    suffixes, seeded gaps and budgets, every fourth cancelled."""
    rng = random.Random(seed)
    templates = [[rng.randrange(1, 250) for _ in range(12)]
                 for _ in range(4)]
    t, out = 0.0, []
    for i in range(n):
        k = rng.randrange(4)
        out.append(rp.WorkloadRequest(
            offset_s=t, prompt=templates[k] + [250 + i, rng.randrange(1, 250)],
            max_new_tokens=rng.randrange(1, 9), template=k,
            cancel_after_s=0.05 * (i + 1) if i % 4 == 0 else None))
        t += rng.expovariate(8.0)
    return {"source": "handmade", "seed": seed, "requests": n}, out


def test_workload_jsonl_roundtrip(tmp_path):
    meta, wl = _workload(seed=3, n=16)
    path = str(tmp_path / "wl.jsonl")
    rp.save_workload(path, wl, meta)
    meta2, back = rp.load_workload(path)
    assert meta2 == meta
    src = sorted(wl, key=lambda r: r.offset_s)
    assert len(back) == len(src)
    for a, b in zip(src, back):
        assert a.prompt == b.prompt
        assert a.max_new_tokens == b.max_new_tokens
        assert abs(a.offset_s - b.offset_s) < 1e-5
        assert (a.cancel_after_s is None) == (b.cancel_after_s is None)


def test_workload_schema_is_strict(tmp_path):
    p = tmp_path / "bad.jsonl"
    # wrong header kind
    p.write_text('{"kind": "nope", "version": 1}\n')
    with pytest.raises(rp.WorkloadError, match="not a workload trace"):
        rp.load_workload(str(p))
    # unknown record key is a hard error, not silently dropped
    hdr = json.dumps({"kind": "dstpu-workload", "version": 1, "meta": {}})
    p.write_text(hdr + '\n{"offset_s": 0, "prompt": [1], "bogus": 2}\n')
    with pytest.raises(rp.WorkloadError, match="bogus"):
        rp.load_workload(str(p))
    # empty / non-token prompts rejected
    p.write_text(hdr + '\n{"offset_s": 0, "prompt": []}\n')
    with pytest.raises(rp.WorkloadError, match="prompt"):
        rp.load_workload(str(p))
    p.write_text(hdr + '\n{"offset_s": 0}\n')
    with pytest.raises(rp.WorkloadError, match="offset_s and prompt"):
        rp.load_workload(str(p))


def test_workload_inspector_cli(tmp_path, capsys):
    meta, wl = _workload(seed=1, n=12)
    path = str(tmp_path / "wl.jsonl")
    rp.save_workload(path, wl, meta)
    assert obs_main(["workload", path]) == 0
    out = capsys.readouterr().out
    assert "requests: 12" in out
    assert "prefix sharing" in out
    assert "source=handmade" in out
    assert "cancels: 3" in out


# ---------------------------------------------------------------------------
# broker-side live capture
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def inproc_pool(devices, tiny_model):
    cfg, params = tiny_model
    scfg = ServingConfig(num_replicas=1, max_queue=32,
                         metrics_interval_s=0.1)
    pool = ReplicaPool.build(
        lambda: InferenceEngineV2(cfg, params, V2Config(**V2)),
        scfg).start()
    yield pool
    pool.shutdown()


def test_capture_records_live_traffic(inproc_pool):
    with rp.WorkloadCapture() as cap:
        h1 = inproc_pool.submit([5, 6, 7], max_new_tokens=4)
        h1.result(timeout=120)
        # fill every seat (max_seqs=4) so the next submit parks in the
        # queue — a queued request can be cancelled deterministically; a
        # running one races its own length finish on a warm engine
        blockers = [inproc_pool.submit([40 + i], max_new_tokens=60)
                    for i in range(4)]
        h2 = inproc_pool.submit([8, 9], max_new_tokens=32)
        h2.cancel()
        for b in blockers:
            b.cancel()
        wait_until(lambda: inproc_pool.replicas[0].num_running() == 0,
                   timeout=60, msg="cancels settle")
    # hooks are inert once the capture context exits
    h3 = inproc_pool.submit([1, 2], max_new_tokens=2)
    h3.result(timeout=120)
    wl = cap.to_workload()
    by_prompt = {tuple(r.prompt): r for r in wl}
    assert len(wl) == 6 and (1, 2) not in by_prompt
    r1, r2 = by_prompt[(5, 6, 7)], by_prompt[(8, 9)]
    assert r1.max_new_tokens == 4 and r1.cancel_after_s is None
    assert r1.offset_s == 0.0 and r2.offset_s >= 0.0
    # cancel_after_s is relative to the request's own submit, not t0
    assert r2.cancel_after_s is not None and r2.cancel_after_s >= 0.0
    meta = cap.meta()
    assert meta["source"] == "capture" and meta["requests"] == 6


def test_captured_trace_holds_what_a_traffic_file_needs(inproc_pool,
                                                        tmp_path):
    """capture -> save_workload -> load_workload keeps, for every request,
    what ``benchmark/loadgen.py`` would need to send it again: when it was
    due, how long its prompt was, its budget, its stop ids, its temperature
    and its tenant."""
    sent = [([3, 1, 4, 1, 5, 9], dict(max_new_tokens=3, stop_token_ids=[249],
                                      temperature=0.7, tenant="acme")),
            ([2, 7], dict(max_new_tokens=5, tenant="zenith")),
            ([6] * 11, dict(max_new_tokens=2, temperature=0.0))]
    with rp.WorkloadCapture() as cap:
        for prompt, kw in sent:
            inproc_pool.submit(prompt, **kw).result(timeout=120)
            time.sleep(0.01)
    path = str(tmp_path / "captured.jsonl")
    rp.save_workload(path, cap.to_workload(), cap.meta())
    meta, back = rp.load_workload(path)
    assert meta["source"] == "capture" and meta["requests"] == len(sent)
    offsets = [r.offset_s for r in back]
    assert offsets[0] == 0.0 and offsets == sorted(offsets)
    assert len(set(offsets)) == len(sent)
    for (prompt, kw), r in zip(sent, back):
        assert r.prompt == prompt
        assert r.max_new_tokens == kw["max_new_tokens"]
        assert r.stop_token_ids == tuple(kw.get("stop_token_ids", ()))
        assert r.temperature == kw.get("temperature")
        assert r.tenant == kw.get("tenant", "default")
        assert r.cancel_after_s is None and r.rid


# ---------------------------------------------------------------------------
# strict Perfetto schema validity (/debug/trace, both transports)
# ---------------------------------------------------------------------------


def _get(port, path, timeout=120):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp, body


def _assert_perfetto_valid(doc):
    """Strict Chrome/Perfetto JSON schema check: required fields per
    event, known phase codes, a process_name metadata event for every pid
    track, and monotonic span nesting per (pid, tid, category)."""
    events = doc["traceEvents"]
    assert events and events[0]["ph"] == "M"
    meta_pids, sample_pids = set(), set()
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e), e
        assert e["ph"] in ("M", "X", "i"), e
        if e["ph"] == "M":
            assert "args" in e and "name" in e["args"]
            meta_pids.add(e["pid"])
            continue
        assert {"ts", "cat", "args"} <= set(e), e
        assert e["ts"] >= 0
        if e["ph"] == "X":
            assert e["dur"] >= 0
        sample_pids.add(e["pid"])
    assert sample_pids <= meta_pids, \
        f"pids without process_name metadata: {sample_pids - meta_pids}"
    # spans on one track+category must nest (a request's phase spans under
    # its root), never partially overlap
    groups = {}
    for e in events:
        if e["ph"] == "X":
            groups.setdefault((e["pid"], e["tid"], e["cat"]), []).append(e)
    eps = 5.0  # µs float slack
    for key, evs in groups.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        ends = []
        for e in evs:
            while ends and ends[-1] <= e["ts"] + eps:
                ends.pop()
            if ends:
                assert e["ts"] + e["dur"] <= ends[-1] + eps, \
                    f"partial overlap on track {key}: {e}"
            ends.append(e["ts"] + e["dur"])
    return events


def test_debug_trace_schema_inprocess(inproc_pool):
    scfg = ServingConfig(num_replicas=1, max_queue=32)
    srv = create_server(inproc_pool, inproc_pool.metrics, scfg)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        h = inproc_pool.submit([2, 7, 1], max_new_tokens=4)
        assert len(h.result(timeout=120)) == 4
        resp, body = _get(srv.server_port, "/debug/trace")
        assert resp.status == 200
        events = _assert_perfetto_valid(json.loads(body))
        cats = {e.get("cat") for e in events if e["ph"] != "M"}
        assert h.rid in cats
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# subprocess fleet: stitching, one-timeline failover
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fleet_pool():
    cfg = ServingConfig(num_replicas=2, replica_transport="subprocess",
                        default_max_tokens=8, max_queue=32,
                        heartbeat_interval_s=0.2, heartbeat_timeout_s=2.0,
                        respawn_backoff_s=0.2, respawn_reset_s=1.0,
                        submit_timeout_s=120.0, spawn_timeout_s=300.0,
                        retry_backoff_s=0.02, retry_backoff_max_s=0.5)
    pool = ReplicaPool.build_subprocess(WORKER_ARGV, cfg)
    pool.start()
    pool.wait_ready()
    yield pool
    pool.shutdown()
    for t in pool.replicas:  # zero leaked worker processes
        assert t._proc is None or t._proc.poll() is not None


def _fleet_heal(pool, n=2, timeout=300.0):
    wait_until(lambda: len(pool.healthy_replicas()) >= n, timeout=timeout,
               interval=0.2, msg=f"{n} healthy replicas")


def _worker_pids_in_trace(trace_id=None):
    spans = global_tracer.spans(trace_id=trace_id)
    return {s.pid for s in spans if s.pid is not None}


def test_fleet_trace_stitching(fleet_pool, ref_fn):
    h = fleet_pool.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    toks = h.result(timeout=120)
    assert toks == ref_fn([3, 1, 4, 1, 5], 6)
    # the worker batches its spans onto heartbeats: wait for the request's
    # worker-side spans AND engine/step spans to land in the front tracer
    wait_until(lambda: any(
        s.pid is not None for s in global_tracer.spans(trace_id=h.rid)),
        timeout=30, msg="worker request spans stitched")
    wait_until(lambda: any(
        s.pid is not None for s in global_tracer.spans(name="engine/step")),
        timeout=30, msg="worker engine/step spans stitched")
    spans = global_tracer.spans(trace_id=h.rid)
    names = {s.name for s in spans}
    # front-side dispatch event + worker-side request phase spans share one
    # trace id: the stitched timeline crosses the process boundary
    assert "request/dispatch" in names
    assert "request" in names and "request/prefill" in names
    worker = [s for s in spans if s.pid is not None]
    assert worker and all(s.process.startswith("replica") or
                          s.process.startswith("worker")
                          for s in worker)
    # /debug/trace over the fleet: strict schema + per-process tracks
    scfg = ServingConfig(num_replicas=2, max_queue=32)
    srv = create_server(fleet_pool, fleet_pool.metrics, scfg)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        resp, body = _get(srv.server_port, "/debug/trace")
        assert resp.status == 200
        events = _assert_perfetto_valid(json.loads(body))
        pids = {e["pid"] for e in events if e["ph"] != "M"}
        assert len(pids) >= 2, "no worker-process track in /debug/trace"
        step_pids = {e["pid"] for e in events
                     if e["ph"] != "M" and e["name"] == "engine/step"}
        # the front pid may legitimately appear too (other tests run
        # in-process engines in this process); what stitching must prove
        # is that WORKER-pid engine/step spans crossed the socket
        import os as _os
        assert step_pids - {_os.getpid()}, \
            "no worker-process engine/step spans in /debug/trace"
    finally:
        srv.shutdown()


def test_fleet_kill_is_one_timeline_across_workers(fleet_pool, ref_fn):
    _fleet_heal(fleet_pool)
    prompt = [9, 8, 7]
    h = fleet_pool.submit(prompt, max_new_tokens=8)
    it = h.tokens(timeout=120)
    got = [next(it)]  # stream started: the request is placed and running
    fleet_pool.kill_replica(h.replica_index, "test_kill")
    got += list(it)  # failover resubmits; prefix is replayed and skipped
    assert got == ref_fn(prompt, 8)
    trace_id = h._kwargs.get("trace_id") or h.rid
    # both workers' request spans carry the SAME trace id: one continuous
    # request timeline across two worker processes
    wait_until(lambda: len(_worker_pids_in_trace(trace_id)) >= 2,
               timeout=60, msg="request timeline spanning two workers")
    spans = global_tracer.spans(trace_id=trace_id)
    assert any(s.name == "request/failover" for s in spans)
    # the killed worker never records its root span (it died mid-request),
    # but its submit event reached the front over an earlier heartbeat:
    # the trace carries both placements' rids under one trace id
    rids = {s.attrs.get("rid") for s in spans if s.attrs.get("rid")}
    assert len(rids) >= 2  # two placements, one trace
    _fleet_heal(fleet_pool)
