"""Fault-isolated serving fleet tests: out-of-process replica workers,
supervised respawn, chaos injection (reference: DeepSpeed-MII replica
processes + torchelastic-style supervision).

The expensive fixture is ``fleet_pool`` — two real worker processes, each
paying its own JAX import and engine compile — shared by the chaos tests
(each test restores the fleet to 2 healthy replicas before returning).
Everything else (process-group teardown, jitter backoff, wire frames,
supervisor state machine, stale health) is process-free and fast.
"""

import argparse
import http.client
import json
import os
import signal
import socket
import struct
import subprocess
import threading
import time

import jax
import numpy as np
import pytest

from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.serving import (NoReplicaError, ReplicaPool,
                                   ReplicaSupervisor, ServingConfig,
                                   ServingMetrics, create_server)
from deepspeed_tpu.serving.balancer import BalancedHandle
from deepspeed_tpu.serving.server import (add_engine_cli_args,
                                          engine_argv_from_args)
from deepspeed_tpu.serving.transport import (MAX_FRAME, recv_frame,
                                             send_frame)
from deepspeed_tpu.utils.proc import terminate_procs

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
    """Greedy continuation via the plain uncached forward — the reference
    every fleet path (including failover replays) must match."""
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


@pytest.fixture(scope="module")
def flight_dir(tmp_path_factory):
    """Parent-side flight-recorder destination: every worker death must
    leave a postmortem dump here."""
    d = str(tmp_path_factory.mktemp("flight"))
    prev = os.environ.get("DSTPU_FLIGHT_DIR")
    os.environ["DSTPU_FLIGHT_DIR"] = d
    yield d
    if prev is None:
        os.environ.pop("DSTPU_FLIGHT_DIR", None)
    else:
        os.environ["DSTPU_FLIGHT_DIR"] = prev


@pytest.fixture(scope="module")
def fleet_pool(flight_dir):
    """Two out-of-process replica workers under supervision."""
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
    for t in pool.replicas:
        assert t._proc is None or t._proc.poll() is not None


def _fleet_heal(pool, n=2, timeout=180.0):
    """Wait for the supervisor to bring the fleet back to n replicas."""
    wait_until(lambda: len(pool.healthy_replicas()) >= n, timeout=timeout,
               interval=0.2, msg=f"{n} healthy replicas")


def _worker_pids(pool):
    return [t._proc.pid for t in pool.replicas if t._proc is not None]


# ---------------------------------------------------------------------------
# process-group teardown (utils/proc)
# ---------------------------------------------------------------------------


def _spawn_tree():
    """A child (own session) that forks a grandchild and reports its pid."""
    p = subprocess.Popen(
        ["bash", "-c", "sleep 300 & echo $!; wait"],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    gc_pid = int(p.stdout.readline())
    return p, gc_pid


def _alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


def test_terminate_procs_group_reaps_grandchildren():
    p, gc_pid = _spawn_tree()
    assert _alive(gc_pid)
    terminate_procs([p], term_timeout_s=2.0, process_group=True)
    assert p.poll() is not None
    wait_until(lambda: not _alive(gc_pid), timeout=5.0,
               msg="grandchild reaped")
    p.stdout.close()


def test_terminate_procs_direct_signal_orphans_grandchildren():
    """The contrast case process_group=True exists for: direct signals
    reach only the immediate child; the grandchild keeps running."""
    p, gc_pid = _spawn_tree()
    try:
        terminate_procs([p], term_timeout_s=2.0, process_group=False)
        assert p.poll() is not None
        assert _alive(gc_pid), "orphaned grandchild should survive — if it "\
            "doesn't, this platform forwards signals and the test is moot"
    finally:
        try:
            os.kill(gc_pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        p.stdout.close()


def test_terminate_procs_group_fallback_without_session():
    """process_group=True must still work when the child did NOT opt into
    start_new_session (no group led by its pid → direct-signal fallback)."""
    p = subprocess.Popen(["sleep", "300"])
    terminate_procs([p], term_timeout_s=2.0, process_group=True)
    assert p.poll() is not None


# ---------------------------------------------------------------------------
# failover backoff: exponential with decorrelated jitter
# ---------------------------------------------------------------------------


class _FakePool:
    def __init__(self, cfg):
        self.cfg = cfg


def test_decorrelated_jitter_backoff_bounds(monkeypatch):
    cfg = ServingConfig(retry_backoff_s=0.05, retry_backoff_max_s=2.0)
    h = BalancedHandle(_FakePool(cfg), None, 0, {})
    # upper envelope: uniform returns its hi bound → 3x growth, capped
    monkeypatch.setattr("deepspeed_tpu.utils.backoff.random.uniform",
                        lambda lo, hi: hi)
    seq, prev = [], cfg.retry_backoff_s
    for _ in range(8):
        prev = h._backoff(prev)
        seq.append(prev)
    assert seq[0] == pytest.approx(0.15)   # 3 * base
    assert seq[1] == pytest.approx(0.45)
    assert max(seq) == cfg.retry_backoff_max_s  # cap reached and held
    assert seq[-1] == cfg.retry_backoff_max_s
    # lower envelope: uniform returns its lo bound → never below base
    monkeypatch.setattr("deepspeed_tpu.utils.backoff.random.uniform",
                        lambda lo, hi: lo)
    assert h._backoff(1.7) == cfg.retry_backoff_s
    # real draws stay inside [base, cap]
    monkeypatch.undo()
    prev = cfg.retry_backoff_s
    for _ in range(100):
        prev = h._backoff(prev)
        assert cfg.retry_backoff_s <= prev <= cfg.retry_backoff_max_s


# ---------------------------------------------------------------------------
# wire protocol frames
# ---------------------------------------------------------------------------


def test_frame_roundtrip_and_eof():
    a, b = socket.socketpair()
    rfile = b.makefile("rb")
    try:
        lock = threading.Lock()
        send_frame(a, {"op": "submit", "rid": "r1", "prompt": [1, 2]}, lock)
        send_frame(a, {"ev": "hb", "stats": {"busy": False}})
        assert recv_frame(rfile) == {"op": "submit", "rid": "r1",
                                     "prompt": [1, 2]}
        assert recv_frame(rfile) == {"ev": "hb", "stats": {"busy": False}}
        a.close()
        assert recv_frame(rfile) is None  # clean EOF
    finally:
        rfile.close()
        b.close()


def test_frame_truncation_and_oversize_are_errors():
    a, b = socket.socketpair()
    rfile = b.makefile("rb")
    try:
        a.sendall(struct.pack(">I", 100) + b'{"x": 1}')  # 8 of 100 bytes
        a.close()
        with pytest.raises(ConnectionError):
            recv_frame(rfile)
    finally:
        rfile.close()
        b.close()
    a, b = socket.socketpair()
    rfile = b.makefile("rb")
    try:
        a.sendall(struct.pack(">I", MAX_FRAME + 1))
        with pytest.raises(ConnectionError):
            recv_frame(rfile)
    finally:
        rfile.close()
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# supervisor state machine (no processes: scripted liveness)
# ---------------------------------------------------------------------------


class _ScriptedReplica:
    """Duck-typed SubprocessReplica for deterministic supervisor ticks."""

    def __init__(self):
        self.name = "replica0"
        self.generation = 0
        self.consecutive_failures = 0
        self.circuit_open = False
        self.next_respawn_at = 0.0
        self.live = {"down": None, "stopping": False, "connected": True,
                     "alive": True, "pid": 1234, "hb_age": 0.0,
                     "progress_age": 0.0, "busy": False,
                     "broker_healthy": True, "spawn_age": 0.0}
        self.marked = []
        self.respawns = 0

    def liveness(self):
        return dict(self.live)

    def mark_down(self, reason):
        self.marked.append(reason)
        self.live["down"] = reason

    def respawn(self):
        self.respawns += 1
        self.generation += 1
        self.live["down"] = None
        self.live["spawn_age"] = 0.0
        return self


def _sup(cfg=None, metrics=None):
    cfg = cfg or ServingConfig(heartbeat_timeout_s=1.0,
                               hung_replica_timeout_s=5.0,
                               respawn_backoff_s=0.5,
                               respawn_backoff_max_s=4.0,
                               circuit_breaker_threshold=3,
                               respawn_reset_s=2.0)
    return ReplicaSupervisor([], cfg, metrics=metrics)


def test_supervisor_detects_missed_heartbeats():
    m = ServingMetrics()
    sup, r = _sup(metrics=m), _ScriptedReplica()
    r.live["hb_age"] = 0.5
    sup._tick(r)
    assert r.marked == []
    r.live["hb_age"] = 1.5
    sup._tick(r)
    assert r.marked == ["heartbeat_timeout"]
    assert m.fleet["heartbeat_misses"] == 1


def test_supervisor_hung_detection_requires_busy():
    m = ServingMetrics()
    sup, r = _sup(metrics=m), _ScriptedReplica()
    r.live["progress_age"] = 99.0  # idle: stale progress is fine
    sup._tick(r)
    assert r.marked == []
    r.live["busy"] = True
    sup._tick(r)
    assert r.marked == ["hung_replica"]
    assert m.fleet["hung_detected"] == 1


def test_supervisor_detects_dead_broker():
    sup, r = _sup(), _ScriptedReplica()
    r.live["broker_healthy"] = False
    sup._tick(r)
    assert r.marked == ["broker_dead"]


def test_supervisor_backoff_doubles_and_circuit_opens():
    m = ServingMetrics()
    sup, r = _sup(metrics=m), _ScriptedReplica()
    backoffs = []
    for _ in range(2):
        r.mark_down("worker_exited")
        sup._tick(r)  # schedules the respawn
        backoffs.append(r.next_respawn_at - time.monotonic())
        r.next_respawn_at = time.monotonic() - 0.01  # due now
        sup._tick(r)  # fires it
        assert r.live["down"] is None
    assert r.respawns == 2
    assert 0.3 < backoffs[0] <= 0.55     # ~base
    assert 0.8 < backoffs[1] <= 1.05     # ~2x base
    # third consecutive failure hits the threshold: breaker opens
    r.mark_down("worker_exited")
    sup._tick(r)
    assert r.circuit_open
    assert m.fleet["circuit_opens"] == 1
    before = r.respawns
    sup._tick(r)  # open breaker: no further respawns, ever
    assert r.respawns == before


def test_supervisor_healthy_streak_resets_failures():
    sup, r = _sup(), _ScriptedReplica()
    r.consecutive_failures = 2
    r.live["spawn_age"] = 1.0  # not yet respawn_reset_s
    sup._tick(r)
    assert r.consecutive_failures == 2
    r.live["spawn_age"] = 3.0
    sup._tick(r)
    assert r.consecutive_failures == 0


# ---------------------------------------------------------------------------
# worker CLI round-trip: a worker rebuilds the same engine the front would
# ---------------------------------------------------------------------------


def test_engine_argv_roundtrip():
    p = argparse.ArgumentParser()
    add_engine_cli_args(p)
    args = p.parse_args(["--model", "tiny", "--seed", "3", "--spec_mode",
                         "self_draft", "--spec_k", "2",
                         "--enable_prefix_cache", "--num_blocks", "128"])
    p2 = argparse.ArgumentParser()
    add_engine_cli_args(p2)
    args2 = p2.parse_args(engine_argv_from_args(args))
    assert vars(args2) == vars(args)


# ---------------------------------------------------------------------------
# health endpoint: dead replicas report last-known stats, flagged stale
# ---------------------------------------------------------------------------


def test_health_never_raises_reports_stale(devices, tiny_model):
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config

    cfg, params = tiny_model
    v2 = V2Config(max_tokens_per_step=32, max_seqs=4, block_size=8,
                  num_blocks=64, max_blocks_per_seq=8)
    pool = ReplicaPool.build(lambda: InferenceEngineV2(cfg, params, v2),
                             ServingConfig(num_replicas=2))
    pool.start()
    try:
        first = pool.health()
        assert first["status"] == "ok"
        assert all(not r["stale"] for r in first["replicas"])
        assert first["healthy_replicas"] == 2

        def boom():
            raise RuntimeError("engine unreachable")

        pool.replicas[0].prefix_stats = boom  # instance shadow
        h = pool.health()
        assert h["status"] == "ok"  # replica 1 still carries the pool
        entry = h["replicas"][0]
        assert entry["stale"] is True and entry["healthy"] is False
        # last-known stats survive from the pre-failure probe
        assert entry["queue_depth"] == first["replicas"][0]["queue_depth"]
        assert h["replicas"][1]["stale"] is False
        assert h["healthy_replicas"] == 1
        # the metrics pump thread must also survive the broken replica
        time.sleep(0.05)
        assert pool._pump.is_alive()
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# the fleet: out-of-process replicas, chaos, supervised recovery
# ---------------------------------------------------------------------------


def test_fleet_roundtrip_token_identity(fleet_pool, ref_fn):
    for prompt in ([5, 6, 7], [9, 3]):
        h = fleet_pool.submit(prompt, max_new_tokens=12)
        assert list(h.tokens(timeout=180)) == ref_fn(prompt, 12)
        assert h.finish_reason == "length"
    health = fleet_pool.health()
    assert health["status"] == "ok"
    assert health["healthy_replicas"] == 2
    assert all(r["transport"] == "subprocess" and r["pid"]
               for r in health["replicas"])


def test_fleet_hardkill_failover_and_respawn(fleet_pool, ref_fn, flight_dir):
    _fleet_heal(fleet_pool)
    deaths0 = fleet_pool.metrics.fleet["worker_deaths"]
    dumps0 = len(os.listdir(flight_dir))
    h = fleet_pool.submit([4, 4, 2], max_new_tokens=16)
    it = h.tokens(timeout=180)
    got = [next(it) for _ in range(4)]
    victim = fleet_pool.replicas[h.replica_index]
    gen0 = victim.generation
    # chaos: hard os._exit inside the CURRENT worker generation, armed
    # over the wire — fires at its next heartbeat tick
    assert victim.inject_fault({"serving.worker.hardkill": "exit"})
    got += list(it)
    # delivered-prefix skip on a surviving replica: token-identical
    assert got == ref_fn([4, 4, 2], 16)
    assert h.finish_reason == "length"
    # supervisor respawns the slot as the next generation
    _fleet_heal(fleet_pool)
    assert victim.generation > gen0
    assert fleet_pool.metrics.fleet["worker_deaths"] > deaths0
    assert fleet_pool.metrics.fleet["respawns"] >= 1
    # every injected worker death leaves a flight-recorder dump
    wait_until(lambda: len(os.listdir(flight_dir)) > dumps0, timeout=10.0,
               msg="flight dump after worker death")


def test_fleet_hang_detected_by_missed_heartbeats(fleet_pool, ref_fn,
                                                  flight_dir):
    _fleet_heal(fleet_pool)
    misses0 = fleet_pool.metrics.fleet["heartbeat_misses"]
    h = fleet_pool.submit([7, 1, 3], max_new_tokens=16)
    it = h.tokens(timeout=180)
    got = [next(it) for _ in range(3)]
    victim = fleet_pool.replicas[h.replica_index]
    gen0 = victim.generation
    # chaos: wedge the worker's heartbeat thread — the process stays
    # alive and the socket stays open, so ONLY missed-beat supervision
    # can catch it (EOF detection never fires)
    assert victim.inject_fault({"serving.worker.hang": "hang"})
    got += list(it)
    assert got == ref_fn([7, 1, 3], 16)
    # the wedge fires at the worker's next heartbeat tick and the supervisor
    # needs ``heartbeat_timeout_s`` of silence: a worker that had streamed its
    # last tokens by then left the fleet looking whole, and healing returned
    # before anything was detected
    wait_until(lambda: fleet_pool.metrics.fleet["heartbeat_misses"] > misses0,
               timeout=60.0, msg="missed heartbeats counted")
    _fleet_heal(fleet_pool)
    wait_until(lambda: victim.generation > gen0, timeout=180.0,
               msg="the hung worker's slot respawned")
    assert fleet_pool.metrics.fleet["heartbeat_misses"] > misses0


def test_fleet_hung_engine_detected_while_busy(fleet_pool, ref_fn):
    _fleet_heal(fleet_pool)
    hung0 = fleet_pool.metrics.fleet["hung_detected"]
    # warm BOTH replicas first: earlier chaos tests leave respawned
    # generations with cold jit caches, and a legitimate first-compile
    # step (~2s on CPU) must not trip the shrunken threshold below
    seen = set()
    while len(seen) < 2:
        h = fleet_pool.submit([2, 8, 5], max_new_tokens=16)
        assert list(h.tokens(timeout=180)) == ref_fn([2, 8, 5], 16)
        seen.add(h.replica_index)
    # shrink the hung threshold only now — past warmup, so no legitimate
    # first-compile can trip it (cfg is read live by the supervisor)
    fleet_pool.cfg.hung_replica_timeout_s = 2.0
    try:
        # chaos: wedge replica 0's engine loop itself (a stuck compile /
        # hung device).  The site only fires once work is outstanding, so
        # arming while idle is safe: the next request to land there hangs
        # with busy=True and frozen progress while heartbeats keep flowing
        # — only hung-replica supervision can catch it.
        victim = fleet_pool.replicas[0]
        gen0 = victim.generation
        assert victim.inject_fault({"serving.step": "hang"})
        # submit until a stream routes onto the armed replica (round-robin
        # tiebreak over two replicas: a couple of tries at most)
        h = fleet_pool.submit([2, 8, 5], max_new_tokens=16)
        while h.replica_index != 0:
            assert list(h.tokens(timeout=180)) == ref_fn([2, 8, 5], 16)
            h = fleet_pool.submit([2, 8, 5], max_new_tokens=16)
        # the hung stream fails over to replica 1: token-identical replay
        assert list(h.tokens(timeout=300)) == ref_fn([2, 8, 5], 16)
        wait_until(
            lambda: fleet_pool.metrics.fleet["hung_detected"] > hung0,
            timeout=30.0, msg="hung-replica detection")
        _fleet_heal(fleet_pool)
        assert victim.generation > gen0
    finally:
        fleet_pool.cfg.hung_replica_timeout_s = 120.0


def test_http_front_survives_worker_death(fleet_pool, ref_fn):
    _fleet_heal(fleet_pool)
    cfg = fleet_pool.cfg
    srv = create_server(fleet_pool, fleet_pool.metrics, cfg,
                        host="127.0.0.1", port=0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.server_port,
                                          timeout=180)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [6, 5, 4], "max_tokens": 12,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        toks, killed = [], False
        for raw in resp:
            for line in raw.splitlines():
                if not line.startswith(b"data: ") or b"[DONE]" in line:
                    continue
                tok = json.loads(line[6:])["choices"][0].get("token")
                if tok is not None:
                    toks.append(tok)
            if len(toks) >= 3 and not killed:
                killed = True
                with srv._handles_lock:
                    handles = list(srv._handles.values())
                # SIGKILL the worker process group carrying the stream
                # (or any worker, if delivery already outran generation)
                fleet_pool.kill_replica(
                    handles[0].replica_index if handles else 0)
        conn.close()
        assert killed
        assert toks == ref_fn([6, 5, 4], 12)  # stream survived the murder
        # the front itself never blinked: healthz + prometheus live on
        conn = http.client.HTTPConnection("127.0.0.1", srv.server_port,
                                          timeout=30)
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["status"] == "ok"
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "dstpu_serving_replica_worker_deaths" in text
        assert "dstpu_serving_replica_respawns" in text
        conn.close()
        _fleet_heal(fleet_pool)
    finally:
        srv.shutdown()


def test_fleet_graceful_degradation_capacity_signal(fleet_pool):
    _fleet_heal(fleet_pool)
    h = fleet_pool.health()
    assert set(h) >= {"healthy_replicas", "num_replicas", "kv_utilization"}
    assert h["healthy_replicas"] == h["num_replicas"] == 2
    assert 0.0 <= h["kv_utilization"] <= 1.0
    # one replica down → the pool reports reduced capacity but stays ok
    fleet_pool.kill_replica(0)
    h = fleet_pool.health()
    assert h["status"] == "ok" and h["healthy_replicas"] < 2
    _fleet_heal(fleet_pool)


def test_fleet_chaos_soak_and_clean_drain(fleet_pool, ref_fn, flight_dir):
    """The chaos gate: concurrent streams while a worker is hard-killed
    and another has its heartbeat wedged, four of them arriving staggered
    after the kill, while its slot respawns; every stream must deliver the
    exact greedy reference, the fleet must heal with no block left pinned,
    and the final drain must leave zero worker processes."""
    _fleet_heal(fleet_pool)
    dumps0 = len(os.listdir(flight_dir))
    prompts = [[i + 1, i + 2, i + 3] for i in range(10)]
    results, errors = {}, []

    def run(i):
        try:
            h = fleet_pool.submit(prompts[i], max_new_tokens=16)
            results[i] = list(h.tokens(timeout=300))
        except Exception as e:  # noqa: BLE001 — collected and asserted
            errors.append((i, e))

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    for t in threads[:6]:
        t.start()
    time.sleep(0.4)  # let streams get going mid-decode
    fleet_pool.replicas[0].inject_fault({"serving.worker.hardkill": "exit"})
    for t in threads[6:]:
        time.sleep(0.15)
        t.start()
    fleet_pool.replicas[1].inject_fault({"serving.worker.hang": "hang"})
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "soak stream wedged"
    assert not errors, errors
    for i, prompt in enumerate(prompts):
        assert results[i] == ref_fn(prompt, 16), f"stream {i} diverged"
    _fleet_heal(fleet_pool)
    assert len(os.listdir(flight_dir)) > dumps0
    wait_until(lambda: all(t.num_running() == 0
                           for t in fleet_pool.replicas if t.healthy()),
               timeout=60.0, msg="fleet idle")
    assert all(t.prefix_stats().get("pinned_blocks", 0) == 0
               for t in fleet_pool.replicas if t.healthy())
    # drain: every worker process (all generations) must be gone, and the
    # parent must shed the transport fds (sockets + stdout pipes) it held
    pids = _worker_pids(fleet_pool)
    assert pids
    fds_before = len(os.listdir("/proc/self/fd"))
    transport_fds = sum(
        (1 if t._sock is not None and t._sock.fileno() >= 0 else 0)
        + (1 if t._proc is not None and t._proc.stdout is not None
           and not t._proc.stdout.closed else 0)
        for t in fleet_pool.replicas)
    assert transport_fds >= 4  # 2 live workers x (socket + stdout pipe)
    fleet_pool.drain(timeout=60.0)
    for pid in pids:
        wait_until(lambda: not _alive(pid), timeout=10.0,
                   msg=f"worker {pid} reaped")
    for t in fleet_pool.replicas:
        assert t._proc is None or t._proc.poll() is not None
        assert t._sock is None or t._sock.fileno() == -1
        assert t._proc is None or t._proc.stdout is None \
            or t._proc.stdout.closed
    wait_until(lambda: len(os.listdir("/proc/self/fd"))
               <= fds_before - transport_fds,
               timeout=10.0, msg="transport fds released")


# ---------------------------------------------------------------------------
# crash loop → circuit breaker (persistent fault: every generation dies)
# ---------------------------------------------------------------------------


def test_crash_loop_opens_circuit_breaker():
    cfg = ServingConfig(num_replicas=1, replica_transport="subprocess",
                        heartbeat_interval_s=0.2, spawn_timeout_s=300.0,
                        respawn_backoff_s=0.05, respawn_backoff_max_s=0.2,
                        circuit_breaker_threshold=2)
    metrics = ServingMetrics()
    # env-armed faults persist across respawns (unlike protocol-armed
    # ones): generation after generation dies at the spawn site — the
    # definition of a crash loop
    pool = ReplicaPool.build_subprocess(
        WORKER_ARGV, cfg, metrics=metrics,
        extra_env={"DSTPU_FAULTS": "serving.worker.start=exit:71"})
    pool.start()
    try:
        wait_until(lambda: pool.replicas[0].circuit_open, timeout=180.0,
                   interval=0.2, msg="circuit breaker open")
        assert pool.healthy_replicas() == []
        assert metrics.fleet["circuit_opens"] == 1
        assert metrics.fleet["worker_deaths"] >= 2
        assert pool.replicas[0].consecutive_failures == 2
        with pytest.raises(NoReplicaError):
            pool.wait_ready(timeout=0.5)
        with pytest.raises(NoReplicaError):
            pool.submit([1, 2, 3])
        snap = metrics.snapshot()
        assert snap["replica_circuit_opens"] == 1.0
    finally:
        pool.shutdown()
    assert pool.replicas[0]._proc is None or \
        pool.replicas[0]._proc.poll() is not None
