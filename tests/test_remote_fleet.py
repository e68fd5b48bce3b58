"""Multi-host fleet tests: network transport, fenced registration,
goodput autoscaling, rolling weight swaps (reference: DeepSpeed-MII
multi-node deployments + torchelastic rendezvous fencing).

Fast by construction: the TCP/fencing/failover tests run against
``tests/scripted_worker.py`` — a protocol-exact worker subprocess that
generates tokens from a fixed function instead of a model, so a real
process + real loopback TCP costs ~0.1s instead of a JAX import.  Only
the rolling-swap story and the broker-swap unit pay for real engines.
"""

import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import types

import pytest

from deepspeed_tpu.serving import (Autoscaler, ReplicaPool,
                                   ReplicaSupervisor, ServingConfig,
                                   ServingMetrics)
from deepspeed_tpu.serving.remote import RemoteReplica, WorkerRegistry
from deepspeed_tpu.serving.transport import (FLEET_MAGIC, MAX_FRAME,
                                             PROTO_VERSION, ProtocolError,
                                             recv_frame, send_frame)
from deepspeed_tpu.utils.backoff import (decorrelated_jitter,
                                         exponential_backoff)

from scripted_worker import scripted_tokens

SCRIPTED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "scripted_worker.py")
_LEN = struct.Struct(">I")


def wait_until(pred, timeout=30.0, interval=0.05, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


def _cfg(**over):
    base = dict(num_replicas=2, default_max_tokens=8, max_queue=32,
                heartbeat_interval_s=0.25, heartbeat_timeout_s=3.0,
                lease_ttl_s=2.0, submit_timeout_s=30.0,
                spawn_timeout_s=30.0, retry_backoff_s=0.02,
                retry_backoff_max_s=0.5, supervise_interval_s=0.1)
    base.update(over)
    return ServingConfig(**base)


# ---------------------------------------------------------------------------
# shared backoff policies (utils/backoff)
# ---------------------------------------------------------------------------


def test_exponential_backoff_deterministic():
    assert [exponential_backoff(0.5, 4.0, a) for a in (1, 2, 3, 4, 5)] == \
        [0.5, 1.0, 2.0, 4.0, 4.0]
    assert exponential_backoff(0.5, 4.0, 0) == 0.5  # pre-first clamps
    assert exponential_backoff(0.0, 4.0, 7) == 0.0  # disabled


def test_decorrelated_jitter_bounds_and_growth():
    hi = types.SimpleNamespace(uniform=lambda a, b: b)
    lo = types.SimpleNamespace(uniform=lambda a, b: a)
    # worst-case draw grows 3x per step and is capped
    s = 0.2
    seen = []
    for _ in range(4):
        s = decorrelated_jitter(0.2, 5.0, s, rng=hi)
        seen.append(s)
    assert seen == [pytest.approx(0.6), pytest.approx(1.8),
                    pytest.approx(5.0), pytest.approx(5.0)]
    # best-case draw never dips below base, even from a tiny prev
    assert decorrelated_jitter(0.2, 5.0, 0.01, rng=lo) == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# frame protocol hardening: oversize / garbage / truncation over real TCP
# ---------------------------------------------------------------------------


def _tcp_pair():
    a, b = socket.socketpair()
    return a, b, b.makefile("rb")


def test_recv_frame_rejects_oversized_length():
    a, b, rfile = _tcp_pair()
    try:
        a.sendall(_LEN.pack(MAX_FRAME + 1))
        with pytest.raises(ProtocolError):
            recv_frame(rfile)
    finally:
        a.close(), b.close()


def test_recv_frame_rejects_garbage_payload():
    a, b, rfile = _tcp_pair()
    try:
        junk = b"\xff\xfe{not json"
        a.sendall(_LEN.pack(len(junk)) + junk)
        with pytest.raises(ProtocolError):
            recv_frame(rfile)
    finally:
        a.close(), b.close()


def test_recv_frame_truncated_mid_frame_is_connection_error():
    a, b, rfile = _tcp_pair()
    try:
        a.sendall(_LEN.pack(64) + b"x" * 10)  # promises 64, delivers 10
        a.close()
        with pytest.raises(ConnectionError):
            recv_frame(rfile)
    finally:
        b.close()


def test_recv_frame_clean_eof_returns_none():
    a, b, rfile = _tcp_pair()
    try:
        a.close()
        assert recv_frame(rfile) is None
    finally:
        b.close()


# ---------------------------------------------------------------------------
# registry handshake: magic / version / auth / fencing epochs
# ---------------------------------------------------------------------------


@pytest.fixture
def make_registry():
    created = []

    def make(token=None, **cfg_over):
        cfg = _cfg(num_replicas=1, fleet_token=token, **cfg_over)
        metrics = ServingMetrics()
        reg = WorkerRegistry(cfg, metrics).start()
        slot = RemoteReplica(cfg, "replica0", metrics)
        reg.register_slot(slot)
        slot.start()
        created.append((reg, slot))
        return reg, slot, metrics

    yield make
    for reg, slot in created:
        try:
            slot.stop(drain=False, timeout=1.0)
        except Exception:
            pass
        reg.stop()


def _drop(s):
    """Sever a hand-dialed connection for real: ``makefile`` holds an
    io-ref on the fd, so ``close()`` alone would not send the FIN."""
    try:
        s.shutdown(socket.SHUT_RDWR)
    except OSError:
        pass
    s.close()


def _hello(address, **overrides):
    """Hand-dial the registry; returns (sock, rfile, reply)."""
    host, port = address.rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=5.0)
    frame = {"op": "hello", "magic": FLEET_MAGIC, "version": PROTO_VERSION,
             "name": "replica0", "pid": os.getpid()}
    frame.update(overrides)
    for k in [k for k, v in frame.items() if v is None]:
        del frame[k]
    send_frame(s, frame)
    rfile = s.makefile("rb")
    return s, rfile, recv_frame(rfile)


def test_hello_rejects_bad_magic_version_and_unknown(make_registry):
    reg, _, _ = make_registry()
    for overrides, reason in (
            ({"op": "nonsense"}, "bad_hello"),
            ({"magic": "http/1.1"}, "bad_magic"),
            ({"version": 99}, "version_mismatch"),
            ({"name": "nobody"}, "unknown_worker")):
        s, rf, reply = _hello(reg.address, epoch=1, **overrides)
        assert reply == {"ev": "hello_err", "reason": reason}
        assert rf.read(1) == b""  # clean close after the verdict
        s.close()


def test_hello_auth_token(make_registry):
    reg, slot, _ = make_registry(token="sekrit")
    for bad in (None, "wrong"):
        s, _, reply = _hello(reg.address, epoch=1, token=bad)
        assert reply == {"ev": "hello_err", "reason": "auth_failed"}
        s.close()
    s, _, reply = _hello(reg.address, epoch=1, token="sekrit")
    assert reply == {"ev": "hello_ok", "epoch": 1}
    wait_until(slot.healthy, msg="slot healthy after authed hello")
    s.close()


def test_hello_garbage_counts_protocol_error(make_registry):
    reg, _, metrics = make_registry()
    host, port = reg.address.rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=5.0)
    junk = b"GET / HTTP/1.1\r\n"
    s.sendall(_LEN.pack(len(junk)) + junk)
    wait_until(lambda: metrics.fleet["protocol_errors"] == 1,
               msg="protocol_errors counter")
    assert s.makefile("rb").read(1) == b""  # clean close, no frame back
    s.close()


def test_fencing_epoch_lifecycle(make_registry):
    """One continuous story: grant → duplicate rejected → stale rejected →
    newer epoch fences the live holder → reconnect bumps the epoch →
    zombie's prev_epoch rejected."""
    reg, slot, metrics = make_registry()
    sa, rfa, reply = _hello(reg.address, epoch=5)
    assert reply == {"ev": "hello_ok", "epoch": 5}
    wait_until(slot.healthy, msg="slot healthy after first registration")
    assert slot.epoch == 5

    # same epoch while the holder is live: split-brain, rejected
    s, _, reply = _hello(reg.address, epoch=5)
    assert reply == {"ev": "hello_err", "reason": "duplicate_epoch"}
    s.close()
    # older epoch: stale returnee, rejected
    s, _, reply = _hello(reg.address, epoch=4)
    assert reply == {"ev": "hello_err", "reason": "stale_epoch"}
    s.close()
    assert metrics.fleet["stale_epoch_rejects"] == 2

    # newer epoch wins the slot and severs the old holder
    sb, rfb, reply = _hello(reg.address, epoch=6)
    assert reply == {"ev": "hello_ok", "epoch": 6}
    wait_until(lambda: slot.epoch == 6, msg="slot adopts the newer epoch")
    assert metrics.fleet["fenced"] == 1
    sa.settimeout(5.0)
    assert rfa.read(1) == b""  # the fenced connection is closed
    sa.close()

    # reconnect path: proving the CURRENT epoch earns the next one
    _drop(sb)  # drop the network, as a blip would
    wait_until(lambda: not slot.healthy(), msg="slot notices the drop")
    sc, _, reply = _hello(reg.address, epoch=None, prev_epoch=6)
    assert reply == {"ev": "hello_ok", "epoch": 7}
    wait_until(lambda: slot.epoch == 7, msg="reconnect bumps the epoch")
    # a zombie proving a pre-decision epoch stays out, forever
    s, _, reply = _hello(reg.address, epoch=None, prev_epoch=5)
    assert reply == {"ev": "hello_err", "reason": "stale_epoch"}
    s.close()
    sc.close()
    # counted after the slot is wired, on the registry's thread
    wait_until(lambda: metrics.fleet["registrations"] == 3,
               msg="three registrations counted")


# ---------------------------------------------------------------------------
# lease discipline: network loss holds the slot; expiry escalates ONCE
# ---------------------------------------------------------------------------


def test_lease_holds_slot_then_expires_exactly_once(make_registry):
    reg, slot, metrics = make_registry(lease_ttl_s=0.4)
    sup = ReplicaSupervisor([slot], slot.cfg, metrics=metrics)
    s, _, reply = _hello(reg.address, epoch=1)
    assert reply["ev"] == "hello_ok"
    send_frame(s, {"ev": "hb", "pid": os.getpid(),
                   "stats": {"healthy": True, "busy": False,
                             "queue_depth": 0, "outstanding_tokens": 0,
                             "running": 0, "kv_utilization": 0.0,
                             "progress_age": 0.0, "prefix": {}, "spec": {}}})
    wait_until(lambda: slot.liveness()["lease_remaining"] is not None,
               msg="heartbeat opens the lease")
    _drop(s)  # network loss, not worker death
    wait_until(lambda: slot.liveness()["down"] == "connection_lost",
               msg="reader declares connection_lost")
    # inside the lease: the supervisor holds the slot open
    sup._tick(slot)
    assert metrics.fleet["lease_expiries"] == 0
    assert not slot.lease_escalated
    # past the lease: escalate to death — but only once
    wait_until(lambda: slot.liveness()["lease_remaining"] == 0.0,
               msg="lease expiry")
    sup._tick(slot)
    sup._tick(slot)
    assert metrics.fleet["lease_expiries"] == 1
    assert slot.lease_escalated


# ---------------------------------------------------------------------------
# scripted-worker fleet: loopback TCP, real processes, fake tokens
# ---------------------------------------------------------------------------


class _Fleet:
    def __init__(self, pool):
        self.pool = pool
        self.procs = []  # (name, Popen)

    def spawn(self, name, epoch, **kw):
        argv = [sys.executable, SCRIPTED, "--connect",
                self.pool.registry.address, "--name", name,
                "--epoch", str(epoch)]
        for k, v in kw.items():
            argv += [f"--{k}", str(v)]
        p = subprocess.Popen(argv, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
        self.procs.append((name, p))
        return p


@pytest.fixture
def remote_fleet():
    fleets = []

    def make(workers=2, **cfg_over):
        cfg = _cfg(**cfg_over)
        pool = ReplicaPool.build_remote([], cfg, launch_workers=False)
        pool.start()
        fl = _Fleet(pool)
        fleets.append(fl)
        for i in range(workers):
            fl.spawn(f"replica{i}", 1)
        if workers:
            pool.wait_ready(timeout=15.0)
        return fl

    yield make
    for fl in fleets:
        try:
            fl.pool.shutdown()
        except Exception:
            pass
        for _, p in fl.procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=5.0)


def test_scripted_fleet_roundtrip_membership_prometheus(remote_fleet):
    fl = remote_fleet(workers=2)
    pool = fl.pool
    h = pool.submit([3, 4, 5], max_new_tokens=6)
    assert list(h.tokens(timeout=20.0)) == scripted_tokens([3, 4, 5], 6)
    assert h.finish_reason == "length"
    members = {m["worker"]: m for m in pool.registry.membership()}
    assert set(members) == {"replica0", "replica1"}
    assert all(m["connected"] and m["epoch"] == 1
               for m in members.values())
    assert pool.metrics.fleet["registrations"] >= 2
    # the pump publishes membership; the exposition carries the fleet
    # gauge (per-worker epoch label) and the autoscaler counters
    wait_until(lambda: "dstpu_serving_registry_member"
               in pool.metrics.to_prometheus(),
               timeout=10.0, msg="membership gauge in /metrics")
    expo = pool.metrics.to_prometheus()
    assert 'worker="replica0"' in expo and 'epoch="1"' in expo
    assert "dstpu_serving_autoscale_up" in expo
    assert "dstpu_serving_autoscale_down" in expo
    assert "dstpu_serving_autoscale_blocked" in expo


def test_mid_stream_tcp_drop_fails_over_token_identical(remote_fleet):
    fl = remote_fleet(workers=0)
    pool = fl.pool
    # replica0 severs its own TCP connection after the 3rd token (one
    # shot), then dials back in like a worker riding out a network blip
    fl.spawn("replica0", 1, drop_after_toks=3, tok_delay_s=0.03)
    fl.spawn("replica1", 1, tok_delay_s=0.03)
    pool.wait_ready(timeout=15.0)
    pool.quiesce("replica1")  # force placement onto the dropper
    h = pool.submit([3, 4, 5], max_new_tokens=8)
    time.sleep(0.05)
    pool.resume_replica("replica1")
    # mid-stream TCP drop → failover resubmit → token-identical stream
    assert list(h.tokens(timeout=20.0)) == scripted_tokens([3, 4, 5], 8)
    # the dropped worker reconnects under the NEXT epoch (prev_epoch
    # proof), so the blip is visible in the membership history
    wait_until(lambda: any(m["worker"] == "replica0" and m["epoch"] == 2
                           and m["connected"]
                           for m in pool.registry.membership()),
               timeout=10.0, msg="dropped worker re-registers, epoch bumped")
    # zero leaked streams on either side of the drop
    wait_until(lambda: all(t.outstanding_tokens() == 0
                           for t in pool.replicas),
               timeout=5.0, msg="no outstanding tokens after failover")


def test_worker_sigkill_fails_over_and_lease_expires(remote_fleet):
    fl = remote_fleet(workers=0, lease_ttl_s=0.8)
    pool = fl.pool
    fl.spawn("replica0", 1, tok_delay_s=0.05)
    fl.spawn("replica1", 1, tok_delay_s=0.05)
    pool.wait_ready(timeout=15.0)
    pool.quiesce("replica1")
    h = pool.submit([1, 2], max_new_tokens=8)
    time.sleep(0.12)
    victim = dict(fl.procs)["replica0"]
    os.kill(victim.pid, signal.SIGKILL)
    pool.resume_replica("replica1")
    assert list(h.tokens(timeout=20.0)) == scripted_tokens([1, 2], 8)
    # SIGKILL looks like connection loss; the slot's lease expires and the
    # supervisor escalates exactly once (externally managed: no respawn)
    wait_until(lambda: pool.metrics.fleet["lease_expiries"] >= 1,
               timeout=10.0, msg="lease expiry escalation")
    time.sleep(0.4)
    assert pool.metrics.fleet["lease_expiries"] == 1
    assert pool.healthy_replicas() == [1]
    members = {m["worker"]: m for m in pool.registry.membership()}
    assert members["replica0"]["connected"] is False
    assert members["replica1"]["connected"] is True
    assert victim.poll() is not None  # no zombie worker


def test_stale_epoch_returnee_fenced_and_exits(remote_fleet):
    fl = remote_fleet(workers=2)
    pool = fl.pool
    old = dict(fl.procs)["replica0"]
    # a replacement claims the slot with a newer epoch → the old worker is
    # fenced, its reconnect (prev_epoch=1 < 2) is stale, and it exits 3
    fl.spawn("replica0", 2)
    assert old.wait(timeout=15.0) == 3
    wait_until(lambda: pool.metrics.fleet["fenced"] >= 1,
               timeout=5.0, msg="fence counter")
    wait_until(lambda: pool.metrics.fleet["stale_epoch_rejects"] >= 1,
               timeout=5.0, msg="stale-epoch counter")
    wait_until(lambda: any(m["worker"] == "replica0" and m["epoch"] == 2
                           and m["connected"]
                           for m in pool.registry.membership()),
               timeout=10.0, msg="replacement owns the slot")
    h = pool.submit([9, 9], max_new_tokens=5)
    assert list(h.tokens(timeout=20.0)) == scripted_tokens([9, 9], 5)


def test_remove_replica_concurrent_single_release(remote_fleet):
    """Simultaneous scale-down and crash cleanup both call
    remove_replica; exactly ONE of them owns releasing the slot."""
    fl = remote_fleet(workers=0)
    pool = fl.pool
    results = []
    barrier = threading.Barrier(2)

    def rm():
        barrier.wait()
        results.append(pool.remove_replica("replica1"))

    ts = [threading.Thread(target=rm) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10.0)
    assert sorted(results) == [False, True]
    assert [t.name for t in pool.replicas] == ["replica0"]
    # the epoch book remembers retired names: a late dial-in under the
    # retired name must not be mistaken for a fresh slot
    s, _, reply = _hello(pool.registry.address, name="replica1", epoch=1)
    assert reply == {"ev": "hello_err", "reason": "unknown_worker"}
    s.close()


# ---------------------------------------------------------------------------
# autoscaler control law (fake pool: no processes, no sleep > debounce)
# ---------------------------------------------------------------------------


class _FakeReplica:
    def __init__(self, name, pool=None):
        self.name = name
        self.pool = pool

    def healthy(self):
        return True

    def queue_depth(self):
        # per-replica share of the pool-level knob the tests drive
        if self.pool is None:
            return 0
        return self.pool.queue / max(1, len(self.pool.replicas))

    def outstanding_tokens(self):
        return 0


class _FakePool:
    def __init__(self, n, cfg):
        self.cfg = cfg
        self.metrics = ServingMetrics()
        self.replicas = [_FakeReplica(f"replica{i}", self) for i in range(n)]
        self._quiesced = set()
        self.autoscaler = None
        self.queue = 0
        self.spawn_error = None
        self.spawned, self.retired = [], []

    def healthy_replicas(self):
        return [i for i, t in enumerate(self.replicas) if t.healthy()]

    def queue_depth(self):
        return self.queue

    def spawn_remote_replica(self, name=None, replica_class=None):
        if self.spawn_error is not None:
            raise self.spawn_error
        name = name or f"replica{len(self.replicas)}"
        self.replicas = self.replicas + [_FakeReplica(name, self)]
        self.spawned.append(name)
        return name

    def retire_replica(self, name, drain_timeout_s):
        self.retired.append(name)
        self.replicas = [t for t in self.replicas if t.name != name]
        return True


def _auto(n=1, queue=0, **over):
    cfg = _cfg(autoscale_min=1, autoscale_max=3, scale_up_pressure=10.0,
               scale_up_debounce_s=0.05, scale_down_pressure=1.0,
               scale_down_idle_s=0.05, autoscale_backoff_s=0.01,
               autoscale_backoff_max_s=0.05, autoscale_max_spawn_fails=2,
               drain_timeout_s=1.0, **over)
    pool = _FakePool(n, cfg)
    pool.queue = queue
    return Autoscaler(pool, cfg), pool


def test_autoscaler_debounce_then_up_then_blocked_at_max():
    asc, pool = _auto(n=1, queue=100)
    asc._tick()  # hot, but inside the debounce window: no spawn yet
    assert pool.spawned == [] and asc.decisions["up"] == 0
    time.sleep(0.06)
    asc._tick()
    assert pool.spawned == ["replica1"] and asc.decisions["up"] == 1
    asc._tick()  # fresh hot episode + cooldown: no immediate second spawn
    assert asc.decisions["up"] == 1
    time.sleep(0.06)
    asc._tick()
    assert pool.spawned == ["replica1", "replica2"]
    # now at autoscale_max: a sustained-hot fleet notes "blocked" ONCE
    asc._tick()
    time.sleep(0.06)
    asc._tick()
    asc._tick()
    assert asc.decisions == {"up": 2, "down": 0, "blocked": 1}
    assert pool.metrics.autoscale == asc.decisions


def test_autoscaler_restores_floor_without_debounce():
    asc, pool = _auto(n=0, queue=0)
    asc._tick()  # below autoscale_min: immediate, no debounce, no pressure
    assert pool.spawned == ["replica0"] and asc.decisions["up"] == 1


def test_autoscaler_scale_down_after_sustained_idle():
    asc, pool = _auto(n=3, queue=0)
    asc._tick()  # cold, but inside the idle window
    assert pool.retired == []
    time.sleep(0.06)
    asc._tick()  # retires the newest replica, keeps the warm core
    assert pool.retired == ["replica2"] and asc.decisions["down"] == 1
    time.sleep(0.06)
    asc._tick()  # idle clock restarted after the retire
    time.sleep(0.06)
    asc._tick()
    assert pool.retired == ["replica2", "replica1"]
    for _ in range(3):  # at the floor: never retires below autoscale_min
        time.sleep(0.06)
        asc._tick()
    assert len(pool.replicas) == 1 and asc.decisions["down"] == 2


def test_autoscaler_banned_after_consecutive_spawn_failures():
    asc, pool = _auto(n=1, queue=100)
    pool.spawn_error = RuntimeError("no capacity")
    asc._tick()  # starts the hot clock
    time.sleep(0.06)
    asc._tick()  # strike 1, short cooldown
    assert not asc.banned
    time.sleep(0.06)
    asc._tick()  # strike 2 == autoscale_max_spawn_fails → banned
    assert asc.banned
    blocked = asc.decisions["blocked"]
    pool.spawn_error = None
    for _ in range(3):
        time.sleep(0.06)
        asc._tick()  # banned: no further spawn attempts, ever
    assert pool.spawned == []
    assert asc.decisions["up"] == 0
    assert asc.decisions["blocked"] == blocked


# ---------------------------------------------------------------------------
# rolling weight swaps (real tiny engines, in-process pool)
# ---------------------------------------------------------------------------

V2 = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
          max_blocks_per_seq=8)


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from deepspeed_tpu.models import transformer as tfm
    cfg = tfm.get_config("tiny", dtype="float32")
    return cfg, tfm.init_params(jax.random.PRNGKey(0), cfg)


def _ref(params, cfg, prompt, n):
    import numpy as np

    from deepspeed_tpu.models import transformer as tfm
    seq = np.array([list(prompt)], np.int32)
    for _ in range(n):
        logits = tfm.forward(params, seq, cfg)
        nxt = np.asarray(logits[:, -1].argmax(-1)).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    return seq[0, len(prompt):].tolist()


def test_broker_swap_and_rollback_unit(tiny_model):
    import jax

    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.serving.broker import RequestBroker

    cfg, params = tiny_model
    params_b = tfm.init_params(jax.random.PRNGKey(1), cfg)
    broker = RequestBroker(InferenceEngineV2(cfg, params, V2Config(**V2)),
                           ServingConfig()).start()
    try:
        p = [5, 6, 7]
        out_a = broker.submit(prompt=p, max_new_tokens=6).result(timeout=60)
        assert out_a == _ref(params, cfg, p, 6)
        # a tree that isn't this model's params is refused atomically
        with pytest.raises(ValueError):
            broker.swap_params({"bogus": 1.0})
        out = broker.submit(prompt=p, max_new_tokens=6).result(timeout=60)
        assert out == out_a  # failed swap left the old weights intact
        broker.swap_params(params_b)
        out_b = broker.submit(prompt=p, max_new_tokens=6).result(timeout=60)
        assert out_b == _ref(params_b, cfg, p, 6)
        broker.swap_rollback()
        out = broker.submit(prompt=p, max_new_tokens=6).result(timeout=60)
        assert out == out_a
    finally:
        broker.stop(drain=False, timeout=5.0)


def test_rolling_swap_story(tiny_model, tmp_path):
    """Publish → refuse corrupt → halt-and-rollback on probe mismatch →
    zero-drop successful swap, all against one 2-replica live pool."""
    import jax

    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.serving.rollout import (RolloutError, RolloutHalted,
                                               publish_params, rolling_swap)

    cfg, params = tiny_model
    params_b = tfm.init_params(jax.random.PRNGKey(1), cfg)
    P = [5, 6, 7]
    scfg = ServingConfig(num_replicas=2, default_max_tokens=8,
                         rollout_drain_timeout_s=20.0,
                         rollout_probe_tokens=4,
                         rollout_probe_timeout_s=120.0)
    pool = ReplicaPool.build(
        lambda: InferenceEngineV2(cfg, params, V2Config(**V2)), scfg)
    pool.start()
    try:
        ref_a = _ref(params, cfg, P, 6)
        ref_b = _ref(params_b, cfg, P, 6)
        assert ref_a != ref_b  # distinct weights must be distinguishable
        assert list(pool.submit(P, max_new_tokens=6).tokens(timeout=120)) \
            == ref_a

        d_good = publish_params(params_b, str(tmp_path), "v2")
        d_bad = publish_params(params_b, str(tmp_path), "corrupt")
        with open(os.path.join(d_bad, "model.safetensors"), "r+b") as f:
            f.seek(-1, os.SEEK_END)
            last = f.read(1)[0]
            f.seek(-1, os.SEEK_END)
            f.write(bytes([last ^ 0xFF]))
        # digest mismatch: refused up front, before any replica is touched
        with pytest.raises(RolloutError):
            rolling_swap(pool, d_bad, P)
        assert pool.metrics.fleet.get("worker_deaths", 0) == 0

        # probe mismatch on the FIRST replica: halt, roll back, old
        # weights keep serving on every replica
        with pytest.raises(RolloutHalted):
            rolling_swap(pool, d_good, P, probe_expected=[0, 0, 0, 0])
        assert pool._quiesced == set()
        for _ in range(4):  # hits both replicas (least-outstanding routing)
            assert list(pool.submit(P, max_new_tokens=6)
                        .tokens(timeout=120)) == ref_a

        # zero-drop: streams in flight when the rollout starts complete on
        # the old weights — a swap never splices generations into a stream
        inflight = [pool.submit(P, max_new_tokens=12) for _ in range(4)]
        summary = rolling_swap(pool, d_good, P)
        ref_a12 = _ref(params, cfg, P, 12)
        for h in inflight:
            assert list(h.tokens(timeout=120)) == ref_a12
        assert sorted(summary["swapped"]) == ["replica0", "replica1"]
        assert summary["probe_tokens"] == \
            _ref(params_b, cfg, P, scfg.rollout_probe_tokens)
        assert pool._quiesced == set()
        for _ in range(4):  # the whole fleet now serves the new weights
            assert list(pool.submit(P, max_new_tokens=6)
                        .tokens(timeout=120)) == ref_b
    finally:
        pool.shutdown()
