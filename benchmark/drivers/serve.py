"""Driver ``serve``: the HTTP front over one ``InferenceEngineV2``, under load
from the benchmark's own generator.

The server is built as ``deepspeed_tpu/serving/server.py:main`` builds it
(``ReplicaPool.build`` → ``create_server``), in this process, which holds the
chip; the load generator (``benchmark/loadgen.py``) is a child process that
imports neither JAX nor the program and talks HTTP to ``127.0.0.1``.

Weights are made on the chip from the seed in one jitted call: each layer is
initialised by the program's own ``init_params`` and, for a W8A16
configuration, quantized at once by the program's own
``quantize_gemm_weight``, so only int8 codes and scales are ever kept and the
host builds nothing.  The finished tree goes to the engine with
``quantize_bits=0``: its forward dispatches on the leaf's type.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Mapping

import numpy as np

from benchmark import common, loadgen
from benchmark.reference import dense_decoder as reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_params(cfg, seed: int, weight_bits: int, group: int):
    """The whole parameter tree on the device, in the types it is served in,
    from one jitted call.  Layers are made one at a time inside the call
    (``lax.map``), so the bf16 form of more than one layer never exists."""
    import dataclasses

    import jax

    from deepspeed_tpu.inference.quantization import quantize_model_params
    from deepspeed_tpu.models import transformer as tfm

    one = dataclasses.replace(cfg, num_layers=1)

    def layer(key):
        lay = jax.tree.map(lambda a: a[0], tfm.init_params(key, one)["layers"])
        if weight_bits:
            lay = quantize_model_params({"layers": lay}, bits=weight_bits,
                                        group=group)["layers"]
        return lay

    def whole(key):
        k_rest, k_layers = jax.random.split(key)
        params = tfm.init_params(k_rest, one)  # embedding, head, final norm
        params["layers"] = jax.lax.map(
            layer, jax.random.split(k_layers, cfg.num_layers))
        return params

    return jax.jit(whole)(jax.random.PRNGKey(seed))


def build_server(cfg, params, config: Mapping[str, Any]):
    """→ (pool, engine, server, serving config), started."""
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.serving.balancer import ReplicaPool
    from deepspeed_tpu.serving.config import ServingConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.serving.server import create_server

    v2 = V2Config(**config["engine"]["v2"])
    scfg = ServingConfig(**config["engine"]["serving"])
    metrics = ServingMetrics()
    pool = ReplicaPool.build(lambda: InferenceEngineV2(cfg, params, v2),
                             scfg, metrics=metrics)
    engine = pool.replicas[0].engine
    pool.start()
    pool.wait_ready(timeout=scfg.spawn_timeout_s)
    server = create_server(pool, metrics, scfg, port=0,
                           model_name=config["name"])
    threading.Thread(target=server.serve_forever, name="bench-http",
                     daemon=True).start()
    return pool, engine, server, scfg


class SpanCollector:
    """Copies the program's span ring out once a second, so that a long
    window does not wrap it (the ring keeps 8,192 spans)."""

    def __init__(self):
        self.spans: Dict[int, dict] = {}
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-spans")

    def _take(self) -> None:
        from deepspeed_tpu.observability.trace import tracer

        for s in tracer.spans():
            if s.t_end is not None and s.span_id not in self.spans:
                self.spans[s.span_id] = {
                    "name": s.name, "t_start": s.t_start, "t_end": s.t_end,
                    "attrs": dict(s.attrs)}

    def _loop(self) -> None:
        while not self._stop.wait(1.0):
            self._take()

    def start(self) -> "SpanCollector":
        self._thread.start()
        return self

    def finish(self, t0: float, t1: float) -> List[dict]:
        self._stop.set()
        self._thread.join()
        self._take()
        return [s for s in self.spans.values() if t0 <= s["t_end"] < t1]


def check_served(params, model: Mapping[str, Any], sequences, check,
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """Every served token against the plain reference, which reads the whole
    sequence (prompt, then what the server sent) in one uncached pass over
    the same parameters.  Tokens are not compared: with random weights the
    largest logit changes on rounding.  The served token's reference logit
    has to lie within ``check["margin"]`` of the reference's maximum.

    A sequence is padded (causal: the padding changes nothing) to the next
    multiple of ``reference_pad``, so that the longest request of the window
    is read at its own length and the reference is compiled for a few
    lengths only.

    The limit and the readings it was set from are in PERF.md section 2
    (``correct``): over bf16 through 32 layers and int8 weights a right
    program's worst served token lies 0.05-0.13 under the maximum, the top
    two logits lie about 0.2 apart, a wrong token about 4 under.

    With ``control_bits`` (never in a committed configuration: tools and
    tests ask for it) the run is the control's: at the same positions the
    tokens that ``reference/dense_control.py`` puts first at that many bits
    are judged in the served tokens' place, and the served tokens' own
    reading goes to the log."""
    import jax.numpy as jnp

    pad = check["reference_pad"]
    bits = check.get("control_bits", 0)
    if bits:
        from benchmark.reference import dense_control
    worst, exact, checked, control = 0.0, 0, 0, 0.0
    for prompt, served in sequences:
        n = len(prompt) + len(served)
        seq = np.zeros(-(-n // pad) * pad, np.int32)
        seq[:n] = prompt + served
        m, rank = reference.served_margins(params, model, jnp.asarray(seq),
                                           len(prompt))
        m, rank = np.asarray(m)[:len(served)], np.asarray(rank)[:len(served)]
        if not np.isfinite(m).all():
            worst = float("inf")
        worst = max(worst, float(m.max()))
        exact += int((rank == 0).sum())
        checked += len(served)
        if bits:
            c, _ = dense_control.control_margins(
                params, model, jnp.asarray(seq), len(prompt), bits)
            control = max(control, float(np.asarray(c)[:len(served)].max()))
    log(f"reference: {checked} served tokens of {len(sequences)} sequences "
        f"(lengths {[len(p) + len(t) for p, t in sequences]}), "
        f"{exact} are the reference's argmax, worst margin {worst:.4f} "
        f"(allowed {check['margin']})"
        + (f"; THE CONTROL at {bits} bits is judged in their place: worst "
           f"margin {control:.4f}" if bits else ""))
    if bits:
        worst = control
    return {"tokens_checked": checked, "argmax_equal": exact,
            "worst_margin": worst,
            "ok": checked > 0 and worst <= check["margin"]}


def pick_sequences(finished: List[dict], check: Mapping[str, Any],
                   seed: int) -> List[dict]:
    """The window's finished requests that the reference reads, for every
    serving driver: the longest that fits ``reference_len``, and
    ``window_sequences - 1`` others drawn from the seed.  A request is drawn
    by what it is (its stream and index, which fix its prompt), not by where
    it stands among the finished: a request more or fewer at the window's
    edges, which the machine's load decides, moves no other pick."""
    fits = [r for r in finished
            if r["n_prompt"] + len(r["tokens"]) <= check["reference_len"]]
    if not fits or check["window_sequences"] < 1:
        return []
    fits.sort(key=lambda r: (r["stream"], r["index"]))
    longest = max(fits, key=lambda r: r["n_prompt"] + len(r["tokens"]))
    others = sorted(
        (r for r in fits if r is not longest),
        key=lambda r: np.random.default_rng(
            [seed, 0xC4EC, r["stream"], r["index"]]).random())
    return [longest] + others[:check["window_sequences"] - 1]


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] not in loadgen.LOOPS:
        raise ValueError(f"driver serve runs loops {loadgen.LOOPS}, not "
                         f"{traffic['loop']!r}")
    import jax

    compiles = common.start_jax(log)

    cfg, model = common.program_config(config)
    eng = config["engine"]
    t0 = time.monotonic()
    params = make_params(cfg, seed, eng["weight_bits"], eng["weight_group"])
    jax.block_until_ready(params)
    log(f"{config['name']}: {cfg.num_layers} layers, "
        f"{cfg.num_params() / 1e9:.3f} B parameters, W{eng['weight_bits'] or 16}"
        f"A16, made on the device in {time.monotonic() - t0:.1f}s")
    pool, engine, server, scfg = build_server(cfg, params, config)
    port = server.server_port
    total_blocks = engine.total_blocks

    # the configuration's check; a cell whose traffic needs another sample
    # (short outputs: more sequences) says so in its traffic file
    check = {**config["check"], **traffic.get("check", {})}

    # warm-up: one request whose prompt is longer than a step's token budget
    # compiles the mixed step (twice run: chunked prefill), the sampler and
    # the decode step; shapes are static, so these are all there are
    warm = {"prompt": np.random.default_rng([seed, 0xBEEF]).integers(
                1, cfg.vocab_size, size=check["warmup_prompt"]).tolist(),
            "max_tokens": check["warmup_tokens"]}
    rec = loadgen.Record(0xBEEF, 0, len(warm["prompt"]), warm["max_tokens"],
                         due=time.monotonic())
    loadgen.stream_completion(port, warm, rec, None, timeout_s=1100.0)
    if rec.status != "ok":
        raise RuntimeError(f"warm-up request failed: {rec.status}")
    log(f"warm-up request done ({rec.done - rec.due:.1f}s)")

    session = None
    if trace:  # spans round the calls into the program, from outside
        session = common.TraceSession(log)
        engine.step = common.annotated(engine.step, "bench/engine.step")
        engine._fwd = common.annotated(engine._fwd, "bench/_fwd")
        engine._decode_fwd = common.annotated(engine._decode_fwd,
                                              "bench/_decode_fwd")

    # the window, on the clock every process of this machine shares
    t_open = time.monotonic() + traffic["lead_s"] + traffic["ramp_s"]
    t_close = t_open + seconds
    spec = {"traffic": dict(traffic), "seed": seed, "vocab": cfg.vocab_size,
            "port": port, "t_open": t_open, "t_close": t_close,
            "timeout_s": traffic["request_timeout_s"]}
    with tempfile.TemporaryDirectory(prefix="bench-load-") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), spec_path],
            stdout=subprocess.PIPE)
        try:
            collector = SpanCollector().start()
            tracer_thread = session.run_beside(
                t_open + traffic["trace_after_s"],
                traffic["trace_seconds"]) if session else None
            time.sleep(max(0.0, t_open - time.monotonic()))
            setup_s = t_open - t_ready
            log(f"window opens; set-up {setup_s:.1f}s")
            time.sleep(max(0.0, t_close - time.monotonic()))
            peak = common.memory_peak_bytes()
            log("window closed; waiting for the generator")
            out, _ = child.communicate(
                timeout=traffic["request_timeout_s"] + 60.0)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    records = json.loads(out)["records"]
    if tracer_thread:
        tracer_thread.join()
    spans = collector.finish(t_open, t_close)

    # drain and shut down as the server's own main does, then the cache
    # must be whole again
    pool.drain(scfg.drain_timeout_s)
    server.shutdown()
    server.server_close()
    free = engine.free_blocks
    log(f"drained: {free} of {total_blocks} KV blocks free")
    programs_in_window = compiles.between(t_open, t_close)
    del engine, pool, server  # the KV cache goes; the reference needs room
    gc.collect()
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in jax.local_devices()]
    log(f"engine freed: {max(in_use) / 1e9:.2f} GB in use on the device")

    # what the window holds
    if traffic["loop"] == "open":
        in_window = [r for r in records if t_open <= r["due"] < t_close]
    else:
        # a closed loop's request is attempted when it ended in the window
        # (or was still running at its close, which is no failure)
        in_window = [r for r in records if r["status"] != "pending"
                     and t_open <= r["done"] and r["due"] < t_close]
    attempted = len(in_window)
    failed = sum(r["status"] not in ("ok", "cut") for r in in_window)

    # correct: the warm-up request and a few of the window's complete
    # sequences, the longest among them, token by token
    picked = [(warm["prompt"], rec.tokens)] + [
        (r["prompt"], r["tokens"]) for r in pick_sequences(
            [r for r in in_window if r["status"] == "ok"], check, seed)]
    served = check_served(params, model, picked, check, log)
    correct = (served["ok"] and free == total_blocks and failed == 0
               and attempted > 0)
    # every number compared, beside its limit
    checks = {"worst_margin": [served["worst_margin"], check["margin"]],
              "kv_blocks_free": [free, total_blocks],
              "failed_requests": [failed, 0]}

    for r in records:  # prompts were for the check only
        r.pop("prompt", None)
    return {
        "correct": correct,
        "checks": checks,
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "window": {"t_open": t_open, "t_close": t_close, "seconds": seconds},
        "requests": records,
        "spans": spans,
        "compiles_in_window": programs_in_window,
        "memory_peak_bytes": peak,
        "device": dict(device),
        "chips": cell["chips"],
        "trace": session.reduce() if session else None,
    }
