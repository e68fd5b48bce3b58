"""Driver ``serve_moe``: driver ``serve`` for a sparse-expert model.

The same server (``ReplicaPool.build`` → ``create_server`` over one
``InferenceEngineV2``), load generator, span collection and weight builder as
``drivers/serve.py``, imported from it; what differs is what a sparse-expert
configuration adds:

* the file's expert keys and what its ``model_type`` implies (both listed
  under the file's ``program`` key, so the next family is a new file) are
  checked against the program's configuration, as ``common.program_config``
  checks the dense ones;
* the q/k norm's scales are drawn from the seed (``make_params``), so that a
  program without the norm cannot pass for the right one;
* ``correct`` is decided by ``benchmark/reference/moe_decoder.py``, three
  times: the served tokens' margins (``serve.py``'s rule); outside the timed
  window, the **logits of the engine's own step programs** on a seeded sample
  of sequences (chunked prefill, then decode through the paged cache and the
  int8 experts) against the reference's one uncached float32 pass over the
  same codes (``check_logits``); and the program's router against the
  reference's, directly (``check_router``), because the logits cannot see
  the precision the router was computed in;
* the traced run also reduces the trace by kernel and scope name
  (``benchmark/kernel_time.py``) for the ``moe_*`` per-layer metrics.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import numpy as np

from benchmark import common, kernel_time, loadgen, trace_reduce
from benchmark.drivers import serve
from benchmark.reference import moe_decoder as reference

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def program_config(config: Mapping[str, Any]):
    """``common.program_config`` plus what a sparse-expert configuration adds,
    read from the file's ``program`` key so that the next family is a new
    file and no edit here: ``published`` (expert key → attribute of the
    program's ``TransformerConfig``), ``implied`` (what ``model_type`` means
    without a key: attribute → value) and ``must_be_off`` (published keys the
    program has no switch for).  → (the program's configuration, the
    published sizes as run, for the reference)."""
    cfg, model = common.program_config(config)
    program = config["program"]
    for key, attr in program["published"].items():
        if getattr(cfg, attr) != config[key]:
            raise ValueError(
                f"configuration {config['name']}: the file says {key} = "
                f"{config[key]}, the program's preset gives "
                f"{getattr(cfg, attr)}")
        model[key] = config[key]
    for attr, value in program["implied"].items():
        if getattr(cfg, attr) != value:
            raise ValueError(
                f"configuration {config['name']}: model_type "
                f"{config['model_type']} needs {attr} = {value}, the "
                f"program's preset gives {getattr(cfg, attr)}")
    for key in program["must_be_off"]:
        if config.get(key):
            raise ValueError(f"configuration {config['name']}: {key} = "
                             f"{config[key]} is not something the program "
                             f"computes")
    return cfg, model


def make_params(cfg, seed: int, bits: int, group: int):
    """``serve.make_params`` (random weights made on the device, every norm
    scale 1), then the q/k norm's scales drawn from ``seed``, uniform in
    [0.5, 1.5): with unit scales and random projections the norm divides by
    almost exactly 1, and a program that left it out would read like the
    right one (measured: PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    params = serve.make_params(cfg, seed, bits, group)
    attn = params["layers"]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        if name in attn:
            old = attn[name]["scale"]
            attn[name]["scale"] = jax.random.uniform(
                jax.random.fold_in(jax.random.PRNGKey(seed), 0x9A + i),
                old.shape, jnp.float32, 0.5, 1.5).astype(old.dtype)
    return params


#: the routed FFN's scopes (``moe/dropless.py``)
MOE_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")


class StepProgram:
    """A jitted step program of the engine, called inside a profiler span
    ``name`` (as ``common.annotated`` does), that remembers the shapes of its
    arguments so that its compiled text can be read afterwards: the trace
    names operations by their HLO text, which carries no scope; the compiled
    program's instructions do."""

    def __init__(self, fn: Callable, name: str):
        self.__wrapped__ = fn
        self.name = name
        self.shapes = None

    def __call__(self, *args):
        import jax

        if self.shapes is None:
            self.shapes = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        with jax.profiler.TraceAnnotation(self.name):
            return self.__wrapped__(*args)

    def scopes(self) -> Tuple[str, Dict[str, str]]:
        """→ (the program's module name as the trace has it, instruction →
        scope).  Compiles the program again, from the persistent cache."""
        compiled = self.__wrapped__.lower(*self.shapes).compile()
        text = compiled.as_text()
        module = text.split("HloModule ", 1)[1].split(",", 1)[0].split()[0]
        return module, kernel_time.scopes_of_text(text, MOE_SCOPES)


class TraceSession(common.TraceSession):
    """``common.TraceSession`` whose reduction also carries the time by
    kernel and scope name (``kernel_time.reduce``), under ``by_name``."""

    def __init__(self, log, programs: List[StepProgram]):
        super().__init__(log)
        self.programs = programs

    def reduce(self) -> Optional[dict]:
        try:
            files = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                return None
            self.log(f"trace: {os.path.getsize(files[0]) / 1e6:.1f} MB")
            trace = trace_reduce.load(files[0])
            reduced = trace_reduce.reduce(trace)
            if reduced is not None:
                scope_of = dict(p.scopes() for p in self.programs
                                if p.shapes is not None)
                reduced["by_name"] = kernel_time.reduce(trace, scope_of)
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def check_served(params, model, sequences, pad_to: int, margin: float,
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """``serve.check_served`` against this driver's reference (that function
    names its reference module, so it is copied): every served token's
    reference logit within ``margin`` of the reference's maximum."""
    import jax.numpy as jnp

    worst, exact, checked = 0.0, 0, 0
    for prompt, served in sequences:
        seq = np.zeros(pad_to, np.int32)  # causal: the padding changes nothing
        seq[:len(prompt) + len(served)] = prompt + served
        m, rank = reference.served_margins(params, model, jnp.asarray(seq),
                                           len(prompt))
        m, rank = np.asarray(m)[:len(served)], np.asarray(rank)[:len(served)]
        worst = max(worst, float(m.max()) if np.isfinite(m).all()
                    else float("inf"))
        exact += int((rank == 0).sum())
        checked += len(served)
    log(f"reference: {checked} served tokens of {len(sequences)} sequences, "
        f"{exact} are the reference's argmax, worst margin {worst:.4f} "
        f"(allowed {margin})")
    return {"tokens_checked": checked, "argmax_equal": exact,
            "worst_margin": worst, "ok": checked > 0 and worst <= margin}


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]
               ) -> List[Tuple[List[int], List[int], list]]:
    """A seeded sample of sequences through the (drained) engine's own step
    programs with a logit tap on: → [(prompt, tokens, [(position, logits)])].
    One prompt is longer than a step's token budget, so prefill is chunked;
    every sequence then decodes ``logit_tokens - 1`` steps through the paged
    cache, all in one batch."""
    from benchmark.logit_tap import LogitTap

    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    tap = LogitTap(engine)
    try:
        uids = [engine.put(p, max_new_tokens=check["logit_tokens"])
                for p in prompts]
        out = engine.generate_all(burst=1)  # step by step: the tapped path
    finally:
        tap.remove()
    return [(p, out[u][len(p):], tap.logits[u])
            for p, u in zip(prompts, uids)]


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The engine's step-program logits against the reference's full forward
    over the same codes, row by row (a row: one sequence at one position,
    the largest |difference| over the vocabulary).

    Two bounds, both from the configuration's ``check`` (sized on the chip;
    the reasons are in the configuration file and PERF.md section 6):
    ``logit_tol_median`` on the median row (bf16 activations and int8
    experts through every layer move every row alike, and so does a
    systematic fault: an expert left out, gates renormalised, a wrong
    layer's codes, a missing norm), and ``logit_tol`` on the worst row (a
    local fault: a stale cache block, a wrong position).  No row is excused
    for a router tie: at 64 experts a flip between the 8th and 9th
    probabilities swaps two experts of nearly equal weight and moves a logit
    by less than bf16 rounding does (measured; the tier-1 tests, at 8 and 16
    experts in float32, do skip by the reference's router margin)."""
    import jax.numpy as jnp

    pad_to = check["reference_len"]
    errs = []
    for prompt, tokens, rows in tapped:
        seq = np.zeros(pad_to, np.int32)
        seq[:len(prompt) + len(tokens)] = prompt + tokens
        want = np.asarray(reference.logits(params, model, jnp.asarray(seq)))
        errs += [float(np.abs(row - want[pos]).max()) for pos, row in rows]
    errs = np.asarray(errs)
    median, worst = float(np.median(errs)), float(errs.max())
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"])
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(p) for p, _, _ in tapped]}); |engine - reference| median "
        f"{median:.4f} (allowed {check['logit_tol_median']}), worst "
        f"{worst:.4f} (allowed {check['logit_tol']}); quartiles "
        f"{np.percentile(errs, [25, 50, 75, 90]).round(4).tolist()}")
    return {"rows": len(errs), "median": median, "worst": worst,
            "ok": bool(ok)}


def check_router(params, model, cfg, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The one computation the configuration states in float32, compared
    directly: the program's ``route`` (the function every step body calls,
    jitted here on the device) against the reference's router, both on what
    block ``router_layer``'s router reads in the reference's pass over the
    first tapped sequence, rounded to the engine's activation type.  The
    logit check cannot see a router in bfloat16 (a flip between the 8th and
    9th of 64 probabilities moves a logit less than rounding does); this can:
    ``router_tol`` bounds the largest relative difference of a probability,
    and float32 on the chip reads orders of magnitude under what a bfloat16
    logit does (sized on the chip, PERF.md section 6).  The experts chosen
    must be the reference's wherever its margin exceeds that tolerance."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.dropless import route

    prompt, tokens, _ = tapped[0]
    seq = np.zeros(check["reference_len"], np.int32)
    seq[:len(prompt) + len(tokens)] = prompt + tokens
    layer = check["router_layer"]
    m = reference.router_input(params, model, jnp.asarray(seq), layer)
    m = m[:len(prompt) + len(tokens)].astype(jnp.dtype(cfg.dtype))
    w_router = params["layers"]["moe"]["router"][layer]
    got = jax.jit(lambda x, w: route(x, w, cfg))(m, w_router)
    p, top, idx, margin = reference.router(
        m, w_router, top_k=model["num_experts_per_tok"],
        norm_topk=bool(model["norm_topk_prob"]))
    p, top, idx, margin = (np.asarray(a) for a in (p, top, idx, margin))
    rel = float((np.abs(np.asarray(got.probs) - p) / p).max())
    clear = margin > check["router_tol"] * top[:, -1]
    same = bool((np.sort(np.asarray(got.experts)[clear], -1)
                 == np.sort(idx[clear], -1)).all())  # as sets: order may tie
    gate = float((np.abs(np.asarray(got.weights)[clear] - top[clear])
                  / top[clear]).max())
    ok = (np.isfinite(rel) and rel <= check["router_tol"] and same
          and gate <= check["router_tol"] and clear.mean() > 0.9)
    log(f"router: block {layer}, {len(p)} positions; probabilities differ "
        f"from the reference's by {rel:.2e} of their size at most, the top-"
        f"{idx.shape[1]} gates by {gate:.2e} (allowed "
        f"{check['router_tol']:.0e}); experts "
        f"{'equal' if same else 'DIFFER'} on the {int(clear.sum())} "
        f"positions whose margin is clear")
    return {"positions": len(p), "prob_rel": rel, "gate_rel": gate,
            "experts_equal": same, "ok": bool(ok)}


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] not in loadgen.LOOPS:
        raise ValueError(f"driver serve_moe runs loops {loadgen.LOOPS}, not "
                         f"{traffic['loop']!r}")
    import jax

    compiles = common.start_jax(log)

    cfg, model = program_config(config)
    eng = config["engine"]
    t0 = time.monotonic()
    params = make_params(cfg, seed, eng["weight_bits"], eng["weight_group"])
    jax.block_until_ready(params)
    log(f"{config['name']}: {cfg.num_layers} layers, {cfg.num_experts} "
        f"experts (top {cfg.moe_top_k}), {cfg.num_params() / 1e9:.3f} B "
        f"parameters, W{eng['weight_bits'] or 16}A16, made on the device in "
        f"{time.monotonic() - t0:.1f}s")
    pool, engine, server, scfg = serve.build_server(cfg, params, config)
    port = server.server_port
    total_blocks = engine.total_blocks

    # warm-up: one request whose prompt is longer than a step's token budget
    # compiles the mixed step (twice run: chunked prefill), the sampler and
    # the decode step; shapes are static, so these are all there are
    check = config["check"]
    warm = {"prompt": np.random.default_rng([seed, 0xBEEF]).integers(
                1, cfg.vocab_size, size=check["warmup_prompt"]).tolist(),
            "max_tokens": check["warmup_tokens"]}
    rec = loadgen.Record(0xBEEF, 0, len(warm["prompt"]), warm["max_tokens"],
                         due=time.monotonic())
    loadgen.stream_completion(port, warm, rec, None, timeout_s=1100.0)
    if rec.status != "ok":
        raise RuntimeError(f"warm-up request failed: {rec.status}")
    log(f"warm-up request done ({rec.done - rec.due:.1f}s)")

    fallbacks = kernel_fallbacks(since=t_ready)  # of the programs just traced

    session = None
    if trace:  # spans round the calls into the program, from outside
        engine.step = common.annotated(engine.step, "bench/engine.step")
        engine._fwd = StepProgram(engine._fwd, "bench/_fwd")
        engine._decode_fwd = StepProgram(engine._decode_fwd,
                                         "bench/_decode_fwd")
        session = TraceSession(log, [engine._fwd, engine._decode_fwd])

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
            collector = serve.SpanCollector().start()
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
    reduced = session.reduce() if session else None  # reads the programs

    # outside the window: the engine's own step programs on a seeded sample,
    # their logits tapped; then the engine goes, the reference needs room
    t0 = time.monotonic()
    tapped = tap_logits(engine, cfg, seed, check)
    fallbacks += kernel_fallbacks(since=t0)  # of the tapped decode program
    free_after = engine.free_blocks
    log(f"logit sample served in {time.monotonic() - t0:.1f}s; "
        f"{free_after} of {total_blocks} KV blocks free")
    del engine, pool, server
    gc.collect()

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

    # correct: step-program logits against the reference; and the warm-up
    # request and a few of the window's complete sequences that fit the
    # reference's length (``serve.pick_sequences``), token by token
    agree = check_logits(params, model, tapped, check, log)
    routed = check_router(params, model, cfg, tapped, check, log)
    picked = [(warm["prompt"], rec.tokens)] + [
        (r["prompt"], r["tokens"]) for r in serve.pick_sequences(
            [r for r in in_window if r["status"] == "ok"], check, seed)]
    served = check_served(params, model, picked, check["reference_len"],
                          check["margin"], log)
    if fallbacks:
        log(f"{fallbacks} grouped or mixed GEMM call(s) fell back to XLA")
    correct = (agree["ok"] and routed["ok"] and served["ok"]
               and free == total_blocks
               and free_after == total_blocks and failed == 0
               and attempted > 0 and fallbacks == 0)

    for r in records:  # prompts were for the check only
        r.pop("prompt", None)
    return {
        "correct": correct,
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
        "model": model,
        "engine": dict(eng),
        "trace": reduced,
    }


def kernel_fallbacks(since: float) -> int:
    """GEMM calls of the programs traced since ``since`` (the tracer's
    clock, ``time.monotonic``) that gave way to XLA: the ring's
    ``kernel/*_tiles`` events with ``fallback``.  Read right after the
    programs are traced: the ring keeps the newest 8,192 spans."""
    from deepspeed_tpu.observability.trace import tracer

    return sum(1 for s in tracer.spans()
               if s.name.startswith("kernel/") and s.attrs.get("fallback")
               and s.t_start >= since)
