"""Driver ``train_swa_moe``: ``train_latent_moe``'s loop (optimizer steps back
to back through ``deepspeed_tpu.initialize`` → ``engine.train_batch``, the host
at most ``in_flight`` steps ahead, the window first and the comparison after
it with the engine freed) on a model of window and full attention layers
mixed, a gate on the attention's output, norms on both sides of each branch,
a leading dense layer, a chip's share of sigmoid-routed experts and a router
bias that a RULE moves, with what that model adds:

* the step's metrics carry ``moe_expert_counts`` (routed layers x all the
  router's experts), fetched with the loss: the rule's input, the load's
  ``max / mean`` a step, and, for ``correct``, the counts of the first step;
* ``correct`` compares, on the first batch and the seeded parameters, with
  ``benchmark/reference/gated_swa_moe_trainer.py`` (float32): everything
  ``train_latent_moe`` compares but the balance loss (there is none), AND the
  bias: after the timed program's first step every routed layer's
  ``router_bias`` is the reference's rule applied to the counts THAT STEP
  reported (``bias_rule_diff``: exact), those counts are the reference's but
  for the assignments that rounding moves (``counts_moved``: the share of
  assignments that sit elsewhere; ``bias_entries_differ``: the share of
  biases that then step the other way), and it has no moment in the
  optimizer's state; the gradient norm, which the reference takes without
  it, says it is not in the norm.  Limits and their readings: the file's
  ``check``;
* the window's end reads the largest ``|router_bias|`` off the engine's
  parameters;
* every norm's scale is drawn uniform in [0.5, 1.5) from the seed
  (``train_latent_moe.draw_norms``), and those the file names are then
  multiplied (``scale_norms``, ``assumed.norm_factors``): seeded attention
  averages thousands of keys, and at a post-branch norm of unit scale that
  one vector is half of what the seeded router sees; it collapses, and the
  step's time then follows the seed.

``train_latent_moe``'s helpers are called, not edited; its loop is copied.
"""

from __future__ import annotations

import gc
import glob
import os
import shutil
import time
from collections import deque
from typing import Any, Callable, Dict, List, Mapping, Optional

import numpy as np

from benchmark import common, kernel_time, swa_moe_train_flops, trace_reduce
from benchmark.drivers.train import make_batch
from benchmark.drivers.train_latent_moe import (
    FETCH, STACKS, compare_gradients, compare_updates, draw_norms, fallbacks,
    fingerprint, in_use_bytes, kernel_events, optimizer_of, own_gradient)
from benchmark.reference import gated_swa_moe_trainer as reference

#: the trained forward's scopes (``models/transformer.py``,
#: ``models/mixed_ffn.py``, ``moe/dropless.py``); the gate's and the q/k
#: norm's lie inside ``attn`` and come first
SCOPES = ("attn_gate", "qk_norm", "attn", "mlp", "moe_route", "moe_dispatch",
          "moe_experts", "moe_combine", "moe_shared", "embed")
COUNTERS = ("moe_local_rows", "moe_rows_max", "moe_experts_hit")
#: faults of the PROGRAM's side that ``check.reference_faults`` may name
#: beside the reference's own (``train_latent_moe.PROGRAM_FAULTS``)
PROGRAM_FAULTS = ("state_unchanged",)


def check_program(config: Mapping[str, Any], cfg) -> None:
    """The file's published keys against the program's preset as run."""
    prog = config["program"]
    for key, attr in prog["published"].items():
        want = (config["as_run"][key] if key in config["reduced"]
                else config[key])
        if getattr(cfg, attr) != want:
            raise ValueError(
                f"configuration {config['name']}: the file says {key} = "
                f"{want}, the program's preset gives {getattr(cfg, attr)}")
    for attr, want in prog["implied"].items():
        got = getattr(cfg, attr)
        if (list(got) if isinstance(got, tuple) else got) != want:
            raise ValueError(f"configuration {config['name']}: the program's "
                             f"{attr} is {got!r}, the model implies {want!r}")
    for key in prog["must_be_off"]:
        if config.get(key):
            raise ValueError(f"the program has no switch for {key}")
    for key in prog["must_be_one"]:  # no group limit on the choice
        if config[key] != 1:
            raise ValueError(f"the program routes in one group; {key} is "
                             f"{config[key]}")
    as_run = config["as_run"]
    kinds = [t.split("_")[0] for t in as_run["layer_types"]]
    first = as_run["first_layer"]
    if as_run["layer_types"] != config["layer_types"][
            first:first + as_run["num_hidden_layers"]] \
            or kinds != list(cfg.layer_kinds):
        raise ValueError(f"as_run.layer_types are not the published layers "
                         f"{first} on, or not the program's {cfg.layer_kinds}")
    if cfg.mlp_layer_types.count("dense") != as_run["num_dense_layers"]:
        raise ValueError("the program's dense layers are not as_run's")


def model_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The published keys the reference and ``swa_moe_train_flops`` take, with
    the cuts of ``as_run`` applied and the share beside them."""
    model = {k: config[k] for k in config["program"]["model_keys"]}
    as_run = config["as_run"]
    model.update(num_hidden_layers=as_run["num_hidden_layers"],
                 num_dense_layers=as_run["num_dense_layers"],
                 layer_types=as_run["layer_types"],
                 vocab_size=as_run["vocab_size"],
                 experts_held=as_run["num_experts"],
                 first_expert=as_run["first_expert"])
    return model


def scale_norms(params: Dict[str, Any], factors: Mapping[str, float]
                ) -> Dict[str, Any]:
    """The scales of the named norms (``final_norm``; of stack "A"
    ``ln1_post``, ``ln2_post``, and ``q_norm`` and ``k_norm`` inside
    ``attn``) times their factor, in their
    dtype and sharding (``assumed.norm_factors`` in the file says which and
    why)."""
    import jax

    def times(node, factor):
        a = node["scale"]
        return {"scale": jax.jit(
            lambda t: (t.astype(jax.numpy.float32) * factor).astype(t.dtype),
            out_shardings=a.sharding)(a)}

    params = dict(params)
    A = dict(params["layers"]["A"])
    A["attn"] = dict(A["attn"])
    for name, factor in factors.items():
        at = params if name in params else \
            A["attn"] if name in A["attn"] else A
        at[name] = times(at[name], factor)
    return {**params, "layers": {**params["layers"], "A": A}}


def build(config: Mapping[str, Any], seed: int):
    """→ (program config, params on the mesh, the ``ModelSpec`` that holds
    them and names the rule-moved leaves, the engine's config dict, the
    topology)."""
    import jax

    from deepspeed_tpu.models import mixed_ffn
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.runtime.config import load_config
    from deepspeed_tpu.runtime.engine import ModelSpec
    from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

    topology.reset_topology()
    overrides = dict(config["overrides"])
    for key in ("mlp_layer_types", "layer_types"):
        overrides[key] = tuple(overrides[key])
    cfg = tfm.get_config(config["preset"], **overrides)
    check_program(config, cfg)
    ds = config["engine"]["deepspeed"]
    ds_cfg = load_config(ds)
    topo = MeshTopology.from_config(ds_cfg.mesh)
    with zero.Init(topo, stage=ds_cfg.zero_optimization.stage) as init:
        params = init.init_sharded(lambda k: tfm.init_params(k, cfg),
                                   tfm.param_axes(cfg),
                                   jax.random.PRNGKey(seed))
    params = scale_norms(draw_norms(params, seed),
                         config["assumed"]["norm_factors"]["value"])
    tile = config["engine"]["loss_tile"]

    def loss_fn(p, b, r):
        return tiled_loss_fn(p, b, cfg, tile_size=tile)

    spec = ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg),
                     **mixed_ffn.spec_rules(params, cfg))
    return cfg, params, spec, ds, topo


# ---------------------------------------------------------------------------
# what ``correct`` compares
# ---------------------------------------------------------------------------


def _bias(tree) -> np.ndarray:
    return np.asarray(tree["layers"]["S"]["moe"]["router_bias"], np.float32)


def compare_router(params, cfg, model, taps, faults) -> Dict[str, float]:
    """The program's ``route`` against the reference's float32 router on the
    same inputs (the reference's own router inputs, rounded to bfloat16, as
    the program holds them) under the same bias, at every routed layer: the
    largest difference of a score, and the share of rows whose choice
    differs."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.dropless import route

    def routed(x, w, b):
        r = route(x, w, cfg, b)
        return r.experts, r.probs

    mine = jax.jit(routed)
    worst, differ, seen = 0.0, 0, 0
    moe = params["layers"]["S"]["moe"]
    for layer, (m, _, _) in enumerate(taps):
        x = jnp.asarray(m, jnp.bfloat16)
        w, b = moe["router"][layer], moe["router_bias"][layer]
        experts, probs = mine(x, w, b)
        with jax.default_matmul_precision("highest"):
            ref_probs, ref_chosen, _ = jax.jit(
                lambda a, w_, b_: reference.router(a, w_, b_, model=model,
                                                   faults=faults))(
                x.astype(jnp.float32), w.astype(jnp.float32),
                b.astype(jnp.float32))
        worst = max(worst, float(jnp.abs(probs - ref_probs).max()))
        differ += int((jnp.sort(experts, -1)
                       != jnp.sort(ref_chosen, -1)).any(-1).sum())
        seen += int(experts.shape[0])
    return {"router_prob_diff": worst,
            "router_rows_differ": differ / max(seen, 1)}


def compare_bias(before, after, expected, expected_own, counts, ref_counts,
                 moments: int) -> Dict[str, float]:
    """The routers' biases over the timed program's first step.  ``after``:
    the engine's; ``expected_own``: the reference's step fed the counts the
    ENGINE's step reported; ``expected``: fed the reference's own."""
    step = float(np.abs(expected - before).max())
    return {
        "bias_rule_diff": float(np.abs(after - expected_own).max()),
        "bias_entries_differ": float(
            (np.abs(after - expected) > 0.25 * max(step, 1e-12)).mean()),
        "counts_moved": float(np.abs(counts - ref_counts).sum()
                              / (2.0 * ref_counts.sum())),
        "bias_moments": float(moments),
    }


def checks_of(check: Mapping[str, Any], first: Mapping[str, float],
              grads: Mapping[str, Mapping[str, float]],
              router: Mapping[str, float], bias: Mapping[str, float],
              ref: Mapping[str, Any], ref_norm: float,
              updates: Mapping[str, Mapping[str, float]]
              ) -> Dict[str, List[float]]:
    """name → [number, limit] of everything the reference decides; a number
    over its limit makes the run not correct."""
    out = {
        "loss_rel": [abs(first["loss"] - ref["loss"]) / abs(ref["loss"]),
                     check["loss_rel_tol"]],
        "grad_norm_rel": [abs(first["grad_norm"] - ref_norm) / ref_norm,
                          check["grad_norm_rel_tol"]],
        "router_prob_diff": [router["router_prob_diff"],
                             check["router_prob_tol"]],
        "router_rows_differ": [router["router_rows_differ"],
                               check["router_rows_differ_max"]],
        "bias_rule_diff": [bias["bias_rule_diff"], check["bias_rule_tol"]],
        "bias_entries_differ": [bias["bias_entries_differ"],
                                check["bias_entries_differ_max"]],
        "counts_moved": [bias["counts_moved"], check["counts_moved_max"]],
        "bias_moments": [bias["bias_moments"], 0],
    }

    def limit(key, stack):  # one, or one a stack
        value = check[key]
        return value[stack] if isinstance(value, Mapping) else value

    for stack in STACKS:
        out[f"grad_norm_rel.{stack}"] = [grads[stack]["norm_rel"],
                                         limit("stack_norm_rel_tol", stack)]
        out[f"grad_one_less_cos.{stack}"] = [
            grads[stack]["one_less_cos"],
            limit("stack_one_less_cos_max", stack)]
    for stack in STACKS:  # the timed program's first step, by what it moved
        out[f"update_norm_rel.{stack}"] = [updates[stack]["norm_rel"],
                                           limit("update_norm_rel_tol", stack)]
        out[f"update_one_less_cos.{stack}"] = [
            updates[stack]["one_less_cos"],
            limit("update_one_less_cos_max", stack)]
    return out


def reference_side(params, cfg, model, optimizer, input_ids, faults, mine,
                   after, first_counts, moments, log):
    """The reference (with ``faults``) on the first batch and the parameters
    from before the first step, and everything that is compared with it: →
    (its ``loss``, the gradients' comparison by stack against ``mine``, the
    routers' comparison, the biases', its global gradient norm, the
    comparison by stack of the first step's change of the parameters, ``after
    - params``, with the change its own gradients make through its own AdamW
    step and its own rule)."""
    import jax

    t0 = time.monotonic()
    ref = reference.loss_and_grads(params, model, input_ids, faults)
    log(f"reference loss {ref['loss']:.6f} and gradients "
        f"({time.monotonic() - t0:.1f}s)"
        + (f", FAULTS {sorted(faults)}" if faults else ""))
    ref_grads = ref.pop("grads")
    grads = compare_gradients(mine, ref_grads)
    ref_norm = float(np.sqrt(sum(g["reference"] ** 2
                                 for g in grads.values())))
    router = compare_router(params, cfg, model, ref.pop("router"), faults)
    expected = reference.first_step(params, ref_grads, ref["counts"], model,
                                    faults, **optimizer)
    own = reference.bias_after_step(  # fed the counts the ENGINE reported
        _bias(params), _bias(ref_grads), first_counts, model, faults,
        **optimizer)
    del ref_grads
    bias = compare_bias(_bias(params), _bias(after), _bias(expected),
                        own, first_counts, ref["counts"], moments)
    updates = compare_updates(params, jax.device_put(after), expected)
    del expected
    gc.collect()
    return ref, grads, router, bias, ref_norm, updates


class TraceSession(common.TraceSession):
    """``train_latent_moe.TraceSession`` under this model's scopes."""

    def __init__(self, log, program: Callable[[], str]):
        super().__init__(log)
        self.program = program

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
                text = self.program()
                module = text.split("HloModule ", 1)[1].split(
                    ",", 1)[0].split()[0]
                reduced["by_name"] = kernel_time.reduce(trace, {
                    module: kernel_time.scopes_of_text(text, SCOPES)})
                reduced["by_name"]["steps"] = kernel_time.whole_steps(
                    trace, module, FETCH)
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def load_of(counts: np.ndarray) -> float:
    """The busiest expert's assignments over the mean, mean over the routed
    layers of one step."""
    c = np.asarray(counts, np.float64)
    return float((c.max(-1) / c.mean(-1)).mean())


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] != "steps":
        raise ValueError(f"driver train_swa_moe runs loop 'steps', not "
                         f"{traffic['loop']!r}")
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.observability.trace import tracer

    compiles = common.start_jax(log)
    check = config["check"]
    faults = frozenset(check.get("reference_faults", ()))
    planted, faults = faults & set(PROGRAM_FAULTS), faults - set(PROGRAM_FAULTS)
    optimizer = optimizer_of(config)
    tracer_was = tracer.enabled
    tracer.enabled = True  # the kernels' ring events are read below
    tracer.clear()

    cfg, params, spec, ds, topo = build(config, seed)
    model = model_of(config)
    seq_len = traffic["seq_len"]
    log(f"{config['name']}: {cfg.num_layers} layers, "
        f"{cfg.num_params() / 1e9:.4f} B parameters, made on the device")
    rows = (ds["train_micro_batch_size_per_gpu"]
            * ds.get("gradient_accumulation_steps", 1) * topo.dp_world_size)
    if rows != traffic["rows"] * topo.dp_world_size:  # rows a chip
        raise ValueError(f"the traffic's {traffic['rows']} rows a step a "
                         f"chip are not the engine's {rows} over "
                         f"{topo.dp_world_size}")
    first = make_batch(seed, 0, rows, seq_len, cfg.vocab_size)
    made = fingerprint(params)  # the comparison makes them again, afterwards

    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=ds,
                                               topo=topo)
    spec.params = None  # the caller's copy goes; the engine has its own
    del params
    gc.collect()
    tokens_per_step = rows * seq_len
    bias_shape = (cfg.mlp_layer_types.count("sparse"), cfg.num_experts)
    moments = sum(1 for a in jax.tree.leaves(engine.state.opt_state)
                  if getattr(a, "shape", None) == bias_shape)

    # warm-up: the first step compiles, the second must not
    out = engine.train_batch(engine.place_batch(first))
    first_step = {k: float(out[k]) for k in ("loss", "grad_norm", *COUNTERS)}
    first_counts = np.asarray(out["moe_expert_counts"]).astype(np.int64)
    losses = [first_step["loss"]]
    # what the timed program's first step made of the parameters; it waits
    # on the host for the comparison
    after = None if "state_unchanged" in planted else jax.device_get(
        engine.state.params)
    log(f"first step done, loss {losses[0]:.6f}, gradient norm "
        f"{first_step['grad_norm']:.6f}, busiest expert over the mean "
        f"{load_of(first_counts):.3f}")
    for i in range(1, 1 + traffic["warmup_steps"]):
        losses.append(float(engine.train_batch(engine.place_batch(
            make_batch(seed, i, rows, seq_len, cfg.vocab_size)))["loss"]))
    step0 = len(losses)
    kernel_fallbacks = fallbacks()
    events = kernel_events()
    # the ring's ``train/step`` span is the traced run's; an untraced window
    # records nothing
    tracer.enabled = bool(trace)
    tracer.clear()

    session = None
    if trace:  # the compiled step's text gives the trace its scopes
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            engine.place_batch(first).placed)
        session = TraceSession(log, lambda: engine._train_step.lower(
            engine.state, shapes).compile().as_text())
    train_batch, place_batch = engine.train_batch, engine.place_batch
    if trace:
        train_batch = common.annotated(train_batch, "bench/train_batch")
        place_batch = common.annotated(place_batch, "bench/place_batch")
    in_flight = traffic["in_flight"]
    pending: deque = deque()
    done_times, counters, loads, parts = [], [], [], []
    gc.collect()
    pauses, began = [], [0.0]

    def timed(phase, info):  # a collection inside the window is the user's too
        if phase == "start":
            began[0] = time.monotonic()
        else:
            pauses.append(time.monotonic() - began[0])

    gc.callbacks.append(timed)
    trace_from = traffic["trace_after_s"] if session else float("inf")
    trace_to = float("inf")  # set when the profiler starts
    traced_from = None  # the steps fetched before the profiler started
    t_open = time.monotonic()
    setup_s = t_open - t_ready
    log(f"window opens; set-up {setup_s:.1f}s")

    def fetch(out):
        losses.append(float(out["loss"]))  # the one fetch: all the metrics
        counters.append([out[k] for k in COUNTERS])
        loads.append(load_of(out["moe_expert_counts"]))
        done_times.append(time.monotonic())

    step = step0
    while True:
        t0 = time.monotonic()
        now = t0 - t_open
        if now >= trace_from:  # between two steps, on this thread
            session.start()
            trace_from, trace_to = float("inf"), now + traffic["trace_seconds"]
            traced_from = len(counters)
        elif now >= trace_to:
            session.stop()
            trace_to = float("inf")
        t1 = time.monotonic()
        batch = make_batch(seed, step, rows, seq_len, cfg.vocab_size)
        t2 = time.monotonic()
        batch = place_batch(batch)
        t3 = time.monotonic()
        pending.append(train_batch(batch))
        t4 = time.monotonic()
        step += 1
        if len(pending) > in_flight:
            with jax.profiler.TraceAnnotation(FETCH):
                fetch(pending.popleft())
        t5 = time.monotonic()
        parts.append((t1 - t0, t2 - t1, t3 - t2, t4 - t3, t5 - t4))
        if t5 - t_open >= seconds:
            break
    while pending:  # the window ends in the fetch of the last step's loss
        fetch(pending.popleft())
    t_close = time.monotonic()
    gc.callbacks.remove(timed)
    if trace_to != float("inf"):  # the window was shorter than the trace
        session.stop()
    steps = step - step0
    peak = common.memory_peak_bytes()  # the trainer's: nothing else has run
    programs = engine._train_step._cache_size()
    bias_abs_max = float(np.abs(_bias(engine.state.params)).max())
    log(f"window closed: {steps} steps in {t_close - t_open:.2f}s, "
        f"peak {peak / 1e9:.2f} GB, {programs} train program(s)")
    mean = dict(zip(COUNTERS, np.mean(counters, axis=0).tolist()))
    mean["moe_load_max_over_mean"] = float(np.mean(loads))
    mean["moe_bias_abs_max"] = bias_abs_max
    log(f"counters, means over {len(counters)} steps: {mean}")
    gaps = np.diff(done_times) * 1e3
    parts_ms = np.asarray(parts) * 1e3
    worst = int(parts_ms.sum(1).argmax())
    names = ("profiler", "make_batch", "place_batch", "train_batch", "fetch")
    log(f"step to step, ms: median {np.median(gaps):.2f}, 90th percentile "
        f"{np.percentile(gaps, 90):.2f}, longest {gaps.max():.2f}; the "
        f"host's iteration, ms, median "
        f"{dict(zip(names, np.round(np.median(parts_ms, 0), 2).tolist()))}, "
        f"the longest (iteration {worst + 1} of {len(parts)}) "
        f"{dict(zip(names, np.round(parts_ms[worst], 2).tolist()))}; "
        f"{len(pauses)} garbage collections in the window, the longest "
        f"{max(pauses, default=0.0) * 1e3:.1f} ms")
    reduced = session.reduce() if session else None
    if reduced and reduced.get("by_name", {}).get("busy_s"):
        t = reduced["by_name"]  # PERF.md section 5's table, off the log
        log("busy time by scope and by kernel, % of the traced window's: "
            + str({k.rsplit("/", 1)[-1]: round(100 * v / t["busy_s"], 2)
                   for part in (t["scope_s"], t["kernel_s"])
                   for k, v in sorted(part.items())}))
    session = None  # its program's text came from the engine
    spans = [{"name": s.name, "t_end": s.t_end, "attrs": dict(s.attrs)}
             for s in tracer.spans() if s.name == "train/step"]
    tracer.enabled = tracer_was  # the process's own setting again

    # the comparison, once the engine is gone: the parameters from before the
    # first step made again from the seed, the gradient of the engine's own
    # loss function, the reference's loss, gradients, routers, counts, AdamW
    # step and rule
    engine.state = None
    del engine, out, batch, train_batch, place_batch
    gc.collect()
    log(f"the engine is freed: {in_use_bytes() / 1e9:.2f} GB in use")
    cfg, params, spec, _, _ = build(config, seed)
    spec.params = None
    if fingerprint(params) != made:
        raise RuntimeError("the seed made other parameters the second time")
    mine = own_gradient(spec.loss_fn, params, first["input_ids"], log)
    ref, grads, router, bias, ref_norm, updates = reference_side(
        params, cfg, model, optimizer, first["input_ids"], faults, mine,
        jax.device_get(params) if after is None else after, first_counts,
        moments, log)
    del mine, params, after

    checks = checks_of(check, first_step, grads, router, bias, ref, ref_norm,
                       updates)
    finite = bool(np.isfinite(losses).all())
    checks["losses_not_finite"] = [float((~np.isfinite(losses)).sum()), 0]
    checks["train_programs"] = [float(programs), 1]
    checks["kernel_fallbacks"] = [float(kernel_fallbacks), 0]
    failed = sorted(k for k, (v, lim) in checks.items()
                    if not (np.isfinite(v) and v <= lim))
    log(f"first-step loss {losses[0]:.6f} against reference "
        f"{ref['loss']:.6f}; gradient norm {first_step['grad_norm']:.6f} "
        f"against {ref_norm:.6f}; by stack, gradient "
        + ", ".join(f"{s} {g['norm']:.5f}/{g['reference']:.5f} "
                    f"(1-cos {g['one_less_cos']:.2e})"
                    for s, g in grads.items())
        + "; the first step's change of the parameters "
        + ", ".join(f"{s} {u['norm']:.5f}/{u['reference']:.5f} "
                    f"(1-cos {u['one_less_cos']:.2e})"
                    for s, u in updates.items())
        + f"; the bias {bias}"
        + (f"; OVER THEIR LIMITS: {failed}" if failed else ""))

    # a model with rule-moved leaves reduces under GSPMD (the engine's
    # explicit reduction stacks scalars): its counters are the whole step's
    local = mean["moe_local_rows"] / tokens_per_step
    return {
        "correct": not failed,
        "checks": checks,
        "attempted": steps,
        "failed": 0 if finite else int((~np.isfinite(losses)).sum()),
        "setup_s": setup_s,
        "window": {"t_open": t_open, "t_close": t_close,
                   "seconds": t_close - t_open},
        "train": {"steps": steps, "tokens_per_step": tokens_per_step,
                  "done_times": done_times, "in_flight": in_flight,
                  "seq_len": seq_len, "rows": rows, "counters": mean,
                  # step by step, and the first whose fetch the trace holds
                  "step_counters": [dict(zip(COUNTERS, map(float, c)))
                                    for c in counters],
                  "traced_from": traced_from,
                  # the tokens the counters count: the whole step's
                  "tokens_per_replica": tokens_per_step,
                  "flops_per_token":
                      swa_moe_train_flops.train_flops_per_token(
                          model, seq_len, local)},
        # ``first_k_dense_replace``: what ``latent_moe_flops.grouped_roofline``
        # (the reader of ``train_moe_gemm_roofline_pct``) calls the leading
        # dense layers
        "model": {**model,
                  "first_k_dense_replace": model["num_dense_layers"]},
        "kernel_events": events,
        "compiles_in_window": compiles.between(t_open, t_close),
        "memory_peak_bytes": peak,
        "device": dict(device),
        "chips": cell["chips"],
        "trace": reduced,
        "spans": spans, "requests": [],
    }
