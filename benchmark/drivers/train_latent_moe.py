"""Driver ``train_latent_moe``: ``train``'s loop (optimizer steps back to back
through ``deepspeed_tpu.initialize`` → ``engine.train_batch``, the host at most
``in_flight`` steps ahead, each iteration ending in the fetch of an earlier
step's loss) on a model with latent attention, a leading dense layer and a
chip's share of the routed experts, with what that model adds:

* the configuration file's ``program`` section holds the published keys to the
  program's preset (``check_program``), and ``model_of`` hands the reference
  and the FLOP counts the published keys as run;
* every norm's scale is drawn uniform in [0.5, 1.5) from the seed (at 1 a
  norm read from the wrong layer, or ``kv_a``'s left out, would hardly show);
* ``correct`` compares, on the first batch and the seeded parameters, with
  ``benchmark/reference/latent_moe_trainer.py`` (float32: loss, gradients AND
  one AdamW step): the ENGINE'S first-step loss, balance loss and global
  gradient norm (what the timed program itself produced, out of the step's
  metrics); THE PARAMETERS' CHANGE OVER THE TIMED PROGRAM'S FIRST STEP, stack
  by stack, against the reference's gradients put through the reference's
  float32 AdamW step (norm and direction: a state left unchanged reads 1 in
  both, and the direction is that of the timed program's own gradients,
  element by element); the gradient of the engine's own loss function
  (``jax.value_and_grad`` of the ``ModelSpec.loss_fn`` the engine
  differentiates, jitted once) stack by stack: norm and direction; the
  program's router (``moe/dropless.route``) against the reference's float32
  router on the same inputs, at every routed layer; every loss finite, one
  train program, no kernel fallback.  Limits and their readings: the file's
  ``check``;
* THE WINDOW COMES FIRST, the comparison after it, as in the serving drivers:
  while steps are timed the process holds what a user's process holds (the
  engine, its one program, the batches), the peak read at the window's close
  is the trainer's own, and the reference and the second gradient program
  exist only once the engine is freed.  The parameters from before and after
  the first step wait on the host meanwhile;
* the step's counters (``moe_local_rows``, ``moe_rows_max``,
  ``moe_experts_hit``, ``moe_aux_loss``) are read out of the metrics the loss
  is fetched with, and in a traced run the window's time is reduced by kernel
  and scope name (``benchmark/kernel_time.py``) for the per-layer readers:
  the window's, and the step program's whole executions one by one, each to
  be read beside the counters of its OWN step (``step_counters``,
  ``traced_from``: a roofline share's work and time are of the same steps).

``train.py`` is called for the batches and copied for the loop; it is not
edited.
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

from benchmark import (common, kernel_time, latent_moe_flops,
                       trace_reduce)
from benchmark.drivers.train import make_batch
from benchmark.reference import latent_moe_trainer as reference

#: the trained forward's scopes (``models/latent_sparse.py``,
#: ``moe/dropless.py``), outermost first
SCOPES = ("mla_qkv", "mla_attn", "mla_out", "moe_route", "moe_dispatch",
          "moe_experts", "moe_combine", "moe_shared", "moe_aux")
COUNTERS = ("moe_local_rows", "moe_rows_max", "moe_experts_hit",
            "moe_aux_loss")
#: the host span round the loop's one fetch a step
FETCH = "bench/fetch_loss"
#: the stacks the gradients and the first step's updates are compared by
STACKS = ("attention", "dense_mlp", "held_experts", "shared_experts",
          "router", "embedding", "head")
#: faults of the PROGRAM's side that ``check.reference_faults`` may name
#: beside the reference's own: ``state_unchanged``: the comparison is handed
#: the parameters from before the first step as those after it, which is
#: what a trainer whose update never lands would hand it
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
    rs, rope = config["rope_scaling"], cfg.rope_of("full")
    got = (rope.factor, rope.original_max_position_embeddings, rope.beta_fast,
           rope.beta_slow, rope.mscale_all_dim, rope.theta)
    want = (rs["factor"], rs["original_max_position_embeddings"],
            rs["beta_fast"], rs["beta_slow"], rs["mscale_all_dim"],
            config["rope_theta"])
    if got != want or rs["mscale"] != rs["mscale_all_dim"] \
            or rope.attention_factor != 1.0:
        raise ValueError(f"rope_scaling {want} is not the preset's {got}")


def model_of(config: Mapping[str, Any]) -> Dict[str, Any]:
    """The published keys the reference and ``latent_moe_flops`` take, with
    the cuts of ``as_run`` applied and the share and the assumed coefficient
    beside them."""
    model = {k: config[k] for k in config["program"]["model_keys"]}
    as_run = config["as_run"]
    model.update(num_hidden_layers=as_run["num_hidden_layers"],
                 vocab_size=as_run["vocab_size"],
                 experts_held=as_run["n_routed_experts"],
                 first_expert=as_run["first_expert"],
                 aux_loss_alpha=config["assumed"]["aux_loss_alpha"]["value"])
    return model


def draw_norms(params: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every ``scale`` leaf uniform in [0.5, 1.5), in its dtype and
    sharding, from the seed."""
    import jax

    leaves = jax.tree_util.tree_leaves_with_path(params)
    scales = [p for p, _ in leaves if getattr(p[-1], "key", None) == "scale"]
    keys = dict(zip(map(jax.tree_util.keystr, scales),
                    jax.random.split(jax.random.PRNGKey(seed), len(scales))))

    def draw(path, a):
        key = keys.get(jax.tree_util.keystr(path))
        if key is None:
            return a
        return jax.jit(lambda k: jax.random.uniform(
            k, a.shape, jax.numpy.float32, 0.5, 1.5).astype(a.dtype),
            out_shardings=a.sharding)(key)

    return jax.tree_util.tree_map_with_path(draw, params)


def fingerprint(params: Mapping[str, Any]) -> List[float]:
    """A leaf's sum of magnitudes, leaf by leaf: two trees made from one seed
    by one program read the same, bit for bit."""
    import jax
    import jax.numpy as jnp

    total = jax.jit(lambda a: jnp.sum(jnp.abs(a.astype(jnp.float32))))
    return [float(total(leaf)) for leaf in jax.tree.leaves(params)]


def build(config: Mapping[str, Any], seed: int):
    """→ (program config, params on the mesh, the ``ModelSpec`` that holds
    them, the engine's config dict, the topology): ``train.build`` for this
    file's ``program`` section."""
    import jax

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.parallel.topology import MeshTopology
    from deepspeed_tpu.runtime import zero
    from deepspeed_tpu.runtime.config import load_config
    from deepspeed_tpu.runtime.engine import ModelSpec
    from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

    topology.reset_topology()
    overrides = dict(config["overrides"])
    for key in ("mlp_layer_types",):
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
    params = draw_norms(params, seed)
    tile = config["engine"]["loss_tile"]

    def loss_fn(p, b, r):
        return tiled_loss_fn(p, b, cfg, tile_size=tile)

    spec = ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg))
    return cfg, params, spec, ds, topo


# ---------------------------------------------------------------------------
# what ``correct`` compares
# ---------------------------------------------------------------------------


def by_stack(tree: Mapping[str, Any]) -> Dict[str, List[Any]]:
    """A parameter-shaped tree's leaves by stack (:data:`STACKS`)."""
    import jax

    lay = tree["layers"]
    moe = lay["S"]["moe"]
    return {
        "attention": jax.tree.leaves(lay["A"]),
        "dense_mlp": jax.tree.leaves(lay["D"]),
        "held_experts": [moe[k] for k in ("w_gate", "w_in", "w_out")],
        "shared_experts": [moe[k] for k in ("sh_w_gate", "sh_w_in",
                                            "sh_w_out")],
        "router": [moe["router"]],
        "embedding": jax.tree.leaves(tree["embed"]),
        "head": jax.tree.leaves(tree["lm_head"])
        + jax.tree.leaves(tree["final_norm"]),
    }


def compare_gradients(mine: Mapping[str, Any], ref: Mapping[str, Any]
                      ) -> Dict[str, Dict[str, float]]:
    """Stack by stack: the two norms, their relative difference, and one
    less the cosine between the two gradients."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dots(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.stack([jnp.vdot(a, a), jnp.vdot(b, b), jnp.vdot(a, b)])

    out = {}
    a_all, b_all = by_stack(mine), by_stack(ref)
    for stack in STACKS:
        aa, bb, ab = np.sum([np.asarray(dots(a, b), np.float64)
                             for a, b in zip(a_all[stack], b_all[stack])],
                            axis=0)
        out[stack] = {"norm": float(np.sqrt(aa)),
                      "reference": float(np.sqrt(bb)),
                      "norm_rel": float(abs(np.sqrt(aa) - np.sqrt(bb))
                                        / np.sqrt(bb)),
                      "one_less_cos": float(1.0 - ab / np.sqrt(aa * bb))}
    return out


def compare_updates(before: Mapping[str, Any], after: Mapping[str, Any],
                    expected: Mapping[str, Any]
                    ) -> Dict[str, Dict[str, float]]:
    """The parameters' change over one step, stack by stack: ``after -
    before`` (the program's) against ``expected - before`` (the reference's):
    the two norms, their relative difference, and one less the cosine between
    the two changes.  A state left unchanged reads 1 in both."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def dots(p, a, e):
        p = p.astype(jnp.float32)
        a, e = a.astype(jnp.float32) - p, e.astype(jnp.float32) - p
        return jnp.stack([jnp.vdot(a, a), jnp.vdot(e, e), jnp.vdot(a, e)])

    out = {}
    p_all, a_all, e_all = by_stack(before), by_stack(after), by_stack(expected)
    for stack in STACKS:
        aa, ee, ae = np.sum([np.asarray(dots(p, a, e), np.float64)
                             for p, a, e in zip(p_all[stack], a_all[stack],
                                                e_all[stack])], axis=0)
        moved = aa > 0 and ee > 0
        out[stack] = {"norm": float(np.sqrt(aa)),
                      "reference": float(np.sqrt(ee)),
                      "norm_rel": float(abs(np.sqrt(aa) - np.sqrt(ee))
                                        / np.sqrt(ee)) if ee > 0
                      else float("inf"),
                      "one_less_cos": float(1.0 - ae / np.sqrt(aa * ee))
                      if moved else 1.0}
    return out


def compare_router(params, cfg, model, taps, faults) -> Dict[str, float]:
    """The program's ``route`` against the reference's float32 router on the
    same inputs (the reference's own router inputs, rounded to bfloat16, as
    the program holds them), at every routed layer: the largest difference
    of a probability, and the share of choices that differ."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.dropless import route

    def routed(x, w):
        r = route(x, w, cfg)
        return r.experts, r.probs

    mine = jax.jit(routed)
    worst, differ, seen = 0.0, 0, 0
    routers = params["layers"]["S"]["moe"]["router"]
    for layer, (m, _, _) in enumerate(taps):
        x = jnp.asarray(m, jnp.bfloat16)
        experts, probs = mine(x, routers[layer])
        with jax.default_matmul_precision("highest"):
            ref_probs, ref_chosen, _ = jax.jit(
                lambda a, w: reference.router(a, w, model=model,
                                              faults=faults))(
                x.astype(jnp.float32), routers[layer].astype(jnp.float32))
        worst = max(worst, float(jnp.abs(probs - ref_probs).max()))
        differ += int((jnp.sort(experts, -1)
                       != jnp.sort(ref_chosen, -1)).any(-1).sum())
        seen += int(experts.shape[0])
    return {"router_prob_diff": worst,
            "router_rows_differ": differ / max(seen, 1)}


def in_use_bytes() -> int:
    import jax

    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


def fallbacks() -> int:
    """Kernel ring events of this process that say ``fallback``."""
    from deepspeed_tpu.observability.trace import tracer

    return sum(1 for s in tracer.spans()
               if s.name.startswith("kernel/") and s.attrs.get("fallback"))


def kernel_events() -> List[dict]:
    from deepspeed_tpu.observability.trace import tracer

    seen = {}
    for s in tracer.spans():
        if s.name in ("kernel/flash_attention_tiles",
                      "kernel/grouped_matmul_tiles"):
            seen[repr(sorted(s.attrs.items()))] = {"name": s.name, **s.attrs}
    return list(seen.values())


def checks_of(check: Mapping[str, Any], first: Mapping[str, float],
              grads: Mapping[str, Mapping[str, float]],
              router: Mapping[str, float], ref: Mapping[str, Any],
              ref_norm: float, updates: Mapping[str, Mapping[str, float]]
              ) -> Dict[str, List[float]]:
    """name → [number, limit] of everything the reference decides; a number
    over its limit makes the run not correct."""
    out = {
        "loss_rel": [abs(first["loss"] - ref["loss"]) / abs(ref["loss"]),
                     check["loss_rel_tol"]],
        "aux_rel": [abs(first["moe_aux_loss"] - ref["aux"])
                    / max(abs(ref["aux"]), 1e-30), check["aux_rel_tol"]],
        "grad_norm_rel": [abs(first["grad_norm"] - ref_norm) / ref_norm,
                          check["grad_norm_rel_tol"]],
        "router_prob_diff": [router["router_prob_diff"],
                             check["router_prob_tol"]],
        "router_rows_differ": [router["router_rows_differ"],
                               check["router_rows_differ_max"]],
    }
    def limit(key, stack):  # one, or one a stack
        value = check[key]
        return value[stack] if isinstance(value, Mapping) else value

    for stack in STACKS:
        out[f"grad_norm_rel.{stack}"] = [grads[stack]["norm_rel"],
                                         check["stack_norm_rel_tol"]]
        out[f"grad_one_less_cos.{stack}"] = [
            grads[stack]["one_less_cos"],
            limit("stack_one_less_cos_max", stack)]
    for stack in STACKS:  # the timed program's first step, by what it moved
        out[f"update_norm_rel.{stack}"] = [updates[stack]["norm_rel"],
                                           check["update_norm_rel_tol"]]
        out[f"update_one_less_cos.{stack}"] = [
            updates[stack]["one_less_cos"],
            limit("update_one_less_cos_max", stack)]
    return out


def optimizer_of(config: Mapping[str, Any]) -> Dict[str, float]:
    """The numbers of the engine's AdamW step, for the reference's: the
    engine section's, and the defaults the engine takes where it is silent.
    What would make the first step anything but one plain AdamW step is
    refused."""
    ds = config["engine"]["deepspeed"]
    opt, p = ds["optimizer"], ds["optimizer"]["params"]
    off = [k for k in ("scheduler", "gradient_clipping", "fp16") if ds.get(k)]
    if opt["type"] != "AdamW" or p.get("weight_decay") or off:
        raise ValueError(f"the reference steps plain AdamW without decay; "
                         f"the engine section has {opt['type']}, "
                         f"weight_decay {p.get('weight_decay')}, {off}")
    b1, b2 = p.get("betas", (0.9, 0.999))
    return {"lr": p["lr"], "b1": b1, "b2": b2, "eps": p.get("eps", 1e-8)}


def own_gradient(loss_fn, params, input_ids, log):
    """The gradient of the engine's own loss function on one batch: a program
    of its own, because the engine hands out its gradients' norm and the
    parameters they moved, not the gradients."""
    import jax

    t0 = time.monotonic()
    (_, _), mine = jax.jit(jax.value_and_grad(
        lambda p, b: loss_fn(p, {"input_ids": b}, None), has_aux=True)
    )(params, jax.device_put(input_ids))
    jax.block_until_ready(mine)
    log(f"gradient of the loss function ({time.monotonic() - t0:.1f}s)")
    return mine


def reference_side(params, cfg, model, optimizer, input_ids, faults, mine,
                   after, log):
    """The reference (with ``faults``) on the first batch and the parameters
    from before the first step, and everything that is compared with it: →
    (its ``loss``, ``ce`` and ``aux``, the gradients' comparison by stack
    against ``mine``, the routers' comparison, its global gradient norm, the
    comparison by stack of the first step's change of the parameters, ``after
    - params``, with the change its own gradients make through its own AdamW
    step).  ``after`` waits on the host until the reference's stages are
    done."""
    import jax

    t0 = time.monotonic()
    ref = reference.loss_and_grads(params, model, input_ids, faults)
    log(f"reference loss {ref['loss']:.6f} (cross-entropy {ref['ce']:.6f}, "
        f"balance loss {ref['aux']:.6f}) and gradients "
        f"({time.monotonic() - t0:.1f}s)"
        + (f", FAULTS {sorted(faults)}" if faults else ""))
    ref_grads = ref.pop("grads")
    grads = compare_gradients(mine, ref_grads)
    ref_norm = float(np.sqrt(sum(g["reference"] ** 2
                                 for g in grads.values())))
    router = compare_router(params, cfg, model, ref.pop("router"), faults)
    expected = reference.adamw_step(params, ref_grads, **optimizer)
    del ref_grads
    updates = compare_updates(params, jax.device_put(after), expected)
    del expected
    gc.collect()
    return ref, grads, router, ref_norm, updates


class TraceSession(common.TraceSession):
    """``common.TraceSession`` whose reduction also carries the window's time
    by kernel and by scope name (``kernel_time.reduce``), under ``by_name``;
    ``program`` gives the compiled train step's text."""

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
                # the step's executions the window holds whole, for the
                # shares whose work is a step's own
                reduced["by_name"]["steps"] = kernel_time.whole_steps(
                    trace, module, FETCH)
            return reduced
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] != "steps":
        raise ValueError(f"driver train_latent_moe runs loop 'steps', not "
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

    # warm-up: the first step compiles, the second must not
    out = engine.train_batch(engine.place_batch(first))
    first_step = {k: float(out[k]) for k in ("loss", "grad_norm",
                                             *COUNTERS)}
    losses = [first_step["loss"]]
    # what the timed program's first step made of the parameters; it waits
    # on the host for the comparison
    after = None if "state_unchanged" in planted else jax.device_get(
        engine.state.params)
    log(f"first step done, loss {losses[0]:.6f}, balance loss "
        f"{first_step['moe_aux_loss']:.6f}, gradient norm "
        f"{first_step['grad_norm']:.6f}")
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
    done_times, counters, parts = [], [], []
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
    log(f"window closed: {steps} steps in {t_close - t_open:.2f}s, "
        f"peak {peak / 1e9:.2f} GB, {programs} train program(s)")
    mean = dict(zip(COUNTERS, np.mean(counters, axis=0).tolist()))
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
    session = None  # its program's text came from the engine
    spans = [{"name": s.name, "t_end": s.t_end, "attrs": dict(s.attrs)}
             for s in tracer.spans() if s.name == "train/step"]
    tracer.enabled = tracer_was  # the process's own setting again

    # the comparison, once the engine is gone: the parameters from before the
    # first step made again from the seed, the gradient of the engine's own
    # loss function, the reference's loss, gradients, routers and AdamW step
    engine.state = None
    del engine, out, batch, train_batch, place_batch
    gc.collect()
    log(f"the engine is freed: {in_use_bytes() / 1e9:.2f} GB in use")
    cfg, params, spec, _, _ = build(config, seed)
    spec.params = None
    if fingerprint(params) != made:
        raise RuntimeError("the seed made other parameters the second time")
    mine = own_gradient(spec.loss_fn, params, first["input_ids"], log)
    ref, grads, router, ref_norm, updates = reference_side(
        params, cfg, model, optimizer, first["input_ids"], faults, mine,
        jax.device_get(params) if after is None else after, log)
    del mine, params, after

    checks = checks_of(check, first_step, grads, router, ref, ref_norm,
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
        + (f"; OVER THEIR LIMITS: {failed}" if failed else ""))

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
                  # the counters are a replica's: its own tokens' rows
                  "tokens_per_replica": tokens_per_step // topo.dp_world_size,
                  "flops_per_token": latent_moe_flops.train_flops_per_token(
                      model, seq_len, mean["moe_local_rows"]
                      / (tokens_per_step // topo.dp_world_size))},
        "model": model,
        "kernel_events": events,
        "compiles_in_window": compiles.between(t_open, t_close),
        "memory_peak_bytes": peak,
        "device": dict(device),
        "chips": cell["chips"],
        "trace": reduced,
        "spans": spans, "requests": [],
    }
