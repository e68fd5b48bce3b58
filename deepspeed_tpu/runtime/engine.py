"""The training engine.

Capability analogue of the reference's ``runtime/engine.py``
(``DeepSpeedEngine:235`` — forward:2675 / backward:3066 / step:3241) with a
functional core: one jitted ``train_step`` that fuses forward, backward,
gradient accumulation, ZeRO-sharded reduction, loss scaling, clipping and the
optimizer update into a single XLA program.  The imperative DeepSpeed surface
(``engine.train_batch``, ``save_checkpoint`` …) is a thin shell holding the
current ``TrainState``.

Where the reference hand-schedules overlap (IPG buckets, side streams,
`stage_1_and_2.py:1125`), here the schedule is emergent: gradients carry the
optimizer-state sharding, so XLA lowers the DP reduction to
reduce-scatter + sharded update + all-gather — ZeRO-1/2 — and stage-3 param
sharding makes the per-layer all-gathers part of the scanned program.
"""

from __future__ import annotations

import collections.abc
import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..accelerator import get_accelerator
from ..observability.trace import tracer
from ..parallel.topology import MeshTopology, set_topology
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .config import DeepSpeedTPUConfig, ResolvedBatchConfig
from .config_utils import ConfigError
from .loss_scaler import (LossScaleState, grads_finite, init_loss_scale,
                          scale_loss, unscale_grads, update_loss_scale)
from .lr_schedules import create_scheduler
from .optimizers import create_optimizer, default_weight_decay_mask
from .zero.sharding import (rules_for_optimizer, rules_for_params,
                            sharding_for_tree)

LossFn = Callable[..., Tuple[jax.Array, Dict[str, jax.Array]]]


class LazyMetrics(collections.abc.Mapping):
    """Per-step metrics whose device→host transfer is deferred to first read.

    Blocking on every step's scalars would serialize the host loop with the
    device (each ``float()`` drains the async dispatch queue), exposing the
    next batch's H2D copy and dispatch latency.  Returning this instead lets
    callers that ignore or batch-read metrics keep the pipeline full; any
    access materializes all values as plain floats.

    Deliberately NOT a dict subclass: CPython's C fast paths (json.dumps,
    PyDict_Merge, .copy) read a dict subclass's raw storage without calling
    the overridden accessors and would silently see an empty dict.  As a
    Mapping, ``dict(m)`` / ``{**m}`` go through keys()+__getitem__ correctly
    and json.dumps fails loudly (convert with ``dict(m)`` first).
    """

    def __init__(self, device_metrics: Dict[str, jax.Array]):
        self._dev: Optional[Dict[str, jax.Array]] = device_metrics
        self._host: Dict[str, float] = {}
        # while the tracer is on, the step leaves a ``train/step`` span in
        # the ring, from its dispatch to the fetch that brought its metrics,
        # with every one of them: whatever the loss function returned
        self._t_dispatch = time.monotonic() if tracer.enabled else None

    def _materialize(self) -> Dict[str, float]:
        if self._dev is not None:
            host = jax.device_get(self._dev)
            self._dev = None
            # a metric that is no scalar (a rule's input: every expert's
            # count) stays the array it is, and out of the span
            self._host = {k: float(v) if np.ndim(v) == 0 else v
                          for k, v in host.items()}
            if self._t_dispatch is not None and tracer.enabled:
                tracer.add_span("train/step", self._t_dispatch,
                                time.monotonic(), attrs={
                                    k: v for k, v in self._host.items()
                                    if isinstance(v, float)})
        return self._host

    def __getitem__(self, k):
        return self._materialize()[k]

    def __iter__(self):
        return iter(self._materialize())

    def __len__(self):
        return len(self._materialize())

    def __repr__(self):
        return repr(self._materialize())

    def __reduce__(self):  # pickle as a plain dict
        return (dict, (self._materialize(),))


@dataclasses.dataclass
class ModelSpec:
    """What the engine needs from a model: pure functions + annotated params.

    ``loss_fn(params, batch, rng) -> (loss, metrics_dict)`` must be jittable,
    with MEAN semantics over the batch (loss and metrics are per-example
    averages — the contract data-parallel reduction relies on).
    ``param_axes`` is the logical-axes pytree (may be a prefix tree / None).
    """

    loss_fn: LossFn
    params: Any
    param_axes: Any = None
    # optional extra aux-loss fn (e.g. MoE router losses already inside loss_fn)
    eval_fn: Optional[LossFn] = None
    # Leaves that a RULE of the model's moves, and no gradient (a router's
    # balancing bias): ``rule_moved`` is a pytree of bools shaped like
    # ``params``, True on such a leaf, and ``apply_rules(params, metrics) ->
    # params`` moves them from the step's metrics (what ``loss_fn`` returned,
    # a mean over the micro-batches; a metric may be an array).  The engine
    # keeps those leaves out of differentiation, the optimizer's moments,
    # clipping and the gradient norm, and calls the rule after the
    # optimizer's update inside the one compiled train step.  ZeRO stages 0
    # to 2 on the device; not with offload, PEFT or 1-bit compression yet
    rule_moved: Any = None
    apply_rules: Optional[Callable[[Any, Dict[str, jax.Array]], Any]] = None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EngineState:
    step: jax.Array
    params: Any
    opt_state: Any
    loss_scale: LossScaleState
    rng: jax.Array
    skipped_steps: jax.Array

    def tree_flatten(self):
        return ((self.step, self.params, self.opt_state, self.loss_scale,
                 self.rng, self.skipped_steps), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


class TrainingEngine:
    """Reference: ``DeepSpeedEngine``.  Owns topology, shardings, the jitted
    step, checkpoint IO, timers and monitoring."""

    def __init__(self, model: ModelSpec, config: DeepSpeedTPUConfig,
                 topo: Optional[MeshTopology] = None):
        self.config = config
        self.accelerator = get_accelerator()
        self.model = model

        # ---- topology -------------------------------------------------
        if topo is None:
            mesh_cfg = config.mesh
            from .config import MeshConfig
            from .config_utils import is_auto

            mics = config.zero_optimization.mics_shard_size
            if config.zero_optimization.stage >= 3 and mics > 0:
                # MiCS (reference runtime/zero/mics.py): shard params within
                # groups of mics_shard_size, replicate across groups — i.e.
                # fsdp = shard size, dp = the replica groups
                mesh_cfg = MeshConfig(**{
                    **mesh_cfg.model_dump(),
                    "fsdp_size": mics, "data_parallel_size": "auto"})
            elif config.zero_optimization.stage >= 3:
                # ZeRO-3 shards params over the whole DP world: fold dp→fsdp
                if is_auto(mesh_cfg.fsdp_size) or int(mesh_cfg.fsdp_size) == 1:
                    mesh_cfg = MeshConfig(**{
                        **mesh_cfg.model_dump(),
                        "fsdp_size": "auto", "data_parallel_size": 1})
            topo = MeshTopology.from_config(mesh_cfg)
        self.topo = topo
        set_topology(topo)

        # ---- batch math ----------------------------------------------
        self.batch_config: ResolvedBatchConfig = config.resolve_batch_config(
            topo.dp_world_size)

        # ---- precision ------------------------------------------------
        self.compute_dtype = jnp.dtype(config.compute_dtype)
        self.fp16_enabled = config.fp16.enabled is True

        # ---- PEFT / LoRA (linear/) ------------------------------------
        # Swap targeted projections for LoRAWeight nodes (frozen — possibly
        # quantized — base + trainable A/B factors) BEFORE shardings are
        # derived, so the expanded axes tree drives every placement decision.
        # Trees that already carry LoRA nodes (restored adapter runs, user-
        # built models) are detected rather than re-wrapped.
        from ..linear.optimized_linear import (apply_lora, has_lora,
                                               merge_trainable,
                                               trainable_mask,
                                               trainable_subtree)

        lora_cfg = config.peft.lora
        if lora_cfg.enabled and not has_lora(model.params):
            new_params, new_axes = apply_lora(
                model.params, model.param_axes,
                jax.random.PRNGKey(config.seed), lora_cfg)
            model = dataclasses.replace(model, params=new_params,
                                        param_axes=new_axes)
            self.model = model
        self.peft_enabled = has_lora(model.params)
        self._trainable_mask = None
        if self.peft_enabled:
            self._trainable_mask = trainable_mask(model.params)
            off_o = config.zero_optimization.offload_optimizer
            off_p = config.zero_optimization.offload_param
            if (off_o is not None and off_o.device_str != "none") or \
                    (off_p is not None and off_p.device_str != "none"):
                raise ConfigError(
                    "peft.lora + offload_optimizer/offload_param is not "
                    "supported: the host fp32 master-weight path cannot "
                    "carry frozen quantized-code leaves, and adapter state "
                    "is small enough to stay device-resident")
            if config.zenflow.enabled:
                raise ConfigError("peft.lora + zenflow is not supported "
                                  "(zenflow is an offload schedule)")
            if config.gradient_compression.enabled:
                raise ConfigError(
                    "peft.lora + gradient_compression is not supported: "
                    "adapter gradients are tiny, wire compression would "
                    "cost more in error-feedback state than it saves")
            if config.zero_optimization.zero_quantized_weights:
                raise ConfigError(
                    "peft.lora + zero_quantized_weights is not supported "
                    "(the frozen base is already stored quantized; qwZ "
                    "would re-quantize the stage-3 gathers of int codes)")
        if model.rule_moved is not None:
            # rule-moved leaves ride the mask that keeps PEFT's frozen base
            # out of gradients and optimizer state; the rule itself runs in
            # ``step_fn``
            off_o = config.zero_optimization.offload_optimizer
            off_p = config.zero_optimization.offload_param
            if self.peft_enabled or config.zero_optimization.stage >= 3 \
                    or (off_o is not None and off_o.device_str != "none") \
                    or (off_p is not None and off_p.device_str != "none") \
                    or config.zenflow.enabled \
                    or config.gradient_compression.enabled \
                    or config.zero_optimization.zero_quantized_gradients:
                raise ConfigError(
                    "a model with rule-moved leaves (ModelSpec.rule_moved) "
                    "trains at ZeRO stages 0 to 2 on the device: not with "
                    "peft.lora, stage 3, offload, zenflow or compressed "
                    "gradients yet")
            self._trainable_mask = jax.tree.map(lambda moved: not moved,
                                                model.rule_moved)

        # ---- sharding rules ------------------------------------------
        stage = config.zero_optimization.stage
        self.zero_stage = stage
        self.param_rules = rules_for_params(stage, topo)
        self.opt_rules = rules_for_optimizer(stage, topo)
        self.param_shardings = sharding_for_tree(
            model.params, model.param_axes, self.param_rules, topo)
        # param-shaped leaves of the optimizer state (and stage≥2 gradients)
        # follow the optimizer rules — computed once, reused everywhere
        self.opt_param_shardings = sharding_for_tree(
            model.params, model.param_axes, self.opt_rules, topo)
        # PEFT: gradients/optimizer state exist for adapter leaves only — the
        # trainable template (frozen leaves → None, absent on flatten) is the
        # shape source for everything gradient-adjacent, and the opt/grad
        # sharding tree is masked to match
        if self._trainable_mask is not None:
            self._trainable_template = trainable_subtree(
                model.params, self._trainable_mask)
            self.opt_param_shardings = trainable_subtree(
                self.opt_param_shardings, self._trainable_mask)
        else:
            self._trainable_template = model.params

        # ---- optimizer ------------------------------------------------
        base_lr = config.optimizer.params.get("lr", 1e-3)
        self.lr_schedule = create_scheduler(config.scheduler, base_lr=base_lr)
        wd_mask = None
        if config.optimizer.params.get("weight_decay", 0.0):
            wd_mask = default_weight_decay_mask(self._trainable_template)
        chain = []
        if config.gradient_clipping and config.gradient_clipping > 0:
            chain.append(optax.clip_by_global_norm(config.gradient_clipping))
        chain.append(create_optimizer(
            config.optimizer, self.lr_schedule, wd_mask,
            wire_compression=config.gradient_compression.enabled))
        self.optimizer = optax.chain(*chain)

        # ---- offload mode --------------------------------------------
        off = config.zero_optimization.offload_optimizer
        self.offload_enabled = off is not None and off.device_str != "none"
        self.offloaded_optimizer = None

        # ZeRO-Infinity param offload: stacked layer params live in the host
        # memory space and stream per-layer inside the scanned program
        # (zero/param_offload.py; reference partitioned_param_swapper.py).
        off_p = config.zero_optimization.offload_param
        self.param_offload_enabled = off_p is not None and \
            off_p.device_str != "none"
        if self.param_offload_enabled:
            from .zero.param_offload import (apply_host_memory_kind,
                                             host_memory_available,
                                             offload_mask,
                                             set_param_streaming)

            if self.fp16_enabled:
                raise ConfigError(
                    "fp16 + offload_param is not supported; use bf16")
            if not host_memory_available():
                logger.warning(
                    "offload_param requested but this backend exposes no "
                    "pinned_host memory space — params stay in device memory")
                self.param_offload_enabled = False
            else:
                thresh = config.zero_optimization.stage3_param_persistence_threshold
                # "auto" keeps small per-layer tensors (norm scales, biases)
                # device-resident — the reference's auto resolves to ~10×
                # hidden elements; 1e5 is that order for typical models.
                # Offloading them would add a tiny host DMA per layer per
                # step for negligible HBM savings.
                thresh = 100_000 if isinstance(thresh, str) else int(thresh)
                self._param_offload_mask = offload_mask(
                    model.params, model.param_axes, min_numel=thresh)
                self.param_shardings = apply_host_memory_kind(
                    self.param_shardings, self._param_offload_mask)
                set_param_streaming(True)
                if not self.offload_enabled:
                    # params off-device imply the fp32 master + update live on
                    # the host too (there is no device copy to update)
                    from .config import OffloadOptimizerConfig

                    off = OffloadOptimizerConfig(device="cpu")
                    self.offload_enabled = True
        if self.offload_enabled and self.fp16_enabled:
            raise ConfigError(
                "fp16 + offload_optimizer is not supported; use bf16")
        if config.zero_optimization.zero_quantized_gradients:
            if self.offload_enabled:
                raise ConfigError(
                    "zero_quantized_gradients + offload_optimizer is not "
                    "supported yet (the offloaded grad step has no compressed-"
                    "reduction wiring)")
            if stage >= 3:
                raise ConfigError(
                    "zero_quantized_gradients requires stage <= 2 (params must "
                    "be replicated across the dp axes for the manual reduction)")
            for ax in ("tp", "sp", "ep", "pp"):
                if topo.size(ax) > 1:
                    raise ConfigError(
                        f"zero_quantized_gradients cannot combine with {ax} "
                        "parallelism (model-internal collectives cannot nest "
                        "inside the manual dp reduction)")
        if config.zero_optimization.zero_quantized_weights:
            if stage < 3:
                raise ConfigError(
                    "zero_quantized_weights (qwZ) requires stage 3 — below "
                    "stage 3 params are replicated and there is no weight "
                    "all-gather to quantize")
            if self.offload_enabled:
                raise ConfigError(
                    "zero_quantized_weights + offload_optimizer is not "
                    "supported")
        if config.gradient_compression.enabled:
            # same structural constraints as qgZ: the manual shard_map DP
            # reduction owns the gradient traffic
            if self.offload_enabled:
                raise ConfigError(
                    "gradient_compression + offload_optimizer is not supported")
            if self.fp16_enabled:
                raise ConfigError(
                    "gradient_compression requires bf16/fp32: error-feedback "
                    "residuals live in the loss-scaled domain, so a dynamic "
                    "scale change (or one overflow poisoning them with NaN) "
                    "breaks the compensation — use bf16")
            if config.zero_optimization.zero_quantized_gradients:
                raise ConfigError(
                    "gradient_compression and zero_quantized_gradients are "
                    "both wire-compression schemes — enable one")
            if stage >= 3:
                raise ConfigError(
                    "gradient_compression requires stage <= 2 (params must be "
                    "replicated across the dp axes for the manual reduction)")
            for ax in ("tp", "sp", "ep", "pp"):
                if topo.size(ax) > 1:
                    raise ConfigError(
                        f"gradient_compression cannot combine with {ax} "
                        "parallelism (model-internal collectives cannot nest "
                        "inside the manual dp reduction)")

        # ---- gradient coalescing (IPG buckets; coalesce.py) -----------
        # Fuse the per-leaf gradient reductions into a few contiguous
        # per-dtype buckets (reference reduce_independent_p_g_buckets /
        # allreduce_bucket_size).  Eligible whenever the DP reduction can be
        # made explicit: params replicated over the dp axes (stage ≤ 2), no
        # model-internal collectives (tp/sp/ep/pp == 1), no offload (the
        # offloaded grad step reduces on a different schedule).  Stage 3
        # keeps the emergent GSPMD schedule: its reductions live inside the
        # scanned backward, interleaved with the fsdp param all-gathers.
        from .coalesce import (plan_buckets, resolve_bucket_numel,
                               shard_dims_for)

        self.reduce_bucket_numel = resolve_bucket_numel(
            config.zero_optimization)
        explicit_dp_ok = (
            stage <= 2 and not self.offload_enabled
            and not self.param_offload_enabled
            and topo.dp_world_size > 1  # nothing to reduce across on 1 rank
            # the explicit reduction stacks the metrics as scalars; a rule's
            # metrics are arrays, so such a model reduces under GSPMD
            and model.rule_moved is None
            and all(topo.size(ax) == 1 for ax in ("tp", "sp", "ep", "pp")))
        self._bucket_plan = None   # exact path (scatter buckets at stage ≥2)
        self._wire_plan = None     # compressed paths (flat buckets only)
        if self.reduce_bucket_numel > 0 and explicit_dp_ok:
            # under PEFT only adapter leaves ever have gradients — buckets
            # are planned over the trainable template so no slot (and no
            # reduction traffic) exists for the frozen base
            grad_shapes = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(tuple(p.shape), jnp.float32),
                self._trainable_template)
            shard_dims = None
            if stage >= 2:
                # ZeRO-2: leaves whose optimizer sharding splits a dim over
                # the dp world ride shard-major buckets → fused reduce-
                # scatter lands directly in the optimizer-state sharding
                shard_dims = shard_dims_for(
                    grad_shapes, self.opt_param_shardings, ("dp", "fsdp"),
                    {ax: topo.size(ax) for ax in ("dp", "fsdp")})
            self._bucket_plan = plan_buckets(
                grad_shapes, self.reduce_bucket_numel,
                world=topo.dp_world_size, shard_dims=shard_dims)
            self._wire_plan = plan_buckets(grad_shapes,
                                           self.reduce_bucket_numel)
            st = self._bucket_plan.stats()
            log_dist(
                f"gradient coalescing: {st['num_leaves']} leaves -> "
                f"{st['num_buckets']} bucket(s) "
                f"({st['scatter_buckets']} reduce-scatter), cap="
                f"{self.reduce_bucket_numel} elements")

        # ---- param all-gather coalescing (ZeRO 1-2; allgather_bucket_size)
        # At stages 1-2 the optimizer update runs in the dp-sharded layout
        # and the params come back replicated — which the seed paid for with
        # one all-gather PER LEAF (11 on the evidence model).  Same bucket
        # machinery as gradients: shard-major buckets over the leaves whose
        # optimizer sharding splits a dim across dp, one fused all-gather per
        # dtype bucket inside the step (reference all_gather_dp_groups /
        # allgather_bucket_size).
        from .coalesce import resolve_allgather_numel

        self._gather_plan = None
        gather_numel = resolve_allgather_numel(config.zero_optimization)
        if stage in (1, 2) and explicit_dp_ok and gather_numel > 0:
            param_shapes = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(tuple(p.shape), p.dtype),
                self._trainable_template)
            g_dims = shard_dims_for(
                param_shapes, self.opt_param_shardings, ("dp", "fsdp"),
                {ax: topo.size(ax) for ax in ("dp", "fsdp")})
            gp = plan_buckets(param_shapes, gather_numel,
                              world=topo.dp_world_size, shard_dims=g_dims)
            if any(b.scatter for b in gp.buckets):
                self._gather_plan = gp
                gst = gp.stats()
                log_dist(
                    f"param-gather coalescing: {gst['num_leaves']} leaves -> "
                    f"{gst['scatter_buckets']} fused all-gather bucket(s), "
                    f"cap={gather_numel} elements")

        # ---- tp×sp gather anchoring ----------------------------------
        # models/transformer.py pins these shardings around the two
        # vocab-dim gathers (embedding lookup, loss take_along_axis).  On
        # tensor × sequence parallel meshes GSPMD's partitioning of a gather
        # with a vocab(tp)-sharded operand and seq(sp)-sharded indices
        # miscompiles into NaN loss (ROADMAP item); replicating the tiny
        # int32 index tensors across sp before the gather sidesteps it, and
        # the activation constraint re-anchors the sp layout downstream.
        # Installed per-call and cleared afterwards (_anchored_step) — the
        # step may be traced for several engines in one process, and a
        # leftover anchor would poison standalone traces of the model on
        # other meshes; pipeline runs the model inside shard_map where
        # NamedSharding constraints don't apply.
        self._embed_act_sharding = None
        self._gather_index_sharding = None
        if topo.size("pp") == 1 and (topo.size("sp") > 1
                                     or topo.size("tp") > 1):
            self._embed_act_sharding = NamedSharding(
                topo.mesh, P(("dp", "fsdp"), "sp", None))
            if topo.size("sp") > 1:
                self._gather_index_sharding = NamedSharding(
                    topo.mesh, P(("dp", "fsdp"), None))

        # ---- state init (sharded at construction) ---------------------
        self.opt_shardings = None  # set inside _init_state
        self.state = self._init_state()

        # ---- step function -------------------------------------------
        self._delayed_update = False
        self._pending_grads = None
        self._pending_lr_scale = None
        self._pending_lr = None
        self.zenflow_optimizer = None
        if config.zenflow.enabled and not self.offload_enabled:
            raise ConfigError(
                "zenflow requires offload_optimizer (it is a stall-free "
                "*offload* schedule; reference zenflow_stage_1_and_2.py)")
        if config.zenflow.enabled and self.param_offload_enabled:
            raise ConfigError(
                "zenflow + offload_param is not supported (the hot-column "
                "scatter needs device-resident params)")
        if self.offload_enabled:
            from .zero.offload import OffloadedOptimizer

            self.offloaded_optimizer = OffloadedOptimizer(
                self.optimizer, self.state.params, off, aio=config.aio,
                param_cfg=config.zero_optimization.offload_param)
            self._delayed_update = bool(getattr(off, "delayed_update", False))
            if config.zenflow.enabled:
                from .zenflow import ZenFlowOptimizer

                self.zenflow_optimizer = ZenFlowOptimizer(
                    self.optimizer, self.state.params, config.zenflow,
                    host_opt=self.offloaded_optimizer)
                if self._delayed_update:
                    logger.warning(
                        "zenflow already removes the per-step offload stall; "
                        "ignoring delayed_update")
                    self._delayed_update = False
            self._grad_step = self._build_grad_step()
        else:
            self._train_step = self._build_train_step()
            if config.gradient_compression.enabled:
                self._init_onebit()
        self._eval_step = self._build_eval_step()

        # ---- observability -------------------------------------------
        self.timers = SynchronizedWallClockTimer(synchronize=config.wall_clock_breakdown)
        self.tput = ThroughputTimer(batch_size=self.batch_config.train_batch_size,
                                    steps_per_output=config.steps_per_print,
                                    synchronize=config.wall_clock_breakdown)
        self.monitor = self._configure_monitor()
        self.global_steps = 0
        log_dist(f"engine ready: zero_stage={stage} topo={topo} "
                 f"batch={self.batch_config.train_batch_size} "
                 f"micro={self.batch_config.micro_batch_size_per_device} "
                 f"gas={self.batch_config.gradient_accumulation_steps} "
                 f"dtype={self.compute_dtype}")
        if stage >= 3:
            rep = self.shard_report()
            log_dist(
                f"ZeRO-3 shard accounting: {rep['sharded_fraction']:.1%} of "
                f"{rep['total_bytes'] / 2**20:.1f} MiB param bytes removed "
                f"per device ({rep['per_device_bytes'] / 2**20:.1f} MiB local)")
            fsdp_n = self.topo.size("fsdp")
            expected = 1.0 - 1.0 / max(fsdp_n, 1)
            if fsdp_n > 1 and rep["sharded_fraction"] < 0.5 * expected:
                logger.warning(
                    "ZeRO-3 is sharding only %.1f%% of param bytes (expected "
                    "~%.1f%% at fsdp=%d) — large replicated leaves: %s. "
                    "Check logical-axes annotations / dim divisibility.",
                    100 * rep["sharded_fraction"], 100 * expected, fsdp_n,
                    rep["replicated_leaves"][:5])

    # ------------------------------------------------------------------
    # setup helpers
    # ------------------------------------------------------------------

    def _configure_monitor(self):
        from ..monitor.monitor import MonitorMaster

        return MonitorMaster(self.config)

    def _opt_state_shardings(self, params_sharded):
        """Sharding tree for the optimizer state: param-like leaves get the
        *optimizer* rules (ZeRO-1/2 shard them over dp even when params are
        replicated); scalar counters replicate.  Under PEFT the state covers
        adapter leaves only (frozen base leaves are absent, not zero-sized),
        and never a rule-moved leaf."""
        if self._trainable_mask is not None:
            from ..linear.optimized_linear import trainable_subtree

            params_sharded = trainable_subtree(params_sharded,
                                               self._trainable_mask)
        state_shape = jax.eval_shape(self.optimizer.init, params_sharded)
        replicated = NamedSharding(self.topo.mesh, P())

        return optax.tree_map_params(
            self.optimizer,
            lambda _leaf, shard: shard,
            state_shape,
            self.opt_param_shardings,
            transform_non_params=lambda _leaf: replicated,
        )

    def _coalesced_gather_fn(self, tree):
        """Re-replicate the ZeRO-1/2 sharded optimizer outputs with ONE fused
        ``all_gather`` per dtype bucket (``_gather_plan``).  ``tree`` is the
        updated (trainable) param tree; scatter-bucket leaves enter in their
        optimizer-state sharding, everything exits replicated."""
        from jax import shard_map
        from .coalesce import unflatten_bucket_shard_major

        plan = self._gather_plan
        world = int(self.topo.dp_world_size)
        dp_axes = ("dp", "fsdp")
        sh_leaves, treedef = jax.tree_util.tree_flatten(
            self.opt_param_shardings)
        scatter_leaves = {s.leaf for b in plan.buckets if b.scatter
                          for s in b.slots}
        in_specs = jax.tree_util.tree_unflatten(
            treedef, [sh.spec if i in scatter_leaves else P()
                      for i, sh in enumerate(sh_leaves)])
        rep = jax.tree_util.tree_unflatten(treedef, [P()] * len(sh_leaves))

        def local_fn(t):
            leaves, td = jax.tree_util.tree_flatten(t)
            out = list(leaves)
            for b in plan.buckets:
                if not b.scatter:
                    continue
                # each shard's local row = its slice of every member leaf,
                # exactly the shard-major layout; tiled all_gather rebuilds
                # the full buffer in one collective
                row = jnp.concatenate([out[s.leaf].reshape(-1)
                                       for s in b.slots])
                full = jax.lax.all_gather(row, dp_axes, tiled=True)
                for i, v in unflatten_bucket_shard_major(b, full, world):
                    out[i] = v
            return jax.tree_util.tree_unflatten(td, out)

        return shard_map(local_fn, mesh=self.topo.mesh,
                         in_specs=(in_specs,), out_specs=rep,
                         check_vma=False)(tree)

    def _init_state(self) -> EngineState:
        # The train step donates state buffers, so the engine must own fresh
        # copies — aliasing the caller's arrays would let donation delete them
        # out from under the user (or a second engine sharing the ModelSpec).
        # A jitted copy guarantees new buffers (device_put may alias even with
        # may_alias=False when the sharding already matches).
        if self.param_offload_enabled:
            # the jitted copy cannot carry mixed memory kinds (the placement
            # custom-call defeats the SPMD partitioner): copy with device
            # kinds, then move the host-space leaves eagerly
            dev_sh = jax.tree.map(
                lambda s: s.with_memory_kind("device")
                if s.memory_kind == "pinned_host" else s, self.param_shardings)
            params = jax.jit(
                lambda t: jax.tree.map(jnp.copy, t),
                out_shardings=dev_sh)(self.model.params)
            params = jax.tree.map(lambda x, s: jax.device_put(x, s),
                                  params, self.param_shardings)
        else:
            params = jax.jit(
                lambda t: jax.tree.map(jnp.copy, t),
                out_shardings=self.param_shardings)(self.model.params)
        if self.offload_enabled:
            # optimizer state lives on host (OffloadedOptimizer); keep no
            # device copy at all — that's the memory savings offload buys
            self.opt_shardings = ()
            opt_state = ()
        else:
            opt_shardings = self._opt_state_shardings(params)
            self.opt_shardings = opt_shardings
            init_params = params
            if self._trainable_mask is not None:
                from ..linear.optimized_linear import trainable_subtree

                init_params = trainable_subtree(params, self._trainable_mask)
            opt_state = jax.jit(self.optimizer.init,
                                out_shardings=opt_shardings)(init_params)
            opt_state = self._cast_opt_to_steady_state(
                opt_state, init_params, opt_shardings)
        if self.fp16_enabled:
            ls = init_loss_scale(
                initial_scale_power=self.config.fp16.initial_scale_power,
                hysteresis=self.config.fp16.hysteresis,
                static_scale=self.config.fp16.loss_scale,
            )
        else:
            ls = init_loss_scale(static_scale=1.0)
        # the step hands its scalars back committed to the mesh, replicated:
        # start them there too, or step 2 meets new input shardings and the
        # whole program compiles a second time
        scalars = jax.device_put(
            (jnp.zeros((), jnp.int32), ls,
             jax.random.PRNGKey(self.config.seed), jnp.zeros((), jnp.int32)),
            NamedSharding(self.topo.mesh, P()))
        return EngineState(
            step=scalars[0],
            params=params,
            opt_state=opt_state,
            loss_scale=scalars[1],
            rng=scalars[2],
            skipped_steps=scalars[3],
        )

    def _cast_opt_to_steady_state(self, opt_state, init_params, opt_shardings):
        """Cast fresh optimizer state to the dtypes it holds after step 1.

        ``optimizer.init`` mirrors the param dtypes (bf16 moments for bf16
        params), but the engine feeds f32 grads to ``optimizer.update``, so
        optax promotes the *output* moments to f32.  Left alone, the step-1
        program has bf16 moment inputs and f32 moment outputs — every moment
        buffer is donated-but-unaliased (the zero0 4.9 MB / zero3 1.2 MB /
        lora 82 KB stragglers of the donation audit) and step 2 silently
        recompiles against the new dtypes.  Casting at init is numerically
        free (moments start at zero) and makes step 1 the steady-state
        program: donation aliases in-place and there is exactly one compile.
        """
        try:
            grads_sds = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32),
                init_params)
            _, steady = jax.eval_shape(self.optimizer.update, grads_sds,
                                       opt_state, init_params)
        except Exception:  # exotic optimizers: keep init dtypes
            return opt_state
        flat_now = jax.tree_util.tree_leaves(opt_state)
        flat_steady = jax.tree_util.tree_leaves(steady)
        if len(flat_now) != len(flat_steady) or all(
                a.dtype == b.dtype for a, b in zip(flat_now, flat_steady)):
            return opt_state
        steady_dt = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(opt_state),
            [b.dtype for b in flat_steady])
        return jax.jit(
            lambda t: jax.tree.map(lambda x, d: x.astype(d), t, steady_dt),
            out_shardings=opt_shardings)(opt_state)

    # ------------------------------------------------------------------
    # the jitted step
    # ------------------------------------------------------------------

    # ---- 1-bit wire compression (reference: runtime/comm/nccl.py) -----
    _ONEBIT_MIN_NUMEL = 2048  # leaves below this psum exactly
    _ONEBIT_BLOCK = 2048      # scale-block length (multiple of 8)

    def _onebit_freeze_step(self) -> int:
        """Warmup length before compression engages: the optimizer's own
        freeze_step when a 1-bit optimizer is configured (variance freeze
        and wire compression must flip together), else the
        gradient_compression config value."""
        name = self.config.optimizer.type.lower().replace("_", "")
        if name in ("onebitadam", "zerooneadam", "onebitlamb"):
            return int(self.config.optimizer.params.get("freeze_step", 100))
        return int(self.config.gradient_compression.freeze_step)

    def _init_onebit(self) -> None:
        """Error-feedback residuals (worker + server) and the compressed-
        reduction step function.  Residuals are (W, len) fp32 sharded over
        the dp axes — each shard owns its own feedback.  With coalescing the
        unit of compression is the BUCKET, so residuals are a tuple aligned
        with ``_wire_plan.buckets`` (0-length for buckets small enough to
        psum exactly); without it they mirror the param tree per leaf."""
        from jax.sharding import NamedSharding
        from ..ops.onebit import residual_shapes

        W = int(self.topo.dp_world_size)
        sh = NamedSharding(self.topo.mesh, P(("dp", "fsdp")))
        plan = self._wire_plan

        def length(numel, slot):
            if numel >= self._ONEBIT_MIN_NUMEL:
                # worker residual (slot 0): each shard's FULL padded vector;
                # server residual (slot 1): each shard's own chunk
                return residual_shapes(numel, W, self._ONEBIT_BLOCK)[slot]
            return 0

        if plan is not None:
            def zero_trees():
                return tuple(
                    tuple(jnp.zeros((W, length(b.numel, slot)), jnp.float32)
                          for b in plan.buckets)
                    for slot in (0, 1))
        else:
            def zero_trees():
                return tuple(
                    jax.tree.map(
                        lambda l: jnp.zeros((W, length(l.size, slot)),
                                            jnp.float32),
                        self.state.params)
                    for slot in (0, 1))

        # ONE jitted call allocates every residual directly sharded (a
        # device_put of materialized (W, n) buffers would stage W copies of
        # each leaf's fp32 size on one device first — OOM at exactly the
        # scale this feature targets; per-leaf jits would compile 2x per
        # leaf). 0-sized leaves reject sharding overrides → device_put them.
        shaped = jax.eval_shape(zero_trees)
        out_sh = jax.tree.map(lambda s: None if s.shape[1] == 0 else sh,
                              shaped)
        wres, sres = jax.jit(zero_trees, out_shardings=out_sh)()
        fix0 = lambda x: (jax.device_put(x, sh) if x.shape[1] == 0 else x)
        self._onebit_wres = jax.tree.map(fix0, wres)
        self._onebit_sres = jax.tree.map(fix0, sres)
        self._train_step_onebit = self._build_train_step(onebit=True)

    def _build_train_step(self, onebit: bool = False):
        cfg = self.config
        gas = self.batch_config.gradient_accumulation_steps
        loss_fn = self.model.loss_fn
        optimizer = self.optimizer
        fp16 = self.fp16_enabled
        dynamic = cfg.fp16.dynamic_loss_scale if fp16 else False
        opt_param_shardings = self.opt_param_shardings

        qwz = cfg.zero_optimization.zero_quantized_weights
        param_shardings = self.param_shardings
        topo = self.topo

        # PEFT: differentiate w.r.t. the trainable subtree only — frozen
        # (possibly quantized) base leaves enter the forward as constants, so
        # no gradient, cotangent buffer, or reduction ever exists for them
        # ... and w.r.t. no leaf that a rule moves (ModelSpec.rule_moved):
        # one mask serves both
        tmask = self._trainable_mask
        masked = tmask is not None
        apply_rules = self.model.apply_rules
        if masked:
            from ..linear.optimized_linear import (merge_trainable,
                                                   trainable_subtree)

        def microbatch_grads(params, mb, rng, ls_state):
            def scaled_loss(p):
                if masked:
                    p = merge_trainable(p, params, tmask)
                if qwz:
                    # ZeRO++ qwZ: stage-3 gathers ship int8 codes + scales
                    from .zero.qwz import qwz_gather_tree

                    p = qwz_gather_tree(p, param_shardings, topo)
                loss, metrics = loss_fn(p, mb, rng)
                return scale_loss(loss, ls_state) if fp16 else loss, metrics

            diff_params = trainable_subtree(params, tmask) if masked else params
            (loss, metrics), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(diff_params)
            return loss, metrics, grads

        # validated in __init__: stage <= 2, no tp/sp/ep/pp, no offload
        qgz = cfg.zero_optimization.zero_quantized_gradients

        # coalescing plans (built once in __init__; None → legacy paths)
        plan = self._bucket_plan
        wire_plan = self._wire_plan
        grad_out_specs = None
        if plan is not None:
            # scatter-bucket leaves exit the shard_map already sharded like
            # the optimizer state (ZeRO-2); everything else replicated
            dims = {s.leaf: s.shard_dim
                    for b in plan.buckets for s in b.slots}
            opt_leaves, ptd = jax.tree_util.tree_flatten(opt_param_shardings)
            grad_out_specs = jax.tree_util.tree_unflatten(
                ptd, [sh.spec if dims.get(i) is not None else P()
                      for i, sh in enumerate(opt_leaves)])

        def step_fn(state: EngineState, batch: Dict[str, jax.Array],
                    residuals=None, lr_scale=None):
            # lr_scale: per-batch LR multiplier from the variable-batch
            # sampler (data_sampling/variable_batch_size_and_lr.py); None
            # (the default trace) compiles the scale away entirely.
            rng, step_rng = jax.random.split(state.rng)

            # metrics pytree mirrors whatever the user's loss_fn returns
            one_mb = jax.tree.map(lambda x: x[0], batch)
            _, metrics_shape = jax.eval_shape(
                lambda p, b: loss_fn(p, b, step_rng), state.params, one_mb)
            zero_metrics = jax.tree.map(
                lambda s: jnp.zeros(s.shape, jnp.float32), metrics_shape)

            def accumulate(params, batch):
                grad_tmpl = trainable_subtree(params, tmask) if masked else params
                zg = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                  grad_tmpl)

                def acc(carry, mb):
                    grads_acc, metrics_acc = carry
                    _, metrics, grads = microbatch_grads(
                        params, mb, step_rng, state.loss_scale)
                    grads = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), grads_acc, grads)
                    metrics_acc = jax.tree.map(
                        lambda a, m: a + m.astype(jnp.float32), metrics_acc,
                        metrics)
                    return (grads, metrics_acc), None

                if gas > 1:
                    (g, m), _ = jax.lax.scan(acc, (zg, zero_metrics), batch)
                else:
                    (g, m), _ = acc((zg, zero_metrics),
                                    jax.tree.map(lambda x: x[0], batch))
                return g, m

            new_residuals = residuals
            dp_axes = ("dp", "fsdp")
            ws = float(self.topo.dp_world_size)
            # bucketed paths also coalesce the grad-norm reduction: the
            # per-shard sum-of-squares rides the stacked metrics psum, so
            # computing ||g|| outside adds no per-leaf scalar all-reduces
            gsq = None

            def explicit_dp(local_fn, extra_in=(), extra_specs=(),
                            grad_specs=None, norm_out=False):
                """Shared scaffolding of the manual-DP reduction paths
                (bucketed exact, 1-bit, qgZ): params replicated in, batch
                sharded over dp, metrics replicated out; grads come back
                replicated unless ``grad_specs`` marks a leaf as exiting
                sharded (ZeRO-2 scatter buckets); ``extra`` pytrees
                (residuals) ride sharded over the dp axes.  ``norm_out``
                adds a replicated scalar (the gradient sum-of-squares,
                psummed inside with the metrics) after the metrics."""
                from jax import shard_map

                batch_specs = jax.tree.map(lambda _: P(None, dp_axes), batch)
                rep = jax.tree.map(lambda _: P(), state.params)
                grad_rep = (jax.tree.map(
                    lambda _: P(), trainable_subtree(state.params, tmask))
                    if masked else rep)
                gspec = grad_specs if grad_specs is not None else grad_rep
                mspec = jax.tree.map(lambda _: P(), zero_metrics)
                nspec = (P(),) if norm_out else ()
                return shard_map(
                    local_fn, mesh=self.topo.mesh,
                    in_specs=(rep, batch_specs) + tuple(extra_specs),
                    out_specs=(gspec, mspec) + nspec + tuple(extra_specs),
                    check_vma=False)(state.params, batch, *extra_in)

            if onebit:
                # 1-bit Adam wire path (reference runtime/comm/nccl.py
                # compressed_allreduce): gradients reduce through the
                # two-phase sign-compressed scheme with worker + server
                # error feedback (ops/onebit.py), ~32x less gradient
                # traffic.  With coalescing the unit of compression is the
                # BUCKET — one two-phase round trip per bucket, and
                # sub-block leaves share scale blocks instead of each
                # padding one out; tiny buckets psum exactly.
                from ..ops.onebit import onebit_all_reduce

                W = int(self.topo.dp_world_size)

                if wire_plan is not None:
                    from .coalesce import (flatten_bucket, psum_scalars,
                                           unflatten_bucket)

                    def local(params, batch, wres, sres):
                        g, m = accumulate(params, batch)
                        leaves, treedef = jax.tree_util.tree_flatten(g)
                        out = list(leaves)
                        new_w, new_s = [], []
                        sq = jnp.zeros((), jnp.float32)
                        for bi, b in enumerate(wire_plan.buckets):
                            flat = flatten_bucket(b, leaves)
                            w, s = wres[bi], sres[bi]
                            if w.shape[-1] > 0:
                                # the primitive computes the MEAN internally
                                # — pre-dividing (the qgZ sum-semantics
                                # convention) would shrink compressed grads
                                # by another 1/W
                                red, nw, ns = onebit_all_reduce(
                                    flat, w[0], s[0], dp_axes, W,
                                    self._ONEBIT_BLOCK)
                                new_w.append(nw[None])
                                new_s.append(ns[None])
                            else:  # bucket below _ONEBIT_MIN_NUMEL: exact
                                red = jax.lax.psum(flat / ws, dp_axes)
                                new_w.append(w)
                                new_s.append(s)
                            sq = sq + jnp.sum(jnp.square(red)) / ws
                            for i, v in unflatten_bucket(b, red):
                                out[i] = v
                        g = jax.tree_util.tree_unflatten(treedef, out)
                        m, nsq = psum_scalars(m, dp_axes, 1.0 / ws, extra=sq)
                        return g, m, nsq, tuple(new_w), tuple(new_s)

                    res_spec = tuple(P(dp_axes) for _ in wire_plan.buckets)
                else:
                    def local(params, batch, wres, sres):
                        g, m = accumulate(params, batch)

                        def red(t, w, s):
                            if t.size >= self._ONEBIT_MIN_NUMEL:
                                out, nw, ns = onebit_all_reduce(
                                    t, w[0], s[0], dp_axes, W,
                                    self._ONEBIT_BLOCK)
                                return out, nw[None], ns[None]
                            return jax.lax.psum(t / ws, dp_axes), w, s

                        triples = jax.tree.map(red, g, wres, sres)
                        is3 = lambda x: isinstance(x, tuple) and len(x) == 3
                        g = jax.tree.map(lambda tr: tr[0], triples,
                                         is_leaf=is3)
                        nw = jax.tree.map(lambda tr: tr[1], triples,
                                          is_leaf=is3)
                        ns = jax.tree.map(lambda tr: tr[2], triples,
                                          is_leaf=is3)
                        m = jax.tree.map(
                            lambda t: jax.lax.psum(t / ws, dp_axes), m)
                        return g, m, nw, ns

                    res_spec = jax.tree.map(lambda _: P(dp_axes),
                                            state.params)
                if wire_plan is not None:
                    grads, msum, gsq, new_w, new_s = explicit_dp(
                        local, extra_in=residuals,
                        extra_specs=(res_spec, res_spec), norm_out=True)
                else:
                    grads, msum, new_w, new_s = explicit_dp(
                        local, extra_in=residuals,
                        extra_specs=(res_spec, res_spec))
                new_residuals = (new_w, new_s)
            elif qgz:
                # ZeRO++ qgZ: explicit DP with int8-compressed gradient
                # reduction (ops/quantizer.compressed_all_reduce) instead of
                # XLA's exact psum — 4x less gradient traffic over DCN, one
                # quantize→all_gather→dequantize round trip per BUCKET when
                # coalescing is on (fewer compression round trips, full
                # block utilization for sub-block leaves).
                # Assumes MEAN-semantics loss/metrics (the ModelSpec contract):
                # per-shard values are averaged across dp; sum-semantics
                # outputs would be rescaled by 1/dp_world.
                from ..ops.quantizer import compressed_all_reduce

                if wire_plan is not None:
                    from .coalesce import psum_scalars, reduce_bucketed

                    def local(params, batch):
                        g, m = accumulate(params, batch)
                        sqs = []

                        def red(b, f):
                            r = compressed_all_reduce(f / ws, dp_axes)
                            sqs.append(jnp.sum(jnp.square(r)) / ws)
                            return r

                        g = reduce_bucketed(wire_plan, g, red)
                        m, nsq = psum_scalars(m, dp_axes, 1.0 / ws,
                                              extra=sum(sqs))
                        return g, m, nsq

                    grads, msum, gsq = explicit_dp(local, norm_out=True)
                else:
                    def local(params, batch):
                        g, m = accumulate(params, batch)
                        g = jax.tree.map(
                            lambda t: compressed_all_reduce(t / ws, dp_axes)
                            if t.ndim >= 1
                            else jax.lax.psum(t / ws, dp_axes), g)
                        m = jax.tree.map(
                            lambda t: jax.lax.psum(t / ws, dp_axes), m)
                        return g, m

                    grads, msum = explicit_dp(local)
            elif plan is not None:
                # Bucketed exact DP (the IPG-bucket role, coalesce.py): the
                # DP reduction is made explicit so XLA sees ONE psum per
                # per-dtype bucket — a handful of large collectives instead
                # of one per parameter leaf.  At ZeRO-2, shard-major buckets
                # reduce with a single fused psum_scatter whose output IS
                # the optimizer-state sharding (no re-layout copy).
                from .coalesce import psum_scalars, reduce_bucketed

                def local(params, batch):
                    g, m = accumulate(params, batch)
                    sqs = []

                    def red(b, f):
                        r = jax.lax.psum(f / ws, dp_axes)
                        # replicated: every shard holds the full bucket
                        sqs.append(jnp.sum(jnp.square(r)) / ws)
                        return r

                    def red_scatter(b, f):
                        r = jax.lax.psum_scatter(
                            f / ws, dp_axes, scatter_dimension=0, tiled=True)
                        # scattered: each shard owns a disjoint 1/W chunk
                        sqs.append(jnp.sum(jnp.square(r)))
                        return r

                    g = reduce_bucketed(plan, g, red, red_scatter)
                    m, nsq = psum_scalars(m, dp_axes, 1.0 / ws,
                                          extra=sum(sqs))
                    return g, m, nsq

                grads, msum, gsq = explicit_dp(
                    local, grad_specs=grad_out_specs, norm_out=True)
            else:
                grads, msum = accumulate(state.params, batch)
            metrics = jax.tree.map(lambda m: m / gas, msum)

            # --- unscale + average ------------------------------------
            scale_div = float(gas)
            grads = jax.tree.map(lambda g: g / scale_div, grads)
            if fp16:
                grads = unscale_grads(grads, state.loss_scale)

            # ZeRO-2/3: constrain grads to the optimizer-state sharding →
            # XLA reduce-scatters instead of all-reducing.
            if self.zero_stage >= 2:
                grads = jax.tree.map(
                    lambda g, s: jax.lax.with_sharding_constraint(g, s),
                    grads, opt_param_shardings)

            finite = grads_finite(grads) if fp16 else jnp.array(True)
            if gsq is not None:
                # ||g|| from the in-shard_map sum-of-squares, rescaled the
                # same way the grads just were (uniform factors commute
                # through the 2-norm)
                grad_norm = jnp.sqrt(gsq) / scale_div
                if fp16:
                    grad_norm = grad_norm / state.loss_scale.scale
            else:
                grad_norm = optax.global_norm(grads)

            # --- optimizer update (skipped on overflow) ----------------
            def do_update(operand):
                params, opt_state, grads = operand
                upd_params = (trainable_subtree(params, tmask) if masked
                              else params)
                updates, new_opt = optimizer.update(grads, opt_state,
                                                    upd_params)
                if lr_scale is not None:
                    updates = jax.tree.map(lambda u: u * lr_scale, updates)
                new_trainable = optax.apply_updates(upd_params, updates)
                new_params = (merge_trainable(new_trainable, params, tmask)
                              if masked else new_trainable)
                if apply_rules is not None:  # a skipped step skips it too
                    new_params = apply_rules(new_params, metrics)
                return new_params, new_opt

            def skip_update(operand):
                params, opt_state, _ = operand
                return params, opt_state

            if fp16:
                new_params, new_opt = jax.lax.cond(
                    finite, do_update, skip_update,
                    (state.params, state.opt_state, grads))
                new_ls = update_loss_scale(
                    state.loss_scale, finite,
                    loss_scale_window=cfg.fp16.loss_scale_window,
                    min_scale=cfg.fp16.min_loss_scale,
                    hysteresis=cfg.fp16.hysteresis,
                    dynamic=dynamic)
                skipped = state.skipped_steps + jnp.where(finite, 0, 1)
            else:
                new_params, new_opt = do_update((state.params, state.opt_state, grads))
                new_ls = state.loss_scale
                skipped = state.skipped_steps

            # ZeRO 1-2 coalesced param re-replication: the sharded update's
            # outputs ride ONE fused all-gather per dtype bucket instead of
            # one per leaf (reference all_gather_dp_groups with
            # allgather_bucket_size).  Runs before the canonical pinning so
            # GSPMD sees already-replicated values and inserts nothing.
            if self._gather_plan is not None:
                gathered = self._coalesced_gather_fn(
                    trainable_subtree(new_params, tmask) if masked
                    else new_params)
                new_params = (merge_trainable(gathered, new_params, tmask)
                              if masked else gathered)

            # Pin the new state to its canonical shardings: prevents GSPMD
            # placement drift across steps (e.g. stage-1 params must come back
            # replicated — the all-gather after the sharded update IS ZeRO-1's
            # schedule) and keeps eval/checkpoint numerics placement-stable.
            new_params = jax.tree.map(jax.lax.with_sharding_constraint,
                                      new_params, self.param_shardings)
            new_opt = jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(x, s),
                new_opt, self.opt_shardings)
            new_state = EngineState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                loss_scale=new_ls,
                rng=rng,
                skipped_steps=skipped,
            )
            metrics = dict(metrics)
            metrics["grad_norm"] = grad_norm
            metrics["loss_scale"] = state.loss_scale.scale
            # effective update count = step - skipped: matches both the optax
            # counter (which doesn't advance on overflow-skipped steps) and
            # the reference's "scheduler not stepped on overflow" behavior
            metrics["lr"] = jnp.asarray(
                self.lr_schedule(state.step - state.skipped_steps), jnp.float32)
            if lr_scale is not None:
                metrics["lr"] = metrics["lr"] * lr_scale
            metrics["overflow"] = (~finite).astype(jnp.float32)
            if onebit:
                return new_state, metrics, new_residuals
            return new_state, metrics

        if onebit:
            # residuals donated: they are rewritten every step
            return jax.jit(step_fn, donate_argnums=(0, 2))

        def step_compat(state, batch, lr_scale=None):
            # positional-compat wrapper: existing callers pass lr_scale third
            return step_fn(state, batch, None, lr_scale)

        # the new state leaves with the shardings the old one came in with.
        # Left to the compiler, a replicated ``P(None, None)`` comes back as
        # ``P()``; the jit cache takes that for a new input sharding, and
        # step 2 compiles the whole program a second time
        state_shardings = jax.tree.map(lambda x: x.sharding, self.state)
        return jax.jit(step_compat, donate_argnums=(0,),
                       out_shardings=(state_shardings, None))

    def _build_grad_step(self):
        """Device half of the offloaded step: fwd+bwd+accumulate only.
        (Reference: ZeRO-Offload computes grads on GPU, optimizer on CPU.)"""
        gas = self.batch_config.gradient_accumulation_steps
        loss_fn = self.model.loss_fn

        def step_fn(params, batch, rng):
            rng, step_rng = jax.random.split(rng)

            def accum(carry, mb):
                grads_acc, metrics_acc = carry
                (_, metrics), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, mb, step_rng), has_aux=True)(params)
                grads = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     grads_acc, grads)
                metrics_acc = jax.tree.map(lambda a, m: a + m.astype(jnp.float32),
                                           metrics_acc, metrics)
                return (grads, metrics_acc), None

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            one_mb = jax.tree.map(lambda x: x[0], batch)
            _, metrics_shape = jax.eval_shape(
                lambda p, b: loss_fn(p, b, step_rng), params, one_mb)
            zero_metrics = jax.tree.map(lambda s: jnp.zeros((), jnp.float32),
                                        metrics_shape)
            if gas > 1:
                (grads, msum), _ = jax.lax.scan(accum, (zero_grads, zero_metrics),
                                                batch)
            else:
                (grads, msum), _ = accum((zero_grads, zero_metrics), one_mb)
            metrics = jax.tree.map(lambda m: m / gas, msum)
            grads = jax.tree.map(lambda g: g / float(gas), grads)
            metrics = dict(metrics)
            metrics["grad_norm"] = optax.global_norm(grads)
            return grads, metrics, rng

        # NOTE on grads: ideally the stacked layer grads would land in
        # pinned_host via out_shardings (per-scan-step writeback), but this
        # XLA version's SPMD partitioner rejects memory-kind annotations at
        # the jit boundary under a mesh ("side-effect ops cannot be
        # replicated"); grads therefore return in device memory and move to
        # host in OffloadedOptimizer.step's device_get.  Host-space *inputs*
        # (the streamed params) are unaffected.
        # params are NOT donated: the host optimizer owns the update, and
        # the same param buffers are re-read next step after in-place patch
        return jax.jit(step_fn)  # lint: allow(jit-no-donate)

    def _train_batch_offloaded(self, placed, lr_scale=None
                               ) -> Dict[str, float]:
        lr = self.get_lr()  # pre-increment: the lr this update applies
        if lr_scale is not None:
            lr *= float(lr_scale)
        grads, metrics, rng = self._grad_step(self.state.params, placed,
                                              self.state.rng)
        # the grad step is DISPATCHED, not awaited: start NVMe read-ahead of
        # master/moments now so disk IO overlaps the device compute
        self.offloaded_optimizer.prefetch()
        if self.zenflow_optimizer is not None:
            # ZenFlow: hot columns update on device now; cold grads stay on
            # device and flush through the host optimizer every interval
            new_params = self.zenflow_optimizer.step(
                self.state.params, grads, lr_scale=lr_scale)
        elif self._delayed_update:
            # DPU overlap: the grad step above is DISPATCHED (async) — while
            # the device runs batch N, the host applies batch N-1's update
            # (its grads are already materialized) and pushes params for
            # batch N+1.  Step time ≈ max(device, host) — the SuperOffload /
            # pipelined-swapper dataflow (superoffload_stage3.py:1,
            # pipelined_optimizer_swapper.py:52).
            applied_lr = None
            if self._pending_grads is not None:
                applied_lr = self._pending_lr
                new_params = self.offloaded_optimizer.step(
                    self._pending_grads, lr_scale=self._pending_lr_scale)
                new_params = jax.tree.map(
                    lambda x, s: jax.device_put(x, s), new_params,
                    self.param_shardings)
            else:  # first step: nothing to apply yet
                new_params = self.state.params
            self._pending_grads = grads
            self._pending_lr_scale = lr_scale
            self._pending_lr = lr
        else:
            new_params = self.offloaded_optimizer.step(grads, lr_scale=lr_scale)
            new_params = jax.tree.map(
                lambda x, s: jax.device_put(x, s), new_params,
                self.param_shardings)
        self.state = dataclasses.replace(
            self.state, step=self.state.step + 1, params=new_params, rng=rng)
        out = {k: float(v) for k, v in metrics.items()}
        out["lr"] = lr
        if (self._delayed_update and self.zenflow_optimizer is None
                and applied_lr is not None):
            # metrics (lr/loss/grad_norm) describe the CURRENT batch, but the
            # parameters were just updated with the PREVIOUS batch's pending
            # grads — surface the lr that update actually deserved so logs
            # aren't off by one (r3 advisor); absent on step 1 (no update)
            out["applied_lr"] = applied_lr
        return out

    def flush_delayed_update(self) -> None:
        """Apply the pending (one-step-delayed) update, if any.  Called
        automatically before checkpoint save and eval; end-of-training code
        should call it too so the last batch's gradients are not dropped."""
        if getattr(self, "_pending_grads", None) is None:
            return
        new_params = self.offloaded_optimizer.step(
            self._pending_grads, lr_scale=self._pending_lr_scale)
        self._pending_grads = None
        self._pending_lr_scale = None
        self._pending_lr = None
        new_params = jax.tree.map(
            lambda x, s: jax.device_put(x, s), new_params,
            self.param_shardings)
        self.state = dataclasses.replace(self.state, params=new_params)

    def _build_eval_step(self):
        loss_fn = self.model.eval_fn or self.model.loss_fn

        def eval_fn(state: EngineState, batch):
            _, metrics = loss_fn(state.params, batch, state.rng)
            return metrics

        return jax.jit(eval_fn)

    # ------------------------------------------------------------------
    # data placement
    # ------------------------------------------------------------------

    def _place_batch(self, batch: Dict[str, np.ndarray],
                     allow_variable: bool = False) -> Dict[str, jax.Array]:
        """Reshape a global batch (train_batch, ...) → (gas, micro_global, ...)
        and place it sharded over (dp, fsdp) on the batch axis.

        ``allow_variable``: variable-batch mode (a batch carrying
        ``lr_scale``) accepts any leading dim divisible by gas×dp — the
        token-budget batcher bounds the set of distinct shapes, so the
        compile cache stays bounded too."""
        gas = self.batch_config.gradient_accumulation_steps
        tb = self.batch_config.train_batch_size

        sp = self.topo.size("sp")
        dp = self.topo.dp_world_size

        def place(x):
            x = np.asarray(x)
            if x.shape[0] != tb:
                if not allow_variable:
                    raise ConfigError(
                        f"batch leading dim {x.shape[0]} != train_batch_size "
                        f"{tb}")
                if x.shape[0] % (gas * dp) != 0:
                    raise ConfigError(
                        f"variable batch leading dim {x.shape[0]} not "
                        f"divisible by gas*dp = {gas}*{dp}")
            x = x.reshape((gas, x.shape[0] // gas) + x.shape[1:])
            # (gas, batch, seq, ...): batch over dp/fsdp; seq over sp when
            # sequence parallelism is on (reference: UlyssesSPDataLoaderAdapter
            # shards dataloader batches on the sequence dim)
            spec = [None, ("dp", "fsdp")]
            if sp > 1 and x.ndim >= 3:
                if x.shape[2] % sp != 0:
                    raise ConfigError(
                        f"sequence length {x.shape[2]} not divisible by "
                        f"sequence_parallel_size {sp}")
                spec.append("sp")
            sharding = NamedSharding(self.topo.mesh, P(*spec))
            return jax.device_put(x, sharding)

        return jax.tree.map(place, batch)

    # ------------------------------------------------------------------
    # public API (reference surface)
    # ------------------------------------------------------------------

    def place_batch(self, batch: Dict[str, np.ndarray]) -> "Any":
        """Shard a host batch onto the mesh NOW (async dispatch) and return
        a ``PlacedBatch`` that ``train_batch`` consumes without re-placing.
        Thread-safe: ``PrefetchLoader(loader, place_fn=engine.place_batch)``
        overlaps the H2D copy of batch N+1 with step N's compute."""
        from .data_pipeline.loader import PlacedBatch

        lr_scale = None
        if "lr_scale" in batch:
            batch = dict(batch)
            lr_scale = np.float32(batch.pop("lr_scale"))
        placed = self._place_batch(batch, allow_variable=lr_scale is not None)
        return PlacedBatch(placed, lr_scale)

    def train_batch(self, batch: Dict[str, np.ndarray]
                    ) -> "collections.abc.Mapping[str, float]":
        """One full global-batch step (fwd+bwd+opt).  Reference:
        ``PipelineEngine.train_batch`` / engine forward+backward+step.

        Returns a Mapping (LazyMetrics): reads materialize floats; convert
        with ``dict(m)`` for serialization.  Not a dict instance."""
        from .data_pipeline.loader import PlacedBatch

        self._assert_streaming_flag()
        self.reload_states()  # states evicted by offload_states() come back
        if self.config.trace_profiler.enabled:
            self._maybe_trace(starting=True)
        self.tput.start()
        if not isinstance(batch, PlacedBatch):
            batch = self.place_batch(batch)  # ONE home for the lr_scale pop
        # pre-placed (PrefetchLoader): the H2D transfer was dispatched while
        # the previous step ran
        placed, lr_scale = batch.placed, batch.lr_scale
        with self._anchored_step():
            if self.offload_enabled:
                out = self._train_batch_offloaded(placed, lr_scale)
            elif (getattr(self, "_train_step_onebit", None) is not None
                    and self.global_steps >= self._onebit_freeze_step()):
                # 1-bit wire compression engages after the warmup ("freeze")
                # phase, matching the optimizer's variance freeze — host-side
                # switch, so each variant stays a single compiled program
                residuals = (self._onebit_wres, self._onebit_sres)
                self.state, metrics, residuals = self._train_step_onebit(
                    self.state, placed, residuals, lr_scale)
                self._onebit_wres, self._onebit_sres = residuals
                out = LazyMetrics(metrics)
            else:
                if lr_scale is None:
                    self.state, metrics = self._train_step(self.state, placed)
                else:
                    self.state, metrics = self._train_step(self.state, placed,
                                                           lr_scale)
                out = LazyMetrics(metrics)
        self.global_steps += 1
        will_read = self.monitor.enabled or (
            self.config.steps_per_print
            and self.global_steps % self.config.steps_per_print == 0)
        if will_read and isinstance(out, LazyMetrics):
            # materialize INSIDE the throughput window so the blocking wait
            # counts as step time — otherwise samples/sec reports dispatch rate
            out._materialize()
        self.tput.stop()
        if self.config.trace_profiler.enabled:
            self._maybe_trace(starting=False)
        self._write_monitor(out)
        if self.config.sanity_checks:
            self._run_sanity_checks(out)
        if self.config.steps_per_print and \
                self.global_steps % self.config.steps_per_print == 0:
            log_dist(f"step={self.global_steps} loss={out.get('loss', float('nan')):.4f} "
                     f"lr={out['lr']:.2e} grad_norm={out.get('grad_norm', 0.0):.3f}")
        return out

    def _maybe_trace(self, starting: bool) -> None:
        """jax.profiler trace capture over the configured step window
        (reference: the flops profiler's "profile at step N" UX — here the
        artifact is a TensorBoard/Perfetto device trace).  ``starting`` is
        True before the step runs, False after: the trace starts before
        ``start_step`` executes and stops after ``end_step`` completes."""
        cfg = self.config.trace_profiler
        step_about_to_run = self.global_steps + 1
        try:
            # >= (not ==): a checkpoint resume past start_step, or
            # start_step <= 0, must still capture a window rather than
            # silently never firing
            if (starting and not getattr(self, "_tracing", False)
                    and not getattr(self, "_traced_once", False)
                    and step_about_to_run >= cfg.start_step
                    and step_about_to_run <= cfg.end_step):
                jax.profiler.start_trace(cfg.output_dir)
                self._tracing = True
                # training may END before end_step (short run, crash) —
                # without this the session never stops and no artifact is
                # written; weakref so the hook doesn't pin the engine
                import atexit
                import weakref

                atexit.register(_stop_trace_at_exit, weakref.ref(self))
            elif (not starting and self.global_steps >= cfg.end_step
                    and getattr(self, "_tracing", False)):
                jax.device_get(self.state.step)  # drain dispatched work
                jax.profiler.stop_trace()
                self._tracing = False
                self._traced_once = True
                log_dist(f"trace captured: steps up to {cfg.end_step} "
                         f"-> {cfg.output_dir}")
        except Exception as e:  # tracing must never kill training
            if getattr(self, "_tracing", False):
                # the profiler session MUST end — an orphaned session
                # buffers trace events in host memory for the rest of the
                # run and never writes an artifact
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
            self._tracing = False
            self._traced_once = True
            logger.warning(f"trace_profiler: capture failed: {e}")

    def finalize_trace(self) -> None:
        """Stop a still-active trace (end of training before ``end_step``)
        and write the partial artifact.  Idempotent."""
        if getattr(self, "_tracing", False):
            self._tracing = False
            self._traced_once = True
            try:
                # drain dispatched work like the in-window stop path — the
                # partial artifact should hold the in-flight steps' device
                # activity, not just host-side dispatch
                jax.device_get(self.state.step)
            except Exception:
                pass
            try:
                jax.profiler.stop_trace()
                log_dist(f"trace stopped at training end (partial window) "
                         f"-> {self.config.trace_profiler.output_dir}")
            except Exception as e:
                logger.warning(f"trace_profiler: stop at exit failed: {e}")

    def _run_sanity_checks(self, out) -> None:
        """``sanity_checks`` mode (reference ``engine.py:1346``
        ``is_sanity_checks_enabled``): fail FAST and LOUD on silent
        corruption instead of training on garbage.

        * every step: loss / grad_norm must be finite (a dynamic-loss-scale
          overflow step is legitimate and exempt — the engine already skips
          its update);
        * every ``steps_per_print`` steps: replicated param leaves must be
          bit-identical across their addressable shards — the cross-rank
          payload-digest idea (reference ``moe/ep_tp_dispatch.py:210``)
          applied to GSPMD replicas (catches device desync / flipped bits).
        """
        if float(out.get("overflow", 0.0)) == 0.0:
            for key in ("loss", "grad_norm"):
                if key in out and not np.isfinite(float(out[key])):
                    raise RuntimeError(
                        f"sanity_checks: non-finite {key}="
                        f"{float(out[key])} at step {self.global_steps} — "
                        "data or numerics corruption upstream of the update")
        interval = max(1, int(self.config.steps_per_print or 10))
        if self.global_steps % interval == 0:
            bad = self._replica_consistency_violations(max_leaves=8)
            if bad:
                raise RuntimeError(
                    f"sanity_checks: replicated params diverged across "
                    f"shards at step {self.global_steps}: {bad}")

    def _replica_consistency_violations(self, max_leaves: int = 8):
        """Digest-compare the first vs last addressable shard of replicated
        leaves (bounded work: the ``max_leaves`` largest)."""
        import hashlib

        leaves = [
            (path, leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(self.state.params)[0]
            if getattr(leaf, "sharding", None) is not None
            and leaf.sharding.is_fully_replicated
            and len(leaf.addressable_shards) > 1
        ]
        leaves.sort(key=lambda pl: -pl[1].size)
        bad = []
        for path, leaf in leaves[:max_leaves]:
            digests = {
                hashlib.sha1(np.ascontiguousarray(
                    np.asarray(s.data)).tobytes()).hexdigest()
                for s in leaf.addressable_shards  # ALL shards: a middle
            }  # replica diverging must not hide behind matching endpoints
            if len(digests) > 1:
                name = "/".join(str(getattr(p, "key", p)) for p in path)
                bad.append(name)
        return bad

    def shard_report(self) -> Dict[str, Any]:
        """Per-param sharded-byte accounting (see zero.sharding.shard_accounting)."""
        from .zero.sharding import shard_accounting

        return shard_accounting(self.state.params, self.param_shardings)

    def _assert_streaming_flag(self) -> None:
        """Pin the trace-time param-streaming flag to THIS engine's mode right
        before any call that may trace — engines with different offload_param
        settings can then coexist in one process (tests, hybrid setups)."""
        from .zero.param_offload import set_param_streaming
        from ..models.transformer import set_embed_activation_sharding

        set_param_streaming(self.param_offload_enabled)
        # same per-call pinning for the tp×sp embed activation anchor: an
        # inference engine (or an engine on a different mesh) may have
        # changed it since this engine last traced
        set_embed_activation_sharding(self._embed_act_sharding,
                                      self._gather_index_sharding)

    @contextlib.contextmanager
    def _anchored_step(self):
        """Pin the trace-time globals for the duration of one engine call,
        then clear the mesh-specific gather anchors.  The anchors name THIS
        engine's mesh axes; left installed they would poison any later
        standalone trace of the model (a bare ``jax.grad`` over ``loss_fn``
        on the default device would inherit an 8-device sharding)."""
        from ..models.transformer import set_embed_activation_sharding

        self._assert_streaming_flag()
        try:
            yield
        finally:
            set_embed_activation_sharding(None, None)

    def eval_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        from .data_pipeline.loader import PlacedBatch

        self._assert_streaming_flag()
        # eval needs the params only — optimizer moments evicted for a
        # rollout phase (hybrid engine) STAY on the host
        self.reload_states(include=("lp_params",))
        self.flush_delayed_update()
        if isinstance(batch, PlacedBatch):  # prefetched validation loops
            placed = batch.placed
        else:
            placed = self._place_batch(batch)
        flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), placed)
        with self._anchored_step():
            metrics = self._eval_step(self.state, flat)
        return {k: float(v) for k, v in metrics.items()}

    def _write_monitor(self, metrics: Dict[str, float]) -> None:
        if self.monitor.enabled:
            events = [(f"Train/{k}", v, self.global_steps)
                      for k, v in metrics.items() if np.ndim(v) == 0]
            self.monitor.write_events(events)

    # -- state accessors (reference: engine property surface) -----------

    @property
    def train_batch_size(self) -> int:
        return self.batch_config.train_batch_size

    @property
    def train_micro_batch_size_per_device(self) -> int:
        return self.batch_config.micro_batch_size_per_device

    @property
    def gradient_accumulation_steps(self) -> int:
        return self.batch_config.gradient_accumulation_steps

    def get_lr(self) -> float:
        return float(self.lr_schedule(self.state.step - self.state.skipped_steps))

    def get_global_step(self) -> int:
        return int(self.state.step)

    def get_loss_scale(self) -> float:
        return float(self.state.loss_scale.scale)

    # -- checkpointing ---------------------------------------------------

    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None) -> str:
        self.flush_delayed_update()
        if self.zenflow_optimizer is not None:
            # mid-interval cold gradients must not be dropped by the save
            new_params = self.zenflow_optimizer.flush(self.state.params)
            self.state = dataclasses.replace(self.state, params=new_params)
        from .checkpoint.engine import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        fallback: Optional[bool] = None,
                        ) -> Tuple[Optional[str], Dict]:
        from .checkpoint.engine import load_checkpoint as _load

        return _load(self, load_dir, tag=tag,
                     load_optimizer_states=load_optimizer_states,
                     fallback=fallback)

    def export_merged_weights(self, save_dir: str, tag: str = "merged") -> str:
        """PEFT serving export: fold LoRA adapters into the base weights and
        write a plain full-model checkpoint (see
        checkpoint.engine.export_merged_weights)."""
        self.flush_delayed_update()
        from .checkpoint.engine import export_merged_weights as _export

        return _export(self, save_dir, tag=tag)

    def load_universal_checkpoint(self, root: str, **kwargs) -> str:
        """Ingest a DeepSpeed universal checkpoint (ds_to_universal.py
        output) — reference ``universal_checkpoint.py:17``."""
        from .checkpoint.universal import load_universal_checkpoint as _lu

        return _lu(self, root, **kwargs)

    # -- phase-alternation state offload (reference: engine.py:5573
    # offload_states / reload_states — RLHF rollouts evict optimizer state
    # to free HBM for the KV cache, then reload before the next update) ---

    _OFFLOADABLE = ("optim_states", "lp_params")

    def offload_states(self, include: Optional[Sequence[str]] = None,
                       device: str = "cpu", pin_memory: bool = True,
                       non_blocking: bool = False) -> None:
        """Evict engine state to host memory between phases.

        ``include`` ⊆ {"optim_states", "lp_params"} (default: optimizer
        states only — evicting the compute params too means nothing can run
        until :meth:`reload_states`).  Device buffers are deleted after the
        host copy, so HBM is actually freed, not just mirrored.  With
        ``offload_optimizer`` the optimizer already lives on the host and
        "optim_states" is a no-op.  Idempotent; ``train_batch`` reloads
        automatically."""
        if device != "cpu":
            raise ConfigError(f"offload_states supports device='cpu', "
                              f"got {device!r}")
        include = set(include) if include is not None else {"optim_states"}
        unknown = include - set(self._OFFLOADABLE)
        if unknown:
            raise ConfigError(
                f"offload_states: unknown state types {sorted(unknown)}; "
                f"valid: {self._OFFLOADABLE}")
        self.flush_delayed_update()

        def evict(tree):
            shardings = jax.tree.map(
                lambda x: x.sharding if isinstance(x, jax.Array) else None,
                tree)
            host = jax.device_get(tree)
            jax.tree.map(
                lambda x: x.delete() if isinstance(x, jax.Array) else None,
                tree)
            return host, shardings

        offloaded = getattr(self, "_offloaded_states", None) or {}
        if ("optim_states" in include and "optim_states" not in offloaded
                and self.offloaded_optimizer is None):
            host, sh = evict(self.state.opt_state)
            self.state = dataclasses.replace(self.state, opt_state=host)
            offloaded["optim_states"] = sh
        if "lp_params" in include and "lp_params" not in offloaded:
            host, sh = evict(self.state.params)
            self.state = dataclasses.replace(self.state, params=host)
            offloaded["lp_params"] = sh
        self._offloaded_states = offloaded
        if offloaded:
            log_dist(f"offloaded states to host: {sorted(offloaded)}")

    def reload_states(self, non_blocking: bool = False,
                      include: Optional[Sequence[str]] = None) -> None:
        """Restore states evicted by :meth:`offload_states` onto their
        original shardings.  ``include=None`` restores everything; a subset
        restores only those kinds and leaves the rest on the host (eval
        during an RLHF rollout needs params, not optimizer moments).
        Idempotent."""
        offloaded = getattr(self, "_offloaded_states", None)
        if not offloaded:
            return
        wanted = set(include) if include is not None else set(offloaded)

        def restore(tree, shardings):
            return jax.tree.map(
                lambda x, s: jax.device_put(x, s) if s is not None else x,
                tree, shardings)

        restored = []
        if "optim_states" in offloaded and "optim_states" in wanted:
            self.state = dataclasses.replace(
                self.state,
                opt_state=restore(self.state.opt_state,
                                  offloaded.pop("optim_states")))
            restored.append("optim_states")
        if "lp_params" in offloaded and "lp_params" in wanted:
            self.state = dataclasses.replace(
                self.state,
                params=restore(self.state.params,
                               offloaded.pop("lp_params")))
            restored.append("lp_params")
        self._offloaded_states = offloaded or None
        if restored:
            log_dist(f"reloaded host-offloaded states: {sorted(restored)}")

    @property
    def states_offloaded(self) -> bool:
        return bool(getattr(self, "_offloaded_states", None))


def _stop_trace_at_exit(engine_ref) -> None:
    """atexit hook (module-level so atexit never pins an engine instance)."""
    engine = engine_ref()
    if engine is not None:
        engine.finalize_trace()
