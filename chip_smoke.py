#!/usr/bin/env python3
"""Prove that the trainer and the server start, run and are right on a TPU.

    python chip_smoke.py             # one chip: trainer, server, int8 server,
                                     # and the Pallas kernels in each step
    python chip_smoke.py --chips 4   # four chips: ZeRO-3 sharded training and
                                     # the one-device loss it is compared with

The model is the ``mistral-7b`` preset at its published widths (hidden 4096,
MLP 14336, 32 query / 8 KV heads, head_dim 128, vocab 32000, window 4096).
Depth is cut to what the memory of the chips holds, and every phase prints
the depth it used and why.  Weights, batches and prompts come from ``--seed``.

One process throughout: a chip belongs to one process at a time, so nothing
here starts a child.  Every phase ends in a device-to-host fetch.  A phase
that fails raises, and the exit code is then non-zero; there is no fallback
to the CPU.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import http.client
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from deepspeed_tpu.models import mixed_ffn  # noqa: E402
from deepspeed_tpu.models import transformer as tfm  # noqa: E402


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything a run is sized by.  The defaults fill one 16 GB v5e chip;
    a rehearsal on the CPU hands in small ones (see the verify skill)."""
    preset: str = "mistral-7b"
    # -- trainer: bf16 params (2 B) + f32 Adam moments (8 B) + f32 grads
    # (4 B) + the caller's own copy (2 B) is 16 B a parameter; two layers
    # with embedding and head are 0.70 B parameters = 11.2 GB of the 15.75
    train_layers: int = 2
    train_micro: int = 4
    train_seq: int = 2048
    train_warmup: int = 2
    train_steps: int = 6
    loss_tile: int = 512
    lr: float = 2e-4
    # -- server: 0.44 GB a layer in bf16 + 0.52 GB embedding and head, and a
    # KV cache of 4 KiB a token a layer: 24 layers are 11.0 GB of weights
    # and 1.6 GB of cache for 16k tokens
    serve_layers: int = 24
    # int8 codes are 0.22 GB a layer: all 32 layers are 7.5 GB
    quant_layers: int = 32
    block_size: int = 64
    num_blocks: int = 256
    max_blocks_per_seq: int = 16
    max_seqs: int = 16
    max_tokens_per_step: int = 512
    # (prompt tokens, new tokens); one prompt is longer than a step's token
    # budget, so its prefill is chunked across steps
    requests: Tuple[Tuple[int, int], ...] = (
        (40, 24), (96, 32), (160, 16), (300, 40), (64, 28), (200, 20),
        (600, 24), (24, 36))
    quant_requests: Tuple[Tuple[int, int], ...] = (
        (48, 16), (130, 24), (20, 20), (260, 12))
    # a served token's reference logit may lie this far under the reference
    # maximum: random-init logits have a standard deviation near 1 and a
    # maximum near 4, the top two lie 0.2 apart on average, and bf16 noise
    # through the stack is under 0.1; a wrong token lies about 4 under
    margin: float = 0.5
    # -- sparse experts: two layers at OLMoE-1B-7B's widths (64 experts of
    # 2048 x 1024, top 8) are 0.81 GB of int8 codes; the engine quantizes
    # them on the host, a layer's experts at a time
    moe_preset: str = "olmoe-1b-7b"
    moe_layers: int = 2
    moe_requests: Tuple[Tuple[int, int], ...] = (
        (48, 16), (600, 20), (20, 24), (130, 12))
    # -- window and global layers: one period (S S S F) at Mellum2's widths
    # (64 experts of 2304 x 896, top 8) is 1.67 GB of int8 codes at group
    # 128; prompts pass two windows of 1,024, tables hold 2,560 tokens
    swa_preset: str = "mellum2-12b-a2.5b"
    swa_layers: int = 4
    swa_max_blocks_per_seq: int = 40
    swa_requests: Tuple[Tuple[int, int], ...] = (
        (2300, 12), (1100, 20), (300, 16))
    # -- one mixer a layer of three kinds: E M E M * at Nemotron-3-Nano's
    # widths (128 experts of 2688 x 1856, top 6, a shared expert; Mamba-2 of
    # 64 heads x 64 x 128) is 2.8 GB of int8 codes at group 128; a prompt
    # crosses the step budget three times, a second batch reuses the slots
    ssm_preset: str = "nemotron3-nano-30b-a3b"
    ssm_pattern: str = "EMEM*"
    ssm_max_blocks_per_seq: int = 24
    ssm_requests: Tuple[Tuple[int, int], ...] = (
        (1300, 8), (300, 12), (40, 10), (9, 14))
    ssm_second: Tuple[Tuple[int, int], ...] = ((70, 8), (600, 6), (5, 12))
    # the tapped rows against the reference held to the program's routing
    # choices (median, worst: the serving cell's bounds), and the share of
    # served tokens within ``margin`` of the free reference's maximum
    ssm_logit_tol: Tuple[float, float] = (0.12, 0.2)
    ssm_served_min: float = 0.6
    # the tapped sequences' slots of the engine's state array against the
    # reference pass's final states, of the largest element (the cell's bound)
    ssm_state_tol: float = 0.15
    # -- a Mamba-1 mixer or attention, and a dense FFN behind each: S F S F *
    # F S F at AI21-Jamba2-3B's widths (d_inner 5120, 16 states a channel,
    # 20 query heads on ONE K/V head, FFN 8192, the tied 65,536-row head) is
    # 0.6 B parameters in bfloat16; a prompt crosses the step budget three
    # times, a second batch reuses the slots
    selective_preset: str = "jamba2-3b"
    selective_pattern: str = "SFSF*FSF"
    selective_max_blocks_per_seq: int = 24
    selective_requests: Tuple[Tuple[int, int], ...] = (
        (1300, 8), (300, 12), (40, 10), (9, 14))
    selective_second: Tuple[Tuple[int, int], ...] = (
        (70, 8), (600, 6), (5, 12))
    # the tapped rows against the reference (median, worst) and the slots'
    # states against its final states, of the largest element
    selective_logit_tol: Tuple[float, float] = (0.1, 0.3)
    selective_state_tol: float = 0.05
    # -- latent attention, a learned selection of keys, a share of the experts:
    # the dense layer that picks and one period (shared shared shared full)
    # at GLM-5.2's widths with 16 of 256 experts and an eighth of the
    # vocabulary is 4.3 GB of int8 codes at group 128; a prompt passes two
    # index_topk (4,400: nine chunks), one stays under one; tables of 80
    # blocks hold 5,120 tokens
    latent_preset: str = "glm-5.2"
    latent_periods: int = 1
    latent_held: int = 16
    latent_vocab: int = 19360
    latent_max_blocks_per_seq: int = 80
    latent_blocks: int = 321
    latent_requests: Tuple[Tuple[int, int], ...] = (
        (4400, 10), (2300, 8), (600, 12), (9, 14))
    latent_second: Tuple[Tuple[int, int], ...] = ((70, 8), (2100, 6))
    # the tapped rows against the reference held to the program's picks and
    # experts (median, worst: the serving cell's bounds)
    latent_logit_tol: Tuple[float, float] = (0.12, 0.3)
    # -- a latent model TRAINED on one chip of eight: the dense layer and five
    # routed ones at DeepSeek-V2-Lite's widths, 8 of 64 experts, an eighth of
    # the vocabulary: 0.635 B parameters, 8.9 GB at 14 B a parameter; two
    # rows of 8,192 (the benchmark cell's configuration and batch)
    latent_train_preset: str = "deepseek-v2-lite"
    latent_train_layers: int = 6
    latent_train_held: int = 8
    latent_train_vocab: int = 12800
    latent_train_micro: int = 2
    latent_train_seq: int = 8192
    latent_train_steps: int = 2
    # -- window and full attention layers mixed TRAINED on one chip of
    # sixteen: published layers 1-5 of Trinity-Mini (a dense layer, then
    # window, FULL, window, window over routed FFNs), 8 of 128 experts, an
    # eighth of the vocabulary: 0.5041 B parameters, 7.1 GB at 14 B a
    # parameter (16 experts, 0.7055 B, do not load beside the step's temp:
    # PERF.md section 4); one row of 16,384 (the benchmark cell's
    # configuration and batch)
    swa_train_preset: str = "trinity-mini"
    swa_train_kinds: Tuple[str, ...] = ("sliding", "sliding", "full",
                                        "sliding", "sliding")
    swa_train_held: int = 8
    swa_train_vocab: int = 25024
    swa_train_micro: int = 1
    swa_train_seq: int = 16384
    swa_train_steps: int = 2
    # -- EVA attention: four layers of EvaByte-6.5B at its published widths
    # (0.81 B parameters in int8), both pools sized as the cell sizes them a
    # row (a whole window of 32 blocks, a summary block a 1,024 tokens);
    # prompts that close one and two windows in prefill and one that decode
    # carries over the next edge
    eva_preset: str = "evabyte-6.5b"
    eva_layers: int = 4
    eva_max_blocks_per_seq: int = 96
    eva_requests: Tuple[Tuple[int, int], ...] = (
        (4090, 12), (2300, 8), (600, 12), (9, 14))
    # the tapped rows of all eight heads against the reference (median,
    # worst: the serving cell's bounds)
    eva_logit_tol: Tuple[float, float] = (0.12, 0.3)
    # linear_latent_moe_server: the leading dense layer and one whole period
    # of Kimi-Linear (K K A, then K K K A) at published widths, 32 of 256
    # experts held, an eighth of the vocabulary; a prompt chunked three times
    # off the chunk's edge, short rows beside it, a second batch through the
    # same slots
    linear_preset: str = "kimi-linear-48b"
    linear_pattern: str = "KKAKKKA"
    linear_held: int = 32
    linear_vocab: int = 20480
    linear_blocks: int = 8 * 32 + 1
    linear_max_blocks_per_seq: int = 32
    linear_requests: Tuple[Tuple[int, int], ...] = (
        (1300, 12), (300, 16), (70, 10), (5, 8))
    linear_second: Tuple[Tuple[int, int], ...] = ((600, 6), (40, 9))
    linear_logit_tol: Tuple[float, float] = (0.15, 0.4)
    linear_state_tol: Tuple[float, float] = (0.02, 0.2)
    # -- four chips: ZeRO-3 shards 14 B a parameter over four chips, beside
    # the caller's unsharded copy on chip 0
    zero3_layers: int = 8
    zero3_steps: int = 3
    compare_layers: int = 2
    loss_rel_tol: float = 5e-3


def log(phase: str, **kv: Any) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def memory_line(phase: str, device) -> None:
    stats = device.memory_stats()
    if not stats:
        log(phase, memory_stats="not reported by this backend")
        return
    log(phase, peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_in_use=stats.get("bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"))


def require_kernel(phase: str, name: str, hlo_text: str) -> None:
    """Phase 4: the compiled step must hold a Mosaic kernel.  One that gave
    way to its XLA reference compiles without any ``tpu_custom_call``."""
    n = hlo_text.count("tpu_custom_call")
    log(phase, program=name, tpu_custom_calls=n)
    if n == 0:
        raise AssertionError(
            f"{name}: no tpu_custom_call in the compiled program — a Pallas "
            f"kernel gave way to its reference")


class Recorded:
    """Stand-in for one of the engine's jitted steps: counts the calls,
    keeps the argument shapes of the first, so the very program that ran can
    be lowered and compiled again for inspection, and the largest number of
    live rows (``context_lens > 0``, argument ``rows_arg``) in any call."""

    def __init__(self, jitted, rows_arg: int):
        self.jitted = jitted
        self.rows_arg = rows_arg
        self.calls = 0
        self.max_rows = 0
        self.specs = None

    def __call__(self, *args):
        if self.specs is None:
            self.specs = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        self.calls += 1
        self.max_rows = max(self.max_rows, int(np.count_nonzero(
            np.asarray(args[self.rows_arg]))))
        return self.jitted(*args)

    def compiled_text(self) -> str:
        return self.jitted.lower(*self.specs).compile().as_text()


# ---------------------------------------------------------------------------
# phase 1: the trainer
# ---------------------------------------------------------------------------


def build_trainer(sz: Sizes, seed: int, num_layers: int,
                  ds_extra: Dict[str, Any], preset: Optional[str] = None,
                  overrides: Optional[Dict[str, Any]] = None,
                  micro: Optional[int] = None, seq: Optional[int] = None):
    """``deepspeed_tpu.initialize`` as ``examples/train.py`` drives it."""
    import deepspeed_tpu
    from deepspeed_tpu.parallel import topology
    from deepspeed_tpu.runtime.engine import ModelSpec
    from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

    topology.reset_topology()
    cfg = tfm.get_config(preset or sz.preset, num_layers=num_layers,
                         param_dtype="bfloat16", **(overrides or {}))
    params = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))

    def loss_fn(p, b, r):
        return tiled_loss_fn(p, b, cfg, tile_size=sz.loss_tile)

    spec = ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg),
                     **mixed_ffn.spec_rules(params, cfg))
    config = {
        "train_micro_batch_size_per_gpu": micro or sz.train_micro,
        "optimizer": {"type": "AdamW", "params": {"lr": sz.lr}},
        "zero_optimization": {"stage": 0},
        "bf16": {"enabled": True},
        "steps_per_print": 1_000_000,
    }
    config.update(ds_extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=config)
    batch = {"input_ids": np.random.default_rng(seed).integers(
        0, cfg.vocab_size,
        size=(engine.train_batch_size, seq or sz.train_seq)
    ).astype(np.int32)}
    return cfg, params, engine, batch


def timed_steps(phase: str, engine, placed, warmup: int, steps: int
                ) -> Tuple[List[float], float]:
    """``warmup + steps`` steps on one repeated batch, each waited for and
    timed on its own, so that a step that compiles again shows.  Returns
    every loss and the median seconds of the timed steps.  Each wait is a
    barrier and then a fetch; the fetch is timed apart, since it would be
    long if the barrier returned early."""
    losses, seconds, fetch = [], [], 0.0
    for _ in range(warmup + steps):
        t0 = time.perf_counter()
        out = engine.train_batch(placed)
        jax.block_until_ready(engine.state.step)
        t1 = time.perf_counter()
        losses.append(float(out["loss"]))  # device-to-host
        seconds.append(time.perf_counter() - t0)
        fetch = max(fetch, time.perf_counter() - t1)
    step_s = float(np.median(seconds[warmup:]))
    log(phase, compile_seconds=round(seconds[0] - step_s, 2),
        step_seconds=round(step_s, 4),
        each_step_seconds=[round(x, 3) for x in seconds],
        programs_compiled=engine._train_step._cache_size(),
        longest_fetch_after_barrier_seconds=round(fetch, 5))
    log(phase, losses=[round(x, 4) for x in losses])
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{phase}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(
            f"{phase}: loss on the repeated batch did not fall: {losses}")
    return losses, step_s


def phase_trainer(sz: Sizes, seed: int, check_kernels: bool = True) -> None:
    phase = "trainer"
    log(phase, preset=sz.preset, layers=sz.train_layers,
        why="bf16 params + f32 Adam moments + f32 grads + the caller's copy "
            "are 16 B a parameter; 2 layers with embedding and head are "
            "0.70 B parameters = 11.2 GB of one 16 GB chip")
    cfg, params, engine, batch = build_trainer(sz, seed, sz.train_layers, {})
    log(phase, params_m=round(cfg.num_params() / 1e6, 1), dtype="bfloat16",
        optimizer="AdamW", zero_stage=0, attn=cfg.attn_impl,
        window=cfg.sliding_window, loss_tile=sz.loss_tile,
        micro_batch=sz.train_micro, seq=sz.train_seq)
    placed = engine.place_batch(batch)
    _, step_s = timed_steps(phase, engine, placed, sz.train_warmup,
                            sz.train_steps)
    log(phase, tokens_per_second=round(
        engine.train_batch_size * sz.train_seq / step_s, 1))
    memory_line(phase, jax.devices()[0])
    if check_kernels:
        require_kernel("kernels", "train_step", engine._train_step.lower(
            engine.state, placed.placed).compile().as_text())


def phase_latent_moe_trainer(sz: Sizes, seed: int,
                             check_kernels: bool = True) -> None:
    """A latent model without an indexer TRAINED through the same entry
    points: latent attention through the flash kernel at a query-key width
    that is not the value width, a share of the routed experts with its
    backward, the shared experts, the balance loss and the counters in the
    step's metrics.  Fails on a kernel that gave way to its reference."""
    from deepspeed_tpu.observability.trace import tracer

    phase = "latent_moe_trainer"
    n = sz.latent_train_layers
    log(phase, preset=sz.latent_train_preset, layers=n,
        held=sz.latent_train_held, vocab=sz.latent_train_vocab,
        why="one chip of eight that share each layer: the dense layer and "
            "the routed ones that fit at 14 B a parameter")
    tracer.clear()
    cfg, params, engine, batch = build_trainer(
        sz, seed, n, {}, preset=sz.latent_train_preset, overrides=dict(
            vocab_size=sz.latent_train_vocab,
            moe_experts_held=sz.latent_train_held,
            mlp_layer_types=("dense",) + ("sparse",) * (n - 1)),
        micro=sz.latent_train_micro, seq=sz.latent_train_seq)
    del params
    gc.collect()
    log(phase, params_m=round(cfg.num_params() / 1e6, 1),
        micro_batch=sz.latent_train_micro, seq=sz.latent_train_seq,
        attn=cfg.attn_impl)
    placed = engine.place_batch(batch)
    _, step_s = timed_steps(phase, engine, placed, 1, sz.latent_train_steps)
    out = engine.train_batch(placed)
    log(phase, tokens_per_second=round(
        engine.train_batch_size * sz.latent_train_seq / step_s, 1),
        **{k: round(float(out[k]), 4) for k in (
            "ce_loss", "moe_aux_loss", "moe_local_rows", "moe_rows_max",
            "moe_experts_hit", "grad_norm")})
    memory_line(phase, jax.devices()[0])
    events = [s for s in tracer.spans() if s.name in (
        "kernel/flash_attention_tiles", "kernel/grouped_matmul_tiles")]
    log(phase, kernel_events=sorted({
        json.dumps({"name": s.name, **s.attrs}, sort_keys=True)
        for s in events}))
    if not any(s.name == "train/step" for s in tracer.spans()):
        raise AssertionError(f"{phase}: no train/step span in the ring")
    if check_kernels:
        bad = [s.attrs for s in events if s.attrs.get("fallback")]
        if bad or not events:
            raise AssertionError(f"{phase}: kernel fallbacks {bad} "
                                 f"(events {len(events)})")
        compiled = engine._train_step.lower(engine.state,
                                            placed.placed).compile()
        ma = compiled.memory_analysis()
        log(phase, arguments_gb=round(ma.argument_size_in_bytes / 1e9, 3),
            temp_gb=round(ma.temp_size_in_bytes / 1e9, 3),
            output_gb=round(ma.output_size_in_bytes / 1e9, 3),
            alias_gb=round(ma.alias_size_in_bytes / 1e9, 3))
        require_kernel("kernels", "train_step", compiled.as_text())


def phase_swa_moe_trainer(sz: Sizes, seed: int,
                          check_kernels: bool = True) -> None:
    """Window and full attention layers in one stack TRAINED through the same
    entry points: the flash kernel's band path beside its full path (32 query
    heads over 4 K/V heads of 128), the gate on the attention's output, a
    share of sigmoid-routed experts with its backward, and the router's bias
    moved by the model's rule inside the one train program.  Fails on a
    kernel that gave way to its reference, on a banded or a plain flash
    kernel missing from the compiled step, and on a bias that did not move."""
    from deepspeed_tpu.observability.trace import tracer

    phase = "swa_moe_trainer"
    kinds = sz.swa_train_kinds
    log(phase, preset=sz.swa_train_preset, kinds=kinds,
        held=sz.swa_train_held, vocab=sz.swa_train_vocab,
        why="one chip of sixteen that share each layer: the dense layer "
            "and one whole period of routed ones at 14 B a parameter")
    tracer.clear()
    cfg, params, engine, batch = build_trainer(
        sz, seed, len(kinds), {}, preset=sz.swa_train_preset, overrides=dict(
            vocab_size=sz.swa_train_vocab, layer_types=kinds,
            moe_experts_held=sz.swa_train_held,
            mlp_layer_types=("dense",) + ("sparse",) * (len(kinds) - 1)),
        micro=sz.swa_train_micro, seq=sz.swa_train_seq)
    bias = np.asarray(params["layers"]["S"]["moe"]["router_bias"])
    del params
    gc.collect()
    log(phase, params_m=round(cfg.num_params() / 1e6, 1),
        micro_batch=sz.swa_train_micro, seq=sz.swa_train_seq,
        attn=cfg.attn_impl, window=cfg.sliding_window)
    placed = engine.place_batch(batch)
    _, step_s = timed_steps(phase, engine, placed, 1, sz.swa_train_steps)
    out = engine.train_batch(placed)
    counts = np.asarray(out["moe_expert_counts"])
    moved = np.abs(np.asarray(engine.state.params["layers"]["S"]["moe"][
        "router_bias"]) - bias).max()
    log(phase, tokens_per_second=round(
        engine.train_batch_size * sz.swa_train_seq / step_s, 1),
        busiest_expert_over_mean=round(float(
            (counts.max(-1) / counts.mean(-1)).mean()), 3),
        bias_moved_by=round(float(moved), 5),
        **{k: round(float(out[k]), 4) for k in (
            "loss", "moe_local_rows", "moe_rows_max", "moe_experts_hit",
            "grad_norm")})
    memory_line(phase, jax.devices()[0])
    events = [s for s in tracer.spans() if s.name in (
        "kernel/flash_attention_tiles", "kernel/grouped_matmul_tiles")]
    log(phase, kernel_events=sorted({
        json.dumps({"name": s.name, **s.attrs}, sort_keys=True)
        for s in events}))
    if not 0 < moved <= 2 * cfg.moe_bias_update_rate * (
            2 + sz.swa_train_steps):
        raise AssertionError(f"{phase}: the routers' biases moved by "
                             f"{moved}, the rule's step is "
                             f"{cfg.moe_bias_update_rate}")
    if check_kernels:
        bad = [s.attrs for s in events if s.attrs.get("fallback")]
        if bad or not events:
            raise AssertionError(f"{phase}: kernel fallbacks {bad} "
                                 f"(events {len(events)})")
        compiled = engine._train_step.lower(engine.state,
                                            placed.placed).compile()
        ma = compiled.memory_analysis()
        log(phase, arguments_gb=round(ma.argument_size_in_bytes / 1e9, 3),
            temp_gb=round(ma.temp_size_in_bytes / 1e9, 3),
            output_gb=round(ma.output_size_in_bytes / 1e9, 3),
            alias_gb=round(ma.alias_size_in_bytes / 1e9, 3))
        text = compiled.as_text()
        require_kernel("kernels", "train_step", text)
        for name in ("flash_attention_fwd_band", "flash_attention_bwd_dq",
                     "flash_attention_bwd_dkv_band", "grouped_matmul"):
            if name not in text:
                raise AssertionError(f"{phase}: no {name} in the train step")


# ---------------------------------------------------------------------------
# phases 2 and 3: the server
# ---------------------------------------------------------------------------


def _post(port: int, path: str, obj: dict) -> Tuple[Any, Any]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    conn.request("POST", path, json.dumps(obj),
                 {"Content-Type": "application/json"})
    return conn, conn.getresponse()


def _get_json(port: int, path: str) -> Any:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    try:
        return json.loads(conn.getresponse().read())
    finally:
        conn.close()


def _complete(port: int, prompt: List[int], n: int, stream: bool
              ) -> Tuple[List[int], str]:
    """One ``/v1/completions`` request → (tokens, finish_reason)."""
    conn, resp = _post(port, "/v1/completions",
                       {"prompt": prompt, "max_tokens": n, "stream": stream})
    try:
        if resp.status != 200:
            raise AssertionError(f"HTTP {resp.status}: {resp.read()[:300]}")
        if not stream:
            choice = json.loads(resp.read())["choices"][0]
            return choice["tokens"], choice["finish_reason"]
        tokens, finish = [], None
        for raw in resp:
            raw = raw.strip()
            if not raw.startswith(b"data: "):
                continue
            if raw[6:] == b"[DONE]":
                break
            choice = json.loads(raw[6:])["choices"][0]
            if choice.get("token") is not None:
                tokens.append(choice["token"])
            else:
                finish = choice["finish_reason"]
        return tokens, finish
    finally:
        conn.close()


def host_params(cfg, seed: int, chunk: int = 8):
    """bf16 weights of the whole depth on the host, as a checkpoint would
    be, without the chip ever holding them whole: made on the chip ``chunk``
    layers at a time (the host's generator is slow, and its f32
    intermediates of a whole stack do not fit the host), moved over, and
    joined leaf by leaf so that the host holds one copy and one leaf more."""
    cpu = jax.local_devices(backend="cpu")[0]
    part_cfg = dataclasses.replace(cfg, num_layers=min(chunk, cfg.num_layers))
    init = jax.jit(lambda k: tfm.init_params(k, part_cfg))
    key = jax.random.PRNGKey(seed)
    params, parts = None, []
    for i in range(0, cfg.num_layers, part_cfg.num_layers):
        part = jax.device_put(init(jax.random.fold_in(key, i)), cpu)
        leaves, treedef = jax.tree_util.tree_flatten(part.pop("layers"))
        parts.append(leaves)
        params = params or part  # embedding, head and final norm: the first
    joined = []
    for j in range(len(parts[0])):
        joined.append(jnp.concatenate([p[j] for p in parts], axis=0))
        for p in parts:
            p[j] = None
    params["layers"] = jax.tree_util.tree_unflatten(treedef, joined)
    return params


def reference_margins(params, cfg, sequences: List[List[int]]):
    """Plain reference: ``tfm.forward`` on the XLA attention path over each
    whole sequence (prompt + served tokens), same parameters.  Returns, for
    every position p, how far the reference logit of the token at p+1 lies
    under the reference maximum at p, how many tokens the reference ranks
    above it, and the spread of the logits over the vocabulary.  XLA
    attention is plain causal: right while every sequence is shorter than
    the model's window, which is checked."""
    s_max = -(-max(len(s) for s in sequences) // 64) * 64
    if cfg.sliding_window and s_max > cfg.sliding_window:
        raise AssertionError("the reference has no window: keep sequences "
                             f"under {cfg.sliding_window} tokens")
    tokens = np.zeros((len(sequences), s_max), np.int32)
    for i, s in enumerate(sequences):
        tokens[i, :len(s)] = s

    @jax.jit
    def margins(p, t):
        logits = tfm.forward(p, t, cfg, attn_fn=tfm.xla_attention)
        # one materialised f32 copy, so that the maximum, the served logit
        # and the rank all read the same values
        logits = jax.lax.optimization_barrier(
            logits[:, :-1].astype(jnp.float32))
        served = jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)
        rank = (logits > served).sum(-1)
        return logits.max(-1) - served[..., 0], rank, logits.std(-1).mean()

    m, rank, std = margins(params, jnp.asarray(tokens))
    return np.asarray(m), np.asarray(rank), float(std)


def phase_server(sz: Sizes, seed: int, quantize_bits: int,
                 check_kernels: bool = True) -> None:
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.serving.balancer import ReplicaPool
    from deepspeed_tpu.serving.config import ServingConfig
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.serving.server import create_server

    phase = f"server-int{quantize_bits}" if quantize_bits else "server"
    device = jax.devices()[0]
    tracer.clear()  # this phase's steps and kernel events alone
    if quantize_bits:
        layers, jobs = sz.quant_layers, sz.quant_requests
        why = ("int8 codes are 0.22 GB a layer, so the full depth is 7.5 GB; "
               "the bf16 weights are made on the host and only the codes "
               "reach the chip")
    else:
        layers, jobs = sz.serve_layers, sz.requests
        why = ("bf16 weights are 0.44 GB a layer + 0.52 GB embedding and "
               "head, the KV cache 4 KiB a token a layer: 24 layers are "
               "11.0 GB + 1.6 GB for 16k tokens, of one 16 GB chip")
    log(phase, preset=sz.preset, layers=layers, why=why)
    memory_line(phase + "/start", device)

    # the engine as serving/server.py:main builds it (build_engine_factory →
    # ReplicaPool.build → create_server), over a config with the depth cut
    # and bf16 parameters, which the server's flags cannot say
    cfg = tfm.get_config(sz.preset, num_layers=layers, dtype="bfloat16",
                         param_dtype="bfloat16")
    t0 = time.perf_counter()
    if quantize_bits:
        params = host_params(cfg, seed)
    else:
        params = jax.jit(lambda k: tfm.init_params(k, cfg))(
            jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    log(phase, init_seconds=round(time.perf_counter() - t0, 1),
        params_m=round(cfg.num_params() / 1e6, 1))
    v2 = V2Config(max_tokens_per_step=sz.max_tokens_per_step,
                  max_seqs=sz.max_seqs, block_size=sz.block_size,
                  num_blocks=sz.num_blocks,
                  max_blocks_per_seq=sz.max_blocks_per_seq, dtype="bfloat16",
                  quantize_bits=quantize_bits)
    log(phase, **{k: getattr(v2, k) for k in (
        "max_tokens_per_step", "max_seqs", "block_size", "num_blocks",
        "max_blocks_per_seq", "quantize_bits")})
    t0 = time.perf_counter()
    scfg = ServingConfig(num_replicas=1, max_queue=64,
                         default_max_tokens=16, drain_timeout_s=120.0)
    metrics = ServingMetrics()
    pool = ReplicaPool.build(lambda: InferenceEngineV2(cfg, params, v2),
                             scfg, metrics=metrics)
    engine = pool.replicas[0].engine
    if quantize_bits:
        params = engine.params  # the codes, on the chip; drop the host bf16
        log(phase, quantize_seconds=round(time.perf_counter() - t0, 1))
    # (params, caches, tokens, positions, seq_index | block_tables, ...)
    prefill = engine._fwd = Recorded(engine._fwd, rows_arg=6)
    decode = engine._decode_fwd = Recorded(engine._decode_fwd, rows_arg=5)
    pool.start()
    pool.wait_ready(timeout=scfg.spawn_timeout_s)
    server = create_server(pool, metrics, scfg, port=0,
                           model_name=sz.preset)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    port = server.server_port
    try:
        rng = np.random.default_rng(seed + 1)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n, _ in jobs]

        # one request alone first: it compiles the prefill and decode steps
        t0 = time.perf_counter()
        warm, _ = _complete(port, prompts[0][:32], 4, stream=False)
        log(phase, warmup_request_seconds=round(time.perf_counter() - t0, 2),
            note="compiles the prefill and the decode step")
        if len(warm) != 4:
            raise AssertionError(f"{phase}: warm-up gave {len(warm)} tokens")

        # then the rest: half at once, half staggered, so that prompts
        # arrive while earlier requests decode (mixed steps), several rows
        # decode together, and the tail is pure decode
        results: Dict[int, Tuple[List[int], str]] = {}
        errors: List[str] = []

        def run(i: int) -> None:
            try:
                results[i] = _complete(port, prompts[i], jobs[i][1],
                                       stream=i % 2 == 0)
            except Exception as e:  # re-raised below, on the main thread
                errors.append(f"request {i}: {e!r}")

        calls0 = (prefill.calls, decode.calls)
        t0 = time.perf_counter()
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(jobs))]
        for i, t in enumerate(threads):
            t.start()
            if i >= len(threads) // 2:
                time.sleep(0.15)
        for t in threads:
            t.join(timeout=600)
            if t.is_alive():
                raise AssertionError(f"{phase}: a request hung")
        wall = time.perf_counter() - t0
        if errors:
            raise AssertionError(f"{phase}: {errors}")
        for i, (_, n) in enumerate(jobs):
            toks, finish = results[i]
            if len(toks) != n or finish != "length":
                raise AssertionError(
                    f"{phase}: request {i} asked {n} tokens, got "
                    f"{len(toks)} (finish_reason {finish})")
        mixed, pure = prefill.calls - calls0[0], decode.calls - calls0[1]
        n_tok = sum(n for _, n in jobs)
        log(phase, requests=len(jobs), streamed=(len(jobs) + 1) // 2,
            unary=len(jobs) // 2, tokens_served=n_tok,
            wall_seconds=round(wall, 2), prefill_or_mixed_steps=mixed,
            pure_decode_steps=pure, most_rows_in_a_mixed_step=prefill.max_rows,
            most_rows_in_a_decode_step=decode.max_rows)
        if mixed == 0 or pure == 0 or decode.max_rows < 2:
            raise AssertionError(
                f"{phase}: wanted mixed steps and pure-decode steps over "
                f"several rows; ran {mixed} and {pure}, at most "
                f"{decode.max_rows} rows")

        health = _get_json(port, "/healthz")
        log(phase, healthz=health["status"])
        if health["status"] != "ok":
            raise AssertionError(f"{phase}: /healthz said {health}")
        if quantize_bits:
            check_gemm_tiles(phase, port)
        check_prefill_tiles(phase, [
            e["args"] for e in _get_json(port, "/debug/trace")["traceEvents"]
            if e["name"] == "kernel/paged_attention_prefill_tiles"])
        check_step_copies(phase)
    finally:
        pool.drain(scfg.drain_timeout_s)
        server.shutdown()
        server.server_close()
    free, total = engine.free_blocks, engine.total_blocks
    log(phase, drained=True, free_blocks=free, total_blocks=total)
    if free != total:
        raise AssertionError(
            f"{phase}: KV blocks leaked: {free} free of {total}")
    del engine, pool, server  # the KV cache goes; the reference needs room
    gc.collect()

    # the served tokens against the plain reference, same parameters.  For
    # the quantized server the reference reads the same int8 codes through
    # the mixed GEMM, so that kernel is checked against its dequantize
    # oracle first, at the projections' own shapes.
    if quantize_bits:
        check_mixed_gemm(phase, params, cfg)
    elif check_kernels:
        check_decode_attention(phase, cfg, sz)
    sequences = [prompts[i] + results[i][0] for i in range(len(jobs))]
    m, rank, std = reference_margins(params, cfg, sequences)
    picks = np.concatenate([
        np.stack([m[i, n_prompt - 1:n_prompt - 1 + n_new],
                  rank[i, n_prompt - 1:n_prompt - 1 + n_new]])
        for i, (n_prompt, n_new) in enumerate(jobs)], axis=1)
    worst = float(picks[0].max())
    log(phase, reference="tfm.forward, XLA attention, same parameters",
        logit_std_over_vocab=round(std, 3), worst_margin=round(worst, 4),
        mean_margin=round(float(picks[0].mean()), 5),
        allowed_margin=sz.margin,
        tokens_equal_to_reference_argmax=int((picks[1] == 0).sum()),
        worst_rank=int(picks[1].max()), tokens_checked=picks.shape[1])
    if not np.isfinite(m).all() or worst > sz.margin:
        raise AssertionError(
            f"{phase}: a served token lies {worst} under the reference "
            f"maximum (allowed {sz.margin})")
    memory_line(phase, device)
    if check_kernels:
        name = f"int{quantize_bits}" if quantize_bits else "bf16"
        require_kernel("kernels", f"prefill_step@{name}",
                       prefill.compiled_text())
        require_kernel("kernels", f"decode_step@{name}",
                       decode.compiled_text())


def phase_moe_server(sz: Sizes, seed: int, check_kernels: bool = True
                     ) -> None:
    """An int8 sparse-expert model through ``InferenceEngineV2`` (which
    quantizes it, experts included, on the host): chunked prefill and decode
    over the routed experts' grouped W8A16 GEMM; every served token within
    ``margin`` of the maximum of ``tfm.forward`` over the same codes; no
    grouped or mixed GEMM fallen back; no KV block leaked."""
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-moe-int8"
    cfg = tfm.get_config(sz.moe_preset, num_layers=sz.moe_layers,
                         dtype="bfloat16", param_dtype="bfloat16",
                         attn_impl="xla")
    log(phase, preset=sz.moe_preset, layers=cfg.num_layers,
        experts=cfg.num_experts, top_k=cfg.moe_top_k,
        params_m=round(cfg.num_params() / 1e6, 1))
    params = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    tracer.clear()
    t0 = time.perf_counter()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.num_blocks,
        max_blocks_per_seq=sz.max_blocks_per_seq, quantize_bits=8,
        quantize_group=min(256, cfg.hidden_size)))
    log(phase, quantize_seconds=round(time.perf_counter() - t0, 1))
    rng = np.random.default_rng([seed, 27])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n, _ in sz.moe_requests]
    uids = [engine.put(p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, sz.moe_requests)]
    whole = engine.generate_all(burst=1)  # step by step, as a server does
    out = {u: whole[u][len(p):] for p, u in zip(prompts, uids)}
    for uid, (_, n) in zip(uids, sz.moe_requests):
        if len(out[uid]) != n:
            raise AssertionError(f"{phase}: asked {n} tokens, got "
                                 f"{len(out[uid])}")
    if engine.free_blocks != engine.total_blocks:
        raise AssertionError(f"{phase}: KV blocks leaked")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    events = [s.attrs for s in tracer.spans()
              if s.name.startswith("kernel/") and s.name.endswith("_tiles")]
    grouped = sorted({(a["k"], a["n"], a["rows"], a["tile_m"], a["tn"])
                      for a in events if "tile_m" in a and "fallback" not in a})
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        experts_hit=[round(a["moe_experts_hit"], 1) for a in steps[-3:]],
        gemm_tile_events=len(events), grouped_k_n_rows_tilem_tn=grouped)
    check_prefill_tiles(phase, [
        s.attrs for s in tracer.spans()
        if s.name == "kernel/paged_attention_prefill_tiles"])
    check_step_copies(phase)
    fallen = [a for a in events if "fallback" in a]
    sliced = [a for a in events if a.get("layers") == 0]
    if check_kernels and (fallen or sliced or not grouped):
        raise AssertionError(f"{phase}: of {len(events)} GEMM tile events, "
                             f"grouped {grouped}, fallen back: {fallen}, "
                             f"on a sliced layer's copy: {sliced}")
    sequences = [p + out[u] for p, u in zip(prompts, uids)]
    m, rank, std = reference_margins(engine.params, cfg, sequences)
    worst, exact, checked = 0.0, 0, 0
    for i, (p, u) in enumerate(zip(prompts, uids)):
        rows = slice(len(p) - 1, len(p) - 1 + len(out[u]))
        worst = max(worst, float(m[i, rows].max()))
        exact += int((rank[i, rows] == 0).sum())
        checked += len(out[u])
    log(phase, served_tokens=checked, reference_argmax=exact,
        worst_margin=round(worst, 4), allowed=sz.margin,
        logit_std=round(std, 3))
    if not worst <= sz.margin:
        raise AssertionError(f"{phase}: a served token lies {worst:.3f} "
                             f"under the reference's maximum")
    memory_line(phase, jax.local_devices()[0])


def phase_swa_moe_server(sz: Sizes, seed: int, check_kernels: bool = True
                         ) -> None:
    """A model with sliding-window AND global attention layers (one period
    of Mellum2's pattern, int8 experts at group 128, YaRN on the global
    layer) through ``InferenceEngineV2``: contexts past two windows, so the
    window layers' blocks go back to their pool while the sequences run;
    every served token within ``margin`` of the maximum of the plain
    reference (``benchmark/reference/swa_moe_decoder.py``) over the same
    codes; both kinds of layer on the paged kernels (the ring's
    ``kernel/paged_attention_window`` events and the windowed ones among
    ``kernel/paged_attention_decode_tiles``, none fallen back); no GEMM
    fallen back; both pools whole after the drain."""
    from benchmark.drivers.serve_swa_moe import published_model
    from benchmark.reference import swa_moe_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-swa-moe-int8"
    cfg = tfm.get_config(sz.swa_preset, num_layers=sz.swa_layers,
                         dtype="bfloat16", param_dtype="bfloat16")
    log(phase, preset=sz.swa_preset, layers=cfg.num_layers,
        kinds=cfg.layer_kinds, window=cfg.sliding_window,
        experts=cfg.num_experts, params_m=round(cfg.num_params() / 1e6, 1))
    params = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    tracer.clear()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.num_blocks,
        max_blocks_per_seq=sz.swa_max_blocks_per_seq, quantize_bits=8,
        quantize_group=128))
    del params
    if engine.kv_win is None:
        raise AssertionError(f"{phase}: the engine built one pool")
    rng = np.random.default_rng([seed, 31])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n, _ in sz.swa_requests]
    uids = [engine.put(p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, sz.swa_requests)]
    whole = engine.generate_all()
    out = {u: whole[u][len(p):] for p, u in zip(prompts, uids)}
    for uid, (_, n) in zip(uids, sz.swa_requests):
        if len(out[uid]) != n:
            raise AssertionError(f"{phase}: asked {n} tokens, got "
                                 f"{len(out[uid])}")
    pools = {"global": engine.kv, "window": engine.kv_win}
    for name, m in pools.items():
        m.allocator.check_consistency()
        if m.allocator.free_blocks != m.allocator.num_blocks or m.reserved:
            raise AssertionError(f"{phase}: the {name} pool leaked blocks")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    windows = sorted({(a.get("kind", "decode"), a["window"])
                      for name, a in events
                      if (name == "kernel/paged_attention_window"
                          or name == "kernel/paged_attention_decode_tiles"
                          and a["window"]) and "fallback" not in a})
    read, full = (sum(a[k] for a in steps)
                  for k in ("kv_blocks_read", "kv_blocks_full"))
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        pool_blocks={n: m.allocator.num_blocks for n, m in pools.items()},
        window_blocks_freed=engine.kv_win.trimmed,
        kv_read_vs_full_pct=round(100 * read / full, 1),
        kernel_events=len(events), window_kernels=windows)
    check_prefill_tiles(phase, [
        a for name, a in events
        if name == "kernel/paged_attention_prefill_tiles"])
    check_step_copies(phase)
    fallen = [e for e in events if "fallback" in e[1]]
    if check_kernels and (fallen or len(windows) < 2):
        raise AssertionError(f"{phase}: kernels fallen back: {fallen}; "
                             f"windowed kernels traced: {windows}")
    if engine.kv_win.trimmed < sum(
            (n - cfg.sliding_window) // sz.block_size
            for n, _ in sz.swa_requests if n > cfg.sliding_window):
        raise AssertionError(f"{phase}: only {engine.kv_win.trimmed} window "
                             f"blocks were freed behind the window")
    model = published_model(cfg)
    served_params = engine.params
    del engine
    gc.collect()
    worst, exact, checked = 0.0, 0, 0
    for p, u in zip(prompts, uids):
        seq = np.zeros(-(-(len(p) + len(out[u])) // 256) * 256, np.int32)
        seq[:len(p) + len(out[u])] = p + out[u]
        m, rank = reference.served_margins(served_params, model,
                                           jnp.asarray(seq), len(p))
        m, rank = np.asarray(m)[:len(out[u])], np.asarray(rank)[:len(out[u])]
        worst = max(worst, float(m.max()))
        exact += int((rank == 0).sum())
        checked += len(out[u])
    log(phase, served_tokens=checked, reference_argmax=exact,
        worst_margin=round(worst, 4), allowed=sz.margin)
    if not worst <= sz.margin:
        raise AssertionError(f"{phase}: a served token lies {worst:.3f} "
                             f"under the reference's maximum")
    memory_line(phase, jax.local_devices()[0])


def check_gemm_tiles(phase: str, port: int) -> None:
    """``GET /debug/trace`` of the warmed server: every mixed GEMM the step
    programs traced left a ``kernel/mixed_gemm_tiles`` event with its tile,
    none gave way to dequantize-then-matmul, and each read the layer stack in
    place (``layers`` > 0; 0 is a layer the scan sliced, which is a copy)."""
    events = [e["args"] for e in _get_json(port, "/debug/trace")["traceEvents"]
              if e["name"] == "kernel/mixed_gemm_tiles"]
    fallen = [a for a in events if "fallback" in a]
    sliced = [a for a in events if a["layers"] == 0]
    tiles = sorted({(a["m"], a["k"], a["n"], a["tm"], a["tn"], a["tk"],
                     a["grid_steps"]) for a in events if "fallback" not in a})
    log(phase, mixed_gemm_tile_events=len(events),
        on_the_layer_stack=len(events) - len(sliced),
        m_k_n_tm_tn_tk_steps=tiles)
    if not events or fallen or sliced:
        raise AssertionError(
            f"{phase}: /debug/trace shows {len(events)} "
            f"kernel/mixed_gemm_tiles events, fallen back: {fallen}, on a "
            f"sliced layer's copy: {sliced}")


def check_prefill_tiles(phase: str, events: list) -> None:
    """The ``kernel/paged_attention_prefill_tiles`` events of the warmed
    server (one a call site of the mixed step program traced): each names the
    tiles the picker chose for the flat queries, and none gave way to the
    blockwise XLA path."""
    fallen = [a for a in events if "fallback" in a]
    tiles = sorted({(a["t"], a["heads"], a["kv"], a["window"], a["tq"],
                     a["kb"]) for a in events if "fallback" not in a})
    log(phase, prefill_attention_tile_events=len(events),
        t_heads_kv_window_tq_kb=tiles)
    if not events or fallen:
        raise AssertionError(
            f"{phase}: {len(events)} kernel/paged_attention_prefill_tiles "
            f"events, fallen back: {fallen}")


def check_step_copies(phase: str) -> None:
    """The ``engine/step`` spans of the phase (it cleared the ring at its
    start): every decode and every mixed step that ran the device made ONE
    host-to-device copy before its program was called (``h2d_copies``,
    ``h2d_bytes``: PERF.md section 3); the median ``engine/h2d`` by kind of
    step is printed beside it, in ms."""
    import statistics

    from deepspeed_tpu.observability.trace import tracer

    spans = tracer.spans()
    steps = [s.attrs for s in spans if s.name == "engine/step"
             and s.attrs["kind"] in ("decode", "mixed")
             and "device_ms" in s.attrs]
    by_kind: Dict[str, List[float]] = {}
    for s in spans:
        if s.name == "engine/h2d":
            by_kind.setdefault(s.attrs["kind"], []).append(s.duration_s * 1e3)
    copies = sorted({(a["kind"], a.get("h2d_copies"), a.get("h2d_bytes"))
                     for a in steps})
    log(phase, steps_on_the_device=len(steps), kind_h2d_copies_bytes=copies,
        engine_h2d_ms_p50={k: round(statistics.median(v), 3)
                           for k, v in sorted(by_kind.items())})
    kinds = {a["kind"] for a in steps}
    if kinds != {"decode", "mixed"} or any(c != 1 for _, c, _ in copies):
        raise AssertionError(
            f"{phase}: wanted decode and mixed steps of one host-to-device "
            f"copy each; ran (kind, h2d_copies, h2d_bytes) {copies}")


def check_decode_attention(phase: str, cfg, sz: Sizes) -> None:
    """The paged decode kernel twice back to back in one program, on one
    pool with different tables and contexts (rows without a context first,
    last and between live ones; contexts on both sides of a block's and of a
    fetch's end), each call against the blockwise XLA path on the same
    operands: a copy the first call left unwaited lands in the second's
    slots, which shows on the chip and nowhere on a CPU (the interpreter
    copies when a copy is started)."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _decode_attention_xla, paged_decode_attention, pick_decode_tiles)

    heads, kv, d, bs = cfg.num_heads, cfg.kv_heads, cfg.head_dim, sz.block_size
    rows, width = 2 * sz.max_seqs, sz.max_blocks_per_seq
    kb = pick_decode_tiles(rows, heads, kv, d, bs, jnp.bfloat16).kb
    edges = [0, 1, bs - 1, bs, bs + 1, kb * bs - 1, kb * bs, kb * bs + 1, 0,
             0, width * bs, 2 * kb * bs + 7, 0]
    rng = np.random.default_rng(0)
    calls = []
    for call in range(2):
        ctx = np.asarray([edges[(r + 5 * call) % len(edges)]
                          for r in range(rows)], np.int32)
        tables = np.stack([rng.permutation(sz.num_blocks)[:width]
                           for _ in range(rows)]).astype(np.int32)
        calls.append((jnp.asarray(tables), jnp.asarray(ctx)))
    key = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(key[0], (rows, heads, d), jnp.bfloat16)
    pool = (2, sz.num_blocks, bs, kv, d)
    k, v = (jax.random.normal(x, pool, jnp.bfloat16) for x in key[1:])

    def both(attend):
        return jax.jit(lambda q, k, v: [attend(q, k, v, 1, *c)
                                        for c in calls])(q, k, v)

    worst = 0.0
    for (_, ctx), got, want in zip(calls, both(paged_decode_attention),
                                   both(_decode_attention_xla)):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        err = float(np.abs(got - want).max())
        worst = max(worst, err)
        # bf16 results of values up to 3-4: a rounding is 2**-7
        if not err < 3e-2 or got[np.asarray(ctx) == 0].any():
            raise AssertionError(
                f"{phase}: paged_decode_attention is {err} off the blockwise "
                f"XLA path (contexts {np.asarray(ctx).tolist()})")
    log(phase, decode_attention_vs_xla_worst_abs_err=round(worst, 5),
        rows=rows, heads=heads, kv=kv, kb=kb, calls=len(calls))


def check_mixed_gemm(phase: str, params, cfg) -> None:
    """The int8 mixed GEMM against dequantize-then-matmul on layer 0's
    projections, at the rows the benchmark's serving cells run: 32 decode
    rows and a chunk of 512 tokens."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                     dequantize_gemm_weight,
                                                     mixed_gemm)

    worst = 0.0
    layer = params["layers"]
    for name, qw in (("wq", layer["attn"]["wq"]),
                     ("wk", layer["attn"]["wk"]),
                     ("wo", layer["attn"]["wo"]),
                     ("w_in", layer["mlp"]["w_in"]),
                     ("w_out", layer["mlp"]["w_out"])):
        qw0 = QuantizedWeight(qw.codes[0], qw.scales[0], qw.bits, qw.group,
                              qw.k)
        for m_rows in (32, 512):
            x = jax.random.normal(jax.random.PRNGKey(m_rows),
                                  (m_rows, qw0.k_features), jnp.bfloat16)
            got = jax.jit(mixed_gemm)(x, qw0).astype(jnp.float32)
            want = jnp.dot(x, dequantize_gemm_weight(qw0).astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
            err = float(jnp.abs(got - want).max() / jnp.abs(want).max())
            worst = max(worst, err)
            if not err < 2e-2:
                raise AssertionError(
                    f"{phase}: mixed_gemm {name} M={m_rows} is {err} off "
                    f"its dequantize oracle")
    log(phase, mixed_gemm_vs_oracle_worst_rel_err=round(worst, 5))


# ---------------------------------------------------------------------------
# four chips: ZeRO-3 sharded training, and what it is compared with
# ---------------------------------------------------------------------------


def check_state_updates(phase: str, cfg) -> None:
    """The program's two state updates alone, on the device at the model's
    sizes (``ssd_chunk_scan`` on rows of 1, 127, 128, 129 and 300 tokens in
    one call, each from the state of its slot, then ``ssm_decode_update``),
    against the reference's recurrence one token at a time: the final states
    within 1e-3 of the largest state and ``y`` within 2e-2 (it carries the
    chunk's bfloat16 products); the recurrence with its state kept in
    bfloat16 between tokens has to read over 1e-3.  (The serving cell
    compares the engine's own state arrays: ``serve_ssm_moe.check_logits``.)"""
    from benchmark.reference import ssm_moe_decoder as reference
    from deepspeed_tpu.ops.pallas.ssm import ssd_chunk_scan, ssm_decode_update

    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, Q = cfg.mamba_n_groups, cfg.mamba_state_size, cfg.mamba_chunk_size
    lens = np.asarray([1, Q - 1, Q, Q + 1, 2 * Q + 44], np.int32)
    T, R = int(lens.sum()), len(lens)
    k = jax.random.split(jax.random.PRNGKey(5), 8)
    dtype = jnp.dtype(cfg.dtype)
    x = jax.random.normal(k[0], (T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 3.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    B = jax.random.normal(k[3], (T, G, N)).astype(dtype)
    C = jax.random.normal(k[4], (T, G, N)).astype(dtype)
    D = jax.random.normal(k[5], (H,))
    ssm0 = jax.random.normal(k[6], (1, R + 1, H, P, N))
    starts = jnp.asarray(np.cumsum(lens) - lens, jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False])

    @jax.jit
    def program(ssm):
        y, ssm = ssd_chunk_scan(ssm, jnp.int32(0), x, dt, A, B, C, D, starts,
                                jnp.asarray(lens),
                                jnp.arange(R, dtype=jnp.int32), fresh,
                                jnp.asarray(lens >= 2), Q)

        def put(a):  # the row of one token, in slot order
            return jnp.zeros((R + 1,) + a.shape[1:], a.dtype).at[0].set(a[0])

        one = jnp.zeros((R + 1,), bool).at[0].set(True)
        y1, ssm = ssm_decode_update(ssm, jnp.int32(0), put(x), put(dt), A,
                                    put(B), put(C), D, one,
                                    jnp.zeros((R + 1,), bool))
        return y.at[0].set(y1[0]), ssm

    y, got = program(ssm0)
    f32 = jnp.float32
    heads = lambda m: jnp.repeat(m.astype(f32), H // G, 1)  # noqa: E731
    longest = int(lens.max())

    def row(a, r):  # a row's tokens, padded to the longest row
        s, n = int(starts[r]), int(lens[r])
        return jnp.pad(a[s:s + n], ((0, longest - n),) + ((0, 0),)
                       * (a.ndim - 1))

    recur = jax.jit(reference.recurrence, static_argnums=7)
    out = {}
    for name, low in (("float32", False), ("bfloat16", True)):
        worst_y = worst_s = 0.0
        for r in range(R):
            s, n = int(starts[r]), int(lens[r])
            first = jnp.zeros((H, P, N)) if bool(fresh[r]) else ssm0[0, r]
            # a padded token has dt 0: no decay and no input, the state stays
            want, state = recur(row(x, r).astype(f32), row(dt, r), A,
                                heads(row(B, r)), heads(row(C, r)), D, first,
                                low)
            worst_y = max(worst_y, float(jnp.abs(y[s:s + n] - want[:n]).max()
                                         / jnp.abs(want[:n]).max()))
            worst_s = max(worst_s, float(jnp.abs(got[0, r] - state).max()
                                         / jnp.abs(state).max()))
        out[name] = (worst_y, worst_s)
    log(phase, state_updates_rows=lens.tolist(),
        state_rel=float(f"{out['float32'][1]:.3g}"),
        y_rel=float(f"{out['float32'][0]:.3g}"),
        state_rel_kept_in_bfloat16=float(f"{out['bfloat16'][1]:.3g}"))
    if not (out["float32"][1] <= 1e-3 and out["float32"][0] <= 2e-2
            and out["bfloat16"][1] > 1e-3):
        raise AssertionError(f"{phase}: the state updates against the "
                             f"recurrence one token at a time: {out}")


def phase_ssm_moe_server(sz: Sizes, seed: int, check_kernels: bool = True
                         ) -> None:
    """A model of one mixer a layer (Mamba-2, routed MoE with a shared
    expert, attention without positions: a cut of Nemotron-3-Nano's pattern,
    int8 at group 128) through ``InferenceEngineV2``: per-sequence state in
    slots beside the paged K/V, a prompt chunked three times with decode
    rows riding in its mixed steps, then a second batch through the SAME
    slots (what the first left there must not be read: a row that starts a
    sequence starts from zeros), then the first batch once more with the
    logits of the engine's own step programs tapped; the tiles of both state
    updates and of the grouped GEMM at the experts' width 1856 (stored 1920)
    printed, none fallen back; every block and every slot free after each
    drain.  Against the plain reference
    (``benchmark/reference/ssm_moe_decoder.py``) over the same codes, as the
    serving cell compares (``PERF.md`` section 6: with seeded random weights
    128 near-uniform router scores tie, bfloat16 falls on the other side of
    a tie now and then, and every later position reads the flip through the
    states): the tapped rows against the reference HELD TO THE PROGRAM'S
    ROUTING CHOICES, median and worst row (a state read from the wrong slot
    or left from the sequence before fails the worst row), and of the served
    tokens the share within ``margin`` of the free reference's maximum."""
    from benchmark.drivers.serve_ssm_moe import (draw_small_tensors,
                                                 low_bits_share,
                                                 published_model, row_errors)
    from benchmark.reference import ssm_moe_decoder as reference
    from benchmark.routing_tap import RoutedLogitTap
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-ssm-moe-int8"
    cfg = tfm.get_config(sz.ssm_preset, num_layers=len(sz.ssm_pattern),
                         mixer_pattern=sz.ssm_pattern, dtype="bfloat16",
                         param_dtype="bfloat16")
    log(phase, preset=sz.ssm_preset, pattern=sz.ssm_pattern,
        experts=cfg.num_experts, top=cfg.moe_top_k,
        params_m=round(cfg.num_params() / 1e6, 1))
    params = jax.jit(lambda k: draw_small_tensors(
        tfm.init_params(k, cfg), seed))(jax.random.PRNGKey(seed))
    tracer.clear()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.num_blocks,
        max_blocks_per_seq=sz.ssm_max_blocks_per_seq, quantize_bits=8,
        quantize_group=128))
    del params
    rng = np.random.default_rng([seed, 37])
    served, tapped = [], []
    for batch, tap_it in ((sz.ssm_requests, False), (sz.ssm_second, False),
                          (sz.ssm_requests, True)):
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n, _ in batch]
        tap = RoutedLogitTap(engine) if tap_it else None
        try:
            uids = [engine.put(p, max_new_tokens=n)
                    for p, (_, n) in zip(prompts, batch)]
            # step by step under the tap (a state model never bursts)
            whole = engine.generate_all(burst=1) if tap_it \
                else engine.generate_all()
        finally:
            if tap is not None:
                tap.remove()
        for p, u, (_, n) in zip(prompts, uids, batch):
            if len(whole[u]) - len(p) != n:
                raise AssertionError(f"{phase}: asked {n} tokens, got "
                                     f"{len(whole[u]) - len(p)}")
            if tap is None:
                served.append((p, whole[u][len(p):]))
            else:
                tapped.append((p, whole[u][len(p):], tap.logits[u],
                               tap.forced(u, len(whole[u])), np.asarray(
                                   engine.caches["ssm"][:, tap.slots[u]],
                                   np.float32)))
        engine.kv.check_consistency()
        if not engine.drained():
            raise AssertionError(
                f"{phase}: {engine.free_state_slots} of "
                f"{engine.total_state_slots} state slots and "
                f"{engine.free_blocks} of {engine.total_blocks} blocks free "
                f"after the drain")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"
             and "ssm_tokens" in s.attrs]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    started = sum(a["state_rows_started"] for a in steps)
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        rows_started=started, ssm_tokens=sum(a["ssm_tokens"] for a in steps),
        slots=engine.total_state_slots, kernel_events=len(events))
    for name in ("kernel/ssm_decode_update", "kernel/ssd_chunk_scan_tiles",
                 "kernel/grouped_mixed_gemm_tiles"):
        seen = {tuple(sorted(a.items())) for n, a in events if n == name}
        if not seen:
            raise AssertionError(f"{phase}: no {name} event")
        for attrs in sorted(seen):
            log(phase, event=name, **dict(attrs))
    check_prefill_tiles(phase, [
        a for name, a in events
        if name == "kernel/paged_attention_prefill_tiles"])
    check_step_copies(phase)
    fallen = [e for e in events if "fallback" in e[1]]
    if check_kernels and fallen:
        raise AssertionError(f"{phase}: kernels fallen back: {fallen}")
    if started != len(served) + len(tapped):
        raise AssertionError(f"{phase}: {started} rows started from zeros, "
                             f"{len(served) + len(tapped)} sequences were "
                             f"served")
    model = published_model(cfg)
    served_params = engine.params
    del engine
    gc.collect()
    check_state_updates(phase, cfg)
    errs, agree, state = row_errors(served_params, model, tapped, 256)
    median, worst_row = float(np.median(errs)), float(errs.max())
    low_bits = low_bits_share(np.stack([t[4] for t in tapped]))
    log(phase, tapped_rows=len(errs), median_row=round(median, 4),
        worst_row=round(worst_row, 4), allowed=sz.ssm_logit_tol,
        reference_router_agrees=[round(float(a), 3) for a in agree],
        slot_state_rel=float(f"{state.max():.3g}"),
        state_allowed=sz.ssm_state_tol, state_low_bits=low_bits)
    if not (state.max() <= sz.ssm_state_tol and low_bits > 0.5):
        raise AssertionError(
            f"{phase}: the tapped sequences' state slots lie "
            f"{state.max():.3g} of the largest element from the reference's "
            f"final states ({sz.ssm_state_tol} allowed); {low_bits} of their "
            f"elements hold what bfloat16 cannot")
    if not (median <= sz.ssm_logit_tol[0] and worst_row <= sz.ssm_logit_tol[1]):
        raise AssertionError(
            f"{phase}: the step programs' logits lie {median:.3f} (median "
            f"row) / {worst_row:.3f} (worst row) from the reference held to "
            f"their routing choices (a state read from the wrong slot or "
            f"left from the sequence before would read so)")
    within, worst, exact, checked = 0, 0.0, 0, 0
    for p, out in served:
        seq = np.zeros(-(-(len(p) + len(out)) // 256) * 256, np.int32)
        seq[:len(p) + len(out)] = p + out
        m, rank = reference.served_margins(served_params, model,
                                           jnp.asarray(seq), len(p))
        m, rank = np.asarray(m)[:len(out)], np.asarray(rank)[:len(out)]
        within += int((m <= sz.margin).sum())
        worst = max(worst, float(m.max()))
        exact += int((rank == 0).sum())
        checked += len(out)
    log(phase, served_tokens=checked, reference_argmax=exact,
        within_margin=within, margin=sz.margin, worst_margin=round(worst, 4),
        share_asked=sz.ssm_served_min)
    if not within >= sz.ssm_served_min * checked:
        raise AssertionError(
            f"{phase}: {within} of {checked} served tokens lie within "
            f"{sz.margin} of the free reference's maximum, "
            f"{sz.ssm_served_min:.0%} asked")
    memory_line(phase, jax.local_devices()[0])


def phase_latent_moe_server(sz: Sizes, seed: int, check_kernels: bool = True
                            ) -> None:
    """A model with latent attention, a learned indexer whose pick three
    layers share, a leading dense layer and a SHARE of its routed experts (a
    cut of GLM-5.2, int8 at group 128) through ``InferenceEngineV2``: a
    latent pool and an indexer-key pool behind one block table, a prompt past
    two ``index_topk`` chunked nine times with decode rows riding in its
    mixed steps, then a second batch through the same blocks, then the first
    batch once more with the logits, the picks and the expert choices of the
    engine's own step programs tapped; the grouped GEMM's tiles at 16 held
    experts printed, none fallen back; every block of both pools free after
    each drain.  Against the plain reference
    (``benchmark/reference/latent_sparse_moe_decoder.py``) over the same
    codes, as the serving cell compares: the tapped rows against the
    reference HELD TO THE PROGRAM'S PICKS AND EXPERTS, and the indexer and
    the router directly (``serve_latent_moe.check_logits`` /
    ``check_router``)."""
    from benchmark.drivers import serve_latent_moe as drv
    from benchmark.selection_tap import SelectionTap
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-latent-moe-int8"
    n = 1 + 4 * sz.latent_periods
    cfg = tfm.get_config(
        sz.latent_preset, num_layers=n, vocab_size=sz.latent_vocab,
        moe_experts_held=sz.latent_held,
        indexer_types=("full",) + ("shared", "shared", "shared",
                                   "full") * sz.latent_periods,
        mlp_layer_types=("dense",) + ("sparse",) * (n - 1),
        dtype="bfloat16", param_dtype="bfloat16")
    log(phase, preset=sz.latent_preset, layers=n, held=cfg.experts_held,
        experts=cfg.num_experts, top=cfg.moe_top_k,
        index_topk=cfg.index_topk, params_m=round(cfg.num_params() / 1e6, 1))
    params = drv.make_params(cfg, seed, 8, 128)
    tracer.clear()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.latent_blocks,
        max_blocks_per_seq=sz.latent_max_blocks_per_seq))
    rng = np.random.default_rng([seed, 41])
    tapped = []
    for batch, tap_it in ((sz.latent_requests, False),
                          (sz.latent_second, False),
                          (sz.latent_requests[:3], True)):
        prompts = [rng.integers(1, cfg.vocab_size, size=m).tolist()
                   for m, _ in batch]
        tap = SelectionTap(engine) if tap_it else None
        try:
            uids = [engine.put(p, max_new_tokens=m)
                    for p, (_, m) in zip(prompts, batch)]
            whole = engine.generate_all(burst=1)
        finally:
            if tap is not None:
                tap.remove()
        for p, u, (_, m) in zip(prompts, uids, batch):
            if len(whole[u]) - len(p) != m:
                raise AssertionError(f"{phase}: asked {m} tokens, got "
                                     f"{len(whole[u]) - len(p)}")
            if tap is not None:
                tapped.append((p, whole[u][len(p):], tap.logits[u],
                               tap.forced(u, len(whole[u])),
                               tap.picked(u, len(whole[u]))))
        engine.kv.check_consistency()
        if not engine.drained():
            raise AssertionError(
                f"{phase}: {engine.free_blocks} of {engine.total_blocks} "
                f"blocks free after the drain")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"
             and "dsa_keys_visible" in s.attrs]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    local = sum(a.get("moe_assignments_local", 0) for a in steps)
    made = sum(a["moe_assignments"] for a in steps)
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        keys_visible=sum(a["dsa_keys_visible"] for a in steps),
        keys_selected=sum(a["dsa_keys_selected"] for a in steps),
        local_share=round(local / max(made, 1), 4),
        pools={k: list(v.shape) for k, v in engine.caches.items()})
    for name in ("kernel/grouped_mixed_gemm_tiles",
                 "kernel/latent_attention_prefill_tiles",
                 "kernel/latent_attention_decode_tiles"):
        seen = {tuple(sorted(a.items())) for m, a in events if m == name}
        if not seen:
            raise AssertionError(f"{phase}: no {name} event")
        for attrs in sorted(seen):
            log(phase, event=name, **dict(attrs))
    fell = [e for e in events if e[1].get("fallback")]
    if fell and check_kernels:
        raise AssertionError(f"{phase}: a kernel fell back to XLA: {fell}")
    if check_kernels:
        require_kernel(phase, "decode_step", engine._decode_fwd.lower(
            *_decode_shapes(engine)).compile().as_text())
    del engine
    gc.collect()
    model = drv.published_model(cfg)
    drv._CHECK.update(cfg=cfg)
    drv._NOTES.update(ok=True, dtypes={}, shapes={})
    check = {"logit_pad": 256, "logit_tol_median": sz.latent_logit_tol[0],
             "logit_tol": sz.latent_logit_tol[1], "agree_min": 0.7,
             "index_tol": 1e-4, "select_band": 0.05,
             "select_agree_min": 0.9, "router_tol": 1e-4}
    got = drv.check_logits(params, model, tapped, check,
                           lambda m: log(phase, check=m))
    routed = drv.check_router(params, model, cfg, tapped, check,
                              lambda m: log(phase, check=m))
    if not (got["ok"] and routed["ok"]):
        raise AssertionError(f"{phase}: the step programs' logits, picks or "
                             f"router differ from the reference: {got} "
                             f"{routed}")


def phase_eva_server(sz: Sizes, seed: int, check_kernels: bool = True
                     ) -> None:
    """A model with EVA attention (``eva_layers`` layers of EvaByte-6.5B, W8A16
    at group 256, eight output heads) through ``InferenceEngineV2``: a
    tumbling window pool and a summary pool in every layer, prompts that
    close windows in prefill and in decode.  The logits of all eight heads
    that its own step programs give, tapped, lie within ``eva_logit_tol`` of
    the plain reference (``benchmark/reference/eva_byte_decoder.py``) over
    the same codes; attention and the summariser ran their kernels and every
    GEMM its own (the ring's ``kernel/*_tiles`` events, none fallen back; a
    ``tpu_custom_call`` in both compiled step programs); the window's blocks
    went back whole; both pools whole after the drain."""
    from benchmark.drivers import serve_eva
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-eva-int8"
    cfg = tfm.get_config(sz.eva_preset, num_layers=sz.eva_layers,
                         dtype="bfloat16", param_dtype="bfloat16")
    log(phase, preset=sz.eva_preset, layers=cfg.num_layers,
        window=cfg.eva_window, chunk=cfg.eva_chunk,
        heads_out=cfg.num_pred_heads,
        params_m=round(cfg.num_params() / 1e6, 1))
    params = serve_eva.draw_norm_offsets(jax.jit(
        lambda k: tfm.init_params(k, cfg))(jax.random.PRNGKey(seed)), seed)
    tracer.clear()
    rows = min(sz.max_seqs, len(sz.eva_requests))
    per_row = cfg.eva_window // sz.block_size
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=rows,
        block_size=sz.block_size,
        num_blocks=1 + rows * -(-sz.eva_max_blocks_per_seq // cfg.eva_chunk),
        num_window_blocks=1 + rows * per_row,
        max_blocks_per_seq=sz.eva_max_blocks_per_seq, quantize_bits=8,
        quantize_group=256))
    del params
    check = {"logit_prompts": [n for n, _ in sz.eva_requests],
             "logit_tokens": max(n for _, n in sz.eva_requests)}
    tapped = serve_eva.tap_logits(engine, cfg, seed, check)
    for m in engine._managers:
        m.check_consistency()
    if not engine.drained() or engine.kv.reserved or engine.kv_win.reserved:
        raise AssertionError(f"{phase}: a pool leaked blocks")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    kinds = sorted({a["kind"] for name, a in events
                    if name == "kernel/eva_attention_tiles"})
    read, full = (sum(a.get(k, 0) for a in steps) for k in (
        "eva_window_keys", "eva_keys_full"))
    read += sum(a.get("eva_summary_keys", 0) for a in steps)
    closed = sum(a.get("eva_windows_closed", 0) for a in steps)
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        pool_blocks={"summary": engine.kv.allocator.num_blocks,
                     "window": engine.kv_win.allocator.num_blocks},
        windows_closed=closed, window_blocks_freed=engine.kv_win.trimmed,
        eva_keys_read_vs_full_pct=round(100 * read / full, 1),
        kernel_events=len(events), attention_kernels=kinds)
    check_step_copies(phase)
    fallen = [e for e in events if "fallback" in e[1]]
    if check_kernels and (fallen or kinds != ["decode", "prefill"]):
        raise AssertionError(f"{phase}: kernels fallen back: {fallen}; "
                             f"EVA attention kernels traced: {kinds}")
    want = sum((n + check["logit_tokens"]) // cfg.eva_window  # every row
               for n, _ in sz.eva_requests)  # decodes the longest budget
    if closed != want or engine.kv_win.trimmed != want * per_row:
        raise AssertionError(
            f"{phase}: {closed} windows closed and {engine.kv_win.trimmed} "
            f"window blocks freed, the requests pass {want} edges")
    if check_kernels:
        require_kernel(phase, "decode_step", engine._decode_fwd.lower(
            *_decode_shapes(engine)).compile().as_text())
    model = serve_eva.published_model(cfg)
    served_params = engine.params
    del engine
    gc.collect()
    errs = np.asarray([e for seq in serve_eva.row_errors(
        served_params, model, tapped) for e in seq])
    median, worst = float(np.median(errs)), float(errs.max())
    log(phase, tapped_rows=len(errs), heads=cfg.num_pred_heads,
        logit_err_median=round(median, 4), logit_err_worst=round(worst, 4),
        allowed=sz.eva_logit_tol)
    if not (median <= sz.eva_logit_tol[0] and worst <= sz.eva_logit_tol[1]):
        raise AssertionError(
            f"{phase}: the step programs' logits lie {median:.3f} (median) "
            f"and {worst:.3f} (worst) from the reference's")
    memory_line(phase, jax.local_devices()[0])


def _decode_shapes(engine):
    """The decode step's arguments as shapes, for a compile from the cache."""
    v2 = engine.cfg

    def rows(dtype):
        return jax.ShapeDtypeStruct((v2.max_seqs,), dtype)

    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          (engine.params, engine.caches))
    table = jax.ShapeDtypeStruct((v2.max_seqs, v2.max_blocks_per_seq),
                                 jnp.int32)
    return (*shapes, rows(jnp.int32), rows(jnp.int32),
            (table, table) if engine.kv_win is not None else table,
            rows(jnp.int32), rows(jnp.float32),
            jax.ShapeDtypeStruct((2,), jnp.uint32), rows(jnp.int32))


def phase_zero3(sz: Sizes, seed: int) -> None:
    phase = "zero3x4"
    n = len(jax.devices())
    zero3 = {"zero_optimization": {"stage": 3}, "mesh": {"fsdp_size": n},
             "train_micro_batch_size_per_gpu": 1}

    # (a) the comparison, at a depth one chip holds: the sharded first-step
    # loss against the one-device dense loss, same parameters and batch
    log(phase, part="compare", layers=sz.compare_layers,
        why="a depth one chip holds beside the sharded engine")
    cfg, params, engine, batch = build_trainer(sz, seed, sz.compare_layers,
                                               zero3)
    loss = float(engine.train_batch(batch)["loss"])
    # XLA attention is plain causal: the window (4096) is wider than seq
    ref = float(jax.jit(lambda p, b: tfm.loss_fn(
        p, b, cfg, attn_fn=tfm.xla_attention)[0])(params, batch))
    rel = abs(loss - ref) / abs(ref)
    log(phase, part="compare", sharded_first_step_loss=round(loss, 5),
        one_device_loss=round(ref, 5), rel_diff=f"{rel:.2e}",
        allowed=sz.loss_rel_tol)
    if not (np.isfinite(loss) and rel < sz.loss_rel_tol):
        raise AssertionError(
            f"{phase}: sharded loss {loss} != one-device loss {ref}")
    del params, engine
    gc.collect()

    # (b) the sharded run, at a depth fitted to the four chips
    log(phase, part="train", layers=sz.zero3_layers,
        why="ZeRO-3 shards 14 B a parameter (bf16 params, f32 moments and "
            "grads) over 4 chips: 8 layers are 2.0 B parameters = 7 GB a "
            "chip, beside the caller's 4 GB unsharded copy on chip 0")
    cfg, params, engine, batch = build_trainer(sz, seed, sz.zero3_layers,
                                               zero3)
    log(phase, params_m=round(cfg.num_params() / 1e6, 1), topo=engine.topo,
        train_batch_size=engine.train_batch_size, seq=sz.train_seq)
    leaf = engine.state.params["layers"]["mlp"]["w_in"]
    shard = leaf.addressable_shards[0].data
    devices = {s.device for s in leaf.addressable_shards}
    log(phase, leaf="layers/mlp/w_in", leaf_shape=leaf.shape,
        shard_shape=shard.shape, shard_devices=len(devices))
    if shard.size * n != leaf.size or len(devices) != n:
        raise AssertionError(
            f"{phase}: parameters are not sharded {n} ways: leaf "
            f"{leaf.shape}, shard {shard.shape} on {len(devices)} devices")
    placed = engine.place_batch(batch)
    _, step_s = timed_steps(phase, engine, placed, sz.train_warmup,
                            sz.zero3_steps)
    log(phase, tokens_per_second=round(
        engine.train_batch_size * sz.train_seq / step_s, 1))
    for d in jax.devices():
        memory_line(f"{phase}/chip{d.id}", d)
    text = engine._train_step.lower(engine.state,
                                    placed.placed).compile().as_text()
    # the TPU compiler writes a reduce-scatter as a fusion named
    # ``all-reduce-scatter``, so that one is counted by name
    counts = {op: text.count(f" {op}(") + text.count(f" {op}-start(")
              for op in ("all-gather", "all-reduce", "all-to-all")}
    counts["reduce-scatter"] = text.count("reduce-scatter")
    log(phase, compiled_collectives=counts)
    if not (counts["all-gather"] and counts["reduce-scatter"]):
        raise AssertionError(
            f"{phase}: the compiled step lacks all-gather or "
            f"reduce-scatter: {counts}")
    require_kernel(phase, "train_step@zero3", text)


# ---------------------------------------------------------------------------


def phase_linear_latent_moe_server(sz: Sizes, seed: int,
                                   check_kernels: bool = True) -> None:
    """A model of KDA layers beside latent attention without an indexer, a
    leading dense layer and a SHARE of its routed experts (a cut of
    Kimi-Linear-48B-A3B at published widths, int8 at group 128) through
    ``InferenceEngineV2``: state slots of a delta-rule matrix state beside a
    latent pool read whole; a prompt chunked three times with decode rows
    riding in its mixed steps, then a second batch through the same slots and
    blocks, then the first batch once more with the logits and the expert
    choices of the engine's own step programs tapped; the KDA decode update
    and both latent paths ran their kernels, none fallen back; every block
    and slot free after each drain.  Against the plain reference
    (``benchmark/reference/linear_latent_moe_decoder.py``) over the same
    codes, as the serving cell compares: the tapped rows against the
    reference HELD TO THE PROGRAM'S EXPERTS, every KDA layer's state of the
    tapped sequences' slots against the reference's, and the router directly
    (``serve_linear_latent_moe.check_logits``, ``check_router``)."""
    from unittest import mock

    from benchmark.drivers import serve_latent_moe
    from benchmark.drivers import serve_linear_latent_moe as drv
    from benchmark.reference import linear_latent_moe_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-linear-latent-moe-int8"
    n = len(sz.linear_pattern)
    cfg = tfm.get_config(
        sz.linear_preset, num_layers=n, vocab_size=sz.linear_vocab,
        moe_experts_held=sz.linear_held, kda_pattern=tuple(sz.linear_pattern),
        mlp_layer_types=("dense",) + ("sparse",) * (n - 1),
        dtype="bfloat16", param_dtype="bfloat16")
    log(phase, preset=sz.linear_preset, pattern=sz.linear_pattern,
        held=cfg.experts_held, experts=cfg.num_experts, top=cfg.moe_top_k,
        params_m=round(cfg.num_params() / 1e6, 1))
    params = drv.make_params(cfg, seed, 8, 128)
    tracer.clear()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.linear_blocks,
        max_blocks_per_seq=sz.linear_max_blocks_per_seq))
    rng = np.random.default_rng([seed, 51])
    for batch in (sz.linear_requests, sz.linear_second):
        prompts = [rng.integers(1, cfg.vocab_size, size=m).tolist()
                   for m, _ in batch]
        uids = [engine.put(p, max_new_tokens=m)
                for p, (_, m) in zip(prompts, batch)]
        whole = engine.generate_all(burst=1)
        for p, u, (_, m) in zip(prompts, uids, batch):
            if len(whole[u]) - len(p) != m:
                raise AssertionError(f"{phase}: asked {m} tokens, got "
                                     f"{len(whole[u]) - len(p)}")
        engine.kv.check_consistency()
        if not engine.drained():
            raise AssertionError(
                f"{phase}: {engine.free_blocks} of {engine.total_blocks} "
                f"blocks and {engine.free_state_slots} of "
                f"{engine.total_state_slots} slots free after the drain")
    check = {"logit_prompts": [m for m, _ in sz.linear_requests[:3]],
             "logit_tokens": 12, "logit_pad": 256,
             "logit_tol_median": sz.linear_logit_tol[0],
             "logit_tol": sz.linear_logit_tol[1],
             "state_tol": sz.linear_state_tol[0],
             "state_tol_deep": sz.linear_state_tol[1],
             "state_low_bits_min": 0.5, "kda_tol": 1e-3, "agree_min": 0.5,
             "router_tol": 1e-4}
    tapped = drv.tap_logits(engine, cfg, seed, check)
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"
             and "kda_tokens" in s.attrs]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    local = sum(a.get("moe_assignments_local") or 0 for a in steps)
    made = sum(a["moe_assignments"] for a in steps)
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        kda_tokens=sum(a["kda_tokens"] for a in steps),
        latent_keys_read=sum(a["latent_keys_read"] for a in steps),
        local_share=round(local / max(made, 1), 4),
        arrays={k: list(v.shape) for k, v in engine.caches.items()})
    for name in ("kernel/grouped_mixed_gemm_tiles", "kernel/kda_decode_update",
                 "kernel/kda_chunk_scan_tiles",
                 "kernel/latent_attention_decode_full_tiles",
                 "kernel/latent_attention_prefill_tiles"):
        seen = {tuple(sorted(a.items())) for m, a in events if m == name}
        if not seen:
            raise AssertionError(f"{phase}: no {name} event")
        for attrs in sorted(seen):
            log(phase, event=name, **dict(attrs))
    fell = [e for e in events if e[1].get("fallback")]
    if fell and check_kernels:
        raise AssertionError(f"{phase}: a kernel fell back to XLA: {fell}")
    if check_kernels:
        require_kernel(phase, "decode_step", engine._decode_fwd.lower(
            *_decode_shapes(engine)).compile().as_text())
    memory_line(phase, jax.local_devices()[0])
    del engine
    gc.collect()
    model = drv.published_model(cfg)
    got = drv.check_logits(params, model, tapped, check,
                           lambda m: log(phase, check=m))
    with mock.patch.object(serve_latent_moe, "reference", reference):
        routed = serve_latent_moe.check_router(
            params, model, cfg, tapped, check, lambda m: log(phase, check=m))
    if not (got["ok"] and routed["ok"]):
        raise AssertionError(f"{phase}: the step programs' logits, state or "
                             f"router differ from the reference: {got} "
                             f"{routed}")


def check_selective_updates(phase: str, cfg) -> None:
    """The two Mamba-1 state updates alone, on the device at the model's
    widths (``selective_scan`` on rows of 1, 127, 128, 129 and 300 tokens in
    one call, each from the state of its slot, then
    ``selective_decode_update``), against the recurrence one token at a time:
    states and ``y`` within 1e-4 of their largest element (float32 on both
    sides: what differs is the order of a sum of 16)."""
    from deepspeed_tpu.ops.pallas import selective_scan as ss

    di, N = cfg.mamba_d_inner, cfg.mamba_state_size
    lens = np.array([1, 127, 128, 129, 300, 0], np.int32)
    T, S1 = int(lens.sum()) + 11, 7
    k = jax.random.split(jax.random.PRNGKey(11), 8)
    x = jax.random.normal(k[0], (T, di)).astype(jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(k[1], (T, di)) - 4.0)
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, di))
    B, C = (jax.random.normal(k[i], (T, N)) for i in (2, 3))
    D = jax.random.uniform(k[4], (di,), jnp.float32, 0.5, 1.5)
    ssm0 = jax.random.normal(k[5], (2, S1, N, di))
    slots = jnp.asarray([4, 0, 5, 2, 1, 6], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False, False])
    starts = jnp.asarray(np.cumsum(lens) - lens, jnp.int32)
    y, new = jax.jit(ss.selective_scan)(
        ssm0, jnp.int32(1), x, delta, A, B, C, D, starts, jnp.asarray(lens),
        slots, fresh, jnp.asarray(lens >= 2))
    walk = jax.jit(ss.selective_recurrence)
    worst = 0.0
    for r in range(1, 5):
        a, n, slot = int(starts[r]), int(lens[r]), int(slots[r])
        first = jnp.zeros((N, di)) if bool(fresh[r]) else ssm0[1, slot]
        want, state = walk(x[a:a + n], delta[a:a + n], A, B[a:a + n],
                           C[a:a + n], D, first)
        worst = max(worst,
                    float(jnp.abs(y[a:a + n] - want).max()
                          / jnp.abs(want).max()),
                    float(jnp.abs(new[1, slot] - state).max()
                          / jnp.abs(state).max()))
    kept = bool((new[0] == ssm0[0]).all()
                and (new[1, [3, 4, 6]] == ssm0[1, [3, 4, 6]]).all())
    active = jnp.ones((S1,), bool).at[3].set(False)
    y1, dec = jax.jit(ss.selective_decode_update)(
        ssm0, jnp.int32(0), x[:S1], delta[:S1], A, B[:S1], C[:S1], D, active,
        jnp.zeros((S1,), bool).at[2].set(True))
    for r in range(S1):
        first = jnp.zeros((N, di)) if r == 2 else ssm0[0, r]
        want, state = walk(x[r:r + 1], delta[r:r + 1], A, B[r:r + 1],
                           C[r:r + 1], D, first)
        if r == 3:
            kept &= bool((dec[0, r] == ssm0[0, r]).all())
            continue
        worst = max(worst,
                    float(jnp.abs(y1[r] - want[0]).max()
                          / jnp.abs(want).max()),
                    float(jnp.abs(dec[0, r] - state).max()
                          / jnp.abs(state).max()))
    log(phase, state_updates_rel=float(f"{worst:.3g}"), untouched_kept=kept,
        d_inner=di, n=N)
    if not (worst <= 1e-4 and kept):
        raise AssertionError(f"{phase}: the state updates differ from the "
                             f"recurrence by {worst:.3g} (kept {kept})")


def phase_selective_server(sz: Sizes, seed: int, check_kernels: bool = True
                           ) -> None:
    """A model whose layers are a Mamba-1 mixer (or attention without
    positions) AND a dense FFN (a cut of AI21-Jamba2-3B's pattern at its
    published widths, bfloat16 weights, the tied head) through
    ``InferenceEngineV2``: per-sequence state in slots beside the paged K/V
    of ONE K/V head, a prompt chunked three times with decode rows riding in
    its mixed steps, then a second batch through the SAME slots, then the
    first batch once more with the logits of the engine's own step programs
    tapped; both state updates and both paged attention kernels leave their
    events without ``fallback``; every block and every slot free after each
    drain.  Against ``benchmark/reference/selective_ssm_decoder.py``: the
    tapped rows (median, worst) and the slots' states, as the serving cell
    compares them; the two state updates alone against the recurrence."""
    from benchmark.drivers.serve_selective import (draw_small_tensors,
                                                   published_model,
                                                   sequence_errors)
    from benchmark.drivers.serve_ssm_moe import low_bits_share
    from benchmark.logit_tap_donated import DonatedLogitTap
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer

    phase = "server-selective-bf16"
    cfg = tfm.get_config(
        sz.selective_preset, num_layers=len(sz.selective_pattern),
        mixer_pattern=sz.selective_pattern, dtype="bfloat16",
        param_dtype="bfloat16")
    log(phase, preset=sz.selective_preset, pattern=sz.selective_pattern,
        d_inner=cfg.mamba_d_inner, heads=cfg.num_heads, kv_heads=cfg.kv_heads,
        params_m=round(cfg.num_params() / 1e6, 1))
    params = jax.jit(lambda k: draw_small_tensors(
        tfm.init_params(k, cfg), k))(jax.random.PRNGKey(seed))
    tracer.clear()
    engine = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=sz.max_tokens_per_step, max_seqs=sz.max_seqs,
        block_size=sz.block_size, num_blocks=sz.num_blocks,
        max_blocks_per_seq=sz.selective_max_blocks_per_seq))
    rng = np.random.default_rng([seed, 58])
    served, tapped = 0, []
    for batch, tap_it in ((sz.selective_requests, False),
                          (sz.selective_second, False),
                          (sz.selective_requests, True)):
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n, _ in batch]
        tap = DonatedLogitTap(engine) if tap_it else None
        slot_of, take = {}, engine.kv.slots.take
        engine.kv.slots.take = lambda uid: slot_of.setdefault(uid, take(uid))
        try:
            uids = [engine.put(p, max_new_tokens=n)
                    for p, (_, n) in zip(prompts, batch)]
            whole = engine.generate_all(burst=1) if tap_it \
                else engine.generate_all()
        finally:
            engine.kv.slots.take = take
            if tap is not None:
                tap.remove()
        for p, u, (_, n) in zip(prompts, uids, batch):
            if len(whole[u]) - len(p) != n:
                raise AssertionError(f"{phase}: asked {n} tokens, got "
                                     f"{len(whole[u]) - len(p)}")
            served += 1
            if tap is not None:
                tapped.append({
                    "prompt": len(p), "tokens": whole[u][:-1],
                    "rows": tap.logits[u], "long": False,
                    "state": np.asarray(
                        engine.caches["ssm"][:, slot_of[u]], np.float32)})
        engine.kv.check_consistency()
        if not engine.drained():
            raise AssertionError(
                f"{phase}: {engine.free_state_slots} of "
                f"{engine.total_state_slots} state slots and "
                f"{engine.free_blocks} of {engine.total_blocks} blocks free "
                f"after the drain")
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"
             and "ssm_tokens" in s.attrs]
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    started = sum(a["state_rows_started"] for a in steps)
    log(phase, steps=len(steps), kinds=sorted({a["kind"] for a in steps}),
        rows_started=started, ssm_tokens=sum(a["ssm_tokens"] for a in steps),
        slots=engine.total_state_slots, kernel_events=len(events))
    for name in ("kernel/selective_decode_update", "kernel/selective_scan",
                 "kernel/paged_attention_decode_tiles"):
        seen = {tuple(sorted(a.items())) for n, a in events if n == name}
        if not seen:
            raise AssertionError(f"{phase}: no {name} event")
        for attrs in sorted(seen):
            log(phase, event=name, **dict(attrs))
    check_prefill_tiles(phase, [
        a for name, a in events
        if name == "kernel/paged_attention_prefill_tiles"])
    check_step_copies(phase)
    fallen = [e for e in events if "fallback" in e[1]]
    if check_kernels and fallen:
        raise AssertionError(f"{phase}: kernels fallen back: {fallen}")
    if started != served:
        raise AssertionError(f"{phase}: {started} rows started from zeros, "
                             f"{served} sequences were served")
    model = published_model(cfg)
    memory_line(phase, jax.local_devices()[0])
    del engine
    gc.collect()
    if check_kernels:
        check_selective_updates(phase, cfg)
    rows, states = [], []
    for t in tapped:
        errs, state = sequence_errors(params, model, t, pad=256)
        rows.append(errs)
        states.append(state)
    rows, states = np.concatenate(rows), np.concatenate(states)
    low_bits = low_bits_share(np.stack([t["state"] for t in tapped]))
    median, worst = float(np.median(rows)), float(rows.max())
    log(phase, tapped_rows=len(rows), median_row=round(median, 4),
        worst_row=round(worst, 4), allowed=sz.selective_logit_tol,
        slot_state_rel=float(f"{states.max():.3g}"),
        state_allowed=sz.selective_state_tol, state_low_bits=low_bits)
    if not (median <= sz.selective_logit_tol[0]
            and worst <= sz.selective_logit_tol[1]
            and states.max() <= sz.selective_state_tol and low_bits > 0.5):
        raise AssertionError(
            f"{phase}: the step programs' logits or the slots' states "
            f"differ from the reference: median row {median:.4f}, worst "
            f"{worst:.4f} (allowed {sz.selective_logit_tol}), state "
            f"{states.max():.4f} (allowed {sz.selective_state_tol}), low "
            f"bits {low_bits}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", default="",
                    help="run one phase only (its function's name less "
                         "'phase_'), e.g. latent_moe_trainer")
    args = ap.parse_args()

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    devices = jax.devices()  # raises when the backend cannot start
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r} "
              f"({dev.device_kind}); there is no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    log("setup", device_kind=dev.device_kind, devices=len(devices),
        jax=jax.__version__, compile_cache=cache, seed=args.seed)
    t0 = time.perf_counter()
    sz = Sizes()
    if args.phase:
        globals()[f"phase_{args.phase}"](sz, args.seed)
    elif args.chips == 4:
        phase_zero3(sz, args.seed)
    else:
        phase_trainer(sz, args.seed)
        gc.collect()
        phase_latent_moe_trainer(sz, args.seed)
        gc.collect()
        phase_swa_moe_trainer(sz, args.seed)
        gc.collect()
        phase_server(sz, args.seed, quantize_bits=0)
        gc.collect()
        phase_server(sz, args.seed, quantize_bits=8)
        gc.collect()
        phase_moe_server(sz, args.seed)
        gc.collect()
        phase_swa_moe_server(sz, args.seed)
        gc.collect()
        phase_ssm_moe_server(sz, args.seed)
        gc.collect()
        phase_latent_moe_server(sz, args.seed)
        gc.collect()
        phase_eva_server(sz, args.seed)
        gc.collect()
        phase_linear_latent_moe_server(sz, args.seed)
        gc.collect()
        phase_selective_server(sz, args.seed)
    log("done", total_seconds=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
