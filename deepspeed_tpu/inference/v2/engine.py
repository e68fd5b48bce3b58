"""Continuous-batching inference engine (v2).

Capability analogue of the reference's FastGen / inference-v2 engine
(``inference/v2/engine_v2.py:30 InferenceEngineV2.put``, Dynamic SplitFuse
scheduling ``scheduling_utils.py``, ragged forward over
``model_implementations/``): many requests share one forward pass; decode
tokens are batched with *chunks* of prefill so every step runs near the
compute-optimal token budget.

TPU-native: the ragged batch is padded to a static token budget (XLA static
shapes); KV lives in a paged (num_blocks, block_size, kv_heads, head_dim)
pool per layer, indexed through block tables; attention uses the paged
Pallas kernel for pure-decode steps and a gather-based XLA path for mixed
prefill steps.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models import transformer as tfm
from ...observability.recorder import recorder
from ...observability.trace import tracer
from ...ops.pallas.latent_attention import TILE_Q
from ...ops.pallas.selective_scan import scan_pieces
# ``_decode_body`` and ``_memo`` are not used here: ``benchmark/logit_tap.py``
# imports them from this module
from .programs import (REFUSED, _decode_body, _memo,  # noqa: F401
                       _with_stats, build_cow_copy, build_decode_forward,
                       build_multi_decode_forward, build_ragged_forward,
                       build_unpack, kind_of,
                       mixed_step_attn_tiles, sample_rows)
from .ragged import (DecodeStateTable, KVCacheManager, RaggedBatch,
                     RaggedBatchBuilder, SequenceDescriptor, StepLayout,
                     decode_layout)
from .spec import build_draft_spec_step, build_self_draft_step


# What a step's body hands back to ``step``: the tokens by uid, the tokens in
# its batch, and where its split is made (None where it dispatched nothing):
# its ``engine/dispatch`` span with the thread's CPU clock where that opened
# (two of None where the call found its program under way: its split opens at
# its entry), its ``engine/wait`` span with the clock where that closed
# (``_thread_cpu``; four of None with tracing off).
_StepResult = Tuple[Dict[int, List[int]], int,
                    Optional[Tuple[Any, Optional[float], Any, Optional[float]]]]


@dataclasses.dataclass
class _Underway:
    """A step program that has been called and whose tokens no call has
    fetched yet, of either kind.  ``out``: what it returned, the tokens on the
    device with an MoE model's stats behind them (a mixed program's: what its
    sampler returned, None until that is enqueued, ``InferenceEngineV2.
    _sample``); ``counts`` / ``h2d`` / ``tokens``: what its ``engine/step``
    span will say of it (the kind's counters, its copies and bytes, the tokens
    in its batch).  A decode program ran ``rows`` of the table, a mixed one
    its ``picks`` (``sampler``: what its sampler is still to be called with,
    the logits, the temperatures and seeds by pick, an MoE model's stats;
    ``hidden``: what the self-draft heads propose from, fetched with the
    tokens).

    ``_advance`` then says who gets a token from it, an entry a sequence:
    ``rows`` / ``uids`` (its row of the table, and whose that was then: a row
    retired since has lost its own), ``src`` (where in ``out`` its token lies:
    the row itself, or its place among the picks), ``pos`` (the token's
    position in the sequence), ``at`` (where the table's history keeps a
    place for it; past its end: the descriptor does) and ``ends`` (the
    sequence is over with it); and ``dropped``: how many were retired
    between its call and now, whose token nobody gets."""

    kind: str
    out: Optional[jax.Array]
    counts: Dict[str, Any]
    h2d: Tuple[int, int]
    tokens: int = 0
    rows: Any = None
    uids: Any = None
    picks: Any = ()
    sampler: Any = None
    hidden: Any = None
    src: Any = None
    pos: Any = None
    at: Any = None
    ends: Any = None
    dropped: int = 0


def _thread_cpu() -> Optional[float]:
    """The calling thread's CPU clock, for the step's split; None with
    tracing off, which reads no clock.  A system call (trace.py)."""
    return time.thread_time() if tracer.enabled else None


# The ``thread`` an ``engine/program`` span is recorded under, behind the
# name of the thread that steps the engine (two replicas in one process each
# have their own): a track of the chrome export beside that thread's.  Two,
# by the step's parity: a program overlaps its predecessor and its successor
# and no other.
_QUEUE_TRACKS = ("/device-queue/0", "/device-queue/1")
# ``unqueued_ms`` and its parts, in the order the engine thread passes their
# ends; and what a program called behind another says of them
_UNQUEUED = ("unqueued_ms", "unqueued_post_ms", "unqueued_turn_ms",
             "unqueued_pre_ms")
_NOTHING_UNQUEUED = dict.fromkeys(_UNQUEUED, 0.0)


def _host_split(sp, cpu_entry, sp_dispatch, cpu_called, sp_wait, cpu_fetched
                ) -> Dict[str, float]:
    """``engine/step`` ``sp``, about to close, split where the device's work
    begins and ends, in milliseconds.  ``device_ms``: from the start of
    ``engine/dispatch`` to the end of ``engine/wait``, host clocks round the
    first enqueue and the token fetch, between which the device is presumed
    busy.  ``pre_ms``: from the step's entry to there; ``post_ms``: from the
    fetch's return to now; ``pre_cpu_ms`` / ``post_cpu_ms``: the engine
    thread's CPU time over the same two intervals (its clock read at their
    four ends: ``cpu_entry``, ``cpu_called``, ``cpu_fetched``, now), so wall
    less CPU is what the thread waited for inside its own step."""
    return {
        "device_ms": (sp_wait.t_end - sp_dispatch.t_start) * 1e3,
        "pre_ms": (sp_dispatch.t_start - sp.t_start) * 1e3,
        "pre_cpu_ms": (cpu_called - cpu_entry) * 1e3,
        "post_ms": (time.monotonic() - sp_wait.t_end) * 1e3,
        "post_cpu_ms": (time.thread_time() - cpu_fetched) * 1e3}


def _live_rows(start: "np.ndarray", n: "np.ndarray"):
    """Of all the rows the host holds (``n``: a row's tokens this step, or
    whether it takes its one), those in the step: first position, tokens."""
    live = n > 0
    return start[live].astype(np.int64), n[live].astype(np.int64)


class AdmissionError(ValueError):
    """A request cannot be admitted: the prompt+budget exceeds the maximum
    context, or (``put(strict=True)``) no sequence slot / KV block budget is
    currently available.  Typed so callers (the serving broker) can convert
    transient exhaustion into deferral instead of a user-facing failure,
    and so capacity problems never surface as internal allocator
    ``MemoryError`` asserts mid-schedule."""


@dataclasses.dataclass
class V2Config:
    max_tokens_per_step: int = 256  # ragged token budget (SplitFuse chunk)
    max_seqs: int = 16
    block_size: int = 64
    num_blocks: int = 512
    max_blocks_per_seq: int = 32
    # a model with window AND global attention layers keeps a pool for each
    # kind: ``num_blocks`` is the global layers', this the window layers'
    # (0: what ``max_seqs`` sequences hold at most, see ragged.window_bound).
    # A model with one kind of layer has the one pool of ``num_blocks``
    num_window_blocks: int = 0
    dtype: str = "bfloat16"
    # cross-request KV prefix cache (inference/v2/prefix_cache.py): finished
    # sequences donate full prefix blocks into a radix tree; new requests
    # skip prefill for the longest cached prefix via block-table sharing
    enable_prefix_cache: bool = False
    prefix_cache_min_tokens: int = 0  # min shareable prefix to take a hit
    prefix_eviction: str = "lru"  # "lru" | "none"
    # serving memory hierarchy (inference/v2/paging.py): demote cold prefix
    # blocks to a host-DRAM pool (and optionally disk) instead of evicting,
    # so a returning session promotes instead of recomputing.  All paging
    # is host-side: the compiled prefill/decode HLO is identical on/off.
    kv_host_pool_mb: int = 0  # 0 disables the paging tier entirely
    # exact-bytes override of kv_host_pool_mb (tests/benches sizing the
    # host pool below one MiB to force bottom-tier overflow; 0 = use mb)
    kv_host_pool_bytes: int = 0
    kv_spill_dir: str = ""  # third tier: safetensors spill files (optional)
    kv_promote_ahead: bool = False  # background disk→host prefetch thread
    # crash-durable cold tier (inference/v2/coldstore.py): host-pool
    # overflow lands as manifest-verified committed entries keyed by chain
    # digest instead of bare spill files, and ``rehydrate_coldstore()``
    # re-adopts surviving entries into the radix tree after a restart
    kv_coldstore_dir: str = ""  # replaces kv_spill_dir's bottom tier
    # speculative decoding (inference/v2/spec.py): "draft" proposes with a
    # small second model, "self_draft" with Medusa-style bolt-on heads
    # (linear/spec_heads.py); spec_k tokens proposed per step, verified in
    # one multi-position forward with in-graph accept/reject
    spec_mode: str = "off"  # "off" | "draft" | "self_draft"
    spec_k: int = 4
    # weight-only quantization of the served base (inference/quantization.py):
    # attention/MLP projections become ``QuantizedWeight`` nodes that the
    # Pallas mixed GEMM dequantizes in-kernel, so decode reads weights at the
    # quantized width (int8: K·N bytes, int4: K·N/2) instead of 2·K·N bf16
    quantize_bits: int = 0  # 0 = off; 4 / 6 / 8 = W4A16 / W6A16 / W8A16
    quantize_group: int = 256  # per-group scale granularity along K
    # multi-tenant LoRA serving (serving/adapters.py): a device-resident
    # stack of per-slot adapter factors rides every forward as an extra
    # read-only argument; each row gathers ITS slot's A/B and adds the
    # low-rank delta on top of the unchanged (quantized) base projections.
    # Slot 0 is reserved as the all-zero null adapter, so base-only rows
    # stay bit-identical to an adapterless engine.  0 disables entirely —
    # every compiled program is then byte-identical to pre-adapter builds.
    adapter_slots: int = 0  # total device slots INCLUDING the null slot 0
    adapter_rank: int = 0  # stack rank r (shorter adapters are zero-padded)


# ---------------------------------------------------------------------------
# batched heterogeneous-adapter LoRA (S-LoRA / Punica shape)
# ---------------------------------------------------------------------------

#: projections the device adapter stack can carry deltas for — the
#: attention projections of ``models/transformer.py`` (classic LoRA
#: targets).  MLP-targeted adapters are rejected at registry load; the
#: serving path never silently drops part of an adapter.
ADAPTER_TARGETS = ("wq", "wk", "wv", "wo")


def adapter_target_shapes(model_cfg: tfm.TransformerConfig
                          ) -> Dict[str, Tuple[int, int]]:
    """(K, N) of each stackable projection — what a loaded adapter's
    ``lora_a (L, K, r)`` / ``lora_b (L, r, N)`` must match."""
    H = model_cfg.hidden_size
    qd = model_cfg.num_heads * model_cfg.head_dim
    kvd = model_cfg.kv_heads * model_cfg.head_dim
    return {"wq": (H, qd), "wk": (H, kvd), "wv": (H, kvd), "wo": (qd, H)}


def init_adapter_stack(model_cfg: tfm.TransformerConfig, v2: V2Config):
    """All-zero device adapter stack: per target, ``a (L, slots, K, r)`` +
    ``b (L, slots, r, N)`` in the compute dtype.  Slot 0 stays zero forever
    (the null adapter); ``serving/adapters.py`` pages real adapters in and
    out of slots ``1..slots-1`` with ``set_adapter_slot``."""
    dt = jnp.dtype(v2.dtype)
    L, S, r = model_cfg.num_layers, v2.adapter_slots, v2.adapter_rank
    return {name: {"a": jnp.zeros((L, S, K, r), dt),
                   "b": jnp.zeros((L, S, r, N), dt)}
            for name, (K, N) in adapter_target_shapes(model_cfg).items()}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class InferenceEngineV2:
    """Reference surface: ``put(uids, tokens) → logits/tokens``, plus a
    convenience ``generate_all`` driving requests to completion."""

    def __init__(self, model_config: tfm.TransformerConfig, params: Any,
                 config: Optional[V2Config] = None,
                 draft_params: Any = None,
                 draft_config: Optional[tfm.TransformerConfig] = None,
                 spec_heads: Any = None):
        if (getattr(model_config, "num_experts", 0) > 0 and
                getattr(model_config, "moe_routing", "capacity") == "expert_choice"):
            raise ValueError(
                "expert_choice routing is non-causal — continuous-batching "
                "decode with it would route across unrelated requests; "
                "serve experts trained with top-k routing")
        if getattr(model_config, "position", "rope") == "alibi":
            raise NotImplementedError(
                "v2's paged Pallas attention takes no additive logit bias "
                "yet — serve ALiBi models (bloom) through the v1 engine "
                "(deepspeed_tpu.init_inference), which supports alibi")
        self.cfg = config or V2Config()
        self.model_cfg = dataclasses.replace(model_config, dtype=self.cfg.dtype)
        self.params = self._quantized(params)
        # device adapter stack for multi-tenant LoRA routing (slot 0 is the
        # reserved all-zero null adapter; serving/adapters.py owns 1..N-1)
        self.adapter_stack = None
        if self.cfg.adapter_slots:
            if self.cfg.adapter_slots < 2:
                raise ValueError(
                    "adapter_slots must be >= 2 when enabled (slot 0 is "
                    "the reserved null adapter)")
            if self.cfg.adapter_rank <= 0:
                raise ValueError(
                    "adapter_slots > 0 requires adapter_rank > 0")
            if self.cfg.spec_mode == "draft":
                raise ValueError(
                    "adapter routing composes with spec_mode='self_draft' "
                    "only — the separate draft model has no adapter stack "
                    "to stay consistent with per-row deltas")
            self.adapter_stack = init_adapter_stack(self.model_cfg, self.cfg)
        # The model's KIND (programs.ServedKind) says what it caches, and the
        # arrays it declares what the managers need.  ``k_win``: window AND
        # global layers keep a pool each: ``kv`` holds the global layers'
        # blocks and ``kv_win`` the window layers', which go back to their
        # pool as they fall behind the window (ragged.KVCacheManager); where
        # every layer is windowed the one pool is.  The kind's ``state``:
        # per-SEQUENCE arrays beside the paged pool, a slot a sequence from
        # the one manager.
        # A latent model's two pools share the ONE block table and allocator.
        # An EVA model's: ``kv`` holds the summaries' blocks, which grow by a
        # window's worth whenever one closes, and ``kv_win`` the current
        # window's, which go back whole at that same step
        self.kind = kind_of(self.model_cfg)
        arrays = self.kind.arrays(self.model_cfg, self.cfg)
        two_pools = "k_win" in arrays
        state_slots = self.cfg.max_seqs if self.kind.state else 0
        self._window = self.kind.window(self.model_cfg, self.cfg)  # 0: none
        # a window that TUMBLES (freed whole when it closes) leaves this many
        # entries in the main pool (its summaries), which then holds those
        # and no tokens; 0: the window slides
        self._per_window = self.kind.closes(self.model_cfg)
        self._refuse()
        max_chunk = self.cfg.max_tokens_per_step
        # one block of each pool reserved as write-scratch for padded tokens
        self.kv = KVCacheManager(
            self.cfg.num_blocks - 1, self.cfg.block_size,
            self.cfg.max_blocks_per_seq,
            window=self._window if self._per_window or not two_pools else 0,
            max_chunk=max_chunk, per_window=self._per_window,
            state_slots=state_slots)
        self.kv_win = None
        if two_pools:
            self.kv_win = KVCacheManager(
                arrays["k_win"][0][1] - 1, self.cfg.block_size,
                self.cfg.max_blocks_per_seq, window=self._window,
                max_chunk=max_chunk, chain="win_",
                tumbling=bool(self._per_window))
        self._managers = [self.kv] + ([self.kv_win] if self.kv_win else [])
        # the manager whose blocks are freed behind the window, if any; the
        # managers whose chains grow while a sequence runs (a decode step
        # opens their blocks); how many layers read a window (the step's
        # counters)
        self._windowed = (self.kv_win or self.kv) if self._window else None
        self._growing = [m for m in self._managers if m.window]
        self._win_layers = (arrays["k_win" if two_pools else "k"][0][0]
                            if self._window else 0)
        self.prefix_cache = None
        self._cow_copy = None
        self.pager = None
        if self.cfg.enable_prefix_cache:
            from .prefix_cache import PrefixCache

            self.prefix_cache = PrefixCache(
                self.kv.allocator, self.cfg.block_size,
                min_prefix_tokens=self.cfg.prefix_cache_min_tokens,
                eviction=self.cfg.prefix_eviction)
            self.kv.prefix_cache = self.prefix_cache
            self._cow_copy = build_cow_copy()
            if self.cfg.kv_host_pool_mb > 0 or self.cfg.kv_host_pool_bytes:
                from .coldstore import ColdStore
                from .paging import BlockPager

                cold = (ColdStore(self.cfg.kv_coldstore_dir)
                        if self.cfg.kv_coldstore_dir else None)
                self.pager = BlockPager(
                    host_bytes=(self.cfg.kv_host_pool_bytes
                                or self.cfg.kv_host_pool_mb << 20),
                    spill_dir=self.cfg.kv_spill_dir,
                    promote_ahead=self.cfg.kv_promote_ahead,
                    coldstore=cold)
                self.prefix_cache.attach_pager(
                    self.pager, self._demote_node, self._promote_node)
        self.builder = RaggedBatchBuilder(self.cfg.max_tokens_per_step,
                                          self.cfg.max_seqs,
                                          self.cfg.max_blocks_per_seq,
                                          two_pools=self.kv_win is not None,
                                          state_scratch=state_slots or -1,
                                          adapters=self.adapter_stack
                                          is not None)
        # A step's host inputs reach the device in ONE copy: each kind of
        # step lays them in one int32 buffer (the decode step's here, the
        # mixed step's the builder's) and a small program takes it apart
        # there (``_to_device``).  ``_h2d``: the copies and bytes of the step
        # under way, for its span
        self._decode_layout = decode_layout(
            self.cfg.max_seqs, self.cfg.max_blocks_per_seq,
            two_pools=self.kv_win is not None,
            adapters=self.adapter_stack is not None)
        self._h2d = None
        # The NEXT decode step's buffer and its fields on the device, made at
        # the end of the step before while no streaming thread is awake
        # (``_stage_next``: the buffer's views, the device's fields, the
        # copy's count), and what the step under way did with the ones staged
        # for it: ``"used"`` / ``"fresh"`` (``_decode_inputs``), and the bytes
        # it dropped unused
        self._staged: Optional[Tuple[Any, Dict[str, jax.Array], Any]] = None
        self._stage_use: Optional[str] = None
        self._stage_dropped = 0
        # Two steps in flight: the step program, of either kind, that the call
        # before called BEHIND its own, before it fetched its own tokens, and
        # that the next call of ``step`` takes for its step (``_step_impl``).
        # The token ids its decode rows read never saw the host: they are in
        # the array its predecessor returned, and its buffer says where
        # (``_promise``).  While it is set the descriptors and the table read
        # what they read on an engine that never goes ahead between the same
        # two calls (the step before is whole: counted, recorded, trimmed,
        # what it ended finished), and everything that touches a pool from
        # the host does so through ``self.caches``, which this program
        # returned: the device runs it behind the program, so a block that a
        # ``cancel`` frees under it, a demoted or exported block (hashed
        # content ends before the slots the program writes), a promoted or
        # imported one (a later owner's writes are dispatched later) need no
        # wait.  Nor do ``close`` (the pager's alone) and ``swap_params`` (a
        # drained engine's, and the program holds the weights it was called
        # with): what is left of the program is dropped by the next ``step``.
        # ``_ahead_flags``: of the step under way, for its span (whether it
        # found its program under way, whether it called its successor, the
        # rows whose token it dropped; None: it ran no such program).
        # ``_out``: what the latest step program returned, fetched or not: the
        # unpack program takes it beside every buffer, so that it has one
        # shape whoever is behind whom (``_to_device``).  ``_kept_out``: the
        # head of the waiting queue that the latest ``_schedule`` could not
        # admit (its uid; 0: none)
        self._ahead: Optional[_Underway] = None
        self._ahead_flags: Optional[Tuple[int, int, int]] = None
        self._out: Optional[jax.Array] = None
        self._kept_out = 0
        # The device's queue as the engine saw it, for ``engine/program``
        # (``_program_called``), all of it read off spans and none of it kept
        # with tracing off: the step programs called and not yet fetched, by
        # their step (two at most); the ``engine/step`` span under way; the
        # latest fetch, as its ``engine/wait`` span and the ``engine/step``
        # span it lay in, whose ``t_end`` say where the fetch returned and
        # where its step did (None where the engine cannot say: before the
        # first fetch, behind a failed step and behind ``_burst_decode``,
        # whose program no span stands for)
        self._calls: Dict[int, Tuple[float, Dict[str, Any]]] = {}
        self._step_sp: Any = None
        self._fetched: Optional[Tuple[Any, Any]] = None
        self.caches = {name: jnp.zeros(shape, dtype)
                       for name, (shape, dtype) in arrays.items()}
        # state bytes a row reads and writes a step, all state layers
        self._state_row_bytes = (
            2 * self.caches[self.kind.state[0]].nbytes // (state_slots + 1)
            if state_slots else 0)
        # what the kind counts of a step, and the step's under way
        self._count = getattr(self, self.kind.counters)
        self._step_counts: Optional[Dict[str, Any]] = None
        self._fwd = build_ragged_forward(self.model_cfg, self.cfg)
        self._decode_fwd = build_decode_forward(self.model_cfg, self.cfg)
        # an MoE model's step programs: assignments (rows x top-k) and the
        # rows of the grouped layout they are computed on, by step kind
        self._moe_rows: Dict[str, Tuple[int, int]] = {}
        self._moe_layers = self.kind.moe_layers(self.model_cfg)
        # a layer that holds a SHARE of its experts reports a third stat,
        # the assignments that were local
        self._moe_share = (self.model_cfg.experts_held
                           != self.model_cfg.num_experts)
        if self.model_cfg.num_experts > 0 and self._moe_layers:
            from ...moe.dropless import padded_rows, share_padded_rows

            E, k = self.model_cfg.num_experts, self.model_cfg.moe_top_k
            for kind, rows in (("decode", self.cfg.max_seqs),
                               ("mixed", self.cfg.max_tokens_per_step)):
                self._moe_rows[kind] = (rows * k, share_padded_rows(
                    rows * k, E, self.model_cfg.experts_held)
                    if self._moe_share else padded_rows(rows * k, E))
        self._moe_stats = None  # (experts hit, rows max) of the last step
        self._multi_decode = {}  # num_steps -> jitted burst decoder
        self.running: Dict[int, SequenceDescriptor] = {}
        self.waiting: Deque[SequenceDescriptor] = deque()
        # SoA decode state: the steady-state (all-decode) path reads/writes
        # these arrays with vectorized ops instead of walking descriptors
        # (VERDICT weak #7: Python-per-step scheduler)
        self.table = DecodeStateTable(
            self.cfg.max_seqs, self.cfg.max_blocks_per_seq,
            self.cfg.max_blocks_per_seq * self.cfg.block_size,
            two_pools=self.kv_win is not None,
            main_grows=self.kv in self._growing)
        # the mixed step's prefill attention tiling, for ``attn_q_slots``
        self._attn_tiles = mixed_step_attn_tiles(self.model_cfg, self.cfg)
        self._prefilling = 0  # running seqs still before their first token
        self.steps = 0  # step() calls so far: the spans' ``step``
        self.fast_steps = 0  # telemetry: SoA decode steps taken
        # of those, the ones that found their program under way, and the
        # tokens computed ahead for rows that were retired before the fetch
        self.ahead_steps = 0
        self.mixed_ahead_steps = 0  # mixed steps that found theirs under way
        self.ahead_dropped = 0
        self.burst_steps = 0  # telemetry: multi-token burst programs run
        self._uid = 0
        self._rng = jax.random.PRNGKey(0)
        self._step_key = None  # the next step's key, once split off
        # -- speculative decoding (inference/v2/spec.py) ---------------
        mode = self.cfg.spec_mode
        if mode not in ("off", "draft", "self_draft"):
            raise ValueError(f"unknown spec_mode {mode!r}")
        if mode != "off" and self.cfg.spec_k < 1:
            raise ValueError("spec_k must be >= 1 when speculation is on")
        self.spec_heads = spec_heads
        self.draft_params = draft_params
        self.draft_cfg = None
        self._draft_caches = None
        self._draft_fwd = None
        self._spec_fwd = None
        # carried final-norm hidden state at each row's last accepted
        # position — what the self-draft heads propose from
        self._spec_hidden = np.zeros(
            (self.cfg.max_seqs, self.model_cfg.hidden_size), np.float32)
        self.spec_steps = 0
        self.spec_proposed = 0  # draft tokens offered to verification
        self.spec_accepted = 0  # draft tokens that made it into the output
        self.spec_emitted = 0  # total tokens emitted by spec steps
        self.spec_fallback = 0  # mixed steps taken while speculation enabled
        if mode == "self_draft":
            if self.spec_heads is None:
                from ...linear.spec_heads import init_spec_heads

                # untrained heads still decode correctly (acceptance is just
                # lower); w2 seeded from the base lm head
                self.spec_heads = init_spec_heads(
                    jax.random.PRNGKey(1), self.model_cfg, self.cfg.spec_k,
                    base_params=self.params)
            self._spec_fwd = build_self_draft_step(self.model_cfg, self.cfg)
        elif mode == "draft":
            if draft_params is None or draft_config is None:
                raise ValueError(
                    "spec_mode='draft' needs draft_params and draft_config")
            self.draft_cfg = dataclasses.replace(draft_config,
                                                 dtype=self.cfg.dtype)
            dshape = (self.draft_cfg.num_layers, self.cfg.num_blocks,
                      self.cfg.block_size, self.draft_cfg.kv_heads,
                      self.draft_cfg.head_dim)
            dt = jnp.dtype(self.cfg.dtype)
            self._draft_caches = {"k": jnp.zeros(dshape, dt),
                                  "v": jnp.zeros(dshape, dt)}
            self._draft_fwd = build_ragged_forward(self.draft_cfg, self.cfg)
            self._spec_fwd = build_draft_spec_step(
                self.model_cfg, self.draft_cfg, self.cfg)

    def _refuse(self) -> None:
        """What this kind of model cannot be combined with yet
        (``programs.REFUSED``, the kind's rows): refused by name, never
        served wrong."""
        for name in self.kind.refuses(self.model_cfg, self.cfg):
            if any(getattr(self.cfg, f) != getattr(V2Config, f)
                   for f in name.split(" / ")):
                raise ValueError(
                    f"V2Config.{name} cannot be combined with "
                    + self.kind.because.format(does=REFUSED[name],
                                               window=self._window))

    def _quantized(self, raw_params: Any) -> Any:
        """``raw_params`` as this engine serves them: untouched without
        ``quantize_bits``; else quantized on the host, and the codes moved
        beside the KV cache, on the default device, where the jitted steps
        want them."""
        if not self.cfg.quantize_bits:
            return raw_params
        from ..quantization import quantize_on_host

        return jax.device_put(
            quantize_on_host(raw_params, self.cfg.quantize_bits,
                             self.cfg.quantize_group),
            jax.local_devices()[0])

    # -- rolling weight swaps (serving/rollout.py) ----------------------

    def swap_params(self, raw_params: Any) -> None:
        """Point the engine at a new param pytree (rolling weight swap).
        ``raw_params`` is the UNQUANTIZED checkpoint tree; the engine
        re-applies its own quantization config so a quantized deployment
        swaps into quantized weights.  The previous tree is retained for
        :meth:`swap_rollback`.  Safe only between steps on a drained
        engine: the jitted forwards take params as call arguments, so
        the swap is a pointer move, but swapping mid-request would mix
        weight generations within one stream."""
        raw_params = self._quantized(raw_params)
        if (jax.tree_util.tree_structure(raw_params)
                != jax.tree_util.tree_structure(self.params)):
            raise ValueError("swap_params: incoming pytree structure does "
                             "not match the serving model")
        self._prev_params = self.params
        self.params = raw_params

    def swap_rollback(self) -> None:
        """Restore the pre-swap weights (failed post-swap probe)."""
        prev = getattr(self, "_prev_params", None)
        if prev is None:
            raise RuntimeError("swap_rollback: no previous params retained")
        self.params = prev
        self._prev_params = None

    # -- device adapter stack (serving/adapters.py) ---------------------

    def set_adapter_slot(self, slot: int, pack: Dict[str, Tuple[Any, Any]]
                         ) -> None:
        """Load one adapter's stacked factors into device slot ``slot``.

        ``pack`` maps target names (a subset of :data:`ADAPTER_TARGETS`)
        to ``(lora_a (L, K, r), lora_b (L, r, N))`` host arrays with any
        scaling already folded into ``lora_b`` and rank padded to
        ``adapter_rank``.  Targets absent from the pack keep their zeros
        (exact-zero delta).  Engine-thread only — this is a JAX call."""
        if self.adapter_stack is None:
            raise RuntimeError("engine built without adapter_slots")
        if not (0 < slot < self.cfg.adapter_slots):
            raise ValueError(
                f"slot must be in 1..{self.cfg.adapter_slots - 1} "
                f"(0 is the null adapter), got {slot}")
        dt = jnp.dtype(self.cfg.dtype)
        stack = dict(self.adapter_stack)
        for name, (a, b) in pack.items():
            if name not in stack:
                raise ValueError(
                    f"unsupported adapter target {name!r}; the device "
                    f"stack carries {sorted(stack)}")
            tgt = stack[name]
            want_a = tgt["a"].shape[:1] + tgt["a"].shape[2:]
            want_b = tgt["b"].shape[:1] + tgt["b"].shape[2:]
            if tuple(a.shape) != want_a or tuple(b.shape) != want_b:
                raise ValueError(
                    f"adapter target {name!r} shape mismatch: got "
                    f"a{tuple(a.shape)}/b{tuple(b.shape)}, stack wants "
                    f"a{want_a}/b{want_b}")
            stack[name] = {
                "a": tgt["a"].at[:, slot].set(jnp.asarray(a).astype(dt)),
                "b": tgt["b"].at[:, slot].set(jnp.asarray(b).astype(dt))}
        self.adapter_stack = stack

    def clear_adapter_slot(self, slot: int) -> None:
        """Zero a slot's factors (retire/demote) — rows must no longer
        reference it (the registry's refcounts guarantee that)."""
        if self.adapter_stack is None:
            raise RuntimeError("engine built without adapter_slots")
        if not (0 < slot < self.cfg.adapter_slots):
            raise ValueError(f"invalid adapter slot {slot}")
        self.adapter_stack = {
            name: {"a": tgt["a"].at[:, slot].set(0.0),
                   "b": tgt["b"].at[:, slot].set(0.0)}
            for name, tgt in self.adapter_stack.items()}

    def _adapter_args(self) -> tuple:
        """Extra trailing arguments for the jitted forwards when the
        adapter stack is on: (stacked factors, per-row slot vector)."""
        if self.adapter_stack is None:
            return ()
        return (self.adapter_stack, jnp.asarray(self.table.adapter))

    # -- capacity accessors (serving metrics / admission control) -------
    @property
    def total_blocks(self) -> int:
        """Blocks of every pool (a model with two kinds of layer has two)."""
        return sum(m.allocator.num_blocks for m in self._managers)

    @property
    def free_blocks(self) -> int:
        return sum(m.allocator.free_blocks for m in self._managers)

    @property
    def total_state_slots(self) -> int:
        """State slots of a model with state layers (0: it has none)."""
        return self.kv.slots.num_slots if self.kv.slots else 0

    @property
    def free_state_slots(self) -> int:
        return self.kv.free_slots

    def drained(self) -> bool:
        """Every block of every pool and every state slot is back."""
        return all(m.drained() for m in self._managers)

    @property
    def evictable_blocks(self) -> int:
        """Prefix-tree blocks no live sequence shares (refcount 1)."""
        return self.prefix_cache.evictable_blocks if self.prefix_cache else 0

    @property
    def reclaimable_blocks(self) -> int:
        """Evictable blocks admission control may treat as free (0 when
        the cache is off or the eviction policy is 'none')."""
        return (self.prefix_cache.reclaimable_blocks
                if self.prefix_cache else 0)

    @property
    def pinned_blocks(self) -> int:
        """Allocated blocks some live owner still needs — computed from
        allocator refcounts (NOT as total - free - evictable) so the leak
        invariant ``free + evictable + pinned == total`` is a real check."""
        live = sum(1 for m in self._managers
                   for b in range(m.allocator.num_blocks)
                   if m.allocator.refcount(b) > 0)
        return live - self.evictable_blocks

    def prefix_stats(self) -> Dict[str, float]:
        """Prefix-cache counters + block-accounting gauges for serving
        metrics; all-zero (enabled=0) when the cache is off."""
        stats: Dict[str, float] = {
            "enabled": 0, "lookups": 0, "hits": 0, "hit_rate": 0.0,
            "prefill_tokens_skipped": 0, "evictions": 0, "cow_copies": 0,
            "cached_blocks": 0, "shared_blocks": 0, "evictable_blocks": 0,
            # memory-hierarchy tiers (inference/v2/paging.py); ride the
            # worker heartbeat into /healthz and the balancer aggregate
            "tier_device_blocks": 0, "tier_host_blocks": 0,
            "tier_spill_blocks": 0, "demotions": 0, "promotions": 0,
            "promote_wait_ms": 0.0,
            # crash-durable cold tier (inference/v2/coldstore.py)
            "tier_cold_blocks": 0, "rehydrated_blocks": 0,
            "gc_spill_files": 0, "coldstore_entries": 0,
            "coldstore_bytes": 0, "coldstore_writes": 0,
            "coldstore_corrupt_dropped": 0, "coldstore_gc_tmp": 0,
        }
        if self.prefix_cache is not None:
            stats.update(self.prefix_cache.stats())
            stats["enabled"] = 1
        if self.pager is not None:
            stats["gc_spill_files"] = self.pager.gc_spill_files
            if self.pager.coldstore is not None:
                stats.update(self.pager.coldstore.stats())
        stats["pinned_blocks"] = self.pinned_blocks
        # what a full server ran out of: blocks, or (a model with state
        # layers) state slots
        stats["free_blocks"] = self.free_blocks
        stats["state_slots"] = self.total_state_slots
        stats["state_slots_free"] = self.free_state_slots
        return stats

    def prefix_summary(self, max_digests: int = 1024) -> Dict[str, Any]:
        """Radix-tree digest summary for cache-aware routing (empty when
        the cache is off) — rides the worker heartbeat."""
        if self.prefix_cache is None:
            return {"block_size": self.cfg.block_size, "digests": []}
        return self.prefix_cache.summary(max_digests)

    # -- KV handoff between replica classes (disaggregated serving) -----

    def export_prefix(self, tokens: List[int]) -> Optional[bytes]:
        """Serialize the longest cached full-block prefix of ``tokens`` as
        a safetensors payload (``io/fast_writer.py`` header format): the
        k/v block data of the matched radix subtree plus the covered token
        ids.  This is the unit of KV transfer between replica classes — a
        prefill replica exports the prompt's KV, a decode replica imports
        it and decodes from the first uncached token.  Returns ``None``
        when nothing is cached."""
        if self.prefix_cache is None:
            return None
        from ...io.fast_writer import build_safetensors_header

        blocks, matched = self.prefix_cache.walk_full_blocks(tokens)
        if not blocks:
            return None
        try:
            idx = np.asarray(blocks, np.int64)
            arrays = {
                "k": np.ascontiguousarray(np.asarray(self.caches["k"][:, idx])),
                "v": np.ascontiguousarray(np.asarray(self.caches["v"][:, idx])),
            }
            meta = {
                "tokens": ",".join(str(int(t)) for t in tokens[:matched]),
                "block_size": str(self.cfg.block_size),
            }
            header, offsets, _ = build_safetensors_header(arrays, meta)
            parts = [header]
            for name in arrays:  # dict order == offset order
                parts.append(arrays[name].tobytes())
            return b"".join(parts)
        finally:
            self.kv.allocator.free(blocks)  # drop the export walk's pins

    def import_prefix(self, payload: bytes) -> int:
        """Adopt an exported prefix: allocate blocks, scatter the k/v data
        into the paged caches, and donate the chain into the radix tree.
        Imports the longest leading run of blocks the pool can hold;
        returns the number of prompt tokens now cached locally."""
        if self.prefix_cache is None:
            return 0
        import json as _json

        import ml_dtypes

        hlen = int.from_bytes(payload[:8], "little")
        hdr = _json.loads(payload[8:8 + hlen].decode())
        data = payload[8 + hlen:]
        meta = hdr.pop("__metadata__", {})
        if int(meta.get("block_size", -1)) != self.cfg.block_size:
            return 0  # block-size mismatch: not transferable
        tokens = [int(t) for t in meta["tokens"].split(",") if t]
        dt_map = {"BF16": ml_dtypes.bfloat16, "F32": np.float32,
                  "F16": np.float16}
        tensors = {}
        for name, ent in hdr.items():
            lo, hi = ent["data_offsets"]
            tensors[name] = np.frombuffer(
                data[lo:hi], dtype=dt_map[ent["dtype"]]
            ).reshape(ent["shape"])
        k_arr, v_arr = tensors["k"], tensors["v"]
        n = k_arr.shape[1]
        alloc = self.kv.allocator
        if n > alloc.free_blocks:
            self.prefix_cache.evict(n - alloc.free_blocks)
        n = min(n, alloc.free_blocks)
        if n == 0:
            return 0
        blocks = alloc.allocate(n)
        idx = jnp.asarray(np.asarray(blocks, np.int64))
        dt = jnp.dtype(self.cfg.dtype)
        self.caches = {
            "k": self.caches["k"].at[:, idx].set(
                jnp.asarray(k_arr[:, :n]).astype(dt)),
            "v": self.caches["v"].at[:, idx].set(
                jnp.asarray(v_arr[:, :n]).astype(dt)),
        }
        covered = n * self.cfg.block_size
        # donate adopts our references (or dedupes against already-cached
        # chunks by freeing the duplicate block)
        self.prefix_cache.donate(tokens[:covered], covered, blocks)
        return covered

    # -- serving memory hierarchy (inference/v2/paging.py) ---------------

    def _read_kv_block(self, block: int) -> Dict[str, np.ndarray]:
        """One block's k/v bytes as host arrays (the pager's demote input;
        same layout ``export_prefix`` ships between replicas)."""
        return {
            "k": np.ascontiguousarray(np.asarray(self.caches["k"][:, block])),
            "v": np.ascontiguousarray(np.asarray(self.caches["v"][:, block])),
        }

    def _demote_node(self, node) -> Optional[Tuple[int, str]]:
        """Prefix-cache demote callback: serialize the node's device block
        into the pager.  Returns ``(handle, tier)`` or ``None`` (pager
        full → the caller falls back to true eviction).

        With a cold store attached, the block also gets its *durable
        identity*: the chain digest of its full token prefix becomes the
        cold-store key, and the manifest meta carries the chain tokens —
        everything a respawned worker needs to rebuild the radix path in
        ``rehydrate_coldstore``."""
        sp = tracer.begin("paging/demote", block=int(node.block))
        meta = key = None
        if self.pager.coldstore is not None:
            from .prefix_cache import chain_tokens, prefix_digests

            tokens = chain_tokens(node)
            bs = self.cfg.block_size
            key = "kv-" + prefix_digests(tokens, bs)[-1]
            meta = {"kind": "kv_block",
                    "tokens": ",".join(str(t) for t in tokens),
                    "block_size": str(bs)}
        res = self.pager.put(self._read_kv_block(node.block),
                             metadata=meta, durable_key=key)
        if res is None:
            tracer.end(sp, ok=False, full=True)
            return None
        handle, tier = res
        tracer.end(sp, ok=True, handle=handle, tier=tier)
        return handle, tier

    def rehydrate_coldstore(self) -> Dict[str, int]:
        """Restart rehydration: re-adopt the cold-store entries a crashed
        (or gracefully restarted) predecessor left behind, so resumed
        sessions promote instead of re-prefilling.

        Every entry is verified BEFORE adoption (sha256 manifest + its
        key recomputed from the chain tokens it claims) — a torn, corrupt
        or tampered entry is deleted and the prefix degrades to
        re-prefill, never to wrong tokens.  Entries whose ancestor chunks
        did not survive are orphans and are deleted too (a radix chunk is
        only reachable through its full chain).  Returns adoption counts;
        a no-op without a cold store or prefix cache."""
        out = {"adopted": 0, "orphaned": 0, "skipped": 0}
        pager = self.pager
        if (pager is None or pager.coldstore is None
                or self.prefix_cache is None):
            return out
        from ...utils import faults
        from .prefix_cache import prefix_digests

        cs = pager.coldstore
        bs = self.cfg.block_size
        sp = tracer.begin("coldstore/rehydrate_kv")
        chains: List[Tuple[str, List[int], int]] = []
        for key, meta, nbytes in cs.entries():
            if meta.get("kind") != "kv_block":
                continue  # not ours (e.g. an adapter section sharing root)
            try:
                entry_bs = int(meta.get("block_size", -1))
                tokens = [int(t) for t in
                          str(meta.get("tokens", "")).split(",") if t]
            except ValueError:
                entry_bs, tokens = -1, []
            if (entry_bs != bs or not tokens or len(tokens) % bs != 0
                    or key != "kv-" + prefix_digests(tokens, bs)[-1]):
                cs.delete(key)  # wrong geometry or tampered meta
                out["skipped"] += 1
                continue
            chains.append((key, tokens, nbytes))
        chains.sort(key=lambda c: len(c[1]))  # parent-first (shallow first)
        for key, tokens, nbytes in chains:
            faults.maybe_fail("serving.coldstore.rehydrate")
            if cs.read(key) is None:  # verify-before-adopt; corrupt → GC'd
                out["skipped"] += 1
                continue
            handle = pager.adopt(key, nbytes)
            if handle is None:
                out["skipped"] += 1
                continue
            status = self.prefix_cache.adopt_demoted(tokens, handle,
                                                     tier="cold")
            if status == "adopted":
                out["adopted"] += 1
            elif status == "duplicate":
                # the chain is already in the tree, and its node may be
                # backed by this very durable entry — unwind the handle
                # bookkeeping WITHOUT deleting the shared entry
                pager.forget(handle)
                out["skipped"] += 1
            else:  # orphan: unreachable without its ancestors
                pager.drop(handle)  # unwind + delete the dead entry
                out["orphaned"] += 1
        tracer.end(sp, **out)
        return out

    def _promote_node(self, node) -> bool:
        """Prefix-cache promote callback: fetch a demoted node's bytes
        (staged by the promote-ahead thread when enabled) and scatter them
        into a freshly-allocated device block.  The scatter is a host-side
        ``.at[].set`` on the cache arrays — exactly ``import_prefix``'s
        path — so the compiled prefill/decode programs never change."""
        t0 = time.perf_counter()
        sp = tracer.begin("paging/promote", handle=int(node.handle or -1),
                          tier=node.tier)
        arrays = self.pager.get(node.handle)
        if arrays is None:
            tracer.end(sp, ok=False, lost=True)
            return False
        alloc = self.kv.allocator
        if alloc.free_blocks == 0:
            # make room by demoting a colder node (walked-path ancestors
            # are pinned by match(), so they are never victims)
            self.prefix_cache.evict(1)
        if alloc.free_blocks == 0:
            tracer.end(sp, ok=False)
            return False  # match stops here; the tail prefills normally
        (dst,) = alloc.allocate(1)
        dt = jnp.dtype(self.cfg.dtype)
        self.caches = {
            "k": self.caches["k"].at[:, dst].set(
                jnp.asarray(arrays["k"]).astype(dt)),
            "v": self.caches["v"].at[:, dst].set(
                jnp.asarray(arrays["v"]).astype(dt)),
        }
        handle = node.handle
        node.block = dst
        node.tier = "device"
        node.handle = None
        self.pager.drop(handle)
        alloc.note_promote()
        wait_ms = (time.perf_counter() - t0) * 1e3
        self.pager.record_promote_wait(wait_ms)
        tracer.end(sp, ok=True, block=dst, wait_ms=wait_ms)
        return True

    def _prefetch_demoted(self, tokens: List[int]) -> None:
        """Promote-ahead: walk the radix tree read-only along a just-queued
        prompt and hand any demoted handles to the pager's background
        thread, so the disk→host half of their promotion overlaps the
        steps before this request is scheduled."""
        node = self.prefix_cache._root
        bs = self.cfg.block_size
        handles: List[int] = []
        matched = 0
        while matched + bs <= len(tokens):
            child = node.children.get(tuple(tokens[matched:matched + bs]))
            if child is None:
                break
            if child.tier != "device" and child.handle is not None:
                handles.append(child.handle)
            node = child
            matched += bs
        if handles:
            self.pager.prefetch(handles)

    def close(self) -> None:
        """Release paging resources (promote-ahead thread, spill writer).
        Safe to call more than once; a pagerless engine is a no-op.  A
        program still under way, which no call will fetch, leaves the ring as
        an ``engine/program`` marked ``error`` that ends here."""
        if self._calls:
            self._programs_dropped()
        if self.pager is not None:
            self.pager.close()

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decoding counters for serving metrics; ``enabled=0``
        and all-zero when ``spec_mode`` is 'off'.  ``acceptance_rate`` is
        accepted-draft tokens / proposed-draft tokens (bonus/correction
        tokens excluded from both sides)."""
        on = self._spec_fwd is not None
        return {
            "enabled": float(on),
            "k": float(self.cfg.spec_k) if on else 0.0,
            "steps": float(self.spec_steps),
            "proposed_tokens": float(self.spec_proposed),
            "accepted_tokens": float(self.spec_accepted),
            "emitted_tokens": float(self.spec_emitted),
            "acceptance_rate": (self.spec_accepted / self.spec_proposed
                                if self.spec_proposed else 0.0),
            "fallback_steps": float(self.spec_fallback),
        }

    @property
    def num_running(self) -> int:
        return len(self.running)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    def _reserved_by_waiting(self, manager: Optional[KVCacheManager] = None
                             ) -> int:
        """Blocks of ``manager``'s pool (default: the main one) the waiting
        queue will claim at admission (running sequences already hold their
        full budget — reserved at admission)."""
        manager = manager or self.kv
        return sum(manager.reservation(s.cur_len + s.max_new_tokens)
                   - len(manager.chain(s)) for s in self.waiting)

    # -- request API ---------------------------------------------------
    def put(self, prompt_tokens: List[int], max_new_tokens: int = 64,
            strict: bool = False, temperature: Optional[float] = None,
            seed: int = 0, adapter_slot: int = 0) -> int:
        """Queue a request.  Raises :class:`AdmissionError` if the request
        could NEVER run (exceeds max context).  With ``strict=True`` it also
        raises when the engine cannot admit it RIGHT NOW — no free sequence
        slot, or the block pool (minus what the waiting queue has coming)
        cannot hold the full prompt+budget reservation.  A strictly-admitted
        request is therefore guaranteed schedulable on the next step.

        ``temperature``/``seed`` pin THIS request's sampling row in the
        per-row vector; ``temperature=None`` inherits whatever scalar the
        caller passes to :meth:`step` (the pre-disaggregation behaviour).
        ``adapter_slot`` selects the device adapter-stack slot this
        request's rows read (0 = base model, no delta)."""
        if adapter_slot:
            if self.adapter_stack is None:
                raise AdmissionError(
                    "engine built without adapter_slots; adapter requests "
                    "cannot run here")
            if not (0 < adapter_slot < self.cfg.adapter_slots):
                raise AdmissionError(
                    f"adapter_slot {adapter_slot} out of range "
                    f"1..{self.cfg.adapter_slots - 1}")
        max_ctx = self.cfg.max_blocks_per_seq * self.cfg.block_size
        need = len(prompt_tokens) + max_new_tokens
        if need > max_ctx:
            raise AdmissionError(
                f"request needs {need} tokens of KV but max context is "
                f"{max_ctx} (max_blocks_per_seq * block_size); an admitted "
                "request could never be scheduled")
        if strict:
            if self.num_running + self.num_waiting >= self.cfg.max_seqs:
                # a model with state layers: a sequence slot is a state slot
                raise AdmissionError(
                    f"all {self.cfg.max_seqs} sequence slots in use "
                    f"({self.num_running} running, {self.num_waiting} "
                    "waiting)" + (
                        f"; {self.kv.free_slots} of {self.total_state_slots}"
                        f" state slots free, {self.free_blocks} of "
                        f"{self.total_blocks} KV blocks free"
                        if self.kv.slots is not None else ""))
            # evictable prefix-cache blocks count as free: admission must
            # not starve on a warm cache (the scheduler evicts on demand)
            for i, m in enumerate(self._managers):  # every pool has to hold it
                avail = (m.unreserved_blocks - self._reserved_by_waiting(m)
                         + (self.reclaimable_blocks if m is self.kv else 0))
                if m.reservation(need) > avail:
                    raise AdmissionError(
                        f"KV block pool exhausted: request needs "
                        f"{m.reservation(need)} blocks"
                        f"{' of the window layers' if i else ''}, "
                        f"{avail} unreserved"
                        + (f" ({self.kv.free_slots} state slots free)"
                           if self.kv.slots is not None else ""))
        self._uid += 1
        seq = SequenceDescriptor(uid=self._uid, tokens=list(prompt_tokens),
                                 max_new_tokens=max_new_tokens,
                                 temperature=temperature, seed=seed,
                                 adapter_slot=adapter_slot)
        self.waiting.append(seq)
        if self.pager is not None and self.cfg.kv_promote_ahead:
            # overlap the disk→host half of any needed promotions with the
            # steps that run before the queue head is scheduled
            self._lookahead_prefetch()
        return self._uid

    def _lookahead_prefetch(self) -> None:
        """Promote-ahead keyed off the scheduler's ADMISSION lookahead: walk
        the waiting queue in admission order, bounded by the free sequence
        slots and the per-step token budget the next `_schedule` will have,
        and prefetch demoted prefix blocks for exactly the requests that can
        actually land in the upcoming batch.  Strictly better targeted than
        prefetching every queued prompt — a deep queue no longer floods the
        staging thread with promotions the scheduler cannot consume yet."""
        slots = self.cfg.max_seqs - self.num_running
        budget = self.cfg.max_tokens_per_step
        for seq in self.waiting:
            if slots <= 0 or budget <= 0:
                break
            self._prefetch_demoted(seq.tokens)
            slots -= 1
            budget -= min(len(seq.tokens), budget)

    def _schedule(self) -> List[Tuple[SequenceDescriptor, int]]:
        """Dynamic SplitFuse: decode tokens first, then prefill chunks."""
        budget = self.cfg.max_tokens_per_step
        picks: List[Tuple[SequenceDescriptor, int]] = []
        # running sequences: 1 decode token each (or remaining prefill); one
        # that ends with the token under way (``_advance`` took its row out
        # of the steps to come) holds its place until that token's fetch
        for seq in list(self.running.values()):
            if len(picks) >= self.cfg.max_seqs or budget <= 0:
                break
            if not self.table.active[self.table.row_of[seq.uid]]:
                continue
            n = min(seq.cur_len - seq.seen_tokens, budget) or 1
            # (a window that tumbles ends a chunk at its edge)
            n = min(n, budget, self._managers[-1].chunk_cap(seq.seen_tokens))
            if not all(m.ensure_capacity(seq, n) for m in self._managers):
                continue  # stalled on memory this step
            picks.append((seq, n))
            budget -= n
        # admit waiting sequences with prefill chunks. Admission reserves the
        # request's ENTIRE block budget (prompt + max_new_tokens) up front so
        # an admitted sequence can never stall mid-decode — without this the
        # pool can be exhausted by half-admitted requests and livelock.
        # (a row of the table each: one that ended with the token under way
        # has not given its own back yet)
        while (self.waiting and budget > 0 and len(picks) < self.cfg.max_seqs
               and len(self.running) < self.cfg.max_seqs):
            seq = self.waiting[0]
            # draft mode can't take prefix hits: skipped prefill would leave
            # the DRAFT cache without KV for the shared tokens (the tree only
            # indexes target blocks); self-draft composes fully
            if (self.prefix_cache is not None and not seq.blocks
                    and seq.seen_tokens == 0
                    and self.cfg.spec_mode != "draft"):
                self._match_prefix(seq)
            n = min(seq.cur_len - seq.seen_tokens, budget,
                    self._managers[-1].chunk_cap(seq.seen_tokens))
            total_needed = (seq.cur_len - seq.seen_tokens) + seq.max_new_tokens
            if n <= 0 or not self._reserve(seq, total_needed, n):
                if seq.blocks or seq.seen_tokens:
                    # roll the prefix match back — waiting sequences hold
                    # no blocks (admission-reservation invariant); the
                    # lookup is uncounted so stalls don't skew hit rate
                    self.kv.release(seq)
                    seq.seen_tokens = 0
                    self.prefix_cache.lookups -= 1
                break
            if seq.seen_tokens:
                self.prefix_cache.hits += 1
                self.prefix_cache.tokens_skipped += seq.seen_tokens
            self.waiting.popleft()
            self.running[seq.uid] = seq
            self.table.admit(seq)
            self._prefilling += 1
            picks.append((seq, n))
            budget -= n
        self._kept_out = self.waiting[0].uid if self.waiting else 0
        return picks

    def _reserve(self, seq: SequenceDescriptor, total_tokens: int,
                 chunk: int) -> bool:
        """Admission: every pool sets the sequence's whole budget aside, or
        none does."""
        if not self.kv.take_slot(seq):  # a model with state layers
            return False
        for i, m in enumerate(self._managers):
            if not m.reserve(seq, total_tokens, chunk):
                for done in self._managers[:i]:
                    done.release(seq)
                if i == 0 and self.kv.slots is not None:
                    self.kv.release(seq)  # the slot just taken
                return False
        return True

    def _release(self, seq: SequenceDescriptor) -> None:
        for m in self._managers:
            m.release(seq)

    def _match_prefix(self, seq: SequenceDescriptor) -> None:
        """Seed a waiting sequence's block table from the radix tree.

        Full shared blocks are pure block-table indirection (the jitted
        forwards never change); a partial-block divergence forks a private
        copy-on-write block on device.  ``seen_tokens`` advances past the
        cached prefix so SplitFuse prefill starts at the first uncached
        token.  The scheduler rolls this back via ``kv.release`` if the
        sequence still cannot be admitted."""
        m = self.prefix_cache.match(seq.tokens, limit=seq.cur_len - 1)
        if m is None:
            return
        blocks = list(m.blocks)
        skipped = m.tokens
        if m.cow_src is not None:
            alloc = self.kv.allocator
            if alloc.free_blocks == 0:
                self.prefix_cache.evict(1)
            if (alloc.free_blocks > 0
                    and len(blocks) < self.cfg.max_blocks_per_seq):
                (dst,) = alloc.allocate(1)
                self.caches = self._cow_copy(
                    self.caches, jnp.int32(m.cow_src), jnp.int32(dst))
                self.prefix_cache.cow_copies += 1
                blocks.append(dst)
                skipped += m.cow_tokens
            alloc.free([m.cow_src])  # drop match()'s pin on the source
        if skipped == 0:
            self.kv.allocator.free(blocks)
            return
        seq.blocks = blocks
        seq.seen_tokens = skipped

    def _flush_table(self) -> None:
        """Re-sync descriptors from the SoA rows before any descriptor-based
        (mixed prefill/decode) step."""
        for seq in self.running.values():
            self.table.flush_tokens(seq)

    def _finish(self, seq: SequenceDescriptor) -> None:
        seq.done = True
        self.table.retire(seq)
        if self.prefix_cache is not None and self.cfg.spec_mode != "draft":
            # donate full prefix blocks into the radix tree instead of
            # freeing them (retire() just flushed the SoA row, so
            # seen_tokens == tokens actually written to KV)
            self.prefix_cache.donate(seq.tokens, seq.seen_tokens, seq.blocks)
            seq.blocks = []
            if self.pager is not None:
                # demote-on-pressure: keep one sequence's worth of headroom
                # so the NEXT admission demotes nothing on its critical
                # path (the donate above may have just consumed it)
                short = (self.cfg.max_blocks_per_seq
                         - self.kv.allocator.free_blocks)
                if short > 0:
                    self.prefix_cache.evict(short)
        else:
            self._release(seq)
        del self.running[seq.uid]

    def cancel(self, uid: int) -> bool:
        """Abort a request mid-prefill or mid-decode: retire its table row
        and return every KV block to the pool.  Safe between steps (the
        serving broker serializes cancels onto the engine thread).  Returns
        False if the uid is unknown / already finished."""
        for seq in self.waiting:
            if seq.uid == uid:
                self.waiting.remove(seq)
                self._release(seq)  # waiting seqs hold no blocks; belt+braces
                seq.done = True
                return True
        seq = self.running.get(uid)
        if seq is None:
            return False
        if not seq.in_decode:
            self._prefilling -= 1
        self._finish(seq)
        return True

    def _table_inputs(self):
        """The speculative and the burst decode's inputs straight off the
        SoA table, an array each (padded static shapes; inactive rows carry
        ctx 0); the decode step's go in one buffer (``_pack_decode``)."""
        t = self.table
        ctx_in = ((t.ctx + 1) * t.active).astype(np.int32)
        tables = jnp.asarray(t.block_tables)
        if t.win_tables is not None:  # a table a pool (programs.tables_of)
            tables = (tables, jnp.asarray(t.win_tables))
        return (jnp.asarray(t.next_tok), jnp.asarray(t.ctx), tables,
                jnp.asarray(ctx_in))

    # -- a windowed pool's upkeep (ragged.KVCacheManager, window > 0) ------

    def _window_open_blocks(self) -> None:
        """Before a decode step: the rows whose next token starts a block
        (or completes a window whose summaries need theirs) take it from the
        pool that grows (admission reserved it)."""
        t = self.table
        for m in self._growing:
            table = t.win_tables if m is self.kv_win else t.block_tables
            for r in np.nonzero(t.active & m.opens_at(t.ctx))[0]:
                chain = m.chain(t.seq_at[int(r)])
                have, need = len(chain), m.blocks_for(int(t.ctx[r]) + 1)
                if have < need:
                    chain.extend(m.allocator.allocate(need - have))
                    table[r, have:need] = chain[have:]

    def _window_trim_rows(self) -> None:
        """After a decode step: the rows whose oldest visible key just left
        a block (a tumbling window: whose window just closed) give back what
        no later query reads."""
        t, m = self.table, self._windowed
        for r in np.nonzero(t.active & m.trims_at(t.ctx))[0]:
            m.trim(t.seq_at[int(r)], int(t.ctx[r]))

    # -- what a kind counts of a step (programs.ServedKind.counters): ``start``
    # / ``n``, each row's first position and its tokens as the host holds them
    # (the table's ``ctx`` / ``active``, the batch's ``chunk_start`` /
    # ``chunk_len``) → attributes of the step's span

    def _count_kv(self, start: "np.ndarray", n: "np.ndarray", mixed: bool
                  ) -> Dict[str, Any]:
        """A mixed step: the query slots the prefill kernel multiplies, a
        row's tokens rounded up to its tiles.  A windowed model's: the K/V
        blocks its attention has to read, summed over rows and layers (a
        layer reads a row's blocks from the one that holds the oldest key
        its oldest query sees to the one of its newest token), what full
        attention on every layer would have read, and the (query, key)
        pairs it multiplies (``trimmed``: the blocks freed so far, which
        ``step`` turns into the step's own)."""
        counts = ({"attn_q_slots": int(self._attn_tiles.slots(n).sum())}
                  if mixed else {})
        if self._windowed is None:
            return counts
        start, n = _live_rows(start, n)
        bs, w = self.cfg.block_size, self._window
        last = -(-(start + n) // bs)  # blocks up to the row's newest token
        full = int(last.sum())
        win = int((last - np.maximum(start - w + 1, 0) // bs).sum())
        # a query at p sees p + 1 keys, a windowed one min(p + 1, window)
        cols = np.arange(int(n.max()))[None]
        seen = np.where(cols < n[:, None], start[:, None] + 1 + cols, 0)
        L, Lw = self.model_cfg.num_layers, self._win_layers
        return dict(
            counts, kv_blocks_read=win * Lw + full * (L - Lw),
            kv_blocks_full=full * L,
            kv_query_keys=int(np.minimum(seen, w).sum()) * Lw
            + int(seen.sum()) * (L - Lw),
            trimmed=self._windowed.trimmed)

    def _count_state(self, start: "np.ndarray", n: "np.ndarray", mixed: bool
                     ) -> Dict[str, Any]:
        """A state model's step: the slots taken, the rows that began from
        zeros, the tokens through the scan, the state bytes read and written;
        of a mixed step also what the chunked scan walks: the rows of two
        tokens and more, their tokens and their pieces of a chunk."""
        counts = self._count_kv(start, n, mixed)
        start, n = _live_rows(start, n)
        counts.update(
            state_slots_used=self.total_state_slots - self.free_state_slots,
            state_rows_started=int((start == 0).sum()),
            ssm_tokens=int(n.sum()),
            ssm_state_bytes=len(n) * self._state_row_bytes)
        if mixed and self.model_cfg.layers_of("S"):
            # the selective scan cuts the BATCH into blocks, and a row into
            # the segments its tokens make with them
            q_start = np.cumsum(n) - n
            many = n >= 2
            counts.update(
                ssm_scan_rows=int(many.sum()),
                ssm_scan_tokens=int(n[many].sum()),
                ssm_scan_pieces=int(scan_pieces(q_start, n)[many].sum()))
        elif mixed:
            many = n[n >= 2]
            chunk = self.model_cfg.mamba_chunk_size
            counts.update(
                ssm_scan_rows=len(many), ssm_scan_tokens=int(many.sum()),
                ssm_scan_pieces=int((-(-many // chunk)).sum()))
        counts["kv_blocks_used"] = self.total_blocks - self.free_blocks
        return counts

    def _count_latent(self, start: "np.ndarray", n: "np.ndarray", mixed: bool
                      ) -> Dict[str, Any]:
        """A latent model's step, with the layer pattern.  Summed over rows
        and layers: the (query, key) pairs before
        the selection (a query at ``p`` sees ``p + 1`` keys) and after it
        (``index_topk`` of them at most), those after it again by the path
        that attends (``_single``: the rows of one token; ``_prefill``: the
        rows of two and more) beside the keys that path has to READ
        (``latent_keys_*``: a row's picked keys once, however many of its
        queries picked them: the smaller of its picks and its context); the
        pairs the layers that pick score and the indexer keys they read (a
        row's context once); the blocks the latent pool has out; the expert
        assignments the routed layers make (of a share of the experts, the
        local ones come back with the step: ``_split_stats``); of a mixed
        step the query slots: the prefill tiles of a row, one a single row."""
        c = self.model_cfg
        start, n = _live_rows(start, n)
        # every layer attends; the layers that pick hold the indexer's pool
        L, Lf = c.num_layers, self.caches["index"].shape[0]
        cols = np.arange(int(n.max(initial=0)))[None]
        seen = np.where(cols < n[:, None], start[:, None] + 1 + cols, 0)
        picked = np.minimum(seen, c.index_topk).sum(1)  # a row's pairs
        read = np.minimum(picked, start + n)  # keys it cannot but read
        one = n == 1
        visible = int(seen.sum())
        counts = {
            "dsa_keys_visible": visible * L,
            "dsa_keys_selected": int(picked.sum()) * L,
            "dsa_selected_single": int(picked[one].sum()) * L,
            "dsa_selected_prefill": int(picked[~one].sum()) * L,
            "latent_keys_single": int(read[one].sum()) * L,
            "latent_keys_prefill": int(read[~one].sum()) * L,
            "dsa_index_pairs": visible * Lf,
            "dsa_index_keys": int((start + n).sum()) * Lf,
            "latent_blocks_used": self.total_blocks - self.free_blocks,
            "moe_assignments": int(n.sum()) * c.moe_top_k * self._moe_layers}
        if self._moe_share:
            counts["moe_assignments_local"] = None  # behind the step's tokens
        if mixed:
            counts["attn_q_slots"] = int(
                (-(-n[~one] // TILE_Q) * TILE_Q).sum() + one.sum())
        return counts

    def _count_linear_latent(self, start: "np.ndarray", n: "np.ndarray",
                             mixed: bool) -> Dict[str, Any]:
        """A step of a model of KDA layers beside latent attention.  Over
        rows: the slots taken, the rows that began from zeros, the tokens
        through the KDA layers and the state bytes read and written (all KDA
        layers); summed over rows and latent layers: the keys its queries
        have to READ (a row's whole context once, by the path that attends:
        ``_single`` the rows of one token, ``_prefill`` the rows of two and
        more) and the (query, key) pairs it multiplies; the blocks the
        latent pool has out; the expert assignments the routed layers make
        (the local ones come back with the step: ``_split_stats``); of a
        mixed step what the chunked form walks (the rows of two tokens and
        more, their tokens, their pieces of a chunk) and the query slots of
        the prefill tiles."""
        c = self.model_cfg
        start, n = _live_rows(start, n)
        La = self.caches["latent"].shape[0]
        one = n == 1
        read = start + n  # a row's keys up to its newest token
        # a query at p sees p + 1 keys: n queries from start on
        pairs = n * (start + 1) + n * (n - 1) // 2
        counts = {
            "state_slots_used": self.total_state_slots
            - self.free_state_slots,
            "state_rows_started": int((start == 0).sum()),
            "kda_tokens": int(n.sum()),
            "kda_state_bytes": len(n) * self._state_row_bytes,
            "latent_keys_read": int(read.sum()) * La,
            "latent_keys_single": int(read[one].sum()) * La,
            "latent_keys_prefill": int(read[~one].sum()) * La,
            "latent_query_keys": int(pairs.sum()) * La,
            "blocks_used_latent": self.total_blocks - self.free_blocks,
            "moe_assignments": int(n.sum()) * c.moe_top_k * self._moe_layers}
        if self._moe_share:
            counts["moe_assignments_local"] = None  # behind the step's tokens
        if mixed:
            many = n[~one]
            counts.update(
                kda_scan_rows=len(many), kda_scan_tokens=int(many.sum()),
                kda_scan_pieces=int((-(-many // c.kda_chunk_size)).sum()),
                attn_q_slots=int((-(-many // TILE_Q) * TILE_Q).sum()
                                 + one.sum()))
        return counts

    def _count_eva(self, start: "np.ndarray", n: "np.ndarray", mixed: bool
                   ) -> Dict[str, Any]:
        """An EVA model's step, summed over rows and layers.  The keys its
        queries have to READ: of the row's window up to its newest token
        (``eva_window_keys``) and one summary a chunk of every window the row
        has closed (``eva_summary_keys``), beside what full attention would
        read (``eva_keys_full``: the row's whole context); the (query, key)
        pairs it multiplies (``eva_query_keys``: a query at ``p`` sees ``p %
        window + 1`` keys and ``p // window`` windows' summaries).  Over rows
        alone: the windows this step completes and the summaries a layer
        writes for them; the blocks both pools have out; of a mixed step the
        query slots of its tiles."""
        start, n = _live_rows(start, n)
        L, W, per = self.model_cfg.num_layers, self._window, self._per_window
        last = start + n - 1  # a row's tokens lie in one window
        cols = np.arange(int(n.max(initial=0)))[None]
        pos = np.where(cols < n[:, None], start[:, None] + cols, -1)
        closed = int(((start + n) % W == 0).sum())
        used = [m.allocator.num_blocks - m.allocator.free_blocks
                for m in self._managers]
        counts = {
            "eva_window_keys": int((last % W + 1).sum()) * L,
            "eva_summary_keys": int((last // W * per).sum()) * L,
            "eva_keys_full": int((last + 1).sum()) * L,
            "eva_query_keys": int(np.where(
                pos >= 0, pos % W + 1 + pos // W * per, 0).sum()) * L,
            "eva_windows_closed": closed, "eva_chunks_written": closed * per,
            "blocks_used_summary": used[0], "blocks_used_window": used[1]}
        if mixed:
            counts["attn_q_slots"] = int(self._attn_tiles.slots(n).sum())
        return counts

    def _row_temps(self, temperature: float) -> "np.ndarray":
        """Effective per-row temperature vector: rows whose request pinned a
        temperature keep it; rows that didn't (temp < 0) inherit the
        step-level scalar."""
        t = self.table
        return np.where(t.temp >= 0.0, t.temp,
                        np.float32(temperature)).astype(np.float32)

    def _decode_fields(self, temperature: float) -> Dict[str, "np.ndarray"]:
        """The decode step's host inputs off the SoA table, by the names of
        its layout's fields (padded static shapes; inactive rows carry
        zeros, a free row's and a row's that ended with the token under
        way)."""
        t = self.table
        fields = {"token_ids": t.next_tok * t.active,
                  "position_ids": t.ctx * t.active,
                  "context_lens": (t.ctx + 1) * t.active,
                  "temps": self._row_temps(temperature), "seeds": t.seed,
                  "block_tables": t.block_tables}
        if t.win_tables is not None:
            fields["win_tables"] = t.win_tables
        if self.adapter_stack is not None:
            fields["row_adapter"] = t.adapter
        return fields

    def _pack_decode(self, temperature: float) -> "np.ndarray":
        """``_decode_fields`` in their one buffer."""
        buf = self._decode_layout.new()
        fields = self._decode_fields(temperature)
        for name, view in self._decode_layout.views(buf).items():
            view[...] = fields[name]
        return buf

    def _to_device(self, layout: StepLayout, buf: "np.ndarray",
                   out: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
        """The step's one host-to-device copy: ``buf`` goes to the program
        that takes it apart on the device as it is, and the call makes the
        copy (one trip into the runtime, not two) → its fields as device
        arrays.  ``out``: what the step program before returned, where this
        step is called behind it: the unpack program takes from there, on the
        device, the token ids the buffer only points at (``_promise``).  A
        buffer that points at nothing is handed the latest program's output
        all the same (``_out``), fetched long ago or not: the unpack program
        then has ONE shape a layout from an engine's second step on, whatever
        the traffic later makes of who is called behind whom."""
        copies, nbytes = self._h2d or (0, 0)
        self._h2d = (copies + 1, nbytes + buf.nbytes)
        out = self._out if out is None else out
        unpack = build_unpack(layout)
        return unpack(buf) if out is None else unpack(buf, out)

    @staticmethod
    def _tables(fields: Dict[str, jax.Array]):
        """A step program's ``block_tables``: a table a pool."""
        if "win_tables" in fields:
            return fields["block_tables"], fields["win_tables"]
        return fields["block_tables"]

    def _stage_next(self, temperature: float, sub: Dict[str, Any]) -> None:
        """At the end of a step, once its bookkeeping is done: where the next
        step will be a decode step unless the caller changes the table first
        (nothing waits, nothing prefills, no speculation), make that step's
        one copy NOW, as ``_call_decode`` would at its head: open the
        windowed pool's next blocks, pack the buffer, hand it to the unpack
        program.  ``step`` then returns to a broker that wakes a streaming
        thread a row, and the next step's first trip into the runtime is its
        program's call, not a small copy that lets go of the interpreter lock
        and queues the engine thread behind all of them (PERF.md section 5).
        The copy is counted with what is staged, for the step that runs on
        it; this step's own count is on its span already."""
        if (self.waiting or not self.running or self._prefilling
                or self._spec_fwd is not None or self._ahead is not None):
            return  # (a step dispatched ahead has made its copy)
        sp = tracer.begin("engine/stage", **sub)
        if self._growing:
            self._window_open_blocks()
        buf = self._pack_decode(temperature)
        self._h2d = None
        self._staged = (self._decode_layout.views(buf),
                        self._to_device(self._decode_layout, buf), self._h2d)
        tracer.end(sp)

    def _take_staged(self, temperature: Optional[float] = None):
        """What the step before staged, off the engine, if the step under
        way is a decode step (it passes its ``temperature``) and the staged
        buffer is byte for byte the one it would pack; else None, with the
        bytes dropped unused counted for the span.  ``put``, ``cancel``, a
        stop token's ``_finish``, another ``temperature`` or adapter row, a
        caller's burst: all of it shows in the buffer, so no path has a
        counter to remember.  Compared a field at a time as ``bytes``, which
        holds the interpreter lock throughout: packing a second buffer does
        not (``np.zeros`` and an assignment let go of it from 500 elements
        on), and the engine thread would queue behind every streaming thread
        the broker just woke, the very wait the staging takes out."""
        staged, self._staged = self._staged, None
        if staged is None:
            return None
        if temperature is not None and all(
                staged[0][name].tobytes() == np.ascontiguousarray(
                    x, staged[0][name].dtype).tobytes()
                for name, x in self._decode_fields(temperature).items()):
            return staged
        self._stage_dropped = self._decode_layout.size * 4
        return None

    def _decode_inputs(self, temperature: float) -> Dict[str, jax.Array]:
        """The decode step's fields on the device: the ones the step before
        staged where they are still what this step needs, else a copy made
        now (the first decode step after an admission, a cancel, a stop
        token, and every caller that is not the broker)."""
        staged = self._take_staged(temperature)
        if staged is None:
            self._stage_use = "fresh"
            return self._to_device(self._decode_layout,
                                   self._pack_decode(temperature))
        self._stage_use = "used"
        _, fields, self._h2d = staged
        return fields

    def _step_rng(self, rng: Optional[jax.Array]) -> jax.Array:
        """The step's key: the caller's, or the next of the engine's stream
        (``rng_state -> (rng_state', step_key)``, the key carried on the
        device from step to step).  A decode or mixed step has had it split
        off while the step before was on the device (``_split_ahead``)."""
        if rng is not None:
            return rng
        if self._step_key is None:
            self._split_ahead()
        rng, self._step_key = self._step_key, None
        return rng

    def _split_ahead(self) -> None:
        """Split the next step's key off the engine's, unless it is held
        already: called once a step's program is under way, so host and
        device do it behind that program and the next step finds its key
        there.  The eager ``jax.random.split`` the engine always made (a
        program and two slices): jitted as one program it lowers threefry
        anew at every start, 0.45 s of set-up on the chip's host."""
        if self._step_key is None:
            self._rng, self._step_key = jax.random.split(self._rng)

    def _advance_rows(self, sel: "np.ndarray") -> "np.ndarray":
        """A burst's bookkeeping, vectorized.  ``sel``: (k, ns) new tokens
        for the active rows, into the rows' history, the last of them the
        next input; retires sequences whose budget is exhausted; returns the
        active row indices."""
        t = self.table
        rows = np.nonzero(t.active)[0]
        k = sel.shape[0]
        t.hist[rows[:, None],
               t.hist_len[rows][:, None] + np.arange(k)[None, :]] = sel.T
        t.hist_len[rows] += k
        t.next_tok[rows] = sel[-1]
        t.ctx[rows] += k
        t.gen[rows] += k
        for r in rows[t.gen[rows] >= t.budget[rows]]:
            self._finish(t.seq_at[int(r)])
        return rows

    def _next_kind(self) -> Optional[str]:
        """The kind of the step the engine would run now, by what the
        descriptors and the table say; None: there is nothing to run."""
        if self.waiting or self._prefilling:
            return "mixed"
        if not self.table.active.any():
            return None
        return "spec" if self._spec_fwd is not None else "decode"

    def _may_go_ahead(self, rng: Optional[jax.Array], behind: _Underway,
                      kind: Optional[str]) -> bool:
        """Whether the step of ``kind`` BEHIND the one whose program is under
        way (``behind``, advanced already) may be called before that one's
        tokens are fetched, by what the engine sees now.  Whose step it is:
        the caller leaves the keys to the engine (a key handed to ``step`` is
        one step's, and the next call's is not known yet), no speculation,
        and there is a step to run.  Whom it may keep waiting: a request put
        between the two calls finds the step called and joins the one after,
        so a step goes ahead only where an arrival could not have joined it
        anyway: every sequence slot is taken (the rows that end with the
        token under way hold theirs until its fetch) or the waiting queue's
        head is one the latest ``_schedule`` had to leave there; and, as ever
        since two steps were in flight, a decode step behind a decode step
        with nothing waiting, which costs an arrival one decode step.  Such a
        step needs of its predecessor only the token ids, which never leave
        the device (``_promise``); chunk tokens, positions, context lengths,
        tables, slots, temperatures and seeds the host knows now (a row's
        whole budget of blocks was set aside at admission)."""
        if rng is not None or self._spec_fwd is not None or kind is None:
            return False
        if behind.kind == kind == "decode":
            return True
        return (len(self.running) + len(self.waiting) >= self.cfg.max_seqs
                or bool(self.waiting)
                and self.waiting[0].uid == self._kept_out)

    def _program_called(self, sp_dispatch, sub: Dict[str, Any],
                        behind: Optional[_Underway] = None) -> None:
        """The program of step ``sub["step"]`` is being called, inside its
        ``engine/dispatch`` span (None with tracing off: nothing is kept and
        nothing asked).  What only the engine knows, and only now, is kept by
        the step for the program's ``engine/program`` span, which
        ``_program_fetched`` records: whether the device still held a program
        of this engine (``behind``: the predecessor, called and unfetched) and,
        if so, whether that one had finished already (``late``: one
        non-blocking ``is_ready``; the device ran dry inside ``step`` before
        this call reached it); if not, since when it held none
        (``unqueued_ms``: from the latest fetch's return to this call) and
        whose time that was, in three parts that add up to it, every end a
        ``t_start`` or ``t_end`` some span holds already: ``unqueued_post_ms``
        (the fetch's return to its step's: ``engine/finish``, ``stage``, the
        span's close), ``unqueued_turn_ms`` (that step's return to this
        step's entry: the caller's) and ``unqueued_pre_ms`` (this step's entry
        to the call: ``engine/schedule``, ``build``, ``h2d``).  Where the
        engine does not know the latest fetch (``_fetched``) the four are left
        out."""
        if sp_dispatch is None:
            return
        called = sp_dispatch.t_start
        if behind is not None:
            attrs = {**sub, "behind": 1, "late": int(behind.out.is_ready()),
                     **_NOTHING_UNQUEUED}
        else:
            attrs = {**sub, "behind": 0}
            if self._fetched is not None and self._step_sp is not None:
                sp_wait, sp_step = self._fetched  # (of a step that returned)
                fetched, returned = sp_wait.t_end, sp_step.t_end
                entry = self._step_sp.t_start
                attrs.update(zip(_UNQUEUED, (
                    (called - fetched) * 1e3, (returned - fetched) * 1e3,
                    (entry - returned) * 1e3, (called - entry) * 1e3)))
        self._calls[sub["step"]] = (called, attrs)

    def _program_fetched(self, sub: Dict[str, Any], sp_wait) -> None:
        """The tokens of the program of step ``sub["step"]`` have reached the
        host, inside the ``engine/wait`` span just closed: ONE retroactive
        ``engine/program`` span from the ``t_start`` of its
        ``engine/dispatch`` to the ``t_end`` of this span, with what
        ``_program_called`` kept and ``fetch_wait_ms``, this span's length
        (about 0: the device had finished before the host asked, and the host
        sets the pace; a step's length: the device does); its parent is the
        step whose tokens it made, this one.  Ring-only, like
        every retroactive span: two programs in flight overlap without
        nesting, so in the chrome export they lie on two tracks of their own
        (``<the stepping thread>/device-queue/0`` and ``/1``, by the step's
        parity), beside that thread's."""
        call = self._calls.pop(sub["step"], None)
        if sp_wait is None or self._step_sp is None:  # (tracing off, or on
            self._fetched = None                      # since this step began)
            return
        self._fetched = (sp_wait, self._step_sp)
        if call is not None:
            t_start, attrs = call
            attrs["fetch_wait_ms"] = (sp_wait.t_end - sp_wait.t_start) * 1e3
            tracer.add_span("engine/program", t_start, sp_wait.t_end,
                            parent_id=self._step_sp.span_id, attrs=attrs,
                            thread=sp_wait.thread
                            + _QUEUE_TRACKS[sub["step"] % 2])

    def _programs_dropped(self, sp=None) -> None:
        """Every program called and not fetched leaves the ring as an
        ``engine/program`` marked ``error``, so that the ring never holds a
        call without an end: it ends where the failed ``engine/step`` ``sp``
        did, or now (``close``: no call will fetch it).  What is queued
        behind a failed step the engine no longer knows."""
        t_end = time.monotonic() if sp is None else sp.t_end
        stepped_by = threading.current_thread().name
        for step, (t_start, attrs) in sorted(self._calls.items()):
            tracer.add_span("engine/program", t_start, t_end,
                            parent_id=getattr(sp, "span_id", None),
                            attrs={**attrs, "error": True},
                            thread=stepped_by + _QUEUE_TRACKS[step % 2])
        self._calls.clear()
        self._fetched = None

    # -- a step in two halves: CALL (``_call``: schedule, build, pack, the
    # one copy, the program; a mixed program's sampler with ``_sample``) and
    # FETCH (``_fetch``: wait for the tokens, record them, finish what
    # ended), with ``_advance`` between them: what the step's token makes of
    # the descriptors and the table that is known without the token.  Used
    # the same way whether the successor is called between the halves or not
    # (``_step_impl``)

    @staticmethod
    def _promise(src):
        """What stands for a token that is still on the device, wherever its
        id would stand (a descriptor's ``tokens``, the table's ``hist`` and
        ``next_tok``, and so a step's buffer): where in the output of the
        program under way it lies, as a negative id (``-1``: its first
        entry).  The unpack program of the step called behind that program
        reads it there (``programs.build_unpack``); ``_fetch`` writes the id
        in its place.  It lives inside one call of ``step``: between two
        calls every token the host holds is an id."""
        return -1 - src

    def _call(self, kind: str, temperature: float, rng: Optional[jax.Array],
              sub: Dict[str, Any], behind: Optional[_Underway] = None
              ) -> Optional[Tuple[_Underway, Any, Optional[float]]]:
        """Call the program of the step of ``kind`` that the descriptors and
        the table describe → the program under way, and where its step's
        split opens: its ``engine/dispatch`` span and the thread's CPU clock
        there; None where a mixed step finds nothing to run.  ``behind``: the
        program before it, still under way and advanced: the token ids it
        owes are read from what it returned, on the device.  The engine's
        ``_h2d`` is left as it was: the copy made here is the returned
        program's."""
        held, self._h2d = self._h2d, None
        try:
            call = self._call_decode if kind == "decode" else self._call_mixed
            return call(temperature, rng, sub, behind)
        finally:
            self._h2d = held

    def _call_decode(self, temperature: float, rng: Optional[jax.Array],
                     sub: Dict[str, Any], behind: Optional[_Underway]):
        """``_call`` of a decode step: its inputs ARE the table's arrays
        (``table.ctx`` / ``active`` / ``seq_at`` are that step's own when
        ``_decode_fwd`` is called: the benchmark's taps read them there).
        Called behind nothing it asks for the staged buffer first."""
        t = self.table
        sp = tracer.begin("engine/h2d", **sub)
        if self._growing:
            self._window_open_blocks()
        counts = self._count(t.ctx, t.active, False)
        if behind is None:
            # staged by the step before (the span then holds the check alone)
            # or copied here
            f = self._decode_inputs(temperature)
        else:
            f = self._to_device(self._decode_layout,
                                self._pack_decode(temperature), behind.out)
        args = (f["token_ids"], f["position_ids"], self._tables(f),
                f["context_lens"], f["temps"], self._step_rng(rng),
                f["seeds"])
        if self.adapter_stack is not None:
            args += (self.adapter_stack, f["row_adapter"])
        tracer.end(sp)
        cpu_called = _thread_cpu()
        sp_dispatch = tracer.begin("engine/dispatch", **sub)
        self._program_called(sp_dispatch, sub, behind)
        out, self.caches = self._decode_fwd(self.params, self.caches, *args)
        self._out = out
        self._split_ahead()  # the next step's key, behind this program
        tracer.end(sp_dispatch)
        rows = np.nonzero(t.active)[0]
        return (_Underway("decode", out, counts, self._h2d, len(rows),
                          rows=rows, uids=t.uid[rows]), sp_dispatch,
                cpu_called)

    def _call_mixed(self, temperature: float, rng: Optional[jax.Array],
                    sub: Dict[str, Any], behind: Optional[_Underway]):
        """``_call`` of a mixed step: Dynamic SplitFuse over the descriptors
        (at the call of ``_fwd`` the picks' descriptors read as that step's
        own: ``seen_tokens`` where its chunk begins, ``cur_len`` with the
        place of a token still under way).  The sampler is not enqueued here
        (``_sample``): ``rng`` is its step's, asked for there."""
        sp = tracer.begin("engine/schedule", **sub)
        self._flush_table()
        picks = self._schedule()
        tracer.end(sp)
        if not picks:
            if self.table.active.any():
                raise RuntimeError(
                    "scheduler made no progress with running sequences — "
                    "KV reservation invariant violated (bug)")
            return None
        if self._spec_fwd is not None:
            self.spec_fallback += 1  # prefill/mixed step: no speculation
        sp = tracer.begin("engine/build", **sub)
        batch = self.builder.build(picks)
        tracer.end(sp)
        sp = tracer.begin("engine/h2d", **sub)
        f = self._to_device(self.builder.layout, batch.packed,
                            None if behind is None else behind.out)
        counts = self._count(batch.chunk_start, batch.chunk_len, True)
        batch_args = (
            f["token_ids"], f["position_ids"], f["seq_index"],
            self._tables(f), f["context_lens"], f["logits_rows"],
            f["chunk_start"], f["chunk_len"])
        if batch.state_slots is not None:  # a model with state layers:
            # behind the two adapter arguments, which such a model never has
            batch_args += (None, None, f["state_slots"])
        ad_args = ()
        if self.adapter_stack is not None:
            # the batch's rows are in picks order (seq_index indexes into
            # the pick rows, not the SoA table): the builder's slot vector
            ad_args = (self.adapter_stack, f["row_adapter"])
        tracer.end(sp)
        cpu_called = _thread_cpu()
        sp_dispatch = tracer.begin("engine/dispatch", **sub)
        self._program_called(sp_dispatch, sub, behind)
        logits, hidden, self.caches, *rest = self._fwd(
            self.params, self.caches, *batch_args, *ad_args)
        if self.cfg.spec_mode == "draft":
            # mirror every target KV write into the draft cache (same block
            # tables, its own pool array) so the draft scan can decode from
            # position ctx without ever re-prefilling
            _, _, self._draft_caches, *_ = self._draft_fwd(
                self.draft_params, self._draft_caches, *batch_args)
        tracer.end(sp_dispatch)
        # per-row selection mirrors the jitted decode path: pick rows carry
        # their request's pinned temperature/seed (else the temperature of
        # the call that called the program), padding rows stay greedy
        temps = np.zeros(self.cfg.max_seqs, np.float32)
        seeds = np.zeros(self.cfg.max_seqs, np.int32)
        for row, (seq, _) in enumerate(picks):
            temps[row] = (temperature if seq.temperature is None
                          else seq.temperature)
            seeds[row] = np.int32(np.uint32(seq.seed & 0xFFFFFFFF))
        return (_Underway(
            "mixed", None, counts, self._h2d, sum(n for _, n in picks),
            picks=picks, hidden=hidden,
            # (an MoE model's step stats ride fourth)
            sampler=(logits, temps, seeds, rest[0] if rest else None)),
            sp_dispatch, cpu_called)

    def _sample(self, run: _Underway, rng: Optional[jax.Array],
                sub: Dict[str, Any]) -> None:
        """Enqueue a mixed program's eager sampler behind it (a dozen small
        programs, 15 ms and more of the engine thread): right after the call
        where the step was called at its own turn, and at the entry of the
        step that takes it where it was called behind another, so that the
        predecessor's fetch does not stand behind it and its own successor's
        unpack program still finds ``out``."""
        sp = tracer.begin("engine/sample", **sub)
        (logits, temps, seeds, moe_stats), run.sampler = run.sampler, None
        run.out = self._out = _with_stats(
            sample_rows(tfm.next_token_logits(logits, self.model_cfg),
                        jnp.asarray(temps), self._step_rng(rng),
                        jnp.asarray(seeds)), moe_stats)
        self._split_ahead()  # the next step's key, behind this program
        tracer.end(sp)

    def _advance(self, run: _Underway) -> None:
        """What the token ``run`` computes makes of the descriptors and the
        table that is known without it, and who gets one (``_Underway``): a
        decode row's ``ctx`` / ``gen``, a pick's ``seen_tokens``, a prefill
        that ended with the step (``in_decode``, ``_prefilling``), the
        window's trim; the token's place is kept by a ``_promise``.  A
        sequence that ends with the token leaves the steps to come here (its
        row goes inactive) and is finished at the fetch, with the token: no
        block and no slot is given back early.  A sequence retired since the
        call (a ``cancel``, a stop token) is passed over."""
        t = self.table
        if run.kind == "decode":
            rows = run.rows[t.uid[run.rows] == run.uids]
            run.dropped = len(run.rows) - len(rows)
            t.ctx[rows] += 1
            t.gen[rows] += 1
            run.rows, run.uids, run.src = rows, t.uid[rows], rows
            run.pos, run.at = t.ctx[rows].copy(), t.hist_len[rows].copy()
            t.hist[rows, run.at] = t.next_tok[rows] = self._promise(rows)
            t.hist_len[rows] += 1
            run.ends = t.gen[rows] >= t.budget[rows]
            run.tokens = len(rows)
            t.active[rows[run.ends]] = False
            if self._windowed is not None:
                self._window_trim_rows()
        else:
            rows, src, ends = [], [], []
            for i, (seq, n) in enumerate(run.picks):
                last = seq.seen_tokens + n >= seq.cur_len  # gets a token
                if seq.done:
                    run.dropped += last
                    continue
                seq.seen_tokens += n
                if self._windowed is not None:  # what fell behind the window
                    self._windowed.trim(seq, seq.seen_tokens)
                if last:
                    seq.tokens.append(self._promise(i))
                    seq.generated += 1
                    if not seq.in_decode:
                        seq.in_decode = True
                        self._prefilling -= 1
                    rows.append(t.row_of[seq.uid])
                    src.append(i)
                    ends.append(seq.generated >= seq.max_new_tokens)
                t.sync(seq)
            run.rows = np.array(rows, np.int64)
            run.src, run.ends = np.array(src, np.int64), np.array(ends, bool)
            run.uids, run.pos = t.uid[run.rows], t.ctx[run.rows].copy()
            run.at = np.full(len(rows), t.hist.shape[1])  # the descriptors
            t.active[run.rows[run.ends]] = False
        self.ahead_dropped += run.dropped

    def _fetch(self, run: _Underway, sub: Dict[str, Any]
               ) -> Tuple[Dict[int, List[int]], Any, Optional[float]]:
        """The tokens of ``run`` (advanced) reach the host → the tokens by
        uid, the ``engine/wait`` span and the thread's CPU clock where it
        closed.  Each token goes where its promise stands: the table's
        ``next_tok`` and its history, or the descriptor where a mixed step
        has flushed that since; then the sequences that end with it are
        finished."""
        t = self.table
        sp_wait = tracer.begin("engine/wait", **sub)
        sampled = self._split_stats(np.asarray(run.out))
        hidden = (np.asarray(run.hidden) if run.hidden is not None
                  and self.cfg.spec_mode == "self_draft" else None)
        tracer.end(sp_wait)
        cpu_fetched = _thread_cpu()
        self._program_fetched(sub, sp_wait)
        sp = tracer.begin("engine/finish", **sub)
        rows, at, src, ends = run.rows, run.at, run.src, run.ends
        toks = sampled[src].astype(np.int32)
        t.next_tok[rows] = toks
        held = t.hist_len[rows] > at  # (a flush leaves no history)
        t.hist[rows[held], at[held]] = toks[held]
        for r, p, tok in zip(rows[~held], run.pos[~held], toks[~held]):
            t.seq_at[int(r)].tokens[int(p)] = int(tok)
        out = {int(u): [int(tok)] for u, tok in zip(run.uids, toks)}
        if hidden is not None:
            # hidden at the position whose lm head produced the token — the
            # state the self-draft heads will propose from
            self._spec_hidden[rows[~ends]] = hidden[src[~ends]]
        for r in rows[ends]:
            self._finish(t.seq_at[int(r)])
        tracer.end(sp)
        return out, sp_wait, cpu_fetched

    def _split_stats(self, fetched: "np.ndarray") -> "np.ndarray":
        """The tokens of a step's one fetch; an MoE model's two stats behind
        them are kept for the step's span."""
        if not self._moe_rows:
            return fetched
        n = self.cfg.max_seqs
        self._moe_stats = (float(fetched[n]) / self._moe_layers,
                           int(fetched[n + 1]))
        if "moe_assignments_local" in self._step_counts:
            self._step_counts["moe_assignments_local"] = int(fetched[n + 2])
        return fetched[:n]

    def _spec_decode_step(self, temperature: float, rng: Optional[jax.Array],
                          sub: Dict[str, Any]) -> _StepResult:
        """Steady-state SPECULATIVE decode: one jitted propose→verify→accept
        program emits 1..k+1 tokens per sequence.  The host reads back only
        the emitted tokens + accept lengths; rejected-suffix KV needs no
        device rollback (stale entries are masked by context_lens and
        overwritten next step), so prefix-cache refcounts never move."""
        self.fast_steps += 1
        self.spec_steps += 1
        t = self.table
        self_draft = self.cfg.spec_mode == "self_draft"
        sp = tracer.begin("engine/h2d", **sub)
        rng = self._step_rng(rng)
        next_tok, ctx, block_tables, _ = self._table_inputs()
        limit = jnp.asarray(t.limit)
        temps = jnp.asarray(self._row_temps(temperature))
        seeds = jnp.asarray(t.seed)
        hidden = jnp.asarray(self._spec_hidden) if self_draft else None
        tracer.end(sp)
        hidden_np = None
        cpu_called = _thread_cpu()
        sp_dispatch = tracer.begin("engine/dispatch", **sub)
        self._program_called(sp_dispatch, sub)
        if self_draft:
            emitted, alen, new_hidden, self.caches = self._spec_fwd(
                self.params, self.spec_heads, self.caches, next_tok, ctx,
                block_tables, limit, hidden, rng,
                temps, seeds, *self._adapter_args())
        else:
            emitted, alen, self.caches, self._draft_caches = self._spec_fwd(
                self.params, self.draft_params, self.caches,
                self._draft_caches, next_tok, ctx, block_tables, limit, rng,
                temps, seeds)
        tracer.end(sp_dispatch)
        sp_wait = tracer.begin("engine/wait", **sub)
        if self_draft:
            hidden_np = np.asarray(new_hidden)
        emitted = np.asarray(emitted)  # (max_seqs, k+1)
        alen = np.asarray(alen)
        tracer.end(sp_wait)
        cpu_fetched = _thread_cpu()
        self._program_fetched(sub, sp_wait)
        sp = tracer.begin("engine/finish", **sub)
        out: Dict[int, List[int]] = {}
        k = self.cfg.spec_k
        active = np.nonzero(t.active)[0]
        # per-row Python loop: rows advance by DIFFERENT amounts (accept
        # length), so the vectorized _advance_rows contract doesn't apply;
        # the loop body is a handful of scalar ops per ACTIVE row only
        for r in active:
            r = int(r)
            seq = t.seq_at[r]
            # never emit past the request budget: the verify forward parks
            # (and the attention clamp ignores) positions >= t.limit, so
            # tokens beyond the clamp were never legally produced
            take = int(min(alen[r] + 1, t.budget[r] - t.gen[r]))
            toks = emitted[r, :take].astype(np.int32)
            t.hist[r, t.hist_len[r]:t.hist_len[r] + take] = toks
            t.hist_len[r] += take
            t.next_tok[r] = toks[-1]
            t.ctx[r] += take
            t.gen[r] += take
            if hidden_np is not None:
                self._spec_hidden[r] = hidden_np[r]
            out[seq.uid] = toks.tolist()
            self.spec_proposed += k
            self.spec_accepted += int(min(int(alen[r]), take))
            self.spec_emitted += take
            if t.gen[r] >= t.budget[r]:
                self._finish(seq)
        tracer.end(sp)
        return out, len(active), (sp_dispatch, cpu_called, sp_wait,
                                  cpu_fetched)

    def step(self, temperature: float = 0.0, rng: Optional[jax.Array] = None
             ) -> Dict[int, List[int]]:
        """One continuous-batching step → {uid: new_tokens} for sequences
        that produced tokens (prefill-finished or decode).  Non-speculative
        paths emit exactly one token per sequence; speculative steady-state
        steps emit 1..spec_k+1.

        Instrumentation is host-side only (spans + a flight-recorder append
        around the untouched step body), so tracing provably changes no
        compiled program.  ``engine/step`` has a child span per phase
        (``engine/schedule``, ``build``, ``h2d``, ``dispatch``, ``sample``,
        ``wait``, ``finish``), each carrying ``kind`` and ``step``, and
        itself ends with ``tokens``, ``budget`` and, where it reached the
        device, its own split (``_host_split``): ``pre_ms`` (entry to the
        call of the step's program: schedule, build, pack, the unpack
        program's call), ``device_ms`` (first enqueue to fetch: host clocks,
        the device *presumed* busy between them), ``post_ms`` (the fetch's
        return to ``step``'s), and the engine thread's CPU time over the
        first and the last (``pre_cpu_ms``, ``post_cpu_ms``), so a reader
        needs no join: the three add up to the span, and wall less CPU is
        what the thread waited for.  A decode step says whose copy fed its
        program (``staged``: ``"used"``, the one the step before staged, or
        ``"fresh"``, made here; ``h2d_copies`` / ``h2d_bytes`` count it either
        way), and any step that found staged fields it could not use
        ``stage_discarded`` 1 with their ``stage_bytes``.  The unpack program
        is enqueued inside ``engine/stage`` of the step before or inside
        ``engine/h2d``, before ``device_ms`` opens, and that is still the
        right start: it is a dozen slices of one small buffer, and once it
        is done the device waits for the step's program like before it, so
        the wait the host causes ends where ``engine/dispatch`` opens.

        ``device_ms`` / ``pre_ms`` / ``post_ms`` describe a STEP, not a
        program, and misread a step whose program was under way: a program of
        either kind is called in one step, behind that step's own, and fetched
        in the next (``_step_impl``), so such a step opens ``device_ms`` at
        its entry and its successor's schedule, pack and call lie inside it
        (a mixed step's opens with its own ``engine/sample``; its
        ``engine/schedule``, ``build``, ``h2d`` and ``dispatch`` lie in the
        step before, under its own ``kind`` and ``step``).  Every step that
        ran such a program says ``ahead`` (it found its program under way),
        ``ahead_next`` (it called its successor before its own fetch) and
        ``ahead_dropped`` (the rows retired between the two calls, whose token
        nobody gets); ``kind`` is the fetched program's.  The
        unit the device is occupied by is a program call, and
        ``engine/program`` stands for one (``_program_called``,
        ``_program_fetched``): one retroactive span a call of a step program,
        from the ``t_start`` of its ``engine/dispatch`` to the ``t_end`` of
        its ``engine/wait``, with ``kind`` and ``step`` of the step whose
        tokens it makes, ``behind``, ``late``, ``unqueued_ms`` and its three
        parts, and ``fetch_wait_ms``; a program nobody fetched (a failed
        step, ``close``) ends as one marked ``error``.  Read it, not the
        split, for when the device had nothing queued and whose time that
        was."""
        # a program under way is this call's step, whatever the engine has
        # come to since (``_step_impl``); with nothing to run it is a mixed
        # step that schedules nothing
        kind = (self._ahead.kind if self._ahead is not None
                else self._next_kind() or "mixed")
        running, waiting = self.num_running, len(self.waiting)
        prop0, acc0 = self.spec_proposed, self.spec_accepted
        self.steps += 1
        sub = {"kind": kind, "step": self.steps}  # on the step and its children
        self._moe_stats = None
        self._step_counts = None
        self._h2d = None
        self._stage_use = None
        self._stage_dropped = 0
        self._ahead_flags = None
        if kind != "decode":  # what was staged for a decode step: dropped
            self._take_staged()
        t0 = time.monotonic()
        sp = tracer.begin("engine/step", running=running, waiting=waiting,
                          prefilling=self._prefilling, **sub)
        self._step_sp = sp
        cpu_entry = _thread_cpu()
        try:
            out, tokens, dispatched = self._step_impl(temperature, rng, sub)
        except Exception:
            tracer.end(sp, error=True)
            if self._calls:  # called in this step or the one before
                self._programs_dropped(sp)
            raise
        emitted = sum(len(v) for v in out.values())
        attrs = {"emitted": emitted, "tokens": tokens,
                 "budget": self.cfg.max_tokens_per_step}
        if kind == "spec":
            attrs["proposed"] = self.spec_proposed - prop0
            attrs["accepted"] = self.spec_accepted - acc0
        if self._moe_stats is not None:  # an MoE model's step ran the device
            attrs["moe_rows"], attrs["moe_rows_padded"] = self._moe_rows[kind]
            attrs["moe_experts_hit"], attrs["moe_rows_max"] = self._moe_stats
        if self._h2d is not None:  # the copies that fed the step's program
            attrs["h2d_copies"], attrs["h2d_bytes"] = self._h2d
        if self._stage_use is not None:  # a decode step: whose copy it ran on
            attrs["staged"] = self._stage_use
        if self._ahead_flags is not None:  # it ran a program that may go ahead
            (attrs["ahead"], attrs["ahead_next"],
             attrs["ahead_dropped"]) = self._ahead_flags
        if self._stage_dropped:  # staged for this step and not what it needs
            attrs["stage_discarded"] = 1
            attrs["stage_bytes"] = self._stage_dropped
        if self._step_counts:  # the kind's, of a step that ran the device
            attrs.update(self._step_counts)
        if "trimmed" in attrs:  # a windowed model's: what the step freed
            m = self._windowed
            attrs["window_blocks_freed"] = m.trimmed - attrs.pop("trimmed")
            used = [k.allocator.num_blocks - k.allocator.free_blocks
                    for k in self._managers]
            attrs["blocks_used_global"] = used[0] if m is not self.kv else 0
            attrs["blocks_used_window"] = used[-1]
        if dispatched is not None:
            # the next decode step's copy, inside this step's ``post_ms``;
            # after the counters above, which are this step's
            self._stage_next(temperature, sub)
        if sp is not None and dispatched is not None:  # it reached the device
            if dispatched[0] is None:  # its program was under way at its entry
                dispatched = (sp, cpu_entry) + dispatched[2:]
            # last: ``post_ms`` runs to here
            attrs.update(_host_split(sp, cpu_entry, *dispatched))
        tracer.end(sp, **attrs)
        recorder.record_step({
            "kind": kind, "t_start": t0, "t_end": time.monotonic(),
            "running": running, "waiting": waiting,
            "prefilling": self._prefilling, "emitted": emitted, **(
                {"proposed": attrs["proposed"], "accepted": attrs["accepted"]}
                if kind == "spec" else {})})
        return out

    def _step_impl(self, temperature: float, rng: Optional[jax.Array],
                   sub: Dict[str, Any]) -> _StepResult:
        """The step body.  ``sub`` is what each of its spans carries.

        Two steps in flight, of either kind: the step's program is the one
        the call before called ahead, or is called here; it is advanced;
        then, where ``_may_go_ahead``, the NEXT step's program is called
        behind it, whatever its kind, and only then are this step's tokens
        fetched, recorded and returned.  The fetch's tail, the bookkeeping,
        the caller's turn and the next entry then run beside a program.  What
        happens to the engine between two calls (``put``, ``cancel``, a stop
        token) finds the program under way already: it is fetched whole by
        the next call, the tokens of rows retired since are dropped and
        counted, and a request put since joins the step after.  A steady-state
        decode step stays vectorized: its inputs ARE the table's arrays, and
        Python touches only the sequences that just completed."""
        kind = sub["kind"]
        if kind == "spec":
            return self._spec_decode_step(temperature, rng, sub)
        run, self._ahead = self._ahead, None
        found = run is not None
        if found:  # its split opens at the call's entry (``step``)
            sp_dispatch = cpu_called = None
        else:
            called = self._call(kind, temperature, rng, sub)
            if called is None:
                return {}, 0, None
            run, sp_dispatch, cpu_called = called
        if kind == "decode":  # steady state: the SoA path
            self.fast_steps += 1
            self.ahead_steps += found
            if found:
                self._stage_use = "ahead"
        else:
            self.mixed_ahead_steps += found
        self._h2d, self._step_counts = run.h2d, run.counts
        if run.out is None:
            self._sample(run, rng, sub)
        self._advance(run)
        nxt = self._next_kind()
        called = self._call(
            nxt, temperature, None, {"kind": nxt, "step": self.steps + 1},
            behind=run) if self._may_go_ahead(rng, run, nxt) else None
        if called is not None:
            self._ahead = called[0]
        out, sp_wait, cpu_fetched = self._fetch(run, sub)
        self._ahead_flags = (int(found), int(called is not None), run.dropped)
        return out, run.tokens, (sp_dispatch, cpu_called, sp_wait,
                                 cpu_fetched)

    def _burst_decode(self, k: int, temperature: float = 0.0,
                      rng: Optional[jax.Array] = None) -> None:
        """Decode ``k`` tokens for every running sequence in one jitted
        program (multi-token decode; host loop eliminated). Bookkeeping is
        vectorized over the SoA table (blocks were reserved at admission)."""
        if self._ahead is not None:
            raise RuntimeError("a decode step is under way: ``step`` takes "
                               "its tokens before a burst can run")
        if k not in self._multi_decode:
            self._multi_decode[k] = build_multi_decode_forward(
                self.model_cfg, self.cfg, k)
        t = self.table
        self._fetched = None  # no span stands for this program
        toks, self.caches = self._multi_decode[k](
            self.params, self.caches, *self._table_inputs(),
            self._step_rng(rng), jnp.asarray(self._row_temps(temperature)),
            jnp.asarray(t.seed), *self._adapter_args())
        toks = np.asarray(toks)  # (k, max_seqs)
        rows = np.nonzero(t.active)[0]
        self._advance_rows(toks[:, rows].astype(np.int32))

    def generate_all(self, temperature: float = 0.0, seed: int = 0,
                     max_steps: int = 10000, burst: int = 8
                     ) -> Dict[int, List[int]]:
        """Drive until every queued request completes.  Greedy decode uses
        ``burst``-token in-graph bursts when every running sequence is in
        decode with enough budget."""
        results: Dict[int, List[int]] = {}
        tracked = {s.uid: s for s in list(self.waiting)} | dict(self.running)
        rng = jax.random.PRNGKey(seed)
        for _ in range(max_steps):
            if not self.waiting and not self.running:
                break
            t = self.table
            # spec mode never bursts: the speculative step is already a
            # multi-token in-graph program with its own budget clamp
            # nor does a windowed pool: its tables are kept step by step
            # nor a kind whose every step counts
            steady = (burst > 1 and self._spec_fwd is None
                      and self._windowed is None and self.kind.bursts
                      and self._ahead is None
                      and not self.waiting and self.running
                      and self._prefilling == 0)
            if steady:
                # clamp the burst to the smallest remaining budget instead of
                # disabling bursting outright (the old `min >= burst` gate
                # silently fell back to 1-token steps for entire batches as
                # soon as ONE sequence got within `burst` tokens of its cap)
                eff = min(burst, int((t.budget - t.gen)[t.active].min()))
                if eff > 1:
                    rng, burst_rng = jax.random.split(rng)
                    self._burst_decode(eff, temperature=temperature,
                                       rng=burst_rng)
                    self.burst_steps += 1
                    continue
            rng, step_rng = jax.random.split(rng)
            self.step(temperature=temperature, rng=step_rng)
        self._flush_table()  # max_steps exhaustion: sync still-running seqs
        for uid, seq in tracked.items():
            results[uid] = seq.tokens
        return results
