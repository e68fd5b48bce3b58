"""Decoder-only transformer family (GPT-2 / LLaMA / Mixtral-style).

This is the framework's flagship model zoo, built TPU-first:

* parameters are a plain pytree with a parallel *logical-axes* pytree
  (``embed``/``mlp``/``heads``/``vocab``/``layers``...) consumed by the ZeRO/TP
  sharding rules (`runtime/zero/sharding.py`);
* the layer stack is **stacked and scanned** (`lax.scan`), which is what makes
  ZeRO-3-style gather-per-layer expressible as program structure under XLA
  (SURVEY.md §7 "hard parts") instead of eager hooks;
* rematerialisation is a `jax.checkpoint` policy on the scanned body;
* attention is pluggable (XLA einsum reference path, Pallas flash kernel,
  Ulysses/ring sequence-parallel wrappers).

Covers the reference's training-side model needs (the reference itself defers
models to user code / HF; its fused transformer block lives in
``csrc/transformer`` — here the block is this module + Pallas kernels).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..linear.optimized_linear import (LoRAWeight, expand_axes_for_lora,
                                       lora_forward)
from ..ops.pallas.mixed_gemm import (LayerOf, QuantizedWeight,
                                     mixed_gemm_frozen)


@dataclasses.dataclass(frozen=True)
class RopeParams:
    """Rotary embedding of one kind of layer: plain RoPE at ``theta``, or with
    ``factor`` > 0 static YaRN (Peng et al. 2023, as Hugging Face's
    ``rope_type: "yarn"`` computes it): the frequencies a context of
    ``original_max_position_embeddings`` turns fewer than ``beta_slow`` times
    are divided by ``factor``, those it turns more than ``beta_fast`` times
    stay, a linear ramp between; cos and sin are scaled by
    ``attention_factor``."""
    theta: float = 10000.0
    factor: float = 0.0  # 0 => plain RoPE, the fields below unused
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    # DeepSeek's YaRN: where > 0 the attention's softmax scale is multiplied
    # by ``m(factor, mscale_all_dim) ** 2`` with ``m(s, a) = 0.1 a ln s + 1``
    # (models/latent_sparse.softmax_scale); ``attention_factor`` is then
    # ``m(factor, mscale) / m(factor, mscale_all_dim)``
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    hidden_size: int = 512
    intermediate_size: int = 1408
    num_layers: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None => MHA; < num_heads => GQA
    # explicit per-head width (gemma-7b: 256 != hidden/heads); None derives
    head_dim_override: Optional[int] = None
    max_seq_len: int = 2048
    # architecture switches
    # rmsnorm (llama) | layernorm (gpt2) | gemma_rmsnorm ((1+w) scaling)
    norm: str = "rmsnorm"
    # silu => SwiGLU; gelu => GELU MLP; relu (opt); relu2 => relu(x)**2,
    # ungated (nemotron_h's experts)
    activation: str = "silu"
    # gated two-branch MLP with a non-silu activation (gemma's gated gelu);
    # silu implies gated regardless
    gated_mlp: bool = False
    # multiply embedding output by sqrt(hidden_size) (gemma normalizer)
    embed_scale_by_sqrt_dim: bool = False
    # rope (llama) | learned (gpt2) | alibi (bloom) | none (nemotron_h's
    # attention layers: the state-space layers carry the order)
    position: str = "rope"
    tie_embeddings: bool = True
    # LayerNorm right after the embedding lookup (bloom
    # word_embeddings_layernorm)
    embed_norm: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # parallel attention+MLP residual (falcon/gpt-neox/phi-2):
    #   h = h + attn(ln1(h)) + mlp(ln2(h))
    # (models sharing one layernorm duplicate it into ln1/ln2 on conversion)
    parallel_residual: bool = False
    # rotate only the first fraction of each head's dims (gpt-neox/phi)
    partial_rotary_factor: float = 1.0
    # sliding-window attention (0 == full); Mistral-style band
    sliding_window: int = 0
    # the attention kind of each layer, "sliding" | "full": the whole list or
    # one period of it (tiled over num_layers, cut where they end).  Empty: every layer alike,
    # "sliding" when sliding_window > 0.  The window applies to "sliding"
    # layers only (Mellum2: sliding, sliding, sliding, full)
    layer_types: Tuple[str, ...] = ()
    # RoPE by layer kind, (kind, RopeParams) pairs; a kind not listed rotates
    # plainly at rope_theta (Mellum2: YaRN on the "full" layers only)
    rope_params: Tuple[Tuple[str, RopeParams], ...] = ()
    # MoE (0 == dense); see deepspeed_tpu/moe for the layer implementation
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # how the TRAINING forward routes: 'capacity' (GShard buckets; the ep
    # all-to-all path) | 'dropless' (grouped-GEMM, no token dropping —
    # moe/dropless.py) | 'expert_choice' (experts pick top-C tokens; balanced
    # by construction).  The inference engines do not read it: they compute
    # every top-k assignment (moe/dropless.serving_moe_block), and refuse
    # experts trained with 'expert_choice'
    moe_routing: str = "capacity"
    # whether the top-k gate weights are renormalised to sum 1 (Mixtral,
    # GShard) or stay the raw softmax probabilities (OLMoE's
    # ``norm_topk_prob: false``)
    moe_norm_topk: bool = True
    # RMSNorm of q and k over the whole projection, before the split into
    # heads and before RoPE (OLMoE); v has none
    qk_norm: bool = False
    # RMSNorm of q and k over each HEAD's ``head_dim``, one learned scale of
    # ``head_dim`` that the heads share, after the split into heads and
    # before RoPE (afmoe); not with ``qk_norm``
    qk_norm_per_head: bool = False
    # the attention's output times a sigmoid of a projection of its own
    # (``attn.wg``, as wide as the heads) of the layer's normed input, before
    # ``wo`` (afmoe)
    attn_gate: bool = False
    # the kinds of layer (of ``layer_types``) that carry NO position: q and k
    # go unrotated there (afmoe: RoPE on "sliding" layers, none on "full")
    nope_layer_kinds: Tuple[str, ...] = ()
    # a norm AFTER each branch (``ln1_post`` / ``ln2_post``), before the
    # residual add, beside the one before it (afmoe)
    post_branch_norm: bool = False
    # PR-MoE (reference deepspeed/moe/layer.py:17 use_residual): a dense
    # "shared expert" MLP runs beside the MoE and a learned 2-way softmax
    # coefficient mixes the two outputs per token
    moe_use_residual: bool = False
    # the router's kind: "softmax" over all experts, or "sigmoid" scores with
    # a per-expert correction bias added for the CHOICE only (nemotron_h,
    # DeepSeek-V3's rule with one group): the chosen experts' unbiased scores
    # are renormalised (``moe_norm_topk``) and scaled by ``moe_routed_scaling``
    moe_router: str = "softmax"
    moe_routed_scaling: float = 1.0
    # width of a shared expert that every token passes beside the routed
    # ones, its output added unweighted (0: none)
    moe_shared_size: int = 0
    # test and benchmark tooling (benchmark/routing_tap.py): a step program
    # BUILT for a config with this set carries every row's chosen experts
    # out behind ``routed_ffn``'s two stats.  A comparison of logits has to
    # hold a float32 reference to the choices the program made where seeded
    # random routers tie.  A served model's config leaves it False
    moe_tap_choices: bool = False
    # ONE mixer a layer, the kind of each by ``mixer_pattern`` (nemotron_h's
    # ``hybrid_override_pattern``): "M" a Mamba-2 layer, "E" an MoE layer,
    # "*" an attention layer; ``num_layers`` long, and NOT one period
    # repeated.  Empty: every layer is attention + FFN.  The parameters are
    # stacked by kind (models/ssm_hybrid.py).  "S" a Mamba-1 (selective
    # scan) mixer and "F" a dense gated FFN, each a sub-layer ``x +
    # f(norm(x))`` of its own (jamba's layer is a mixer AND an FFN: two
    # letters, and ``num_layers`` counts the letters); a model holds "S" or
    # "M", not both (models/selective_ssm.py)
    mixer_pattern: Tuple[str, ...] = ()
    # Mamba-2 sizes: d_inner = heads x head_dim, B and C in ``groups`` groups
    # of ``state`` each, a depthwise causal conv of ``conv_kernel`` taps over
    # [x | B | C]; ``chunk``: how the scan is blocked, not what it computes
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_state_size: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk_size: int = 128
    # Mamba-1 (an "S" layer): d_inner = ``mamba_expand`` x hidden, one decay
    # a (channel, state) pair, dt through a bottleneck of ``mamba_dt_rank``;
    # ``mamba_state_size`` and ``mamba_conv_kernel`` as above
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    # Latent attention (MLA; models/latent_sparse.py), on when kv_lora_rank >
    # 0: queries through a rank-``q_lora_rank`` bottleneck, keys and values
    # from ONE latent of ``kv_lora_rank`` values a token and one shared
    # rotated key of ``qk_rope_head_dim``; a head's query-key width is
    # ``qk_nope_head_dim + qk_rope_head_dim`` (the softmax scale's), its value
    # width ``v_head_dim``.  The paged cache holds the latent and the rotated
    # key, ``kv_lora_rank + qk_rope_head_dim`` values a token a layer
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A learned indexer (DSA) picks the ``index_topk`` keys a query attends
    # over: ``index_n_heads`` heads of ``index_head_dim`` score every visible
    # key, on the layers ``indexer_types`` (``num_layers`` long) calls "full";
    # a "shared" layer attends over the pick of the nearest full layer before
    # it.  The first layer is "full"
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Tuple[str, ...] = ()
    # the FFN of each layer, "dense" (``intermediate_size``) or "sparse"
    # (routed experts of ``moe_intermediate_size`` and the shared expert),
    # ``num_layers`` long; the parameters are stacked by kind (a latent
    # model: models/latent_sparse.py; grouped-query attention:
    # models/mixed_ffn.py)
    mlp_layer_types: Tuple[str, ...] = ()
    moe_intermediate_size: int = 0  # 0: an expert is ``intermediate_size`` wide
    # THE CHIP'S SHARE of the experts: the router keeps ``num_experts``
    # outputs and ``moe_top_k`` choices, the layer holds and computes experts
    # ``moe_first_expert`` to ``+ moe_experts_held`` only, and assignments to
    # the others are counted and left out (0: every expert is held)
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    # the balance loss a TRAINED routed layer adds to the cross-entropy, times
    # this coefficient (0: none), summed over the routed layers.
    # ``moe_seq_aux``: DeepSeek's sequence-wise form, per sequence
    # ``sum_i f_i P_i`` with ``f_i = E / (K S) x`` the sequence's tokens that
    # chose expert i and ``P_i`` its mean probability; else Switch's top-1
    # form over the whole batch (moe/dropless.balance_loss)
    moe_aux_loss_coef: float = 0.0
    moe_seq_aux: bool = False
    # the step by which a RULE, not a gradient, moves a sigmoid router's
    # correction bias after every optimizer step (0: the bias is a trained
    # leaf like any other): ``b <- b + d - mean(d)`` with ``d = rate x
    # sign(mean(c) - c)`` over the step's assignments ``c`` to each expert
    # (DeepSeek-V3's balancing without a loss, centred; afmoe's
    # ``load_balance_coeff``; models/mixed_ffn.py)
    moe_bias_update_rate: float = 0.0
    # test and benchmark tooling (benchmark/selection_tap.py): a step program
    # BUILT for a config with this set carries the indexer's scores and picks
    # of its "full" layers out.  A served model's config leaves it False
    dsa_tap: bool = False
    # EVA attention (Zheng et al., arXiv:2302.04542; models/eva.py), on when
    # eva_window > 0: a query reads the keys of its own TUMBLING window of
    # ``eva_window`` positions exactly and, behind it, one learned summary
    # key and value for every ``eva_chunk`` tokens of the closed windows,
    # all under one softmax.  Each layer holds two vectors a head for the
    # summaries (``attn.eva_phi``, ``attn.eva_mu``)
    eva_window: int = 0
    eva_chunk: int = 0
    # Kimi Delta Attention beside latent attention (models/kimi_linear.py),
    # on when ``kda_pattern`` is set: the MIXER of each layer, "K" a KDA
    # layer (``kda_num_heads`` heads with a ``kda_head_dim`` x
    # ``kda_head_dim`` matrix state each, updated by a delta rule under a
    # gate a channel; a depthwise causal conv of ``kda_conv_kernel`` taps
    # over q, k and v; the gate and the output gate through low-rank maps of
    # inner width ``kda_gate_rank``) or "A" a latent attention layer
    # (``kv_lora_rank`` etc. above, no query compression, no indexer, no
    # rotation); ``num_layers`` long.  Every layer has an FFN behind its
    # mixer, by ``mlp_layer_types``.  ``kda_chunk_size``: how the scan over a
    # prompt is blocked, not what it computes
    kda_pattern: Tuple[str, ...] = ()
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    kda_chunk_size: int = 64
    # output heads (EvaByte: head i scores the byte i + 1 positions ahead):
    # ``lm_head.w`` is ``num_pred_heads x vocab_size`` columns wide, head 0's
    # first, and the next token is sampled from head 0's
    num_pred_heads: int = 1
    # the head's product and its logits in float32, whatever ``dtype`` is
    fp32_logits: bool = False
    # dtypes
    dtype: str = "bfloat16"  # compute dtype
    param_dtype: str = "float32"  # master weights
    # attention implementation: 'xla' | 'flash' | 'ulysses' | 'ring'
    attn_impl: str = "xla"
    # remat policy name for the scanned stack
    remat_policy: str = "nothing_saveable"

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.hidden_size // self.num_heads

    @property
    def is_gated_mlp(self) -> bool:
        return self.gated_mlp or self.activation == "silu"

    def __post_init__(self):
        if self.gated_mlp and self.num_experts > 0 and \
                self.activation != "silu":
            raise ValueError(
                "gated_mlp with a non-silu activation is not wired for MoE "
                "expert blocks (they hardcode silu gating)")
        # hashable whatever the caller handed in (a JSON list): the config
        # is a jit memo key
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "mixer_pattern", tuple(self.mixer_pattern))
        if self.mixer_pattern:
            if set(self.mixer_pattern) - {"M", "E", "*", "S", "F"}:
                raise ValueError(f"mixer_pattern holds kinds other than 'M', "
                                 f"'E', '*', 'S' and 'F': "
                                 f"{self.mixer_pattern}")
            if "S" in self.mixer_pattern:
                from .selective_ssm import check_config as check_selective

                check_selective(self)
            if len(self.mixer_pattern) != self.num_layers:
                raise ValueError(
                    f"mixer_pattern names {len(self.mixer_pattern)} layers, "
                    f"num_layers is {self.num_layers}")
            if self.layer_types or self.sliding_window:
                raise ValueError("mixer_pattern with window layers is not "
                                 "something the program computes")
        object.__setattr__(self, "indexer_types", tuple(self.indexer_types))
        object.__setattr__(self, "mlp_layer_types",
                           tuple(self.mlp_layer_types))
        object.__setattr__(self, "kda_pattern", tuple(self.kda_pattern))
        object.__setattr__(self, "nope_layer_kinds",
                           tuple(self.nope_layer_kinds))
        if self.kda_pattern:
            from .kimi_linear import check_config as check_kda

            check_kda(self)
        elif self.kv_lora_rank:
            from .latent_sparse import check_config

            check_config(self)
        elif self.mlp_layer_types:
            from .mixed_ffn import check_config as check_mixed

            check_mixed(self)
        if self.qk_norm and self.qk_norm_per_head:
            raise ValueError("qk_norm is over the whole projection, "
                             "qk_norm_per_head over a head: one or the other")
        if self.eva_window:
            from .eva import check_config as check_eva

            check_eva(self)
        if self.moe_router not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        object.__setattr__(self, "rope_params",
                           tuple((k, r) for k, r in self.rope_params))
        if self.layer_types:
            if set(self.layer_types) - {"sliding", "full"}:
                raise ValueError(f"layer_types holds kinds other than "
                                 f"'sliding' and 'full': {self.layer_types}")
            if "sliding" in self.layer_types and self.sliding_window <= 0:
                raise ValueError("'sliding' layers need sliding_window > 0")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """The attention kind of every layer, ``num_layers`` long."""
        if not self.layer_types:
            return (("sliding" if self.sliding_window > 0 else "full"),
                    ) * self.num_layers
        reps = -(-self.num_layers // len(self.layer_types))
        return (self.layer_types * reps)[:self.num_layers]

    @property
    def layer_period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that, repeated, gives ``layer_kinds``:
        what a layer scan steps over (one kind: a period of one layer)."""
        kinds = self.layer_kinds
        for p in range(1, len(kinds) + 1):
            if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
                return kinds[:p]
        return kinds

    def window_of(self, kind: str) -> int:
        return self.sliding_window if kind == "sliding" else 0

    def rope_of(self, kind: str) -> RopeParams:
        return dict(self.rope_params).get(kind) or \
            RopeParams(theta=self.rope_theta)

    def layers_of(self, kind: str) -> int:
        """Layers of mixer kind ``kind`` ("M", "E", "*", "S", "F") in the
        pattern."""
        return sum(k == kind for k in self.mixer_pattern)

    @property
    def expert_width(self) -> int:
        """Inner width of one routed expert."""
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def experts_held(self) -> int:
        """Routed experts this chip holds (all of them unless told)."""
        return self.moe_experts_held or self.num_experts

    @property
    def mamba_d_inner(self) -> int:
        if self.mamba_expand:  # Mamba-1: no heads
            return self.mamba_expand * self.hidden_size
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the conv runs over: x, B and C side by side."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_state_size)

    @property
    def rot_dim(self) -> int:
        """Rotated head dims (partial rotary rounds down to even)."""
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    def num_params(self, include_embed: bool = True) -> int:
        if self.kda_pattern:
            from .kimi_linear import num_params

            return num_params(self, include_embed)
        if self.kv_lora_rank:
            from .latent_sparse import num_params

            return num_params(self, include_embed)
        if self.mlp_layer_types:
            from .mixed_ffn import num_params

            return num_params(self, include_embed)
        if self.mixer_pattern:
            from .ssm_hybrid import num_params

            return num_params(self, include_embed)
        h, f, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        kvh = self.kv_heads * self.head_dim
        qh = self.num_heads * self.head_dim  # != h with head_dim_override
        per_layer = h * qh + 2 * h * kvh + qh * h  # q, k, v, o
        if self.qk_norm:
            per_layer += qh + kvh
        if self.qk_norm_per_head:
            per_layer += 2 * self.head_dim
        if self.attn_gate:
            per_layer += h * qh
        if self.post_branch_norm:
            per_layer += 2 * h
        n_mlp = 3 * h * f if self.is_gated_mlp else 2 * h * f
        if self.num_experts > 0:
            n_mlp = n_mlp * self.num_experts + h * self.num_experts  # experts + router
        per_layer += n_mlp + 2 * h
        if self.eva_window:  # phi and mu, a head
            per_layer += 2 * qh
        total = L * per_layer + h  # + final norm
        if include_embed:
            total += v * h if self.tie_embeddings else \
                (1 + self.num_pred_heads) * v * h
            if self.position == "learned":
                total += self.max_seq_len * h
        return total


# ---------------------------------------------------------------------------
# presets (BASELINE.md config ladder)
# ---------------------------------------------------------------------------

PRESETS: Dict[str, Dict[str, Any]] = {
    "gpt2-125m": dict(vocab_size=50257, hidden_size=768, intermediate_size=3072,
                      num_layers=12, num_heads=12, max_seq_len=1024, norm="layernorm",
                      activation="gelu", position="learned", tie_embeddings=True),
    "llama3-8b": dict(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                      num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192,
                      rope_theta=500000.0),
    "llama3-70b": dict(vocab_size=128256, hidden_size=8192, intermediate_size=28672,
                       num_layers=80, num_heads=64, num_kv_heads=8, max_seq_len=8192,
                       rope_theta=500000.0),
    "mixtral-8x7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                         num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=32768,
                         num_experts=8, moe_top_k=2),
    # EvaByte-6.5B (EvaByte/EvaByte): bytes in, bytes out (vocabulary 320),
    # EVA attention (a tumbling window of 2,048 exact keys, one summary for
    # every 16 tokens behind it), norms with a unit offset, eight output
    # heads, logits in float32
    "evabyte-6.5b": dict(
        vocab_size=320, hidden_size=4096, intermediate_size=11008,
        num_layers=32, num_heads=32, max_seq_len=32768,
        norm="gemma_rmsnorm", rope_theta=100000.0, norm_eps=1e-5,
        tie_embeddings=False, eva_window=2048, eva_chunk=16,
        num_pred_heads=8, fp32_logits=True),
    "mistral-7b": dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                       num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=32768,
                       sliding_window=4096, attn_impl="flash",
                       tie_embeddings=False),  # as published: 7.24 B
    # allenai/OLMoE-1B-7B-0125-Instruct as published: 6.92 B, 1.3 B active;
    # intermediate_size is the width of ONE expert; no token is dropped
    "olmoe-1b-7b": dict(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
                        num_layers=16, num_heads=16, num_kv_heads=16, max_seq_len=4096,
                        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
                        num_experts=64, moe_top_k=8, moe_norm_topk=False,
                        moe_routing="dropless", qk_norm=True, attn_impl="flash"),
    # JetBrains/Mellum2-12B-A2.5B-Instruct as published: 12.15 B, about 2.5 B
    # active; three window layers (1024) to one global layer, seven times;
    # YaRN (8192 x 16) on the global layers only; intermediate_size is the
    # width of ONE expert (the published 7168 is a dense width no layer has)
    "mellum2-12b-a2.5b": dict(
        vocab_size=98304, hidden_size=2304, intermediate_size=896,
        num_layers=28, num_heads=32, num_kv_heads=4, head_dim_override=128,
        max_seq_len=131072, rope_theta=500000.0, norm_eps=1e-6,
        tie_embeddings=False, sliding_window=1024,
        layer_types=("sliding", "sliding", "sliding", "full"),
        rope_params=(("full", RopeParams(
            theta=500000.0, factor=16.0,
            original_max_position_embeddings=8192, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.2772588722239782)),),
        num_experts=64, moe_top_k=8, moe_norm_topk=True,
        moe_routing="dropless", attn_impl="flash"),
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published: 31.58 B, about
    # 3.2 B active; one mixer a layer by hybrid_override_pattern (23 Mamba-2,
    # 23 MoE, 6 attention); intermediate_size is the width of ONE expert
    "nemotron3-nano-30b-a3b": dict(
        vocab_size=131072, hidden_size=2688, intermediate_size=1856,
        num_layers=52, num_heads=32, num_kv_heads=2, head_dim_override=128,
        max_seq_len=262144, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=False, position="none", activation="relu2",
        mixer_pattern=tuple(
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"),
        mamba_num_heads=64, mamba_head_dim=64, mamba_n_groups=8,
        mamba_state_size=128, mamba_conv_kernel=4, mamba_chunk_size=128,
        num_experts=128, moe_top_k=6, moe_norm_topk=True,
        moe_router="sigmoid", moe_routed_scaling=2.5, moe_shared_size=3712,
        moe_routing="dropless", attn_impl="flash"),
    # ai21labs/AI21-Jamba2-3B as published (jamba): 3.03 B; 28 layers of a
    # mixer AND a dense SwiGLU FFN, written as 56 sub-layers: "S" a Mamba-1
    # mixer, "*" attention (published layers 7 and 21: attn_layer_period 14,
    # offset 7) without positions, "F" the FFN behind every mixer; tied head
    "jamba2-3b": dict(
        vocab_size=65536, hidden_size=2560, intermediate_size=8192,
        num_layers=56, num_heads=20, num_kv_heads=1, head_dim_override=128,
        max_seq_len=262144, norm_eps=1e-6, tie_embeddings=True,
        position="none", activation="silu", gated_mlp=True,
        mixer_pattern=tuple("".join(
            ("*" if i % 14 == 7 else "S") + "F" for i in range(28))),
        mamba_expand=2, mamba_state_size=16, mamba_dt_rank=160,
        mamba_conv_kernel=4, attn_impl="flash"),
    # zai-org/GLM-5.2 as published (glm_moe_dsa): 744 B, about 40 B active;
    # latent attention (MLA), a learned indexer that picks 2,048 keys a query
    # on every fourth layer and shares the pick with the three behind it,
    # three leading dense layers, 256 routed experts (top 8) and one shared;
    # intermediate_size is the DENSE width, an expert's is
    # moe_intermediate_size.  The multi-token-prediction layer is not part of
    # the main model and is not built
    "glm-5.2": dict(
        vocab_size=154880, hidden_size=6144, intermediate_size=12288,
        num_layers=78, num_heads=64, num_kv_heads=64, head_dim_override=192,
        max_seq_len=1048576, rope_theta=8000000.0, norm_eps=1e-5,
        tie_embeddings=False, kv_lora_rank=512, q_lora_rank=2048,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        index_topk=2048, index_n_heads=32, index_head_dim=128,
        indexer_types=("full",) * 3 + ("shared", "shared", "shared",
                                       "full") * 18 + ("shared",) * 3,
        mlp_layer_types=("dense",) * 3 + ("sparse",) * 75,
        num_experts=256, moe_top_k=8, moe_norm_topk=True,
        moe_router="sigmoid", moe_routed_scaling=2.5,
        moe_intermediate_size=2048, moe_shared_size=2048,
        moe_routing="dropless", attn_impl="flash"),
    # deepseek-ai/DeepSeek-V2-Lite as published (deepseek_v2): 15.7 B, about
    # 2.4 B active; latent attention WITHOUT query compression (q_lora_rank
    # null) and without an indexer, one leading dense layer, 64 routed experts
    # (softmax, top 6, raw probabilities as gates) and two shared experts run
    # as one SwiGLU of 2 x 1408; YaRN x 40 over 4,096 with mscale 0.707 in the
    # softmax scale; trained with the sequence-wise balance loss (``seq_aux``;
    # the coefficient is not in config.json: 0.001 assumed)
    "deepseek-v2-lite": dict(
        vocab_size=102400, hidden_size=2048, intermediate_size=10944,
        num_layers=27, num_heads=16, num_kv_heads=16, head_dim_override=128,
        max_seq_len=163840, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=False, kv_lora_rank=512, q_lora_rank=0,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_params=(("full", RopeParams(
            theta=10000.0, factor=40.0,
            original_max_position_embeddings=4096, beta_fast=32.0,
            beta_slow=1.0, attention_factor=1.0, mscale_all_dim=0.707)),),
        mlp_layer_types=("dense",) + ("sparse",) * 26,
        num_experts=64, moe_top_k=6, moe_norm_topk=False,
        moe_router="softmax", moe_routed_scaling=1.0,
        moe_intermediate_size=1408, moe_shared_size=2816,
        moe_aux_loss_coef=0.001, moe_seq_aux=True,
        moe_routing="dropless", attn_impl="flash"),
    # moonshotai/Kimi-Linear-48B-A3B-Instruct as published (kimi_linear):
    # 49.1 B, about 3 B active; three KDA layers (a delta-rule matrix state a
    # head under a gate a channel, short convs of 4 taps) to one latent
    # attention layer WITHOUT positions, query compression or an indexer; one
    # leading dense layer, then 256 routed experts (sigmoid, top 8, one
    # group) and one shared; intermediate_size is the DENSE width.  The
    # published head_dim 72 (2304 / 32) names no array of either mixer
    "kimi-linear-48b": dict(
        vocab_size=163840, hidden_size=2304, intermediate_size=9216,
        num_layers=27, num_heads=32, num_kv_heads=32, max_seq_len=1048576,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        position="none", kda_pattern=tuple("KKKA" * 6 + "KKA"),
        kda_num_heads=32, kda_head_dim=128, kda_conv_kernel=4,
        kda_gate_rank=128, kda_chunk_size=64,
        kv_lora_rank=512, q_lora_rank=0, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128,
        mlp_layer_types=("dense",) + ("sparse",) * 26,
        num_experts=256, moe_top_k=8, moe_norm_topk=True,
        moe_router="sigmoid", moe_routed_scaling=2.446,
        moe_intermediate_size=1024, moe_shared_size=1024,
        moe_routing="dropless", attn_impl="flash"),
    # arcee-ai/Trinity-Mini as published (afmoe): 26.1 B, about 3 B active;
    # three window layers (2,048, RoPE) to one full layer WITHOUT positions;
    # the attention's output gated by a sigmoid of a projection of its own, q
    # and k normed a head, a norm before AND after each branch, the embedding
    # times sqrt(hidden) (``mup_enabled``); two leading dense layers, then
    # 128 routed experts (sigmoid, top 8, renormalised, x 2.826) and one
    # shared; the router's bias moved by a rule (``load_balance_coeff``), no
    # balance loss.  intermediate_size is the DENSE width
    "trinity-mini": dict(
        vocab_size=200192, hidden_size=2048, intermediate_size=6144,
        num_layers=32, num_heads=32, num_kv_heads=4, head_dim_override=128,
        max_seq_len=131072, rope_theta=10000.0, norm_eps=1e-5,
        tie_embeddings=False, embed_scale_by_sqrt_dim=True,
        sliding_window=2048,
        layer_types=("sliding", "sliding", "sliding", "full"),
        nope_layer_kinds=("full",), attn_gate=True, qk_norm_per_head=True,
        post_branch_norm=True,
        mlp_layer_types=("dense",) * 2 + ("sparse",) * 30,
        num_experts=128, moe_top_k=8, moe_norm_topk=True,
        moe_router="sigmoid", moe_routed_scaling=2.826,
        moe_intermediate_size=1024, moe_shared_size=1024,
        moe_bias_update_rate=0.001, moe_routing="dropless",
        attn_impl="flash"),
    # the same block at toy widths: one dense layer and four routed ones
    # (sliding | sliding, full, sliding, sliding), a window of 8, 4 of 16
    # experts held (the second share of four)
    "tiny-trinity": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=5,
        num_heads=4, num_kv_heads=2, head_dim_override=16, max_seq_len=256,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        embed_scale_by_sqrt_dim=True, sliding_window=8,
        layer_types=("sliding", "sliding", "full", "sliding", "sliding"),
        nope_layer_kinds=("full",), attn_gate=True, qk_norm_per_head=True,
        post_branch_norm=True,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_experts=16, moe_top_k=4, moe_norm_topk=True,
        moe_router="sigmoid", moe_routed_scaling=2.826,
        moe_intermediate_size=48, moe_shared_size=48, moe_experts_held=4,
        moe_first_expert=4, moe_bias_update_rate=0.001,
        moe_routing="dropless", attn_impl="flash"),
    # the same block at toy widths: a dense layer, then K K A K K K A K A
    # over routed FFNs (a pattern that is no period repeated); 4 of 16
    # experts held (the second share of four); a chunk of 8, so that a
    # prompt of 20 walks three pieces
    "tiny-kimi-linear": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_layers=10, num_heads=4, num_kv_heads=4, max_seq_len=512,
        norm_eps=1e-5, tie_embeddings=False, position="none",
        kda_pattern=tuple("KKKAKKKAKA"), kda_num_heads=4, kda_head_dim=16,
        kda_conv_kernel=4, kda_gate_rank=16, kda_chunk_size=8,
        kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32,
        mlp_layer_types=("dense",) + ("sparse",) * 9,
        num_experts=16, moe_top_k=4, moe_norm_topk=True, moe_router="sigmoid",
        moe_routed_scaling=2.446, moe_intermediate_size=128,
        moe_shared_size=128, moe_experts_held=4, moe_first_expert=4,
        moe_routing="dropless"),
    # the same block at toy widths: one dense layer and three routed ones,
    # 2 of 8 experts held (the second share of four), a query-key width (24 +
    # 8) that is not the value width (16), YaRN whose ramp lies inside 64
    # positions
    "tiny-dsv2lite": dict(
        vocab_size=256, hidden_size=64, intermediate_size=160, num_layers=4,
        num_heads=4, num_kv_heads=4, head_dim_override=24, max_seq_len=256,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
        kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=16,
        rope_params=(("full", RopeParams(
            theta=10000.0, factor=4.0, original_max_position_embeddings=16,
            beta_fast=4.0, beta_slow=1.0, attention_factor=1.0,
            mscale_all_dim=0.707)),),
        mlp_layer_types=("dense",) + ("sparse",) * 3,
        num_experts=8, moe_top_k=3, moe_norm_topk=False,
        moe_router="softmax", moe_intermediate_size=48, moe_shared_size=96,
        moe_experts_held=2, moe_first_expert=2,
        moe_aux_loss_coef=0.01, moe_seq_aux=True, moe_routing="dropless"),
    # the same block at toy widths: one dense layer, then two periods of
    # shared shared shared full; 4 of 16 experts held (the second share of
    # four); index_topk 16, so that a context of 40 is past two of them
    "tiny-glm52": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256, num_layers=9,
        num_heads=4, num_kv_heads=4, head_dim_override=24, max_seq_len=512,
        rope_theta=10000.0, norm_eps=1e-5, tie_embeddings=False,
        kv_lora_rank=32, q_lora_rank=64, qk_nope_head_dim=24,
        qk_rope_head_dim=8, v_head_dim=32, index_topk=16, index_n_heads=4,
        index_head_dim=16,
        indexer_types=("full",) + ("shared", "shared", "shared", "full") * 2,
        mlp_layer_types=("dense",) + ("sparse",) * 8,
        num_experts=16, moe_top_k=4, moe_norm_topk=True, moe_router="sigmoid",
        moe_routed_scaling=2.5, moe_intermediate_size=128,
        moe_shared_size=128, moe_experts_held=4, moe_first_expert=4,
        moe_routing="dropless"),
    "tiny": dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
                 num_heads=4, max_seq_len=128),
    # EvaByte's block at toy widths: a window of 32 in chunks of 4, two
    # output heads
    "tiny-evabyte": dict(
        vocab_size=320, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, max_seq_len=512, norm="gemma_rmsnorm",
        rope_theta=100000.0, norm_eps=1e-5, tie_embeddings=False,
        eva_window=32, eva_chunk=4, num_pred_heads=2, fp32_logits=True),
    # nemotron_h's three kinds of layer at toy widths: a pattern that is no
    # period repeated, fewer groups than heads, widths (hidden, an expert)
    # that are multiples of 64 and not of 128
    "tiny-nemotron3": dict(
        vocab_size=256, hidden_size=192, intermediate_size=192, num_layers=9,
        num_heads=4, num_kv_heads=2, head_dim_override=32, max_seq_len=512,
        norm_eps=1e-5, tie_embeddings=False, position="none",
        activation="relu2", mixer_pattern=tuple("MEM*EMEME"),
        mamba_num_heads=8, mamba_head_dim=16, mamba_n_groups=2,
        mamba_state_size=32, mamba_conv_kernel=4, mamba_chunk_size=16,
        num_experts=8, moe_top_k=3, moe_norm_topk=True, moe_router="sigmoid",
        moe_routed_scaling=2.5, moe_shared_size=128, moe_routing="dropless"),
    # jamba's two sub-layers at toy widths: 8 published layers (period 4,
    # offset 2), 1 K/V head under 4 query heads, tied head
    "tiny-jamba2": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=16,
        num_heads=4, num_kv_heads=1, head_dim_override=16, max_seq_len=512,
        norm_eps=1e-6, tie_embeddings=True, position="none",
        activation="silu", gated_mlp=True,
        mixer_pattern=tuple("".join(
            ("*" if i % 4 == 2 else "S") + "F" for i in range(8))),
        mamba_expand=2, mamba_state_size=16, mamba_dt_rank=8,
        mamba_conv_kernel=4),
    "tiny-moe": dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
                     num_heads=4, max_seq_len=128, num_experts=4, moe_top_k=2),
    # OLMoE's block at toy widths (tests, the benchmark's rehearsal)
    "tiny-olmoe": dict(vocab_size=256, hidden_size=128, intermediate_size=128,
                       num_layers=2, num_heads=4, max_seq_len=128,
                       tie_embeddings=False, num_experts=8, moe_top_k=2,
                       moe_norm_topk=False, moe_routing="dropless",
                       qk_norm=True),
    # Mellum2's block at toy widths: two periods of S S S F, a window of 8,
    # YaRN whose ramp lies inside 64 positions, renormalised top-2 gates
    "tiny-mellum2": dict(
        vocab_size=256, hidden_size=128, intermediate_size=128, num_layers=8,
        num_heads=4, num_kv_heads=2, max_seq_len=256, rope_theta=10000.0,
        norm_eps=1e-6, tie_embeddings=False, sliding_window=8,
        layer_types=("sliding", "sliding", "sliding", "full"),
        rope_params=(("full", RopeParams(
            theta=10000.0, factor=4.0, original_max_position_embeddings=16,
            beta_fast=4.0, beta_slow=1.0, attention_factor=1.1386)),),
        num_experts=8, moe_top_k=2, moe_norm_topk=True,
        moe_routing="dropless", attn_impl="flash"),
    "tiny-prmoe": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_layers=2, num_heads=4, max_seq_len=128,
                       num_experts=4, moe_top_k=2, moe_use_residual=True),
}


def get_config(name: str, **overrides) -> TransformerConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; have {sorted(PRESETS)}")
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _dense_init(key, shape, in_axis_size, dtype):
    scale = 1.0 / math.sqrt(in_axis_size)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_attention_extras(key, cfg: TransformerConfig, L: int, pd
                          ) -> Dict[str, Any]:
    """What ``attn_gate`` and ``qk_norm_per_head`` add to a stack of ``L``
    layers' ``attn`` dict (nothing for a model with neither)."""
    h, hd = cfg.hidden_size, cfg.head_dim
    extra: Dict[str, Any] = {}
    if cfg.qk_norm_per_head:
        extra["q_norm"] = {"scale": jnp.ones((L, hd), pd)}
        extra["k_norm"] = {"scale": jnp.ones((L, hd), pd)}
    if cfg.attn_gate:
        extra["wg"] = _dense_init(key, (L, h, cfg.num_heads * hd), h, pd)
    return extra


def attention_extras_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    extra: Dict[str, Any] = {}
    if cfg.qk_norm_per_head:
        extra["q_norm"] = {"scale": ("layers", None)}
        extra["k_norm"] = {"scale": ("layers", None)}
    if cfg.attn_gate:
        extra["wg"] = ("layers", "embed", "heads")
    return extra


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    """Create the parameter pytree. Per-layer weights are stacked on a leading
    ``layers`` axis so the forward pass can ``lax.scan`` over them (a model
    with ``mixer_pattern``: one stack a kind of layer, models/ssm_hybrid.py)."""
    if cfg.kda_pattern:
        from .kimi_linear import init_params as init_kda

        return init_kda(rng, cfg)
    if cfg.kv_lora_rank:
        from .latent_sparse import init_params as init_latent

        return init_latent(rng, cfg)
    if cfg.mlp_layer_types:
        from .mixed_ffn import init_params as init_mixed

        return init_mixed(rng, cfg)
    if cfg.mixer_pattern:
        from .ssm_hybrid import init_params as init_hybrid

        return init_hybrid(rng, cfg)
    pd = jnp.dtype(cfg.param_dtype)
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.kv_heads
    keys = jax.random.split(rng, 16)
    # gemma's (1+w) norm is identity at w=0; plain rmsnorm at w=1
    norm_init = jnp.zeros if cfg.norm == "gemma_rmsnorm" else jnp.ones

    layer = {
        "attn": {
            "wq": _dense_init(keys[0], (L, h, nh * hd), h, pd),
            "wk": _dense_init(keys[1], (L, h, nkv * hd), h, pd),
            "wv": _dense_init(keys[2], (L, h, nkv * hd), h, pd),
            "wo": _dense_init(keys[3], (L, nh * hd, h), nh * hd, pd),
        },
        "ln1": {"scale": norm_init((L, h), pd)},
        "ln2": {"scale": norm_init((L, h), pd)},
    }
    if cfg.norm == "layernorm":
        layer["ln1"]["bias"] = jnp.zeros((L, h), pd)
        layer["ln2"]["bias"] = jnp.zeros((L, h), pd)
    if cfg.qk_norm:
        layer["attn"]["q_norm"] = {"scale": jnp.ones((L, nh * hd), pd)}
        layer["attn"]["k_norm"] = {"scale": jnp.ones((L, nkv * hd), pd)}
    layer["attn"].update(init_attention_extras(keys[13], cfg, L, pd))
    if cfg.post_branch_norm:
        layer["ln1_post"] = {"scale": norm_init((L, h), pd)}
        layer["ln2_post"] = {"scale": norm_init((L, h), pd)}
    if cfg.eva_window:
        from .eva import init_vectors

        layer["attn"].update(init_vectors(keys[12], cfg, pd))

    if cfg.num_experts > 0:
        E = cfg.num_experts
        layer["moe"] = {
            "router": _dense_init(keys[4], (L, h, E), h, pd),
            "w_in": _dense_init(keys[5], (L, E, h, f), h, pd),
            "w_gate": _dense_init(keys[6], (L, E, h, f), h, pd),
            "w_out": _dense_init(keys[7], (L, E, f, h), f, pd),
        }
        if cfg.activation != "silu":
            del layer["moe"]["w_gate"]
        if cfg.moe_use_residual:  # PR-MoE shared expert + mixing coefficient
            rk = jax.random.split(keys[11], 4)  # keys[4] feeds the router
            layer["moe"]["res_w_in"] = _dense_init(rk[0], (L, h, f), h, pd)
            layer["moe"]["res_w_out"] = _dense_init(rk[1], (L, f, h), f, pd)
            if cfg.activation == "silu":
                layer["moe"]["res_w_gate"] = _dense_init(rk[2], (L, h, f), h, pd)
            layer["moe"]["coef"] = _dense_init(rk[3], (L, h, 2), h, pd)
    else:
        mlp = {
            "w_in": _dense_init(keys[5], (L, h, f), h, pd),
            "w_out": _dense_init(keys[7], (L, f, h), f, pd),
        }
        if cfg.is_gated_mlp:
            mlp["w_gate"] = _dense_init(keys[6], (L, h, f), h, pd)
        layer["mlp"] = mlp

    params: Dict[str, Any] = {
        "embed": {"tokens": _dense_init(keys[8], (cfg.vocab_size, h), h, pd)},
        "layers": layer,
        "final_norm": {"scale": norm_init((h,), pd)},
    }
    if cfg.norm == "layernorm":
        params["final_norm"]["bias"] = jnp.zeros((h,), pd)
    if cfg.position == "learned":
        params["embed"]["position"] = _dense_init(keys[9], (cfg.max_seq_len, h), h, pd)
    if cfg.embed_norm:
        params["embed_norm"] = {"scale": jnp.ones((h,), pd),
                                "bias": jnp.zeros((h,), pd)}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": _dense_init(
            keys[10], (h, cfg.num_pred_heads * cfg.vocab_size), h, pd)}
    return params


def param_axes(cfg: TransformerConfig, params: Optional[Dict[str, Any]] = None
               ) -> Dict[str, Any]:
    """Logical-axes pytree matching ``init_params`` output, consumed by
    sharding rules (the zero.Init / AutoTP annotation surface).

    Pass ``params`` for HF-converted trees that carry linear biases
    (qwen2/opt/gpt-neox …): bias leaves get matching axes entries."""
    if cfg.kda_pattern:
        from .kimi_linear import param_axes as kda_axes

        return kda_axes(cfg)
    if cfg.kv_lora_rank:
        from .latent_sparse import param_axes as latent_axes

        return latent_axes(cfg)
    if cfg.mlp_layer_types:
        from .mixed_ffn import param_axes as mixed_axes

        return mixed_axes(cfg)
    if cfg.mixer_pattern:
        from .ssm_hybrid import param_axes as hybrid_axes

        return hybrid_axes(cfg)
    ln = {"scale": ("layers", "embed")}
    if cfg.norm == "layernorm":
        ln = {"scale": ("layers", "embed"), "bias": ("layers", "embed")}
    layer = {
        "attn": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
        },
        "ln1": dict(ln),
        "ln2": dict(ln),
    }
    if cfg.qk_norm:
        layer["attn"]["q_norm"] = {"scale": ("layers", "heads")}
        layer["attn"]["k_norm"] = {"scale": ("layers", "kv_heads")}
    layer["attn"].update(attention_extras_axes(cfg))
    if cfg.post_branch_norm:
        layer["ln1_post"] = dict(ln)
        layer["ln2_post"] = dict(ln)
    if cfg.num_experts > 0:
        moe = {
            "router": ("layers", "embed", None),
            "w_in": ("layers", "expert", "embed", "mlp"),
            "w_out": ("layers", "expert", "mlp", "embed"),
        }
        if cfg.activation == "silu":
            moe["w_gate"] = ("layers", "expert", "embed", "mlp")
        if cfg.moe_use_residual:
            moe["res_w_in"] = ("layers", "embed", "mlp")
            moe["res_w_out"] = ("layers", "mlp", "embed")
            if cfg.activation == "silu":
                moe["res_w_gate"] = ("layers", "embed", "mlp")
            moe["coef"] = ("layers", "embed", None)
        layer["moe"] = moe
    else:
        mlp = {"w_in": ("layers", "embed", "mlp"), "w_out": ("layers", "mlp", "embed")}
        if cfg.is_gated_mlp:
            mlp["w_gate"] = ("layers", "embed", "mlp")
        layer["mlp"] = mlp

    fn = {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        fn["bias"] = ("embed",)
    axes: Dict[str, Any] = {
        "embed": {"tokens": ("vocab", "embed")},
        "layers": layer,
        "final_norm": fn,
    }
    if cfg.position == "learned":
        axes["embed"]["position"] = ("seq", "embed")
    if cfg.embed_norm:
        axes["embed_norm"] = {"scale": ("embed",), "bias": ("embed",)}
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"w": ("embed", "vocab")}
        if params is not None and "b" in params.get("lm_head", {}):
            axes["lm_head"]["b"] = ("vocab",)

    if params is not None:  # add axes for optional bias leaves
        bias_axes = {
            "bq": ("layers", "heads"), "bk": ("layers", "kv_heads"),
            "bv": ("layers", "kv_heads"), "bo": ("layers", "embed"),
            "b_gate": ("layers", "mlp"), "b_in": ("layers", "mlp"),
            "b_out": ("layers", "embed"),
        }
        for blk in ("attn", "mlp"):
            have = params.get("layers", {}).get(blk, {})
            for key, ax in bias_axes.items():
                if key in have and key not in layer.get(blk, {}):
                    layer.setdefault(blk, {})[key] = ax
        # trees that already carry LoRA nodes (adapter checkpoints loaded for
        # unmerged serving) need the per-node axes expansion
        axes = expand_axes_for_lora(axes, params)
    return axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _norm(x, p, kind: str, eps: float):
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x * lax.rsqrt(var + eps).astype(x.dtype)
        return y * p["scale"].astype(x.dtype)
    if kind == "gemma_rmsnorm":  # zero-init weights scale by (1 + w)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x * lax.rsqrt(var + eps).astype(x.dtype)
        return y * (1.0 + p["scale"].astype(x.dtype))
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True).astype(x.dtype)
    y = (x - mean) * lax.rsqrt(var + eps).astype(x.dtype)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def rope_table(seq_len: int, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)  # (seq, head_dim/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def yarn_inv_freq(head_dim: int, rope: RopeParams) -> jax.Array:
    """Static YaRN's ``head_dim / 2`` inverse frequencies: frequency ``j``
    keeps ``theta ** (-2j / head_dim)`` below ``low`` (it turns more than
    ``beta_fast`` times in the original context), is divided by ``factor``
    above ``high`` (fewer than ``beta_slow`` turns), and is blended linearly
    between."""
    def turns_at(n):  # the dimension that turns n times in the old context
        return head_dim * math.log(
            rope.original_max_position_embeddings / (2 * math.pi * n)
        ) / (2 * math.log(rope.theta))

    low = max(math.floor(turns_at(rope.beta_fast)), 0)
    high = min(math.ceil(turns_at(rope.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # as the published code: no division by zero
    j = jnp.arange(head_dim // 2, dtype=jnp.float32)
    extra = rope.theta ** (-2.0 * j / head_dim)
    keep = 1.0 - jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return keep * extra + (1.0 - keep) * extra / rope.factor


def rope_table_of(seq_len: int, head_dim: int, rope: RopeParams
                  ) -> Tuple[jax.Array, jax.Array]:
    """``rope_table`` for one kind of layer: plain RoPE exactly as
    ``rope_table`` makes it, or YaRN's frequencies with cos and sin scaled by
    its ``attention_factor``."""
    if not rope.factor:
        return rope_table(seq_len, head_dim, rope.theta)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, yarn_inv_freq(head_dim, rope))
    return (jnp.cos(freqs) * rope.attention_factor,
            jnp.sin(freqs) * rope.attention_factor)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D). Rotates pairs (even, odd) of the head dim.
    (TPU-equivalent of the reference's ``apply_rotary_pos_emb.cu``.)

    Partial rotary (gpt-neox/phi): when the table covers fewer than D/2
    frequencies, only the first 2*len(freqs) dims rotate; the rest pass
    through unchanged."""
    return _rotate_pairs(x, cos, sin, (None, slice(None), None, slice(None)))


def rope_at(x: jax.Array, cos_full: jax.Array, sin_full: jax.Array,
            positions: jax.Array) -> jax.Array:
    """``apply_rope`` for rows that each sit at a position of their own:
    ``x (..., H, D)`` with ``positions`` of shape ``x.shape[:-2]`` into the
    tables of ``rope_table`` (the serving step programs: ragged tokens, one
    token a row, ``Q`` positions a row)."""
    return _rotate_pairs(x, cos_full[positions], sin_full[positions],
                         (..., None, slice(None)))


def _rotate_pairs(x, cos, sin, expand):
    """The rotation itself; ``cos[expand]`` broadcasts against
    ``x[..., ::2]`` (indexed here, after the slices of ``x``, so that
    ``apply_rope`` traces the operations in the order it always did), and
    dims past twice its width pass through."""
    rot = 2 * cos.shape[-1]
    xr = x[..., :rot]
    x1, x2 = xr[..., ::2], xr[..., 1::2]
    c = cos[expand].astype(x.dtype)
    s = sin[expand].astype(x.dtype)
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], axis=-1)


def alibi_slopes(n_heads: int) -> jax.Array:
    """Per-head ALiBi slopes (Press et al.; matches HF
    ``build_alibi_tensor``): powers of 2^(-8/n) for the nearest power-of-two
    head count, with interleaved extras for non-power-of-two counts."""
    import math as _m

    p2 = 2 ** _m.floor(_m.log2(n_heads))
    base = 2.0 ** (-(2.0 ** -(_m.log2(p2) - 3)))
    slopes = [base ** (i + 1) for i in range(p2)]
    if p2 != n_heads:
        extra_base = 2.0 ** (-(2.0 ** -(_m.log2(2 * p2) - 3)))
        slopes += [extra_base ** (i + 1)
                   for i in range(0, 2 * (n_heads - p2), 2)]
    return jnp.asarray(slopes, jnp.float32)


def alibi_bias(n_heads: int, seq_len: int) -> jax.Array:
    """(H, 1, S) additive attention-logit bias: slope · key-position.  Per
    query row this differs from the relative form by a constant, which
    softmax cancels — exactly HF bloom's formulation."""
    return alibi_slopes(n_heads)[:, None, None] * \
        jnp.arange(seq_len, dtype=jnp.float32)[None, None, :]


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
                  segment_ids: Optional[jax.Array] = None,
                  bias: Optional[jax.Array] = None) -> jax.Array:
    """Reference einsum attention (B, S, H, D). GQA-aware.  ``bias``
    broadcasts onto the (B, H, S, T) logits (ALiBi, padding masks)."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    if KV != H:  # grouped-query: repeat kv heads
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(D)
    logits = jnp.einsum("bshd,bthd->bhst", q, k) * scale
    logits = logits.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None] == segment_ids[:, None, None, :]
        logits = jnp.where(seg, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v)


AttentionFn = Callable[..., jax.Array]


def resolve_attention(impl: str) -> AttentionFn:
    """Select the attention implementation by name.

    'xla'     — einsum reference path (always correct, any shape)
    'flash'   — Pallas fused kernel (ops/pallas/flash_attention.py)
    'ulysses' — all-to-all sequence parallelism over the sp axis
    'ring'    — ring attention (blockwise, ppermute over the sp axis)
    """
    if impl == "xla":
        return xla_attention
    if impl == "flash":
        from ..ops.pallas.flash_attention import flash_attention

        return flash_attention
    if impl == "ulysses":
        from ..sequence.ulysses import ulysses_attention

        return ulysses_attention
    if impl == "ring":
        from ..sequence.ring_attention import ring_attention

        return ring_attention
    raise ValueError(f"unknown attn_impl {impl!r}")


#: activation sharding pinned around the embedding gather.  On
#: tensor_parallel × sequence_parallel meshes GSPMD's partitioning of a
#: gather whose OPERAND is vocab(tp)-sharded and whose INDICES are
#: seq(sp)-sharded miscompiles — the embedding lookup and the loss-side
#: ``take_along_axis`` both have that shape, and the result surfaced as NaN
#: loss (ROADMAP tp×sp item).  The fix is two explicit constraints: the
#: index tensors (tiny int32 ``(batch, seq)``) are replicated across sp
#: before the gather, and the embedding-gather output is re-anchored to the
#: sp-sharded activation layout so downstream propagation is unchanged.
#: The engine pins both for the duration of each traced step and clears
#: them afterwards (mirroring ``set_param_streaming``, plus the clear —
#: the shardings name one engine's mesh and must not outlive its call);
#: inference clears them at construction too.
_EMBED_ACTIVATION_SHARDING = None
_GATHER_INDEX_SHARDING = None


def set_embed_activation_sharding(sharding, index_sharding=None) -> None:
    """Install (or clear, with ``None``) the activation sharding applied to
    the embedding-gather output whenever it is a ``(batch, seq, embed)``
    activation, and the sharding applied to ``(batch, seq)`` int gather
    indices (token ids, shifted labels) right before vocab-dim gathers."""
    global _EMBED_ACTIVATION_SHARDING, _GATHER_INDEX_SHARDING
    _EMBED_ACTIVATION_SHARDING = sharding
    _GATHER_INDEX_SHARDING = index_sharding


def embed_tokens(params, token_ids, cfg: TransformerConfig,
                 position_ids=None):
    """Shared embedding preamble — token lookup, gemma sqrt(d) normalizer,
    learned positions, bloom embedding layernorm.  EVERY forward path
    (training, pipeline, inference v1/v2) starts here, so an embedding-level
    architecture switch cannot silently diverge between engines.
    ``position_ids`` defaults to arange over the trailing token axis."""
    dt = jnp.dtype(cfg.dtype)
    if _GATHER_INDEX_SHARDING is not None and token_ids.ndim == 2:
        token_ids = jax.lax.with_sharding_constraint(
            token_ids, _GATHER_INDEX_SHARDING)
    x = params["embed"]["tokens"].astype(dt)[token_ids]
    if _EMBED_ACTIVATION_SHARDING is not None and x.ndim == 3:
        x = jax.lax.with_sharding_constraint(x, _EMBED_ACTIVATION_SHARDING)
    if cfg.embed_scale_by_sqrt_dim:
        x = x * jnp.asarray(cfg.hidden_size ** 0.5, dt)
    if cfg.position == "learned":
        if position_ids is None:
            position_ids = jnp.arange(token_ids.shape[-1])
        x = x + params["embed"]["position"].astype(dt)[position_ids]
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm"], "layernorm", cfg.norm_eps)
    return x


def _lin(x, p, w_key, b_key):
    w = p[w_key]
    if isinstance(w, LoRAWeight):  # frozen (possibly quantized) base + LoRA
        y = lora_forward(x, w)
    elif isinstance(w, QuantizedWeight):  # W8A16/W4A16 in-kernel dequant
        y = mixed_gemm_frozen(x, w)
    elif isinstance(w, LayerOf):  # the same, on the layer stack in place
        y = mixed_gemm_frozen(x, w.stack, w.layer)
    else:
        y = x @ w.astype(x.dtype)
    if b_key in p:
        y = y + p[b_key].astype(x.dtype)
    return y


def qk_norm(x, p, which: str, cfg: TransformerConfig):
    """The q/k norm of ``cfg.qk_norm`` models, the one place it is written:
    RMSNorm of a q or k projection (``which``: ``"q_norm"`` / ``"k_norm"``)
    over the whole ``(..., heads * head_dim)`` width, before the split into
    heads and before RoPE.  Every layer body (training forward, v1 engine,
    the v2 engine's mixed, decode and verify steps) calls it on q and on k;
    for a model without the norm it is the identity and traces nothing."""
    if not cfg.qk_norm:
        return x
    with jax.named_scope("qk_norm"):
        return _norm(x, p[which], "rmsnorm", cfg.norm_eps)


def _attention_block(x, p, cfg: TransformerConfig, cos, sin, attn_fn: AttentionFn):
    # named scopes feed the flops profiler's per-module census
    with jax.named_scope("attn"):
        B, S, h = x.shape
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        dt = x.dtype
        q = qk_norm(_lin(x, p, "wq", "bq"), p, "q_norm", cfg
                    ).reshape(B, S, nh, hd)
        k = qk_norm(_lin(x, p, "wk", "bk"), p, "k_norm", cfg
                    ).reshape(B, S, nkv, hd)
        v = _lin(x, p, "wv", "bv").reshape(B, S, nkv, hd)
        if cfg.qk_norm_per_head:  # over each head's own width
            with jax.named_scope("qk_norm"):
                q = _norm(q, p["q_norm"], "rmsnorm", cfg.norm_eps)
                k = _norm(k, p["k_norm"], "rmsnorm", cfg.norm_eps)
        if cfg.position == "rope" and cos is not None:  # None: no position
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        if cfg.position == "alibi":
            # additive logit bias rides the einsum path (flash+bias belongs
            # to the evoformer-style biased kernel; alibi models use 'xla')
            o = attn_fn(q, k, v, causal=True,
                        bias=alibi_bias(nh, S)[None])
        else:
            o = attn_fn(q, k, v, causal=True)
        o = o.reshape(B, S, nh * hd)
        if cfg.attn_gate:
            with jax.named_scope("attn_gate"):
                o = o * jax.nn.sigmoid(_lin(x, p, "wg", "bg").astype(
                    jnp.float32)).astype(dt)
        return _lin(o, p, "wo", "bo")


def apply_activation(x, kind: str):
    """Shared activation dispatch (decoder MLPs, encoder blocks, heads)."""
    if kind == "relu":
        return jax.nn.relu(x)
    if kind == "gelu_exact":  # erf form (falcon/gpt-neox/phi/bert)
        return jax.nn.gelu(x, approximate=False)
    if kind == "gelu":  # tanh approximation (gpt2's gelu_new, bloom)
        return jax.nn.gelu(x, approximate=True)
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "relu2":  # nemotron_h's experts
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {kind!r}")


def _mlp_block(x, p, cfg: TransformerConfig):
    with jax.named_scope("mlp"):
        if cfg.is_gated_mlp:
            gate = apply_activation(_lin(x, p, "w_gate", "b_gate"),
                                    cfg.activation)
            return _lin(gate * _lin(x, p, "w_in", "b_in"), p,
                        "w_out", "b_out")
        mid = apply_activation(_lin(x, p, "w_in", "b_in"), cfg.activation)
        return _lin(mid, p, "w_out", "b_out")


def _remat_policy(name: str):
    pols = {
        "everything": None,  # no remat
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "dots_with_no_batch_dims_saveable":
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        # save only the (tagged) attention outputs: backward re-runs the cheap
        # elementwise/matmul parts but never the O(S²)-FLOP attention kernel
        "save_attn": jax.checkpoint_policies.save_only_these_names("attn_out"),
        # additionally save the MLP output (more memory, less recompute)
        "save_attn_mlp": jax.checkpoint_policies.save_only_these_names(
            "attn_out", "mlp_out"),
    }
    if name not in pols:
        raise ValueError(f"unknown remat policy {name!r}")
    return pols[name]


def attention_of_kind(cfg: TransformerConfig, kind: str, seq_len: int,
                      attn_fn: Optional[AttentionFn] = None):
    """→ (the attention function, (cos, sin)) of a layer of ``kind``
    ("sliding" | "full"): ``attn_fn`` or the config's implementation, under
    the kind's window; the kind's RoPE table, ``(None, None)`` for a model or
    a kind without rotation."""
    fn = attn_fn
    if fn is None:
        fn = resolve_attention(cfg.attn_impl)
        if cfg.window_of(kind) > 0:
            if cfg.attn_impl != "flash":
                raise ValueError("sliding_window requires attn_impl='flash'")
            fn = partial(fn, window=cfg.window_of(kind))
    rope = (rope_table_of(seq_len, cfg.rot_dim, cfg.rope_of(kind))
            if cfg.position == "rope" and kind not in cfg.nope_layer_kinds
            else (None, None))
    return fn, rope


def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   cfg: TransformerConfig,
                   attn_fn: Optional[AttentionFn] = None,
                   moe_fn: Optional[Callable] = None) -> jax.Array:
    """tokens (B, S) int32 → final hidden states (B, S, H) after final norm.

    ``attn_fn``/``moe_fn`` are injection points for Pallas flash attention,
    Ulysses/ring sequence parallelism and expert-parallel MoE dispatch.
    """
    if cfg.kda_pattern:
        from .kimi_linear import NOT_TRAINED as KDA_NOT_TRAINED

        raise NotImplementedError(KDA_NOT_TRAINED)
    if cfg.kv_lora_rank:
        from .latent_sparse import forward_hidden as latent_hidden

        return latent_hidden(params, tokens, cfg, attn_fn)
    if cfg.mlp_layer_types:
        from .mixed_ffn import forward_train as mixed_train

        return mixed_train(params, tokens, cfg, attn_fn)[0]
    if cfg.mixer_pattern:
        from .ssm_hybrid import forward_hidden as hybrid_hidden

        return hybrid_hidden(params, tokens, cfg, attn_fn=attn_fn)
    if cfg.eva_window:
        from .eva import NOT_TRAINED

        raise NotImplementedError(NOT_TRAINED)
    dt = jnp.dtype(cfg.dtype)
    if cfg.position == "alibi" and cfg.attn_impl != "xla":
        # the additive logit bias rides the einsum path only; the Pallas
        # flash/ring kernels take no bias operand (mirror of the
        # sliding_window constraint below)
        raise ValueError("position='alibi' requires attn_impl='xla'")
    # one (attention function, RoPE table) a kind of layer in the period
    period = cfg.layer_period
    B, S = tokens.shape
    attn_fns, ropes = zip(*(attention_of_kind(cfg, kind, S, attn_fn)
                            for kind in period))

    with jax.named_scope("embed"):
        x = embed_tokens(params, tokens, cfg)

    from jax.ad_checkpoint import checkpoint_name

    def layer_body(carry, layer_params, kind=0):
        attn_fn, (cos, sin) = attn_fns[kind], ropes[kind]
        # ZeRO-Infinity param streaming: when the engine enabled offload_param,
        # this layer's slice rides host→device DMA here (and the remat'd
        # backward re-streams it); otherwise identity.
        from ..runtime.zero.param_offload import maybe_stream_in

        layer_params = maybe_stream_in(layer_params)
        h = carry
        a_in = _norm(h, layer_params["ln1"], cfg.norm, cfg.norm_eps)
        attn_out = _attention_block(a_in, layer_params["attn"], cfg, cos, sin,
                                    attn_fn)
        if cfg.post_branch_norm:
            attn_out = _norm(attn_out, layer_params["ln1_post"], cfg.norm,
                             cfg.norm_eps)
        if cfg.parallel_residual:
            # falcon/gpt-neox/phi-2: both branches read the SAME input h
            m_in = _norm(h, layer_params["ln2"], cfg.norm, cfg.norm_eps)
        else:
            h = h + checkpoint_name(attn_out, "attn_out")
            m_in = _norm(h, layer_params["ln2"], cfg.norm, cfg.norm_eps)
        if cfg.num_experts > 0:
            if moe_fn is None:
                from ..moe.layer import dense_moe_block

                mlp_out = dense_moe_block(m_in, layer_params["moe"], cfg)
            else:
                mlp_out = moe_fn(m_in, layer_params["moe"], cfg)
        else:
            mlp_out = _mlp_block(m_in, layer_params["mlp"], cfg)
        if cfg.post_branch_norm:
            mlp_out = _norm(mlp_out, layer_params["ln2_post"], cfg.norm,
                            cfg.norm_eps)
        if cfg.parallel_residual:
            h = h + checkpoint_name(attn_out, "attn_out") \
                + checkpoint_name(mlp_out, "mlp_out")
        else:
            h = h + checkpoint_name(mlp_out, "mlp_out")
        return h, None

    policy = _remat_policy(cfg.remat_policy)
    body = layer_body
    if policy is not None:
        body = jax.checkpoint(layer_body, policy=policy, prevent_cse=False,
                              static_argnums=(2,) if len(period) > 1 else ())

    with jax.named_scope("layers"):
        if len(period) == 1:
            x, _ = lax.scan(body, x, params["layers"])
        else:
            # kinds differ: the scan steps over periods of the pattern, the
            # layers of one period unrolled inside with their kinds static
            def period_body(carry, period_params):
                for i in range(len(period)):
                    carry, _ = body(
                        carry, jax.tree.map(lambda a: a[i], period_params), i)
                return carry, None

            x, _ = lax.scan(period_body, x, jax.tree.map(
                lambda a: a.reshape((-1, len(period)) + a.shape[1:]),
                params["layers"]))

    return _norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)


def forward(params: Dict[str, Any], tokens: jax.Array, cfg: TransformerConfig,
            attn_fn: Optional[AttentionFn] = None,
            moe_fn: Optional[Callable] = None) -> jax.Array:
    """tokens (B, S) int32 → logits (B, S, V) in compute dtype."""
    x = forward_hidden(params, tokens, cfg, attn_fn=attn_fn, moe_fn=moe_fn)
    with jax.named_scope("lm_head"):
        return lm_logits(params, x, cfg)


def lm_logits(params: Dict[str, Any], hidden: jax.Array,
              cfg: TransformerConfig) -> jax.Array:
    """Hidden state after the final norm ``(..., H)`` → logits ``(..., V)`` in
    its dtype: the head of ``forward``, of the v1 engine and of the v2
    engine's three step bodies."""
    dt = hidden.dtype
    if cfg.fp32_logits:  # the product itself in float32, not its result cast
        return jnp.matmul(hidden.astype(jnp.float32),
                          params["lm_head"]["w"].astype(jnp.float32),
                          precision=lax.Precision.HIGHEST)
    if cfg.tie_embeddings:
        return hidden @ params["embed"]["tokens"].astype(dt).T
    logits = hidden @ params["lm_head"]["w"].astype(dt)
    if "b" in params["lm_head"]:  # gpt-j ties off with a bias
        logits = logits + params["lm_head"]["b"].astype(dt)
    return logits


def next_token_logits(logits: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """The columns of ``lm_logits``' result the NEXT token is drawn from: all
    of them, or head 0's of a model with several output heads."""
    if cfg.num_pred_heads == 1:
        return logits
    return logits[..., :cfg.vocab_size]


def shift_labels(batch: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Next-token (labels, mask) from a batch, shifting in place (pad + mask
    the final position) so the sequence length is unchanged — keeps S
    divisible for sequence parallelism.  Honors explicit 'labels' and
    'loss_mask' keys.  Shared by all loss paths (dense/tiled/pipelined)."""
    tokens = batch["input_ids"]
    mask = batch.get("loss_mask")
    if "labels" in batch:
        return batch["labels"], mask
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    shift_mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:]), jnp.zeros_like(tokens[:, :1])],
        axis=1).astype(jnp.float32)
    return labels, (shift_mask if mask is None else mask * shift_mask)


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array], cfg: TransformerConfig,
            attn_fn: Optional[AttentionFn] = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal-LM cross entropy. batch: {'input_ids': (B,S)}; optional
    'labels' (shift done here when absent), optional 'loss_mask'."""
    tokens = batch["input_ids"]
    labels, mask = shift_labels(batch)
    logits = forward(params, tokens, cfg, attn_fn=attn_fn)
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if _GATHER_INDEX_SHARDING is not None and labels.ndim == 2:
        # same tp×sp gather hazard as the embedding lookup: logp is
        # vocab(tp)-sharded, labels arrive seq(sp)-sharded from the loader
        labels = jax.lax.with_sharding_constraint(
            labels, _GATHER_INDEX_SHARDING)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    correct = (logits.argmax(-1) == labels).astype(jnp.float32)
    if mask is None:
        loss = nll.mean()
        denom = float(nll.size)
        acc = correct.mean()
    else:
        mask = mask.astype(jnp.float32)
        denom = jnp.maximum(mask.sum(), 1.0)
        loss = (nll * mask).sum() / denom
        acc = (correct * mask).sum() / denom
    return loss, {"loss": loss, "accuracy": acc, "tokens": jnp.asarray(denom, jnp.float32)}
