"""Inference engine v1.

Capability analogue of the reference's ``deepspeed/inference/engine.py``
(``InferenceEngine:40``): wrap a model for generation with tensor-parallel
sharding and fused decode.  TPU-native: a jitted prefill step + a jitted
single-token decode step over a static KV cache (static shapes keep XLA
happy); TP sharding comes from the same logical-axis rules as training.

The v2-style ragged/continuous-batching engine (paged KV cache + scheduler)
lives in ``deepspeed_tpu/inference/v2/``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import transformer as tfm
from ..parallel.topology import MeshTopology
from ..runtime.config import MeshConfig, load_config
from ..runtime.zero.sharding import rules_for_params, sharding_for_tree
from .v2.programs import KV, kind_of


@dataclasses.dataclass
class InferenceConfig:
    tensor_parallel_size: int = 1
    max_seq_len: int = 2048
    max_batch_size: int = 8
    dtype: str = "bfloat16"
    # weight-only quantization (W8A16 / W4A16 via the Pallas mixed GEMM);
    # reference: deepspeed/inference/quantization group-wise weight quant
    quantize_bits: int = 0
    quantize_group: int = 256


def _kv_cache_init(cfg: tfm.TransformerConfig, batch: int, max_len: int, dtype):
    L, kvh, hd = cfg.num_layers, cfg.kv_heads, cfg.head_dim
    shape = (L, batch, max_len, kvh, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "length": jnp.zeros((batch,), jnp.int32)}


def forward_cached(params, tokens, cache, start_pos, cfg: tfm.TransformerConfig):
    """Forward over ``tokens`` (B, T) with KV cache starting at ``start_pos``.

    Returns (logits_last, new_cache).  Works for prefill (T = prompt len) and
    decode (T = 1).  Causal masking accounts for cache offset.
    """
    dt = jnp.dtype(cfg.dtype)
    B, T = tokens.shape
    max_len = cache["k"].shape[2]

    x = tfm.embed_tokens(params, tokens, cfg,
                         position_ids=start_pos + jnp.arange(T))
    cos_full, sin_full = (None, None)
    if cfg.position == "rope":
        cos_full, sin_full = tfm.rope_table(max_len, cfg.rot_dim, cfg.rope_theta)

    def layer_body(carry, inputs):
        h, = carry
        layer_params, layer_k, layer_v = inputs
        a_in = tfm._norm(h, layer_params["ln1"], cfg.norm, cfg.norm_eps)
        nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
        ap = layer_params["attn"]
        q = tfm.qk_norm(tfm._lin(a_in, ap, "wq", "bq"), ap, "q_norm", cfg
                        ).reshape(B, T, nh, hd)
        k = tfm.qk_norm(tfm._lin(a_in, ap, "wk", "bk"), ap, "k_norm", cfg
                        ).reshape(B, T, nkv, hd)
        v = tfm._lin(a_in, ap, "wv", "bv").reshape(B, T, nkv, hd)
        if cfg.position == "rope":
            cos = jax.lax.dynamic_slice_in_dim(cos_full, start_pos, T)
            sin = jax.lax.dynamic_slice_in_dim(sin_full, start_pos, T)
            q = tfm.apply_rope(q, cos, sin)
            k = tfm.apply_rope(k, cos, sin)
        # write new kv into the cache at start_pos
        new_k = jax.lax.dynamic_update_slice(layer_k, k.astype(layer_k.dtype),
                                             (0, start_pos, 0, 0))
        new_v = jax.lax.dynamic_update_slice(layer_v, v.astype(layer_v.dtype),
                                             (0, start_pos, 0, 0))
        # attend over cache[0:start_pos+T]
        kk, vv = new_k, new_v  # (B, max_len, KV, D)
        if nkv != nh:
            rep = nh // nkv
            kk = jnp.repeat(kk, rep, axis=2)
            vv = jnp.repeat(vv, rep, axis=2)
        import math as _math

        logits = jnp.einsum("bthd,bshd->bhts", q, kk) / _math.sqrt(hd)
        logits = logits.astype(jnp.float32)
        key_pos = jnp.arange(max_len)[None, None, None, :]
        qry_pos = (start_pos + jnp.arange(T))[None, None, :, None]
        if cfg.position == "alibi":
            # slope · key-position, identical to the training-side formulation
            # (per-query-row constants cancel in softmax)
            logits = logits + tfm.alibi_slopes(nh)[None, :, None, None] * \
                key_pos.astype(jnp.float32)
        mask = key_pos <= qry_pos
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(dt)
        o = jnp.einsum("bhts,bshd->bthd", probs, vv).reshape(B, T, nh * hd)
        attn_out = tfm._lin(o, ap, "wo", "bo")

        m_src = h if cfg.parallel_residual else h + attn_out
        m_in = tfm._norm(m_src, layer_params["ln2"], cfg.norm, cfg.norm_eps)
        if cfg.num_experts > 0:
            from ..moe.dropless import serving_moe_block

            mlp_out, _ = serving_moe_block(m_in, layer_params["moe"], cfg)
        else:
            mlp_out = tfm._mlp_block(m_in, layer_params["mlp"], cfg)
        h = (h + attn_out + mlp_out) if cfg.parallel_residual \
            else (m_src + mlp_out)
        return (h,), (new_k, new_v)

    (x,), (new_ks, new_vs) = jax.lax.scan(
        layer_body, (x,), (params["layers"], cache["k"], cache["v"]))

    x = tfm._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    logits = tfm.lm_logits(params, x[:, -1], cfg)
    new_cache = {"k": new_ks, "v": new_vs,
                 "length": cache["length"] + T}
    return logits.astype(jnp.float32), new_cache


class InferenceEngine:
    """Reference: ``InferenceEngine`` — ``.generate()`` with TP sharding."""

    def __init__(self, model=None, config=None, model_config=None, params=None,
                 **kwargs):
        if isinstance(config, dict):
            icfg = InferenceConfig(**{k: v for k, v in config.items()
                                      if k in InferenceConfig.__dataclass_fields__})
        elif isinstance(config, InferenceConfig):
            icfg = config
        else:
            icfg = InferenceConfig()
        self.config = icfg

        if model is not None and hasattr(model, "params"):
            # ModelSpec-style bundle; model_config must be the TransformerConfig
            params = model.params
        if model_config is None or params is None:
            raise ValueError("pass model_config=TransformerConfig and params=")
        if (getattr(model_config, "num_experts", 0) > 0 and
                getattr(model_config, "moe_routing", "capacity") == "expert_choice"):
            raise ValueError(
                "expert_choice routing is non-causal (experts pick top-C "
                "tokens over the whole sequence) — autoregressive decode "
                "with it is incoherent; serve experts trained with top-k "
                "routing (dataclasses.replace(cfg, moe_routing='dropless'))")
        kind = kind_of(model_config)
        if (kind is not KV or len(model_config.layer_period) > 1
                or model_config.rope_params):
            raise NotImplementedError(
                "the v1 engine serves attention + FFN layers of one kind with "
                f"plain RoPE over one K/V cache; it was handed {kind.name}"
                + (" with layer_types or rope_params (window and global "
                   "layers, YaRN)" if kind is KV else "")
                + ", which the v2 engine (inference/v2) serves")
        self.model_config = dataclasses.replace(model_config, dtype=icfg.dtype)
        # a training engine in the same process may have pinned the tp×sp
        # gather anchors — they name mesh axes this engine's mesh lacks
        tfm.set_embed_activation_sharding(None, None)
        # dp absorbs the remaining devices (params replicated across it)
        self.topo = MeshTopology.from_config(
            MeshConfig(tensor_parallel_size=icfg.tensor_parallel_size))
        rules = rules_for_params(0, self.topo)
        shardings = sharding_for_tree(params,
                                      tfm.param_axes(self.model_config,
                                                     params=params),
                                      rules, self.topo)
        from ..linear.optimized_linear import has_lora

        if has_lora(params) and icfg.quantize_bits:
            # unmerged LoRA serving keeps the (possibly already-quantized)
            # base + adapters as-is; the mixed-GEMM WxA16 path doesn't know
            # LoRAWeight nodes — merge first for a quantized artifact
            raise ValueError(
                "quantize_bits with an unmerged LoRA tree is not supported: "
                "export merged weights (engine.export_merged_weights) and "
                "serve those quantized, or serve the LoRA tree with "
                "quantize_bits=0")
        if icfg.quantize_bits:
            # quantize on host FIRST: the chip never holds the fp weights
            # (a model that only fits quantized must not OOM during init)
            from .quantization import quantize_on_host, shardings_for_quantized

            params = quantize_on_host(params, icfg.quantize_bits,
                                      icfg.quantize_group)
            shardings = shardings_for_quantized(params, shardings)
        self.params = jax.tree.map(lambda x, s: jax.device_put(jnp.asarray(x), s),
                                   params, shardings)

        self._prefill = jax.jit(partial(forward_cached, cfg=self.model_config),
                                static_argnames=())
        self._decode = jax.jit(partial(forward_cached, cfg=self.model_config))

    def generate(self, input_ids: np.ndarray, max_new_tokens: int = 32,
                 temperature: float = 0.0, seed: int = 0,
                 eos_token_id: Optional[int] = None) -> np.ndarray:
        """Greedy / temperature sampling. input_ids: (B, T_prompt) int32."""
        tokens = jnp.asarray(input_ids, jnp.int32)
        B, T = tokens.shape
        max_len = min(self.config.max_seq_len,
                      T + max_new_tokens)
        cache = _kv_cache_init(self.model_config, B, max_len,
                               jnp.dtype(self.config.dtype))
        rng = jax.random.PRNGKey(seed)

        logits, cache = self._prefill(self.params, tokens, cache, 0)
        out = [tokens]
        cur = self._sample(logits, rng, temperature)
        out.append(cur[:, None])
        finished = jnp.zeros((B,), bool)
        for i in range(max_new_tokens - 1):
            rng, step_rng = jax.random.split(rng)
            pos = T + i
            if pos >= max_len:
                break
            logits, cache = self._decode(self.params, cur[:, None], cache, pos)
            cur = self._sample(logits, step_rng, temperature)
            if eos_token_id is not None:
                finished = finished | (cur == eos_token_id)
                cur = jnp.where(finished, eos_token_id, cur)
            out.append(cur[:, None])
            if eos_token_id is not None and bool(finished.all()):
                break
        return np.asarray(jnp.concatenate(out, axis=1))

    @staticmethod
    def _sample(logits: jax.Array, rng: jax.Array, temperature: float) -> jax.Array:
        if temperature <= 0.0:
            return logits.argmax(-1).astype(jnp.int32)
        return jax.random.categorical(rng, logits / temperature).astype(jnp.int32)


class EncoderInferenceEngine:
    """Encoder-model serving (BERT family) — the reference's encoder
    kernel-injection path (``module_inject/containers/bert.py:30``).

    No KV cache or decode loop: one jitted bidirectional forward, TP-sharded
    by the encoder's logical axes.  ``encode()`` returns hidden states,
    ``mlm_logits()`` the masked-LM head, ``pooled()`` the [CLS] pooler."""

    def __init__(self, model_config, params, config=None, **kwargs):
        from ..models import encoder as enc

        if isinstance(config, dict):
            icfg = InferenceConfig(**{k: v for k, v in config.items()
                                      if k in InferenceConfig.__dataclass_fields__})
        elif isinstance(config, InferenceConfig):
            icfg = config
        else:
            icfg = InferenceConfig()
        self.config = icfg
        self.model_config = dataclasses.replace(model_config, dtype=icfg.dtype)
        self._enc = enc
        self.topo = MeshTopology.from_config(
            MeshConfig(tensor_parallel_size=icfg.tensor_parallel_size))
        rules = rules_for_params(0, self.topo)
        shardings = sharding_for_tree(
            params, enc.param_axes(self.model_config, params=params),
            rules, self.topo)
        self.params = jax.tree.map(
            lambda x, s: jax.device_put(jnp.asarray(x), s), params, shardings)
        cfg = self.model_config
        self._encode = jax.jit(partial(enc.encode, cfg=cfg))
        self._mlm = jax.jit(partial(enc.mlm_logits, cfg=cfg))
        self._pooled = (jax.jit(partial(enc.pooled_output, cfg=cfg))
                        if "pooler" in params else None)

    def _args(self, input_ids, attention_mask, token_type_ids):
        ids = jnp.asarray(input_ids, jnp.int32)
        am = None if attention_mask is None else jnp.asarray(attention_mask)
        tt = None if token_type_ids is None else jnp.asarray(token_type_ids,
                                                             jnp.int32)
        return ids, am, tt

    def encode(self, input_ids, attention_mask=None, token_type_ids=None):
        ids, am, tt = self._args(input_ids, attention_mask, token_type_ids)
        return np.asarray(self._encode(self.params, ids,
                                       attention_mask=am, token_type_ids=tt))

    def mlm_logits(self, input_ids, attention_mask=None, token_type_ids=None):
        if "mlm" not in self.params:
            raise ValueError("model has no MLM head (converted from a bare "
                             "BertModel?)")
        ids, am, tt = self._args(input_ids, attention_mask, token_type_ids)
        return np.asarray(self._mlm(self.params, ids,
                                    attention_mask=am, token_type_ids=tt))

    def pooled(self, input_ids, attention_mask=None, token_type_ids=None):
        if self._pooled is None:
            raise ValueError("model has no pooler")
        ids, am, tt = self._args(input_ids, attention_mask, token_type_ids)
        return np.asarray(self._pooled(self.params, ids,
                                       attention_mask=am, token_type_ids=tt))
