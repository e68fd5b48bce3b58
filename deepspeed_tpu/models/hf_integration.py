"""HuggingFace model integration (AutoTP role).

Capability analogue of the reference's ``module_inject/auto_tp.py`` +
``inference/v2/checkpoint`` HF loading: map HF transformer checkpoints
(LLaMA / GPT-2 family state dicts) onto this framework's param pytree, with
tensor-parallel sharding applied by the usual logical-axis rules — checkpoint
-level AutoTP instead of nn.Module surgery (there are no modules to patch in
a functional model zoo).

Also provides the reverse export so trained params can be saved back into an
HF-compatible state dict (the ``save_16bit_model`` / zero_to_fp32 role).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import numpy as np

from . import transformer as tfm


def _getter(hf_config) -> Callable:
    return (hf_config.get if isinstance(hf_config, dict)
            else lambda k, d=None: getattr(hf_config, k, d))


def config_from_hf(hf_config) -> tfm.TransformerConfig:
    """Map an HF config object/dict to a TransformerConfig.

    The architecture map (reference role: ``module_inject/containers/`` — one
    policy per HF architecture, ``replace_module.py:189``): each supported
    ``model_type`` contributes its structural switches (norm flavor,
    activation, residual topology, rotary fraction, fused layouts) on top of
    the shared decoder schema.
    """
    get = _getter(hf_config)
    model_type = get("model_type", "llama")
    if model_type == "gpt2":
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("n_embd"),
            intermediate_size=4 * get("n_embd"), num_layers=get("n_layer"),
            num_heads=get("n_head"), max_seq_len=get("n_positions", 1024),
            norm="layernorm", activation="gelu", position="learned",
            tie_embeddings=True)
    if model_type == "gpt_neox":
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            max_seq_len=get("max_position_embeddings", 2048),
            rope_theta=get("rotary_emb_base", 10000.0),
            partial_rotary_factor=get("rotary_pct", 1.0),
            parallel_residual=bool(get("use_parallel_residual", True)),
            norm="layernorm", activation="gelu_exact",
            norm_eps=get("layer_norm_eps", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", False)))
    if model_type == "falcon":
        if get("alibi", False):
            raise ValueError(
                "ALiBi Falcon variants (falcon-rw-*) are not supported — "
                "this map converts the rotary falcon family only")
        nh = get("num_attention_heads")
        if get("new_decoder_architecture", False):
            nkv = get("num_kv_heads", nh)
        else:
            nkv = 1 if get("multi_query", True) else nh
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("ffn_hidden_size") or 4 * get("hidden_size"),
            num_layers=get("num_hidden_layers"), num_heads=nh,
            num_kv_heads=nkv,
            max_seq_len=get("max_position_embeddings", 2048),
            rope_theta=get("rope_theta", 10000.0),
            parallel_residual=bool(get("parallel_attn", True)),
            norm="layernorm", activation="gelu_exact",
            norm_eps=get("layer_norm_epsilon", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    if model_type == "gptj":
        h = get("n_embd")
        hd = h // get("n_head")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=h,
            intermediate_size=get("n_inner") or 4 * h,
            num_layers=get("n_layer"), num_heads=get("n_head"),
            max_seq_len=get("n_positions", 2048),
            norm="layernorm", activation="gelu", position="rope",
            parallel_residual=True,
            partial_rotary_factor=(get("rotary_dim") or hd) / hd,
            norm_eps=get("layer_norm_epsilon", 1e-5),
            tie_embeddings=False)
    if model_type == "bloom":
        if get("apply_residual_connection_post_layernorm", False):
            raise ValueError(
                "bloom apply_residual_connection_post_layernorm=True "
                "(bloom-176b-intermediate variants) is not supported")
        h = get("hidden_size") or get("n_embed")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=h,
            intermediate_size=4 * h, num_layers=get("n_layer"),
            num_heads=get("n_head"), max_seq_len=get("seq_length", 2048),
            norm="layernorm", activation="gelu", position="alibi",
            embed_norm=True, norm_eps=get("layer_norm_epsilon", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    if model_type == "opt":
        h = get("hidden_size")
        if get("word_embed_proj_dim", h) != h:
            raise ValueError("OPT word_embed_proj_dim != hidden_size "
                             "(projected embeddings) is not supported")
        if not get("do_layer_norm_before", True):
            raise ValueError("OPT post-layernorm variant (350m) not supported")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=h,
            intermediate_size=get("ffn_dim"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            max_seq_len=get("max_position_embeddings", 2048),
            norm="layernorm", activation="relu", position="learned",
            norm_eps=1e-5,
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    if model_type == "gpt_bigcode":  # starcoder: gpt2 block + MQA
        h = get("n_embd")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=h,
            intermediate_size=get("n_inner") or 4 * h,
            num_layers=get("n_layer"), num_heads=get("n_head"),
            num_kv_heads=1 if get("multi_query", True) else get("n_head"),
            max_seq_len=get("n_positions", 2048),
            norm="layernorm", activation="gelu", position="learned",
            norm_eps=get("layer_norm_epsilon", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    if model_type == "gemma":
        # llama key schema; architecture switches: (1+w) rmsnorm, gated
        # tanh-gelu MLP, sqrt(d) embedding normalizer, explicit head_dim
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            head_dim_override=get("head_dim"),
            max_seq_len=get("max_position_embeddings", 8192),
            rope_theta=get("rope_theta", 10000.0),
            norm="gemma_rmsnorm", activation="gelu", gated_mlp=True,
            embed_scale_by_sqrt_dim=True,
            norm_eps=get("rms_norm_eps", 1e-6),
            tie_embeddings=bool(get("tie_word_embeddings", True)))
    if model_type == "phi":  # phi-1/phi-1.5/phi-2
        if get("qk_layernorm", False):
            raise ValueError(
                "phi qk_layernorm=True (per-head q/k layernorms) is not "
                "supported by the conversion")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            max_seq_len=get("max_position_embeddings", 2048),
            rope_theta=get("rope_theta", 10000.0),
            partial_rotary_factor=get("partial_rotary_factor", 0.5),
            parallel_residual=True, norm="layernorm", activation="gelu",
            norm_eps=get("layer_norm_eps", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", False)))
    if model_type == "phi3":
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            max_seq_len=get("max_position_embeddings", 4096),
            rope_theta=get("rope_theta", 10000.0),
            norm_eps=get("rms_norm_eps", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", False)))
    if model_type == "olmoe":
        # llama attention with an RMSNorm of q and k over the whole
        # projection; 64 routed SwiGLU experts of width intermediate_size,
        # dropless, gates the raw top-k probabilities unless norm_topk_prob
        if get("clip_qkv") is not None or get("attention_bias", False):
            raise ValueError("olmoe: clip_qkv / attention_bias not supported")
        return tfm.TransformerConfig(
            vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
            intermediate_size=get("intermediate_size"),
            num_layers=get("num_hidden_layers"),
            num_heads=get("num_attention_heads"),
            num_kv_heads=get("num_key_value_heads"),
            max_seq_len=get("max_position_embeddings", 4096),
            rope_theta=get("rope_theta", 10000.0),
            norm_eps=get("rms_norm_eps", 1e-5),
            tie_embeddings=bool(get("tie_word_embeddings", False)),
            num_experts=get("num_experts"),
            moe_top_k=get("num_experts_per_tok", 8),
            moe_norm_topk=bool(get("norm_topk_prob", False)),
            moe_routing="dropless", qk_norm=True)
    # llama / mistral / qwen2 / mixtral share the llama schema
    num_experts = get("num_local_experts", 0) or 0
    sliding = get("sliding_window") or 0
    if model_type == "qwen2" and not get("use_sliding_window", False):
        sliding = 0
    return tfm.TransformerConfig(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        intermediate_size=get("intermediate_size"),
        num_layers=get("num_hidden_layers"),
        num_heads=get("num_attention_heads"),
        num_kv_heads=get("num_key_value_heads"),
        max_seq_len=get("max_position_embeddings", 4096),
        rope_theta=get("rope_theta", 10000.0),
        norm_eps=get("rms_norm_eps", 1e-5),
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        sliding_window=sliding,
        attn_impl="flash" if sliding else "xla",
        num_experts=num_experts,
        moe_top_k=get("num_experts_per_tok", 2) if num_experts else 2,
    )


def _stack(tensors) -> np.ndarray:
    return np.stack([np.asarray(t) for t in tensors])


def _rope_unpermute(w_t: np.ndarray, n_heads: int, head_dim: int,
                    rot_dim: Optional[int] = None) -> np.ndarray:
    """Convert q/k projection columns from HF's half-split RoPE layout to the
    interleaved even/odd layout this repo's ``apply_rope`` uses.

    HF checkpoints compute rotary with ``rotate_half`` (first-half /
    second-half split); our kernel rotates adjacent (even, odd) pairs.  Per
    head, the rotate_half column order is [j=0 block of rot/2, j=1 block];
    interleaved order is (i, j) pairs.  This is a pure reparametrization:
    unpermuted weights + interleaved rope ≡ HF weights + rotate_half, for any
    checkpoint using the HF convention.  With partial rotary (gpt-neox/phi),
    only the first ``rot_dim`` dims of each head participate.

    ``w_t``: transposed projection, shape (in, n_heads*head_dim).
    """
    rot = rot_dim or head_dim
    d_in = w_t.shape[0]
    w = w_t.reshape(d_in, n_heads, head_dim)
    wr = (w[..., :rot].reshape(d_in, n_heads, 2, rot // 2)
          .swapaxes(-1, -2).reshape(d_in, n_heads, rot))
    return np.concatenate([wr, w[..., rot:]], axis=-1) \
        .reshape(d_in, n_heads * head_dim)


def _rope_permute(w_t: np.ndarray, n_heads: int, head_dim: int,
                  rot_dim: Optional[int] = None) -> np.ndarray:
    """Inverse of :func:`_rope_unpermute` (interleaved → HF half-split)."""
    rot = rot_dim or head_dim
    d_in = w_t.shape[0]
    w = w_t.reshape(d_in, n_heads, head_dim)
    wr = (w[..., :rot].reshape(d_in, n_heads, rot // 2, 2)
          .swapaxes(-1, -2).reshape(d_in, n_heads, rot))
    return np.concatenate([wr, w[..., rot:]], axis=-1) \
        .reshape(d_in, n_heads * head_dim)


def _rope_unpermute_bias(b: np.ndarray, n_heads: int, head_dim: int,
                         rot_dim: Optional[int] = None) -> np.ndarray:
    """Bias rows are permuted exactly like weight output rows."""
    return _rope_unpermute(b[None], n_heads, head_dim, rot_dim)[0]


def _rope_permute_bias(b: np.ndarray, n_heads: int, head_dim: int,
                       rot_dim: Optional[int] = None) -> np.ndarray:
    return _rope_permute(b[None], n_heads, head_dim, rot_dim)[0]


# shared per-layer stacking helpers (every converter maps "pattern with layer
# index" → stacked (L, ...) arrays; torch Linear stores (out, in) → transpose)


def _lw(sd, pattern: str, L: int) -> np.ndarray:
    return _stack([sd[pattern.format(i)].T for i in range(L)])


def _lnorm(sd, pattern: str, L: int) -> np.ndarray:
    return _stack([sd[pattern.format(i)] for i in range(L)])


def _lw_rope(sd, pattern: str, L: int, n_heads: int, head_dim: int,
             rot_dim: Optional[int] = None) -> np.ndarray:
    return _stack([_rope_unpermute(sd[pattern.format(i)].T, n_heads,
                                   head_dim, rot_dim) for i in range(L)])


def _lb_rope(sd, pattern: str, L: int, n_heads: int, head_dim: int,
             rot_dim: Optional[int] = None) -> np.ndarray:
    """Stack rope-unpermuted BIAS rows (qwen2/phi biased rotary layers)."""
    return _stack([_rope_unpermute_bias(sd[pattern.format(i)], n_heads,
                                        head_dim, rot_dim)
                   for i in range(L)])


def params_from_hf_llama(state_dict: Dict[str, Any], cfg: tfm.TransformerConfig
                         ) -> Dict[str, Any]:
    """LLaMA/Mistral-family HF state_dict → stacked param pytree.

    HF nn.Linear stores (out, in); our params are (in, out) → transpose.
    """
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L = cfg.num_layers

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["model.embed_tokens.weight"]},
        "layers": {
            "attn": {
                "wq": _lw_rope(sd, "model.layers.{}.self_attn.q_proj.weight",
                               L, cfg.num_heads, cfg.head_dim),
                "wk": _lw_rope(sd, "model.layers.{}.self_attn.k_proj.weight",
                               L, cfg.kv_heads, cfg.head_dim),
                "wv": _lw(sd, "model.layers.{}.self_attn.v_proj.weight", L),
                "wo": _lw(sd, "model.layers.{}.self_attn.o_proj.weight", L),
            },
            "ln1": {"scale": _lnorm(
                sd, "model.layers.{}.input_layernorm.weight", L)},
            "ln2": {"scale": _lnorm(
                sd, "model.layers.{}.post_attention_layernorm.weight", L)},
            "mlp": {
                "w_gate": _lw(sd, "model.layers.{}.mlp.gate_proj.weight", L),
                "w_in": _lw(sd, "model.layers.{}.mlp.up_proj.weight", L),
                "w_out": _lw(sd, "model.layers.{}.mlp.down_proj.weight", L),
            },
        },
        "final_norm": {"scale": sd["model.norm.weight"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_from_hf_gpt2(state_dict: Dict[str, Any], cfg: tfm.TransformerConfig
                        ) -> Dict[str, Any]:
    """GPT-2 HF state_dict → param pytree.  GPT-2 uses Conv1D ((in, out),
    no transpose) and a fused c_attn; linear biases are carried through."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, h = cfg.num_layers, cfg.hidden_size

    def per_layer(fn):
        return _stack([fn(i) for i in range(L)])

    return {
        "embed": {"tokens": sd["wte.weight"], "position": sd["wpe.weight"]},
        "layers": {
            "attn": {
                "wq": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.weight"][:, :h]),
                "wk": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.weight"][:, h:2 * h]),
                "wv": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.weight"][:, 2 * h:]),
                "wo": per_layer(lambda i: sd[f"h.{i}.attn.c_proj.weight"]),
                "bq": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.bias"][:h]),
                "bk": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.bias"][h:2 * h]),
                "bv": per_layer(lambda i: sd[f"h.{i}.attn.c_attn.bias"][2 * h:]),
                "bo": per_layer(lambda i: sd[f"h.{i}.attn.c_proj.bias"]),
            },
            "ln1": {"scale": per_layer(lambda i: sd[f"h.{i}.ln_1.weight"]),
                    "bias": per_layer(lambda i: sd[f"h.{i}.ln_1.bias"])},
            "ln2": {"scale": per_layer(lambda i: sd[f"h.{i}.ln_2.weight"]),
                    "bias": per_layer(lambda i: sd[f"h.{i}.ln_2.bias"])},
            "mlp": {
                "w_in": per_layer(lambda i: sd[f"h.{i}.mlp.c_fc.weight"]),
                "w_out": per_layer(lambda i: sd[f"h.{i}.mlp.c_proj.weight"]),
                "b_in": per_layer(lambda i: sd[f"h.{i}.mlp.c_fc.bias"]),
                "b_out": per_layer(lambda i: sd[f"h.{i}.mlp.c_proj.bias"]),
            },
        },
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }


def params_from_hf_qwen2(state_dict: Dict[str, Any], cfg: tfm.TransformerConfig
                         ) -> Dict[str, Any]:
    """Qwen2: LLaMA schema + q/k/v projection biases (bias rows carry the
    same rotate_half permutation as the weight's output rows)."""
    params = params_from_hf_llama(state_dict, cfg)
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd = cfg.num_layers, cfg.head_dim
    if "model.layers.0.self_attn.q_proj.bias" in sd:
        params["layers"]["attn"]["bq"] = _lb_rope(
            sd, "model.layers.{}.self_attn.q_proj.bias", L,
            cfg.num_heads, hd)
        params["layers"]["attn"]["bk"] = _lb_rope(
            sd, "model.layers.{}.self_attn.k_proj.bias", L,
            cfg.kv_heads, hd)
        params["layers"]["attn"]["bv"] = _stack([
            sd[f"model.layers.{i}.self_attn.v_proj.bias"] for i in range(L)])
    return params


def params_from_hf_mixtral(state_dict: Dict[str, Any],
                           cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """Mixtral: LLaMA attention + block-sparse MoE FFN.  Expert weights stack
    to (L, E, h, f)/(L, E, f, h); w1=gate, w3=up, w2=down; the router gate
    transposes to (h, E).  Reference:
    ``inference/v2/model_implementations/mixtral``."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, E = cfg.num_layers, cfg.num_experts

    def experts(w_name):
        return _stack([
            np.stack([sd[f"model.layers.{i}.block_sparse_moe.experts."
                         f"{e}.{w_name}.weight"].T for e in range(E)])
            for i in range(L)])

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["model.embed_tokens.weight"]},
        "layers": {
            "attn": {
                "wq": _lw_rope(sd, "model.layers.{}.self_attn.q_proj.weight",
                               L, cfg.num_heads, cfg.head_dim),
                "wk": _lw_rope(sd, "model.layers.{}.self_attn.k_proj.weight",
                               L, cfg.kv_heads, cfg.head_dim),
                "wv": _lw(sd, "model.layers.{}.self_attn.v_proj.weight", L),
                "wo": _lw(sd, "model.layers.{}.self_attn.o_proj.weight", L),
            },
            "ln1": {"scale": _stack(
                [sd[f"model.layers.{i}.input_layernorm.weight"]
                 for i in range(L)])},
            "ln2": {"scale": _stack(
                [sd[f"model.layers.{i}.post_attention_layernorm.weight"]
                 for i in range(L)])},
            "moe": {
                "router": _lw(sd, "model.layers.{}.block_sparse_moe.gate.weight", L),
                "w_gate": experts("w1"),
                "w_out": experts("w2"),
                "w_in": experts("w3"),
            },
        },
        "final_norm": {"scale": sd["model.norm.weight"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_from_hf_olmoe(state_dict: Dict[str, Any],
                         cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """OLMoE: llama attention plus ``q_norm`` / ``k_norm`` (their entries
    follow the q/k columns through the rope un-permutation: an RMS over the
    whole projection does not see the order, the scale does), and
    ``mlp.gate`` routing over ``mlp.experts.{e}.{gate,up,down}_proj``."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, E = cfg.num_layers, cfg.num_experts
    attn = "model.layers.{}.self_attn."

    def experts(w_name):
        return _stack([
            np.stack([sd[f"model.layers.{i}.mlp.experts.{e}.{w_name}.weight"
                         ].T for e in range(E)]) for i in range(L)])

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["model.embed_tokens.weight"]},
        "layers": {
            "attn": {
                "wq": _lw_rope(sd, attn + "q_proj.weight", L, cfg.num_heads,
                               cfg.head_dim),
                "wk": _lw_rope(sd, attn + "k_proj.weight", L, cfg.kv_heads,
                               cfg.head_dim),
                "wv": _lw(sd, attn + "v_proj.weight", L),
                "wo": _lw(sd, attn + "o_proj.weight", L),
                "q_norm": {"scale": _lb_rope(sd, attn + "q_norm.weight", L,
                                             cfg.num_heads, cfg.head_dim)},
                "k_norm": {"scale": _lb_rope(sd, attn + "k_norm.weight", L,
                                             cfg.kv_heads, cfg.head_dim)},
            },
            "ln1": {"scale": _lnorm(
                sd, "model.layers.{}.input_layernorm.weight", L)},
            "ln2": {"scale": _lnorm(
                sd, "model.layers.{}.post_attention_layernorm.weight", L)},
            "moe": {
                "router": _lw(sd, "model.layers.{}.mlp.gate.weight", L),
                "w_gate": experts("gate_proj"),
                "w_out": experts("down_proj"),
                "w_in": experts("up_proj"),
            },
        },
        "final_norm": {"scale": sd["model.norm.weight"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_from_hf_phi3(state_dict: Dict[str, Any], cfg: tfm.TransformerConfig
                        ) -> Dict[str, Any]:
    """Phi-3: LLaMA schema with fused qkv_proj and gate_up_proj."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd, nh, nkv = cfg.num_layers, cfg.head_dim, cfg.num_heads, cfg.kv_heads
    f = cfg.intermediate_size

    def split_qkv(i):
        w = sd[f"model.layers.{i}.self_attn.qkv_proj.weight"]  # (q+k+v, h)
        q = _rope_unpermute(w[:nh * hd].T, nh, hd)
        k = _rope_unpermute(w[nh * hd:nh * hd + nkv * hd].T, nkv, hd)
        v = w[nh * hd + nkv * hd:].T
        return q, k, v

    qs, ks, vs = zip(*(split_qkv(i) for i in range(L)))

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["model.embed_tokens.weight"]},
        "layers": {
            "attn": {"wq": _stack(qs), "wk": _stack(ks), "wv": _stack(vs),
                     "wo": _lw(sd, "model.layers.{}.self_attn.o_proj.weight", L)},
            "ln1": {"scale": _stack(
                [sd[f"model.layers.{i}.input_layernorm.weight"]
                 for i in range(L)])},
            "ln2": {"scale": _stack(
                [sd[f"model.layers.{i}.post_attention_layernorm.weight"]
                 for i in range(L)])},
            "mlp": {
                "w_gate": _stack(
                    [sd[f"model.layers.{i}.mlp.gate_up_proj.weight"][:f].T
                     for i in range(L)]),
                "w_in": _stack(
                    [sd[f"model.layers.{i}.mlp.gate_up_proj.weight"][f:].T
                     for i in range(L)]),
                "w_out": _lw(sd, "model.layers.{}.mlp.down_proj.weight", L),
            },
        },
        "final_norm": {"scale": sd["model.norm.weight"]},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_from_hf_falcon(state_dict: Dict[str, Any],
                          cfg: tfm.TransformerConfig, hf_config=None
                          ) -> Dict[str, Any]:
    """Falcon: fused query_key_value (three layouts by generation), parallel
    attention residual, GELU MLP.  Models with a single shared layernorm get
    it duplicated into ln1/ln2 — mathematically identical to the shared
    read."""
    get = _getter(hf_config) if hf_config is not None else (lambda k, d=None: d)
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd, nh, nkv = cfg.num_layers, cfg.head_dim, cfg.num_heads, cfg.kv_heads

    def split_qkv(i):
        w = sd[f"h.{i}.self_attention.query_key_value.weight"]  # (out, h)
        if get("new_decoder_architecture", False):
            g = nh // nkv  # heads per kv group: [g q-heads, 1 k, 1 v] each
            wg = w.reshape(nkv, g + 2, hd, -1)
            q = wg[:, :g].reshape(nh * hd, -1)
            k = wg[:, g].reshape(nkv * hd, -1)
            v = wg[:, g + 1].reshape(nkv * hd, -1)
        elif get("multi_query", True):
            q, k, v = (w[:nh * hd], w[nh * hd:(nh + 1) * hd],
                       w[(nh + 1) * hd:])
        else:  # per-head [q, k, v] interleave
            wg = w.reshape(nh, 3, hd, -1)
            q, k, v = (wg[:, j].reshape(nh * hd, -1) for j in range(3))
        return (_rope_unpermute(q.T, nh, hd), _rope_unpermute(k.T, nkv, hd),
                v.T)

    qs, ks, vs = zip(*(split_qkv(i) for i in range(L)))

    dual_ln = "h.0.ln_attn.weight" in sd
    ln1_key, ln2_key = (("ln_attn", "ln_mlp") if dual_ln
                        else ("input_layernorm", "input_layernorm"))

    def lnorm(key, suffix):
        return _stack([sd[f"h.{i}.{key}.{suffix}"] for i in range(L)])

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["word_embeddings.weight"]},
        "layers": {
            "attn": {"wq": _stack(qs), "wk": _stack(ks), "wv": _stack(vs),
                     "wo": _lw(sd, "h.{}.self_attention.dense.weight", L)},
            "ln1": {"scale": lnorm(ln1_key, "weight"),
                    "bias": lnorm(ln1_key, "bias")},
            "ln2": {"scale": lnorm(ln2_key, "weight"),
                    "bias": lnorm(ln2_key, "bias")},
            "mlp": {"w_in": _lw(sd, "h.{}.mlp.dense_h_to_4h.weight", L),
                    "w_out": _lw(sd, "h.{}.mlp.dense_4h_to_h.weight", L)},
        },
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    if not cfg.tie_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_from_hf_gpt_neox(state_dict: Dict[str, Any],
                            cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """GPT-NeoX / Pythia: per-head-fused QKV ([q,k,v] per head), partial
    rotary, parallel residual, biases throughout."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd, nh = cfg.num_layers, cfg.head_dim, cfg.num_heads
    rot = cfg.rot_dim

    def split_qkv(i):
        w = sd[f"gpt_neox.layers.{i}.attention.query_key_value.weight"]
        b = sd[f"gpt_neox.layers.{i}.attention.query_key_value.bias"]
        wg = w.reshape(nh, 3, hd, -1)
        bg = b.reshape(nh, 3, hd)
        out = []
        for j in range(3):
            wj = wg[:, j].reshape(nh * hd, -1).T
            bj = bg[:, j].reshape(nh * hd)
            if j < 2:  # q, k rotate
                wj = _rope_unpermute(wj, nh, hd, rot)
                bj = _rope_unpermute_bias(bj, nh, hd, rot)
            out.append((wj, bj))
        return out

    per_layer = [split_qkv(i) for i in range(L)]
    lb = lambda pattern: _lnorm(sd, pattern, L)

    params: Dict[str, Any] = {
        "embed": {"tokens": sd["gpt_neox.embed_in.weight"]},
        "layers": {
            "attn": {
                "wq": _stack([pl[0][0] for pl in per_layer]),
                "wk": _stack([pl[1][0] for pl in per_layer]),
                "wv": _stack([pl[2][0] for pl in per_layer]),
                "wo": _lw(sd, "gpt_neox.layers.{}.attention.dense.weight", L),
                "bq": _stack([pl[0][1] for pl in per_layer]),
                "bk": _stack([pl[1][1] for pl in per_layer]),
                "bv": _stack([pl[2][1] for pl in per_layer]),
                "bo": lb("gpt_neox.layers.{}.attention.dense.bias"),
            },
            "ln1": {"scale": lb("gpt_neox.layers.{}.input_layernorm.weight"),
                    "bias": lb("gpt_neox.layers.{}.input_layernorm.bias")},
            "ln2": {"scale": lb(
                "gpt_neox.layers.{}.post_attention_layernorm.weight"),
                "bias": lb(
                    "gpt_neox.layers.{}.post_attention_layernorm.bias")},
            "mlp": {
                "w_in": _lw(sd, "gpt_neox.layers.{}.mlp.dense_h_to_4h.weight", L),
                "w_out": _lw(sd, "gpt_neox.layers.{}.mlp.dense_4h_to_h.weight", L),
                "b_in": lb("gpt_neox.layers.{}.mlp.dense_h_to_4h.bias"),
                "b_out": lb("gpt_neox.layers.{}.mlp.dense_4h_to_h.bias"),
            },
        },
        "final_norm": {"scale": sd["gpt_neox.final_layer_norm.weight"],
                       "bias": sd["gpt_neox.final_layer_norm.bias"]},
    }
    if not cfg.tie_embeddings and "embed_out.weight" in sd:
        params["lm_head"] = {"w": sd["embed_out.weight"].T}
    return params


def params_from_hf_opt(state_dict: Dict[str, Any], cfg: tfm.TransformerConfig
                       ) -> Dict[str, Any]:
    """OPT: pre-LN decoder with ReLU MLP, biases throughout, and learned
    positions with the HF offset of 2 baked into the stored table."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L = cfg.num_layers
    pre = "model.decoder.layers.{}"

    def lw(name):
        return _stack([sd[(pre + "." + name + ".weight").format(i)].T
                       for i in range(L)])

    def lb(name, field="bias"):
        return _stack([sd[(pre + "." + name + "." + field).format(i)]
                       for i in range(L)])

    params: Dict[str, Any] = {
        "embed": {
            "tokens": sd["model.decoder.embed_tokens.weight"],
            # OPTLearnedPositionalEmbedding looks up position+2
            "position": sd["model.decoder.embed_positions.weight"][2:],
        },
        "layers": {
            "attn": {
                "wq": lw("self_attn.q_proj"), "wk": lw("self_attn.k_proj"),
                "wv": lw("self_attn.v_proj"), "wo": lw("self_attn.out_proj"),
                "bq": lb("self_attn.q_proj"), "bk": lb("self_attn.k_proj"),
                "bv": lb("self_attn.v_proj"), "bo": lb("self_attn.out_proj"),
            },
            "ln1": {"scale": lb("self_attn_layer_norm", "weight"),
                    "bias": lb("self_attn_layer_norm")},
            "ln2": {"scale": lb("final_layer_norm", "weight"),
                    "bias": lb("final_layer_norm")},
            "mlp": {"w_in": lw("fc1"), "w_out": lw("fc2"),
                    "b_in": lb("fc1"), "b_out": lb("fc2")},
        },
        "final_norm": {
            "scale": sd["model.decoder.final_layer_norm.weight"],
            "bias": sd["model.decoder.final_layer_norm.bias"]},
    }
    if not cfg.tie_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_to_hf_llama(params: Dict[str, Any], cfg: tfm.TransformerConfig
                       ) -> Dict[str, np.ndarray]:
    """Reverse export (save_16bit_model / zero_to_fp32 role)."""
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    lp = params["layers"]
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        out[f"{pre}.self_attn.q_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wq"][i]), cfg.num_heads, cfg.head_dim).T
        out[f"{pre}.self_attn.k_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wk"][i]), cfg.kv_heads, cfg.head_dim).T
        out[f"{pre}.self_attn.v_proj.weight"] = np.asarray(lp["attn"]["wv"][i]).T
        out[f"{pre}.self_attn.o_proj.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.mlp.gate_proj.weight"] = np.asarray(lp["mlp"]["w_gate"][i]).T
        out[f"{pre}.mlp.up_proj.weight"] = np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.down_proj.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_from_hf_gptj(state_dict: Dict[str, Any],
                        cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """GPT-J: separate unbiased q/k/v/out projections, ONE shared layernorm
    per block (parallel residual — duplicated into ln1/ln2), partial rotary
    in the INTERLEAVED even/odd convention (mesh-transformer heritage) —
    exactly this repo's ``apply_rope``, so NO rotate_half permutation; the
    untied lm_head carries a bias.  Reference policy:
    ``module_inject/containers/gptj.py``."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L = cfg.num_layers
    ln_scale = _lnorm(sd, "h.{}.ln_1.weight", L)
    ln_bias = _lnorm(sd, "h.{}.ln_1.bias", L)
    return {
        "embed": {"tokens": sd["wte.weight"]},
        "layers": {
            "attn": {
                "wq": _lw(sd, "h.{}.attn.q_proj.weight", L),
                "wk": _lw(sd, "h.{}.attn.k_proj.weight", L),
                "wv": _lw(sd, "h.{}.attn.v_proj.weight", L),
                "wo": _lw(sd, "h.{}.attn.out_proj.weight", L),
            },
            "ln1": {"scale": ln_scale, "bias": ln_bias},
            "ln2": {"scale": ln_scale.copy(), "bias": ln_bias.copy()},
            "mlp": {
                "w_in": _lw(sd, "h.{}.mlp.fc_in.weight", L),
                "w_out": _lw(sd, "h.{}.mlp.fc_out.weight", L),
                "b_in": _lnorm(sd, "h.{}.mlp.fc_in.bias", L),
                "b_out": _lnorm(sd, "h.{}.mlp.fc_out.bias", L),
            },
        },
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
        "lm_head": {"w": sd["lm_head.weight"].T, "b": sd["lm_head.bias"]},
    }


def params_to_hf_gptj(params: Dict[str, Any], cfg: tfm.TransformerConfig
                      ) -> Dict[str, np.ndarray]:
    """GPT-J export (shared-layernorm architecture: ln1 wins if training
    diverged the duplicated copies)."""
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "transformer.wte.weight": np.asarray(params["embed"]["tokens"]),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
        "lm_head.weight": np.asarray(params["lm_head"]["w"]).T,
        "lm_head.bias": np.asarray(params["lm_head"]["b"]),
    }
    for i in range(cfg.num_layers):
        pre = f"transformer.h.{i}"
        out[f"{pre}.attn.q_proj.weight"] = np.asarray(lp["attn"]["wq"][i]).T
        out[f"{pre}.attn.k_proj.weight"] = np.asarray(lp["attn"]["wk"][i]).T
        out[f"{pre}.attn.v_proj.weight"] = np.asarray(lp["attn"]["wv"][i]).T
        out[f"{pre}.attn.out_proj.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.ln_1.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.ln_1.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.mlp.fc_in.weight"] = np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.fc_in.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.fc_out.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.mlp.fc_out.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    return out


def params_from_hf_gpt_bigcode(state_dict: Dict[str, Any],
                               cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """StarCoder/gpt_bigcode: the GPT-2 block with nn.Linear layouts and a
    fused c_attn of [q (h rows), k (kv·hd), v (kv·hd)] — multi-query (one
    shared kv head) in the published checkpoints.  Reference policy: the
    bigcode AutoTP entry."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, h = cfg.num_layers, cfg.hidden_size
    nh, hd = cfg.num_heads, cfg.head_dim
    kvd = cfg.kv_heads * cfg.head_dim
    mq = cfg.kv_heads != cfg.num_heads

    def split_w(i):
        w = sd[f"h.{i}.attn.c_attn.weight"]
        if mq:  # (h + 2*kvd, h): [all q rows, k, v]
            return w[:h].T, w[h:h + kvd].T, w[h + kvd:].T
        wg = w.reshape(nh, 3, hd, h)  # non-MQ: per-head [q,k,v] interleave
        return (wg[:, 0].reshape(nh * hd, h).T,
                wg[:, 1].reshape(nh * hd, h).T,
                wg[:, 2].reshape(nh * hd, h).T)

    def split_b(i):
        b = sd[f"h.{i}.attn.c_attn.bias"]
        if mq:
            return b[:h], b[h:h + kvd], b[h + kvd:]
        bg = b.reshape(nh, 3, hd)
        return (bg[:, 0].reshape(nh * hd), bg[:, 1].reshape(nh * hd),
                bg[:, 2].reshape(nh * hd))

    qs, ks, vs = zip(*(split_w(i) for i in range(L)))
    bqs, bks, bvs = zip(*(split_b(i) for i in range(L)))
    lb = lambda pattern: _lnorm(sd, pattern, L)  # noqa: E731
    params: Dict[str, Any] = {
        "embed": {"tokens": sd["wte.weight"], "position": sd["wpe.weight"]},
        "layers": {
            "attn": {
                "wq": _stack(qs), "wk": _stack(ks), "wv": _stack(vs),
                "wo": _lw(sd, "h.{}.attn.c_proj.weight", L),
                "bq": _stack(bqs), "bk": _stack(bks), "bv": _stack(bvs),
                "bo": lb("h.{}.attn.c_proj.bias"),
            },
            "ln1": {"scale": lb("h.{}.ln_1.weight"),
                    "bias": lb("h.{}.ln_1.bias")},
            "ln2": {"scale": lb("h.{}.ln_2.weight"),
                    "bias": lb("h.{}.ln_2.bias")},
            "mlp": {
                "w_in": _lw(sd, "h.{}.mlp.c_fc.weight", L),
                "w_out": _lw(sd, "h.{}.mlp.c_proj.weight", L),
                "b_in": lb("h.{}.mlp.c_fc.bias"),
                "b_out": lb("h.{}.mlp.c_proj.bias"),
            },
        },
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }
    if not cfg.tie_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_to_hf_gpt_bigcode(params: Dict[str, Any],
                             cfg: tfm.TransformerConfig
                             ) -> Dict[str, np.ndarray]:
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "transformer.wte.weight": np.asarray(params["embed"]["tokens"]),
        "transformer.wpe.weight": np.asarray(params["embed"]["position"]),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
    }
    nh, hd, h = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    mq = cfg.kv_heads != cfg.num_heads
    for i in range(cfg.num_layers):
        pre = f"transformer.h.{i}"
        q = np.asarray(lp["attn"]["wq"][i]).T
        k = np.asarray(lp["attn"]["wk"][i]).T
        v = np.asarray(lp["attn"]["wv"][i]).T
        bq = np.asarray(lp["attn"]["bq"][i])
        bk = np.asarray(lp["attn"]["bk"][i])
        bv = np.asarray(lp["attn"]["bv"][i])
        if mq:
            out[f"{pre}.attn.c_attn.weight"] = np.concatenate([q, k, v])
            out[f"{pre}.attn.c_attn.bias"] = np.concatenate([bq, bk, bv])
        else:  # re-interleave per head
            wg = np.stack([q.reshape(nh, hd, h), k.reshape(nh, hd, h),
                           v.reshape(nh, hd, h)], axis=1)
            out[f"{pre}.attn.c_attn.weight"] = wg.reshape(3 * nh * hd, h)
            bg = np.stack([bq.reshape(nh, hd), bk.reshape(nh, hd),
                           bv.reshape(nh, hd)], axis=1)
            out[f"{pre}.attn.c_attn.bias"] = bg.reshape(3 * nh * hd)
        out[f"{pre}.attn.c_proj.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.attn.c_proj.bias"] = np.asarray(lp["attn"]["bo"][i])
        out[f"{pre}.ln_1.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.ln_1.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.ln_2.weight"] = np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.ln_2.bias"] = np.asarray(lp["ln2"]["bias"][i])
        out[f"{pre}.mlp.c_fc.weight"] = np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.c_fc.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.c_proj.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.mlp.c_proj.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_from_hf_phi(state_dict: Dict[str, Any],
                       cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """Phi-1/2: llama-style naming with biases everywhere, ONE shared
    layernorm per block (parallel residual — duplicated into ln1/ln2),
    rotate_half partial rotary, untied lm_head WITH bias."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd, nh, nkv = cfg.num_layers, cfg.head_dim, cfg.num_heads, cfg.kv_heads
    rot = cfg.rot_dim
    pre = "model.layers.{}"
    ln_scale = _lnorm(sd, pre + ".input_layernorm.weight", L)
    ln_bias = _lnorm(sd, pre + ".input_layernorm.bias", L)
    return {
        "embed": {"tokens": sd["model.embed_tokens.weight"]},
        "layers": {
            "attn": {
                "wq": _lw_rope(sd, pre + ".self_attn.q_proj.weight",
                               L, nh, hd, rot),
                "wk": _lw_rope(sd, pre + ".self_attn.k_proj.weight",
                               L, nkv, hd, rot),
                "wv": _lw(sd, pre + ".self_attn.v_proj.weight", L),
                "wo": _lw(sd, pre + ".self_attn.dense.weight", L),
                "bq": _lb_rope(sd, pre + ".self_attn.q_proj.bias",
                               L, nh, hd, rot),
                "bk": _lb_rope(sd, pre + ".self_attn.k_proj.bias",
                               L, nkv, hd, rot),
                "bv": _lnorm(sd, pre + ".self_attn.v_proj.bias", L),
                "bo": _lnorm(sd, pre + ".self_attn.dense.bias", L),
            },
            "ln1": {"scale": ln_scale, "bias": ln_bias},
            "ln2": {"scale": ln_scale.copy(), "bias": ln_bias.copy()},
            "mlp": {
                "w_in": _lw(sd, pre + ".mlp.fc1.weight", L),
                "w_out": _lw(sd, pre + ".mlp.fc2.weight", L),
                "b_in": _lnorm(sd, pre + ".mlp.fc1.bias", L),
                "b_out": _lnorm(sd, pre + ".mlp.fc2.bias", L),
            },
        },
        "final_norm": {"scale": sd["model.final_layernorm.weight"],
                       "bias": sd["model.final_layernorm.bias"]},
        "lm_head": {"w": sd["lm_head.weight"].T, "b": sd["lm_head.bias"]},
    }


def params_to_hf_phi(params: Dict[str, Any], cfg: tfm.TransformerConfig
                     ) -> Dict[str, np.ndarray]:
    """Phi export (shared-layernorm architecture: ln1 wins)."""
    lp = params["layers"]
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    rot = cfg.rot_dim
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
        "model.final_layernorm.weight": np.asarray(
            params["final_norm"]["scale"]),
        "model.final_layernorm.bias": np.asarray(params["final_norm"]["bias"]),
        "lm_head.weight": np.asarray(params["lm_head"]["w"]).T,
        "lm_head.bias": np.asarray(params["lm_head"]["b"]),
    }
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        out[f"{pre}.self_attn.q_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wq"][i]), nh, hd, rot).T
        out[f"{pre}.self_attn.q_proj.bias"] = _rope_permute_bias(
            np.asarray(lp["attn"]["bq"][i]), nh, hd, rot)
        out[f"{pre}.self_attn.k_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wk"][i]), nkv, hd, rot).T
        out[f"{pre}.self_attn.k_proj.bias"] = _rope_permute_bias(
            np.asarray(lp["attn"]["bk"][i]), nkv, hd, rot)
        out[f"{pre}.self_attn.v_proj.weight"] = np.asarray(lp["attn"]["wv"][i]).T
        out[f"{pre}.self_attn.v_proj.bias"] = np.asarray(lp["attn"]["bv"][i])
        out[f"{pre}.self_attn.dense.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.self_attn.dense.bias"] = np.asarray(lp["attn"]["bo"][i])
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.input_layernorm.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.mlp.fc1.weight"] = np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.fc1.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.fc2.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.mlp.fc2.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    return out


def params_from_hf_bloom(state_dict: Dict[str, Any],
                         cfg: tfm.TransformerConfig) -> Dict[str, Any]:
    """BLOOM: ALiBi positions (no rotary permutation), embedding layernorm,
    per-head-fused [q,k,v] query_key_value (same head-major layout as
    gpt-neox), GELU MLP, biases throughout.  Reference policy:
    ``module_inject/containers/bloom.py:105``."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    L, hd, nh = cfg.num_layers, cfg.head_dim, cfg.num_heads

    def split_qkv(i):
        w = sd[f"h.{i}.self_attention.query_key_value.weight"]  # (3h, h)
        b = sd[f"h.{i}.self_attention.query_key_value.bias"]
        wg = w.reshape(nh, 3, hd, -1)
        bg = b.reshape(nh, 3, hd)
        return [(wg[:, j].reshape(nh * hd, -1).T, bg[:, j].reshape(nh * hd))
                for j in range(3)]

    per_layer = [split_qkv(i) for i in range(L)]
    lb = lambda pattern: _lnorm(sd, pattern, L)  # noqa: E731

    return {
        "embed": {"tokens": sd["word_embeddings.weight"]},
        "embed_norm": {"scale": sd["word_embeddings_layernorm.weight"],
                       "bias": sd["word_embeddings_layernorm.bias"]},
        "layers": {
            "attn": {
                "wq": _stack([pl[0][0] for pl in per_layer]),
                "wk": _stack([pl[1][0] for pl in per_layer]),
                "wv": _stack([pl[2][0] for pl in per_layer]),
                "wo": _lw(sd, "h.{}.self_attention.dense.weight", L),
                "bq": _stack([pl[0][1] for pl in per_layer]),
                "bk": _stack([pl[1][1] for pl in per_layer]),
                "bv": _stack([pl[2][1] for pl in per_layer]),
                "bo": lb("h.{}.self_attention.dense.bias"),
            },
            "ln1": {"scale": lb("h.{}.input_layernorm.weight"),
                    "bias": lb("h.{}.input_layernorm.bias")},
            "ln2": {"scale": lb("h.{}.post_attention_layernorm.weight"),
                    "bias": lb("h.{}.post_attention_layernorm.bias")},
            "mlp": {
                "w_in": _lw(sd, "h.{}.mlp.dense_h_to_4h.weight", L),
                "w_out": _lw(sd, "h.{}.mlp.dense_4h_to_h.weight", L),
                "b_in": lb("h.{}.mlp.dense_h_to_4h.bias"),
                "b_out": lb("h.{}.mlp.dense_4h_to_h.bias"),
            },
        },
        "final_norm": {"scale": sd["ln_f.weight"], "bias": sd["ln_f.bias"]},
    }


def params_to_hf_bloom(params: Dict[str, Any], cfg: tfm.TransformerConfig
                       ) -> Dict[str, np.ndarray]:
    """BLOOM export: re-fuse the per-head [q,k,v] query_key_value."""
    lp = params["layers"]
    nh, hd, h = cfg.num_heads, cfg.head_dim, cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "transformer.word_embeddings.weight": np.asarray(
            params["embed"]["tokens"]),
        "transformer.word_embeddings_layernorm.weight": np.asarray(
            params["embed_norm"]["scale"]),
        "transformer.word_embeddings_layernorm.bias": np.asarray(
            params["embed_norm"]["bias"]),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"transformer.h.{i}"
        ws = [np.asarray(lp["attn"][n][i]).T.reshape(nh, hd, h)
              for n in ("wq", "wk", "wv")]
        bs = [np.asarray(lp["attn"][n][i]).reshape(nh, hd)
              for n in ("bq", "bk", "bv")]
        out[f"{pre}.self_attention.query_key_value.weight"] = \
            np.stack(ws, axis=1).reshape(3 * nh * hd, h)
        out[f"{pre}.self_attention.query_key_value.bias"] = \
            np.stack(bs, axis=1).reshape(3 * nh * hd)
        out[f"{pre}.self_attention.dense.weight"] = \
            np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.self_attention.dense.bias"] = \
            np.asarray(lp["attn"]["bo"][i])
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.input_layernorm.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.bias"] = \
            np.asarray(lp["ln2"]["bias"][i])
        out[f"{pre}.mlp.dense_h_to_4h.weight"] = \
            np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.dense_h_to_4h.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.dense_4h_to_h.weight"] = \
            np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.mlp.dense_4h_to_h.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_qwen2(params: Dict[str, Any], cfg: tfm.TransformerConfig
                       ) -> Dict[str, np.ndarray]:
    """Qwen2 export: llama schema + rotate_half-permuted q/k/v biases."""
    out = params_to_hf_llama(params, cfg)
    attn = params["layers"]["attn"]
    if "bq" in attn:
        for i in range(cfg.num_layers):
            pre = f"model.layers.{i}.self_attn"
            out[f"{pre}.q_proj.bias"] = _rope_permute_bias(
                np.asarray(attn["bq"][i]), cfg.num_heads, cfg.head_dim)
            out[f"{pre}.k_proj.bias"] = _rope_permute_bias(
                np.asarray(attn["bk"][i]), cfg.kv_heads, cfg.head_dim)
            out[f"{pre}.v_proj.bias"] = np.asarray(attn["bv"][i])
    return out


def params_to_hf_gpt2(params: Dict[str, Any], cfg: tfm.TransformerConfig
                      ) -> Dict[str, np.ndarray]:
    """GPT-2 export (Conv1D layout: (in, out), fused c_attn).  Keys carry
    the ``transformer.`` prefix of the HF LMHead checkpoint; the tied
    lm_head is omitted as HF does for tied weights."""
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "transformer.wte.weight": np.asarray(params["embed"]["tokens"]),
        "transformer.wpe.weight": np.asarray(params["embed"]["position"]),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"transformer.h.{i}"
        a = lp["attn"]
        out[f"{pre}.attn.c_attn.weight"] = np.concatenate(
            [np.asarray(a["wq"][i]), np.asarray(a["wk"][i]),
             np.asarray(a["wv"][i])], axis=1)
        out[f"{pre}.attn.c_attn.bias"] = np.concatenate(
            [np.asarray(a["bq"][i]), np.asarray(a["bk"][i]),
             np.asarray(a["bv"][i])])
        out[f"{pre}.attn.c_proj.weight"] = np.asarray(a["wo"][i])
        out[f"{pre}.attn.c_proj.bias"] = np.asarray(a["bo"][i])
        out[f"{pre}.ln_1.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.ln_1.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.ln_2.weight"] = np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.ln_2.bias"] = np.asarray(lp["ln2"]["bias"][i])
        out[f"{pre}.mlp.c_fc.weight"] = np.asarray(lp["mlp"]["w_in"][i])
        out[f"{pre}.mlp.c_fc.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.c_proj.weight"] = np.asarray(lp["mlp"]["w_out"][i])
        out[f"{pre}.mlp.c_proj.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    return out


def params_to_hf_mixtral(params: Dict[str, Any], cfg: tfm.TransformerConfig
                         ) -> Dict[str, np.ndarray]:
    """Mixtral export: llama attention + per-expert w1/w2/w3 + router gate."""
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    moe = lp["moe"]
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        out[f"{pre}.self_attn.q_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wq"][i]), cfg.num_heads, cfg.head_dim).T
        out[f"{pre}.self_attn.k_proj.weight"] = _rope_permute(
            np.asarray(lp["attn"]["wk"][i]), cfg.kv_heads, cfg.head_dim).T
        out[f"{pre}.self_attn.v_proj.weight"] = np.asarray(lp["attn"]["wv"][i]).T
        out[f"{pre}.self_attn.o_proj.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.block_sparse_moe.gate.weight"] = \
            np.asarray(moe["router"][i]).T
        for e in range(cfg.num_experts):
            epre = f"{pre}.block_sparse_moe.experts.{e}"
            out[f"{epre}.w1.weight"] = np.asarray(moe["w_gate"][i, e]).T
            out[f"{epre}.w2.weight"] = np.asarray(moe["w_out"][i, e]).T
            out[f"{epre}.w3.weight"] = np.asarray(moe["w_in"][i, e]).T
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_olmoe(params: Dict[str, Any], cfg: tfm.TransformerConfig
                       ) -> Dict[str, np.ndarray]:
    """OLMoE export: llama attention + q_norm / k_norm (back through the rope
    permutation with their columns) + per-expert gate/up/down + router."""
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    attn, moe = lp["attn"], lp["moe"]
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        for hf, ours, heads in (("q", "wq", cfg.num_heads),
                                ("k", "wk", cfg.kv_heads)):
            out[f"{pre}.self_attn.{hf}_proj.weight"] = _rope_permute(
                np.asarray(attn[ours][i]), heads, cfg.head_dim).T
            out[f"{pre}.self_attn.{hf}_norm.weight"] = _rope_permute_bias(
                np.asarray(attn[f"{hf}_norm"]["scale"][i]), heads,
                cfg.head_dim)
        out[f"{pre}.self_attn.v_proj.weight"] = np.asarray(attn["wv"][i]).T
        out[f"{pre}.self_attn.o_proj.weight"] = np.asarray(attn["wo"][i]).T
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.mlp.gate.weight"] = np.asarray(moe["router"][i]).T
        for e in range(cfg.num_experts):
            epre = f"{pre}.mlp.experts.{e}"
            out[f"{epre}.gate_proj.weight"] = np.asarray(moe["w_gate"][i, e]).T
            out[f"{epre}.down_proj.weight"] = np.asarray(moe["w_out"][i, e]).T
            out[f"{epre}.up_proj.weight"] = np.asarray(moe["w_in"][i, e]).T
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_phi3(params: Dict[str, Any], cfg: tfm.TransformerConfig
                      ) -> Dict[str, np.ndarray]:
    """Phi-3 export: re-fuse qkv_proj and gate_up_proj."""
    lp = params["layers"]
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": np.asarray(params["embed"]["tokens"]),
        "model.norm.weight": np.asarray(params["final_norm"]["scale"]),
    }
    for i in range(cfg.num_layers):
        pre = f"model.layers.{i}"
        q = _rope_permute(np.asarray(lp["attn"]["wq"][i]),
                          cfg.num_heads, cfg.head_dim).T
        k = _rope_permute(np.asarray(lp["attn"]["wk"][i]),
                          cfg.kv_heads, cfg.head_dim).T
        v = np.asarray(lp["attn"]["wv"][i]).T
        out[f"{pre}.self_attn.qkv_proj.weight"] = np.concatenate([q, k, v])
        out[f"{pre}.self_attn.o_proj.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.mlp.gate_up_proj.weight"] = np.concatenate(
            [np.asarray(lp["mlp"]["w_gate"][i]).T,
             np.asarray(lp["mlp"]["w_in"][i]).T])
        out[f"{pre}.mlp.down_proj.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_falcon(params: Dict[str, Any], cfg: tfm.TransformerConfig,
                        hf_config=None) -> Dict[str, np.ndarray]:
    """Falcon export: re-fuse query_key_value in the generation's layout.
    Models with ONE shared layernorm read it from ``ln1`` (the import
    duplicated it; if training diverged ln1/ln2, the shared-LN architecture
    cannot represent both — ln1 wins)."""
    get = _getter(hf_config) if hf_config is not None else (lambda k, d=None: d)
    lp = params["layers"]
    nh, nkv, hd = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    h = cfg.hidden_size
    out: Dict[str, np.ndarray] = {
        "transformer.word_embeddings.weight": np.asarray(
            params["embed"]["tokens"]),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"]),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"]),
    }
    # layout detection mirrors the import: dual ln_attn/ln_mlp on
    # new-architecture models (falcon-40b/180b style)
    dual_ln = bool(get("new_decoder_architecture", False)) and \
        (get("num_ln_in_parallel_attn") or 2) == 2
    for i in range(cfg.num_layers):
        pre = f"transformer.h.{i}"
        q = _rope_permute(np.asarray(lp["attn"]["wq"][i]), nh, hd).T
        k = _rope_permute(np.asarray(lp["attn"]["wk"][i]), nkv, hd).T
        v = np.asarray(lp["attn"]["wv"][i]).T
        if get("new_decoder_architecture", False):
            g = nh // nkv
            wg = np.empty((nkv, g + 2, hd, h), q.dtype)
            wg[:, :g] = q.reshape(nkv, g, hd, h)
            wg[:, g] = k.reshape(nkv, hd, h)
            wg[:, g + 1] = v.reshape(nkv, hd, h)
            qkv = wg.reshape((g + 2) * nkv * hd, h)
        elif get("multi_query", True):
            qkv = np.concatenate([q, k, v])
        else:
            wg = np.stack([q.reshape(nh, hd, h), k.reshape(nh, hd, h),
                           v.reshape(nh, hd, h)], axis=1)
            qkv = wg.reshape(3 * nh * hd, h)
        out[f"{pre}.self_attention.query_key_value.weight"] = qkv
        out[f"{pre}.self_attention.dense.weight"] = \
            np.asarray(lp["attn"]["wo"][i]).T
        if dual_ln:
            out[f"{pre}.ln_attn.weight"] = np.asarray(lp["ln1"]["scale"][i])
            out[f"{pre}.ln_attn.bias"] = np.asarray(lp["ln1"]["bias"][i])
            out[f"{pre}.ln_mlp.weight"] = np.asarray(lp["ln2"]["scale"][i])
            out[f"{pre}.ln_mlp.bias"] = np.asarray(lp["ln2"]["bias"][i])
        else:
            out[f"{pre}.input_layernorm.weight"] = \
                np.asarray(lp["ln1"]["scale"][i])
            out[f"{pre}.input_layernorm.bias"] = \
                np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.mlp.dense_h_to_4h.weight"] = \
            np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.dense_4h_to_h.weight"] = \
            np.asarray(lp["mlp"]["w_out"][i]).T
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_gpt_neox(params: Dict[str, Any], cfg: tfm.TransformerConfig
                          ) -> Dict[str, np.ndarray]:
    """GPT-NeoX export: re-fuse the per-head [q,k,v] query_key_value."""
    lp = params["layers"]
    nh, hd, h, rot = cfg.num_heads, cfg.head_dim, cfg.hidden_size, cfg.rot_dim
    out: Dict[str, np.ndarray] = {
        "gpt_neox.embed_in.weight": np.asarray(params["embed"]["tokens"]),
        "gpt_neox.final_layer_norm.weight": np.asarray(
            params["final_norm"]["scale"]),
        "gpt_neox.final_layer_norm.bias": np.asarray(
            params["final_norm"]["bias"]),
    }
    for i in range(cfg.num_layers):
        pre = f"gpt_neox.layers.{i}"
        ws, bs = [], []
        for name, bname, rotate in (("wq", "bq", True), ("wk", "bk", True),
                                    ("wv", "bv", False)):
            w = np.asarray(lp["attn"][name][i])
            b = np.asarray(lp["attn"][bname][i])
            if rotate:
                w = _rope_permute(w, nh, hd, rot)
                b = _rope_permute_bias(b, nh, hd, rot)
            ws.append(w.T.reshape(nh, hd, h))
            bs.append(b.reshape(nh, hd))
        out[f"{pre}.attention.query_key_value.weight"] = \
            np.stack(ws, axis=1).reshape(3 * nh * hd, h)
        out[f"{pre}.attention.query_key_value.bias"] = \
            np.stack(bs, axis=1).reshape(3 * nh * hd)
        out[f"{pre}.attention.dense.weight"] = np.asarray(lp["attn"]["wo"][i]).T
        out[f"{pre}.attention.dense.bias"] = np.asarray(lp["attn"]["bo"][i])
        out[f"{pre}.input_layernorm.weight"] = np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.input_layernorm.bias"] = np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.post_attention_layernorm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.post_attention_layernorm.bias"] = \
            np.asarray(lp["ln2"]["bias"][i])
        out[f"{pre}.mlp.dense_h_to_4h.weight"] = \
            np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.mlp.dense_h_to_4h.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.mlp.dense_4h_to_h.weight"] = \
            np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.mlp.dense_4h_to_h.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["embed_out.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def params_to_hf_opt(params: Dict[str, Any], cfg: tfm.TransformerConfig
                     ) -> Dict[str, np.ndarray]:
    """OPT export.  The HF positional table's first two rows (the padding
    offset OPTLearnedPositionalEmbedding never reads for causal LM inputs)
    are reconstructed as zeros."""
    lp = params["layers"]
    pos = np.asarray(params["embed"]["position"])
    out: Dict[str, np.ndarray] = {
        "model.decoder.embed_tokens.weight": np.asarray(
            params["embed"]["tokens"]),
        "model.decoder.embed_positions.weight": np.concatenate(
            [np.zeros((2,) + pos.shape[1:], pos.dtype), pos]),
        "model.decoder.final_layer_norm.weight": np.asarray(
            params["final_norm"]["scale"]),
        "model.decoder.final_layer_norm.bias": np.asarray(
            params["final_norm"]["bias"]),
    }
    names = (("self_attn.q_proj", "wq", "bq"),
             ("self_attn.k_proj", "wk", "bk"),
             ("self_attn.v_proj", "wv", "bv"),
             ("self_attn.out_proj", "wo", "bo"))
    for i in range(cfg.num_layers):
        pre = f"model.decoder.layers.{i}"
        for hf_name, wkey, bkey in names:
            out[f"{pre}.{hf_name}.weight"] = np.asarray(lp["attn"][wkey][i]).T
            out[f"{pre}.{hf_name}.bias"] = np.asarray(lp["attn"][bkey][i])
        out[f"{pre}.self_attn_layer_norm.weight"] = \
            np.asarray(lp["ln1"]["scale"][i])
        out[f"{pre}.self_attn_layer_norm.bias"] = \
            np.asarray(lp["ln1"]["bias"][i])
        out[f"{pre}.final_layer_norm.weight"] = \
            np.asarray(lp["ln2"]["scale"][i])
        out[f"{pre}.final_layer_norm.bias"] = np.asarray(lp["ln2"]["bias"][i])
        out[f"{pre}.fc1.weight"] = np.asarray(lp["mlp"]["w_in"][i]).T
        out[f"{pre}.fc1.bias"] = np.asarray(lp["mlp"]["b_in"][i])
        out[f"{pre}.fc2.weight"] = np.asarray(lp["mlp"]["w_out"][i]).T
        out[f"{pre}.fc2.bias"] = np.asarray(lp["mlp"]["b_out"][i])
    if not cfg.tie_embeddings and "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


# model_type → converter.  The registry the reference keeps as
# ``module_inject/containers/`` policies + ``replace_module.py`` policy_to_ds
# dispatch; new architectures register here.
ARCH_CONVERTERS: Dict[str, Callable] = {
    "llama": params_from_hf_llama,
    "mistral": params_from_hf_llama,  # llama schema (+ sliding window cfg)
    "qwen2": params_from_hf_qwen2,
    "mixtral": params_from_hf_mixtral,
    "olmoe": params_from_hf_olmoe,
    "phi3": params_from_hf_phi3,
    "falcon": params_from_hf_falcon,
    "gpt_neox": params_from_hf_gpt_neox,
    "opt": params_from_hf_opt,
    "gpt2": params_from_hf_gpt2,
    "bloom": params_from_hf_bloom,
    "gptj": params_from_hf_gptj,
    "phi": params_from_hf_phi,
    "gemma": params_from_hf_llama,  # llama key schema (config switches differ)
    "gpt_bigcode": params_from_hf_gpt_bigcode,
}


# model_type → reverse exporter (save_16bit_model / zero_to_fp32 role):
# every importable family exports back to its HF state-dict schema.
ARCH_EXPORTERS: Dict[str, Callable] = {
    "llama": params_to_hf_llama,
    "mistral": params_to_hf_llama,
    "qwen2": params_to_hf_qwen2,
    "mixtral": params_to_hf_mixtral,
    "olmoe": params_to_hf_olmoe,
    "phi3": params_to_hf_phi3,
    "falcon": params_to_hf_falcon,
    "gpt_neox": params_to_hf_gpt_neox,
    "opt": params_to_hf_opt,
    "gpt2": params_to_hf_gpt2,
    "bloom": params_to_hf_bloom,
    "gptj": params_to_hf_gptj,
    "phi": params_to_hf_phi,
    "gemma": params_to_hf_llama,
    "gpt_bigcode": params_to_hf_gpt_bigcode,
}


def params_to_hf(params: Dict[str, Any], cfg: tfm.TransformerConfig,
                 model_type: str = "llama", hf_config=None
                 ) -> Dict[str, np.ndarray]:
    """Export a trained param pytree back to the HF state dict of
    ``model_type`` (reference: ``zero_to_fp32``/``save_16bit_model`` — the
    consolidated export the HF ecosystem reloads).  A LoRA-trained tree is
    merged first (adapters folded into the dequantized base), so PEFT runs
    export exactly like full fine-tunes."""
    from ..linear.optimized_linear import has_lora, merge_lora_weights

    if has_lora(params):
        params = merge_lora_weights(params)
    if model_type == "bert":
        return params_to_hf_bert(params, cfg)
    if model_type == "roberta":
        return params_to_hf_roberta(params, cfg)
    if model_type in ("t5", "mt5"):
        return params_to_hf_t5(params, cfg)
    export = ARCH_EXPORTERS.get(model_type)
    if export is None:
        raise ValueError(
            f"no HF exporter for model_type {model_type!r}; supported: "
            f"{tuple(sorted(ARCH_EXPORTERS))}")
    if export is params_to_hf_falcon:
        return export(params, cfg, hf_config)
    return export(params, cfg)


# ---------------------------------------------------------------------------
# encoder family (BERT) — reference: module_inject/containers/bert.py:30
# ---------------------------------------------------------------------------


def encoder_config_from_hf(hf_config) -> "Any":
    from .encoder import EncoderConfig

    get = _getter(hf_config)
    act = str(get("hidden_act", "gelu"))
    # HF bert 'gelu' is the erf form; 'gelu_new' the tanh approximation
    act_map = {"gelu": "gelu_exact", "gelu_new": "gelu", "relu": "relu"}
    if act not in act_map:
        raise ValueError(f"unsupported bert hidden_act {act!r}; "
                         f"supported: {sorted(act_map)}")
    return EncoderConfig(
        vocab_size=get("vocab_size"), hidden_size=get("hidden_size"),
        intermediate_size=get("intermediate_size"),
        num_layers=get("num_hidden_layers"),
        num_heads=get("num_attention_heads"),
        max_seq_len=get("max_position_embeddings", 512),
        type_vocab_size=get("type_vocab_size", 2),
        norm_eps=get("layer_norm_eps", 1e-12),
        activation=act_map[act])


def params_from_hf_bert(state_dict: Dict[str, Any], cfg) -> Dict[str, Any]:
    """BertModel/BertForMaskedLM state dict → encoder param pytree.  The
    ``bert.`` prefix is accepted with or without; the pooler and MLM head
    convert when present."""
    sd = {k.removeprefix("bert."): np.asarray(v)
          for k, v in state_dict.items()}
    L = cfg.num_layers
    pre = "encoder.layer.{}"

    def lw(name):
        return _stack([sd[(pre + "." + name + ".weight").format(i)].T
                       for i in range(L)])

    def lb(name, field="bias"):
        return _stack([sd[(pre + "." + name + "." + field).format(i)]
                       for i in range(L)])

    params: Dict[str, Any] = {
        "embed": {
            "tokens": sd["embeddings.word_embeddings.weight"],
            "position": sd["embeddings.position_embeddings.weight"],
            "token_type": sd["embeddings.token_type_embeddings.weight"],
        },
        "embed_norm": {"scale": sd["embeddings.LayerNorm.weight"],
                       "bias": sd["embeddings.LayerNorm.bias"]},
        "layers": {
            "attn": {
                "wq": lw("attention.self.query"),
                "bq": lb("attention.self.query"),
                "wk": lw("attention.self.key"),
                "bk": lb("attention.self.key"),
                "wv": lw("attention.self.value"),
                "bv": lb("attention.self.value"),
                "wo": lw("attention.output.dense"),
                "bo": lb("attention.output.dense"),
            },
            "ln_attn": {"scale": lb("attention.output.LayerNorm", "weight"),
                        "bias": lb("attention.output.LayerNorm")},
            "mlp": {
                "w_in": lw("intermediate.dense"),
                "b_in": lb("intermediate.dense"),
                "w_out": lw("output.dense"),
                "b_out": lb("output.dense"),
            },
            "ln_mlp": {"scale": lb("output.LayerNorm", "weight"),
                       "bias": lb("output.LayerNorm")},
        },
    }
    if "pooler.dense.weight" in sd:
        params["pooler"] = {"w": sd["pooler.dense.weight"].T,
                            "b": sd["pooler.dense.bias"]}
    if "cls.predictions.transform.dense.weight" in sd:
        params["mlm"] = {
            "w": sd["cls.predictions.transform.dense.weight"].T,
            "b": sd["cls.predictions.transform.dense.bias"],
            "norm": {"scale": sd["cls.predictions.transform.LayerNorm.weight"],
                     "bias": sd["cls.predictions.transform.LayerNorm.bias"]},
            "decoder_bias": sd.get("cls.predictions.bias",
                                   sd.get("cls.predictions.decoder.bias")),
        }
    return params


def params_to_hf_bert(params: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Encoder export back to the BertForMaskedLM state-dict schema."""
    out: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": np.asarray(
            params["embed"]["tokens"]),
        "bert.embeddings.position_embeddings.weight": np.asarray(
            params["embed"]["position"]),
        "bert.embeddings.token_type_embeddings.weight": np.asarray(
            params["embed"]["token_type"]),
        "bert.embeddings.LayerNorm.weight": np.asarray(
            params["embed_norm"]["scale"]),
        "bert.embeddings.LayerNorm.bias": np.asarray(
            params["embed_norm"]["bias"]),
    }
    lp = params["layers"]
    pairs = (("attention.self.query", "attn", "wq", "bq"),
             ("attention.self.key", "attn", "wk", "bk"),
             ("attention.self.value", "attn", "wv", "bv"),
             ("attention.output.dense", "attn", "wo", "bo"),
             ("intermediate.dense", "mlp", "w_in", "b_in"),
             ("output.dense", "mlp", "w_out", "b_out"))
    for i in range(cfg.num_layers):
        pre = f"bert.encoder.layer.{i}"
        for hf_name, blk, wk, bk in pairs:
            out[f"{pre}.{hf_name}.weight"] = np.asarray(lp[blk][wk][i]).T
            out[f"{pre}.{hf_name}.bias"] = np.asarray(lp[blk][bk][i])
        out[f"{pre}.attention.output.LayerNorm.weight"] = \
            np.asarray(lp["ln_attn"]["scale"][i])
        out[f"{pre}.attention.output.LayerNorm.bias"] = \
            np.asarray(lp["ln_attn"]["bias"][i])
        out[f"{pre}.output.LayerNorm.weight"] = \
            np.asarray(lp["ln_mlp"]["scale"][i])
        out[f"{pre}.output.LayerNorm.bias"] = \
            np.asarray(lp["ln_mlp"]["bias"][i])
    if "pooler" in params:
        out["bert.pooler.dense.weight"] = np.asarray(params["pooler"]["w"]).T
        out["bert.pooler.dense.bias"] = np.asarray(params["pooler"]["b"])
    if "mlm" in params:
        out["cls.predictions.transform.dense.weight"] = \
            np.asarray(params["mlm"]["w"]).T
        out["cls.predictions.transform.dense.bias"] = \
            np.asarray(params["mlm"]["b"])
        out["cls.predictions.transform.LayerNorm.weight"] = \
            np.asarray(params["mlm"]["norm"]["scale"])
        out["cls.predictions.transform.LayerNorm.bias"] = \
            np.asarray(params["mlm"]["norm"]["bias"])
        out["cls.predictions.bias"] = np.asarray(params["mlm"]["decoder_bias"])
    return out


def params_from_hf_roberta(state_dict: Dict[str, Any], cfg) -> Dict[str, Any]:
    """RoBERTa → the BERT encoder schema.  RoBERTa's learned positions are
    stored with a padding offset of 2 (position ids = cumsum + padding_idx);
    for unpadded inputs that is exactly ``arange + 2``, so the table is
    sliced from row 2 — same treatment as OPT's offset."""
    sd = {k.removeprefix("roberta."): np.asarray(v)
          for k, v in state_dict.items()}
    renamed = dict(sd)
    renamed["embeddings.position_embeddings.weight"] = \
        sd["embeddings.position_embeddings.weight"][2:]
    # the MLM head lives under lm_head.* instead of cls.predictions.*
    if "lm_head.dense.weight" in sd:
        renamed["cls.predictions.transform.dense.weight"] = \
            sd["lm_head.dense.weight"]
        renamed["cls.predictions.transform.dense.bias"] = \
            sd["lm_head.dense.bias"]
        renamed["cls.predictions.transform.LayerNorm.weight"] = \
            sd["lm_head.layer_norm.weight"]
        renamed["cls.predictions.transform.LayerNorm.bias"] = \
            sd["lm_head.layer_norm.bias"]
        renamed["cls.predictions.bias"] = sd["lm_head.bias"]
    return params_from_hf_bert(renamed, cfg)


def params_to_hf_roberta(params: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    bert_sd = params_to_hf_bert(params, cfg)
    out: Dict[str, np.ndarray] = {}
    head_map = {
        "cls.predictions.transform.dense.weight": "lm_head.dense.weight",
        "cls.predictions.transform.dense.bias": "lm_head.dense.bias",
        "cls.predictions.transform.LayerNorm.weight": "lm_head.layer_norm.weight",
        "cls.predictions.transform.LayerNorm.bias": "lm_head.layer_norm.bias",
        "cls.predictions.bias": "lm_head.bias",
    }
    for k, v in bert_sd.items():
        if k in head_map:
            out[head_map[k]] = v
        elif k.startswith("bert."):
            out["roberta." + k[len("bert."):]] = v
        else:
            out[k] = v
    pos = out["roberta.embeddings.position_embeddings.weight"]
    out["roberta.embeddings.position_embeddings.weight"] = np.concatenate(
        [np.zeros((2,) + pos.shape[1:], pos.dtype), pos])
    return out


# ---------------------------------------------------------------------------
# encoder-decoder family (T5/mT5)
# ---------------------------------------------------------------------------


def t5_config_from_hf(hf_config) -> "Any":
    from .t5 import T5ModelConfig

    get = _getter(hf_config)
    ff = str(get("feed_forward_proj", "relu"))
    if ff not in ("relu", "gated-gelu"):
        raise ValueError(f"unsupported T5 feed_forward_proj {ff!r}; "
                         f"supported: relu, gated-gelu")
    return T5ModelConfig(
        vocab_size=get("vocab_size"), d_model=get("d_model"),
        d_kv=get("d_kv"), d_ff=get("d_ff"),
        num_layers=get("num_layers"),
        num_decoder_layers=get("num_decoder_layers") or get("num_layers"),
        num_heads=get("num_heads"),
        relative_attention_num_buckets=get(
            "relative_attention_num_buckets", 32),
        relative_attention_max_distance=get(
            "relative_attention_max_distance", 128),
        feed_forward=ff,
        tie_word_embeddings=bool(get("tie_word_embeddings", True)),
        decoder_start_token_id=get("decoder_start_token_id", 0) or 0,
        norm_eps=get("layer_norm_epsilon", 1e-6))


def params_from_hf_t5(state_dict: Dict[str, Any], cfg) -> Dict[str, Any]:
    """T5ForConditionalGeneration state dict → encoder-decoder pytree.  The
    per-stack relative bias is read from block 0 (every block shares it)."""
    sd = {k: np.asarray(v) for k, v in state_dict.items()}
    gated = cfg.feed_forward == "gated-gelu"

    def stack_w(pattern, L):
        return _stack([sd[pattern.format(i)].T for i in range(L)])

    def stack_n(pattern, L):
        return _stack([sd[pattern.format(i)] for i in range(L)])

    def attn_block(base, L, attn_name):
        return {
            "wq": stack_w(f"{base}.block.{{}}.layer.{attn_name[0]}"
                          f".{attn_name[1]}.q.weight", L),
            "wk": stack_w(f"{base}.block.{{}}.layer.{attn_name[0]}"
                          f".{attn_name[1]}.k.weight", L),
            "wv": stack_w(f"{base}.block.{{}}.layer.{attn_name[0]}"
                          f".{attn_name[1]}.v.weight", L),
            "wo": stack_w(f"{base}.block.{{}}.layer.{attn_name[0]}"
                          f".{attn_name[1]}.o.weight", L),
        }

    def mlp_block(base, L, idx):
        if gated:
            return {
                "wi_0": stack_w(f"{base}.block.{{}}.layer.{idx}"
                                f".DenseReluDense.wi_0.weight", L),
                "wi_1": stack_w(f"{base}.block.{{}}.layer.{idx}"
                                f".DenseReluDense.wi_1.weight", L),
                "wo": stack_w(f"{base}.block.{{}}.layer.{idx}"
                              f".DenseReluDense.wo.weight", L),
            }
        return {
            "wi": stack_w(f"{base}.block.{{}}.layer.{idx}"
                          f".DenseReluDense.wi.weight", L),
            "wo": stack_w(f"{base}.block.{{}}.layer.{idx}"
                          f".DenseReluDense.wo.weight", L),
        }

    Le, Ld = cfg.num_layers, cfg.num_decoder_layers
    params: Dict[str, Any] = {
        "shared": {"tokens": sd["shared.weight"]},
        "encoder": {
            "layers": {
                "attn": attn_block("encoder", Le, (0, "SelfAttention")),
                "ln1": {"scale": stack_n(
                    "encoder.block.{}.layer.0.layer_norm.weight", Le)},
                "mlp": mlp_block("encoder", Le, 1),
                "ln2": {"scale": stack_n(
                    "encoder.block.{}.layer.1.layer_norm.weight", Le)},
            },
            "rel_bias": sd["encoder.block.0.layer.0.SelfAttention"
                           ".relative_attention_bias.weight"],
            "final_norm": {"scale": sd["encoder.final_layer_norm.weight"]},
        },
        "decoder": {
            "layers": {
                "self_attn": attn_block("decoder", Ld, (0, "SelfAttention")),
                "ln1": {"scale": stack_n(
                    "decoder.block.{}.layer.0.layer_norm.weight", Ld)},
                "cross_attn": attn_block("decoder", Ld, (1, "EncDecAttention")),
                "ln2": {"scale": stack_n(
                    "decoder.block.{}.layer.1.layer_norm.weight", Ld)},
                "mlp": mlp_block("decoder", Ld, 2),
                "ln3": {"scale": stack_n(
                    "decoder.block.{}.layer.2.layer_norm.weight", Ld)},
            },
            "rel_bias": sd["decoder.block.0.layer.0.SelfAttention"
                           ".relative_attention_bias.weight"],
            "final_norm": {"scale": sd["decoder.final_layer_norm.weight"]},
        },
    }
    if not cfg.tie_word_embeddings and "lm_head.weight" in sd:
        params["lm_head"] = {"w": sd["lm_head.weight"].T}
    return params


def params_to_hf_t5(params: Dict[str, Any], cfg) -> Dict[str, np.ndarray]:
    """Reverse export to the T5ForConditionalGeneration schema (tied
    embed_tokens copies included, as HF serializes them)."""
    gated = cfg.feed_forward == "gated-gelu"
    shared = np.asarray(params["shared"]["tokens"])
    out: Dict[str, np.ndarray] = {
        "shared.weight": shared,
        "encoder.embed_tokens.weight": shared,
        "decoder.embed_tokens.weight": shared,
        "encoder.final_layer_norm.weight": np.asarray(
            params["encoder"]["final_norm"]["scale"]),
        "decoder.final_layer_norm.weight": np.asarray(
            params["decoder"]["final_norm"]["scale"]),
        "encoder.block.0.layer.0.SelfAttention.relative_attention_bias"
        ".weight": np.asarray(params["encoder"]["rel_bias"]),
        "decoder.block.0.layer.0.SelfAttention.relative_attention_bias"
        ".weight": np.asarray(params["decoder"]["rel_bias"]),
    }

    def put_attn(base, idx, name, p, i):
        for ours, theirs in (("wq", "q"), ("wk", "k"), ("wv", "v"),
                             ("wo", "o")):
            out[f"{base}.layer.{idx}.{name}.{theirs}.weight"] = \
                np.asarray(p[ours][i]).T

    def put_mlp(base, idx, p, i):
        if gated:
            out[f"{base}.layer.{idx}.DenseReluDense.wi_0.weight"] = \
                np.asarray(p["wi_0"][i]).T
            out[f"{base}.layer.{idx}.DenseReluDense.wi_1.weight"] = \
                np.asarray(p["wi_1"][i]).T
        else:
            out[f"{base}.layer.{idx}.DenseReluDense.wi.weight"] = \
                np.asarray(p["wi"][i]).T
        out[f"{base}.layer.{idx}.DenseReluDense.wo.weight"] = \
            np.asarray(p["wo"][i]).T

    enc = params["encoder"]["layers"]
    for i in range(cfg.num_layers):
        base = f"encoder.block.{i}"
        put_attn(base, 0, "SelfAttention", enc["attn"], i)
        out[f"{base}.layer.0.layer_norm.weight"] = \
            np.asarray(enc["ln1"]["scale"][i])
        put_mlp(base, 1, enc["mlp"], i)
        out[f"{base}.layer.1.layer_norm.weight"] = \
            np.asarray(enc["ln2"]["scale"][i])
    dec = params["decoder"]["layers"]
    for i in range(cfg.num_decoder_layers):
        base = f"decoder.block.{i}"
        put_attn(base, 0, "SelfAttention", dec["self_attn"], i)
        out[f"{base}.layer.0.layer_norm.weight"] = \
            np.asarray(dec["ln1"]["scale"][i])
        put_attn(base, 1, "EncDecAttention", dec["cross_attn"], i)
        out[f"{base}.layer.1.layer_norm.weight"] = \
            np.asarray(dec["ln2"]["scale"][i])
        put_mlp(base, 2, dec["mlp"], i)
        out[f"{base}.layer.2.layer_norm.weight"] = \
            np.asarray(dec["ln3"]["scale"][i])
    if cfg.tie_word_embeddings:
        out["lm_head.weight"] = shared
    elif "lm_head" in params:
        out["lm_head.weight"] = np.asarray(params["lm_head"]["w"]).T
    return out


def supported_architectures() -> tuple:
    return tuple(sorted(ARCH_CONVERTERS)) + ("bert", "roberta", "t5", "mt5")


def load_hf_model(model_name_or_sd, hf_config=None,
                  ) -> tuple:
    """One-call loader: (TransformerConfig, params).  Accepts a transformers
    PreTrainedModel, or (state_dict, config) pair."""
    if hasattr(model_name_or_sd, "state_dict"):  # a transformers model
        hf_config = model_name_or_sd.config
        sd = {k: v.detach().cpu().numpy()
              for k, v in model_name_or_sd.state_dict().items()}
        # strip common prefixes
        if any(k.startswith("transformer.") for k in sd):
            sd = {k.removeprefix("transformer."): v for k, v in sd.items()}
    else:
        sd = model_name_or_sd
    model_type = _getter(hf_config)("model_type", "llama")
    if model_type == "bert":  # encoder family: its own config + schema
        ecfg = encoder_config_from_hf(hf_config)
        return ecfg, params_from_hf_bert(sd, ecfg)
    if model_type == "roberta":
        import dataclasses as _dc

        ecfg = encoder_config_from_hf(hf_config)
        # the position table loses its 2-row padding offset in conversion;
        # the usable length shrinks with it or a max-length input would
        # index past the sliced table
        ecfg = _dc.replace(ecfg, max_seq_len=ecfg.max_seq_len - 2)
        return ecfg, params_from_hf_roberta(sd, ecfg)
    if model_type in ("t5", "mt5"):  # encoder-decoder family
        tcfg = t5_config_from_hf(hf_config)
        return tcfg, params_from_hf_t5(sd, tcfg)
    cfg = config_from_hf(hf_config)
    convert = ARCH_CONVERTERS.get(model_type)
    if convert is None:
        raise ValueError(
            f"unsupported HF model_type {model_type!r}; supported: "
            f"{supported_architectures()}")
    if convert is params_from_hf_falcon:
        return cfg, convert(sd, cfg, hf_config)
    return cfg, convert(sd, cfg)
