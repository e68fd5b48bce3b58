"""Golden-logits tests for the HF architecture map (AutoTP model policies).

Reference role: ``module_inject/containers/`` (one policy per architecture)
and ``inference/v2/model_implementations/`` — each supported model_type must
reproduce transformers' own forward exactly (fp32) through
``load_hf_model`` → ``tfm.forward``.  Random-init tiny configs; no downloads.
"""

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402

from deepspeed_tpu.models import transformer as tfm  # noqa: E402
from deepspeed_tpu.models.hf_integration import (  # noqa: E402
    load_hf_model, supported_architectures)


def _golden(hf_cfg, cfg_overrides=None, atol=3e-4, rtol=3e-3, seq=16):
    from transformers import AutoModelForCausalLM

    torch.manual_seed(0)
    hf = AutoModelForCausalLM.from_config(
        hf_cfg, attn_implementation="eager").eval()
    cfg, params = load_hf_model(hf)
    over = {"dtype": "float32", "param_dtype": "float32"}
    over.update(cfg_overrides or {})
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, **over})
    toks = np.random.default_rng(0).integers(
        0, hf_cfg.vocab_size, (2, seq)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.tensor(toks.astype(np.int64))).logits.numpy()
    ours = np.asarray(tfm.forward(params, toks, cfg))
    np.testing.assert_allclose(ours, ref, atol=atol, rtol=rtol)
    return cfg, params


def test_mistral_golden(devices):
    from transformers import MistralConfig

    _golden(MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=None,
        tie_word_embeddings=False))


def test_qwen2_golden(devices):
    from transformers import Qwen2Config

    cfg, params = _golden(Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True))
    assert "bq" in params["layers"]["attn"]  # qkv biases carried through


def test_mixtral_golden(devices):
    from transformers import MixtralConfig

    _golden(MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, tie_word_embeddings=False),
        # capacity ≥ worst-case routing so the capacity-bucketed dispatch
        # is exact (HF's reference block is dropless)
        cfg_overrides={"moe_capacity_factor": 4.0})


def test_olmoe_golden(devices):
    """q/k norm over the whole projection (its scales moved off 1 and
    carried through the rope un-permutation), 8 routed experts, top 2, gates
    the raw probabilities: transformers' own forward, to float32 precision."""
    from transformers import AutoModelForCausalLM, OlmoeConfig

    hf_cfg = OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(0)
    hf = AutoModelForCausalLM.from_config(
        hf_cfg, attn_implementation="eager").eval()
    with torch.no_grad():
        for layer in hf.model.layers:
            for norm in (layer.self_attn.q_norm, layer.self_attn.k_norm):
                norm.weight.add_(0.3 * torch.randn_like(norm.weight))
    cfg, params = load_hf_model(hf)
    assert cfg.qk_norm and not cfg.moe_norm_topk and cfg.num_experts == 8
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": "float32",
                                   "param_dtype": "float32"})
    toks = np.random.default_rng(0).integers(0, 128, (2, 16)).astype(np.int32)
    with torch.no_grad():
        ref = hf(torch.tensor(toks.astype(np.int64))).logits.numpy()
    np.testing.assert_allclose(np.asarray(tfm.forward(params, toks, cfg)),
                               ref, atol=3e-4, rtol=3e-3)


def test_phi3_golden(devices):
    Phi3Config = pytest.importorskip("transformers").Phi3Config

    _golden(Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0))


def test_falcon_multiquery_golden(devices):
    from transformers import FalconConfig

    _golden(FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True,
        new_decoder_architecture=False, parallel_attn=True, bias=False,
        alibi=False, tie_word_embeddings=True))


def test_falcon_new_arch_golden(devices):
    from transformers import FalconConfig

    _golden(FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_kv_heads=2,
        new_decoder_architecture=True, bias=False, alibi=False,
        tie_word_embeddings=True))


def test_gpt_neox_golden(devices):
    from transformers import GPTNeoXConfig

    cfg, _ = _golden(GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=0.25,
        use_parallel_residual=True, max_position_embeddings=64,
        tie_word_embeddings=False))
    assert cfg.parallel_residual and cfg.rot_dim == 4  # 16 * 0.25


def test_gpt_neox_nonparallel_golden(devices):
    from transformers import GPTNeoXConfig

    _golden(GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, rotary_pct=1.0,
        use_parallel_residual=False, max_position_embeddings=64,
        tie_word_embeddings=False))


def test_opt_golden(devices):
    from transformers import OPTConfig

    _golden(OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=256, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64))


@pytest.mark.parametrize("arch", ["qwen2", "gpt_neox", "opt", "gptj"])
def test_converted_models_serve_through_inference_v1(devices, arch):
    """The KV-cache inference engine must honor the new architecture features
    (projection biases, parallel residual, partial rotary, learned offset
    positions): greedy decode == uncached forward argmax."""
    import deepspeed_tpu
    from transformers import AutoModelForCausalLM

    if arch == "qwen2":
        from transformers import Qwen2Config
        hf_cfg = Qwen2Config(vocab_size=128, hidden_size=64,
                             intermediate_size=128, num_hidden_layers=2,
                             num_attention_heads=4, num_key_value_heads=2,
                             max_position_embeddings=64)
    elif arch == "gpt_neox":
        from transformers import GPTNeoXConfig
        hf_cfg = GPTNeoXConfig(vocab_size=128, hidden_size=64,
                               intermediate_size=256, num_hidden_layers=2,
                               num_attention_heads=4, rotary_pct=0.25,
                               use_parallel_residual=True,
                               max_position_embeddings=64)
    elif arch == "gptj":
        from transformers import GPTJConfig
        hf_cfg = GPTJConfig(vocab_size=128, n_embd=64, n_layer=2, n_head=4,
                            rotary_dim=8, n_positions=64,
                            tie_word_embeddings=False)
    else:
        from transformers import OPTConfig
        hf_cfg = OPTConfig(vocab_size=128, hidden_size=64, ffn_dim=256,
                           num_hidden_layers=2, num_attention_heads=4,
                           max_position_embeddings=64,
                           do_layer_norm_before=True, word_embed_proj_dim=64)
    torch.manual_seed(0)
    hf = AutoModelForCausalLM.from_config(
        hf_cfg, attn_implementation="eager").eval()
    cfg, params = load_hf_model(hf)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": "float32",
                                   "param_dtype": "float32"})
    engine = deepspeed_tpu.init_inference(
        config={"max_seq_len": 32}, model_config=cfg, params=params)
    prompt = np.array([[5, 6, 7, 8]], np.int32)
    out = engine.generate(prompt, max_new_tokens=5, temperature=0.0)
    seq = prompt.copy()
    for t in range(5):
        nxt = np.asarray(tfm.forward(params, seq, cfg)[:, -1]
                         .argmax(-1)).astype(np.int32)
        assert nxt[0] == out[0, 4 + t], f"{arch} divergence at step {t}"
        seq = np.concatenate([seq, nxt[:, None]], axis=1)


def test_unsupported_arch_rejected(devices):
    with pytest.raises(ValueError, match="unsupported HF model_type"):
        load_hf_model({"fake.weight": np.zeros((2, 2))},
                      {"model_type": "whisper"})


def test_supported_architectures_surface(devices):
    archs = supported_architectures()
    for required in ("llama", "mistral", "mixtral", "qwen2", "phi3",
                     "falcon", "gpt_neox", "opt", "gpt2"):
        assert required in archs, archs


def test_bloom_golden(devices):
    from transformers import BloomConfig

    _golden(BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        layer_norm_epsilon=1e-5, tie_word_embeddings=True))


def test_gptj_golden(devices):
    from transformers import GPTJConfig

    _golden(GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
        n_positions=64, tie_word_embeddings=False))


def test_phi_golden(devices):
    from transformers import PhiConfig

    _golden(PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=64,
        tie_word_embeddings=False))


def test_gemma_golden(devices):
    """Gemma: (1+w) rmsnorm, sqrt(d) embedding normalizer, gated tanh-gelu,
    and an EXPLICIT head_dim wider than hidden/heads (the gemma-7b shape)."""
    from transformers import GemmaConfig

    _golden(GemmaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16,  # 4*16=64 != 48: exercises head_dim_override
        max_position_embeddings=64, tie_word_embeddings=True))


def test_gemma_fresh_init_identity_norms(devices):
    """Native init of a gemma-style config matches the architecture's
    identity-at-init norm design ((1+w) with w=0) and num_params honors the
    explicit head_dim."""
    from deepspeed_tpu.models.hf_integration import config_from_hf

    cfg = config_from_hf({"model_type": "gemma", "vocab_size": 128,
                          "hidden_size": 48, "intermediate_size": 128,
                          "num_hidden_layers": 2, "num_attention_heads": 4,
                          "num_key_value_heads": 2, "head_dim": 16})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert float(np.abs(params["layers"]["ln1"]["scale"]).max()) == 0.0
    assert float(np.abs(params["final_norm"]["scale"]).max()) == 0.0
    # q: 48x(4*16), o: (4*16)x48 per layer — not 48x48
    n = cfg.num_params(include_embed=False)
    expected_attn = 2 * (48 * 64 + 48 * 2 * 16)  # per layer: q+o, k+v
    assert n >= 2 * expected_attn  # undercounting h*h would fail this
    # and the fresh model runs
    toks = np.zeros((1, 8), np.int32)
    out = tfm.forward(params, toks, cfg)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("mq", [True, False])
def test_gpt_bigcode_golden(devices, mq):
    """StarCoder block: fused [q, kv] c_attn with multi-query (1 shared kv
    head) and the multi-head variant."""
    from transformers import GPTBigCodeConfig

    _golden(GPTBigCodeConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        multi_query=mq))
