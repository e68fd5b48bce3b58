"""HF-export roundtrip tests: every importable architecture exports back to
its HF state-dict schema (reference role: ``zero_to_fp32`` /
``save_16bit_model`` — the consolidated export the HF ecosystem reloads).

For each family: tiny random-init HF model → ``load_hf_model`` →
``params_to_hf`` must (a) byte-match the original state dict on every
exported key, (b) cover every original parameter except known buffers and
tied heads, and (c) re-import to the identical param pytree.
"""

import re

import numpy as np
import pytest

torch = pytest.importorskip("torch")

import jax  # noqa: E402

from deepspeed_tpu.models.hf_integration import (  # noqa: E402
    ARCH_EXPORTERS, load_hf_model, params_to_hf)

# state-dict entries that are not parameters of the conversion schema:
# rotary tables and causal-mask buffers (tied lm_head views are handled by
# the tie_word_embeddings flag below)
_BUFFER_RE = re.compile(r"inv_freq|masked_bias|\.attn\.bias$|rotary_emb")


def _roundtrip(hf_model, special=()):
    sd = {k: v.detach().cpu().numpy() for k, v in hf_model.state_dict().items()}
    cfg, params = load_hf_model(hf_model)
    out = params_to_hf(params, cfg, model_type=hf_model.config.model_type,
                       hf_config=hf_model.config)

    # (a) every exported tensor byte-matches the original
    for k, v in out.items():
        assert k in sd, f"exported key {k} not in HF state dict"
        if k in special:
            continue
        np.testing.assert_array_equal(
            v.astype(np.float32), sd[k].astype(np.float32), err_msg=k)

    # (b) coverage: no real parameter left behind
    tied = hf_model.config.tie_word_embeddings
    missing = [k for k in sd
               if k not in out and not _BUFFER_RE.search(k)
               and not (tied and k.endswith(("lm_head.weight",
                                             "embed_out.weight")))]
    assert not missing, f"export misses parameters: {missing}"

    # (c) import(export(params)) == params
    stripped = {k.removeprefix("transformer."): v for k, v in out.items()}
    _, params2 = load_hf_model(stripped, hf_config=hf_model.config)
    flat1 = jax.tree_util.tree_flatten_with_path(params)[0]
    flat2 = dict(jax.tree_util.tree_flatten_with_path(params2)[0])
    for path, leaf in flat1:
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(flat2[path]),
                                      err_msg=str(path))
    return out


def test_exporter_registry_covers_all_importers():
    from deepspeed_tpu.models.hf_integration import ARCH_CONVERTERS

    assert set(ARCH_EXPORTERS) == set(ARCH_CONVERTERS)


def test_llama_export_roundtrip():
    from transformers import LlamaConfig, LlamaForCausalLM

    torch.manual_seed(0)
    m = LlamaForCausalLM(LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False)).eval()
    _roundtrip(m)


def test_gpt2_export_roundtrip():
    from transformers import GPT2Config, GPT2LMHeadModel

    torch.manual_seed(0)
    m = GPT2LMHeadModel(GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4,
        n_positions=64)).eval()
    _roundtrip(m)


def test_qwen2_export_roundtrip():
    from transformers import Qwen2Config, Qwen2ForCausalLM

    torch.manual_seed(0)
    m = Qwen2ForCausalLM(Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=True)).eval()
    _roundtrip(m)


def test_mixtral_export_roundtrip():
    from transformers import MixtralConfig, MixtralForCausalLM

    torch.manual_seed(0)
    m = MixtralForCausalLM(MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, tie_word_embeddings=False)).eval()
    _roundtrip(m)


def test_olmoe_export_roundtrip():
    from transformers import OlmoeConfig, OlmoeForCausalLM

    torch.manual_seed(0)
    m = OlmoeForCausalLM(OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=False,
        max_position_embeddings=64, tie_word_embeddings=False)).eval()
    with torch.no_grad():  # scales off 1, so that their order is checked
        for layer in m.model.layers:
            layer.self_attn.q_norm.weight.add_(
                0.3 * torch.randn_like(layer.self_attn.q_norm.weight))
    _roundtrip(m)


def test_phi3_export_roundtrip():
    tr = pytest.importorskip("transformers")

    torch.manual_seed(0)
    m = tr.Phi3ForCausalLM(tr.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, tie_word_embeddings=False,
        pad_token_id=0)).eval()
    _roundtrip(m)


@pytest.mark.parametrize("layout", ["new_arch", "multi_query", "per_head"])
def test_falcon_export_roundtrip(layout):
    from transformers import FalconConfig, FalconForCausalLM

    torch.manual_seed(0)
    kw = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
              num_attention_heads=4, alibi=False, bias=False,
              max_position_embeddings=64, tie_word_embeddings=True,
              parallel_attn=True)
    if layout == "new_arch":
        kw.update(new_decoder_architecture=True, num_kv_heads=2)
    elif layout == "multi_query":
        kw.update(new_decoder_architecture=False, multi_query=True)
    else:
        kw.update(new_decoder_architecture=False, multi_query=False)
    m = FalconForCausalLM(FalconConfig(**kw)).eval()
    _roundtrip(m)


def test_gpt_neox_export_roundtrip():
    from transformers import GPTNeoXConfig, GPTNeoXForCausalLM

    torch.manual_seed(0)
    m = GPTNeoXForCausalLM(GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.5,
        tie_word_embeddings=False)).eval()
    _roundtrip(m)


def test_opt_export_roundtrip():
    from transformers import OPTConfig, OPTForCausalLM

    torch.manual_seed(0)
    m = OPTForCausalLM(OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=128, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, word_embed_proj_dim=64,
        tie_word_embeddings=True)).eval()
    # the first two positional rows (HF's never-read padding offset) are
    # reconstructed as zeros — compare that key from row 2 only
    out = _roundtrip(m, special=("model.decoder.embed_positions.weight",))
    sd = m.state_dict()
    np.testing.assert_array_equal(
        out["model.decoder.embed_positions.weight"][2:],
        sd["model.decoder.embed_positions.weight"].numpy()[2:])


def test_bloom_export_roundtrip():
    from transformers import BloomConfig, BloomForCausalLM

    torch.manual_seed(0)
    m = BloomForCausalLM(BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        tie_word_embeddings=True)).eval()
    _roundtrip(m)


def test_gptj_export_roundtrip():
    from transformers import GPTJConfig, GPTJForCausalLM

    torch.manual_seed(0)
    m = GPTJForCausalLM(GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, rotary_dim=8,
        n_positions=64, tie_word_embeddings=False)).eval()
    _roundtrip(m)


def test_phi_export_roundtrip():
    from transformers import PhiConfig, PhiForCausalLM

    torch.manual_seed(0)
    m = PhiForCausalLM(PhiConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
        partial_rotary_factor=0.5, max_position_embeddings=64,
        tie_word_embeddings=False)).eval()
    _roundtrip(m)


def test_gemma_export_roundtrip():
    from transformers import GemmaConfig, GemmaForCausalLM

    torch.manual_seed(0)
    m = GemmaForCausalLM(GemmaConfig(
        vocab_size=128, hidden_size=48, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, max_position_embeddings=64,
        tie_word_embeddings=True)).eval()
    _roundtrip(m)


@pytest.mark.parametrize("mq", [True, False])
def test_gpt_bigcode_export_roundtrip(mq):
    from transformers import GPTBigCodeConfig, GPTBigCodeForCausalLM

    torch.manual_seed(0)
    m = GPTBigCodeForCausalLM(GPTBigCodeConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        multi_query=mq)).eval()
    _roundtrip(m)
