"""OLMoE-shaped models against the plain reference
(``benchmark/reference/moe_decoder.py``): q/k norm with non-unit scales,
un-normalised top-k gates, dropless routing, bf16-free float32 programs and
int8 experts.  Logits are compared, never tokens: with random weights the
largest logit changes on rounding.

Tolerances, and why:

* ``F32_TOL`` 2e-4 on logits of magnitude 3-4: the program and the reference
  both compute in float32, in different orders (measured: 2e-6).  One
  expert's contribution to a logit is about 0.1-0.5 (``test_batch_
  independence`` measures it), so a skipped expert, a renormalised gate or a
  missing q/k norm is three orders of magnitude over this.
* ``INT8_TOL`` 0.03, int8 *experts* (attention stays float32, so that what
  is compared is the expert path): the grouped W8A16 kernel feeds the MXU
  bfloat16 (activations and dequantized weights are rounded to 8 bits of
  mantissa inside the kernel, as on the chip), the reference multiplies the
  same codes in float32.  Measured 0.004-0.016 over 216 rows; a dropped
  expert is 0.1-0.5, a wrong layer's codes more.
* Router ties: a position whose k-th and (k+1)-th router probabilities lie
  closer than the precision of the compared computation may route
  differently, and from there on the sequence differs by an expert's
  contribution (seen: 0.05-0.5).  Such positions are found by the
  *reference's own* margin (``logits_and_margin``) and skipped, with every
  later position of that sequence; the number skipped is bounded in each
  test.  The tolerance of the positions that are compared is not widened.
  Float32 against float32 a margin under 1e-5 counts as a tie; with bf16
  inside the expert kernel 5e-5 (no flip was seen at 4e-5 and over; with the
  attention projections quantized too, flips were seen at 1e-4 and 3.7e-4,
  which is why those stay float32 here: the driver's rehearsal serves the
  fully quantized model).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.logit_tap import LogitTap
from benchmark.reference import moe_decoder as ref
from deepspeed_tpu.inference.quantization import (quantize_model_params,
                                                  quantized_bytes)
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import tracer

F32_TOL = 2e-4
INT8_TOL = 0.03
#: float32 against float32: a margin under this may flip on summation order
F32_MARGIN = 1e-5
#: bf16 inside the expert kernel moves the next layer's router a little
INT8_MARGIN = 5e-5


def olmoe_cfg(experts: int, top_k: int, **over) -> tfm.TransformerConfig:
    """OLMoE's block at toy widths: MHA, q/k norm, SwiGLU experts, raw
    top-k probabilities as gates, untied head, float32."""
    kw = dict(vocab_size=128, hidden_size=128, intermediate_size=128,
              num_layers=2, num_heads=4, num_kv_heads=4, max_seq_len=256,
              tie_embeddings=False, num_experts=experts, moe_top_k=top_k,
              moe_norm_topk=False, moe_routing="dropless", qk_norm=True,
              dtype="float32", param_dtype="float32")
    kw.update(over)
    return tfm.TransformerConfig(**kw)


def model_of(cfg: tfm.TransformerConfig) -> dict:
    """The reference's view of ``cfg``: the published keys."""
    return dict(num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.kv_heads,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                num_experts_per_tok=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk)


def make_params(cfg, seed: int = 0):
    """Seeded random weights; the q/k norm scales are moved off 1 so that a
    forward that skipped the norm, or normed per head, would differ."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    attn = params["layers"]["attn"]
    for i, name in enumerate(("q_norm", "k_norm")):
        shape = attn[name]["scale"].shape
        attn[name]["scale"] = 1.0 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(100 + seed + i), shape)
    return params


def compared(rows, ref_logits, margin, min_margin):
    """→ (largest |difference| over the rows compared, rows skipped).  A row
    is skipped from the first position of its sequence whose reference router
    margin is under ``min_margin``."""
    margin = np.asarray(margin)
    tied = np.nonzero(margin < min_margin)[0]
    first_tie = int(tied[0]) if len(tied) else len(margin)
    worst, skipped = 0.0, 0
    for pos, row in rows:
        if pos >= first_tie:
            skipped += 1
            continue
        worst = max(worst, float(np.abs(row - np.asarray(ref_logits[pos])
                                        ).max()))
    return worst, skipped


@pytest.mark.parametrize("experts,top_k", [(8, 2), (16, 4)])
def test_forward_matches_reference(experts, top_k):
    """(a) ``tfm.forward`` is the model the reference describes."""
    cfg = olmoe_cfg(experts, top_k)
    params = make_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 1,
                                cfg.vocab_size)
    got = tfm.forward(params, tokens, cfg)
    skipped = 0
    for b in range(2):
        want, margin = ref.logits_and_margin(params, model_of(cfg), tokens[b])
        worst, s = compared(list(enumerate(np.asarray(got[b]))), want, margin,
                            F32_MARGIN)
        assert worst < F32_TOL
        skipped += s
    assert skipped <= 4  # of 96 positions


def test_unnormalised_gates_and_qk_norm_are_seen():
    """The comparison has teeth: renormalised gates, or no q/k norm, leave
    the tolerance by orders of magnitude."""
    cfg = olmoe_cfg(8, 2)
    params = make_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 1,
                                cfg.vocab_size)
    want = ref.logits(params, model_of(cfg), tokens[0])
    for wrong in (dataclasses.replace(cfg, moe_norm_topk=True),
                  dataclasses.replace(cfg, qk_norm=False)):
        got = tfm.forward(params, tokens, wrong)[0]
        assert float(jnp.abs(got - want).max()) > 100 * F32_TOL


def serve(cfg, params, prompts, new_tokens, v2=None):
    """Drive the v2 engine to the end → (the tap's logits by uid, tokens by
    uid, uids in the prompts' order)."""
    v2 = v2 or V2Config(max_tokens_per_step=16, max_seqs=8, block_size=8,
                        num_blocks=96, max_blocks_per_seq=16,
                        dtype="float32")
    engine = InferenceEngineV2(cfg, params, v2)
    tap = LogitTap(engine)
    uids = [engine.put(p, max_new_tokens=new_tokens) for p in prompts]
    whole = engine.generate_all(burst=1)  # step by step: the tapped path
    out = {u: whole[u][len(p):] for p, u in zip(prompts, uids)}
    kinds = set()
    for s in tracer.spans():
        if s.name == "engine/step" and "moe_rows" in s.attrs:
            kinds.add(s.attrs["kind"])
    assert engine.free_blocks == engine.total_blocks
    assert {"mixed", "decode"} <= kinds  # both programs ran, both with stats
    return tap.logits, out, uids


@pytest.mark.parametrize("experts,top_k,int8", [(8, 2, False), (16, 4, False),
                                                (8, 2, True), (16, 4, True)],
                         ids=["e8k2", "e16k4", "e8k2-int8", "e16k4-int8"])
def test_engine_matches_reference(experts, top_k, int8):
    """(b), (c) Chunked prefill across a chunk boundary (prompts of 5 to 37
    tokens through steps of 16), then 18 decode steps through the paged
    cache, six sequences in one batch: the logits of every step against the
    reference's one uncached pass.  With int8 experts the reference reads the
    same codes, and the grouped W8A16 kernel (interpret mode) must have run
    for every expert GEMM, none fallen back."""
    cfg = olmoe_cfg(experts, top_k)
    params = make_params(cfg)
    if int8:
        params["layers"]["moe"] = quantize_model_params(
            {"moe": params["layers"]["moe"]}, bits=8, group=128)["moe"]
        q = quantized_bytes(params)
        assert q["quantized"] > 0.5 * q["total"]  # the experts are counted
    tracer.clear()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (23, 9, 37, 5, 18, 12)]
    logits, out, uids = serve(cfg, params, prompts, new_tokens=18)
    if int8:
        events = [s.attrs for s in tracer.spans()
                  if s.name == "kernel/grouped_mixed_gemm_tiles"]
        assert events and not any("fallback" in a for a in events)
    tol, min_margin = (INT8_TOL, INT8_MARGIN) if int8 else (F32_TOL,
                                                            F32_MARGIN)
    rows = skipped = 0
    for uid, prompt in zip(uids, prompts):
        assert len(logits[uid]) == 18  # one at the prompt's end, 17 decoded
        seq = jnp.asarray(prompt + out[uid])
        want, margin = ref.logits_and_margin(params, model_of(cfg), seq)
        worst, s = compared(logits[uid], want, margin, min_margin)
        assert worst < tol, (uid, worst)
        rows += len(logits[uid])
        skipped += s
    assert rows == 6 * 18
    assert skipped <= (rows // 3 if int8 else 3), (skipped, rows)


def test_batch_independence():
    """(d) A request's logits alone and beside 7 others agree to 1e-5, four
    orders of magnitude under one expert's contribution to a logit (measured
    here, by leaving the least-weighted expert of the last layer out: over
    0.01).  Capacity routing (the parent's serving path) drops whole expert
    outputs by what else is in the step and fails this by that much."""
    cfg = olmoe_cfg(8, 2)
    params = make_params(cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, size=21).tolist()
    others = [rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
              for n in rng.integers(5, 30, size=7)]
    alone, out_a, (uid_a,) = serve(cfg, params, [prompt], new_tokens=12)
    beside, out_b, uids = serve(cfg, params, [prompt] + others, new_tokens=12)
    assert out_a[uid_a] == out_b[uids[0]]
    worst = max(float(np.abs(a - b).max()) for (pa, a), (pb, b)
                in zip(alone[uid_a], beside[uids[0]]) if pa == pb)
    assert len(alone[uid_a]) == len(beside[uids[0]]) == 12
    assert worst < 1e-5, worst

    # what one expert is worth: top-1 routing on the same weights
    tokens = jnp.asarray([prompt])
    full = tfm.forward(params, tokens, cfg)
    less = tfm.forward(params, tokens, dataclasses.replace(cfg, moe_top_k=1))
    assert float(jnp.abs(full - less).max()) > 1000 * 1e-5


@pytest.mark.parametrize("preset", ["tiny-moe", "tiny-olmoe"])
def test_v1_engine_serves_moe(preset):
    """The v1 engine (``forward_cached``, ``InferenceEngine.generate``) runs
    the same routed FFN: prefill, then decode through its contiguous cache,
    logits of every step against ``tfm.forward`` over the whole prefix, and
    for the OLMoE block against the reference too.  ``tiny-moe`` is the
    renormalised, learned-position, GELU block the reference does not
    describe, so there ``tfm.forward`` (dropless) is what is compared."""
    from deepspeed_tpu.inference.engine import (InferenceEngine,
                                                _kv_cache_init,
                                                forward_cached)

    cfg = tfm.get_config(preset, moe_routing="dropless", dtype="float32",
                         param_dtype="float32")
    params = make_params(cfg) if cfg.qk_norm else tfm.init_params(
        jax.random.PRNGKey(0), cfg)
    B, T, new = 2, 20, 8
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, T + new), 1,
                                cfg.vocab_size)
    want = np.asarray(tfm.forward(params, tokens, cfg))
    cache = _kv_cache_init(cfg, B, T + new, jnp.float32)
    got, cache = forward_cached(params, tokens[:, :T], cache, 0, cfg)
    rows = [(T - 1, np.asarray(got))]
    for pos in range(T, T + new):
        got, cache = forward_cached(params, tokens[:, pos:pos + 1], cache,
                                    pos, cfg)
        rows.append((pos, np.asarray(got)))
    for pos, row in rows:  # same route function, same inputs to 1e-6
        assert np.abs(row - want[:, pos]).max() < F32_TOL
    if cfg.qk_norm:
        for b in range(B):
            ref_logits, margin = ref.logits_and_margin(params, model_of(cfg),
                                                       tokens[b])
            worst, skipped = compared([(pos, row[b]) for pos, row in rows],
                                      ref_logits, margin, F32_MARGIN)
            assert worst < F32_TOL and skipped <= 2

    engine = InferenceEngine(model_config=cfg, params=params,
                             config={"dtype": "float32"})
    out = engine.generate(np.asarray(tokens[:, :T]), max_new_tokens=new)
    assert out.shape == (B, T + new)
    full = np.asarray(tfm.forward(params, jnp.asarray(out), cfg))
    for pos in range(T - 1, T + new - 1):  # greedy: the argmax, or a tie
        top = full[:, pos].max(-1)
        assert (top - full[np.arange(B), pos, out[:, pos + 1]]).max() < 1e-3


def _kernel_case(rows_per_expert, seed=0, k=256, n=384, group=128, tile_m=16):
    """Rows in the tile-aligned layout for the given rows-per-expert → the
    kernel's output on the real rows and dequantize-then-einsum's."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import tile_aligned_layout
    from deepspeed_tpu.ops.pallas.grouped_mixed_gemm import grouped_mixed_gemm
    from deepspeed_tpu.ops.pallas.mixed_gemm import (dequantize_gemm_weight,
                                                     quantize_gemm_weight)

    E = len(rows_per_expert)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    qw = quantize_gemm_weight(jax.random.normal(keys[0], (3, E, k, n)) * 0.05,
                              bits=8, group=group)
    expert = jnp.asarray(np.random.default_rng(seed).permutation(
        np.repeat(np.arange(E), rows_per_expert)), jnp.int32)
    T = int(expert.shape[0])
    pos, tile_group, sizes, m_pad = tile_aligned_layout(expert, E, T, tile_m)
    used = jnp.sum(-(-jnp.bincount(expert, length=E) // tile_m))
    x = jax.random.normal(keys[1], (T, k)).astype(jnp.bfloat16)
    xs = jnp.zeros((m_pad, k), jnp.bfloat16).at[pos].set(x)
    layer = jnp.int32(1)
    got = jax.jit(lambda *a: grouped_mixed_gemm(
        *a, tile_m=tile_m, layer=layer))(xs, qw, tile_group, sizes, used)
    w = dequantize_gemm_weight(jax.tree.map(lambda a: a[1], qw))
    want = jnp.einsum("tk,tkn->tn", x.astype(jnp.float32),
                      w.astype(jnp.bfloat16).astype(jnp.float32)[expert])
    return got[pos].astype(jnp.float32), want


@pytest.mark.parametrize("rows", [
    [4, 1, 0, 8, 3, 0, 2, 5],  # decode-like: 1-8 rows an expert, two empty
    [0, 0, 0, 23, 0, 0, 0, 0],  # (f) every row routed to one expert
    [70, 61, 64, 58, 75, 49, 66, 69],  # prefill-like: several tiles each
], ids=["decode", "one-expert", "prefill"])
def test_grouped_mixed_gemm_kernel(rows):
    """(e), (f) The grouped W8A16 kernel in interpret mode against
    dequantize-then-einsum on the same bf16 operands: both accumulate in
    float32 and round the result to bf16 once, so they differ by one bf16
    rounding of an output of magnitude up to 3: 0.02."""
    tracer.clear()
    got, want = _kernel_case(rows)
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert events and "fallback" not in events[-1]
    assert events[-1]["tk"] == 256 and events[-1]["tile_m"] == 16
    assert float(jnp.abs(got - want).max()) < 0.02


def test_grouped_mixed_gemm_falls_back_on_shapes_that_do_not_tile():
    """A quantization group that is no multiple of 128 lanes cannot be a
    block of the kernel: dequantize-then-``ragged_dot``, recorded as such."""
    tracer.clear()
    got, want = _kernel_case([3, 0, 6, 2], k=96, n=64, group=32)
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert events[-1].get("fallback") == 1
    assert float(jnp.abs(got - want).max()) < 0.02


@pytest.mark.parametrize("assignments,experts,tile,padded", [
    (256, 64, 16, 1280),  # OLMoE's decode step: 32 rows x top-8
    (4096, 64, 128, 12288),  # its mixed step: 512 tokens x top-8
    (2, 8, 16, 144), (1 << 20, 8, 512, (2048 + 8) * 512)])
def test_tile_follows_the_step(assignments, experts, tile, padded):
    from deepspeed_tpu.moe.dropless import moe_tile_m, padded_rows

    assert moe_tile_m(assignments, experts) == tile
    assert padded_rows(assignments, experts) == padded


def test_preset_is_the_published_model():
    cfg = tfm.get_config("olmoe-1b-7b")
    assert cfg.num_params() == 6_919_161_856  # 6.92 B, as the model card
    experts = 16 * 64 * 3 * 2048 * 1024
    assert experts == 6_442_450_944  # 6.44 B of them in experts
    axes = tfm.param_axes(cfg)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, dataclasses.replace(
        cfg, num_layers=1)), jax.random.PRNGKey(0))
    assert shapes["layers"]["attn"]["q_norm"]["scale"].shape == (1, 2048)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        == jax.tree.structure(shapes)
