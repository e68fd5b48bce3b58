"""Mixed-GEMM as the *compute path*: the kernel swap must be invisible.

`tests/test_mixed_gemm.py` proves the kernel's numerics against the dequant
oracle in isolation; this suite proves the *wiring* — the quantized frozen
base in `linear/optimized_linear.py` and the quantized serving path in
`inference/v2` actually route through the Pallas kernel, and doing so
changes nothing observable: forward parity across bits/group/odd-K/
scan-stacked layers, gradient flow through the frozen base, and
token-identical greedy serving output vs the pre-swap dequantize-then-dot
path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.linear.config import LoRAConfig, QuantizationConfig
from deepspeed_tpu.linear.optimized_linear import (LoRAWeight,
                                                   QuantizedBaseWeight,
                                                   init_lora_weight,
                                                   lora_forward,
                                                   quantize_base_weight)
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import mixed_gemm as mg


def _dequant_path(x, w: LoRAWeight):
    """The pre-swap forward: materialize the base, then dense dot."""
    dt = x.dtype
    mat = jax.lax.stop_gradient(w.base_materialized(dt))
    ax = x @ w.lora_a.astype(dt)
    return x @ mat + (ax @ w.lora_b.astype(dt)) * w.scaling


def _lora_weight(key, k, n, qcfg: QuantizationConfig, r=4):
    kw, ka = jax.random.split(key)
    w = jax.random.normal(kw, (k, n), jnp.float32) / np.sqrt(k)
    lw = init_lora_weight(ka, w, LoRAConfig(
        enabled=True, lora_r=r, lora_alpha=8.0, quantize_base=True,
        quantization=qcfg))
    # adapters start with B=0; randomize so the test sees base + adapter
    lw.lora_b = jax.random.normal(ka, lw.lora_b.shape, jnp.float32) * 0.1
    return lw


@pytest.mark.parametrize("bits,mantissa", [(8, 0), (4, 0), (6, 2)])
@pytest.mark.parametrize("k,n,group", [(256, 256, 128), (256, 128, 256),
                                       (200, 128, 256)])  # odd K: shrink
def test_lora_forward_kernel_matches_dequant_path(bits, mantissa, k, n,
                                                  group):
    qcfg = QuantizationConfig(q_bits=bits, mantissa_bits=mantissa,
                              group_size=group)
    lw = _lora_weight(jax.random.PRNGKey(0), k, n, qcfg)
    assert isinstance(lw.base, QuantizedBaseWeight)
    assert lw.base.layout == "gemm"
    x = jax.random.normal(jax.random.PRNGKey(1), (8, k), jnp.bfloat16)
    got = lora_forward(x, lw)
    ref = _dequant_path(x, lw)
    tol = 2e-2 * float(jnp.max(jnp.abs(ref)).astype(jnp.float32)) + 1e-3
    assert float(jnp.max(jnp.abs((got - ref).astype(jnp.float32)))) < tol


def test_kernel_path_actually_taken(monkeypatch):
    """The bf16 gemm-layout forward must call the kernel — a silent fall
    back to materialize-then-dot would pass every parity check while
    paying the 2·K·N HBM traffic the PR exists to remove."""
    import deepspeed_tpu.linear.optimized_linear as ol

    calls = []
    real = ol.mixed_gemm_frozen
    monkeypatch.setattr(ol, "mixed_gemm_frozen",
                        lambda x, qw: calls.append(1) or real(x, qw))
    lw = _lora_weight(jax.random.PRNGKey(0), 256, 256, QuantizationConfig(
        q_bits=8, mantissa_bits=0, group_size=256))
    x = jnp.ones((4, 256), jnp.bfloat16)
    lora_forward(x, lw)
    assert calls, "gemm-layout bf16 base took the dequant path"
    # f32 activations keep the full-precision dot (test_linear contract)
    calls.clear()
    lora_forward(jnp.ones((4, 256), jnp.float32), lw)
    assert not calls


def test_grad_flows_through_frozen_base():
    """d/dx must flow *through* the kernel (earlier layers' adapters need
    the cotangent) and match the dequant path's gradient; the codes get
    none (frozen-base contract)."""
    qcfg = QuantizationConfig(q_bits=8, mantissa_bits=0, group_size=128)
    lw = _lora_weight(jax.random.PRNGKey(2), 256, 128, qcfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 256), jnp.bfloat16)

    g_kernel = jax.grad(lambda xx: lora_forward(xx, lw).astype(
        jnp.float32).sum())(x)
    g_ref = jax.grad(lambda xx: _dequant_path(xx, lw).astype(
        jnp.float32).sum())(x)
    np.testing.assert_allclose(np.asarray(g_kernel, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=0.05, atol=0.05)


def test_scan_stacked_layers_parity():
    """Stacked per-layer bases slice to 2-D under lax.scan and must hit the
    kernel per layer, matching a per-layer dequant loop."""
    layers, k, n = 3, 256, 256
    qcfg = QuantizationConfig(q_bits=8, mantissa_bits=0, group_size=128)
    w = jax.random.normal(jax.random.PRNGKey(4), (layers, k, n),
                          jnp.float32) / np.sqrt(k)
    qb = quantize_base_weight(w, qcfg)
    assert qb.layout == "gemm" and qb.codes.ndim == 3
    x0 = jax.random.normal(jax.random.PRNGKey(5), (8, k), jnp.bfloat16)

    def step(x, layer_qw):
        y = mg.mixed_gemm_frozen(x, layer_qw)
        return y[:, :k].astype(jnp.bfloat16), y

    _, ys = jax.lax.scan(step, x0, qb.as_gemm_weight())
    x = x0
    for i in range(layers):
        per = mg.QuantizedWeight(qb.codes[i], qb.scales[i], qb.q_bits,
                                 qb.group_size, k=k)
        ref = x @ mg.dequantize_gemm_weight(per).astype(x.dtype)
        tol = 2e-2 * float(jnp.max(jnp.abs(ref)).astype(jnp.float32)) + 1e-3
        assert float(jnp.max(jnp.abs(
            (ys[i] - ref).astype(jnp.float32)))) < tol, f"layer {i}"
        x = ref[:, :k].astype(jnp.bfloat16)


def test_dequantize_defaults_to_compute_dtype():
    """Satellite: the fallback/export dequant materializes in bf16 by
    default (half the temp spike of the old f32 default); f32 stays one
    explicit argument away."""
    qcfg = QuantizationConfig(q_bits=8, mantissa_bits=0, group_size=128)
    qb = quantize_base_weight(
        jax.random.normal(jax.random.PRNGKey(6), (256, 128), jnp.float32),
        qcfg)
    assert qb.dequantize().dtype == jnp.bfloat16
    assert qb.dequantize(jnp.float32).dtype == jnp.float32
    lw = LoRAWeight(base=qb, lora_a=jnp.zeros((256, 4), jnp.float32),
                    lora_b=jnp.zeros((4, 128), jnp.float32))
    assert lw.base_materialized().dtype == jnp.bfloat16


# -- greedy serving token identity ------------------------------------------


def _greedy_tokens(cfg, params, prompts, max_new):
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config

    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="bfloat16", quantize_bits=8,
        quantize_group=256))
    uids = [eng.put(p, max_new_tokens=max_new) for p in prompts]
    results = eng.generate_all()
    return [results[u] for u in uids]


def test_greedy_serving_token_identity_pre_post_swap(monkeypatch):
    """Greedy decode over the W8A16 base must emit the exact token ids the
    pre-swap dequantize-then-dot path emitted — same quantized params, so
    the only moving part is the kernel, and int8 in-kernel dequant is
    bit-exact against the oracle."""
    cfg = tfm.get_config("tiny", dtype="bfloat16")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7, 8], [1, 2, 3], [9, 8, 7, 6, 5]]

    kernel_out = _greedy_tokens(cfg, params, prompts, max_new=8)

    # pre-swap behavior: full-matrix dequant + dense dot in the model fwd.
    # The second engine has the first's sizes: from ``_memo``'s cache it
    # would be handed the programs that closed over the kernel, and the patch
    # would never be traced (ROADMAP D11), so the programs are built anew
    from deepspeed_tpu.inference.v2 import programs

    traced = []

    def dequant_dot(x, qw, layer=None):
        traced.append(layer)
        w = mg.dequantize_gemm_weight(mg.layer_of_stack(qw, layer))
        return x @ w.astype(x.dtype)

    monkeypatch.setattr(programs, "_BUILD_CACHE", {})
    monkeypatch.setattr(tfm, "mixed_gemm_frozen", dequant_dot)
    dequant_out = _greedy_tokens(cfg, params, prompts, max_new=8)

    assert traced and None not in traced  # every GEMM of the layer stacks
    assert kernel_out == dequant_out


# -- the quantized stacks through the one serving layer body -----------------

_STACK_V2 = dict(max_tokens_per_step=16, max_seqs=4, block_size=8,
                 num_blocks=64, max_blocks_per_seq=8, dtype="bfloat16",
                 quantize_bits=8, quantize_group=128)


def _adapter_pack(cfg, rank=4):
    from deepspeed_tpu.inference.v2.engine import adapter_target_shapes

    rng = np.random.default_rng(7)
    return {target: ((rng.standard_normal((cfg.num_layers, K, rank))
                      / np.sqrt(K)).astype(np.float32),
                     (0.5 * rng.standard_normal((cfg.num_layers, rank, N))
                      ).astype(np.float32))
            for target, (K, N) in adapter_target_shapes(cfg).items()}


@pytest.mark.parametrize("path,over", [
    ("steps", {}), ("burst", {}),
    ("self_draft", dict(spec_mode="self_draft", spec_k=3)),
    ("adapter", dict(adapter_slots=2, adapter_rank=4)),
], ids=["steps", "burst", "self_draft", "adapter"])
def test_served_stacks_give_the_sliced_scans_tokens(monkeypatch, path, over):
    """A three-layer W8A16 model through ``serving_layers``: chunked prefill
    (20-token prompts, 16 tokens a step), single decode steps, the burst
    program, the self-draft verify step, and per-row LoRA deltas on the
    quantized base give exactly the tokens of the parent's programs, whose
    layer scan sliced every ``QuantizedWeight`` (and copied it: what
    ``hoist_quantized`` is for).  Every mixed GEMM a served program traced
    read a stack of three layers in place and none fell back; the sliced
    programs' events say ``layers`` 0."""
    import dataclasses

    from deepspeed_tpu.inference.v2 import programs
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.serving.adapters import AdapterRegistry

    cfg = dataclasses.replace(tfm.get_config("tiny", dtype="bfloat16"),
                              num_layers=3, num_kv_heads=2)
    params = jax.tree.map(lambda a: a * 3.0 if a.ndim >= 2 else a,
                          tfm.init_params(jax.random.PRNGKey(1), cfg))
    rng = np.random.default_rng(1)
    first = rng.integers(1, 256, 20).tolist()
    prompts = [first, first[:12] + rng.integers(1, 256, 4).tolist(), [42]]

    def serve():
        monkeypatch.setattr(programs, "_BUILD_CACHE", {})  # trace anew
        tracer.clear()
        eng = InferenceEngineV2(cfg, params, V2Config(**{**_STACK_V2,
                                                         **over}))
        slot = 0
        if path == "adapter":
            reg = AdapterRegistry(eng)
            reg.register("a", pack=_adapter_pack(cfg))
            slot = reg.acquire("a")
        uids = [eng.put(list(p), max_new_tokens=10,
                        **({"adapter_slot": slot if i != 1 else 0}
                           if path == "adapter" else {}))
                for i, p in enumerate(prompts)]
        res = eng.generate_all(burst=4 if path == "burst" else 1)
        if path == "burst":
            assert eng.burst_steps > 0
        if path == "self_draft":
            assert eng.spec_steps > 0
        events = [s.attrs
                  for s in tracer.spans(name="kernel/mixed_gemm_tiles")]
        assert events and not any("fallback" in e for e in events)
        return [res[u] for u in uids], {e["layers"] for e in events}

    got, layers = serve()
    assert layers == {cfg.num_layers}
    monkeypatch.setattr(  # the scan slices it all
        programs, "hoist_quantized", lambda tree: (tree, lambda lp, _: lp))
    want, layers = serve()
    assert layers == {0}
    assert got == want
