"""Per-op numeric tests (reference: tests/unit/ops — adam, quantizer)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.fused_optimizers import (FusedAdamState, fused_adamw_tree,
                                                init_fused_adam_state)
from deepspeed_tpu.ops.quantizer import (compressed_all_reduce,
                                         dequantize_blockwise,
                                         quantize_blockwise,
                                         quantize_stochastic)
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.runtime.config import MeshConfig


# ---------------------------------------------------------------------------
# quantizer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bits", [8, 4])
def test_quantize_roundtrip_error_bounded(bits):
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    codes, scales = quantize_blockwise(x, bits=bits, block_size=128)
    y = dequantize_blockwise(codes, scales, bits=bits, block_size=128,
                             shape=x.shape)
    qmax = 127 if bits == 8 else 7
    per_block_bound = np.abs(np.asarray(x)).max() / qmax * 0.51 * 2
    assert float(jnp.abs(y - x).max()) <= per_block_bound


def test_quantize_int4_packing():
    x = jnp.arange(-8.0, 8.0)  # exactly representable in int4 range scaled
    codes, scales = quantize_blockwise(x, bits=4, block_size=16)
    assert codes.shape == (1, 8)  # 16 values packed into 8 bytes


def test_quantize_zero_block():
    x = jnp.zeros((256,))
    codes, scales = quantize_blockwise(x, bits=8)
    y = dequantize_blockwise(codes, scales, shape=x.shape)
    np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_stochastic_rounding_unbiased():
    x = jnp.full((512,), 0.3)
    acc = np.zeros(512)
    for s in range(200):
        codes, scales = quantize_stochastic(x, seed=s, block_size=512)
        acc += np.asarray(codes.astype(jnp.float32) * scales[:, None]).reshape(-1)
    mean = acc.mean() / 200
    np.testing.assert_allclose(mean, 0.3, rtol=0.05)


def test_compressed_all_reduce(devices):
    mesh = MeshTopology.from_config(MeshConfig()).mesh
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 512))

    def f(x):
        return compressed_all_reduce(x[0], "dp")

    out = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P(None),
                    check_vma=False)(x)
    exact = np.asarray(x).sum(axis=0)
    err = np.abs(np.asarray(out) - exact).max()
    scale = np.abs(exact).max()
    assert err < scale * 0.05, (err, scale)


# ---------------------------------------------------------------------------
# fused adam
# ---------------------------------------------------------------------------


def test_fused_adamw_matches_optax():
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (130, 7)),
              "b": jnp.zeros((11,))}
    grads = {"w": jax.random.normal(jax.random.PRNGKey(1), (130, 7)),
             "b": jnp.ones((11,))}
    lr, wd = 1e-2, 0.0

    state = init_fused_adam_state(params)
    p_fused, state = fused_adamw_tree(params, grads, state, lr=lr)
    p_fused, state = fused_adamw_tree(p_fused, grads, state, lr=lr)

    opt = optax.adam(lr)
    ost = opt.init(params)
    p_ref = params
    for _ in range(2):
        upd, ost = opt.update(jax.tree.map(lambda g: g, grads), ost, p_ref)
        p_ref = optax.apply_updates(p_ref, upd)

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4), p_fused, p_ref)


def test_fused_adamw_weight_decay():
    params = {"w": jnp.ones((100,))}
    grads = {"w": jnp.zeros((100,))}
    state = init_fused_adam_state(params)
    p1, _ = fused_adamw_tree(params, grads, state, lr=0.1, weight_decay=0.1)
    # zero grad, wd pulls toward zero: p = 1 - lr*wd*1
    np.testing.assert_allclose(np.asarray(p1["w"]), 0.99, rtol=1e-5)


def test_fp8_roundtrip():
    from deepspeed_tpu.ops.quantizer import dequantize_fp8, quantize_fp8

    x = jax.random.normal(jax.random.PRNGKey(7), (1000,)) * 3.0
    codes, scales = quantize_fp8(x, block_size=128)
    assert codes.dtype == jnp.float8_e4m3fn
    y = dequantize_fp8(codes, scales, shape=x.shape)
    # e4m3 has ~2 decimal digits: relative error per element < 2^-3 of absmax
    rel = float(jnp.abs(y - x).max() / jnp.abs(x).max())
    assert rel < 0.07, rel


def test_minifloat_fp6_fp12_roundtrip():
    """FP6 (e3m2) / FP12 (e5m6) tier (reference: csrc/fp_quantizer): every
    representable value round-trips exactly; block quantization error is
    bounded; packing is lossless."""
    from deepspeed_tpu.ops.quantizer import (_minifloat_magnitudes,
                                             dequantize_minifloat,
                                             minifloat_decode,
                                             minifloat_encode, pack_fp6,
                                             pack_fp12, quantize_minifloat,
                                             unpack_fp6, unpack_fp12)

    for bits, (e, m) in ((6, (3, 2)), (12, (5, 6))):
        mags = np.asarray(_minifloat_magnitudes(e, m))
        vals = jnp.asarray(np.concatenate([mags, -mags]))
        dec = minifloat_decode(minifloat_encode(vals, e, m), e, m)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(vals))

    c6 = jnp.asarray(np.random.default_rng(0).integers(0, 64, 256))
    np.testing.assert_array_equal(np.asarray(unpack_fp6(pack_fp6(c6))),
                                  np.asarray(c6))
    c12 = jnp.asarray(np.random.default_rng(1).integers(0, 4096, 128))
    np.testing.assert_array_equal(np.asarray(unpack_fp12(pack_fp12(c12))),
                                  np.asarray(c12))

    x = np.random.default_rng(2).standard_normal(4096).astype(np.float32)
    for bits, tol in ((6, 0.1), (12, 0.005)):
        packed, scales = quantize_minifloat(jnp.asarray(x), bits)
        y = np.asarray(dequantize_minifloat(packed, scales, bits,
                                            shape=x.shape))
        rel = np.abs(y - x).mean() / np.abs(x).mean()
        assert rel < tol, (bits, rel)
