"""Mixed-precision GEMM: kernel numerics vs the dequant oracle, int4
packing round-trip, scan/pytree behavior, and quantized inference e2e."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                 dequantize_gemm_weight,
                                                 mixed_gemm,
                                                 quantize_gemm_weight)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("shape", [(64, 256, 256), (8, 512, 384)])
def test_kernel_matches_dequant_oracle(bits, shape):
    M, K, N = shape
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (K, N), jnp.float32)
    qw = quantize_gemm_weight(w, bits=bits, group=256)
    out = mixed_gemm(x, qw)
    ref = x @ dequantize_gemm_weight(qw).astype(jnp.float32)
    # bf16 MXU feed: tolerance is bf16-epsilon-scale relative to |ref|
    tol = 2e-2 * float(jnp.max(jnp.abs(ref))) + 1e-3
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_quantization_error_bounded():
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    for bits, tol in ((8, 0.02), (4, 0.35)):
        qw = quantize_gemm_weight(w, bits=bits)
        err = jnp.max(jnp.abs(dequantize_gemm_weight(qw) - w))
        assert float(err) < tol, (bits, float(err))


def test_int4_round_trip_exact_codes():
    # integer values whose per-(group, column) absmax is exactly qmax (7)
    # sit on the int4 grid (scale = 1) and must round-trip exactly
    rng = np.random.default_rng(0)
    w = rng.integers(-7, 8, size=(256, 128)).astype(np.float32)
    w[0, :] = 7.0  # pin the absmax of the single 256-row group
    qw = quantize_gemm_weight(jnp.asarray(w), bits=4, group=256)
    back = dequantize_gemm_weight(qw)
    np.testing.assert_allclose(back, w, atol=1e-5)


def test_unaligned_shapes_fall_back():
    # odd group (99) is fine for int8 (kpack=1): stays on the kernel path
    # (group == K satisfies the lane rule), so bf16-feed tolerance applies
    x = jax.random.normal(jax.random.PRNGKey(2), (7, 99), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (99, 33), jnp.float32)
    qw = quantize_gemm_weight(w, bits=8, group=256)  # group shrinks to 99
    out = mixed_gemm(x, qw)
    ref = x @ dequantize_gemm_weight(qw)
    tol = 2e-2 * float(jnp.max(jnp.abs(ref))) + 1e-3
    assert float(jnp.max(jnp.abs(out - ref))) < tol
    # group 49 ∤ 128 and group != K → genuinely off the kernel gate →
    # exact XLA dequant fallback
    x98 = x[:, :98]
    qw49 = quantize_gemm_weight(w[:98], bits=8, group=49)
    out_exact = mixed_gemm(x98, qw49)
    ref_exact = x98 @ dequantize_gemm_weight(qw49)
    np.testing.assert_allclose(out_exact, ref_exact, atol=1e-5, rtol=1e-5)
    # odd K with int4: zero-row padding packs cleanly and dequant drops it
    qw4 = quantize_gemm_weight(w, bits=4, group=256)
    assert qw4.codes.shape[-2] == 50 and qw4.k_features == 99
    out4 = mixed_gemm(x, qw4)
    ref4 = x @ dequantize_gemm_weight(qw4)
    np.testing.assert_allclose(out4, ref4, atol=1e-5, rtol=1e-5)
    assert dequantize_gemm_weight(qw4).shape == (99, 33)


def test_ragged_m_stays_on_kernel_path():
    # M=300 has no 8-aligned divisor: the pad-to-sublane path must keep the
    # kernel (not silently dequantize the whole weight) and match the oracle
    x = jax.random.normal(jax.random.PRNGKey(6), (300, 256), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(7), (256, 256), jnp.float32)
    qw = quantize_gemm_weight(w, bits=8, group=256)
    out = mixed_gemm(x, qw)
    ref = x @ dequantize_gemm_weight(qw).astype(jnp.float32)
    tol = 2e-2 * float(jnp.max(jnp.abs(ref))) + 1e-3
    assert out.shape == (300, 256)
    assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_quantized_tp_matches_single_device():
    from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
    from deepspeed_tpu.models import transformer as tfm

    cfg = tfm.get_config("tiny", hidden_size=128, intermediate_size=256,
                         num_layers=2, num_heads=4, vocab_size=512,
                         max_seq_len=128)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray([[5, 7, 11, 13]], np.int32)
    outs = []
    for tp in (1, 2):
        eng = InferenceEngine(
            model_config=cfg, params=params,
            config=InferenceConfig(dtype="float32", tensor_parallel_size=tp,
                                   quantize_bits=8))
        outs.append(eng.generate(prompt, max_new_tokens=6))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_stacked_layers_slice_under_scan():
    L, K, N = 3, 256, 256
    w = jax.random.normal(jax.random.PRNGKey(4), (L, K, N), jnp.float32)
    qw = quantize_gemm_weight(w, bits=8)
    x = jax.random.normal(jax.random.PRNGKey(5), (4, K), jnp.float32)

    def body(h, layer_qw):
        return mixed_gemm(h, layer_qw) / np.sqrt(K), None

    out, _ = jax.lax.scan(body, x, qw)
    ref = x
    deq = dequantize_gemm_weight(qw)
    for i in range(L):
        ref = (ref @ deq[i]) / np.sqrt(K)
    np.testing.assert_allclose(out, ref, atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("n", [256, 300], ids=["tiled", "fallback"])
@pytest.mark.parametrize("m", [8, 32, 512])
@pytest.mark.parametrize("bits", [8, 4, 6])
def test_layer_of_a_stack_is_read_in_place(bits, m, n, layer):
    """``mixed_gemm(x, stack, layer=i)`` on the codes ``(L, K, N)`` whole is
    bit for bit ``mixed_gemm(x, stack[i])``: eager, under ``jit`` with the
    layer traced, and in a ``lax.scan`` over the layers' indices (what the
    served layer loop does, in place of a scan that slices the stack and
    makes the kernel's operand a copy).  N = 300 does not tile: the fallback
    dequantizes the one layer.  The ring event says how many layers the
    operand held."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import mixed_gemm as mg

    L, K, group = 3, 256, 128
    kx, kw = jax.random.split(jax.random.PRNGKey(bits * 1000 + m + n))
    x = jax.random.normal(kx, (m, K), jnp.bfloat16)
    # another magnitude a layer and a group: the wrong layer's codes or the
    # wrong row of the scales' K column cannot pass
    w = jax.random.normal(kw, (L, K, n), jnp.float32) * (
        2.0 ** jnp.arange(L))[:, None, None] * jnp.repeat(
        jnp.asarray([1.0, 3.0]), group)[None, :, None]
    stack = quantize_gemm_weight(w, bits=bits, group=group)
    assert stack.codes.ndim == 3 and stack.scales.shape == (L, K // group, n)
    assert (mg.pick_gemm_tiles(m, K, n, bits, group) is None) == (n == 300)
    one = jax.tree.map(lambda a: a[layer], stack)

    want = mixed_gemm(x, one)
    np.testing.assert_array_equal(mixed_gemm(x, stack, layer=layer), want)
    event = tracer.spans(name="kernel/mixed_gemm_tiles")[-1].attrs
    assert event["layers"] == L and ("fallback" in event) == (n == 300)

    want_jit = jax.jit(mixed_gemm)(x, one)
    got = jax.jit(lambda x_, qw, i: mixed_gemm(x_, qw, layer=i))(
        x, stack, jnp.int32(layer))
    np.testing.assert_array_equal(got, want_jit)
    _, scanned = jax.lax.scan(
        lambda c, i: (c, mixed_gemm(x, stack, layer=i)), None,
        jnp.arange(L, dtype=jnp.int32))
    np.testing.assert_array_equal(scanned[layer], want_jit)
    _oracle_check(x.astype(jnp.float32), one, want.astype(jnp.float32))


def test_stack_and_layer_go_together():
    """Stacked codes without a layer, and a layer with 2-D codes, raise: the
    one would multiply by L matrices, the other index K."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import mixed_gemm_frozen

    w = jax.random.normal(jax.random.PRNGKey(0), (3, 256, 256), jnp.float32)
    stack = quantize_gemm_weight(w, bits=8, group=128)
    x = jnp.ones((8, 256), jnp.bfloat16)
    for gemm in (mixed_gemm, mixed_gemm_frozen):
        with pytest.raises(ValueError, match="need a layer index"):
            gemm(x, stack)
        with pytest.raises(ValueError, match="take no layer index"):
            gemm(x, jax.tree.map(lambda a: a[0], stack), jnp.int32(0))


def test_quantized_inference_end_to_end():
    from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
    from deepspeed_tpu.inference.quantization import quantized_bytes
    from deepspeed_tpu.models import transformer as tfm

    cfg = tfm.get_config("tiny", hidden_size=128, intermediate_size=256,
                         num_layers=2, num_heads=4, vocab_size=512,
                         max_seq_len=128)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray([[5, 7, 11, 13, 17, 19]], np.int32)

    exact = InferenceEngine(model_config=cfg, params=params,
                            config=InferenceConfig(dtype="float32"))
    quant = InferenceEngine(model_config=cfg, params=params,
                            config=InferenceConfig(dtype="float32",
                                                   quantize_bits=8))
    acct = quantized_bytes(quant.params)
    assert acct["quantized"] > 0
    out_e = exact.generate(prompt, max_new_tokens=8)
    out_q = quant.generate(prompt, max_new_tokens=8)
    assert out_e.shape == out_q.shape
    # int8 weight error can flip near-tie argmaxes on a random tiny model;
    # require strong (not exact) agreement so numerics shifts across
    # backends don't make the suite flaky
    agree = float(np.mean(out_e == out_q))
    assert agree >= 0.75, (agree, out_e, out_q)


def test_fp6_kernel_matches_dequant_oracle():
    """W6A16 (reference: FP6 cuda_linear GEMM): in-kernel fp6 decode must
    match the XLA dequant oracle within bf16-MXU tolerance."""
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    for (M, K, N) in ((64, 256, 256), (8, 512, 384)):
        x = jax.random.normal(kx, (M, K), jnp.float32)
        w = jax.random.normal(kw, (K, N), jnp.float32)
        qw = quantize_gemm_weight(w, bits=6, group=256)
        assert qw.codes.shape == (K // 4 * 3, N) and qw.codes.dtype == jnp.uint8
        out = mixed_gemm(x, qw)
        ref = x @ dequantize_gemm_weight(qw).astype(jnp.float32)
        tol = 2e-2 * float(jnp.max(jnp.abs(ref))) + 1e-3
        assert float(jnp.max(jnp.abs(out - ref))) < tol


def test_fp6_quantization_error_bounded():
    """fp6 e3m2 with per-group scaling: worst-case error is the half-ulp of
    the top binade, absmax * (ulp/2)/fmax = absmax * 2/28 = absmax/14 per
    group — bounded here by the global absmax (the worst group's)."""
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 256), jnp.float32)
    qw = quantize_gemm_weight(w, bits=6)
    err = float(jnp.max(jnp.abs(dequantize_gemm_weight(qw) - w)))
    bound = float(jnp.max(jnp.abs(w))) / 14 + 1e-6
    assert err <= bound, (err, bound)
    # and much tighter in relative terms than int4
    qw4 = quantize_gemm_weight(w, bits=4)
    err4 = float(jnp.max(jnp.abs(dequantize_gemm_weight(qw4) - w)))
    assert err < err4


def test_fp6_representable_values_roundtrip_exactly():
    """Values on the fp6 grid (scaled) must survive quantize→dequantize."""
    from deepspeed_tpu.ops.quantizer import _minifloat_magnitudes

    mags = np.asarray(_minifloat_magnitudes(3, 2))  # 32 magnitudes
    col = np.concatenate([mags, -mags])  # 64 values, absmax = 28 → scale 1
    w = jnp.asarray(np.tile(col[:, None], (1, 128)), jnp.float32)
    qw = quantize_gemm_weight(w, bits=6, group=64)
    np.testing.assert_array_equal(np.asarray(dequantize_gemm_weight(qw)),
                                  np.asarray(w))


def test_fp6_odd_k_pads_and_falls_back():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 130), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (130, 128), jnp.float32)
    qw = quantize_gemm_weight(w, bits=6, group=130)
    out = mixed_gemm(x, qw)  # K=130 not 4-divisible → oracle path
    ref = x @ dequantize_gemm_weight(qw).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_int8_gemm_w8a8_matches_quantized_oracle():
    """W8A8 (dynamic activation quantization + int8 MXU matmul): kernel
    output must equal quant(x) @ dequant(w) computed in fp32."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import (
        int8_gemm, quantize_activations_rowwise)

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    for (M, K, N) in ((64, 256, 256), (8, 512, 384)):
        x = jax.random.normal(kx, (M, K), jnp.float32)
        w = jax.random.normal(kw, (K, N), jnp.float32)
        qw = quantize_gemm_weight(w, bits=8, group=256)
        out = int8_gemm(x, qw)
        # oracle: same activation quantization, fp32 math
        codes, scales = quantize_activations_rowwise(x, qw.group)
        xq = (codes.astype(jnp.float32).reshape(M, K // qw.group, qw.group)
              * scales[..., None]).reshape(M, K)
        ref = xq @ dequantize_gemm_weight(qw).astype(jnp.float32)
        tol = 1e-3 * float(jnp.max(jnp.abs(ref))) + 1e-4
        assert float(jnp.max(jnp.abs(out - ref))) < tol, (M, K, N)
        # and end-to-end accuracy vs fp32 is int8-grade, not garbage
        exact = x @ w
        rel = float(jnp.abs(out - exact).mean() / jnp.abs(exact).mean())
        assert rel < 0.05, rel


def test_int8_gemm_rejects_non8bit_and_falls_back():
    from deepspeed_tpu.ops.pallas.mixed_gemm import int8_gemm

    x = jax.random.normal(jax.random.PRNGKey(0), (4, 130), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (130, 128), jnp.float32)
    with pytest.raises(ValueError, match="bits=8"):
        int8_gemm(x, quantize_gemm_weight(w, bits=4, group=130))
    qw = quantize_gemm_weight(w, bits=8, group=130)  # odd K → oracle path
    out = int8_gemm(x, qw)
    ref = x @ dequantize_gemm_weight(qw).astype(x.dtype)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# a k-tile of several quantization groups, tiles picked from the shapes
# ---------------------------------------------------------------------------


def _oracle_check(x, qw, out):
    ref = x @ dequantize_gemm_weight(qw).astype(jnp.float32)
    tol = 2e-2 * float(jnp.max(jnp.abs(ref))) + 1e-3
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < tol


@pytest.mark.parametrize("bits", [8, 4, 6])
@pytest.mark.parametrize("m", [32, 37], ids=["m32", "ragged"])
@pytest.mark.parametrize("groups,g", [(4, 2), (4, 4), (7, 7), (7, 1)],
                         ids=["4x2", "4xK", "7xK", "7x1"])
def test_k_tile_of_several_groups(monkeypatch, bits, m, groups, g):
    """``tk = g * group``: each group of the k-tile is dequantized with its
    own scale row.  N = 640 makes the in-kernel column chunks ragged (512
    and 128).  The tile is forced where the picker would choose another."""
    from deepspeed_tpu.ops.pallas import mixed_gemm as mg

    group, N = 128, 640
    K = groups * group
    kx, kw = jax.random.split(jax.random.PRNGKey(groups * 10 + g))
    x = jax.random.normal(kx, (m, K), jnp.float32)
    # a different magnitude for every group, so a scale row applied to the
    # wrong group cannot pass
    w = jax.random.normal(kw, (K, N), jnp.float32) * jnp.repeat(
        2.0 ** jnp.arange(groups), group)[:, None]
    qw = quantize_gemm_weight(w, bits=bits, group=group)
    assert qw.group == group and qw.scales.shape == (groups, N)
    mp = m + (-m) % 8
    picked = mg.pick_gemm_tiles(mp, K, N, bits, group, 4)
    forced = mg.GemmTiles(mp, N, g * group, groups // g, 0)
    monkeypatch.setattr(mg, "pick_gemm_tiles", lambda *a: forced)
    _oracle_check(x, qw, mixed_gemm(x, qw))
    assert picked.tm == mp and K % picked.tk == 0 and picked.tk % group == 0


@pytest.mark.parametrize("m,k,n,bits,group", [
    (32, 4096, 4096, 8, 256), (32, 4096, 1024, 8, 256),
    (32, 4096, 14336, 8, 256), (32, 14336, 4096, 8, 256),
    (512, 4096, 14336, 8, 256), (512, 14336, 4096, 8, 256),
    (512, 4096, 14336, 4, 256), (32, 14336, 4096, 6, 256),
    (1024, 4096, 14336, 8, 128), (8, 512, 384, 8, 256),
    (304, 256, 256, 8, 256), (8, 99, 33, 8, 99), (8, 100, 33, 4, 100),
])
def test_picked_tiles_are_legal(m, k, n, bits, group):
    from deepspeed_tpu.ops.pallas import mixed_gemm as mg

    t = mg.pick_gemm_tiles(m, k, n, bits, group)
    assert m % t.tm == 0 and (t.tm == m or t.tm % 8 == 0) and t.tm <= 512
    assert n % t.tn == 0 and (t.tn % 128 == 0 or t.tn == n)
    assert k % t.tk == 0 and t.tk % group == 0
    assert t.grid_steps == (m // t.tm) * (n // t.tn) * (k // t.tk)
    assert t.code_bytes_per_step == t.tk * t.tn * bits // 8
    if m <= 512:
        assert t.tm == m  # one pass over the weights
    if bits == 8 and k * n >= 4 << 20:
        assert t.code_bytes_per_step >= 1 << 20


@pytest.mark.parametrize("k,n,bits,group", [
    (98, 33, 8, 49),  # group neither lane-aligned nor all of K
    (130, 128, 6, 130),  # fp6 packs 4 rows of K in 3 bytes
    (256, 300, 8, 256),  # N wider than a small whole block, not lane-aligned
    (99, 128, 4, 99),  # int4 packs two rows of K a byte
])
def test_shapes_off_the_envelope_pick_nothing(k, n, bits, group):
    from deepspeed_tpu.ops.pallas import mixed_gemm as mg

    assert mg.pick_gemm_tiles(8, k, n, bits, group) is None


def test_tile_choice_is_recorded_at_trace_time():
    """One ring event a traced call, with the tile the picker returns, or
    ``fallback`` where the call gave way to dequantize-then-matmul."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import mixed_gemm as mg

    def last():
        return tracer.spans(name="kernel/mixed_gemm_tiles")[-1].attrs

    M, K, N = 24, 768, 640
    x = jax.random.normal(jax.random.PRNGKey(0), (M, K), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32)
    qw = quantize_gemm_weight(w, bits=8, group=128)
    before = len(tracer.spans(name="kernel/mixed_gemm_tiles"))
    fn = jax.jit(mixed_gemm)
    fn(x, qw)
    fn(x, qw)  # the second call is not traced again: no second event
    assert len(tracer.spans(name="kernel/mixed_gemm_tiles")) == before + 1
    t = mg.pick_gemm_tiles(M, K, N, 8, 128, 4)
    assert last() == {"m": M, "k": K, "n": N, "bits": 8, "group": 128,
                      "layers": 0, "tm": t.tm, "tn": t.tn, "tk": t.tk,
                      "grid_steps": t.grid_steps,
                      "code_bytes_per_step": t.code_bytes_per_step}
    qw49 = quantize_gemm_weight(w[:98, :33], bits=8, group=49)
    mixed_gemm(x[:, :98], qw49)
    assert last() == {"m": M, "k": 98, "n": 33, "bits": 8, "group": 49,
                      "layers": 0, "fallback": 1}
