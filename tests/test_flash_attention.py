"""Flash-attention kernel numeric tests against the XLA reference
(reference model: tests/unit/ops per-kernel numeric tests)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas.flash_attention import flash_attention, mha_reference


def _rand_qkv(key, B, S, H, D, KV=None, dtype=jnp.float32):
    KV = KV or H
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (B, S, H, D), dtype)
    k = jax.random.normal(k2, (B, S, KV, D), dtype)
    v = jax.random.normal(k3, (B, S, KV, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_reference(devices, causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 128, 4, 32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gqa_forward(devices):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 128, 8, 32, KV=2)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gradients_match_reference(devices):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 2, 32)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                block_q=64, block_k=64) ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_gqa_gradients(devices):
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 64, 4, 32, KV=2)

    def f_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True,
                                block_q=32, block_k=32) ** 2).sum()

    def f_ref(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_unaligned_falls_back(devices):
    # S=100 not divisible by blocks → falls back to XLA path, still correct
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 100, 2, 16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [16, 64])
def test_sliding_window_matches_reference(devices, window):
    from deepspeed_tpu.ops.pallas.flash_attention import _windowed_reference

    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 128, 4, 32)
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32)
    ref = _windowed_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sliding_window_gradients(devices):
    from deepspeed_tpu.ops.pallas.flash_attention import _windowed_reference

    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 64, 2, 32)
    f_k = lambda q, k, v: (flash_attention(q, k, v, causal=True, window=16,
                                           block_q=16, block_k=16) ** 2).sum()
    f_r = lambda q, k, v: (_windowed_reference(q, k, v, True, 16)
                           .astype(jnp.float32) ** 2).sum()
    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b, n in zip(gk, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=f"d{n}")


def test_sliding_window_model_config(devices):
    from deepspeed_tpu.models import transformer as tfm

    cfg = tfm.get_config("tiny", attn_impl="flash", sliding_window=16,
                         dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = np.random.default_rng(0).integers(0, 256, (1, 64)).astype(np.int32)
    logits = tfm.forward(params, tokens, cfg)
    assert logits.shape == (1, 64, 256)
    # wrong impl rejected
    bad = tfm.get_config("tiny", attn_impl="xla", sliding_window=16)
    with pytest.raises(ValueError):
        tfm.forward(params, tokens, bad)


# ---------------------------------------------------------------------------
# the operand rule (ISSUE 45): every dot multiplies its operands in the dtype
# the caller handed in and sums in float32; p and ds are rounded to that
# dtype immediately before their dots; softmax, lse, delta and every
# accumulator are float32 whatever the input
# ---------------------------------------------------------------------------

# name → (query-key width, value width, heads, KV heads): the dense models'
# 128 / 128 under GQA, and latent attention's 192 / 128
WIDTHS = {"128-128": (128, 128, 4, 2), "192-128": (192, 128, 2, 2)}
# dots a body of a kernel holds: q kᵀ and p v; q kᵀ, pᵀ dO, dO vᵀ and dsᵀ q;
# q kᵀ, dO vᵀ and ds k.  A causal kernel holds two bodies of one function:
# the tile on the diagonal and the interior tile (ISSUE 61)
KERNEL_DOTS = {"flash_attention_fwd": 2, "flash_attention_bwd_dkv": 4,
               "flash_attention_bwd_dq": 3}
# name → flash_attention's keywords and whether segment ids ride along
MASKS = {"causal": (dict(causal=True), False),
         "window": (dict(causal=True, window=96), False),
         "segments": (dict(causal=True), True)}
SEQ, BLOCK = 256, 128


def _inputs(width, dtype, seed=7):
    d_qk, d_v, heads, kv = WIDTHS[width]
    kq, kk, kv_, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(kq, (1, SEQ, heads, d_qk), jnp.float32)
    k = jax.random.normal(kk, (1, SEQ, kv, d_qk), jnp.float32)
    v = jax.random.normal(kv_, (1, SEQ, kv, d_v), jnp.float32)
    w = jax.random.normal(kw, (1, SEQ, heads, d_v), jnp.float32)
    return tuple(x.astype(dtype) for x in (q, k, v, w))


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        for x in (val if isinstance(val, (list, tuple)) else [val]):
            x = getattr(x, "jaxpr", x)
            if hasattr(x, "eqns"):
                yield x


def _pallas_calls(jaxpr, found):
    """name → the ``pallas_call`` equation of that name, at any depth."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = eqn
        else:
            for sub in _sub_jaxprs(eqn):
                _pallas_calls(sub, found)
    return found


def _dots(jaxpr, found):
    """[(dot equation, the equations that made its two operands)] of a
    kernel's body, through the ``pl.when`` branches."""
    made_by = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append((eqn, [made_by.get(v) for v in eqn.invars]))
        for sub in _sub_jaxprs(eqn):
            _dots(sub, found)
    return found


@functools.lru_cache(maxsize=None)
def _traced_kernels(width, dtype):
    q, k, v, w = _inputs(width, jnp.dtype(dtype))

    def loss(q_, k_, v_):
        out = flash_attention(q_, k_, v_, causal=True, block_q=BLOCK,
                              block_k=BLOCK)
        return (out.astype(jnp.float32) * w.astype(jnp.float32)).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    return _pallas_calls(jaxpr.jaxpr, {})


@pytest.mark.parametrize("kernel", sorted(KERNEL_DOTS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_dots_multiply_what_they_are_given(devices, width, dtype, kernel):
    """Every ``dot_general`` inside a flash kernel takes both operands in the
    caller's dtype and gives float32, and no conversion to float32 feeds
    one (with float32 inputs the casts of p and ds are no conversion)."""
    call = _traced_kernels(width, dtype)[kernel]
    dots = _dots(call.params["jaxpr"], [])
    assert len(dots) == 2 * KERNEL_DOTS[kernel]
    for dot, makers in dots:
        assert [str(x.aval.dtype) for x in dot.invars] == [dtype, dtype]
        assert dot.outvars[0].aval.dtype == jnp.float32
        assert dot.params["preferred_element_type"] == jnp.float32
        for maker in makers:
            assert not (maker is not None
                        and maker.primitive.name == "convert_element_type"
                        and maker.params["new_dtype"] == jnp.float32), maker


@pytest.mark.parametrize("kernel", sorted(KERNEL_DOTS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_accumulators_and_lse_are_float32(devices, dtype, kernel):
    """The scratch a kernel sums into (acc / m / l; dk / dv; dq) is float32
    whatever the input, as are lse and delta where they cross HBM; what the
    kernels hand back (out; dk, dv; dq) is in the caller's dtype."""
    call = _traced_kernels("192-128", dtype)[kernel]
    body = call.params["jaxpr"]
    scratch = body.invars[-call.params["grid_mapping"].num_scratch_operands:]
    assert len(scratch) == {"flash_attention_fwd": 3,
                            "flash_attention_bwd_dkv": 2,
                            "flash_attention_bwd_dq": 1}[kernel]
    assert all(s.aval.dtype == jnp.float32 for s in scratch)
    rows = [x.aval for x in body.invars if x.aval.shape[-1] == 1
            and x not in scratch]  # lse, delta: (1, 1, block, 1)
    outs = [str(a.dtype) for a in call.params["out_avals"]]
    if kernel == "flash_attention_fwd":
        assert outs == [dtype, "float32"]  # out, lse
    else:
        assert set(outs) == {dtype}
        assert len(rows) == 2  # lse and delta come in
    assert all(a.dtype == jnp.float32 for a in rows)


@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_lse_of_bfloat16_inputs_is_summed_in_float32(devices, width):
    """A bf16 x bf16 product is exact in float32, so the saved logsumexp of
    bf16 inputs agrees with the float32 reference on the same values to
    float32's rounding: nothing before the softmax was rounded to bf16."""
    from deepspeed_tpu.ops.pallas.flash_attention import _flash_fwd

    q, k, v, _ = _inputs(width, jnp.bfloat16)
    d_qk, _, heads, kv = WIDTHS[width]
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = _flash_fwd(qt, kt, vt, None, None, None, d_qk ** -0.5, True,
                          BLOCK, BLOCK)
    assert (out.dtype, lse.dtype) == (jnp.bfloat16, jnp.float32)
    s = jnp.einsum("bhsd,bhtd->bhst", qt.astype(jnp.float32),
                   jnp.repeat(kt, heads // kv, axis=1).astype(jnp.float32),
                   precision="highest") * d_qk ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), s, -jnp.inf)
    ref = jax.nn.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _rel_l2(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# bf16 keeps 8 bits of mantissa: one rounding moves a value by at most 2^-9
# of itself.  The forward rounds p once and the output once; a gradient
# rounds p or ds, reads a dO and an output that were rounded, and is rounded
# itself.  Independent roundings add in squares, so over a whole tensor the
# error norm stays under 2^-8 of the result's norm (read here: 0.0018-0.0019
# forward, 0.0022-0.0026 the gradients), and the element furthest off lies
# within two roundings of the largest element (read: 0.0019-0.0046 of it).
BF16_REL_L2 = 2.0 ** -8


@functools.lru_cache(maxsize=None)
def _bf16_against_float32(width, mask):
    """→ {"out" | "dq" | "dk" | "dv": (kernel on bf16 inputs, reference in
    float32 on the same values)}."""
    from deepspeed_tpu.ops.pallas.flash_attention import _reference_attention

    kwargs, with_seg = MASKS[mask]
    q, k, v, w = _inputs(width, jnp.bfloat16)
    seg = (jnp.arange(SEQ)[None] // 100).astype(jnp.int32) if with_seg \
        else None
    wf = w.astype(jnp.float32)

    def ref(q_, k_, v_):
        return _reference_attention(
            q_, k_, v_, causal=True, window=kwargs.get("window", 0),
            segment_ids=seg, block_mask=None, block_q=1, block_k=1)

    def kernel(q_, k_, v_):
        return flash_attention(q_, k_, v_, block_q=BLOCK, block_k=BLOCK,
                               segment_ids=seg, **kwargs)

    f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
    want_out = ref(*f32)
    want = jax.grad(lambda *a: (ref(*a) * wf).sum(), argnums=(0, 1, 2))(*f32)
    got_out = kernel(q, k, v)
    got = jax.grad(lambda *a: (kernel(*a).astype(jnp.float32) * wf).sum(),
                   argnums=(0, 1, 2))(q, k, v)
    assert got_out.dtype == jnp.bfloat16
    assert all(g.dtype == jnp.bfloat16 for g in got)
    return {"out": (got_out, want_out), "dq": (got[0], want[0]),
            "dk": (got[1], want[1]), "dv": (got[2], want[2])}


@pytest.mark.parametrize("which", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("mask", sorted(MASKS))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_bfloat16_inputs_match_the_float32_reference(devices, width, mask,
                                                     which):
    """Forward and the three gradients on bf16 inputs against
    ``_reference_attention`` on the same values in float32: the error norm
    stays under 2^-8 of the result's norm, and no element lies further off
    than 2^-7 of the largest."""
    got, want = _bf16_against_float32(width, mask)[which]
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert _rel_l2(got, want) < BF16_REL_L2
    worst = np.abs(np.asarray(got, np.float32) - np.asarray(want)).max()
    assert worst < 2 * BF16_REL_L2 * np.abs(np.asarray(want)).max()


# ---------------------------------------------------------------------------
# what the operands' dtype decides besides the dots: the blocks past one lane
# tile, and the blocks a step outside the band names
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,d_qk,blocks", [
    ("bfloat16", 192, 1024), ("float16", 192, 1024), ("float32", 192, 512),
    ("bfloat16", 128, 1024), ("float32", 128, 1024)])
def test_blocks_past_one_lane_tile_follow_the_operand_width(devices, dtype,
                                                            d_qk, blocks):
    """A query-key width past 128 is held to blocks of 512 only where the
    blocks are float32 (at 1024 they pass the scoped VMEM beside the
    kernel's float32 tiles); 16-bit blocks take the caller's 1024, as every
    dtype does at a width of 128.  Read off the event a traced call leaves."""
    from deepspeed_tpu.observability.trace import tracer

    qk = jax.ShapeDtypeStruct((1, 2048, 2, d_qk), jnp.dtype(dtype))
    v = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.dtype(dtype))
    tracer.clear()
    jax.eval_shape(lambda q_, k_, v_: flash_attention(q_, k_, v_), qk, qk, v)
    event, = [s.attrs for s in tracer.spans()
              if s.name == "kernel/flash_attention_tiles"]
    assert (event["block_q"], event["block_k"]) == (blocks, blocks)
    assert event["operand_dtype"] == dtype and "fallback" not in event


BLOCK_SHAPES = [(64, 64), (128, 32), (32, 128)]


def _kept_tiles(seq, block_q, block_k, causal, window):
    from deepspeed_tpu.ops.pallas.flash_attention import _tile_in_band

    return np.array([[bool(_tile_in_band(iq * block_q, ik * block_k, block_q,
                                         block_k, causal, window))
                      for ik in range(seq // block_k)]
                     for iq in range(seq // block_q)])


@pytest.mark.parametrize("window", [0, 40, 64, 200])
@pytest.mark.parametrize("block_q,block_k", BLOCK_SHAPES)
def test_a_step_outside_the_band_names_the_bands_edge(block_q, block_k,
                                                      window):
    """The walk of a row of tiles (the dK/dV kernel: of a column) is as long
    as the widest row, ends at the row's last live tile and names every tile
    ``_tile_in_band`` keeps exactly once, in order; the dead steps of a
    shorter row come FIRST, stand for a block that is dead (outside the band
    or outside ``[0, n)``) and name the row's first live block, the one the
    first live step names, so nothing is fetched for them; no step names a
    block outside ``[0, n)``."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _band_tiles, _kv_block_in_band, _q_block_in_band)

    seq = 512
    nq, nk = seq // block_q, seq // block_k
    kept = _kept_tiles(seq, block_q, block_k, True, window)
    assert kept.any(axis=1).all() and kept.any(axis=0).all()
    tiles = _band_tiles(nq, nk, block_q, block_k, True, window)
    assert tiles["kv_steps"] == kept.sum(axis=1).max()
    assert tiles["q_steps"] == kept.sum(axis=0).max()
    assert tiles["live_tiles"] == kept.sum()
    walks = [(kept, nk, tiles["kv_steps"], _kv_block_in_band),
             (kept.T, nq, tiles["q_steps"], _q_block_in_band)]
    for rows, n, steps, block_in_band in walks:
        for i, row in enumerate(rows):
            inside = np.flatnonzero(row)
            walked = [tuple(int(x) for x in block_in_band(
                i, step, steps, block_q, block_k, n, True, window))
                for step in range(steps)]
            true, held = (list(x) for x in zip(*walked))
            assert true == list(range(inside[-1] - steps + 1, inside[-1] + 1))
            assert held == [max(j, inside[0]) for j in true]
            live = [j for j in true if 0 <= j < n and row[j]]
            assert live == list(inside) and 0 <= min(held) <= max(held) < n
            assert row[true[-1]]  # the walk's last step is a live tile


def test_without_a_band_every_step_names_its_own_block():
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _band_tiles, _kv_block_in_band, _q_block_in_band)

    assert _band_tiles(4, 8, 64, 32, False, 0) == {
        "kv_steps": 8, "q_steps": 4, "live_tiles": 32, "edge_tiles": 0,
        "bodies": ((False, False),)}
    for i in range(4):
        for j in range(4):
            assert _kv_block_in_band(i, j, 8, 64, 32, 8, False, 0) == (j, j)
            assert _q_block_in_band(j, i, 4, 64, 32, 4, False, 0) == (i, i)


@pytest.mark.parametrize("window", [0, 40, 64, 200])
@pytest.mark.parametrize("block_q,block_k", BLOCK_SHAPES)
def test_a_bound_cuts_a_tile_where_its_compare_masks_something(block_q,
                                                               block_k,
                                                               window):
    """``_tile_cuts`` against the mask itself: a bound cuts a tile exactly
    where its compare is not all true, so a tile is interior (no mask built)
    exactly where the whole band mask is all true, the mask built from the
    bounds that cut equals the whole mask, ``edge_tiles`` counts the live
    tiles that build one and ``bodies`` holds the pairs that occur."""
    from deepspeed_tpu.ops.pallas.flash_attention import (
        _band_mask, _band_tiles, _tile_cuts)

    seq = 512
    nq, nk = seq // block_q, seq // block_k
    kept = _kept_tiles(seq, block_q, block_k, True, window)
    edge, bodies = 0, set()
    for iq in range(nq):
        for ik in range(nk):
            at = ((block_q, block_k), iq * block_q, ik * block_k)
            by_causal, by_window = (bool(x) for x in _tile_cuts(
                iq * block_q, ik * block_k, block_q, block_k, True, window))
            causal_mask = np.asarray(_band_mask(*at, True, False, window))
            assert by_causal == (not causal_mask.all())
            whole = causal_mask
            if window:
                window_mask = np.asarray(_band_mask(*at, False, True, window))
                assert by_window == (not window_mask.all())
                whole = causal_mask & window_mask
            assert kept[iq, ik] == bool(whole.any())
            built = _band_mask(*at, by_causal, by_window, window)
            assert (built is None) == bool(whole.all())
            if built is not None:
                assert np.array_equal(np.asarray(built), whole)
            if kept[iq, ik]:
                edge += int(by_causal or by_window)
                bodies.add((by_causal, by_window))
    tiles = _band_tiles(nq, nk, block_q, block_k, True, window)
    assert tiles["edge_tiles"] == edge
    assert tiles["bodies"] == tuple(sorted(bodies))
    # a window past one block of queries and one of keys has interior tiles,
    # and no tile that both bounds cut
    wide = window == 0 or window >= block_q + block_k - 1
    assert ((False, False) in bodies) == wide
    assert not (wide and (True, True) in bodies)


def test_trinitys_walk_at_blocks_of_1024():
    """Trinity-Mini's two kinds of layer at 16,384 tokens: a window of 2,048
    walks 3 steps a row with 45 live tiles a head, 30 of them cut by the
    band's edge (16 on the diagonal, 14 at the window's far edge, none by
    both: three bodies); the full layer walks all 16 with 136 live, the 16
    on the diagonal cut (two bodies)."""
    from deepspeed_tpu.ops.pallas.flash_attention import _band_tiles

    interior, diagonal, far = (False, False), (True, False), (False, True)
    assert _band_tiles(16, 16, 1024, 1024, True, 2048) == {
        "kv_steps": 3, "q_steps": 3, "live_tiles": 45, "edge_tiles": 30,
        "bodies": (interior, far, diagonal)}
    assert _band_tiles(16, 16, 1024, 1024, True, 0) == {
        "kv_steps": 16, "q_steps": 16, "live_tiles": 136, "edge_tiles": 16,
        "bodies": (interior, diagonal)}
    assert _band_tiles(32, 32, 512, 512, True, 2048) == {
        "kv_steps": 5, "q_steps": 5, "live_tiles": 150, "edge_tiles": 60,
        "bodies": (interior, far, diagonal)}
    # ``train-1chip``: a window of 4,096 over 2,048 tokens cuts nothing
    assert _band_tiles(2, 2, 1024, 1024, True, 4096) == {
        "kv_steps": 2, "q_steps": 2, "live_tiles": 3, "edge_tiles": 2,
        "bodies": (interior, diagonal)}


def _outputs(q, k, v, w, seg, window, block):
    """(out, lse, dq, dk, dv) of the three kernels at blocks of ``block``.
    The scale is a power of two: the CPU's compiler contracts ``q kᵀ * scale
    - m`` into one fused multiply-add only where no select stands between
    the two, and with an exact product both forms give the same bits."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    t = lambda x: x.transpose(0, 2, 1, 3)
    q_seg = k_seg = None
    if seg is not None:
        q_seg = jax.lax.broadcast_in_dim(seg, (1, SEQ, 128), (0, 1))
        k_seg = jax.lax.broadcast_in_dim(seg, (1, 8, SEQ), (0, 2))
    args = (q_seg, k_seg, None, 0.125, True, block, block, window)
    out, lse = fa._flash_fwd(t(q), t(k), t(v), *args)
    grads = jax.grad(lambda *a: (fa._flash_attention_bhsd(*a, *args).astype(
        jnp.float32) * t(w).astype(jnp.float32)).sum(), argnums=(0, 1, 2))(
            t(q), t(k), t(v))
    return (out, lse) + grads


@pytest.mark.parametrize("with_seg", [False, True], ids=["", "segments"])
@pytest.mark.parametrize("window", [0, 40, 64, 200])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_interior_tiles_give_the_bits_of_masked_ones(devices, monkeypatch,
                                                     width, window, with_seg):
    """Forward, ``lse`` and the three gradients with every tile masked by
    the bounds that cut it, and by no other, equal bit for bit a run in which
    every live tile builds the whole band mask (``_tile_cuts`` patched to say
    both bounds cut everywhere: one body): blocks of 16 at 256 tokens, so
    that every window has interior tiles and tiles one bound cuts."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    q, k, v, w = _inputs(width, jnp.bfloat16)
    seg = (jnp.arange(SEQ)[None] // 100).astype(jnp.int32) if with_seg \
        else None
    tiles = fa._band_tiles(SEQ // 16, SEQ // 16, 16, 16, True, window)
    assert 0 < tiles["edge_tiles"] < tiles["live_tiles"]
    assert len(tiles["bodies"]) == (3 if window else 2)
    got = _outputs(q, k, v, w, seg, window, 16)
    monkeypatch.setattr(fa, "_tile_cuts", lambda *a: (True, window > 0))
    assert fa._band_tiles(SEQ // 16, SEQ // 16, 16, 16, True, window)[
        "bodies"] == ((True, window > 0),)
    want = _outputs(q, k, v, w, seg, window, 16)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), got, want):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32)), name


@pytest.mark.parametrize("window", [0, 72])
def test_a_block_mask_gates_the_walks_tiles(devices, window):
    """A block mask under the short walk: the table is read at the block a
    step names, forward and gradients against the XLA reference."""
    from deepspeed_tpu.ops.pallas.flash_attention import _reference_attention

    q, k, v = _rand_qkv(jax.random.PRNGKey(8), 1, 256, 4, 32, KV=2)
    mask = np.tril(np.random.default_rng(0).integers(0, 2, (8, 8))) | np.eye(
        8, dtype=np.int64)
    kernel = lambda *a: flash_attention(*a, causal=True, window=window,
                                        block_q=32, block_k=32,
                                        block_mask=mask)
    ref = lambda *a: _reference_attention(
        *a, causal=True, window=window, segment_ids=None, block_mask=mask,
        block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda *a: (kernel(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    want = jax.grad(lambda *a: (ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4,
                                   rtol=5e-4, err_msg=f"d{name}")


def test_the_evoformers_biased_forward_walks_every_block(devices):
    """The evoformer's call (two additive biases, not causal, several kv
    blocks a row) against its own XLA formulation: without a band the walk
    is every kv block and the biases' index maps follow it."""
    from deepspeed_tpu.ops.evoformer import evoformer_attention

    B, N, L, H, D = 1, 2, 1024, 2, 16  # blocks of 512: two a row
    keys = jax.random.split(jax.random.PRNGKey(9), 5)
    q, k, v = (jax.random.normal(key, (B, N, L, H, D)) for key in keys[:3])
    b1 = jax.random.normal(keys[3], (B, N, 1, 1, L))
    b2 = jax.random.normal(keys[4], (B, 1, H, L, L))
    out = evoformer_attention(q, k, v, [b1, b2])
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", q, k, precision="highest") \
        * D ** -0.5 + b1 + b2
    ref = jnp.einsum("bnhqk,bnkhd->bnqhd", jax.nn.softmax(s, axis=-1), v,
                     precision="highest")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
