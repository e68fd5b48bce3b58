"""The routed experts' rows are gathered into the grouped layout.

(i) ``moe_rows``' kernel in interpret mode equals the XLA form exactly, in
both 16-bit types and at both ends of the tile's range, over the routings
that bend a layout: even, every assignment on one expert, experts with no
row, a share with no local assignment, a share with all local, no tile used,
and keeps a token that is not finite in its own rows; (ii)
``layout_sources`` (no scatter) is the inverse of ``tile_aligned_layout``'s
``positions`` on the same cases; (iii) ``routed_ffn`` and its share give bit
for bit what the parent's expressions gave (kept here as the reference), at
a decode step's size (the scatter's form) and at a mixed step's (the
gather's); (iv) the gradient through the ``custom_vjp`` is the XLA form's in
both.  What Mosaic makes of the kernel is ``tests/test_tpu_compile.py``'s.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import moe_rows
from deepspeed_tpu.ops.pallas.grouped_matmul import (layout_sources,
                                                     tile_aligned_layout)

E, K, H = 8, 2, 256

# name -> (experts of the N x K assignments, experts held, first held)
ROUTINGS = {
    "even": (lambda n: np.arange(n * K) % E, E, 0),
    "one_expert": (lambda n: np.full(n * K, 3), E, 0),
    "experts_without_rows": (
        lambda n: np.random.default_rng(1).integers(0, E // 2, n * K) * 2,
        E, 0),
    "share_none_local": (lambda n: 4 + np.arange(n * K) % 4, 2, 0),
    "share_all_local": (lambda n: 2 + np.arange(n * K) % 2, 2, 2),
    "share_some_local": (
        lambda n: np.random.default_rng(2).integers(0, E, n * K), 3, 1),
}


def _layout(routing, n, tile_m):
    """→ (group of each assignment, rows of each group that has rows here,
    positions, tile_group, pad_sizes of those groups, used tiles, local)."""
    experts, held, first = ROUTINGS[routing]
    flat = jnp.asarray(experts(n), jnp.int32)
    T = n * K
    if held == E:
        group, local = flat, jnp.ones((T,), bool)
        pos, tg, sizes, _ = tile_aligned_layout(group, E, T, tile_m)
    else:
        local = (flat >= first) & (flat < first + held)
        group = jnp.where(local, flat - first, held)
        pos, tg, sizes, _ = tile_aligned_layout(group, held + 1, T, tile_m)
        tg, sizes = jnp.minimum(tg, held - 1), sizes[:held]
    counts = jnp.bincount(group, length=held + 1)[:held]
    used = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
    return group, counts, pos, tg, sizes, used, local


@pytest.mark.parametrize("routing", list(ROUTINGS))
@pytest.mark.parametrize("tile_m", [16, 128])
def test_sources_invert_the_positions(routing, tile_m):
    n = 64
    group, counts, pos, tg, sizes, _, local = _layout(routing, n, tile_m)
    src = np.asarray(layout_sources(group, counts, tg, sizes, tile_m))
    want = np.full(src.shape, -1)
    want[np.asarray(pos)[np.asarray(local)]] = np.arange(n * K)[
        np.asarray(local)]
    np.testing.assert_array_equal(src, want)


@pytest.mark.parametrize("routing", list(ROUTINGS) + ["no_tile_used"])
@pytest.mark.parametrize("tile_m", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16],
                         ids=["bf16", "f16"])
def test_kernel_equals_the_xla_form(routing, tile_m, dtype):
    n = 64
    group, counts, _, tg, sizes, used, _ = _layout(
        "even" if routing == "no_tile_used" else routing, n, tile_m)
    if routing == "no_tile_used":
        used = jnp.int32(0)
    src = layout_sources(group, counts, tg, sizes, tile_m)
    src = jnp.where(src >= 0, src // K, -1)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, H), dtype)
    rows = moe_rows.block_rows(src.shape[0], tile_m, n, H, dtype)
    assert rows and rows % tile_m == 0
    got = moe_rows._rows_pallas(x, src, used, tile_m=tile_m, rows=rows,
                                interpret=True)
    want = moe_rows._rows_xla(x, src)
    live = int(used) * tile_m  # later tiles are never written
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got[:live], np.float32),
                                  np.asarray(want[:live], np.float32))
    if routing not in ("no_tile_used", "share_none_local"):
        assert live and float(jnp.abs(want[:live]).max()) > 1.0


def test_a_token_that_is_not_finite_stays_in_its_own_rows():
    """The kernel sums a one-hot over ALL tokens, and 0 x Inf is NaN: it reads
    the tokens through a copy with no Inf or NaN in it, so the other tokens'
    rows are what they were (the XLA form gives the bad token's rows as they
    are; the kernel gives them with 0 where the value was not finite)."""
    n, tile_m = 64, 16
    group, counts, _, tg, sizes, used, _ = _layout("even", n, tile_m)
    src = layout_sources(group, counts, tg, sizes, tile_m)
    src = jnp.where(src >= 0, src // K, -1)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, H), jnp.bfloat16)
    bad = x.at[5, 3].set(jnp.inf).at[5, 9].set(jnp.nan).at[5, 11].set(
        -jnp.inf)
    rows = moe_rows.block_rows(src.shape[0], tile_m, n, H, x.dtype)
    got = moe_rows._rows_pallas(bad, src, used, tile_m=tile_m, rows=rows,
                                interpret=True)
    want = moe_rows._rows_xla(bad.at[5, jnp.array([3, 9, 11])].set(0), src)
    live = int(used) * tile_m
    assert bool(jnp.isfinite(got[:live].astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(got[:live], np.float32),
                                  np.asarray(want[:live], np.float32))


@pytest.mark.parametrize("rows,tile_m,tokens,h,dtype,block", [
    (12288, 128, 512, 2304, "bfloat16", 256),
    (1280, 16, 32, 2048, "bfloat16", 256),
    (2432, 16, 64, 2688, "bfloat16", 128),
    (6272, 128, 512, 6144, "bfloat16", 128),
    (912, 16, 48, 2304, "bfloat16", 48),
    (1024, 512, 512, 256, "float16", 512),  # a tile is never cut
    (12288, 128, 512, 2304, "float32", 0),  # the MXU would round the row
    (24576, 128, 2048, 2304, "bfloat16", 0),  # a trained step's tokens
    (128, 16, 24, 256, "bfloat16", 0), (128, 16, 32, 200, "bfloat16", 0),
    (104, 8, 32, 256, "bfloat16", 0), (128, 16, 32, 256, "int16", 0),
])
def test_block_rows(rows, tile_m, tokens, h, dtype, block):
    assert moe_rows.block_rows(rows, tile_m, tokens, h, dtype) == block


def _parent_routed_ffn(x2, p, cfg, r):
    """``routed_ffn`` as the parent wrote it: a scatter in, a gather out."""
    N, Hd = x2.shape
    k = cfg.moe_top_k
    T = N * k
    held, first = cfg.experts_held, cfg.moe_first_expert
    flat = r.experts.reshape(T)
    if held == cfg.num_experts:
        tile_m = dropless.moe_tile_m(T, cfg.num_experts)
        pos, tg, sizes, M_pad = tile_aligned_layout(flat, held, T, tile_m)
        xs = jnp.zeros((M_pad, Hd), x2.dtype).at[pos].set(
            jnp.repeat(x2, k, axis=0))
    else:
        tile_m = dropless.share_tile_m(T, cfg.num_experts, held)
        local = (flat >= first) & (flat < first + held)
        group = jnp.where(local, flat - first, held)
        pos, tg, sizes, M_pad = tile_aligned_layout(group, held + 1, T,
                                                    tile_m)
        tg, sizes = jnp.minimum(tg, held - 1), sizes[:held]
        at = jnp.where(local, pos, M_pad)
        xs = jnp.zeros((M_pad, Hd), x2.dtype).at[at].set(
            jnp.repeat(x2, k, axis=0), mode="drop")

    def gmm(a, key):
        return dropless._expert_gemm(a, p[key], tg, sizes, None, tile_m)

    ys = gmm(jax.nn.silu(gmm(xs, "w_gate")) * gmm(xs, "w_in"), "w_out")
    if held == cfg.num_experts:
        picked = ys[pos]
    else:
        picked = jnp.where(local[:, None], ys[jnp.minimum(at, M_pad - 1)], 0)
    return jnp.sum(picked.reshape(N, k, Hd).astype(jnp.float32)
                   * r.weights[..., None], axis=1).astype(x2.dtype)


def _model(held, first, dtype, n=48, f=128):
    cfg = types.SimpleNamespace(
        num_experts=E, moe_top_k=K, experts_held=held,
        moe_first_expert=first, moe_norm_topk=True)
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    p = {"router": jax.random.normal(keys[0], (H, E), jnp.float32),
         "w_gate": jax.random.normal(keys[1], (held, H, f), dtype) * 0.06,
         "w_in": jax.random.normal(keys[2], (held, H, f), dtype) * 0.06,
         "w_out": jax.random.normal(keys[3], (held, f, H), dtype) * 0.06}
    return cfg, p, jax.random.normal(keys[4], (n, H), dtype)


@pytest.mark.parametrize("held,first", [(E, 0), (3, 2)],
                         ids=["all_held", "share"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("n,form", [(48, "scatter"), (512, "xla")],
                         ids=["decode_sized", "mixed_sized"])
def test_routed_ffn_equals_the_parents_expressions(held, first, dtype, n,
                                                   form):
    cfg, p, x2 = _model(held, first, dtype, n=n)
    r = dropless.route(x2, p["router"], cfg)
    tracer.clear()
    got, _ = dropless.routed_ffn(x2, p, cfg, routing=r)
    events = [s.attrs for s in tracer.spans() if s.name == "kernel/moe_rows"]
    want = _parent_routed_ffn(x2, p, cfg, r)
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 0.1
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # announced once a call, with the static sizes the choice is made from
    assert events and all(
        e["h"] == H and e["tokens"] == n and e["live"] == n * K
        and e.get(form) == 1 for e in events), events


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("n", [32, 512], ids=["scatter", "gather"])
def test_gradient_is_the_xla_forms(dtype, tol, n):
    """By gathers through ``inverse``, where XLA transposes the gather into
    a scatter-add: the same sums (float32 here, then rounded once), in the
    form a decode step takes and in a mixed step's."""
    tile_m = 16
    group, counts, pos, tg, sizes, used, local = _layout(
        "share_some_local", n, tile_m)
    src = layout_sources(group, counts, tg, sizes, tile_m)
    tok = jnp.where(src >= 0, src // K, -1)
    inverse = jnp.where(local, pos, -1).reshape(n, K)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, H), dtype)
    ct = jax.random.normal(jax.random.PRNGKey(1), (src.shape[0], H), dtype)

    def through(fn):
        return jax.grad(lambda x: jnp.sum(
            (fn(x) * ct).astype(jnp.float32)))(x)

    got = through(lambda x: moe_rows.gather_rows(
        x, inverse, used, rows=src.shape[0], tile_m=tile_m,
        sources=lambda: src))
    want = through(lambda x: moe_rows._rows_xla(x, tok))
    assert got.dtype == want.dtype
    assert float(jnp.abs(want.astype(jnp.float32)).max()) > 1.0
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol * 4,
                               rtol=tol)


def test_grad_reaches_through_routed_ffn():
    """``routed_ffn`` stays differentiable in its tokens and its experts, and
    the tokens' gradient is the parent's (float32: the same sums)."""
    cfg, p, x2 = _model(E, 0, jnp.float32, n=512)
    r = dropless.route(x2, p["router"], cfg)

    def loss(fn, x2, p):
        return jnp.sum(fn(x2, p) ** 2)

    got = jax.grad(lambda x2, p: loss(
        lambda x2, p: dropless.routed_ffn(x2, p, cfg, routing=r)[0], x2, p),
        argnums=(0, 1))(x2, p)
    want = jax.grad(lambda x2, p: loss(
        lambda x2, p: _parent_routed_ffn(x2, p, cfg, r), x2, p),
        argnums=(0, 1))(x2, p)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)
