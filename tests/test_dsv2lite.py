"""DeepSeek-V2-Lite's block on the training path, at the ``tiny-dsv2lite``
preset on the CPU: latent attention without query compression through the
flash kernel at a query-key width that is not the value width, a chip's share
of softmax-routed experts with its backward, the shared experts, the leading
dense layer and the sequence-wise balance loss, each against
``benchmark/reference/latent_moe_trainer.py`` (float32, written from the
published description, no code shared with the program)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import latent_moe_trainer as ref
from deepspeed_tpu.models import latent_sparse as ls
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.ops.pallas import grouped_matmul as gm
from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

ROPE = dict(factor=4.0, original_max_position_embeddings=16, beta_fast=4.0,
            beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
#: the tiny preset under the published keys (what ``model_of`` hands over)
MODEL = dict(
    hidden_size=64, intermediate_size=160, num_attention_heads=4,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
    rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=ROPE,
    n_routed_experts=8, n_shared_experts=2, moe_intermediate_size=48,
    experts_held=2, first_expert=2, num_experts_per_tok=3,
    norm_topk_prob=False, routed_scaling_factor=1.0, first_k_dense_replace=1,
    num_hidden_layers=4, vocab_size=256, aux_loss_alpha=0.01)


def _config(**kw):
    return tfm.get_config("tiny-dsv2lite", dtype="float32",
                          param_dtype="float32", **kw)


def _params(cfg, seed=0):
    """Seeded weights with every norm's scale off 1."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.uniform(k, a.shape, a.dtype, 0.5, 1.5)
        if p[-1].key == "scale" else a for (p, a), k in zip(leaves, keys)])


IDS = np.random.default_rng(0).integers(0, 256, (2, 64)).astype(np.int32)


@pytest.fixture(scope="module")
def right():
    cfg = _config()
    params = _params(cfg)
    want = ref.loss_and_grads(params, MODEL, IDS)
    # the first sequence alone, forward: what a fault is compared with
    want["one"] = ref.loss_and_grads(params, MODEL, IDS[:1], grads=False)
    return params, want


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
def test_loss_and_every_gradient_leaf_match_the_reference(right, attn_impl,
                                                          monkeypatch):
    """The engine's loss function (``tiled_loss_fn``: cross-entropy + the
    balance loss x its coefficient) and its gradient, leaf by leaf; the dense
    FFN over a quarter of the sequence at a time."""
    params, want = right
    cfg = _config(attn_impl=attn_impl)
    row = IDS.shape[0] * cfg.intermediate_size * 4
    monkeypatch.setattr(ls, "_FFN_SLICE_BYTES", row * IDS.shape[1] // 4)
    assert ls._ffn_seq_tile(*IDS.shape, cfg) == IDS.shape[1] // 4
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: tiled_loss_fn(p, {"input_ids": IDS}, cfg, tile_size=32),
        has_aux=True))(params)
    assert abs(float(loss) - want["loss"]) < 2e-6 * want["loss"]
    assert abs(float(metrics["ce_loss"]) - want["ce"]) < 2e-6 * want["ce"]
    assert abs(float(metrics["moe_aux_loss"]) - want["aux"]) < 1e-5
    mine = dict(jax.tree_util.tree_leaves_with_path(grads))
    theirs = dict(jax.tree_util.tree_leaves_with_path(want["grads"]))
    assert mine.keys() == theirs.keys()
    for path, g in mine.items():
        scale = float(jnp.abs(theirs[path]).max())
        assert float(jnp.abs(g - theirs[path]).max()) < 2e-5 * scale + 1e-8, \
            jax.tree_util.keystr(path)
    # the counters: 2 of 8 experts held, 2 x 64 tokens x top 3
    assert 0 < float(metrics["moe_local_rows"]) < 2 * 64 * 3
    assert float(metrics["moe_experts_hit"]) == 2.0


@pytest.fixture(scope="module")
def unequal():
    """q, k 32 wide and v 16: the kernel in interpret mode and the einsum
    oracle, values and gradients under one cotangent."""
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(2, 64, 4, 32)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(2, 64, 4, 16)), jnp.float32)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, sm_scale=0.2,
                                  block_q=16, block_k=32)

    def oracle(q, k, v):
        return fa._reference_attention(q, k, v, True, 0, None, None, 16, 32,
                                       sm_scale=0.2)

    out = {}
    for name, fn in (("kernel", kernel), ("oracle", oracle)):
        o, vjp = jax.vjp(fn, q, k, v)
        out[name] = dict(zip(("fwd", "dq", "dk", "dv"), (o,) + vjp(ct)))
    return out


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
def test_flash_kernel_at_unequal_widths(unequal, what):
    """Forward, and both backward kernels (dQ; dK and dV), at a value width
    of its own: ``v`` is never padded to the query-key width."""
    a, b = unequal["kernel"][what], unequal["oracle"][what]
    assert a.shape == b.shape
    assert a.shape[-1] == (16 if what in ("fwd", "dv") else 32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_yarn_scale_and_tables():
    """``scale = 192^-0.5 x m(40, 0.707)^2`` at the published sizes, and the
    preset's blended frequencies are the released code's."""
    full = tfm.get_config("deepseek-v2-lite")
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.2608) < 1e-4
    assert abs(ls.softmax_scale(full) - 192 ** -0.5 * m * m) < 1e-12
    assert abs(ls.softmax_scale(full) - 0.11472) < 1e-5
    published = dict(qk_nope_head_dim=128, qk_rope_head_dim=64,
                     rope_theta=10000.0, rope_scaling=dict(
                         ROPE, factor=40.0,
                         original_max_position_embeddings=4096,
                         beta_fast=32.0, beta_slow=1.0))
    assert abs(ref.softmax_scale(published) - ls.softmax_scale(full)) < 1e-12
    cos, sin = ls.rope_tables(full, 512)
    rcos, rsin = ref.rope_angles(published, 512)
    np.testing.assert_allclose(np.asarray(cos), np.asarray(rcos), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), np.asarray(rsin), atol=2e-4)
    # GLM-5.2 (rope_type default) keeps the plain table and the plain scale
    glm = tfm.get_config("tiny-glm52")
    assert ls.softmax_scale(glm) == (24 + 8) ** -0.5


@pytest.mark.parametrize("seq_aux", [True, False])
def test_balance_loss(seq_aux):
    cfg = _config(moe_seq_aux=seq_aux)
    rng = np.random.default_rng(2)
    B, S, E, k = 2, 16, cfg.num_experts, cfg.moe_top_k
    probs = rng.dirichlet(np.ones(E), size=B * S).astype(np.float32)
    experts = np.argsort(-probs, axis=-1)[:, :k].astype(np.int32)
    r = dropless.Routing(jnp.asarray(np.take_along_axis(probs, experts, -1)),
                         jnp.asarray(experts), jnp.asarray(probs), None)
    got = float(dropless.balance_loss(r, cfg, B))
    if seq_aux:
        want = 0.0
        for b in range(B):
            rows = slice(b * S, (b + 1) * S)
            f = np.bincount(experts[rows].ravel(), minlength=E) * E / (k * S)
            want += float(np.sum(f * probs[rows].mean(0))) / B
    else:
        first = np.bincount(experts[:, 0], minlength=E) / (B * S)
        want = float(E * np.sum(first * probs.mean(0)))
    assert abs(got - want) < 1e-5


def _whole_layer(m, w, alpha, weight):
    """The UNCUT routed layer by the reference's pieces: all 8 experts."""
    model = dict(MODEL, experts_held=8, first_expert=0)
    total, aux = 0.0, 0.0
    for b in range(m.shape[0]):
        probs, chosen, gates = ref.router(m[b], w["router"], model=model,
                                          faults=ref.NONE)
        y = ref.swiglu(m[b], w["sh_w_gate"], w["sh_w_in"], w["sh_w_out"],
                       ref.NONE)
        for e in range(8):
            gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
            y = y + gate[:, None] * ref.swiglu(
                m[b], w["w_gate"][e], w["w_in"][e], w["w_out"][e], ref.NONE)
        f = jnp.zeros((8,)).at[chosen.reshape(-1)].add(1.0) * (
            8 / (3 * m.shape[1]))
        aux = aux + jnp.sum(f * probs.mean(0)) / m.shape[0]
        total = total + jnp.sum(y * weight[b])
    return total + alpha * aux


@pytest.fixture(scope="module")
def shares():
    """The four shares' (2 experts each) weighted outputs and gradients,
    summed with the shared experts and the balance loss counted once, beside
    the uncut reference layer's."""
    cfg8 = _config(moe_experts_held=8, moe_first_expert=0)
    w = jax.tree.map(lambda a: a[1], tfm.init_params(
        jax.random.PRNGKey(3), cfg8)["layers"]["S"]["moe"])
    m = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    weight = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))
    alpha = 0.3

    def share(m, w, s):
        cfg = _config(moe_experts_held=2, moe_first_expert=2 * s)
        p = {k: v for k, v in w.items()
             if s == 0 or not k.startswith("sh_")}  # shared: counted once
        p = dict(p, **{k: w[k][2 * s:2 * s + 2]
                       for k in ("w_gate", "w_in", "w_out")})
        y, aux, _, _ = dropless.dropless_moe_block_with_losses(m, p, cfg)
        return jnp.sum(y * weight) + (alpha * aux if s == 0 else 0.0)

    def all_shares(m, w):
        return sum(share(m, w, s) for s in range(4))

    mine = jax.jit(jax.value_and_grad(all_shares, argnums=(0, 1)))(m, w)
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(jax.value_and_grad(
            lambda m, w: _whole_layer(m, w, alpha, weight),
            argnums=(0, 1)))(m, w)
    return mine, theirs


@pytest.mark.parametrize("what", ["outputs", "gradients"])
def test_the_shares_add_up_to_the_uncut_layer(shares, what):
    (mine, (gm_, gw)), (theirs, (rm, rw)) = shares
    if what == "outputs":
        assert abs(float(mine) - float(theirs)) < 1e-4 * abs(float(theirs))
        return
    np.testing.assert_allclose(np.asarray(gm_), np.asarray(rm), atol=2e-5)
    for key in rw:
        np.testing.assert_allclose(np.asarray(gw[key]), np.asarray(rw[key]),
                                   atol=2e-5, err_msg=key)


def _worst_difference(a, b):
    """The largest relative difference between two references' forward
    numbers: loss, balance loss, the router's probabilities."""
    worst = max(abs(a["loss"] - b["loss"]) / abs(b["loss"]),
                abs(a["aux"] - b["aux"]) / abs(b["aux"]))
    for (_, pa, _), (_, pb, _) in zip(a["router"], b["router"]):
        worst = max(worst, float(np.abs(pa - pb).max()))
    return worst


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_is_another_result(right, fault):
    """A fault that changed nothing would size no limit."""
    params, want = right
    wrong = ref.loss_and_grads(params, MODEL, IDS[:1], {fault}, grads=False)
    assert _worst_difference(wrong, want["one"]) > 2e-4


def test_an_unknown_fault_is_refused(right):
    with pytest.raises(ValueError, match="unknown faults"):
        ref.loss_and_grads(right[0], MODEL, IDS, {"no_such_fault"})


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_references_adamw_step_is_the_engines(dtype):
    """``ref.adamw_step`` (plain float32 arithmetic, rounded to the
    parameters' dtype) against what the engine steps with: ``optax.adamw``
    without decay through ``optax.apply_updates``, from moments at zero."""
    import optax

    key = jax.random.PRNGKey(3)
    params = {"a": jax.random.normal(key, (64, 48)).astype(dtype) * 0.02,
              "n": {"scale": jnp.ones((48,), dtype)}}
    grads = jax.tree.map(
        lambda p: jax.random.normal(key, p.shape, jnp.float32) * 1e-4, params)
    grads["a"] = grads["a"].at[:4].set(0.0)  # rows no token reached
    opt = optax.adamw(2e-4, weight_decay=0.0, b1=0.9, b2=0.999, eps=1e-8)
    updates, _ = opt.update(grads, opt.init(params), params)
    want = optax.apply_updates(params, updates)
    got = ref.adamw_step(params, grads, lr=2e-4)
    for w, g, p in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                       jax.tree.leaves(params)):
        assert g.dtype == p.dtype
        np.testing.assert_allclose(  # bfloat16: the same values exactly
            np.asarray(g, np.float32), np.asarray(w, np.float32), rtol=0,
            atol=1e-7 if dtype == "float32" else 0)  # of a step of 2e-4
    moved = np.asarray(got["a"], np.float32) != np.asarray(params["a"],
                                                           np.float32)
    assert not moved[:4].any() and moved[4:].mean() > 0.9


def test_rounds_compute_what_one_layout_computes(monkeypatch):
    """A layout past ``_ROUNDS_FROM_BYTES`` is walked in rounds: the same
    outputs and gradients, under skewed routing (several rounds) too."""
    cfg = _config(moe_experts_held=4, moe_first_expert=2)
    w = jax.tree.map(lambda a: a[1], tfm.init_params(
        jax.random.PRNGKey(0), cfg)["layers"]["S"]["moe"])
    skewed = dict(w, router=w["router"].at[:, 2:5].add(5.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 64))

    def f(x, p):
        y, aux, _, stats = dropless.dropless_moe_block_with_losses(x, p, cfg)
        return jnp.sum(y * jnp.cos(jnp.arange(64.0))) + 0.1 * aux, stats

    def both(limit):  # a new jit a limit: the limit is read while tracing
        monkeypatch.setattr(dropless, "_ROUNDS_FROM_BYTES", limit)
        g = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
        return [g(x, p) for p in (w, skewed)]

    for ((a, sa), ga), ((b, sb), gb) in zip(both(1 << 30), both(0)):
        assert abs(float(a) - float(b)) < 1e-4 * abs(float(a))
        assert (np.asarray(sa) == np.asarray(sb)).all()
        for u, v in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_allclose(np.asarray(u), np.asarray(v),
                                       atol=5e-5)


@pytest.mark.parametrize("k,n", [(128, 256), (1408, 256), (256, 1408)])
def test_grouped_matmul_backward_kernels(k, n):
    """``grouped_matmul_dlhs`` and ``grouped_matmul_drhs`` (interpret mode)
    against ``ragged_dot``'s gradients on a share's layout: tiles past
    ``used_tiles`` skipped, an expert without a row zero."""
    rng = np.random.default_rng(0)
    E, held, T, tile_m = 8, 3, 96, 16
    ef = jnp.asarray(rng.integers(0, E, T), jnp.int32)
    ef = jnp.where(ef == 1, 0, ef)  # expert 1 gets no row
    local = ef < held
    group = jnp.where(local, ef, held)
    pos, tg, sizes, M_pad = gm.tile_aligned_layout(group, held + 1, T, tile_m)
    tg = jnp.minimum(tg, held - 1)
    counts = jnp.bincount(group, length=held + 1)[:held]
    used = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
    rows = int(used) * tile_m
    lhs = jnp.zeros((M_pad, k), jnp.float32).at[
        jnp.where(local, pos, M_pad)].set(
        jnp.asarray(rng.normal(size=(T, k)), jnp.float32), mode="drop")
    rhs = jnp.asarray(rng.normal(size=(held, k, n)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(M_pad, n)), jnp.float32) * (
        jnp.arange(M_pad) < rows)[:, None]
    o1, v1 = jax.vjp(lambda a, b: gm._gmm(a, b, tg, sizes[:held],
                                          used.reshape(1), tile_m), lhs, rhs)
    o2, v2 = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes[:held]),
                     lhs, rhs)
    (a1, b1), (a2, b2) = v1(ct), v2(ct)
    scale = float(jnp.abs(b2).max())
    np.testing.assert_allclose(np.asarray(o1[:rows]), np.asarray(o2[:rows]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(a1[:rows]), np.asarray(a2[:rows]),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(b1), np.asarray(b2),
                               atol=1e-5 * scale)
    assert float(jnp.abs(b1[1]).max()) == 0.0


def test_param_axes_cover_a_latent_model():
    for name in ("tiny-dsv2lite", "tiny-glm52"):
        cfg = tfm.get_config(name)
        params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                                jax.random.PRNGKey(0))
        axes = tfm.param_axes(cfg)
        flat = dict(jax.tree_util.tree_leaves_with_path(params))
        named = dict(jax.tree_util.tree_leaves_with_path(
            axes, is_leaf=lambda a: isinstance(a, tuple)))
        assert flat.keys() == named.keys()
        for path, a in flat.items():
            assert len(named[path]) == a.ndim, jax.tree_util.keystr(path)
        assert cfg.num_params() == sum(
            int(np.prod(a.shape)) for a in flat.values())
    held = named[next(p for p in named if "'S'" in str(p) and "'w_gate'"
                      in str(p))]
    assert held[1] == "expert"


def test_published_sizes():
    """The preset is the published model (15.7 B) and the cell's cut is what
    ISSUE 42's arithmetic says (635.4 M: 13.76 M of attention a layer)."""
    full = tfm.get_config("deepseek-v2-lite")
    assert round(full.num_params() / 1e9, 2) == 15.71
    cut = tfm.get_config(
        "deepseek-v2-lite", num_layers=6, vocab_size=12800,
        moe_experts_held=8,
        mlp_layer_types=("dense",) + ("sparse",) * 5)
    assert cut.num_params() == 635_466_752
    with pytest.raises(ValueError, match="indexer"):
        tfm.get_config("tiny-dsv2lite", index_topk=4)


def test_a_step_carries_the_counters_and_leaves_a_span(devices):
    """Through ``deepspeed_tpu.initialize`` → ``engine.train_batch``: the
    counters in the step's metrics, one ``train/step`` span with them while
    the tracer is on, none while it is off."""
    import deepspeed_tpu
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.runtime.engine import ModelSpec

    cfg = tfm.get_config("tiny-dsv2lite", param_dtype="bfloat16")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    spec = ModelSpec(
        loss_fn=lambda p, b, r: tiled_loss_fn(p, b, cfg, tile_size=32),
        params=params, param_axes=tfm.param_axes(cfg))
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 1_000_000})
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 256, (engine.train_batch_size, 64)).astype(np.int32)}
    was = tracer.enabled
    try:
        tracer.enabled = True
        tracer.clear()
        first = dict(engine.train_batch(batch))
        spans = [s for s in tracer.spans() if s.name == "train/step"]
        assert len(spans) == 1
        assert spans[0].attrs["moe_local_rows"] == first["moe_local_rows"]
        # every metric of the step, whatever the loss function returned:
        # the engine knows no model's names
        assert spans[0].attrs == first
        tracer.enabled = False
        later = dict(engine.train_batch(batch))
        assert len([s for s in tracer.spans()
                    if s.name == "train/step"]) == 1
    finally:
        tracer.enabled = was
    assert later["loss"] < first["loss"] and np.isfinite(later["grad_norm"])
    assert engine._train_step._cache_size() == 1
