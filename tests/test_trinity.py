"""Trinity-Mini's block on the training path, at the ``tiny-trinity`` preset on
the CPU: window and full flash attention in one stack, the gate on the
attention's output, q and k normed a head, position by layer kind, norms on
both sides of each branch, a leading dense layer, a chip's share of
sigmoid-routed experts, and the router bias that a RULE moves, each against
``benchmark/reference/gated_swa_moe_trainer.py`` (float32, written from the
published description, no code shared with the program); and the engine's
rule-moved leaves (``ModelSpec.rule_moved`` / ``apply_rules``)."""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.reference import gated_swa_moe_trainer as ref
from deepspeed_tpu.models import mixed_ffn
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import flash_attention as fa
from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

#: the tiny preset under the published keys (what the driver's ``model_of``
#: hands the reference)
MODEL = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-5, rope_theta=10000.0, sliding_window=8,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention", "sliding_attention"],
    num_dense_layers=1, num_experts=16, num_experts_per_tok=4,
    route_norm=True, route_scale=2.826, mup_enabled=True,
    load_balance_coeff=0.001, num_hidden_layers=5, vocab_size=256,
    experts_held=4, first_expert=4)
OPTIMIZER = dict(lr=1e-3)


def _config(**kw):
    return tfm.get_config("tiny-trinity", dtype="float32",
                          param_dtype="float32", **kw)


def _params(cfg, seed=0):
    """Seeded weights with every norm's scale off 1."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.uniform(k, a.shape, a.dtype, 0.5, 1.5)
        if p[-1].key == "scale" else a for (p, a), k in zip(leaves, keys)])


IDS = np.random.default_rng(0).integers(0, 256, (8, 32)).astype(np.int32)


@pytest.fixture(scope="module")
def right():
    cfg = _config()
    params = _params(cfg)
    want = ref.loss_and_grads(params, MODEL, IDS)
    # the first sequence alone, forward: what a fault is compared with
    want["one"] = ref.loss_and_grads(params, MODEL, IDS[:1], grads=False)
    want["after"] = ref.first_step(params, want["grads"], want["counts"],
                                   MODEL, **OPTIMIZER)
    return params, want


def _bias(tree):
    return np.asarray(tree["layers"]["S"]["moe"]["router_bias"])


# ---------------------------------------------------------------------------
# the loss function
# ---------------------------------------------------------------------------


def test_loss_gradients_and_counts_match_the_reference(right):
    """The engine's loss function (``tiled_loss_fn``), its gradient leaf by
    leaf, and every expert's assignments, all 16, held here or not."""
    params, want = right
    cfg = _config()
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: tiled_loss_fn(p, {"input_ids": IDS}, cfg, tile_size=16),
        has_aux=True))(params)
    assert abs(float(loss) - want["loss"]) < 2e-6 * want["loss"]
    counts = np.asarray(metrics["moe_expert_counts"])
    assert counts.shape == (4, 16) and counts.dtype == np.int32
    np.testing.assert_array_equal(counts, want["counts"])
    assert (counts.sum(-1) == IDS.size * 4).all()
    held = counts[:, 4:8].sum(-1).mean()
    assert float(metrics["moe_local_rows"]) == held
    mine = dict(jax.tree_util.tree_leaves_with_path(grads))
    for path, w in jax.tree_util.tree_leaves_with_path(want["grads"]):
        np.testing.assert_allclose(
            np.asarray(mine[path]), np.asarray(w), rtol=0,
            atol=1e-5 * max(float(jnp.abs(w).max()), 1e-3),
            err_msg=jax.tree_util.keystr(path))
    # the bias moves the choice and takes no gradient
    assert not _bias(grads).any()


@pytest.mark.parametrize("layout", ["cut_period", "no_dense"])
def test_other_stacks_scan_to_the_same_loss(layout):
    """A routed run that ends inside a period (the published model's does:
    30 layers of a period of four) and a model without a dense layer."""
    kinds = {"cut_period": (("sliding", "sliding", "full") * 2,
                            ("dense",) + ("sparse",) * 5),
             "no_dense": (("sliding", "full") * 2, ("sparse",) * 4)}[layout]
    cfg = _config(num_layers=len(kinds[0]), layer_types=kinds[0],
                  mlp_layer_types=kinds[1])
    params = _params(cfg, seed=3)
    model = dict(MODEL, num_hidden_layers=cfg.num_layers,
                 layer_types=[k + "_attention" for k in kinds[0]],
                 num_dense_layers=kinds[1].count("dense"))
    want = ref.loss_and_grads(params, model, IDS[:2], grads=False)
    loss, metrics = jax.jit(lambda p: tiled_loss_fn(
        p, {"input_ids": IDS[:2]}, cfg, tile_size=16))(params)
    assert abs(float(loss) - want["loss"]) < 2e-6 * want["loss"]
    np.testing.assert_array_equal(np.asarray(metrics["moe_expert_counts"]),
                                  want["counts"])


def test_one_stack_of_dense_layers_computes_the_same():
    """The gate, the norm a head, position by kind and the post-branch norms
    are ``transformer``'s own layer body's too: a model of dense layers alone
    in ONE stack (no ``mlp_layer_types``) gives the hidden states of the same
    weights stacked by kind."""
    kinds = ("sliding", "sliding", "full", "sliding")
    one = _config(num_layers=4, layer_types=kinds, mlp_layer_types=(),
                  num_experts=0, moe_router="softmax", moe_shared_size=0,
                  moe_experts_held=0, moe_first_expert=0,
                  moe_bias_update_rate=0.0)
    by_kind = _config(num_layers=4, layer_types=kinds,
                      mlp_layer_types=("dense",) * 4)
    p = _params(one, seed=5)
    lay = p["layers"]
    assert {"ln1_post", "ln2_post"} <= set(lay) and "wg" in lay["attn"]
    stacked = dict(p, layers={
        "A": {k: v for k, v in lay.items() if k != "mlp"},
        "D": {"mlp": lay["mlp"]}})
    a = jax.jit(lambda q: tfm.forward_hidden(q, IDS[:2], one))(p)
    b = jax.jit(lambda q: tfm.forward_hidden(q, IDS[:2], by_kind))(stacked)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    assert one.num_params() == sum(x.size for x in jax.tree.leaves(p))


def _worst_difference(a, b):
    """The largest relative difference between two references' forward
    numbers: the loss and the router's scores."""
    worst = abs(a["loss"] - b["loss"]) / abs(b["loss"])
    for (_, pa, _), (_, pb, _) in zip(a["router"], b["router"]):
        worst = max(worst, float(np.abs(pa - pb).max()))
    return worst


#: faults of the step that follows the gradients: seen in the bias alone
RULE_FAULTS = ("bias_differentiated", "bias_left", "rule_uncentred")


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_named_fault_is_another_result(right, fault):
    """A fault that changed nothing would size no limit.  A fault of the
    forward moves the loss or the router's scores; one of the rule moves the
    bias by a good part of the rule's own step."""
    params, want = right
    if fault not in RULE_FAULTS:
        wrong = ref.loss_and_grads(params, MODEL, IDS[:1], {fault},
                                   grads=False)
        assert _worst_difference(wrong, want["one"]) > 2e-4
        return
    grads = want["grads"] if fault != "bias_differentiated" else \
        ref.loss_and_grads(params, MODEL, IDS, {fault})["grads"]
    wrong = ref.first_step(params, grads, want["counts"], MODEL, {fault},
                           **OPTIMIZER)
    assert np.abs(_bias(wrong) - _bias(want["after"])).max() > 1e-4


def test_an_unknown_fault_is_refused(right):
    with pytest.raises(ValueError, match="unknown faults"):
        ref.loss_and_grads(right[0], MODEL, IDS, {"no_such_fault"})


# ---------------------------------------------------------------------------
# the shares of one layer
# ---------------------------------------------------------------------------


def _whole_layer(m, w, weight):
    """The UNCUT routed layer by the reference's pieces: all 16 experts."""
    total = 0.0
    for b in range(m.shape[0]):
        _, chosen, weights = ref.router(m[b], w["router"], w["router_bias"],
                                        model=MODEL, faults=ref.NONE)
        y = ref.swiglu(m[b], w["sh_w_gate"], w["sh_w_in"], w["sh_w_out"],
                       ref.NONE)
        for e in range(16):
            gate = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
            y = y + gate[:, None] * ref.swiglu(
                m[b], w["w_gate"][e], w["w_in"][e], w["w_out"][e], ref.NONE)
        total = total + jnp.sum(y * weight[b])
    return total


@pytest.fixture(scope="module")
def shares():
    """The four shares' (4 experts each) weighted outputs and gradients,
    summed with the shared expert counted once, beside the uncut reference
    layer's."""
    cfg16 = _config(moe_experts_held=16, moe_first_expert=0)
    w = jax.tree.map(lambda a: a[1], tfm.init_params(
        jax.random.PRNGKey(3), cfg16)["layers"]["S"]["moe"])
    m = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64))
    weight = jax.random.normal(jax.random.PRNGKey(5), (2, 32, 64))

    def share(m, w, s):
        cfg = _config(moe_experts_held=4, moe_first_expert=4 * s)
        p = {k: v for k, v in w.items()
             if s == 0 or not k.startswith("sh_")}  # shared: counted once
        p = dict(p, **{k: w[k][4 * s:4 * s + 4]
                       for k in ("w_gate", "w_in", "w_out")})
        return jnp.sum(dropless.serving_moe_block(m, p, cfg)[0] * weight)

    def all_shares(m, w):
        return sum(share(m, w, s) for s in range(4))

    mine = jax.jit(jax.value_and_grad(all_shares, argnums=(0, 1)))(m, w)
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(jax.value_and_grad(
            lambda m, w: _whole_layer(m, w, weight), argnums=(0, 1)))(m, w)
    return mine, theirs


@pytest.mark.parametrize("what", ["outputs", "gradients"])
def test_the_shares_add_up_to_the_uncut_layer(shares, what):
    (mine, (gm_, gw)), (theirs, (rm, rw)) = shares
    if what == "outputs":
        assert abs(float(mine) - float(theirs)) < 1e-4 * abs(float(theirs))
        return
    np.testing.assert_allclose(np.asarray(gm_), np.asarray(rm), atol=5e-5)
    for key in rw:
        np.testing.assert_allclose(np.asarray(gw[key]), np.asarray(rw[key]),
                                   atol=5e-5, err_msg=key)


# ---------------------------------------------------------------------------
# the engine: a leaf that a rule moves
# ---------------------------------------------------------------------------


def _engine(params, cfg, **ds):
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import ModelSpec

    spec = ModelSpec(
        loss_fn=lambda p, b, r: tiled_loss_fn(p, b, cfg, tile_size=16),
        params=params, param_axes=tfm.param_axes(cfg),
        **mixed_ffn.spec_rules(params, cfg))
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": dict(OPTIMIZER)},
        "zero_optimization": {"stage": 0}, "steps_per_print": 1_000_000,
        **ds})
    return engine


def _moments(engine):
    """The leaves of the optimizer's state that are shaped like a router's
    bias (4 layers x 16 experts)."""
    return [a for a in jax.tree.leaves(engine.state.opt_state)
            if getattr(a, "shape", None) == (4, 16)]


@pytest.fixture(scope="module")
def stepped(right, devices):
    params, _ = right
    engine = _engine(params, _config())
    out = dict(engine.train_batch({"input_ids": IDS}))
    return engine, out


@pytest.mark.parametrize("what", ["loss", "grad_norm", "counts", "bias",
                                  "parameters", "no_moment"])
def test_one_engine_step_against_the_reference(right, stepped, what):
    """``deepspeed_tpu.initialize`` → ``train_batch``: the timed program's
    loss, norm, counts, the bias after the rule (exactly: the counts are the
    reference's own here) and every other leaf after AdamW's step."""
    params, want = right
    engine, out = stepped
    after = jax.device_get(engine.state.params)
    if what == "loss":
        assert abs(out["loss"] - want["loss"]) < 2e-6 * want["loss"]
    elif what == "grad_norm":  # the bias is not in it: its gradient is none
        norm = np.sqrt(sum(float(jnp.vdot(g, g))
                           for g in jax.tree.leaves(want["grads"])))
        assert abs(out["grad_norm"] - norm) < 1e-5 * norm
    elif what == "counts":
        np.testing.assert_array_equal(out["moe_expert_counts"],
                                      want["counts"])
    elif what == "bias":
        np.testing.assert_allclose(_bias(after), _bias(want["after"]),
                                   rtol=0, atol=1e-9)
        moved = np.abs(_bias(after) - _bias(params))
        assert 5e-4 < moved.max() < 2.1e-3
        np.testing.assert_allclose(  # centred: the biases' sum stays
            _bias(after).sum(-1), _bias(params).sum(-1), atol=1e-6)
    elif what == "parameters":
        for (path, a), w in zip(jax.tree_util.tree_leaves_with_path(after),
                                jax.tree.leaves(want["after"])):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(w), rtol=0, atol=2e-5,
                err_msg=jax.tree_util.keystr(path))
    else:
        assert not _moments(engine)
        n = len(jax.tree.leaves(params))
        shaped = [a for a in jax.tree.leaves(engine.state.opt_state)
                  if getattr(a, "ndim", 0) > 0]
        assert len(shaped) == 2 * (n - 1)  # mu and nu, but not the bias's


def test_the_rule_moved_leaf_is_outside_the_clip(right, devices):
    """With clipping far under the gradient's norm every trained leaf moves
    less; the bias moves by the rule's step all the same, and the norm that
    is reported is the same."""
    params, want = right
    engine = _engine(params, _config(), gradient_clipping=1e-3)
    out = dict(engine.train_batch({"input_ids": IDS}))
    after = jax.device_get(engine.state.params)
    np.testing.assert_allclose(_bias(after), _bias(want["after"]), rtol=0,
                               atol=1e-9)
    norm = np.sqrt(sum(float(jnp.vdot(g, g))
                       for g in jax.tree.leaves(want["grads"])))
    assert abs(out["grad_norm"] - norm) < 1e-5 * norm


def test_accumulated_steps_sum_their_counts_first(right, devices):
    """Two micro-batches a step: the rule sees both's counts together (their
    mean, which the sign does not tell from their sum), once."""
    params, _ = right
    engine = _engine(params, _config(), gradient_accumulation_steps=2)
    ids = np.concatenate([IDS, IDS[::-1, ::-1]])
    out = dict(engine.train_batch({"input_ids": ids}))
    counts = sum(ref.loss_and_grads(params, MODEL, part, grads=False)["counts"]
                 for part in (ids[:8], ids[8:]))
    np.testing.assert_array_equal(out["moe_expert_counts"] * 2, counts)
    np.testing.assert_allclose(
        _bias(jax.device_get(engine.state.params)),
        ref.bias_after_rule(_bias(params), counts, 0.001), rtol=0, atol=1e-9)


def test_the_bias_survives_a_checkpoint(right, stepped, tmp_path, devices):
    params, _ = right
    engine, _ = stepped
    engine.save_checkpoint(str(tmp_path))
    other = _engine(params, _config())
    other.load_checkpoint(str(tmp_path))
    np.testing.assert_array_equal(_bias(jax.device_get(other.state.params)),
                                  _bias(jax.device_get(engine.state.params)))
    assert not _moments(other)
    assert int(other.state.step) == int(engine.state.step)


@pytest.mark.parametrize("option", [
    {"zero_optimization": {"stage": 3}},
    {"zero_optimization": {"stage": 1, "offload_optimizer": {
        "device": "cpu"}}}])
def test_what_rule_moved_leaves_cannot_be_combined_with(right, option,
                                                        devices):
    from deepspeed_tpu.runtime.config import ConfigError

    with pytest.raises(ConfigError, match="rule-moved"):
        _engine(right[0], _config(), **option)


def dense_step_text(deepspeed_tpu, tfm, ModelSpec) -> str:
    """The jaxpr of the dense train step of ``train-1chip``'s tiny twin on
    the eight virtual devices, as text, with what differs from process to
    process taken out (addresses, the order a frozenset prints in)."""
    import re

    cfg = tfm.get_config("tiny", attn_impl="flash")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    spec = ModelSpec(loss_fn=lambda p, b, r: tfm.loss_fn(p, b, cfg),
                     params=params, param_axes=tfm.param_axes(cfg))
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0}, "bf16": {"enabled": True},
        "steps_per_print": 1_000_000})
    batch = engine.place_batch({"input_ids": np.zeros((8, 128), np.int32)})
    text = str(jax.make_jaxpr(engine._train_step)(engine.state,
                                                  batch.placed))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    return re.sub(r"frozenset\(\{([^}]*)\}\)", lambda m: "frozenset({%s})"
                  % ", ".join(sorted(x.strip() for x in
                                     m.group(1).split(","))), text)


#: sha256 of ``dense_step_text`` on this tree.  Until PR 61 it was the text of
#: commit a5eecec (PR 54: this function run over that commit's
#: ``deepspeed_tpu``), 266ab6d0...; PR 61 changed the three flash kernels'
#: grids and bodies on purpose (the walk over the band's tiles, a body for each
#: bound that cuts a tile) and nothing else of the step
DENSE_STEP_SHA256 = (
    "eedd028e3fe12969946502898a04245396f177d1fe4ee35e6075eb71692d4373")


def test_a_model_without_such_a_leaf_traces_what_it_traced(devices):
    """The dense train step is, equation for equation, the one this
    repository compiled before an engine knew of rule-moved leaves.  A PR
    that changes the dense train step on purpose pins the new text."""
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import ModelSpec

    text = dense_step_text(deepspeed_tpu, tfm, ModelSpec)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_STEP_SHA256


def test_a_served_engine_refuses_the_model():
    from deepspeed_tpu.inference.v2 import programs

    with pytest.raises(NotImplementedError, match="gate"):
        programs.kind_of(tfm.get_config("tiny-trinity"))


def test_published_sizes():
    cfg = tfm.get_config("trinity-mini")
    assert abs(cfg.num_params() / 1e9 - 26.12) < 0.01
    assert cfg.layer_kinds.count("full") == 8 and cfg.layer_kinds[3] == "full"
    assert cfg.head_dim == 128 and cfg.kv_heads == 4
    assert cfg.expert_width == 1024 and cfg.moe_shared_size == 1024
    with pytest.raises(ValueError, match="mlp_layer_types names"):
        tfm.get_config("tiny-trinity", num_layers=4)
    with pytest.raises(ValueError, match="one or the other"):
        tfm.get_config("tiny-trinity", qk_norm=True)


def test_param_axes_cover_the_model():
    cfg = tfm.get_config("tiny-trinity")
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    axes = tfm.param_axes(cfg)
    flat = dict(jax.tree_util.tree_leaves_with_path(
        axes, is_leaf=lambda a: isinstance(a, tuple)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        assert len(flat[path]) == leaf.ndim, jax.tree_util.keystr(path)
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params)) \
        == cfg.num_params()


# ---------------------------------------------------------------------------
# the banded flash kernel
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def banded():
    """8 query heads over 1 K/V head, 256 positions, a window of 40 in
    blocks of 64: a band that cuts, and tiles it skips."""
    key = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(key[0], (1, 256, 8, 32))
    k = jax.random.normal(key[1], (1, 256, 1, 32))
    v = jax.random.normal(key[2], (1, 256, 1, 32))
    do = jax.random.normal(key[3], (1, 256, 8, 32))

    def einsum(q, k, v):
        s = jnp.einsum("bthd,bshd->bhts", q, jnp.repeat(k, 8, 2)) / 32 ** 0.5
        t, u = jnp.arange(256)[:, None], jnp.arange(256)[None, :]
        s = jnp.where((u <= t) & (u > t - 40), s, -jnp.inf)
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(s, -1),
                          jnp.repeat(v, 8, 2))

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=40,
                                  block_q=64, block_k=64)

    out = {}
    for name, fn in (("kernel", kernel), ("einsum", einsum)):
        with jax.default_matmul_precision("highest"):
            o, vjp = jax.vjp(fn, q, k, v)
            out[name] = dict(zip(("fwd", "dq", "dk", "dv"), (o, *vjp(do))))
    out["text"] = str(jax.make_jaxpr(jax.grad(
        lambda *a: kernel(*a).sum(), argnums=(0, 1, 2)))(q, k, v))
    return out


@pytest.mark.parametrize("what", ["fwd", "dq", "dk", "dv"])
def test_banded_flash_kernel_against_the_einsum(banded, what):
    np.testing.assert_allclose(np.asarray(banded["kernel"][what]),
                               np.asarray(banded["einsum"][what]),
                               rtol=0, atol=2e-5)


@pytest.mark.parametrize("window,suffix", [(40, "_band"), (256, ""),
                                           (4096, ""), (0, "")])
def test_a_band_that_cuts_names_its_kernels(window, suffix):
    """``_band`` behind the three kernels' names where 0 < window < keys;
    a window that cuts nothing (``train-1chip``'s 4,096 at 2,048) keeps the
    names it had."""
    q = jnp.zeros((1, 256, 2, 32))
    text = str(jax.make_jaxpr(jax.grad(lambda q: fa.flash_attention(
        q, q, q, causal=True, window=window, block_q=64, block_k=64
    ).sum()))(q))
    for which in ("fwd", "bwd_dkv", "bwd_dq"):
        assert f"flash_attention_{which}{suffix}" in text
        assert (f"flash_attention_{which}_band" in text) == bool(suffix)
