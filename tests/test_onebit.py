"""1-bit Adam wire-compression tests (reference: tests/onebit/ +
runtime/comm/nccl.py compressed_allreduce).

The r3 verdict's point: compression must act on the WIRE (inside the DP
reduction), not after an already-exact psum.  These tests check the
primitive's semantics, engine convergence vs the exact path, and — from the
compiled HLO — that the gradient collective volume actually shrinks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.ops.onebit import (chunk_len, onebit_all_reduce,
                                      pack_signs, payload_bytes,
                                      residual_shapes, unpack_signs)
from tests.simple_model import copy_task_batch, tiny_lm_spec


def test_pack_unpack_roundtrip():
    x = np.random.default_rng(0).standard_normal(256).astype(np.float32)
    signs = np.asarray(unpack_signs(pack_signs(jnp.asarray(x)), 256))
    np.testing.assert_array_equal(signs > 0, x >= 0)
    assert set(np.unique(signs)) <= {-1.0, 1.0}


def test_chunk_len_divisibility():
    for n in (100, 4096, 50_000):
        for w in (2, 4, 8):
            c = chunk_len(n, w, block=64)
            assert c % 64 == 0 and c * w >= n


def test_onebit_all_reduce_error_feedback(devices):
    """All workers agree on the result, and the accumulated estimate tracks
    the accumulated true mean (error feedback bounds the drift)."""
    W, n, block = 8, 5000, 64
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:W]).reshape(W), ("dp",))
    wlen, slen = residual_shapes(n, W, block)

    def step(g, wres, sres):
        out, nw, ns = onebit_all_reduce(g[0], wres[0], sres[0], ("dp",), W,
                                        block)
        return out[None], nw[None], ns[None]

    f = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(P("dp"), P("dp"), P("dp")),
                          out_specs=(P("dp"), P("dp"), P("dp")),
                          check_vma=False))
    rng = np.random.default_rng(1)
    wres = jnp.zeros((W, wlen), jnp.float32)
    sres = jnp.zeros((W, slen), jnp.float32)
    acc_est = np.zeros(n)
    acc_true = np.zeros(n)
    for _ in range(30):
        grads = rng.standard_normal((W, n)).astype(np.float32) + 0.1
        out, wres, sres = f(jnp.asarray(grads), wres, sres)
        out = np.asarray(out)
        np.testing.assert_allclose(out[0], out[-1], atol=0,
                                   err_msg="workers disagree")
        acc_est += out[0]
        acc_true += grads.mean(0)
    rel = np.abs(acc_est - acc_true).mean() / np.abs(acc_true).mean()
    assert rel < 0.15, f"error feedback failed to bound drift: {rel}"


def _mk_engine(opt_type, extra=None, freeze_step=4):
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": opt_type,
                      "params": {"lr": 1e-2, "freeze_step": freeze_step}},
        "zero_optimization": {"stage": 1},
        "steps_per_print": 10000,
    }
    cfg.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(),
                                               config=cfg)
    return engine


def test_onebit_converges_vs_exact(devices):
    """Wire-compressed training must keep converging after freeze_step and
    land in the same loss regime as exact adamw on the same task."""
    exact = _mk_engine("adamw")
    onebit = _mk_engine("onebit_adam",
                        extra={"gradient_compression": {"enabled": True}})
    rng = np.random.default_rng(0)
    batch = copy_task_batch(rng, exact.train_batch_size, 32)
    l_exact = [float(exact.train_batch(batch)["loss"]) for _ in range(25)]
    l_1bit = [float(onebit.train_batch(batch)["loss"]) for _ in range(25)]
    assert l_1bit[-1] < l_1bit[4] * 0.5, \
        f"no convergence after compression engaged: {l_1bit}"
    assert l_1bit[-1] < max(4 * l_exact[-1], 0.5), (l_1bit[-1], l_exact[-1])
    # residuals actually carry feedback (the wire path really ran); with
    # coalescing they are per-BUCKET arrays, so check the whole tree
    res_sum = sum(float(np.abs(np.asarray(jax.device_get(x))).sum())
                  for x in jax.tree.leaves(onebit._onebit_wres))
    assert res_sum > 0


def test_onebit_wire_volume_shrinks(devices):
    """From the COMPILED HLO: the 1-bit step's collective volume must be a
    fraction of the exact step's — the wire, not a numerics simulation."""
    from deepspeed_tpu.analysis import collective_bytes

    # stage 0: params replicated → NO ZeRO-1 param all-gather in either
    # program, so every collective byte is gradient-reduction traffic
    exact = _mk_engine("adamw", extra={"zero_optimization": {"stage": 0}})
    onebit = _mk_engine("onebit_adam",
                        extra={"gradient_compression": {"enabled": True},
                               "zero_optimization": {"stage": 0}})
    batch = copy_task_batch(np.random.default_rng(0),
                            exact.train_batch_size, 32)
    placed = exact._place_batch(batch)
    hlo_exact = exact._train_step.lower(
        exact.state, placed).compile().as_text()
    residuals = (onebit._onebit_wres, onebit._onebit_sres)
    hlo_1bit = onebit._train_step_onebit.lower(
        onebit.state, onebit._place_batch(batch), residuals,
        None).compile().as_text()
    b_exact = collective_bytes(hlo_exact)
    b_1bit = collective_bytes(hlo_1bit)
    # gradient traffic = everything except tiny metric reductions; compare
    # totals (same model, same batch — the only difference is the reduction)
    total_exact = sum(b_exact.values())
    total_1bit = sum(b_1bit.values())
    assert total_1bit < total_exact / 4, (
        f"wire volume not reduced: exact={b_exact} onebit={b_1bit}")


def test_payload_bytes_math():
    n, W = 1_000_000, 8
    exact_ring = 2 * 4 * n  # fp32 ring all-reduce moves ~2x the buffer
    assert payload_bytes(n, W) < exact_ring / 16


def test_onebit_rejects_bad_compositions(devices):
    from deepspeed_tpu.runtime.config_utils import ConfigError

    with pytest.raises(ConfigError, match="stage <= 2"):
        _mk_engine("onebit_adam", extra={
            "gradient_compression": {"enabled": True},
            "zero_optimization": {"stage": 3}})
    with pytest.raises(ConfigError, match="tp"):
        _mk_engine("onebit_adam", extra={
            "gradient_compression": {"enabled": True},
            "mesh": {"tensor_parallel_size": 2, "data_parallel_size": 4}})


def test_frozen_variance_adam():
    """After freeze_step the second moment must stop changing."""
    from deepspeed_tpu.runtime.compressed_optimizer import \
        scale_by_adam_freezable

    opt = scale_by_adam_freezable(freeze_step=3)
    params = {"w": jnp.ones(4)}
    state = opt.init(params)
    rng = np.random.default_rng(0)
    nus = []
    for _ in range(6):
        g = {"w": jnp.asarray(rng.standard_normal(4), jnp.float32)}
        _, state = opt.update(g, state)
        nus.append(np.asarray(state.nu["w"]).copy())
    assert not np.allclose(nus[0], nus[2])  # adapting during warmup
    np.testing.assert_array_equal(nus[3], nus[5])  # frozen after


def test_onebit_residuals_checkpoint_roundtrip(devices, tmp_path):
    """Error-feedback residuals are optimizer-coupled state: they must
    survive save/load (dropping them injects a gradient-bias transient)."""
    engine = _mk_engine("onebit_adam",
                        extra={"gradient_compression": {"enabled": True}})
    batch = copy_task_batch(np.random.default_rng(0),
                            engine.train_batch_size, 32)
    for _ in range(8):  # past freeze_step=4 → residuals nonzero
        engine.train_batch(batch)
    wres_before = jax.device_get(engine._onebit_wres)
    assert sum(float(np.abs(np.asarray(x)).sum())
               for x in jax.tree.leaves(wres_before)) > 0
    d = str(tmp_path / "ck")
    engine.save_checkpoint(d)

    engine2 = _mk_engine("onebit_adam",
                         extra={"gradient_compression": {"enabled": True}})
    engine2.load_checkpoint(d)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(b)),
        engine2._onebit_wres, wres_before)
    m = engine2.train_batch(batch)  # compressed step right after resume
    assert np.isfinite(m["loss"])
