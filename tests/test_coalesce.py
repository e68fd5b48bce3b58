"""Bucketed gradient coalescing (runtime/coalesce.py): plan construction,
flatten/unflatten round trips, and — the load-bearing part — numerics of the
bucketed reduction against the per-leaf baseline across ZeRO stages, gas>1,
mixed dtypes, and odd-size leaves (reference: IPG buckets,
``reduce_independent_p_g_buckets_and_remove_grads``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import deepspeed_tpu
from jax import shard_map
from deepspeed_tpu.runtime.coalesce import (
    DEFAULT_BUCKET_NUMEL, flatten_bucket, flatten_bucket_shard_major,
    plan_buckets, psum_scalars, reduce_bucketed, resolve_bucket_numel,
    shard_dims_for, unflatten_bucket, unflatten_bucket_shard)
from tests.simple_model import copy_task_batch, tiny_lm_spec


# ---------------------------------------------------------------------------
# plan construction (host-side, no devices needed)
# ---------------------------------------------------------------------------


def _tree(sizes_dtypes):
    rng = np.random.default_rng(0)
    return {f"p{i}": jnp.asarray(rng.normal(size=shape), dtype)
            for i, (shape, dtype) in enumerate(sizes_dtypes)}


def test_plan_groups_by_dtype_and_caps():
    tree = _tree([((4, 4), jnp.float32), ((8,), jnp.bfloat16),
                  ((10,), jnp.float32), ((3,), jnp.bfloat16)])
    plan = plan_buckets(tree, bucket_numel=1000)
    assert plan.num_leaves == 4
    # one f32 bucket (16+10), one bf16 bucket (8+3)
    assert sorted(np.dtype(b.dtype).name for b in plan.buckets) == [
        "bfloat16", "float32"]
    assert sorted(b.numel for b in plan.buckets) == [11, 26]
    for b in plan.buckets:  # offsets are contiguous, order-preserving
        off = 0
        for s in b.slots:
            assert s.offset == off
            off += s.size
        assert off == b.numel


def test_plan_flushes_at_cap_and_keeps_oversize_leaf_whole():
    tree = _tree([((6,), jnp.float32), ((6,), jnp.float32),
                  ((100,), jnp.float32), ((6,), jnp.float32)])
    plan = plan_buckets(tree, bucket_numel=16)
    # cap=16: [6,6] flush, [100] rides alone (never split), [6]
    assert sorted(b.numel for b in plan.buckets) == [6, 12, 100]
    assert all(len(b.slots) == 1 for b in plan.buckets if b.numel == 100)


def test_plan_scatter_asserts_divisibility():
    tree = _tree([((7, 4), jnp.float32)])
    with pytest.raises(ValueError, match="not divisible"):
        plan_buckets(tree, 1000, world=2, shard_dims=[0])
    plan_buckets(tree, 1000, world=2, shard_dims=[1])  # dim 1 divides fine


def test_flatten_unflatten_roundtrip():
    tree = _tree([((4, 3), jnp.float32), ((5,), jnp.float32),
                  ((2, 2, 2), jnp.float32)])
    leaves = jax.tree_util.tree_leaves(tree)
    plan = plan_buckets(tree, DEFAULT_BUCKET_NUMEL)
    (bucket,) = plan.buckets
    flat = flatten_bucket(bucket, leaves)
    assert flat.shape == (bucket.numel,)
    for i, v in unflatten_bucket(bucket, flat):
        np.testing.assert_array_equal(np.asarray(v), np.asarray(leaves[i]))


def test_shard_major_roundtrip():
    """flatten_shard_major → split into W chunks → unflatten_bucket_shard
    reassembles every leaf's k-th slice exactly."""
    W = 4
    tree = _tree([((8, 3), jnp.float32), ((4, 6), jnp.float32)])
    leaves = jax.tree_util.tree_leaves(tree)
    plan = plan_buckets(tree, DEFAULT_BUCKET_NUMEL, world=W,
                        shard_dims=[0, 0])
    (bucket,) = plan.buckets
    assert bucket.scatter
    flat = flatten_bucket_shard_major(bucket, leaves, W)
    chunk = bucket.numel // W
    for k in range(W):
        shard = flat[k * chunk:(k + 1) * chunk]
        for i, v in unflatten_bucket_shard(bucket, shard, W):
            full = np.asarray(leaves[i])
            d = full.shape[0] // W
            np.testing.assert_array_equal(
                np.asarray(v), full[k * d:(k + 1) * d])


def test_resolve_bucket_numel_semantics():
    class Z:  # minimal zero-config stand-in
        reduce_bucket_size = "auto"
        allreduce_bucket_size = None

    z = Z()
    assert resolve_bucket_numel(z) == DEFAULT_BUCKET_NUMEL
    z.reduce_bucket_size = 1234
    assert resolve_bucket_numel(z) == 1234
    z.allreduce_bucket_size = 99  # stage-0/1 spelling wins when set
    assert resolve_bucket_numel(z) == 99
    z.allreduce_bucket_size = "auto"  # auto defers to reduce_bucket_size
    assert resolve_bucket_numel(z) == 1234
    z.reduce_bucket_size = 0  # 0 disables coalescing
    assert resolve_bucket_numel(z) == 0


def test_shard_dims_for_strict_matching():
    class Sh:
        def __init__(self, spec):
            self.spec = spec

    tree = {"a": jax.ShapeDtypeStruct((8, 4), jnp.float32),
            "b": jax.ShapeDtypeStruct((4, 8), jnp.float32),
            "c": jax.ShapeDtypeStruct((6, 4), jnp.float32),
            "d": jax.ShapeDtypeStruct((8,), jnp.float32)}
    shardings = {"a": Sh(P(("dp", "fsdp"))),      # dim 0 over dp world → 0
                 "b": Sh(P(None, ("dp", "fsdp"))),  # dim 1 → 1
                 "c": Sh(P(("dp", "fsdp"))),      # 6 % 8 != 0 → None
                 "d": Sh(P("tp"))}                # not the dp world → None
    dims = shard_dims_for(tree, shardings, ("dp", "fsdp"),
                          {"dp": 8, "fsdp": 1})
    assert dims == [0, 1, None, None]
    # world of 1 → nothing scatters
    assert shard_dims_for(tree, shardings, ("dp", "fsdp"),
                          {"dp": 1, "fsdp": 1}) == [None] * 4


# ---------------------------------------------------------------------------
# reduction numerics on the 8-device mesh
# ---------------------------------------------------------------------------


def _dp_mesh(devices):
    return Mesh(np.array(devices).reshape(8, 1), ("dp", "fsdp"))


def _rand_tree(seed=0):
    """Mixed shapes including odd sizes that don't divide 8 or align blocks."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
        "odd": jnp.asarray(rng.normal(size=(13,)), jnp.float32),
        "scalar": jnp.asarray(rng.normal(), jnp.float32),
        "nested": {"b": jnp.asarray(rng.normal(size=(7, 3)), jnp.float32)},
    }


def test_bucketed_psum_bit_identical_fp32(devices):
    """ONE fused psum over the concatenated bucket must be bit-identical to
    per-leaf psums (psum(concat) == concat(psums) — same ring, same adds)."""
    mesh = _dp_mesh(devices)
    trees = [_rand_tree(seed) for seed in range(8)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    plan = plan_buckets(trees[0], DEFAULT_BUCKET_NUMEL)
    per_leaf_plan = plan_buckets(trees[0], 1)  # cap 1 → one leaf per bucket
    assert len(per_leaf_plan.buckets) == len(jax.tree.leaves(trees[0]))

    def run(p):
        def local(t):
            mine = jax.tree.map(lambda x: x[0], t)
            return reduce_bucketed(
                p, mine, lambda b, f: jax.lax.psum(f, ("dp", "fsdp")))

        specs = jax.tree.map(lambda _: P(("dp", "fsdp")), stacked)
        out_specs = jax.tree.map(lambda _: P(), trees[0])
        return shard_map(local, mesh=mesh, in_specs=(specs,),
                         out_specs=out_specs, check_vma=False)(stacked)

    fused = jax.device_get(run(plan))
    per_leaf = jax.device_get(run(per_leaf_plan))
    jax.tree.map(np.testing.assert_array_equal, fused, per_leaf)
    # and both equal the host-side sum exactly-ish (fp32 reduction order on
    # host differs, so tolerance here — the bit-identity claim is above)
    host = jax.tree.map(lambda *xs: np.sum(np.stack(xs), 0),
                        *[jax.device_get(t) for t in trees])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5),
                 fused, host)


def test_bucketed_mixed_dtype_trees(devices):
    """bf16 + f32 leaves bucket separately and reduce to the same values as
    per-leaf psums (bit-identical per dtype)."""
    mesh = _dp_mesh(devices)
    rng = np.random.default_rng(3)
    tree = {"f32": jnp.asarray(rng.normal(size=(11,)), jnp.float32),
            "bf16": jnp.asarray(rng.normal(size=(9,)), jnp.bfloat16),
            "bf16b": jnp.asarray(rng.normal(size=(5, 2)), jnp.bfloat16)}
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(8)]), tree)
    plan = plan_buckets(tree, DEFAULT_BUCKET_NUMEL)
    assert len(plan.buckets) == 2  # one per dtype
    per_leaf = plan_buckets(tree, 1)

    def run(p):
        def local(t):
            mine = jax.tree.map(lambda x: x[0], t)
            return reduce_bucketed(
                p, mine, lambda b, f: jax.lax.psum(f, ("dp", "fsdp")))

        return shard_map(
            local, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(("dp", "fsdp")), stacked),),
            out_specs=jax.tree.map(lambda _: P(), tree),
            check_vma=False)(stacked)

    a, b = jax.device_get(run(plan)), jax.device_get(run(per_leaf))
    jax.tree.map(np.testing.assert_array_equal, a, b)
    assert run(plan)["bf16"].dtype == jnp.bfloat16


def test_psum_scalars_matches_per_leaf(devices):
    mesh = _dp_mesh(devices)
    vals = {"a": jnp.arange(8, dtype=jnp.float32),
            "n": {"b": jnp.arange(8, dtype=jnp.float32) * 2}}

    def local(v):
        mine = jax.tree.map(lambda x: x[0], v)
        stacked, extra = psum_scalars(mine, ("dp", "fsdp"), scale=0.5,
                                      extra=mine["a"] * 4)
        return stacked, extra

    (out, extra) = shard_map(
        local, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(("dp", "fsdp")), vals),),
        out_specs=(jax.tree.map(lambda _: P(), vals), P()),
        check_vma=False)(vals)
    assert float(out["a"]) == np.arange(8).sum() * 0.5
    assert float(out["n"]["b"]) == np.arange(8).sum() * 2 * 0.5
    assert float(extra) == np.arange(8).sum() * 4  # extra: unscaled


# ---------------------------------------------------------------------------
# engine-level: bucketed vs per-leaf training across stages / gas
# ---------------------------------------------------------------------------

BASE = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "steps_per_print": 10_000,
}


def _losses(cfg, steps=6):
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(),
                                               config=cfg)
    batch = copy_task_batch(np.random.default_rng(0),
                            engine.train_batch_size, 32)
    return engine, [float(engine.train_batch(batch)["loss"])
                    for _ in range(steps)]


@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_engine_bucketed_matches_per_leaf(devices, stage):
    """Training with coalescing on vs off (reduce_bucket_size: 0) must agree
    to bf16-accumulation tolerance at every stage, gas=1 and gas>1."""
    on = dict(BASE, zero_optimization={"stage": stage})
    off = dict(BASE, zero_optimization={"stage": stage,
                                        "reduce_bucket_size": 0})
    eng_on, l_on = _losses(on)
    eng_off, l_off = _losses(off)
    # stage ≤ 2 gets a plan; stage 3 stays on the emergent GSPMD schedule
    assert (eng_on._bucket_plan is not None) == (stage <= 2)
    assert eng_off._bucket_plan is None
    np.testing.assert_allclose(l_on, l_off, rtol=2e-2)


def test_engine_bucketed_gas_matches(devices):
    on = dict(BASE, zero_optimization={"stage": 1},
              gradient_accumulation_steps=4)
    off = dict(BASE, zero_optimization={"stage": 1, "reduce_bucket_size": 0},
               gradient_accumulation_steps=4)
    _, l_on = _losses(on)
    _, l_off = _losses(off)
    np.testing.assert_allclose(l_on, l_off, rtol=2e-2)


def test_engine_small_buckets_match_single_bucket(devices):
    """Shrinking the cap changes the schedule (more buckets), not the math:
    both are explicit shard_map psums → bit-identical losses."""
    one = dict(BASE, zero_optimization={"stage": 2})
    many = dict(BASE, zero_optimization={"stage": 2,
                                         "reduce_bucket_size": 4096})
    eng_one, l_one = _losses(one)
    eng_many, l_many = _losses(many)
    assert len(eng_many._bucket_plan.buckets) > \
        len(eng_one._bucket_plan.buckets)
    np.testing.assert_array_equal(l_one, l_many)


def test_engine_grad_norm_matches_per_leaf(devices):
    """The coalesced in-shard_map grad-norm must agree with the legacy
    optax.global_norm computed outside."""
    on = dict(BASE, zero_optimization={"stage": 1})
    off = dict(BASE, zero_optimization={"stage": 1, "reduce_bucket_size": 0})

    def norms(cfg):
        engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(),
                                                   config=cfg)
        batch = copy_task_batch(np.random.default_rng(0),
                                engine.train_batch_size, 32)
        return [float(engine.train_batch(batch)["grad_norm"])
                for _ in range(3)]

    np.testing.assert_allclose(norms(on), norms(off), rtol=2e-2)


def test_engine_qgz_bucketed_close_to_exact(devices):
    """qgZ compresses whole buckets; int8 block quantization keeps training
    in the same regime as the exact reduction (tolerance, not identity)."""
    exact = dict(BASE, zero_optimization={"stage": 1})
    qgz = dict(BASE, zero_optimization={"stage": 1,
                                        "zero_quantized_gradients": True})
    _, l_exact = _losses(exact)
    _, l_qgz = _losses(qgz)
    np.testing.assert_allclose(l_qgz, l_exact, rtol=0.15)
    assert l_qgz[-1] < l_qgz[0] * 0.7
