"""GLM-5.2's block (latent attention over a latent paged cache, a learned
indexer whose pick the layers behind it share, a leading dense layer, a share
of the routed experts) at toy widths: the program's logits against
``benchmark/reference/latent_sparse_moe_decoder.py`` (float32, seeded
weights) through chunked prefill and decode with a context past two
``index_topk``; the absorbed form against the expanded one; the share test;
the wrong programs the comparison has to see; what is refused for a model
with a latent pool; the step's counters."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_latent_moe as drv
from benchmark.reference import latent_sparse_moe_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import latent_sparse
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import latent_attention as la

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "benchmark", "tests"))
import glm52_wrong_programs as wrong  # noqa: E402
from served_kinds import assert_step_attrs, refusal_cases  # noqa: E402

# float32 on both sides: what differs is the order of the sums (the absorbed
# form against the expanded, paged chunks against an (S, S) mask).  Logits of
# standard deviation about 1; the right program reads 1e-5
TOL = 2e-4
CHECK = {"logit_prompts": [75, 40, 9], "logit_tokens": 8, "logit_pad": 32,
         "index_tol": 1e-4, "select_band": 0.01, "select_agree_min": 0.99,
         "logit_tol_median": TOL, "logit_tol": TOL, "agree_min": 0.99,
         "router_tol": 1e-4}


def v2_config(**over):
    kw = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=65,
              max_blocks_per_seq=16, dtype="float32")
    kw.update(over)
    return V2Config(**kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = tfm.get_config("tiny-glm52", dtype="float32",
                         param_dtype="float32")
    params = drv.draw_small_tensors(
        tfm.init_params(jax.random.PRNGKey(7), cfg), seed=7)
    return cfg, params, drv.published_model(cfg)


@pytest.fixture(scope="module")
def served(tiny):
    """Three sequences through the engine's own step programs with the tap
    on: prompts of 75 (chunked 32 + 32 + 11: selection inside a chunk, across
    chunks, then in decode; past four ``index_topk`` of 16), 40 and 9 tokens
    (shorter than ``index_topk``: every key is picked), 8 tokens each."""
    cfg, params, _ = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    tapped = drv.tap_logits(engine, cfg, 3, CHECK)
    assert engine.drained()
    return tapped


def test_engine_matches_reference(tiny, served):
    """Chunked prefill, then decode through both pools, against the
    reference's one uncached pass: left to its own picks and choices (float32
    on both sides: they are the program's) and held to the program's."""
    cfg, params, model = tiny
    for force in (False, True):
        errs, _ = drv.row_errors(params, model, served, 32, force=force)
        assert len(errs) == 24 and errs.max() < TOL, (force, errs.max())


def test_checks_pass_on_the_right_program(tiny, served):
    cfg, params, model = tiny
    drv._CHECK.update(cfg=cfg)
    got = drv.check_logits(params, model, served, CHECK, lambda m: None)
    assert got["ok"], got
    assert got["indexer"]["counts"] and got["indexer"]["causal"]
    assert drv.check_router(params, model, cfg, served, CHECK,
                            lambda m: None)["ok"]


def test_a_prompt_still_prefilling_keeps_the_sample_on_the_mixed_program(tiny):
    """``logit_filler``: one more prompt, compared with nothing, prefills
    while the compared sequences decode, as one does all through the cell's
    window: their decode rows ride in mixed steps (40 + 9 tokens, then
    3 + 3 rows beside chunks of the filler's 100), no decode-only step runs,
    so the tapped decode-only program is never built, and the rows compare
    with the reference as the others do.  (The cell's rehearsal has a filler
    too short for its ten tokens: there both programs are tapped.)"""
    cfg, params, model = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    tapped = drv.tap_logits(engine, cfg, 3, dict(
        CHECK, logit_prompts=[40, 9], logit_tokens=3, logit_filler=100))
    assert engine.drained()
    assert drv._NOTES["steps"] == {"mixed": 5, "decode": 0}
    assert [len(t[1]) for t in tapped] == [3, 3]
    errs, _ = drv.row_errors(params, model, tapped, 32)
    assert len(errs) == 6 and errs.max() < TOL, errs.max()


def test_absorbed_matches_expanded(tiny, served):
    """The step programs never expand a key (``W_kvb`` is absorbed into the
    query and the output); ``forward_hidden`` expands every one.  The same
    mathematics: the same logits."""
    cfg, params, _ = tiny
    prompt, tokens, rows, _, _ = served[0]
    seq = jnp.asarray(prompt + tokens)[None]
    want = np.asarray(jax.jit(lambda p, s: tfm.lm_logits(
        p, tfm.forward_hidden(p, s, cfg), cfg))(params, seq))[0]
    assert max(np.abs(row - want[pos]).max() for pos, row in rows) < TOL


def test_a_shared_layer_uses_its_full_layers_selection(tiny, served):
    """Every tapped pick of a layer that picks is what the three layers
    behind it attended over: the reference held to those picks agrees, and
    one whose shared layers score for themselves, or read the selection
    before last, does not."""
    cfg, params, model = tiny
    picked = served[0][4]
    assert picked.shape[0] == latent_sparse.layers_of(cfg, "I") == 3
    n = len(served[0][0])
    assert (picked[:, :n].sum(-1) == np.minimum(np.arange(1, n + 1), 16)).all()
    for fault in ("shared_scores_itself", "selection_before_last"):
        errs, _ = drv.row_errors(params, model, served[:1], 32, (fault,))
        assert np.median(errs) > 100 * TOL, (fault, np.median(errs))


@pytest.mark.parametrize("fault", [
    f for f in reference.FAULTS
    if f not in wrong.INDEXER_FAULTS + ("shared_scores_itself",
                                        "selection_before_last")])
def test_wrong_program_fails(tiny, served, fault):
    cfg, params, model = tiny
    errs, _ = drv.row_errors(params, model, served[:1], 32, (fault,))
    assert np.median(errs) > 100 * TOL, (fault, np.median(errs))


@pytest.mark.parametrize("fault", wrong.INDEXER_FAULTS)
def test_wrong_indexer_fails(tiny, served, fault):
    """The logits are held to the program's picks; ``check_indexer`` is what
    sees a wrong pick: picks made under each of the indexer's faults fail
    it."""
    cfg, params, model = tiny
    got = wrong.readings(drv, reference, params, model, cfg, served[:1],
                         CHECK, (fault,), lambda m: None)
    assert got["indexer"]["ok"] and not got[fault]["ok"], got[fault]


def test_served_check_reads_whole_sequences_and_sees_far_keys_lost(
        tiny, served):
    """``check_served`` reads a served sequence whole at its own length.  A
    program that loses the keys further than 2.25 ``index_topk`` (36 here)
    behind a query is the right one on a context of 17 and another on one of
    83: a check that read only short contexts could not tell it."""
    cfg, params, model = tiny
    seqs = [(p, t) for p, t, *_ in served]  # contexts 83, 48, 17
    right = drv.served_readings(params, model, seqs, 32, 0.5)
    assert [g["context"] for g in right] == [83, 48, 17]
    assert all(g["argmax"] == g["tokens"] == 8 for g in right)  # greedy
    lost = drv.served_readings(params, model, seqs, 32, 0.5,
                               ("far_keys_lost",))
    moved = [float(np.abs(a["margins"] - b["margins"]).max())
             for a, b in zip(right, lost)]
    assert moved[0] > 100 * TOL and moved[1] > 100 * TOL and moved[2] < TOL
    # the check itself: each sequence is held on its own, the warm-up first
    drv._CHECK.update(logit_pad=32, served_min=0.9)
    got = drv.check_served(params, model, seqs, 96, 0.5, lambda m: None)
    assert got["ok"] and got["tokens_checked"] == 24 \
        and got["window_tokens"] == 16 and got["shares"] == [1.0] * 3
    # nothing from the window: nothing of the timed path was compared
    assert not drv.check_served(params, model, seqs[:1], 96, 0.5,
                                lambda m: None)["ok"]


def test_pick_spread_covers_the_lengths():
    """The window's picks: the shortest, the longest and evenly between by
    rank, of those the reference's length holds; by what a request is, not
    by where it stands among the finished."""
    def request(i, n_prompt, n_out):
        return {"stream": i % 3, "index": i // 3, "n_prompt": n_prompt,
                "tokens": [1] * n_out}

    finished = [request(i, n, 10) for i, n in enumerate(
        (900, 100, 500, 300, 700, 2000, 400))]
    check = {"reference_len": 1000, "window_sequences": 3}
    picks = drv.pick_spread(finished, check, seed=1)
    assert [r["n_prompt"] for r in picks] == [100, 500, 900]
    assert drv.pick_spread(finished[::-1], check, seed=2) == picks
    assert [r["n_prompt"] for r in drv.pick_spread(
        finished, dict(check, window_sequences=9), 1)] == [
            100, 300, 400, 500, 700, 900]
    assert drv.pick_spread(finished[5:6], check, 1) == []


@pytest.mark.parametrize("preset", ["tiny-glm52", "tiny-kimi-linear"])
def test_the_shares_add_up_to_the_whole_layer(preset):
    """The guide's share test, a served model with a share of its experts a
    case: the routed parts that all four shares of four experts give, with
    the shared expert counted once, add up to what the layer that holds all
    sixteen gives."""
    cfg = tfm.get_config(preset, dtype="float32", param_dtype="float32")
    whole_cfg = dataclasses.replace(cfg, moe_experts_held=0,
                                    moe_first_expert=0)
    whole = tfm.init_params(jax.random.PRNGKey(5), whole_cfg)
    p = jax.tree.map(lambda a: a[1], whole["layers"]["S"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (40, cfg.hidden_size))
    want, _ = dropless.serving_moe_block(x, p, whole_cfg)
    routed = {k: v for k, v in p.items() if not k.startswith("sh_")}
    total = dropless.serving_moe_block(x, p, whole_cfg)[0] \
        - dropless.serving_moe_block(x, routed, whole_cfg)[0]  # shared: once
    local = 0
    for first in range(0, 16, 4):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=4,
                                        moe_first_expert=first)
        share = dict(routed, **{k: routed[k][first:first + 4]
                                for k in ("w_in", "w_gate", "w_out")})
        y, stats = dropless.serving_moe_block(x, share, share_cfg)
        total = total + y
        local += int(stats[2])
    assert local == 40 * cfg.moe_top_k  # every assignment is local once
    assert float(jnp.abs(total - want).max()) < 1e-4


def test_more_requests_than_rows(tiny):
    """Six requests over four rows give the tokens each gives alone."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in (50, 20, 37, 9, 66, 41)]
    engine = InferenceEngineV2(cfg, params, v2_config())
    uids = [engine.put(p, max_new_tokens=5) for p in prompts]
    together = engine.generate_all(burst=1)
    assert engine.drained()
    for p, u in zip(prompts, uids):
        alone = InferenceEngineV2(cfg, params, v2_config())
        v = alone.put(p, max_new_tokens=5)
        assert alone.generate_all(burst=1)[v] == together[u]


def test_engine_w8a16_matches_reference(tiny):
    """int8 codes read by both sides, bfloat16 activations: the reference
    held to the program's picks and choices (one flip of 16 keys or of 4
    experts is a large share of a token at these widths)."""
    cfg = tfm.get_config("tiny-glm52", dtype="bfloat16",
                         param_dtype="bfloat16")
    params = drv.make_params(cfg, 7, 8, 256)
    engine = InferenceEngineV2(cfg, params, v2_config(dtype="bfloat16"))
    tapped = drv.tap_logits(engine, cfg, 3, CHECK)
    errs, _ = drv.row_errors(params, drv.published_model(cfg), tapped, 32)
    assert np.median(errs) < 0.15 and errs.max() < 0.4, errs


@pytest.mark.parametrize("over,name", refusal_cases(
    programs.LATENT, tfm.get_config("tiny-glm52"), v2_config()))
def test_refused_with_a_latent_pool(tiny, over, name):
    """Every row of the refusal table (``programs.REFUSED``) the kind
    holds."""
    cfg, params, _ = tiny
    with pytest.raises(ValueError, match=f"V2Config.*{name}.*latent"):
        InferenceEngineV2(cfg, params, v2_config(**over))


def test_step_spans_carry_the_counters(tiny):
    cfg, params, _ = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    engine.put(list(range(1, 41)), max_new_tokens=3)
    tracer.clear()
    engine.generate_all(burst=1)
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    assert_step_attrs(steps, "moe", "latent")
    mixed = [a for a in steps if a["kind"] == "mixed"][0]  # tokens 0..31
    seen = sum(range(1, 33))
    assert mixed["dsa_keys_visible"] == seen * 9
    assert mixed["dsa_keys_selected"] == sum(min(p, 16) for p in
                                             range(1, 33)) * 9
    assert mixed["dsa_index_pairs"] == seen * 3
    assert mixed["dsa_index_keys"] == 32 * 3
    assert mixed["latent_keys_prefill"] == 32 * 9  # a row's context, once
    assert mixed["moe_assignments"] == 32 * 4 * 8
    assert 0 < mixed["moe_assignments_local"] < mixed["moe_assignments"]
    assert mixed["latent_blocks_used"] == 6  # 40 + 3 tokens: 6 blocks of 8
    decode = [a for a in steps if a["kind"] == "decode"][0]
    assert decode["dsa_selected_single"] == 16 * 9
    assert decode["latent_keys_single"] == 16 * 9
    assert decode["moe_rows_padded"] == dropless.share_padded_rows(16, 16, 4)


def test_topk_mask_is_top_k():
    """The bisection's mask against ``lax.top_k``: the same keys, ties to
    the lower position, every visible key where there are fewer than k."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(12, 200)).astype(np.float32)
    x[3, 50:120] = 0.0  # a run of ties across the k-th
    x[5, 7:] = -np.inf  # seven visible keys
    x[6] = -np.inf
    got = np.asarray(la.topk_mask(jnp.asarray(x), 32))
    vals, idx = jax.lax.top_k(jnp.asarray(x), 32)
    want = np.zeros_like(got)
    for r in range(12):
        want[r, np.asarray(idx[r])[np.asarray(vals[r]) > -np.inf]] = True
    assert (got == want).all()
    from benchmark.selection_tap import unpack

    assert (unpack(np.asarray(la.pack_mask(jnp.asarray(got))), 200)
            == got).all()


def test_published_configuration_is_the_programs():
    """The cell's file against the program's preset: every published key,
    both per-layer lists a contiguous run of the published ones, the
    indexer's period; the whole model's parameters as published."""
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           "glm-5.2-ep16-w8.json")) as f:
        config = json.load(f)
    cfg, model = drv.program_config(config)
    assert (cfg.num_layers, cfg.experts_held, cfg.num_experts,
            cfg.vocab_size) == (9, 16, 256, 19360)
    assert latent_sparse.pattern(cfg) == tuple("Dssssssss".replace(
        "ssss", "sssS"))
    assert [latent_sparse.layers_of(cfg, k) for k in "AIDS"] == [9, 3, 1, 8]
    whole = tfm.get_config("glm-5.2")
    assert list(whole.indexer_types) == config["indexer_types"]
    assert list(whole.mlp_layer_types) == config["mlp_layer_types"]
    assert round(whole.num_params() / 1e9) == 743  # 744 B with the MTP layer
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "num_nextn_predict_layers"]
    assert la.pool_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim) == 640
    # an expert layer here: 818 M parameters, as the issue counts
    assert abs(latent_sparse.num_params(dataclasses.replace(
        cfg, num_layers=1, indexer_types=("full",),
        mlp_layer_types=("sparse",)), include_embed=False) / 1e6 - 818) < 1


def test_share_tiles():
    """The layout of a share: tiles of the expected rows, but no smaller
    than what cuts all T assignments into 32 tiles; the other models'
    pickers are untouched."""
    assert dropless.share_tile_m(4096, 256, 16) == 128
    assert dropless.share_tile_m(128, 256, 16) == 16
    assert dropless.share_padded_rows(4096, 256, 16) == (32 + 17) * 128
    assert dropless.moe_tile_m(256, 64) == 16
    assert dropless.moe_tile_m(4096, 64) == 128
    assert dropless.moe_tile_m(3072, 128) == 64


def test_v1_engine_refuses_a_latent_model(tiny):
    from deepspeed_tpu.inference.engine import InferenceEngine

    cfg, params, _ = tiny
    with pytest.raises(NotImplementedError, match="latent attention"):
        InferenceEngine(model_config=cfg, params=params)
