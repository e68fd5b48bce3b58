"""Kimi-Linear's block served by the v2 engine (``programs.LINEAR_LATENT``:
KDA state slots beside a latent pool read whole) against the plain reference
(``benchmark/reference/linear_latent_moe_decoder.py``: the recurrence a token
at a time), at the ``tiny-kimi-linear`` preset on the CPU; and the two KDA
paths and the full latent decode kernel alone against their oracles."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_latent_moe
from benchmark.drivers import serve_linear_latent_moe as drv
from benchmark.reference import linear_latent_moe_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import kimi_linear
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import kda
from deepspeed_tpu.ops.pallas import latent_attention as la

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from served_kinds import assert_step_attrs, refusal_cases  # noqa: E402

# float32 on both sides: what differs is the order of the sums (the chunked
# form against the recurrence, the absorbed attention against the expanded,
# paged chunks against an (S, S) mask).  Logits of standard deviation about
# 1; the right program reads 1e-5
TOL = 2e-4
#: the state's largest difference as a share of its largest entry: float32
#: sums in another order read 1e-6
STATE_TOL = 1e-4
CHECK = {"logit_prompts": [75, 40, 9], "logit_tokens": 8, "logit_pad": 32,
         "logit_tol_median": TOL, "logit_tol": TOL, "state_tol": STATE_TOL,
         "state_tol_deep": STATE_TOL, "state_low_bits_min": 0.5,
         "kda_tol": 1e-4,
         "agree_min": 0.99, "router_tol": 1e-4, "margin": 0.5,
         "decode_prompt": 21, "decode_tokens": 12, "conv_tol": STATE_TOL}


#: what ``serve_decode_sample`` read of the ``served`` fixture's engine
DECODE_SAMPLE = {}


def v2_config(**over):
    kw = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=65,
              max_blocks_per_seq=16, dtype="float32")
    kw.update(over)
    return V2Config(**kw)


# -- the two KDA paths and the latent kernel, alone --------------------------


def _kda_inputs(rng, T, H, dk, dv, alike=0.0):
    """``alike``: the share of every key that is one common direction (the
    keys of a piece behind an attention layer are alike)."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(T, H, dk))) / np.sqrt(dk)
    k = unit(alike * rng.normal(size=(1, H, dk))
             + np.sqrt(1 - alike ** 2) * rng.normal(size=(T, H, dk)))
    v = rng.normal(size=(T, H, dv))
    log_a = -np.exp(rng.normal(size=(T, H, dk)) - 3)
    log_a[:, :, :4] = -3.0  # channels that forget inside a piece
    b = 1 / (1 + np.exp(-rng.normal(size=(T, H))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, log_a, b))


@pytest.mark.parametrize("alike", [0.0, 0.99])
def test_chunked_form_matches_the_recurrence(alike):
    """Three rows lying end to end, pieces of 32 from each row's own start
    (so no piece ends on a row's edge but the rows' last): a row of 70 from
    its slot's state, a row of one token the scan leaves alone, a fresh row
    of 79 in a slot that held something; outputs and final states against
    the recurrence a token at a time.  With keys that are ALIKE the
    triangular system of a piece is far from the identity: an inverse by
    the powers of its nilpotent part lost every digit there (1e14 on this
    test), blocked forward substitution does not."""
    rng = np.random.default_rng(0)
    H, dk, dv, T = 2, 16, 16, 150
    q, k, v, log_a, b = _kda_inputs(rng, T, H, dk, dv, alike)
    S0 = jnp.asarray(rng.normal(size=(H, dk, dv)), jnp.float32)
    state = jnp.zeros((2, 4, H, dk, dv)).at[1, 1].set(S0).at[1, 0].set(7.0)
    row_start, row_len = jnp.array([0, 70, 71]), jnp.array([70, 1, 79])
    slots, fresh = jnp.array([1, 2, 0]), jnp.array([False, False, True])
    with jax.default_matmul_precision("highest"):
        o, new = jax.jit(kda.kda_chunk_scan, static_argnames="chunk")(
            state, jnp.int32(1), q, k, v, log_a, b, row_start, row_len,
            slots, fresh, row_len >= 2, chunk=32)
    o_a, S_a = kda.kda_recurrence(q[:70], k[:70], v[:70], log_a[:70], b[:70],
                                  S0)
    o_c, S_c = kda.kda_recurrence(q[71:], k[71:], v[71:], log_a[71:], b[71:],
                                  jnp.zeros_like(S0))
    assert float(jnp.abs(o[:70] - o_a).max()) < 1e-5
    assert float(jnp.abs(o[71:] - o_c).max()) < 1e-5
    assert float(jnp.abs(o[70]).max()) == 0.0  # the row of one token
    assert float(jnp.abs(new[1, 1] - S_a).max()) < 1e-5
    assert float(jnp.abs(new[1, 0] - S_c).max()) < 1e-5
    assert bool((new[0] == state[0]).all())  # the other layer: untouched
    assert bool((new[1, 2:] == state[1, 2:]).all())


@pytest.mark.parametrize("path", ["xla", "kernel-interpreted"])
def test_decode_update_matches_the_recurrence(path):
    """One token a slot at the kernel's own tiling (d_k = d_v = 128): a
    running slot, one that takes no step and keeps its state, a fresh one
    that starts from zeros whatever it held; the kernel's three bfloat16
    pieces of a, k and q give float32's result."""
    rng = np.random.default_rng(1)
    H, dk, dv, S1 = 2, 128, 128, 3
    q, k, v, log_a, b = _kda_inputs(rng, S1, H, dk, dv)
    state = jnp.asarray(rng.normal(size=(2, S1, H, dk, dv)), jnp.float32)
    active = jnp.array([True, False, True])
    fresh = jnp.array([False, False, True])
    if path == "xla":
        o, new = kda._decode_update_xla(state, jnp.int32(1), q, k, v, log_a,
                                        b, active, fresh)
    else:
        o, new = kda._decode_pallas(
            state, jnp.int32(1),
            *kda.decode_operands(q, k, v, log_a, b, active, fresh),
            interpret=True)
    for r in range(S1):
        start = jnp.zeros_like(state[1, r]) if fresh[r] else state[1, r]
        o_r, S_r = kda.kda_recurrence(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                      log_a[r:r + 1], b[r:r + 1], start)
        want = S_r if active[r] else state[1, r]
        assert float(jnp.abs(new[1, r] - want).max()) < 1e-5, r
        if active[r]:
            assert float(jnp.abs(o[r] - o_r[0]).max()) < 1e-5, r
    assert bool((new[0] == state[0]).all())


def test_full_latent_decode_kernel_reads_the_whole_context():
    """The paged kernel (interpreted) and its XLA twin against dense
    attention over each row's whole context: rows of 13, 0 (no step), 48
    (every block), 1 and 25 keys through a shuffled table, fetches of 4
    blocks, values the keys' first lanes."""
    rng = np.random.default_rng(0)
    R, H, W, lat, bs, blocks = 5, 4, 256, 128, 8, 6
    nb = R * blocks + 1
    pool = jnp.asarray(rng.normal(size=(2, nb, bs, W)), jnp.float32)
    tables = jnp.asarray(rng.permutation(nb - 1)[:R * blocks].reshape(
        R, blocks), jnp.int32)
    ctx = jnp.asarray([13, 0, 48, 1, 25], jnp.int32)
    q = jnp.asarray(rng.normal(size=(R, H, W)), jnp.float32)
    keys = pool[1][tables].reshape(R, blocks * bs, W)
    s = jnp.einsum("rhw,rkw->rhk", q, keys) * 0.1
    seen = jnp.arange(blocks * bs)[None, None] < ctx[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, -1e30), -1) * seen
    want = jnp.einsum("rhk,rkc->rhc", p, keys[..., :lat])
    args = (q, pool, jnp.int32(1), tables, ctx)
    got = la._decode_full_pallas(*args, kb=4, scale=0.1, latent=lat,
                                 interpret=True)
    twin = la._decode_full_xla(*args, scale=0.1, latent=lat)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(twin - want).max()) < 1e-5
    assert float(jnp.abs(got[1]).max()) == 0.0


# -- the engine against the reference ----------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = tfm.get_config("tiny-kimi-linear", dtype="float32",
                         param_dtype="float32")
    params = drv.draw_small_tensors(
        tfm.init_params(jax.random.PRNGKey(7), cfg), seed=7)
    return cfg, params, drv.published_model(cfg)


@pytest.fixture(scope="module")
def served(tiny):
    """Three sequences through the engine's own step programs with the tap
    on: prompts of 75 (chunked 32 + 32 + 11, pieces of 8: the state and the
    conv's inputs carried across mixed steps, the last piece off the chunk),
    40 (a fresh row beside a running one in the second mixed step) and 9
    tokens, 8 tokens each (decode rows beside prefill rows, then decode
    steps)."""
    cfg, params, _ = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    tapped = drv.tap_logits(engine, cfg, 3, CHECK)
    assert engine.drained()
    assert engine.free_state_slots == engine.total_state_slots == 4
    assert engine.free_blocks == engine.total_blocks
    # (a later ``tap_logits`` of this module notes its own)
    DECODE_SAMPLE.update(serve_latent_moe._NOTES["decode_sample"])
    return tapped


def test_engine_matches_reference(tiny, served):
    """Chunked prefill, then decode through both caches, against the
    reference's one uncached pass, by LOGITS: left to its own expert choices
    (float32 on both sides: they are the program's) and held to the
    program's."""
    cfg, params, model = tiny
    for force in (False, True):
        errs, _, _ = drv.row_errors(params, model, served, 32, force=force)
        assert len(errs) == 24 and errs.max() < TOL, (force, errs.max())


def test_a_slot_holds_the_references_state(tiny, served):
    """Every KDA layer's state of each sequence's slot, as the engine left
    it, against the reference's state after the same tokens, directly."""
    cfg, params, model = tiny
    _, states, _ = drv.row_errors(params, model, served, 32)
    assert [len(s) for s in states] == [kimi_linear.layers_of(cfg, "K")] * 3
    assert max(max(s) for s in states) < STATE_TOL, states
    # and it is a memory: the first sequence's state is no small thing
    assert float(np.abs(served[0][4]).max()) > 0.01


@pytest.mark.parametrize("fault", [None, "state_lost", "conv_lost",
                                   "stale_start", "no_beta"])
def test_the_served_decode_program_leaves_the_references_state(
        tiny, served, fault):
    """``serve_decode_sample`` (taken by ``tap_logits`` before the tap goes
    on): one request through the step programs as served, eleven decode-only
    steps two in flight; its slot's first KDA layer against the reference's
    after the same tokens, state and conv's kept inputs, and what a program
    that lost either between decode steps would have left."""
    _, params, model = tiny
    sample = DECODE_SAMPLE
    assert sample["prompt"] == 21 and len(sample["tokens"]) == 21 + 11
    assert sample["steps"] == 11 and sample["ahead"] == 10
    got = drv.decode_sample_errors(params, model, sample,
                                   (fault,) if fault else ())
    assert got["conv"] < STATE_TOL < 0.1 < got["conv_late"], got
    if fault is None:
        assert got["state"] < STATE_TOL, got
    else:
        assert got["state"] > 1e-2, got


def test_checks_pass_on_the_right_program(tiny, served):
    cfg, params, model = tiny
    drv._CHECK.update(cfg=cfg)
    drv._NOTES["decode_sample"] = DECODE_SAMPLE
    got = drv.check_logits(params, model, served, CHECK, lambda m: None)
    assert got["ok"], got
    from unittest import mock

    with mock.patch.object(serve_latent_moe, "reference", reference):
        assert serve_latent_moe.check_router(params, model, cfg, served,
                                             CHECK, lambda m: None)["ok"]


@pytest.mark.parametrize("fault", ["decay_after_delta", "state_bf16",
                                   "state_lost", "stale_start"])
def test_the_recurrence_compared_directly_sees_its_faults(tiny, fault):
    """``kda_direct``: the program's two KDA paths against the reference's
    scan on the same float32 inputs read 1e-6; a recurrence with one fault
    of its own reads a hundred times the limit."""
    cfg, params, model = tiny
    tokens = np.random.default_rng(5).integers(1, 256, size=70).tolist()
    assert drv.kda_direct(params, model, cfg, tokens) < 1e-5
    # (the tiny gate forgets slowly: LOST_EVERY tokens apart nothing is lost
    # inside 70 tokens, so the fault is read at the tiny step budget)
    kw = {"lost_every": 32} if fault == "state_lost" else {}
    scan = reference.kda_scan
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "kda_scan",
                   lambda *a, **k: scan(*a, **{**k, **kw}))
        assert drv.kda_direct(params, model, cfg, tokens, (fault,)) > 1e-3


def test_decode_rows_ride_in_mixed_steps(tiny):
    """``logit_filler``: one more prompt prefills while the compared
    sequences decode, so their decode rows take the MIXED program's decode
    path (the KDA decode kernel's dense pass over the slots, the full latent
    decode beside the prefill kernel) and no decode-only step runs."""
    cfg, params, model = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    tapped = drv.tap_logits(engine, cfg, 3, dict(
        CHECK, logit_prompts=[40, 9], logit_tokens=3, logit_filler=100))
    assert engine.drained()
    assert drv._NOTES["steps"] == {"mixed": 5, "decode": 0}
    errs, states, _ = drv.row_errors(params, model, tapped, 32)
    assert len(errs) == 6 and errs.max() < TOL, errs.max()
    assert max(max(s) for s in states) < STATE_TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_wrong_program_fails(tiny, served, fault):
    """Every named wrong program fails a limit of the comparison: the median
    logit row, or (a fault the few tapped rows of a short context hardly
    meet) the state of the slots."""
    cfg, params, model = tiny
    layer = reference.kda_layer
    with pytest.MonkeyPatch.context() as mp:
        # the two faults of a step's edge, at the tiny engine's step budget
        mp.setattr(reference, "kda_layer",
                   lambda *a, **kw: layer(*a, lost_every=32, **kw))
        errs, states, _ = drv.row_errors(params, model, served[:1], 32,
                                         (fault,))
    state = max(max(s) for s in states)
    assert np.median(errs) > 50 * TOL or state > 50 * STATE_TOL, (
        fault, float(np.median(errs)), state)


def test_more_requests_than_rows(tiny):
    """Six requests over four rows and slots give the tokens each gives
    alone: a row's output does not depend on its neighbours, a slot taken a
    second time starts from zeros, and everything is free after the drain."""
    cfg, params, _ = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, size=n).tolist()
               for n in (50, 20, 37, 9, 66, 41)]
    engine = InferenceEngineV2(cfg, params, v2_config())
    uids = [engine.put(p, max_new_tokens=5) for p in prompts]
    together = engine.generate_all(burst=1)
    assert engine.drained()
    assert engine.free_state_slots == engine.total_state_slots
    for p, u in zip(prompts, uids):
        alone = InferenceEngineV2(cfg, params, v2_config())
        v = alone.put(p, max_new_tokens=5)
        assert alone.generate_all(burst=1)[v] == together[u]


def test_engine_w8a16_matches_reference(tiny):
    """int8 codes read by both sides, bfloat16 activations, the state in
    float32: the reference held to the program's expert choices."""
    cfg = tfm.get_config("tiny-kimi-linear", dtype="bfloat16",
                         param_dtype="bfloat16")
    params = drv.make_params(cfg, 7, 8, 128)
    from deepspeed_tpu.ops.pallas.mixed_gemm import QuantizedWeight

    lay = params["layers"]
    assert isinstance(lay["K"]["kda"]["w_qkv"], QuantizedWeight)
    assert isinstance(lay["A"]["attn"]["w_q"], QuantizedWeight)
    assert not isinstance(lay["K"]["kda"]["w_f_up"], QuantizedWeight)
    assert lay["K"]["kda"]["A_log"].dtype == jnp.float32
    engine = InferenceEngineV2(cfg, params, v2_config(dtype="bfloat16"))
    assert engine.caches["kda"].dtype == jnp.float32
    tapped = drv.tap_logits(engine, cfg, 3, CHECK)
    errs, states, _ = drv.row_errors(params, drv.published_model(cfg),
                                     tapped, 32)
    # at a hidden width of 128 bfloat16 moves a row by a quarter of a
    # logit's spread over ten layers, and the deepest state with it; the
    # first KDA layer's state reads what its own arithmetic does
    assert np.median(errs) < 0.4 and errs.max() < 0.6, errs
    assert max(s[0] for s in states) < 0.02, states
    assert max(max(s) for s in states) < 0.2, states


@pytest.mark.parametrize("over,name", refusal_cases(
    programs.LINEAR_LATENT, tfm.get_config("tiny-kimi-linear"), v2_config()))
def test_refused_with_state_slots_beside_a_latent_pool(tiny, over, name):
    """Every row of the refusal table (``programs.REFUSED``): the kind holds
    them all, and says which two caches are why."""
    cfg, params, _ = tiny
    with pytest.raises(ValueError,
                       match=f"V2Config.*{name}.*KDA.*state.*latent"):
        InferenceEngineV2(cfg, params, v2_config(**over))


def test_step_spans_carry_the_counters(tiny):
    cfg, params, _ = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    engine.put(list(range(1, 41)), max_new_tokens=3)
    tracer.clear()
    engine.generate_all(burst=1)
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    assert_step_attrs(steps, "moe", "linear_latent")
    mixed = [a for a in steps if a["kind"] == "mixed"][0]  # tokens 0..31
    assert mixed["state_slots_used"] == 1
    assert mixed["state_rows_started"] == 1
    assert mixed["kda_tokens"] == 32
    assert mixed["kda_state_bytes"] == 2 * 7 * 4 * 16 * 16 * 4
    assert (mixed["kda_scan_rows"], mixed["kda_scan_tokens"],
            mixed["kda_scan_pieces"]) == (1, 32, 4)
    assert mixed["latent_keys_read"] == mixed["latent_keys_prefill"] == 32 * 3
    assert mixed["latent_query_keys"] == sum(range(1, 33)) * 3
    assert mixed["moe_assignments"] == 32 * 4 * 9
    assert 0 < mixed["moe_assignments_local"] < mixed["moe_assignments"]
    assert mixed["blocks_used_latent"] == 6  # 40 + 3 tokens: 6 blocks of 8
    decode = [a for a in steps if a["kind"] == "decode"][0]
    assert decode["latent_keys_single"] == decode["latent_keys_read"] \
        == 41 * 3  # the token at position 40 sees 41 keys
    assert decode["kda_tokens"] == 1 and decode["state_rows_started"] == 0
    assert decode["moe_rows_padded"] == dropless.share_padded_rows(16, 16, 4)


def test_the_model_is_served_not_trained(tiny):
    cfg, params, _ = tiny
    with pytest.raises(NotImplementedError, match="KDA.*served.*backward"):
        tfm.forward_hidden(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_the_gate_reaches_back():
    """``assumed.weights``: the median channel keeps at least 1/e of a write
    after 256 tokens, so a program that loses the state is seen."""
    cfg = tfm.get_config("tiny-kimi-linear", dtype="float32",
                         param_dtype="float32")
    p = jax.tree.map(lambda a: a[0], tfm.init_params(
        jax.random.PRNGKey(3), cfg)["layers"]["K"]["kda"])
    a = jax.random.normal(jax.random.PRNGKey(4), (256, cfg.hidden_size))
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True))
    qkv = jnp.zeros((256, 3 * kimi_linear.kda_width(cfg)))
    log_a = kimi_linear.kda_inputs(qkv, a, p, cfg)[3]
    kept = jnp.median(log_a.sum(0))
    assert -1.0 < float(kept) < -0.1, float(kept)


def test_preset_counts_its_parameters():
    for name in ("tiny-kimi-linear", "kimi-linear-48b"):
        cfg = tfm.get_config(name)
        shapes = jax.eval_shape(
            lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0))
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
            == cfg.num_params(), name
        axes = tfm.param_axes(cfg)
        assert jax.tree.structure(shapes) == jax.tree.structure(
            axes, is_leaf=lambda x: isinstance(x, tuple))
    cfg = tfm.get_config("kimi-linear-48b")
    assert 48.0e9 < cfg.num_params() < 50.0e9  # "48B"
    assert kimi_linear.pattern(cfg) == tuple("k" + "KKA" + "KKKA" * 5 + "KKA")
    assert [kimi_linear.layers_of(cfg, k) for k in "KADS"] == [20, 7, 1, 26]
