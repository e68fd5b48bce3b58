"""NVIDIA-Nemotron-3-Nano's three kinds of layer (Mamba-2, sigmoid-routed MoE
with a shared expert, attention without positions) at toy widths: the
program's logits against ``benchmark/reference/ssm_moe_decoder.py`` (float32,
seeded weights), the per-sequence state's allocator, the wrong programs the
comparison has to see, and what the three other models' step programs must
still be."""

import collections
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.serve_ssm_moe import draw_small_tensors, published_model
from benchmark.logit_tap import LogitTap
from benchmark.reference import ssm_moe_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import (AdmissionError,
                                               InferenceEngineV2, V2Config)
from deepspeed_tpu.models import ssm_hybrid
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import grouped_mixed_gemm, mixed_gemm, ssm
from served_kinds import assert_step_attrs, refusal_cases

# float32 on both sides: what differs is the order of the sums (chunks of 16
# against one token at a time, paged attention against an (S, S) mask).
# Logits of standard deviation about 1; the right program reads 5e-6
TOL = 1e-4
# the same with int8 codes read by both sides.  The kernels round the
# activations and the dequantized weights to bfloat16 for the MXU, whatever
# the engine's dtype (W8A16), so every projection carries 2^-9 of rounding:
# the median row reads 0.03 (a layer's codes read from the wrong layer: 3.4
# and more).  A rounding can also flip a position's choice between the third
# and fourth of 8 experts, which moves that row and, through the state, rows
# after it by 1 and more (one row in eighteen here), and the reference's
# margin at one position does not say which: so the bound is on the median
# row and, four times wider, on nine rows in ten
TOL_W8 = 0.08


def v2_config(**over):
    kw = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=96,
              max_blocks_per_seq=16, dtype="float32")
    kw.update(over)
    return V2Config(**kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = tfm.get_config("tiny-nemotron3", dtype="float32")
    params = draw_small_tensors(
        tfm.init_params(jax.random.PRNGKey(7), cfg), seed=7)
    return cfg, params, published_model(cfg)


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


def tapped_run(cfg, params, v2, prompts, new=6):
    """Serve ``prompts`` together, step by step, every step's logits tapped
    → (engine, {uid: tokens}, {uid: [(position, logits)]}, uids)."""
    eng = InferenceEngineV2(cfg, params, v2)
    tap = LogitTap(eng)
    eng.slot_of, take = {}, eng.kv.slots.take  # uid -> its state slot
    eng.kv.slots.take = lambda uid: eng.slot_of.setdefault(uid, take(uid))
    uids = [eng.put(p, max_new_tokens=new) for p in prompts]
    out = eng.generate_all(burst=1)
    tap.remove()
    return eng, out, tap.logits, uids


def row_errors(ref_params, model, out, logits, uids, prompts, faults=()):
    """|engine - reference|, the largest over the vocabulary, of every
    tapped row."""
    errs = []
    for uid, prompt in zip(uids, prompts):
        want = np.asarray(reference.logits(
            ref_params, model, jnp.asarray(out[uid], jnp.int32),
            faults=frozenset(faults)))
        assert len(logits[uid]) == len(out[uid]) - len(prompt)
        errs += [float(np.abs(row - want[pos]).max())
                 for pos, row in logits[uid]]
    return np.asarray(errs)


def worst_row(*args, **kwargs):
    return float(row_errors(*args, **kwargs).max())


def test_forward_matches_reference(tiny):
    """(a) ``tfm.forward``: the pattern ``MEM*EMEME`` is no period repeated,
    2 groups under 8 heads, widths 192 and 64."""
    cfg, params, model = tiny
    assert ssm_hybrid.segments(cfg.mixer_pattern) == [
        (("M",), 1), (("E",), 1), (("M",), 1), (("*",), 1), (("E", "M"), 2),
        (("E",), 1)]
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))
    toks = jnp.asarray(prompts_of([45, 45]), jnp.int32)
    got = np.asarray(tfm.forward(params, toks, cfg))
    for b in range(2):
        want = np.asarray(reference.logits(params, model, toks[b]))
        assert np.abs(got[b] - want).max() < TOL


# 70 = 32 + 32 + 6 crosses the step budget three times, its chunk edges at
# SSD chunk edges (16); 41 rides behind it and is cut inside a chunk; 9 and
# 23 start while the others decode, so decode rows ride in mixed steps
LENGTHS = [70, 41, 9, 23]


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params, model = tiny
    prompts = prompts_of(LENGTHS)
    eng, out, logits, uids = tapped_run(cfg, params, v2_config(), prompts)
    return eng, out, logits, uids, prompts


def test_engine_matches_reference(tiny, served):
    """(b) chunked prefill across three steps, decode rows in mixed steps,
    then decode: every step's logits against the reference's full forward."""
    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    right = worst_row(params, model, out, logits, uids, prompts)
    print(f"right program: worst row {right:.3g}")
    assert right < TOL
    assert eng.drained() and eng.free_state_slots == 4
    eng.kv.check_consistency()


def test_engine_w8a16_matches_reference(tiny):
    """(c) the same in W8A16, the reference reading the same codes.  The
    experts' width 192 is stored padded to 256 (the rule that stores 1856 as
    1920): the padded columns are zeros on both sides."""
    cfg, params, model = tiny
    prompts = prompts_of(LENGTHS[:3])
    eng, out, logits, uids = tapped_run(
        cfg, params, v2_config(quantize_bits=8, quantize_group=256), prompts)
    moe = eng.params["layers"]["E"]["moe"]
    assert moe["w_in"].codes.shape[-1] == 256
    assert moe["w_out"].codes.shape[-2] == 256
    assert isinstance(eng.params["layers"]["M"]["mamba"]["w_xbc"],
                      mixed_gemm.QuantizedWeight)
    errs = row_errors(eng.params, model, out, logits, uids, prompts)
    print(f"W8A16: median {np.median(errs):.4f}, nine rows in ten under "
          f"{np.quantile(errs, 0.9):.4f}, worst {errs.max():.3f}")
    assert np.median(errs) < TOL_W8
    assert np.quantile(errs, 0.9) < 4 * TOL_W8


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_wrong_program_fails(tiny, served, fault):
    """(e) each wrong program, through the same comparison, by how much
    (``-s`` prints it): 0.34 to 4.7 for eleven of them; the state kept in
    bfloat16 reads 0.0047, 47 times the tolerance and 900 times what the
    right program reads."""
    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    err = worst_row(params, model, out, logits, uids[1:2], prompts[1:2],
                    faults=[fault])
    print(f"fault {fault}: worst row {err:.4g}")
    assert err > 30 * TOL


@pytest.mark.parametrize("state_bf16", [False, True])
def test_engine_state_slots_hold_the_reference_states(tiny, served,
                                                      state_bf16):
    """The engine's own state array after the run (slots are never cleared):
    each sequence's slot against the reference pass's states after the last
    token the engine read (the last one sampled is never read), Mamba layer
    by Mamba layer, as the serving cell compares them
    (``serve_ssm_moe.row_errors``).  In float32 on both sides 3e-6 of the
    largest element; the reference with its state kept in bfloat16 between
    tokens reads 2e-3 and more, and a slot rounded to bfloat16 holds no low
    mantissa bits (``low_bits_share``: what the cell holds the served
    programs' states to)."""
    from benchmark.drivers.serve_ssm_moe import low_bits_share

    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    assert sorted(eng.slot_of.values()) == [0, 1, 2, 3]
    worst = 0.0
    for uid in uids:
        n = len(out[uid])
        want = np.asarray(reference.whole_pass(
            params, model, jnp.asarray(out[uid], jnp.int32), length=n - 1,
            faults=frozenset({"state_bf16"} if state_bf16 else ()))["states"])
        slot = np.asarray(eng.caches["ssm"][:, eng.slot_of[uid]])
        assert slot.shape == want.shape == (4, 8, 16, 32)
        worst = max(worst, float((np.abs(slot - want).max((1, 2, 3))
                                  / np.abs(want).max((1, 2, 3))).max()))
        assert low_bits_share(slot) > 0.99
        assert low_bits_share(jnp.asarray(slot).astype(jnp.bfloat16)) == 0.0
    print(f"state slots against the reference's"
          f"{' (state kept in bfloat16)' if state_bf16 else ''}: {worst:.3g}")
    assert (worst > 1e-3) if state_bf16 else (worst < 1e-5)


@pytest.mark.parametrize("fault", reference.ROUTER_FAULTS)
def test_wrong_router_picks_other_experts(tiny, fault):
    """What ``agree_min`` is sized against: the reference's router with one
    thing wrong picks the right router's experts at a smaller share of the
    (layer, position) pairs (``-s`` prints it: 0.72, 0.992, 0.76 here, at 8
    experts of which 3 are chosen; 1.0 without a fault)."""
    cfg, params, model = tiny
    tokens = jnp.asarray(prompts_of([64], seed=5)[0], jnp.int32)
    inputs = reference.router_inputs(params, model, tokens)
    right = np.sort(np.asarray(reference.own_choices(params, model, inputs)))
    wrong = np.sort(np.asarray(reference.own_choices(params, model, inputs,
                                                     faults={fault})))
    share = float((right == wrong).all(-1).mean())
    print(f"router fault {fault}: the same experts at {share:.3f} of pairs")
    assert right.shape == (4, 64, 3) and share < 1.0


def test_more_sequences_than_slots(tiny):
    """(d) nine requests over three slots: every slot is reused, and each
    answer is the answer of the same request served alone (a stale state, or
    a state carried into the wrong row, changes the tokens)."""
    cfg, params, model = tiny
    prompts = prompts_of([40, 7, 33, 12, 50, 5, 21, 36, 3], seed=11)
    v2 = v2_config(max_seqs=3)
    eng = InferenceEngineV2(cfg, params, v2)
    seen = collections.Counter()
    take = eng.kv.slots.take
    eng.kv.slots.take = lambda uid: seen.update([s := take(uid)]) or s
    uids = [eng.put(p, max_new_tokens=5) for p in prompts]
    together = eng.generate_all(burst=1)
    assert set(seen) == {0, 1, 2} and min(seen.values()) >= 2
    assert eng.drained() and eng.free_state_slots == 3
    eng.kv.check_consistency()
    for uid, prompt in zip(uids, prompts):
        alone = InferenceEngineV2(cfg, params, v2)
        u = alone.put(prompt, max_new_tokens=5)
        assert alone.generate_all(burst=1)[u] == together[uid]


def test_cancel_gives_the_slot_back(tiny):
    cfg, params, model = tiny
    eng = InferenceEngineV2(cfg, params, v2_config(max_seqs=2))
    a = eng.put(prompts_of([40])[0], max_new_tokens=8)
    b = eng.put(prompts_of([10])[0], max_new_tokens=8)
    eng.step()  # a's first chunk: a holds a slot, mid-prefill
    # (b, which that step's budget kept out, has taken the other: it joined
    # the step called behind a's, ISSUE 54, whose program is under way)
    assert eng.free_state_slots == 0 and eng._ahead is not None
    with pytest.raises(AdmissionError, match="0 of 2 state slots free"):
        eng.put([1, 2, 3], strict=True)
    assert eng.cancel(a)  # a timeout and an abort end here too
    assert eng.free_state_slots == 1
    eng.step()
    eng.step()
    assert eng.cancel(b)  # mid-decode
    assert eng.drained()
    eng.kv.check_consistency()
    stats = eng.prefix_stats()
    assert stats["state_slots"] == 2 and stats["state_slots_free"] == 2


@pytest.mark.parametrize("over, name", refusal_cases(
    programs.STATE, tfm.get_config("tiny-nemotron3"), v2_config()))
def test_refused_with_state_layers(tiny, over, name):
    """(h) what cannot carry a state yet is refused by name: every row of
    the refusal table (``programs.REFUSED``) the kind holds."""
    cfg, params, model = tiny
    with pytest.raises(ValueError, match=f"V2Config.*{name}.*state layers"):
        InferenceEngineV2(cfg, params, v2_config(**over))


def test_step_spans_carry_state_counters(tiny):
    from deepspeed_tpu.observability.trace import tracer

    cfg, params, model = tiny
    eng = InferenceEngineV2(cfg, params, v2_config())
    was, tracer.enabled = tracer.enabled, True
    try:
        eng.put(prompts_of([40])[0], max_new_tokens=3)
        eng.generate_all(burst=1)
        steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"
                 and "ssm_tokens" in s.attrs][-4:]
        assert_step_attrs(steps, "moe", "state")
        names = {s.name for s in tracer.spans()}
    finally:
        tracer.enabled = was
    row = 2 * cfg.layers_of("M") * 8 * 16 * 32 * 4
    assert [(a["kind"], a["ssm_tokens"], a["state_rows_started"],
             a["state_slots_used"], a["ssm_state_bytes"]) for a in steps] == [
        ("mixed", 32, 1, 1, row), ("mixed", 8, 0, 1, row),
        ("decode", 1, 0, 1, row), ("decode", 1, 0, 1, row)]
    assert {"kernel/ssm_decode_update", "kernel/ssd_chunk_scan_tiles"} <= names


def test_scan_against_the_recurrence():
    """(f) both state updates against the single-token recurrence: rows of 1,
    127, 128, 129 and 300 tokens in one call, chunk 128, each from the state
    of its slot (one starts a sequence: zeros whatever the slot holds)."""
    H, P, G, N, Q = 4, 8, 2, 16, 128
    lens = np.array([1, 127, 128, 129, 300, 0], np.int32)
    T = int(lens.sum()) + 11  # padding tokens behind the rows
    k = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(k[0], (T, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (T, G, N))
    C = jax.random.normal(k[4], (T, G, N))
    D = jax.random.normal(k[5], (H,))
    ssm0 = jax.random.normal(k[6], (2, 7, H, P, N))
    slots = jnp.asarray([4, 0, 5, 2, 1, 6], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False, False])
    starts = jnp.asarray(np.cumsum(lens) - lens, jnp.int32)
    scanned = jnp.asarray(lens >= 2)
    y, new = jax.jit(ssm.ssd_chunk_scan, static_argnames="chunk")(
        ssm0, jnp.int32(1), x, dt, A, B, C, D, starts, jnp.asarray(lens),
        slots, fresh, scanned, chunk=Q)
    for r in range(1, 5):
        s, n = int(starts[r]), int(lens[r])
        first = jnp.zeros((H, P, N)) if bool(fresh[r]) \
            else ssm0[1, int(slots[r])]
        want, state = ssm.ssm_recurrence(x[s:s + n], dt[s:s + n], A,
                                         B[s:s + n], C[s:s + n], D, first)
        np.testing.assert_allclose(y[s:s + n], want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[1, int(slots[r])], state, rtol=2e-4,
                                   atol=2e-4)
    # untouched: the other layer, the slots of rows not scanned, the padding
    np.testing.assert_array_equal(new[0], ssm0[0])
    np.testing.assert_array_equal(new[1, [3, 4, 6]], ssm0[1, [3, 4, 6]])
    assert not np.asarray(y[:1]).any() and not np.asarray(y[-11:]).any()
    # the row of one token, through the dense pass in slot order
    one = jnp.zeros((7,), bool).at[4].set(True)
    put = lambda a: jnp.zeros((7,) + a.shape[1:]).at[4].set(a[0])
    y1, new1 = jax.jit(ssm.ssm_decode_update)(
        new, jnp.int32(1), put(x), put(dt), A, put(B), put(C), D, one,
        jnp.zeros((7,), bool))
    want, state = ssm.ssm_recurrence(x[:1], dt[:1], A, B[:1], C[:1], D,
                                     ssm0[1, 4])
    np.testing.assert_allclose(y1[4], want[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new1[1, 4], state, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(new1[1, :4], new[1, :4])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_decode_kernel_against_the_recurrence(dtype):
    """(f) the decode update's Pallas kernel in interpret mode: every slot a
    step from ITS state (one starts a sequence, one sits the step out),
    against the single-token recurrence and against the XLA formulation."""
    H, P, G, N, S1 = 4, 32, 2, 128, 5
    k = jax.random.split(jax.random.PRNGKey(9), 8)
    x = jax.random.normal(k[0], (S1, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (S1, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B = jax.random.normal(k[3], (S1, G, N)).astype(dtype)
    C = jax.random.normal(k[4], (S1, G, N)).astype(dtype)
    D = jax.random.normal(k[5], (H,))
    ssm0 = jax.random.normal(k[6], (2, S1, H, P, N))
    active = jnp.asarray([True, True, False, True, True])
    fresh = jnp.asarray([False, True, False, False, False])
    assert ssm.decode_update_tiles(H, P, N)
    assert ssm.decode_update_tiles(64, 64, 128)  # the published sizes
    y, new = jax.jit(ssm._decode_update_kernel)(
        ssm0, jnp.int32(1), x, dt, A, B, C, D, active, fresh)
    y_xla, new_xla = jax.jit(ssm._decode_update_xla)(
        ssm0, jnp.int32(1), x, dt, A, B, C, D, active, fresh)
    np.testing.assert_allclose(new, new_xla, rtol=1e-5, atol=1e-5)
    for r in range(S1):
        first = jnp.zeros((H, P, N)) if bool(fresh[r]) else ssm0[1, r]
        want, state = ssm.ssm_recurrence(x[r:r + 1], dt[r:r + 1], A,
                                         B[r:r + 1], C[r:r + 1], D, first)
        if bool(active[r]):
            np.testing.assert_allclose(y[r], want[0], rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(new[1, r], state, rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(new[1, r], ssm0[1, r])
    np.testing.assert_array_equal(new[0], ssm0[0])


def test_nemotron_engine_holds_state_beside_one_kv_pool(tiny):
    cfg, params, model = tiny
    eng = InferenceEngineV2(cfg, params, v2_config())
    assert set(eng.caches) == {"k", "v", "ssm", "conv"}
    assert eng.caches["k"].shape[0] == 1  # the one attention layer's K/V
    assert eng.caches["ssm"].shape == (4, 5, 8, 16, 32)
    assert eng.caches["conv"].shape == (4, 5, 3, 256)


# ---------------------------------------------------------------------------
# (i) the pickers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows, tile_m", [(2432, 16), (11264, 64)])
def test_grouped_picker_at_nemotrons_experts(rows, tile_m):
    """1856 = 29 x 64 stored as 1920: the dense rule's tile (all of N, K in
    three tiles of whole groups, 1.7 MB of codes a step), for the decode
    step's layout (64 rows x top 6 over 128 experts) and the mixed step's
    (512 tokens)."""
    from deepspeed_tpu.moe.dropless import moe_tile_m, padded_rows

    assert (moe_tile_m(64 * 6, 128), padded_rows(64 * 6, 128)) == (16, 2432)
    assert (moe_tile_m(512 * 6, 128), padded_rows(512 * 6, 128)) == (64, 11264)
    up = grouped_mixed_gemm.pick_grouped_tiles(rows, tile_m, 2688, 1920, 8,
                                               128)
    down = grouped_mixed_gemm.pick_grouped_tiles(rows, tile_m, 1920, 2688, 8,
                                                 128)
    assert (up.tn, up.tk) == (1920, 896) and (down.tn, down.tk) == (2688, 640)
    assert up.code_bytes_per_step == down.code_bytes_per_step == 1720320
    # the unpadded width has no tile at any group
    for group in (32, 64, 128):
        assert grouped_mixed_gemm.pick_grouped_tiles(
            rows, tile_m, 2688, 1856, 8, group) is None


@pytest.mark.parametrize("k, n", [(2688, 4096), (2688, 6144), (4096, 2688),
                                  (2688, 256), (2688, 3712), (3712, 2688)])
@pytest.mark.parametrize("m", [64, 512])
def test_dense_picker_at_nemotrons_projections(m, k, n):
    tiles = mixed_gemm.pick_gemm_tiles(m, k, n, 8, 128)
    assert tiles is not None and k % tiles.tk == 0 and n % tiles.tn == 0


@pytest.mark.parametrize("args, want", [
    # OLMoE (2048 x 1024) and Mellum2 (2304 x 896), decode and mixed layouts
    ((1280, 16, 2048, 1024, 8, 256), (16, 1024, 2048)),
    ((1280, 16, 1024, 2048, 8, 256), (16, 2048, 1024)),
    ((12288, 128, 2048, 1024, 8, 256), (128, 1024, 2048)),
    ((12288, 128, 1024, 2048, 8, 256), (128, 2048, 1024)),
    ((1280, 16, 2304, 896, 8, 128), (16, 896, 2304)),
    ((1280, 16, 896, 2304, 8, 128), (16, 2304, 896)),
    ((12288, 128, 2304, 896, 8, 128), (128, 896, 2304)),
    ((12288, 128, 896, 2304, 8, 128), (128, 2304, 896)),
])
def test_grouped_picker_unchanged_for_olmoe_and_mellum2(args, want):
    tiles = grouped_mixed_gemm.pick_grouped_tiles(*args)
    assert (tiles.tm, tiles.tn, tiles.tk) == want
