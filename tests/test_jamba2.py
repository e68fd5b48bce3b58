"""AI21-Jamba2's two sub-layers (a Mamba-1 selective-scan mixer or attention
without positions, and a dense gated FFN behind every mixer) at toy widths:
the program's hidden states and logits against
``benchmark/reference/selective_ssm_decoder.py`` (float32, seeded weights),
both state updates against the recurrence a token at a time, the state slots,
and the wrong programs the comparison has to see."""

import collections
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.serve_selective import (WindowTap, draw_small_tensors,
                                               make_params, pattern_of,
                                               published_model,
                                               sequence_errors)
from benchmark.logit_tap import LogitTap
from benchmark.reference import selective_ssm_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import ssm_hybrid
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.ops.pallas import selective_scan as ss
from served_kinds import assert_step_attrs, refusal_cases

# float32 on both sides: what differs is the order of the sums (paged
# attention against an (S, S) mask, the conv's taps).  Logits of standard
# deviation about 1; the right program reads 1e-5
TOL = 1e-4


def v2_config(**over):
    kw = dict(max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=96,
              max_blocks_per_seq=16, dtype="float32")
    kw.update(over)
    return V2Config(**kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = tfm.get_config("tiny-jamba2", dtype="float32")
    params = draw_small_tensors(
        tfm.init_params(jax.random.PRNGKey(7), cfg), seed=7)
    return cfg, params, published_model(cfg)


def prompts_of(lengths, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, size=n).tolist() for n in lengths]


def test_preset_is_the_published_model():
    """The preset's sizes: 3.03 B parameters, 26 Mamba and 2 attention
    mixers (layers 7 and 21) and 28 FFNs folded into runs of a two-letter
    unit, a tied head; the tiny preset the issue states."""
    big = tfm.get_config("jamba2-3b")
    assert programs.kind_of(big) is programs.STATE
    assert "".join(big.mixer_pattern) == pattern_of(28, 14, 7)
    assert [i for i, k in enumerate(big.mixer_pattern[::2]) if k == "*"] \
        == [7, 21]
    # ``segments`` is greedy from the left (and stays as the other models
    # have it): three scanned runs of a two-letter unit and four sub-layers
    # that stand alone, ten traced bodies for 56 sub-layers
    assert ssm_hybrid.segments(big.mixer_pattern) == [
        (("S", "F"), 7), (("*",), 1), (("F", "S"), 13), (("F",), 1),
        (("*",), 1), (("F", "S"), 6), (("F",), 1)]
    assert big.mamba_d_inner == 5120 and big.tie_embeddings
    assert abs(big.num_params() - 3.03e9) < 0.005e9
    mixer = ssm_hybrid.selective_ssm.params_per_layer(big)
    assert abs(mixer["S"] - 41.24e6) < 0.01e6 and mixer["F"] - 2560 \
        == 3 * 2560 * 8192
    small = tfm.get_config("tiny-jamba2")
    assert published_model(small) == dict(
        num_hidden_layers=8, attn_layer_period=4, attn_layer_offset=2,
        hidden_size=64, intermediate_size=128, vocab_size=256,
        num_attention_heads=4, num_key_value_heads=1, rms_norm_eps=1e-6,
        mamba_d_conv=4, mamba_d_state=16, mamba_dt_rank=8, mamba_expand=2)


def test_one_recurrence_a_model():
    with pytest.raises(ValueError, match="one recurrence, not both"):
        tfm.get_config("tiny-jamba2", mixer_pattern=tuple("SFM*"),
                       num_layers=4)
    with pytest.raises(ValueError, match="mamba_expand"):
        tfm.get_config("tiny-jamba2", mamba_dt_rank=0)


def test_forward_matches_reference(tiny):
    """(a) ``forward_hidden`` against the reference, float32."""
    cfg, params, model = tiny
    assert cfg.num_params() == sum(x.size for x in jax.tree.leaves(params))
    assert "lm_head" not in params
    toks = jnp.asarray(prompts_of([45]), jnp.int32)
    got = np.asarray(tfm.forward_hidden(params, toks, cfg))
    logits = np.asarray(tfm.forward(params, toks, cfg))
    for b in range(1):
        want = np.asarray(reference.hidden_states(params, model, toks[b]))
        assert np.abs(got[b] - want).max() < 2e-5
        assert np.abs(logits[b] - np.asarray(
            reference.logits(params, model, toks[b]))).max() < TOL


def test_made_params_are_the_served_tree(tiny):
    """The benchmark's ``make_params`` (a stack a kind, a layer at a time)
    builds the tree ``init_params`` builds, in the type asked."""
    cfg, params, _ = tiny
    made = make_params(dataclasses.replace(cfg, param_dtype="bfloat16"), 3)
    assert jax.tree.map(lambda a: a.shape, made) \
        == jax.tree.map(lambda a: a.shape, params)
    mamba = made["layers"]["S"]["mamba"]
    assert mamba["w_in"].dtype == jnp.bfloat16
    assert mamba["A_log"].dtype == mamba["dt_bias"].dtype == jnp.float32
    scale = np.asarray(mamba["dt_norm"], np.float32)
    assert 0.5 <= scale.min() and scale.max() < 1.5 and scale.std() > 0.1


# 70 = 32 + 32 + 6 crosses the step budget three times; 41 rides behind it
# and is cut inside a chunk, so two rows of unequal length share a mixed
# step; 9 and 23 start while the others decode, so decode rows ride beside a
# chunk, and with three slots the fourth starts in a slot another left
LENGTHS = [70, 41, 9, 23]


def tapped_run(cfg, params, v2, prompts, new=6):
    eng = InferenceEngineV2(cfg, params, v2)
    tap = LogitTap(eng)
    eng.slot_of, take = {}, eng.kv.slots.take  # uid -> its state slot
    eng.kv.slots.take = lambda uid: eng.slot_of.setdefault(uid, take(uid))
    uids = [eng.put(p, max_new_tokens=new) for p in prompts]
    out = eng.generate_all(burst=1)
    tap.remove()
    return eng, out, tap.logits, uids


@pytest.fixture(scope="module")
def served(tiny):
    cfg, params, model = tiny
    prompts = prompts_of(LENGTHS)
    eng, out, logits, uids = tapped_run(cfg, params, v2_config(max_seqs=3),
                                        prompts)
    return eng, out, logits, uids, prompts


def row_errors(params, model, out, logits, uids, prompts, faults=()):
    errs = []
    for uid, prompt in zip(uids, prompts):
        want = np.asarray(reference.logits(
            params, model, jnp.asarray(out[uid], jnp.int32),
            faults=frozenset(faults)))
        assert len(logits[uid]) == len(out[uid]) - len(prompt)
        errs += [float(np.abs(row - want[pos]).max())
                 for pos, row in logits[uid]]
    return np.asarray(errs)


def test_engine_matches_reference(tiny, served):
    """(b) chunked prefill across three steps, rows of unequal length in one
    mixed step, decode rows beside a chunk, a row in a slot another left,
    then decode: every step's logits against the reference's full forward."""
    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    right = row_errors(params, model, out, logits, uids, prompts).max()
    print(f"right program: worst row {right:.3g}")
    assert right < TOL
    assert len(set(eng.slot_of.values())) == 3 and len(eng.slot_of) == 4
    assert eng.drained() and eng.free_state_slots == 3
    eng.kv.check_consistency()


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_wrong_program_fails(tiny, served, fault):
    """(e) each named wrong program through the same comparison (``-s``
    prints by how much): the state rounded to bfloat16 each token reads
    hundreds of times the tolerance, every other fault of order 1 or not a
    number."""
    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    err = row_errors(params, model, out, logits, uids[1:2], prompts[1:2],
                     faults=[fault]).max()
    print(f"fault {fault}: worst row {err:.4g}")
    assert not err <= 30 * TOL


def test_state_slots_hold_the_reference_states(tiny, served):
    """The engine's own state array after the run (slots are never cleared):
    the last three sequences' slots against the reference pass's states after
    the last token the engine read, Mamba layer by Mamba layer."""
    cfg, params, model = tiny
    eng, out, logits, uids, prompts = served
    for uid in uids[1:]:  # the first's slot was taken again
        n = len(out[uid])
        want = np.asarray(reference.whole_pass(
            params, model, jnp.asarray(out[uid], jnp.int32),
            length=n - 1)["states"])
        slot = np.asarray(eng.caches["ssm"][:, eng.slot_of[uid]])
        assert slot.shape == (6, 16, 128) and want.shape == (6, 128, 16)
        err = np.abs(slot - np.swapaxes(want, 1, 2)).max() / np.abs(want).max()
        assert err < 1e-5, err


def test_window_tap_reads_the_served_steps(tiny):
    """The cell's tap on the tiny engine: rows kept as slices of the mixed
    steps' own logits and the slot at the last step, against the reference
    as the driver compares them; the named faults are seen through it."""
    cfg, params, model = tiny
    eng = InferenceEngineV2(cfg, params, v2_config())
    tap = WindowTap(eng, long_min=60, long_max=100, others=1)
    tap.armed = True
    long_one, short, *fillers = prompts_of([70, 30, 110, 110], seed=9)
    uids = [eng.put(long_one, max_new_tokens=5),
            eng.put(short, max_new_tokens=4)]
    # two prompts behind them keep the steps mixed while they decode
    uids += [eng.put(p, max_new_tokens=2) for p in fillers]
    out = eng.generate_all(burst=1)
    tap.remove()
    got = tap.finished(0.0, float("inf"), want=3)
    assert [(t["prompt"], t["long"], len(t["rows"])) for t in got] == [
        (70, True, 5), (30, False, 4)] and tap.decode_steps == 0
    assert got[0]["tokens"] == out[uids[0]][:-1]
    for t in got:
        errs, state = sequence_errors(params, model, t, pad=64)
        assert errs.max() < TOL and state.max() < 1e-5
        errs, state = sequence_errors(params, model, t, pad=64,
                                      faults={"no_D"})
        assert errs.max() > 0.1


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


@pytest.mark.parametrize("over, name", refusal_cases(
    programs.STATE, tfm.get_config("tiny-jamba2"), v2_config()))
def test_refused_with_state_layers(tiny, over, name):
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
        assert_step_attrs(steps, "state")
        names = {s.name for s in tracer.spans()}
    finally:
        tracer.enabled = was
    row = 2 * cfg.layers_of("S") * 16 * 128 * 4
    assert [(a["kind"], a["ssm_tokens"], a["state_rows_started"],
             a["state_slots_used"], a["ssm_state_bytes"]) for a in steps] == [
        ("mixed", 32, 1, 1, row), ("mixed", 8, 0, 1, row),
        ("decode", 1, 0, 1, row), ("decode", 1, 0, 1, row)]
    # the scan's pieces by ITS chunk: the segments a row makes with the
    # batch's blocks of 128 tokens
    assert [(a["ssm_scan_rows"], a["ssm_scan_tokens"], a["ssm_scan_pieces"])
            for a in steps[:2]] == [(1, 32, 1), (1, 8, 1)]
    assert steps[0]["kv_blocks_used"] >= 5 and "kv_blocks_used" in steps[-1]
    assert {"kernel/selective_decode_update", "kernel/selective_scan"} \
        <= names


def _operands(T, di, N, S1, seed=5):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (T, di))
    delta = jax.nn.softplus(jax.random.normal(k[1], (T, di)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (N, di), minval=0.0, maxval=2.5))
    B = jax.random.normal(k[3], (T, N))
    C = jax.random.normal(k[4], (T, N))
    D = jax.random.normal(k[5], (di,))
    ssm0 = jax.random.normal(k[6], (2, S1, N, di))
    return x, delta, A, B, C, D, ssm0


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_scan_against_the_recurrence(form):
    """(c) the selective scan, its XLA formulation and the Pallas kernel in
    interpret mode, against the recurrence a token at a time: rows of 1, 127,
    128, 129 and 300 tokens in one call (no multiple of the block of 128, and
    cut by its edges anywhere), each from the state of its slot (one starts a
    sequence: zeros whatever the slot holds)."""
    N, di = 16, 256
    lens = np.array([1, 127, 128, 129, 300, 0], np.int32)
    T = int(lens.sum()) + 11  # padding tokens behind the rows
    x, delta, A, B, C, D, ssm0 = _operands(T, di, N, 7)
    slots = jnp.asarray([4, 0, 5, 2, 1, 6], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False, False])
    starts = jnp.asarray(np.cumsum(lens) - lens, jnp.int32)
    scanned = jnp.asarray(lens >= 2)
    walk = ss._scan_xla if form == "xla" else \
        lambda *a: ss._scan_pallas(*a, interpret=True)
    y, new = jax.jit(walk)(ssm0, jnp.int32(1), delta, delta * x, B, C, A,
                           starts, jnp.asarray(lens), slots, fresh, scanned)
    for r in range(1, 5):
        s, n = int(starts[r]), int(lens[r])
        first = jnp.zeros((N, di)) if bool(fresh[r]) \
            else ssm0[1, int(slots[r])]
        want, state = ss.selective_recurrence(
            x[s:s + n], delta[s:s + n], A, B[s:s + n], C[s:s + n],
            jnp.zeros_like(D), first)
        np.testing.assert_allclose(y[s:s + n], want, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(new[1, int(slots[r])], state, rtol=2e-4,
                                   atol=2e-4)
    # untouched: the other layer, the slots of rows not scanned
    np.testing.assert_array_equal(new[0], ssm0[0])
    np.testing.assert_array_equal(new[1, [3, 4, 6]], ssm0[1, [3, 4, 6]])


def test_scan_entry_point_is_zero_outside_its_rows():
    N, di = 16, 128
    lens = np.array([1, 40, 0], np.int32)
    x, delta, A, B, C, D, ssm0 = _operands(48, di, N, 4)
    starts = jnp.asarray(np.cumsum(lens) - lens, jnp.int32)
    y, _ = ss.selective_scan(
        ssm0, jnp.int32(0), x, delta, A, B, C, D, starts, jnp.asarray(lens),
        jnp.asarray([0, 1, 3]), jnp.zeros((3,), bool), jnp.asarray(lens >= 2))
    want, _ = ss.selective_recurrence(x[1:41], delta[1:41], A, B[1:41],
                                      C[1:41], D, ssm0[0, 1])
    np.testing.assert_allclose(y[1:41], want, rtol=2e-4, atol=2e-4)
    assert not np.asarray(y[:1]).any() and not np.asarray(y[41:]).any()


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_decode_update_against_the_recurrence(form):
    """(c) one token a slot: the XLA formulation and the Pallas kernel in
    interpret mode against the recurrence; an inactive slot keeps its state,
    a fresh one starts from zeros."""
    N, di, S1 = 16, 256, 5
    x, delta, A, B, C, D, ssm0 = _operands(S1, di, N, S1, seed=8)
    active = jnp.asarray([True, False, True, True, False])
    fresh = jnp.asarray([False, False, True, False, False])
    step = ss._decode_update_xla if form == "xla" else \
        lambda *a: ss._decode_pallas(*a, interpret=True)
    y, new = step(ssm0, jnp.int32(1), delta, delta * x, B, C, A, active,
                  fresh)
    for r in range(S1):
        first = jnp.zeros((N, di)) if bool(fresh[r]) else ssm0[1, r]
        want, state = ss.selective_recurrence(
            x[r:r + 1], delta[r:r + 1], A, B[r:r + 1], C[r:r + 1],
            jnp.zeros_like(D), first)
        if bool(active[r]):
            np.testing.assert_allclose(y[r], want[0], rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(new[1, r], state, rtol=2e-5, atol=2e-5)
        else:
            np.testing.assert_array_equal(new[1, r], ssm0[1, r])
    np.testing.assert_array_equal(new[0], ssm0[0])


def test_segments_cover_each_scanned_row_once():
    """The walk's table: a row's segments tile it, the first reads its slot
    (or starts from zeros), the last writes it, and the segments past the
    walk's end are empty."""
    lens = np.array([1, 127, 128, 129, 300, 0], np.int32)
    starts = np.cumsum(lens) - lens
    G = 6 + 6
    meta = np.asarray(jax.jit(ss._segments, static_argnums=(0, 1))(
        6, G, jnp.asarray(starts), jnp.asarray(lens),
        jnp.arange(6, dtype=jnp.int32),
        jnp.asarray([False, False, True, False, False, False]),
        jnp.asarray(lens >= 2), jnp.int32(3)))
    blk, t0, t1, mode, store, slot, new = meta[:7 * G].reshape(7, G)
    assert meta[-1] == 3
    live = t1 > t0
    assert int(live.sum()) == int(ss.scan_pieces(starts, lens)[lens >= 2]
                                  .sum()) == 7
    covered = collections.Counter()
    for g in np.nonzero(live)[0]:
        covered[int(slot[g])] += int(t1[g] - t0[g])
    assert covered == {1: 127, 2: 128, 3: 129, 4: 300}
    assert [int(m) for m in mode[live]] == [1, 2, 1, 0, 1, 0, 0]
    assert int(store[live].sum()) == 4 and not store[~live].any()
    assert (np.diff(blk) >= 0).all() and (blk[~live] == blk[live][-1]).all()
