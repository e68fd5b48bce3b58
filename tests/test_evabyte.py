"""EvaByte's block served (``models/eva.py``, ``programs.EVA``,
``ops/pallas/eva_attention.py``) against the plain reference
(``benchmark/reference/eva_byte_decoder.py``) at toy widths: 2 layers,
hidden 64, 4 heads of 16, a window of 32 in chunks of 4, blocks of 8, two
output heads.  The reference against itself first (the ``C`` = 1 identity,
every fault switch); then the engine's own step-program logits, all heads,
at every position, through chunked prefill and two more window closes; the
three kernels alone at the published head shape; the two pools' manager; what
the kind is refused and what its steps count."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers import serve_eva as drv
from benchmark.logit_tap import LogitTap
from benchmark.reference import dense_decoder
from benchmark.reference import eva_byte_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import (AdmissionError,
                                               InferenceEngineV2, V2Config)
from deepspeed_tpu.inference.v2.ragged import (KVCacheManager,
                                               SequenceDescriptor)
from deepspeed_tpu.models import eva
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.ops.pallas import eva_attention as ea

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from served_kinds import assert_step_attrs, refusal_cases  # noqa: E402

#: float32 on the CPU: the engine and the reference differ by the order of
#: their sums alone (measured 4e-6 on logits of magnitude 4); a fault has to
#: move a logit by a thousand times that to count as seen
TOL = 2e-4
SEEN = 0.02


def v2_config(**over):
    return V2Config(**{**dict(max_tokens_per_step=24, max_seqs=4,
                              block_size=8, num_blocks=17,
                              max_blocks_per_seq=16, dtype="float32"),
                       **over})


@pytest.fixture(scope="module")
def tiny():
    cfg = tfm.get_config("tiny-evabyte", dtype="float32",
                         param_dtype="float32")
    params = drv.draw_norm_offsets(
        tfm.init_params(jax.random.PRNGKey(0), cfg), 5)
    return cfg, params, drv.published_model(cfg)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(1, 320, size=n).tolist()


# -- the reference against itself -------------------------------------------


def test_chunks_of_one_are_full_attention(tiny):
    """With ``C`` = 1 a chunk's summary is its token (``a`` = 1; ``mu`` = 0
    here), so EVA is full causal attention and the reference equals
    ``dense_decoder.py`` on the same weights (whose norm has no unit offset:
    it is handed ``1 + g``)."""
    cfg, params, model = tiny
    params = jax.tree.map(lambda a: a, params)
    params["layers"]["attn"]["eva_mu"] = jnp.zeros_like(
        params["layers"]["attn"]["eva_mu"])
    tokens = jnp.asarray(_tokens(96), jnp.int32)
    got = reference.logits(params, {**model, "chunk_size": 1}, tokens)
    dense = jax.tree.map(lambda a: a, params)
    for norm in (dense["layers"]["ln1"], dense["layers"]["ln2"],
                 dense["final_norm"]):
        norm["scale"] = 1.0 + norm["scale"]
    want = dense_decoder.logits(
        dense, {**model, "num_key_value_heads": cfg.num_heads}, tokens)
    assert float(jnp.abs(got - want).max()) < TOL


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_every_fault_switch_moves_the_logits(tiny, fault):
    _, params, model = tiny
    tokens = jnp.asarray(_tokens(128, 1), jnp.int32)
    right = reference.logits(params, model, tokens)
    wrong = reference.logits(params, model, tokens, faults=(fault,))
    # behind the first window: inside it no summary exists yet
    assert float(jnp.abs(right - wrong)[40:].max()) > SEEN, fault


def test_the_stream_s_rounding_is_no_fault(tiny):
    """(f) ``fp32_skip_add`` as the program runs it, a bfloat16 stream,
    moves the logits by bfloat16's own rounding and no more."""
    _, params, model = tiny
    tokens = jnp.asarray(_tokens(96, 2), jnp.int32)
    moved = jnp.abs(reference.logits(params, model, tokens)
                    - reference.logits(params, model, tokens,
                                       faults=reference.ROUNDINGS)).max()
    assert 0 < float(moved) < 0.1


# -- the system against the reference ---------------------------------------

#: four rows of different lengths in one batch.  Chunks of 24 (less the
#: decode rows riding along) end on the window's edge of 32 only where the
#: scheduler cuts them there; 64 and 96 are edges a prompt ends ON; every
#: row then decodes through two more closes or starts inside its first
ROWS = ((70, 40), (64, 40), (9, 60), (50, 14))


def _serve_tapped(cfg, params, rows=ROWS, **over):
    engine = InferenceEngineV2(cfg, params, v2_config(**over))
    tap = LogitTap(engine)
    prompts = [_tokens(n, 10 + i) for i, (n, _) in enumerate(rows)]
    uids = [engine.put(p, max_new_tokens=m)
            for p, (_, m) in zip(prompts, rows)]
    out = engine.generate_all(burst=1)
    tap.remove()
    assert engine.drained()
    for m in engine._managers:
        m.check_consistency()
    return [(p, out[u][len(p):], tap.logits[u])
            for p, u in zip(prompts, uids)]


@pytest.mark.parametrize("kernels", ["pallas", "xla"])
def test_engine_matches_reference(tiny, monkeypatch, kernels):
    """Prefill in chunks, then decode through at least two more window
    closes: the logits of BOTH heads at every tapped position.  ``pallas``:
    the three kernels in interpret mode; ``xla``: their twins."""
    cfg, params, model = tiny
    if kernels == "xla":
        monkeypatch.setattr(backend, "interpret", lambda: False)
    tapped = _serve_tapped(cfg, params)
    errs = drv.row_errors(params, model, tapped)
    assert [len(e) for e in errs] == [m for _, m in ROWS]
    assert max(map(max, errs)) < TOL, [max(e) for e in errs]
    closes = [(len(p) + len(t)) // cfg.eva_window - len(p) // cfg.eva_window
              for p, t, _ in tapped]
    assert max(closes) >= 2, closes
    # and each reading of the mathematics the other way is seen
    for fault in reference.FAULTS:
        wrong = drv.row_errors(params, model, tapped[:1], faults=(fault,))
        assert max(wrong[0]) > SEEN, fault


def test_eight_heads_are_compared_and_head_0_is_drawn(tiny):
    cfg, params, model = tiny
    (prompt, served, rows), = _serve_tapped(cfg, params, rows=((40, 8),))
    assert rows[0][1].shape == (cfg.num_pred_heads * cfg.vocab_size,)
    want = np.asarray(reference.logits(
        params, model, jnp.asarray(prompt + served, jnp.int32)))
    for (pos, _), byte in zip(rows, served):
        assert byte == int(want[pos, :cfg.vocab_size].argmax())


# -- the kernels alone, at the published head shape --------------------------

H, D, W, C, BS = 32, 128, 2048, 16, 64


def _pools(rows, lengths, seed=0):
    """Both pools filled for ``rows`` sequences of ``lengths`` tokens: one
    layer; tables that scatter the blocks; → (pools, tables, plain K and V by
    position, the summaries by entry)."""
    rng = np.random.default_rng(seed)
    nb, per = W // BS, W // C
    n_win, n_sum = rows * nb + 1, rows * 4 + 1
    k_win, v_win = (jnp.asarray(rng.normal(size=(1, n_win, BS, H, D)),
                                jnp.float32) for _ in range(2))
    k_sum, v_sum = (jnp.asarray(rng.normal(size=(1, n_sum, BS, H, D)),
                                jnp.float32) for _ in range(2))
    order = rng.permutation(n_win - 1).reshape(rows, nb)
    win_t = np.zeros((rows, 256), np.int32)
    sum_t = np.zeros((rows, 256), np.int32)
    sum_order = rng.permutation(n_sum - 1).reshape(rows, 4)
    for r, n in enumerate(lengths):
        w = max(n - 1, 0) // W
        win_t[r, w * nb:(w + 1) * nb] = order[r]
        sum_t[r, :4] = sum_order[r]
    return (k_win, v_win, k_sum, v_sum), jnp.asarray(win_t), \
        jnp.asarray(sum_t), per


def _plain_attention(q, pools, win_t, sum_t, row, pos):
    """One query at ``pos`` of ``row``, plain ``jnp``."""
    k_win, v_win, k_sum, v_sum = (np.asarray(p[0]) for p in pools)
    w, nb = pos // W, W // BS
    blocks = np.asarray(win_t)[row, w * nb:(w + 1) * nb]
    k = k_win[blocks].reshape(W, H, D)[:pos % W + 1]
    v = v_win[blocks].reshape(W, H, D)[:pos % W + 1]
    n_sum = w * (W // C)
    sb = np.asarray(sum_t)[row, :-(-n_sum // BS)] if n_sum else []
    if n_sum:
        k = np.concatenate([k, k_sum[sb].reshape(-1, H, D)[:n_sum]])
        v = np.concatenate([v, v_sum[sb].reshape(-1, H, D)[:n_sum]])
    s = np.einsum("hd,khd->hk", q, k) / np.sqrt(D)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True), v)


def test_decode_attention_alone():
    """Three rows: inside the first window (no summary), deep in the third
    (256 summaries), and one that is not active."""
    positions = [700, 2 * W + 1300, 5]
    pools, win_t, sum_t, _ = _pools(3, [p + 1 for p in positions])
    q = jnp.asarray(np.random.default_rng(1).normal(size=(3, H, D)),
                    jnp.float32)
    got = np.asarray(ea.eva_decode_attention(
        q, *pools, jnp.int32(0), win_t, sum_t, jnp.asarray(positions),
        jnp.asarray([True, True, False]), window=W, chunk=C))
    for r in (0, 1):
        want = _plain_attention(np.asarray(q[r]), pools, win_t, sum_t, r,
                                positions[r])
        assert np.abs(got[r] - want).max() < 1e-4
    assert not got[2].any()


def test_prefill_attention_alone():
    """A chunk of 200 that ends on its window's edge behind one closed
    window, a decode row riding along, and a row without tokens."""
    starts, lens = [2 * W - 200, 30, 0], [200, 1, 0]
    pools, win_t, sum_t, _ = _pools(3, [s + n for s, n in zip(starts, lens)])
    T = 256
    q = jnp.asarray(np.random.default_rng(2).normal(size=(T, H, D)),
                    jnp.float32)
    q_start = jnp.asarray([0, 200, 201])
    got = np.asarray(ea.eva_prefill_attention(
        q, *pools, jnp.int32(0), win_t, sum_t, q_start, jnp.asarray(starts),
        jnp.asarray(lens), window=W, chunk=C))
    for tok, row, pos in ((0, 0, starts[0]), (137, 0, starts[0] + 137),
                          (199, 0, 2 * W - 1), (200, 1, 30)):
        want = _plain_attention(np.asarray(q[tok]), pools, win_t, sum_t, row,
                                pos)
        assert np.abs(got[tok] - want).max() < 1e-4, tok
    assert not got[201:].any()


def test_summarizer_alone():
    """Two rows close their second and first window, one closes nothing:
    128 summaries a head a row, written where the summary table says, and
    nothing else of the pool touched."""
    pools, win_t, sum_t, per = _pools(3, [2 * W, W, 77], seed=3)
    rng = np.random.default_rng(4)
    phi, mu = (jnp.asarray(rng.normal(size=(H, D)) / np.sqrt(D), jnp.float32)
               for _ in range(2))
    closing = jnp.asarray([1, 0, -1])
    k_new, v_new = ea.eva_summarize(*pools, jnp.int32(0), win_t, sum_t,
                                    closing, phi, mu, window=W, chunk=C)
    nb, sb = W // BS, per // BS
    touched = []
    for r, w in ((0, 1), (1, 0)):
        blocks = np.asarray(win_t)[r, w * nb:(w + 1) * nb]
        ks, vs = eva.summarize(pools[0][0, blocks].reshape(W, H, D),
                               pools[1][0, blocks].reshape(W, H, D),
                               phi, mu, C)
        out = np.asarray(sum_t)[r, w * sb:(w + 1) * sb]
        touched += out.tolist()
        assert np.abs(np.asarray(k_new[0, out]).reshape(per, H, D)
                      - np.asarray(ks)).max() < 1e-5
        assert np.abs(np.asarray(v_new[0, out]).reshape(per, H, D)
                      - np.asarray(vs)).max() < 1e-5
    rest = np.setdiff1d(np.arange(pools[2].shape[1] - 1), touched)
    assert np.array_equal(np.asarray(k_new[0, rest]),
                          np.asarray(pools[2][0, rest]))


def test_pools_need_whole_blocks():
    with pytest.raises(ValueError, match="whole blocks"):
        ea.check_geometry(32, 4, 12)
    with pytest.raises(ValueError, match="whole blocks"):
        programs.EVA.arrays(tfm.get_config("tiny-evabyte"),
                            v2_config(block_size=16))  # 8 summaries a window


# -- the manager: a tumbling window beside a pool of summaries ---------------


def test_manager_follows_both_pools(tiny):
    """Step by step: a row never holds more window blocks than a window's
    (the scheduler ends its chunk at the edge), the summary chain is the
    closed windows' entries in blocks, both allocators stay consistent, and
    everything comes back."""
    cfg, params, _ = tiny
    engine = InferenceEngineV2(cfg, params, v2_config())
    W_, per, bs = cfg.eva_window, cfg.eva_window // cfg.eva_chunk, 8
    assert engine.kv_win.bound == W_ // bs and engine.kv_win.tumbling
    assert engine.kv.per_window == per
    for n, m in ROWS:
        engine.put(_tokens(n), max_new_tokens=m)
    seen_trim = False
    for _ in range(400):
        if not (engine.waiting or engine.running):
            break
        engine.step()
        engine._flush_table()
        # the step called ahead (ISSUE 54) has opened what its chunks write
        run, ahead = engine._ahead, {}
        if run is not None:
            ahead = ({seq.uid: n for seq, n in run.picks} if run.picks
                     else dict.fromkeys(run.uids.tolist(), 1))
        for seq in engine.running.values():
            held = len(seq.win_blocks) - seq.win_first_block
            assert held <= W_ // bs
            # what its tokens fill, or the next step's more: the block a
            # staged decode step opened, the chunk of a step called ahead
            at, n = seq.seen_tokens % W_, ahead.get(seq.uid, 1)
            assert held in (-(-at // bs), -(-(at + n) // bs)), (held, at, n)
            assert len(seq.blocks) in (
                engine.kv.blocks_for(seq.seen_tokens),
                engine.kv.blocks_for(seq.seen_tokens + n))
            assert engine.kv.blocks_for(seq.seen_tokens) == \
                -(-(seq.seen_tokens // W_ * per) // bs)
            seen_trim |= seq.win_first_block > 0
        for m in engine._managers:
            m.check_consistency()
    assert seen_trim and engine.drained()
    assert engine.kv_win.trimmed > 0 and engine.kv.reserved == 0


def test_admission_counts_both_pools(tiny):
    """A request is admitted against a whole window of the one pool and its
    closed windows' summaries of the other; what neither can promise is
    refused by name, and never scheduled half."""
    cfg, params, _ = tiny
    # the window pool holds two rows' windows, the summaries' one block
    engine = InferenceEngineV2(cfg, params, v2_config(
        num_window_blocks=9, num_blocks=3))
    assert engine.kv_win.reservation(100) == 4
    assert engine.kv.reservation(100) == 3  # 3 windows x 8 entries / 8
    engine.put(_tokens(60), max_new_tokens=10, strict=True)  # 2 + 4 blocks
    with pytest.raises(AdmissionError, match="needs 2 blocks, 0 unreserved"):
        engine.put(_tokens(60), max_new_tokens=10, strict=True)
    engine.put(_tokens(20), max_new_tokens=10, strict=True)  # no close
    with pytest.raises(AdmissionError,
                       match="needs 3 blocks of the window.*0 unreserved"):
        engine.put(_tokens(10), max_new_tokens=10, strict=True)
    engine.generate_all(burst=1)
    assert engine.drained()


def test_tumbling_manager_alone():
    m = KVCacheManager(8, 8, 16, window=32, chain="win_", tumbling=True)
    s = KVCacheManager(4, 8, 16, window=32, per_window=8)
    seq = SequenceDescriptor(uid=1, tokens=list(range(100)))
    assert m.chunk_cap(0) == 32 and m.chunk_cap(40) == 24
    assert s.chunk_cap(40) > 1000
    assert m.reserve(seq, 100, 24) and s.reserve(seq, 100, 24)
    assert (len(seq.win_blocks), len(seq.blocks)) == (3, 0)
    seq.seen_tokens = 24
    assert m.ensure_capacity(seq, 8) and s.ensure_capacity(seq, 8)
    assert (len(seq.win_blocks), len(seq.blocks)) == (4, 1)
    assert m.trim(seq, 31) == 0 and s.trim(seq, 32) == 0
    assert m.trim(seq, 32) == 4 and m.allocator.free_blocks == 8
    assert m.opens_at(np.array([31, 32, 33])).tolist() == [False, True,
                                                           False]
    assert s.opens_at(np.array([30, 31, 63])).tolist() == [False, True, True]
    assert m.trims_at(np.array([31, 32, 64])).tolist() == [False, True, True]
    m.release(seq), s.release(seq)
    assert m.drained() and s.drained() and m.reserved == s.reserved == 0


# -- the kind: what it is refused, what its steps count ----------------------


def test_kind_of_names_eva():
    for preset in ("tiny-evabyte", "evabyte-6.5b"):
        cfg = tfm.get_config(preset)
        assert programs.kind_of(cfg) is programs.EVA
        assert programs.EVA.closes(cfg) == cfg.eva_window // cfg.eva_chunk
    assert programs.EVA.counters in vars(InferenceEngineV2)
    assert abs(tfm.get_config("evabyte-6.5b").num_params() / 1e9 - 6.49) \
        < 0.01


@pytest.mark.parametrize("over,name", refusal_cases(
    programs.EVA, tfm.get_config("tiny-evabyte"), v2_config()))
def test_refused_with_eva_attention(tiny, over, name):
    cfg, params, _ = tiny
    with pytest.raises(ValueError, match=f"V2Config.*{name}.*EVA attention"):
        InferenceEngineV2(cfg, params, v2_config(**over))


def test_v1_engine_and_trainer_refuse_the_model(tiny):
    from deepspeed_tpu.inference.engine import InferenceEngine

    cfg, params, _ = tiny
    with pytest.raises(NotImplementedError, match="EVA attention"):
        InferenceEngine(model_config=cfg, params=params)
    with pytest.raises(NotImplementedError, match="EVA attention.*served"):
        tfm.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_step_spans_carry_the_counters(tiny):
    cfg, params, _ = tiny
    # sizes no other test builds: its step programs are traced HERE, and a
    # kernel leaves its ring event where it is traced
    engine = InferenceEngineV2(cfg, params, v2_config(num_blocks=19))
    engine.put(_tokens(40), max_new_tokens=30)
    tracer.clear()
    engine.generate_all(burst=1)
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    assert_step_attrs(steps, "eva")
    L, per = cfg.num_layers, cfg.eva_window // cfg.eva_chunk
    first, second = [a for a in steps if a["kind"] == "mixed"][:2]
    assert first["tokens"] == 24 and second["tokens"] == 8  # cut at the edge
    assert first["eva_window_keys"] == 24 * L
    assert first["eva_query_keys"] == sum(range(1, 25)) * L
    assert second["eva_windows_closed"] == 1
    assert second["eva_chunks_written"] == per
    assert second["eva_keys_full"] == 32 * L
    third = [a for a in steps if a["kind"] == "mixed"][2]  # tokens 32..39
    assert third["eva_window_keys"] == 8 * L
    assert third["eva_summary_keys"] == per * L
    assert third["eva_query_keys"] == (sum(range(1, 9)) + 8 * per) * L
    assert third["blocks_used_summary"] == 1
    assert third["blocks_used_window"] == 1  # the first window went back
    closing = [a for a in steps if a["kind"] == "decode"
               and a["eva_windows_closed"]]
    assert len(closing) == 1  # position 63
    assert closing[0]["eva_window_keys"] == 32 * L
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/eva_attention_tiles"]
    assert {e["kind"] for e in events} == {"decode", "prefill"}
    assert not any("fallback" in e for e in events)


def test_mlp_width_is_stored_padded():
    """11008 = 2^8 x 43 is stored as 11264 (``pad_mlp_width``), exactly; the
    widths the other cells serve are left alone."""
    from deepspeed_tpu.inference.quantization import (pad_mlp_width,
                                                      quantize_model_params)

    w = jnp.ones((2, 64, 11008), jnp.bfloat16)
    assert pad_mlp_width(w, "w_in").shape == (2, 64, 11264)
    assert pad_mlp_width(jnp.ones((11008, 64)), "w_out").shape == (11264, 64)
    for width in (14336, 128, 1024, 2048, 12288, 4096, 11264):
        assert pad_mlp_width(jnp.ones((8, width)), "w_in").shape == (8, width)
    cfg = dataclasses.replace(tfm.get_config("tiny-evabyte"),
                              hidden_size=128, intermediate_size=11008,
                              num_layers=1, num_heads=1)
    q = quantize_model_params(tfm.init_params(jax.random.PRNGKey(0), cfg),
                              bits=8, group=128)["layers"]
    assert q["mlp"]["w_in"].codes.shape == (1, 128, 11264)
    assert q["mlp"]["w_out"].codes.shape == (1, 11264, 128)
    assert not hasattr(q["attn"]["eva_phi"], "codes")
