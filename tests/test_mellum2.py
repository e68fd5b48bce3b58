"""Mellum2-shaped models (window and global attention layers in one model,
YaRN on the global layers only, renormalised top-k gates) against the plain
reference ``benchmark/reference/swa_moe_decoder.py``, and the window's two
mechanisms (the kernels' band, the cache manager's freed blocks) one by one.
Logits are compared, never tokens.

Tolerances, and why:

* ``F32_TOL`` 2e-4 on logits of magnitude 3-4: program and reference both
  compute in float32, in different orders (measured here: 3e-6 and under).
  What a wrong program moves a logit by is measured in
  ``test_wrong_programs_are_seen``: from 0.9 (the attention factor left out)
  to 3.5 (YaRN on every layer), four orders of magnitude over the tolerance.
* ``INT8_TOL`` 0.03 on the MEDIAN row, int8 experts (attention stays
  float32, ``test_olmoe``'s rule and reason): the grouped W8A16 kernel feeds
  the MXU bfloat16, the reference multiplies the same codes in float32; why
  the median, ``test_engine_matches_reference_int8_experts`` says.
* Router ties are found by the reference's own margin and skipped with every
  later position of their sequence, as in ``test_olmoe``; the number skipped
  is bounded in each test and the tolerance of what is compared is not
  widened.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.drivers.serve_swa_moe import published_model as model_of
from benchmark.logit_tap import LogitTap
from benchmark.reference import dense_decoder, swa_moe_decoder as ref
from deepspeed_tpu.inference.quantization import quantize_model_params
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.inference.v2.ragged import window_bound
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import paged_attention as pa
from served_kinds import assert_step_attrs, refusal_cases
from deepspeed_tpu.ops.pallas.grouped_mixed_gemm import pick_grouped_tiles
from deepspeed_tpu.ops.pallas.mixed_gemm import (pick_gemm_tiles,
                                                 quantize_gemm_weight)

F32_TOL = 2e-4
INT8_TOL = 0.03
F32_MARGIN = 1e-5

def mellum_cfg(**over) -> tfm.TransformerConfig:
    """Preset ``tiny-mellum2`` (S S S F twice, window 8, YaRN whose ramp lies
    inside 64 positions, 8 experts top 2 renormalised) in float32."""
    return tfm.get_config("tiny-mellum2", dtype="float32",
                          param_dtype="float32", **over)


def make_params(cfg, seed: int = 0):
    return tfm.init_params(jax.random.PRNGKey(seed), cfg)


def compared(rows, ref_logits, margin, min_margin):
    """``test_olmoe.compared``: → (largest |difference| over the rows
    compared, rows skipped from the sequence's first router tie on)."""
    tied = np.nonzero(np.asarray(margin) < min_margin)[0]
    first_tie = int(tied[0]) if len(tied) else len(margin)
    worst, skipped = 0.0, 0
    for pos, row in rows:
        if pos >= first_tie:
            skipped += 1
            continue
        worst = max(worst, float(np.abs(row - np.asarray(ref_logits[pos])
                                        ).max()))
    return worst, skipped


# -- (a) the model definition ------------------------------------------------


def test_forward_matches_reference():
    """(a) ``tfm.forward`` is the model the reference describes: 72
    positions are nine windows, and YaRN's ramp (frequencies 0 to 2 of 16)
    lies inside them."""
    cfg = mellum_cfg()
    assert cfg.layer_period == ("sliding", "sliding", "sliding", "full")
    params = make_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 72), 1,
                                cfg.vocab_size)
    got = tfm.forward(params, tokens, cfg)
    skipped = 0
    for b in range(2):
        want, margin = ref.logits_and_margin(params, model_of(cfg), tokens[b])
        worst, s = compared(list(enumerate(np.asarray(got[b]))), want, margin,
                            F32_MARGIN)
        assert worst < F32_TOL
        skipped += s
    assert skipped <= 6  # of 144 positions


def test_yarn_is_the_published_formula():
    """Mellum2's own numbers: the ramp runs from frequency 18 to 35 of 64,
    below it the frequencies are RoPE's, above it a sixteenth."""
    cfg = tfm.get_config("mellum2-12b-a2.5b")
    got = np.asarray(tfm.yarn_inv_freq(128, cfg.rope_of("full")))
    plain = 500000.0 ** (-2.0 * np.arange(64) / 128)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(np.diff(got / plain) <= 1e-7)  # the ramp only falls
    want, scale = ref.inv_freq(model_of(cfg)["rope_parameters"][
        "full_attention"], 128)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6)
    assert scale == 1.2772588722239782
    assert cfg.rope_of("sliding") == tfm.RopeParams(theta=500000.0)
    assert abs(cfg.num_params() / 1e9 - 12.15) < 0.01


#: (e) the wrong programs, each a configuration the engine would serve
WRONG = {
    "yarn-on-every-layer": lambda c: dataclasses.replace(
        c, rope_params=c.rope_params + (("sliding", c.rope_of("full")),)),
    "yarn-on-none": lambda c: dataclasses.replace(c, rope_params=()),
    "no-attention-factor": lambda c: dataclasses.replace(
        c, rope_params=(("full", dataclasses.replace(
            c.rope_of("full"), attention_factor=1.0)),)),
    "window-off-by-one": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window + 1),
    "window-on-global-layers": lambda c: dataclasses.replace(
        c, layer_types=("sliding",)),
}


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_wrong_programs_are_seen(wrong):
    """(e) The comparison has teeth.  Measured |logit difference| of each
    wrong program against the right reference (the right program: 4e-6):
    YaRN on every layer 3.5, on none 1.3, the attention factor left out
    0.92, the window off by one 3.2, a window on the global layers 2.9."""
    cfg = mellum_cfg()
    params = make_params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 72), 1,
                                cfg.vocab_size)
    want = ref.logits(params, model_of(cfg), tokens[0])
    got = tfm.forward(params, tokens, WRONG[wrong](cfg))[0]
    assert float(jnp.abs(got - want).max()) > 0.5


# -- (b), (c), (d) the engine ------------------------------------------------

PROMPTS = (37, 29, 50, 26)  # all longer than three windows of 8


def v2_config(**over) -> V2Config:
    """Blocks of 4 under a window of 8 and chunks of 16: a chunk ends inside
    a block, and with 37, 29, 50 and 26 tokens no prompt ends on one."""
    kw = dict(max_tokens_per_step=16, max_seqs=4, block_size=4,
              num_blocks=96, max_blocks_per_seq=24, dtype="float32")
    kw.update(over)
    return V2Config(**kw)


def serve(cfg, params, prompts, new_tokens, v2=None, each_step=None):
    """Drive the v2 engine step by step to the end → (engine, the tap's
    logits by uid, tokens by uid, uids in the prompts' order)."""
    engine = InferenceEngineV2(cfg, params, v2 or v2_config())
    tap = LogitTap(engine)
    uids = [engine.put(p, max_new_tokens=new_tokens) for p in prompts]
    seqs = {s.uid: s for s in engine.waiting}
    while engine.waiting or engine.running:
        engine.step()
        if each_step:
            each_step(engine)
    engine._flush_table()
    out = {u: seqs[u].tokens[len(p):] for p, u in zip(prompts, uids)}
    return engine, tap.logits, out, uids


def check_rows(cfg, params, prompts, logits, out, uids, tol, min_margin,
               new_tokens):
    rows = skipped = 0
    for uid, prompt in zip(uids, prompts):
        assert len(logits[uid]) == new_tokens
        seq = jnp.asarray(prompt + out[uid])
        want, margin = ref.logits_and_margin(params, model_of(cfg), seq)
        worst, s = compared(logits[uid], want, margin, min_margin)
        assert worst < tol, (uid, worst)
        rows += len(logits[uid])
        skipped += s
    return rows, skipped


def test_engine_matches_reference():
    """(b) Four sequences of different lengths in one batch, every prompt
    longer than three windows, prefilled in chunks whose ends fall inside
    blocks and inside windows, then 24 decode steps (three windows, six
    blocks: every row frees window blocks at least five times): each step's
    logits against the reference's one uncached pass."""
    cfg = mellum_cfg()
    params = make_params(cfg)
    tracer.clear()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in PROMPTS]
    engine, logits, out, uids = serve(cfg, params, prompts, new_tokens=25)
    assert engine.kv_win is not None and engine.free_blocks == \
        engine.total_blocks
    # a row gives a block back every 4 tokens it moves past the window
    assert engine.kv_win.trimmed >= sum((n + 24 - 8) // 4 for n in PROMPTS)
    steps = [s.attrs for s in tracer.spans() if s.name == "engine/step"]
    assert_step_attrs(steps, "moe", "window")
    assert all(a["kv_blocks_read"] < a["kv_blocks_full"] for a in steps[3:])
    assert all(a["blocks_used_window"] <= 4 * engine.kv_win.bound
               for a in steps)
    assert sum(a["window_blocks_freed"] for a in steps) == \
        engine.kv_win.trimmed
    # each kernel's own event of its windowed calls: the prefill kernel's
    # beside its tiles', the decode kernel's in its tiles' (global layers
    # leave one of window 0)
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/paged_attention_window"
              or s.name == "kernel/paged_attention_decode_tiles"
              and s.attrs["window"]]
    assert {a.get("kind", "decode") for a in events} == {"decode", "prefill"}
    assert all(a["window"] == 8 and "fallback" not in a for a in events)
    rows, skipped = check_rows(cfg, params, prompts, logits, out, uids,
                               F32_TOL, F32_MARGIN, 25)
    assert rows == 100 and skipped <= rows // 4


def test_engine_matches_reference_int8_experts():
    """(c) The same through W8A16 experts (group 128, the grouped kernel in
    interpret mode, none fallen back), the reference reading the same codes;
    one period of the pattern, four layers.  The kernel feeds the MXU
    bfloat16, which moves the next layer's router by about a hundredth of a
    probability: with 8 experts and top 2 that flips a position's experts
    every few dozen positions (seen at reference margins up to 5e-3; one
    swap of two is half of an FFN's output, 0.7-0.9 on that row's logits,
    fading behind it), so a row cannot be held to a bound here as it is in
    float32 above and, at 64 experts and top 8, on the chip.  The median row
    is (measured 0.012-0.02 in rows without a flip; the smallest wrong
    program of ``test_wrong_programs_are_seen`` moves every row by 0.9), and
    at most a quarter of the rows may lie over 0.1."""
    cfg = mellum_cfg(num_layers=4)
    params = make_params(cfg)
    params["layers"]["moe"] = quantize_model_params(
        {"moe": params["layers"]["moe"]}, bits=8, group=128)["moe"]
    tracer.clear()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in PROMPTS]
    engine, logits, out, uids = serve(cfg, params, prompts, new_tokens=25)
    assert engine.free_blocks == engine.total_blocks
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert events and not any("fallback" in a for a in events)
    errs = []
    for uid, prompt in zip(uids, prompts):
        want = np.asarray(ref.logits(params, model_of(cfg),
                                     jnp.asarray(prompt + out[uid])))
        errs += [float(np.abs(row - want[pos]).max())
                 for pos, row in logits[uid]]
    errs = np.asarray(errs)
    assert len(errs) == 100 and np.median(errs) < INT8_TOL, np.median(errs)
    assert (errs > 0.1).sum() <= 25, np.sort(errs)[-30:]


def test_window_blocks_are_freed_and_reused():
    """(d) Two rows, four requests and a window pool of exactly two rows'
    bound: after every step a row's live window blocks stay within the
    bound and both allocators are consistent; the second pair of requests
    is served out of blocks the first pair gave back (ids seen freed behind
    a window are handed out again, to another sequence, while the first
    still runs), and every logit still agrees with the reference: a kernel
    that read a freed block, or a table that kept one, fails here.  Both
    pools are whole after the drain."""
    cfg = mellum_cfg()
    params = make_params(cfg)
    bound = window_bound(8, 16, 4, 24)
    assert bound == 7  # 8 + 16 - 1 positions lie in at most 7 blocks of 4
    v2 = v2_config(max_seqs=2, num_window_blocks=2 * bound + 1)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (41, 30, 27, 45)]
    seen = {"freed": set(), "reused": 0, "held": {}}

    def each_step(engine):
        m = engine.kv_win
        for a in (engine.kv.allocator, m.allocator):
            a.check_consistency()
        now = {}
        for seq in engine.running.values():
            live = seq.win_blocks[seq.win_first_block:]
            assert len(live) <= bound
            now.update({b: seq.uid for b in live})
            seen["freed"].update(seq.win_blocks[:seq.win_first_block])
        assert len(now) == m.allocator.num_blocks - m.allocator.free_blocks
        seen["reused"] += sum(1 for b, uid in now.items()
                              if b in seen["freed"]
                              and seen["held"].get(b, uid) != uid)
        seen["held"].update(now)

    engine, logits, out, uids = serve(cfg, params, prompts, new_tokens=20,
                                      v2=v2, each_step=each_step)
    assert seen["reused"] > 0
    assert engine.kv.allocator.free_blocks == engine.kv.allocator.num_blocks
    assert engine.kv_win.allocator.free_blocks == 2 * bound
    assert engine.kv_win.reserved == 0
    rows, skipped = check_rows(cfg, params, prompts, logits, out, uids,
                               F32_TOL, F32_MARGIN, 20)
    assert rows == 80 and skipped <= 20


def test_admission_counts_both_pools():
    """A strict ``put`` is refused when the WINDOW pool cannot promise the
    request its bound, though the global pool has room; without the request
    before it, it is admitted."""
    from deepspeed_tpu.inference.v2.engine import AdmissionError

    cfg = mellum_cfg()
    engine = InferenceEngineV2(cfg, make_params(cfg), v2_config(
        max_seqs=4, num_window_blocks=7 + 3 + 1))
    assert engine.total_blocks == 95 + 10
    engine.put(list(range(1, 40)), max_new_tokens=8, strict=True)  # bound 7
    with pytest.raises(AdmissionError, match="window layers"):
        engine.put(list(range(1, 40)), max_new_tokens=8, strict=True)
    engine.put(list(range(1, 6)), max_new_tokens=4, strict=True)  # 3 blocks
    engine.generate_all()
    assert engine.free_blocks == engine.total_blocks


# -- (f) the kernels ---------------------------------------------------------


def _paged(rng, lens, bs, kv, d, layers=2):
    """Random pools with each row's blocks scattered over them; one block
    is all NaN, and every table entry a caller marks dead points at it."""
    nblk = [-(-n // bs) for n in lens]
    ids = rng.permutation(sum(nblk) + 1)
    poison = int(ids[-1])
    tables = np.zeros((len(lens), max(nblk) + 1), np.int32)
    at = 0
    for r, n in enumerate(nblk):
        tables[r, :n] = ids[at:at + n]
        at += n
    shape = (layers, sum(nblk) + 2, bs, kv, d)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    return k, v, tables, poison


def _dense(q, k, v, tables, row, q_pos, window, bs):
    """Reference: the row's keys gathered in order, an explicit band mask."""
    n = int(q_pos.max()) + 1
    ks = k[tables[row, :-(-n // bs)]].reshape(-1, *k.shape[-2:])[:n]
    vs = v[tables[row, :-(-n // bs)]].reshape(-1, *v.shape[-2:])[:n]
    rep = q.shape[-2] // ks.shape[-2]
    ks, vs = np.repeat(ks, rep, 1), np.repeat(vs, rep, 1)
    s = np.einsum("qhd,thd->hqt", q, ks) / np.sqrt(q.shape[-1])
    j = np.arange(n)[None, :]
    seen = (j <= q_pos[:, None]) & (q_pos[:, None] - j < window)
    s = np.where(seen[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("hqt,thd->qhd", p / p.sum(-1, keepdims=True), vs)


@pytest.mark.parametrize("window", [8, 11, 16])
def test_kernels_read_the_band_only(window):
    """(f) Both kernels (interpret mode) and the three XLA paths against an
    explicit band mask, at windows that are (8, 16) and are not (11)
    multiples of the block of 8.  For the kernels every table entry behind a
    row's window points at a block of NaN: the block loop must start past
    it.  (The XLA paths gather every entry and mask, so their dead entries
    point at block 0, as the engine leaves them.)"""
    rng = np.random.default_rng(window)
    bs, kv, d, heads = 8, 2, 32, 4
    lens = [45, 9, 30]
    k, v, tables, poison = _paged(rng, lens, bs, kv, d)
    layer = 1
    # decode: one query a row at its last position
    q = rng.standard_normal((3, heads, d)).astype(np.float32)
    ctx = np.asarray(lens, np.int32)
    dead = tables.copy()
    for r, n in enumerate(lens):
        dead[r, :max(n - window, 0) // bs] = poison
    k_nan, v_nan = k.copy(), v.copy()
    k_nan[:, poison], v_nan[:, poison] = np.nan, np.nan
    want = np.stack([_dense(q[r][None], k[layer], v[layer], tables, r,
                            np.asarray([n - 1]), window, bs)[0]
                     for r, n in enumerate(lens)])
    got = pa.paged_decode_attention(q, k_nan, v_nan, layer, dead, ctx,
                                    window=window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    got = pa._decode_attention_xla(*map(jnp.asarray, (q, k, v, layer, tables,
                                                        ctx)), window)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)

    # prefill: a chunk of 13 queries a row ending at the row's length, the
    # rows' queries flat: row r from token 16 * r on (3 tokens of gap a row)
    qp = 16
    start = np.asarray([n - min(n, 13) for n in lens], np.int32)
    clen = ctx - start
    q_start = np.arange(3, dtype=np.int32) * qp
    qs = rng.standard_normal((3, qp, heads, d)).astype(np.float32)
    dead = tables.copy()
    for r in range(3):  # behind the chunk's oldest query's window
        dead[r, :max(int(start[r]) - window + 1, 0) // bs] = poison
    want = np.zeros_like(qs)
    for r in range(3):
        want[r, :clen[r]] = _dense(
            qs[r, :clen[r]], k[layer], v[layer], tables, r,
            start[r] + np.arange(clen[r]), window, bs)
    got = pa.paged_prefill_attention(qs.reshape(-1, heads, d), k_nan, v_nan,
                                     layer, dead, q_start, start, clen,
                                     window=window)
    np.testing.assert_allclose(np.asarray(got).reshape(qs.shape), want,
                               atol=2e-5)
    got = pa._prefill_attention_xla(*map(jnp.asarray, (
        qs.reshape(-1, heads, d), k, v, layer, tables, q_start, start, clen)),
        window)
    np.testing.assert_allclose(np.asarray(got).reshape(qs.shape), want,
                               atol=2e-5)
    flat = np.concatenate([qs[r, :clen[r]] for r in range(3)])
    got = programs.ragged_attention_xla(*map(jnp.asarray, (
        flat, k[layer], v[layer], tables, ctx,
        np.repeat(np.arange(3), clen), np.concatenate(
            [start[r] + np.arange(clen[r]) for r in range(3)]))),
        None, bs, window=window)
    np.testing.assert_allclose(
        np.asarray(got), np.concatenate([want[r, :clen[r]]
                                         for r in range(3)]), atol=2e-5)


@pytest.mark.parametrize("window", [0, 8, 11, 16])
def test_decode_kernel_fetches_its_rows_blocks_only(window):
    """(f) The decode kernel walks the step's rows as one list and has the
    next row's first blocks under way while this row's last are multiplied:
    pools of NaN everywhere but the blocks ``[first, nblocks)`` a row's query
    sees a key of, every other table entry (behind a window, past a row's
    last block, the whole table of a row without a context: first, last and
    between live rows) pointing at a block of NaN, and a finite result equal
    to the band-masked reference's."""
    rng = np.random.default_rng(100 + window)
    bs, kv, d, heads = 8, 2, 32, 4
    lens = [0, 45, 9, 0, 0, 30, 72, 1, 0]
    k, v, tables, poison = _paged(rng, lens, bs, kv, d)
    layer = 1
    q = rng.standard_normal((len(lens), heads, d)).astype(np.float32)
    ctx = np.asarray(lens, np.int32)
    dead = np.full_like(tables, poison)
    k_nan, v_nan = np.full_like(k, np.nan), np.full_like(v, np.nan)
    for r, n in enumerate(lens):
        first = max(n - window, 0) // bs if window else 0
        mine = tables[r, first:-(-n // bs)]
        dead[r, first:first + len(mine)] = mine
        k_nan[:, mine], v_nan[:, mine] = k[:, mine], v[:, mine]
    want = np.stack([
        _dense(q[r][None], k[layer], v[layer], tables, r, np.asarray([n - 1]),
               window or n, bs)[0] if n else np.zeros((heads, d), np.float32)
        for r, n in enumerate(lens)])
    got = np.asarray(pa.paged_decode_attention(q, k_nan, v_nan, layer, dead,
                                               ctx, window=window))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # and once more through slots two deep, one block a fetch
    narrow = dataclasses.replace(pa.pick_decode_tiles(
        len(lens), heads, kv, d, bs, np.float32), kb=1, slots=2)
    got = pa._decode_pallas(*map(jnp.asarray, (q, k_nan, v_nan)),
                            jnp.full((1,), layer, jnp.int32),
                            jnp.asarray(dead), jnp.asarray(ctx), tiles=narrow,
                            window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


# -- (g) one kind of layer, all of them windowed (Mistral) --------------------


def mistral_cfg(window: int) -> tfm.TransformerConfig:
    return tfm.TransformerConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_seq_len=256, tie_embeddings=False,
        sliding_window=window, attn_impl="flash", dtype="float32",
        param_dtype="float32")


def test_inactive_window_builds_the_programs_without_one():
    """(g) ``mistral-7b`` in the serving cells: a window no context of the
    engine reaches (64 x 64 = 4,096 <= 4,096; here 16 x 4 = 64 <= 64) traces
    the decode and mixed programs that ``sliding_window=0`` traces, jaxpr for
    jaxpr, and keeps the one pool unwindowed."""
    v2 = v2_config(max_blocks_per_seq=16)
    texts = []
    for window in (64, 0):
        cfg = mistral_cfg(window)
        assert programs.layer_plan(cfg, v2) == (programs.LayerKind(
            0, 0, tfm.RopeParams(theta=cfg.rope_theta)),)
        engine = InferenceEngineV2(cfg, make_params(cfg), v2)
        assert engine._windowed is None and engine.kv_win is None
        shapes = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
            (engine.params, engine.caches, *engine._table_inputs()))
        i32 = jax.ShapeDtypeStruct((v2.max_tokens_per_step,), jnp.int32)
        row = jax.ShapeDtypeStruct((v2.max_seqs,), jnp.int32)
        texts.append((
            str(jax.make_jaxpr(lambda *a: programs._decode_body(
                *a, engine.model_cfg, v2)[:2])(*shapes)),
            str(jax.make_jaxpr(engine._fwd)(
                *shapes[:2], i32, i32, i32, shapes[4], row, row, row, row))))
    assert texts[0] == texts[1]
    assert "paged_attention_prefill" in texts[0][1]


def test_window_past_the_longest_context_is_honoured():
    """(g) The same model with contexts longer than its window: one pool,
    windowed (blocks freed behind the window), and the engine's logits agree
    with the band-masked dense reference; served as full attention they
    would differ by 0.1 and more (asserted)."""
    cfg = mistral_cfg(12)
    params = make_params(cfg)
    model = dict(num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, rope_theta=cfg.rope_theta,
                 rms_norm_eps=cfg.norm_eps, sliding_window=12)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (41, 30)]
    engine, logits, out, uids = serve(cfg, params, prompts, new_tokens=20)
    assert engine.kv_win is None and engine._windowed is engine.kv
    assert engine.kv.trimmed > 10 and engine.free_blocks == \
        engine.total_blocks
    assert set(engine.caches) == {"k", "v"}
    for uid, prompt in zip(uids, prompts):
        seq = jnp.asarray(prompt + out[uid])
        want = np.asarray(dense_decoder.logits(params, model, seq))
        full = np.asarray(dense_decoder.logits(
            params, dict(model, sliding_window=0), seq))
        for pos, row in logits[uid]:
            assert np.abs(row - want[pos]).max() < F32_TOL
        assert np.abs(full - want)[len(prompt):].max() > 0.1


# -- (h) what does not know two pools refuses --------------------------------


@pytest.mark.parametrize("over,knob", refusal_cases(
    programs.KV, mellum_cfg(), v2_config()))
def test_features_that_move_kv_blocks_refuse_a_window(over, knob):
    """(h) Each row of the refusal table (``programs.REFUSED``) the kind
    holds is refused by name for a model with an active window, be it
    Mellum2's two kinds of layer or Mistral past its window, and accepted
    where the window is inactive (the kind then holds no row)."""
    v2 = v2_config(**over)
    assert programs.KV.refuses(mistral_cfg(256), v2) == ()
    for cfg in (mellum_cfg(), mistral_cfg(12)):
        with pytest.raises(ValueError, match=f"V2Config.*{knob}.*active "
                                             "sliding window"):
            InferenceEngineV2(cfg, None, v2)
    if knob != "spec_mode":
        cfg = mistral_cfg(256)  # no context of 96 tokens reaches it
        InferenceEngineV2(cfg, make_params(cfg), dataclasses.replace(
            v2, enable_prefix_cache=True)).close()


def test_v1_engine_refuses_layer_kinds():
    from deepspeed_tpu.inference.engine import InferenceEngine

    cfg = mellum_cfg()
    with pytest.raises(NotImplementedError, match="layer_types"):
        InferenceEngine(model_config=cfg, params=make_params(cfg))


# -- (i) group 128 -----------------------------------------------------------


@pytest.mark.parametrize("rows", [32, 512])
def test_mellum2_shapes_tile_at_group_128(rows):
    """(i) Mellum2's five GEMM shapes under W8A16 group 128, at a decode
    step's 32 rows and a mixed step's 512: every one has tiles, the experts'
    as one grid step holding all of K; group 256 does not divide 896 and
    leaves the experts' down projection without."""
    from deepspeed_tpu.moe.dropless import moe_tile_m, padded_rows

    def tile(k, n):
        t = pick_gemm_tiles(rows, k, n, 8, 128)
        return t.tn, t.tk, t.grid_steps

    assert tile(2304, 4096) == (4096, 384, 6)   # wq
    assert tile(2304, 512) == (512, 384, 6)     # wk, wv
    assert tile(4096, 2304) == (2304, 512, 8)   # wo
    tm, padded = moe_tile_m(rows * 8, 64), padded_rows(rows * 8, 64)
    for k, n in ((2304, 896), (896, 2304)):     # w_gate / w_in, w_out
        t = pick_grouped_tiles(padded, tm, k, n, 8, 128)
        assert (t.tn, t.tk, t.code_bytes_per_step) == (n, k, 2064384)
    assert pick_grouped_tiles(padded, tm, 896, 2304, 8, 256) is None


def test_quantizer_names_a_width_its_group_does_not_divide():
    w = jnp.ones((2, 896, 256), jnp.float32)
    with pytest.raises(ValueError, match="K = 896"):
        quantize_gemm_weight(w, bits=8, group=256)
    assert quantize_gemm_weight(w, bits=8, group=128).group == 128
    # widths under one group, or off the lane multiple, shrink as before
    assert quantize_gemm_weight(jnp.ones((128, 8)), group=256).group == 128
    assert quantize_gemm_weight(jnp.ones((72, 8)), group=256).group == 72
