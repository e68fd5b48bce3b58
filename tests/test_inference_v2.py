"""Inference-v2 (continuous batching / paged KV) tests
(reference: tests/unit/inference/v2/)."""

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.inference.v2.ragged import (BlockedAllocator, KVCacheManager,
                                               RaggedBatchBuilder,
                                               SequenceDescriptor)
from deepspeed_tpu.models import transformer as tfm
from served_kinds import assert_step_attrs


def test_blocked_allocator():
    a = BlockedAllocator(8)
    got = a.allocate(3)
    assert len(got) == 3 and a.free_blocks == 5
    a.free(got)
    assert a.free_blocks == 8
    with pytest.raises(MemoryError):
        a.allocate(9)


def test_kv_manager_capacity():
    kv = KVCacheManager(num_blocks=4, block_size=4, max_blocks_per_seq=3)
    seq = SequenceDescriptor(uid=1, tokens=list(range(10)))
    assert not kv.ensure_capacity(seq, 13)  # needs 4 blocks > max 3
    assert kv.ensure_capacity(seq, 10)  # 3 blocks
    assert len(seq.blocks) == 3
    kv.release(seq)
    assert kv.allocator.free_blocks == 4


def test_ragged_batch_builder():
    b = RaggedBatchBuilder(max_tokens=16, max_seqs=4, max_blocks_per_seq=4)
    s1 = SequenceDescriptor(uid=1, tokens=[5, 6, 7], blocks=[0])
    s2 = SequenceDescriptor(uid=2, tokens=[8, 9], blocks=[1], seen_tokens=1)
    batch = b.build([(s1, 3), (s2, 1)])
    assert batch.num_tokens == 4
    np.testing.assert_array_equal(batch.token_ids[:4], [5, 6, 7, 9])
    np.testing.assert_array_equal(batch.position_ids[:4], [0, 1, 2, 1])
    np.testing.assert_array_equal(batch.seq_index[:4], [0, 0, 0, 1])
    assert batch.logits_rows[0] == 2 and batch.logits_rows[1] == 3


@pytest.fixture(scope="module")
def tiny_model():
    # fp32: exact-match assertions must not be bf16 argmax-tie noise
    cfg = tfm.get_config("tiny", dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n):
    """``n`` greedy tokens after ``prompt`` by the plain uncached forward."""
    seq = np.array([prompt], np.int32)
    for _ in range(n):
        nxt = np.asarray(tfm.forward(params, seq, cfg)[:, -1].argmax(-1))
        seq = np.concatenate([seq, nxt.astype(np.int32)[:, None]], axis=1)
    return seq[0, len(prompt):].tolist()


def test_v2_matches_v1_greedy(devices, tiny_model):
    """Continuous-batching decode must produce exactly the tokens the plain
    uncached forward produces — the canonical paged-KV correctness check."""
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    prompt = [5, 6, 7, 8]
    uid = eng.put(prompt, max_new_tokens=6)
    results = eng.generate_all()
    np.testing.assert_array_equal(
        results[uid], prompt + _greedy_reference(cfg, params, prompt, 6))


def test_v2_concurrent_requests(devices, tiny_model):
    """Multiple interleaved requests with different lengths complete and match
    their individually-computed continuations."""
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=16, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [11, 12]]
    uids = [eng.put(p, max_new_tokens=4) for p in prompts]
    results = eng.generate_all()
    for p, uid in zip(prompts, uids):
        np.testing.assert_array_equal(
            results[uid], p + _greedy_reference(cfg, params, p, 4),
            err_msg=f"uid {uid} prompt {p}")


def test_v2_full_batch_padding_exact(devices, tiny_model):
    """Full batch (max_seqs sequences) + padding tokens: every sequence must
    match its uncached continuation exactly (the padding tokens reach no
    row: the flat prefill kernel gives them zero)."""
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    # 4 sequences = max_seqs; 3+4+5+2 = 14 tokens < 32 budget → 18 padding
    # tokens in the prefill step; sequence row 0 prefills from position 0
    prompts = [[1, 2, 3], [9, 8, 7, 6], [11, 12, 13, 14, 15], [21, 22]]
    uids = [eng.put(p, max_new_tokens=4) for p in prompts]
    results = eng.generate_all()
    for p, uid in zip(prompts, uids):
        np.testing.assert_array_equal(
            results[uid], p + _greedy_reference(cfg, params, p, 4),
            err_msg=f"uid {uid} prompt {p}")


def test_v2_blocks_recycled(devices, tiny_model):
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=16, max_seqs=2, block_size=8, num_blocks=16,
        max_blocks_per_seq=4, dtype="float32"))
    free0 = eng.kv.allocator.free_blocks
    for round_ in range(3):  # more work than the pool holds at once
        eng.put([1, 2, 3], max_new_tokens=3)
        eng.put([4, 5], max_new_tokens=3)
        eng.generate_all()
    assert eng.kv.allocator.free_blocks == free0  # all blocks returned


def test_paged_decode_kernel_matches_xla(devices):
    """Pallas paged decode == gather-based ragged attention."""
    from deepspeed_tpu.inference.v2.programs import ragged_attention_xla
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention

    S, H, KV, D, BS, NB, MB = 4, 8, 2, 16, 8, 32, 4
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (S, H, D), jnp.float32)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), (NB, BS, KV, D))
    v_cache = jax.random.normal(jax.random.PRNGKey(2), (NB, BS, KV, D))
    rng = np.random.default_rng(0)
    block_tables = jnp.asarray(
        rng.permutation(NB)[: S * MB].reshape(S, MB).astype(np.int32))
    context_lens = jnp.asarray([5, 17, 32, 1], jnp.int32)

    # the kernel takes the pools whole: here a pool of one layer
    out_k = paged_decode_attention(q, k_cache[None], v_cache[None], 0,
                                   block_tables, context_lens)
    # XLA path: one token per seq at position ctx-1
    positions = context_lens - 1
    out_x = ragged_attention_xla(
        q, k_cache, v_cache, block_tables, context_lens,
        jnp.arange(S, dtype=jnp.int32), positions, None, BS)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               atol=2e-5, rtol=2e-5)


def _dense_decode(q, k, v, tables, ctx, window, bs):
    """Float32 reference of one decode step: each row's keys gathered in
    order through its table, the window an explicit band; a row without a
    context reads zero."""
    rep = q.shape[1] // k.shape[2]
    out = np.zeros(q.shape, np.float32)
    for r, n in enumerate(ctx):
        if n == 0:
            continue
        ks = np.repeat(k[tables[r, :-(-n // bs)]].reshape(
            -1, *k.shape[-2:])[:n], rep, 1)
        vs = np.repeat(v[tables[r, :-(-n // bs)]].reshape(
            -1, *v.shape[-2:])[:n], rep, 1)
        s = np.einsum("hd,thd->ht", q[r], ks) / np.sqrt(q.shape[-1])
        if window:
            s[:, :max(n - window, 0)] = -np.inf
        w = np.exp(s - s.max(-1, keepdims=True))
        out[r] = np.einsum("ht,thd->hd", w / w.sum(-1, keepdims=True), vs)
    return out


#: query heads, KV heads, window and head width of the served decode shapes:
#: Mistral, OLMoE, Nemotron-3's attention layers, Mellum2's global and window
#: layers; at the served width of 128 the kernel reads the pools through a
#: view of a block as its (token, KV head) rows, at another as they lie
_DECODE_SHAPES = {"32/8": (32, 8, 0, 128), "16/16": (16, 16, 0, 16),
                  "32/2": (32, 2, 0, 128), "32/4": (32, 4, 0, 16),
                  "32/4-window": (32, 4, 21, 128)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("rows", ["edges", "dead-between", "one-live"])
@pytest.mark.parametrize("shape", list(_DECODE_SHAPES))
def test_paged_decode_kernel_at_served_shapes(devices, shape, rows, dtype):
    """The decode kernel (interpret mode) against the blockwise XLA path and
    a float32 reference, at every served share of query heads a KV head:
    contexts on both sides of a block's end, of a fetch's end (``kb``
    blocks) and at the table's full length, rows without a context first,
    last and between live ones (the fetch under way crosses them), pools in
    bfloat16 and float32."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    heads, kv, window, d = _DECODE_SHAPES[shape]
    bs = 8
    kb = pa.pick_decode_tiles(16, heads, kv, d, bs, dtype).kb
    mb = 2 * kb + 4
    assert kb > 1
    ctx = np.asarray({
        "edges": [0, 1, bs - 1, bs, bs + 1, kb * bs - 1, kb * bs,
                  kb * bs + 1, 2 * kb * bs + 1, mb * bs, 0],
        "dead-between": [0, 0, kb * bs + 3, 0, 0, 0, mb * bs, 0, bs, 0, 0],
        "one-live": [0, 0, 0, 0, 5 * bs + 2, 0, 0, 0]}[rows], np.int32)
    rng = np.random.default_rng([heads, kv, len(ctx)])
    nblk = -(-ctx // bs)
    ids = rng.permutation(int(nblk.sum()) + 1)
    tables = np.full((len(ctx), mb), ids[-1], np.int32)  # a block no row has
    at = 0
    for r, n in enumerate(nblk):
        tables[r, :n] = ids[at:at + n]
        at += n
    pool = (3, int(nblk.sum()) + 1, bs, kv, d)
    q, k, v = (jnp.asarray(rng.standard_normal(sh), dtype)
               for sh in ((len(ctx), heads, d), pool, pool))
    layer = jnp.int32(2)
    got = pa.paged_decode_attention(q, k, v, layer, jnp.asarray(tables),
                                    jnp.asarray(ctx), window=window)
    assert got.dtype == q.dtype and got.shape == q.shape
    got = np.asarray(got, np.float32)
    via_xla = np.asarray(pa._decode_attention_xla(
        q, k, v, layer, jnp.asarray(tables), jnp.asarray(ctx), window),
        np.float32)
    want = _dense_decode(*(np.asarray(x, np.float32) for x in (q, k[2], v[2])),
                         tables, ctx, window, bs)
    # bfloat16: the result's own rounding (2**-8 of values up to 2-3) and
    # the weights rounded for p.v, as the prefill kernel rounds them
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, atol=tol)
    np.testing.assert_allclose(got, via_xla, atol=tol)
    assert not got[ctx == 0].any()


@pytest.mark.parametrize("kb,slots,span", [(1, 2, 16), (2, 4, 16), (4, 3, 8),
                                           (3, 2, 8)])
def test_paged_decode_kernel_under_any_tiling(devices, monkeypatch, kb, slots,
                                              span):
    """The decode kernel under tilings the picker does not pick at these
    sizes: one block a fetch and slots two deep (the parent's), slots four
    deep, a count of blocks a fetch that divides nothing, and rows past what
    a grid step holds (spans of 8 of 11 rows: the last is padded with rows
    without a context, and no fetch crosses a span's end)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "pick_decode_tiles",
                        lambda *_: pa.DecodeTiles(kb, slots, span))
    heads, kv, d, bs, mb = 8, 2, 16, 8, 12
    ctx = np.asarray([0, 1, bs, 3 * bs + 1, 0, 0, mb * bs, 7, 0, 2 * bs - 1,
                      5 * bs], np.int32)
    rng = np.random.default_rng(kb)
    tables = np.stack([rng.permutation(40)[:mb] for _ in ctx]).astype(np.int32)
    q, k, v = (jnp.asarray(rng.standard_normal(sh), jnp.float32)
               for sh in ((len(ctx), heads, d), (2, 40, bs, kv, d),
                          (2, 40, bs, kv, d)))
    for window in (0, 13):
        got = pa.paged_decode_attention(q, k, v, 1, jnp.asarray(tables),
                                        jnp.asarray(ctx), window=window)
        want = _dense_decode(*(np.asarray(x) for x in (q, k[1], v[1])),
                             tables, ctx, window, bs)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_pick_decode_tiles():
    """The picker on the served shapes (blocks of 64): a fetch of 2,048
    (token, KV head) pairs whatever the KV heads, three slots, every row in
    one grid step; rows past 8 MiB of queries are walked in spans."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    for (heads, kv), kb in {(16, 16): 2, (32, 8): 4, (32, 4): 8,
                            (32, 2): 16, (32, 1): 16}.items():
        assert pa.pick_decode_tiles(64, heads, kv, 128, 64, jnp.bfloat16) == \
            pa.DecodeTiles(kb, 3, 64)
    assert pa.pick_decode_tiles(32, 32, 8, 128, 256, jnp.bfloat16).kb == 1
    assert pa.pick_decode_tiles(4096, 32, 8, 128, 64,
                                jnp.bfloat16).span == 1024


def test_v2_rejects_impossible_request(devices, tiny_model):
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        block_size=8, num_blocks=32, max_blocks_per_seq=4, dtype="float32"))
    with pytest.raises(ValueError):
        eng.put(list(range(30)), max_new_tokens=8)  # 38 > 4*8


def test_v2_no_livelock_on_small_pool(devices, tiny_model):
    """Regression: admission reserves the full block budget, so a small pool
    admits fewer sequences instead of livelocking mid-decode."""
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=4, block_size=4, num_blocks=6,
        max_blocks_per_seq=4, dtype="float32"))
    # each request needs ceil((4+8)/4)=3 blocks; pool has 5 usable → only one
    # fits at a time, but all must complete eventually
    uids = [eng.put([1, 2, 3, 4], max_new_tokens=8) for _ in range(3)]
    results = eng.generate_all(max_steps=200)
    for uid in uids:
        assert len(results[uid]) == 4 + 8, results[uid]


def test_burst_decode_matches_single_step(devices, tiny_model):
    """Multi-token in-graph decode must produce exactly the single-step tokens."""
    cfg, params = tiny_model
    mk = lambda: InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    prompts = [[5, 6, 7], [9, 8]]

    e1 = mk()
    uids1 = [e1.put(p, max_new_tokens=12) for p in prompts]
    r1 = e1.generate_all(burst=4)  # burst path

    e2 = mk()
    uids2 = [e2.put(p, max_new_tokens=12) for p in prompts]
    r2 = e2.generate_all(burst=1)  # pure single-step path
    for u1, u2 in zip(uids1, uids2):
        assert r1[u1] == r2[u2], (r1[u1], r2[u2])


def test_scheduler_fuzz_block_ownership(devices, tiny_model):
    """Property test: under random arrivals/lengths, (1) no KV block is ever
    owned by two live sequences, (2) every request completes exactly, and
    (3) the pool is fully recycled."""
    cfg, params = tiny_model
    rng = np.random.default_rng(42)
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=24, max_seqs=3, block_size=4, num_blocks=40,
        max_blocks_per_seq=8, dtype="float32"))
    free0 = eng.kv.allocator.free_blocks
    pending = []
    for _ in range(12):
        plen = int(rng.integers(1, 10))
        mnew = int(rng.integers(1, 12))
        prompt = rng.integers(1, 256, plen).tolist()
        pending.append((prompt, mnew))
    submitted = {}  # uid -> (descriptor, prompt, max_new)
    steps = 0
    while (pending or eng.waiting or eng.running) and steps < 500:
        # random arrival
        if pending and rng.random() < 0.4:
            prompt, mnew = pending.pop()
            uid = eng.put(prompt, max_new_tokens=mnew)
            desc = eng.waiting[-1]
            submitted[uid] = (desc, prompt, mnew)
        eng.step()
        steps += 1
        # invariant: no block owned twice among live sequences
        owned = []
        for s in list(eng.running.values()) + list(eng.waiting):
            owned.extend(s.blocks)
        assert len(owned) == len(set(owned)), "block double-ownership!"
    assert not pending and not eng.running and not eng.waiting, "stalled"
    assert eng.kv.allocator.free_blocks == free0, "block leak"
    # every request completed with exactly prompt + max_new tokens
    assert len(submitted) == 12
    for uid, (desc, prompt, mnew) in submitted.items():
        assert desc.done
        assert len(desc.tokens) == len(prompt) + mnew, \
            (uid, len(desc.tokens), len(prompt), mnew)
        assert desc.tokens[:len(prompt)] == prompt


def test_burst_sampling(devices, tiny_model):
    """Sampled bursts: valid tokens, reproducible per seed, varies across
    seeds."""
    cfg, params = tiny_model
    mk = lambda: InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=32, max_seqs=2, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    out = []
    for seed in (1, 1, 2):
        eng = mk()
        uid = eng.put([5, 6, 7], max_new_tokens=12)
        res = eng.generate_all(temperature=1.0, seed=seed, burst=4)
        toks = res[uid]
        assert len(toks) == 15
        assert all(0 <= t < cfg.vocab_size for t in toks[3:])
        out.append(toks)
    assert out[0] == out[1]  # same seed reproducible
    assert out[0] != out[2]  # different seed differs


def test_soa_fast_path_engages(devices, tiny_model):
    """Steady-state decode must run through the vectorized SoA path, and
    its results must match the descriptor path's token-exact output."""
    cfg, params = tiny_model

    def _engine():
        return InferenceEngineV2(cfg, params, V2Config(
            max_tokens_per_step=32, max_seqs=4, block_size=8, num_blocks=64,
            max_blocks_per_seq=8, dtype="float32"))

    e1 = _engine()
    e2 = _engine()
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    for p in prompts:
        e1.put(p, max_new_tokens=12)
        e2.put(p, max_new_tokens=12)
    r1 = e1.generate_all(burst=1)   # single-step (fast path per token)
    r2 = e2.generate_all(burst=4)   # burst path over the same table
    assert e1.fast_steps > 0, "SoA decode path never engaged"
    assert r1 == r2


def _flat(fn, q, *operands):
    """A flat prefill entry point on the per-row layout ``(S, Qp, H, D)``:
    row s's queries lie from token s * Qp on (a row shorter than Qp leaves a
    gap), the pools, layer and table before ``q_start``, the rest after."""
    S, Qp = q.shape[:2]
    *pools, cs, cl = operands
    return fn(q.reshape((S * Qp,) + q.shape[2:]), *pools,
              jnp.arange(S, dtype=jnp.int32) * Qp, cs, cl).reshape(q.shape)


def _naive_paged_prefill(q, k_cache, v_cache, block_tables, chunk_start,
                         chunk_len):
    """Full-gather reference (the OLD fallback's math) for equivalence
    checks only — materializes (S, S_max, ...)."""
    import math as _math

    S, Qp, H, D = q.shape
    NB, BS, KV, _ = k_cache.shape
    S_max = block_tables.shape[1] * BS
    k_seq = k_cache[block_tables].reshape(S, S_max, KV, D)
    v_seq = v_cache[block_tables].reshape(S, S_max, KV, D)
    if KV != H:
        rep = H // KV
        k_seq = jnp.repeat(k_seq, rep, axis=2)
        v_seq = jnp.repeat(v_seq, rep, axis=2)
    scores = jnp.einsum("sqhd,sthd->shqt", q.astype(jnp.float32),
                        k_seq.astype(jnp.float32)) / _math.sqrt(D)
    t_pos = jnp.arange(S_max)[None, None, None, :]
    q_pos = (chunk_start[:, None] + jnp.arange(Qp)[None, :])[:, None, :, None]
    valid = (t_pos <= q_pos) & \
        (t_pos < (chunk_start + chunk_len)[:, None, None, None]) & \
        (jnp.arange(Qp)[None, None, :, None] < chunk_len[:, None, None, None])
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("shqt,sthd->sqhd", probs, v_seq.astype(jnp.float32))
    return out.astype(q.dtype)


def test_blockwise_prefill_fallback_matches_full_gather(devices):
    """The bounded (lax.scan online-softmax) fallback must equal the full
    per-sequence gather numerically."""
    from deepspeed_tpu.ops.pallas.paged_attention import _prefill_attention_xla

    S, Qp, H, KV, D, BS, MB = 3, 8, 4, 2, 16, 4, 6
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (S, Qp, H, D), jnp.float32)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), (32, BS, KV, D))
    v_cache = jax.random.normal(jax.random.PRNGKey(2), (32, BS, KV, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(32)[:S * MB]
                     .reshape(S, MB).astype(np.int32))
    cs = jnp.asarray([0, 5, 11], jnp.int32)
    cl = jnp.asarray([8, 3, 6], jnp.int32)
    got = _flat(_prefill_attention_xla, q, k_cache[None], v_cache[None], 0,
                bt, cs, cl)
    ref = _naive_paged_prefill(q, k_cache, v_cache, bt, cs, cl)
    # compare only valid rows (padding rows emit zeros vs garbage)
    for s in range(S):
        n = int(cl[s])
        np.testing.assert_allclose(np.asarray(got[s, :n]),
                                   np.asarray(ref[s, :n]),
                                   atol=2e-5, rtol=2e-5)


def test_blockwise_decode_fallback_matches_reference(devices):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _decode_attention_xla)

    S, H, KV, D, BS, MB = 4, 8, 2, 16, 8, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, D), jnp.float32)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), (32, BS, KV, D))
    v_cache = jax.random.normal(jax.random.PRNGKey(2), (32, BS, KV, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(32)[:S * MB]
                     .reshape(S, MB).astype(np.int32))
    ctx = jnp.asarray([5, 17, 32, 1], jnp.int32)
    from deepspeed_tpu.inference.v2.programs import ragged_attention_xla

    got = _decode_attention_xla(q, k_cache[None], v_cache[None], 0, bt, ctx)
    ref = ragged_attention_xla(q, k_cache, v_cache, bt, ctx,
                               jnp.arange(S, dtype=jnp.int32), ctx - 1,
                               None, BS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_serving_scale_fallback_memory_bounded(devices):
    """Serving scale (16 seqs x 4096 ctx): the kernel-unfriendly-shape
    fallbacks' compiled temp memory must stay O(tokens·block) (prefill: one
    K and one V block a token of the flat step) and O(S·block) (decode),
    nowhere near the old full gather's O(S·S_max) working set (r3 verdict
    weak #6).  The prefill bound moved with ISSUE 32, which asked for a
    fallback on the flat ``(T, H, D)`` queries with temp O(T x block_size):
    the parent gathered a block a ROW a column (under an eighth of the old
    working set, 96 MiB here); the flat path gathers one a TOKEN (128 MiB
    here), so a long chunk gathers its row's block once a token.  No served
    shape takes the fallback; if one ever does, gather a row's block once
    and index it by the token's row."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        _decode_attention_xla, _prefill_attention_xla)

    # GQA (H != KV): the grouped einsum must hold the bound without a
    # rep-x jnp.repeat of K/V inflating the per-step working set
    S, Qp, H, KV, D, BS, MB, NB = 16, 256, 8, 2, 64, 32, 128, 2048
    q = jnp.zeros((S * Qp, H, D), jnp.float32)  # the step's tokens, flat
    kc = jnp.zeros((1, NB, BS, KV, D), jnp.float32)  # a pool of one layer
    bt = jnp.zeros((S, MB), jnp.int32)
    z = jnp.zeros((S,), jnp.int32)
    layer = jnp.int32(0)
    ma = jax.jit(_prefill_attention_xla).lower(
        q, kc, kc, layer, bt, z, z, z).compile().memory_analysis()
    old_working_set = 2 * S * MB * BS * H * D * 4 + S * H * Qp * MB * BS * 4
    a_block_a_token = 2 * S * Qp * BS * KV * D * 4
    assert ma.temp_size_in_bytes < min(1.25 * a_block_a_token,
                                       old_working_set / 4), (
        f"prefill fallback temp {ma.temp_size_in_bytes/2**20:.0f} MiB — "
        f"not bounded (old gather ~{old_working_set/2**20:.0f} MiB)")

    qd = jnp.zeros((S, H, D), jnp.float32)
    mad = jax.jit(_decode_attention_xla).lower(
        qd, kc, kc, layer, bt, z).compile().memory_analysis()
    old_decode = 2 * S * MB * BS * H * D * 4
    assert mad.temp_size_in_bytes < old_decode / 8, (
        f"decode fallback temp {mad.temp_size_in_bytes/2**20:.0f} MiB")


# ---------------------------------------------------------------------------
# the pools stay where they lie (ISSUE 28): kernels and fallbacks read the
# whole pool at (layer, block); the step bodies carry it through the scan
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jitted_paged(kernel: str, impl: str):
    """One of the four attention entry points, jitted once: the layer is a
    traced scalar, as in the layer scan, so three layers are one program."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    return jax.jit({
        ("decode", "pallas"): pa.paged_decode_attention,
        ("decode", "xla"): pa._decode_attention_xla,
        ("prefill", "pallas"): pa.paged_prefill_attention,
        ("prefill", "xla"): pa._prefill_attention_xla}[kernel, impl])


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_attention_reads_its_layer_of_the_pool(devices, kernel, impl,
                                                     layer):
    """Both kernels (interpret mode here) and both blockwise fallbacks, given
    a 3-layer pool whole and a traced layer index, equal the full-gather
    reference on that layer's slice alone."""
    from deepspeed_tpu.inference.v2.programs import ragged_attention_xla

    L, S, H, KV, D, BS, NB, MB = 3, 3, 4, 2, 16, 8, 32, 4
    k_pool = jax.random.normal(jax.random.PRNGKey(1), (L, NB, BS, KV, D))
    v_pool = jax.random.normal(jax.random.PRNGKey(2), (L, NB, BS, KV, D))
    bt = jnp.asarray(np.random.default_rng(0).permutation(NB)[:S * MB]
                     .reshape(S, MB).astype(np.int32))
    fn = _jitted_paged(kernel, impl)
    if kernel == "decode":
        q = jax.random.normal(jax.random.PRNGKey(0), (S, H, D), jnp.float32)
        ctx = jnp.asarray([5, 17, 32], jnp.int32)
        got = fn(q, k_pool, v_pool, jnp.int32(layer), bt, ctx)
        ref = ragged_attention_xla(q, k_pool[layer], v_pool[layer], bt, ctx,
                                   jnp.arange(S, dtype=jnp.int32), ctx - 1,
                                   None, BS)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        return
    Qp = 8
    q = jax.random.normal(jax.random.PRNGKey(0), (S, Qp, H, D), jnp.float32)
    cs = jnp.asarray([0, 5, 11], jnp.int32)
    cl = jnp.asarray([8, 3, 6], jnp.int32)
    got = _flat(fn, q, k_pool, v_pool, jnp.int32(layer), bt, cs, cl)
    ref = _naive_paged_prefill(q, k_pool[layer], v_pool[layer], bt, cs, cl)
    for s in range(S):  # padding rows emit zeros, the reference garbage
        n = int(cl[s])
        np.testing.assert_allclose(np.asarray(got[s, :n]),
                                   np.asarray(ref[s, :n]),
                                   atol=2e-5, rtol=2e-5)


# what the one serving layer body (``programs.serving_layers``) branches on,
# each through all its callers: grouped KV heads with full rotary; a
# parallel residual with partial rotary (``rot_dim`` 8 of ``head_dim`` 16:
# the dims past it pass through); learned positions (no RoPE), an untied head
_THREE_LAYER_VARIANTS = {
    "gqa": {},
    "parallel-partial-rotary": dict(parallel_residual=True,
                                    partial_rotary_factor=0.5),
    "learned-position": dict(position="learned", tie_embeddings=False),
}


@pytest.fixture(scope="module", params=list(_THREE_LAYER_VARIANTS))
def three_layer_model(request):
    """Three layers, grouped KV heads, weights scaled so that greedy decoding
    wanders instead of settling on one token; one model a variant."""
    import dataclasses

    cfg = dataclasses.replace(tfm.get_config("tiny", dtype="float32"),
                              num_layers=3, num_kv_heads=2,
                              **_THREE_LAYER_VARIANTS[request.param])
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, jax.tree.map(lambda a: a * 3.0 if a.ndim >= 2 else a, params)


_POOL_V2 = dict(max_tokens_per_step=8, max_seqs=4, block_size=8,
                num_blocks=64, max_blocks_per_seq=8, dtype="float32")


def _shared_prefix_prompts():
    rng = np.random.default_rng(1)
    a = rng.integers(1, 256, 20).tolist()
    return a, a[:12] + rng.integers(1, 256, 4).tolist()


def test_carried_pools_give_the_parents_tokens(devices, three_layer_model):
    """Chunked prefill (20-token prompts, 8 tokens a step), then decode step
    by step, with a second sequence that shares one whole block and forks
    the next through ``cow_copy`` and a third that re-reads the first's
    blocks: greedy tokens are those the parent commit (aee5449: the pools as
    the scan's ``xs``/``ys``) gave on this model, which are the plain
    uncached forward's."""
    cfg, params = three_layer_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        **_POOL_V2, enable_prefix_cache=True))
    assert eng.caches["k"].shape == (3, 64, 8, 2, 16)
    p_a, p_b = _shared_prefix_prompts()
    u_a = eng.put(list(p_a), max_new_tokens=10)
    out_a = eng.generate_all(burst=1)[u_a][len(p_a):]
    u_b = eng.put(list(p_b), max_new_tokens=10)
    u_c = eng.put(list(p_a), max_new_tokens=10)
    res = eng.generate_all(burst=1)
    assert eng.prefix_stats()["cow_copies"] == 2
    ref_a = _greedy_reference(cfg, params, p_a, 10)
    ref_b = _greedy_reference(cfg, params, p_b, 10)
    if cfg.position == "rope" and not cfg.parallel_residual:  # recorded
        # on aee5449, before the pools rode the carry
        assert ref_a == [81, 14, 197, 199, 75, 77, 46, 66, 108, 27]
        assert ref_b == [119, 87, 178, 137, 118, 237, 90, 209, 191, 152]
    assert out_a == ref_a
    assert res[u_b][len(p_b):] == ref_b
    assert res[u_c][len(p_a):] == ref_a
    assert eng.caches["k"].shape == (3, 64, 8, 2, 16)


@pytest.mark.parametrize("path", ["burst", "self_draft", "draft"])
def test_other_step_bodies_agree_with_single_steps(devices,
                                                   three_layer_model, path):
    """``multi_decode_step`` (an outer scan that carries the caches round
    the layer scan's carry) and the speculative verify body (``spec.py``)
    give exactly the tokens of single decode steps on three layers, for every
    branch of the layer body they share."""
    cfg, params = three_layer_model
    prompts = list(_shared_prefix_prompts()) + [[42]]

    def run(burst=1, **over):
        kw = dict(draft_params=params, draft_config=cfg) \
            if over.get("spec_mode") == "draft" else {}
        eng = InferenceEngineV2(cfg, params, V2Config(
            **{**_POOL_V2, "max_tokens_per_step": 32, **over}), **kw)
        uids = [eng.put(list(p), max_new_tokens=12) for p in prompts]
        res = eng.generate_all(burst=burst)
        return [res[u] for u in uids], eng

    single, _ = run()
    if path == "burst":
        got, eng = run(burst=4)
        assert eng.burst_steps > 0
    else:
        got, eng = run(spec_mode=path, spec_k=3)
        assert eng.spec_steps > 0
    assert got == single


@pytest.mark.parametrize("module", ["programs", "spec"])
def test_step_programs_import_without_the_engine(module):
    """Arrows point one way, ``programs`` <- ``spec`` <- ``engine``: what is
    traced imports without the host side (allocator, scheduler, prefix cache,
    paging), in a fresh interpreter."""
    import subprocess
    import sys

    code = (f"import sys, deepspeed_tpu.inference.v2.{module}; "
            "assert 'deepspeed_tpu.inference.v2.engine' not in sys.modules")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# the step seen from inside: engine/step's children (ISSUE 24)
# ---------------------------------------------------------------------------

# a step's two halves: the call of its program (a mixed program's sampler
# is enqueued behind it) and the fetch of its tokens
_CALL = {
    "decode": ["engine/h2d", "engine/dispatch"],
    "spec": ["engine/h2d", "engine/dispatch"],
    "mixed": ["engine/schedule", "engine/build", "engine/h2d",
              "engine/dispatch"],
}
_FETCH = ["engine/wait", "engine/finish"]
# the last child of a step that ends steady: the NEXT decode step's copy
# (ISSUE 38); a speculative engine stages nothing
_STAGE = "engine/stage"


@pytest.mark.parametrize("kind,over", [
    ("decode", {}), ("mixed", {}), ("spec", {"spec_mode": "self_draft",
                                             "spec_k": 2})])
def test_step_children_nest_in_order(devices, tiny_model, kind, over):
    """Every ``engine/step`` of the kind has one child per phase, inside its
    interval and in order, each with the step's ``kind`` and ``step``, and
    ``engine/stage`` behind them where the step ends steady and nowhere
    else; the step itself says how long the device was (presumed) busy, how
    full its batch was, what was copied to the device for its program and,
    a decode step, whether the step before had staged that copy.  A step
    that calls its successor ahead (ISSUE 50; of either kind, ISSUE 54)
    holds that step's call, under that step's kind and number, before its
    own fetch, and stages nothing; the step that finds the program under way
    holds no call of its own, and a mixed one opens with its sampler."""
    from deepspeed_tpu.observability.trace import tracer

    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=16, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32", **over))
    eng.put(list(range(1, 41)), max_new_tokens=4)  # 40 tokens: 3 chunks
    eng.put([7, 8, 9], max_new_tokens=6)
    tracer.clear()
    thread = threading.current_thread().name
    ends_steady = {}
    while eng.running or eng.waiting:
        eng.step()
        ends_steady[eng.steps] = bool(
            kind != "spec" and not eng.waiting and eng.running
            and eng._prefilling == 0)
    spans = [s for s in tracer.spans() if s.thread == thread]
    steps = [s for s in spans
             if s.name == "engine/step" and s.attrs["kind"] == kind]
    assert steps, f"no {kind} step ran"
    if kind != "spec":  # a dense model's attributes: the parent's, no more
        assert_step_attrs([s.attrs for s in spans
                           if s.name == "engine/step"])
    numbers = [s.attrs["step"] for s in spans if s.name == "engine/step"]
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert kind == "spec" or (True in ends_steady.values()
                              and False in ends_steady.values())
    kind_of_step = {s.attrs["step"]: s.attrs["kind"] for s in spans
                    if s.name == "engine/step"}
    assert kind == "spec" or any(s.attrs["ahead"] for s in steps)
    for st in steps:
        kids = [s for s in spans if s.parent_id == st.span_id]
        found, ahead_next = (st.attrs.get("ahead", 0),
                             st.attrs.get("ahead_next", 0))
        staged_next = ends_steady[st.attrs["step"]] and not ahead_next
        own = _CALL[kind] * (not found) + ["engine/sample"] * (kind == "mixed")
        next_kind = kind_of_step.get(st.attrs["step"] + 1)
        nxt = _CALL[next_kind] * ahead_next if ahead_next else []
        want = own + nxt + _FETCH + ([_STAGE] if staged_next else [])
        assert [k.name for k in kids] == want
        edges = [st.t_start]
        for i, k in enumerate(kids):
            of_next = len(own) <= i < len(own) + len(nxt)
            assert k.attrs["kind"] == (next_kind if of_next else kind)
            assert k.attrs["step"] == st.attrs["step"] + of_next
            edges += [k.t_start, k.t_end]
        edges.append(st.t_end)
        assert edges == sorted(edges)  # inside the step, one after another
        if found:  # its call lies in the step before, its split at 0
            kids = [s for s in spans if s.name in _CALL[kind]
                    and s.attrs["step"] == st.attrs["step"]] + kids
            assert [k.name for k in kids[:len(_CALL[kind])]] == _CALL[kind]
            assert st.attrs["pre_ms"] == 0.0
            # where ``device_ms`` opens: the step's entry
            kids[len(_CALL[kind]) - 1] = st
        assert 0.0 <= st.attrs["device_ms"] <= st.duration_s * 1e3
        assert st.attrs["budget"] == 16
        assert 0 < st.attrs["tokens"] <= 16
        by_name = {k.name: k for k in kids
                   if k.attrs["step"] == st.attrs["step"]}
        assert st.attrs["device_ms"] == pytest.approx(
            (by_name["engine/wait"].t_end
             - by_name.get("engine/dispatch", st).t_start) * 1e3)
        # the staging lies in the step's ``post_ms``, behind the fetch
        if staged_next:
            assert st.attrs["post_ms"] >= (
                st.t_end - by_name[_STAGE].t_start) * 1e3 - 1e-6
        for k in kids:  # a child carries what a reader joins on, no more
            assert k is st or set(k.attrs) == {"kind", "step"}
        # the copies that fed the step's program, on the step itself (a
        # speculative step keeps its own arguments and counts none)
        layout = {"decode": eng._decode_layout,
                  "mixed": eng.builder.layout}.get(kind)
        if layout is None:
            assert "h2d_copies" not in st.attrs
        else:
            assert (st.attrs["h2d_copies"], st.attrs["h2d_bytes"]) == (
                1, layout.size * 4)
        # nothing touched the table between two steps here: a decode step
        # runs on what the step before staged whenever that step staged
        assert "stage_discarded" not in st.attrs
        if kind == "decode":
            assert st.attrs["staged"] == (
                "ahead" if found else
                "used" if ends_steady.get(st.attrs["step"] - 1) else "fresh")
            assert st.attrs["ahead_dropped"] == 0
        else:
            assert "staged" not in st.attrs
    if kind == "decode":  # both ways a decode step begins, and both it ends
        assert {(s.attrs["ahead"], s.attrs["ahead_next"]) for s in steps} == {
            (0, 1), (1, 1), (1, 0)}
    if kind == "mixed":  # the long prompt fills whole chunks of the budget
        assert max(s.attrs["tokens"] for s in steps) == 16


def test_mixed_step_counts_the_slots_its_kernel_multiplies(devices,
                                                           tiny_model):
    """``attn_q_slots`` on a mixed ``engine/step`` is counted on the host
    from the tiling ``mixed_step_attn_tiles`` gives, and the kernel's ring
    event names what the traced program picked: the two are one picker on
    one set of sizes.  A budget of 24 (no other test's: the program is
    traced here): a chunk of 24 fills one tile of 24; a decode row beside a
    chunk of 16 costs a tile of 8 and a tile of 24."""
    from deepspeed_tpu.inference.v2.programs import mixed_step_attn_tiles
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.paged_attention import PrefillTiles

    cfg, params = tiny_model
    v2 = V2Config(max_tokens_per_step=24, max_seqs=4, block_size=8,
                  num_blocks=64, max_blocks_per_seq=8, dtype="float32")
    tiles = mixed_step_attn_tiles(cfg, v2)
    assert tiles == PrefillTiles(8, 24, 4, 24)
    tracer.clear()
    eng = InferenceEngineV2(cfg, params, v2)
    eng.put([7, 8, 9], max_new_tokens=8)
    eng.step()  # the short prompt: 3 tokens, a tile of 8
    eng.put(list(range(1, 41)), max_new_tokens=2)  # chunks of 23 and 17
    eng.step()
    eng.step()
    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/paged_attention_prefill_tiles"]
    assert events and all(
        (e["t"], e["tq"], e["kb"], e["grid_steps"]) == (24, "8/24", 4, 1)
        for e in events)
    steps = [s.attrs for s in tracer.spans()
             if s.name == "engine/step" and s.attrs["kind"] == "mixed"]
    assert [(s["tokens"], s["attn_q_slots"]) for s in steps] == [
        (3, 8), (24, 8 + 24), (18, 8 + 24)]


def test_a_failing_phase_stays_in_the_ring(devices, tiny_model):
    """When the forward raises, ``engine/dispatch`` is closed with its step,
    both marked ``error``, and the next step's spans nest cleanly."""
    from deepspeed_tpu.observability.trace import tracer

    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=16, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    eng.put([7, 8, 9], max_new_tokens=2)
    fwd = eng._fwd

    def boom(*args, **kwargs):
        raise RuntimeError("boom")

    eng._fwd = boom
    tracer.clear()
    thread = threading.current_thread().name
    with pytest.raises(RuntimeError, match="boom"):
        eng.step()
    eng._fwd = fwd
    eng.step()
    spans = [s for s in tracer.spans() if s.thread == thread]
    first = [s for s in spans if s.attrs["step"] == spans[0].attrs["step"]]
    assert [(s.name, s.attrs.get("error")) for s in first] == [
        ("engine/schedule", None), ("engine/build", None),
        ("engine/h2d", None), ("engine/dispatch", True),
        ("engine/step", True)]
    assert first[3].parent_id == first[4].span_id
    assert first[3].t_end == first[4].t_end and first[3].annotation is None
    again = spans[-1]
    assert again.name == "engine/step" and again.parent_id is None
    assert "error" not in again.attrs and again.attrs["device_ms"] >= 0.0


# ---------------------------------------------------------------------------
# a step's host inputs reach the device in one copy (ISSUE 34)
# ---------------------------------------------------------------------------

# the four layouts: what an engine can be built with that adds a field to a
# step's buffer.  Sizes no other test uses, so that the unpack programs (one a
# layout, shared by every engine over the same sizes) are these tests' own
_LAYOUTS = {
    "plain": dict(),
    "two_tables": dict(two_pools=True),
    "state_slots": dict(state=True),
    "adapters": dict(adapters=True),
}
_ROWS, _BLOCKS, _TOKENS = 5, 9, 24


def _layout(name, step):
    from deepspeed_tpu.inference.v2.ragged import decode_layout, mixed_layout

    kw = dict(_LAYOUTS[name])
    rows = _ROWS + 1  # not the engines' below: their programs are theirs
    if step == "decode":
        kw.pop("state", None)  # a decode row IS its state slot
        return decode_layout(rows, _BLOCKS, **kw)
    return mixed_layout(_TOKENS, rows, _BLOCKS, **kw)


@pytest.mark.parametrize("step", ["decode", "mixed"])
@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_pack_then_unpack_gives_every_field_back(devices, name, step):
    """What the host writes into a step's one buffer is what the unpack
    program hands the step program, bit for bit, the temperatures (float32
    bits among int32 words) included."""
    from deepspeed_tpu.inference.v2.programs import build_unpack

    layout = _layout(name, step)
    names = [f[0] for f in layout.fields]
    assert ("win_tables" in names) == (name == "two_tables")
    assert ("row_adapter" in names) == (name == "adapters")
    assert ("state_slots" in names) == (name == "state_slots"
                                        and step == "mixed")
    assert layout.size == sum(int(np.prod(f[2])) for f in layout.fields)
    rs = np.random.default_rng(len(names))
    buf = layout.new()
    assert buf.dtype == np.int32 and not buf.any()
    want = {}
    for field, view in layout.views(buf).items():
        if view.dtype == np.float32:  # any bits: -0.0, a denormal, a NaN's
            bits = rs.integers(-2**31, 2**31, view.shape).astype(np.int32)
            bits.flat[:3] = [-2**31, 1, 0x7fc00001]
            view[...] = bits.view(np.float32)
        else:
            view[...] = rs.integers(-2**31, 2**31, view.shape)
        want[field] = view.copy()
    fields = build_unpack(layout)(buf)
    assert sorted(fields) == sorted(want) == sorted(names)
    for field, arr in fields.items():
        assert arr.shape == want[field].shape
        assert arr.dtype == want[field].dtype
        np.testing.assert_array_equal(
            np.asarray(arr).view(np.int32), want[field].view(np.int32))


def _one_copy_engine(name):
    """A tiny engine whose steps use layout ``name`` (the model kind that
    has it), at sizes of these tests' own."""
    v2 = dict(max_tokens_per_step=_TOKENS, max_seqs=_ROWS, block_size=8,
              num_blocks=96, max_blocks_per_seq=_BLOCKS, dtype="float32")
    preset = {"two_tables": "tiny-mellum2",
              "state_slots": "tiny-nemotron3"}.get(name, "tiny")
    cfg = tfm.get_config(preset, dtype="float32")
    if name == "adapters":
        v2.update(adapter_slots=3, adapter_rank=2)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def build():
        eng = InferenceEngineV2(cfg, params, V2Config(**v2))
        if name == "adapters":
            from deepspeed_tpu.inference.v2.engine import \
                adapter_target_shapes

            rs = np.random.default_rng(5)
            L = cfg.num_layers
            eng.set_adapter_slot(1, {
                t: (rs.standard_normal((L, K, 2)).astype(np.float32),
                    rs.standard_normal((L, 2, N)).astype(np.float32))
                for t, (K, N) in adapter_target_shapes(cfg).items()})
        return eng

    return build


def _parents_argument_path(eng):
    """The parent's way of bringing a step's inputs to the device, kept here
    as the reference: an array a field (``jnp.asarray`` each; the decode
    step's straight off the SoA table as ``_table_inputs`` and
    ``_row_temps`` read it) and the engine's key split eagerly on the host,
    unpacked in Python; and, like the parent, one step in flight: no step is
    dispatched before its predecessor's tokens are on the host."""
    eng._may_go_ahead = lambda *a: False

    def to_device(layout, buf, out=None):
        names = [f[0] for f in layout.fields]
        if "seeds" in names:  # the decode step: off the table, not the buffer
            # (copies: on the CPU ``jnp.asarray`` may alias the table's own
            # arrays, which the step advances before the program has run)
            t = eng.table
            fields = {"token_ids": jnp.asarray(t.next_tok.copy()),
                      "position_ids": jnp.asarray(t.ctx.copy()),
                      "context_lens": jnp.asarray(
                          ((t.ctx + 1) * t.active).astype(np.int32)),
                      "temps": jnp.asarray(np.where(
                          t.temp >= 0.0, t.temp,
                          np.float32(eng.step_temperature))
                          .astype(np.float32)),
                      "seeds": jnp.asarray(t.seed.copy()),
                      "block_tables": jnp.asarray(t.block_tables.copy())}
            if t.win_tables is not None:
                fields["win_tables"] = jnp.asarray(t.win_tables.copy())
            if "row_adapter" in names:
                fields["row_adapter"] = jnp.asarray(t.adapter.copy())
        else:
            fields = {n: jnp.asarray(v.copy())
                      for n, v in layout.views(buf).items()}
        return fields

    def step_rng(rng):
        if rng is None:
            eng._rng, rng = jax.random.split(eng._rng)
        return rng

    eng._to_device, eng._step_rng = to_device, step_rng
    eng._split_ahead = lambda: None
    return eng


# requests (prompt length, new tokens, temperature, seed, adapter slot) that
# arrive at the given step: decode steps and mixed steps alternate, rows come
# and go, greedy rows sit beside sampled ones (pinned and inherited
# temperatures)
_ARRIVALS = {
    0: ((30, 12, None, 0, 0), (5, 22, 0.8, 7, 1)),
    6: ((11, 9, 0.0, 0, 0),),
    13: ((40, 8, 1.3, 3, 1), (3, 18, None, 9, 0)),
    30: ((7, 14, 0.5, 1, 0),),
    44: ((26, 9, None, 2, 1), (2, 15, 0.9, 5, 0)),
}


def _drive(eng, adapters, watch=None, temps=None, late=()):
    """Run ``_ARRIVALS`` to the end → ([(kind, {uid: tokens})] a step, the
    step keys in order, the step-level temperature each step ran under).
    ``watch(eng)`` wraps what it wants to observe.  A step that was
    dispatched ahead (ISSUE 50) ran under the temperature of the call that
    dispatched it, the call before, and a request put while such a step was
    under way was admitted by the step after it: ``temps`` hands a second
    engine, which keeps one step in flight, the temperatures the first one's
    steps ran under, and ``late`` the arrivals to put a step later, so that
    both admit every request in the same step (a sampled row draws under
    its step's key)."""
    from deepspeed_tpu.inference.v2 import engine as engine_mod

    keys, steps = [], []
    decode_fwd, sample = eng._decode_fwd, engine_mod.sample_rows

    def decode(params, caches, *args):
        keys.append(np.asarray(args[5]))
        return decode_fwd(params, caches, *args)

    def sample_rows(logits, temps, rng, seeds):
        keys.append(np.asarray(rng))
        return sample(logits, temps, rng, seeds)

    eng._decode_fwd = decode
    engine_mod.sample_rows = sample_rows
    if watch:
        watch(eng)
    rs = np.random.default_rng(2)
    ran_under, found_one = [], []
    try:
        n = 0
        while n <= max(_ARRIVALS) + 1 or eng.running or eng.waiting:
            arrive = (_ARRIVALS.get(n, ()) if n not in late else ()) + (
                _ARRIVALS[n - 1] if n - 1 in late else ())
            if arrive and eng._ahead is not None:
                found_one.append(n)
            for length, new, temp, seed, slot in arrive:
                eng.put(rs.integers(1, 200, length).tolist(),
                        max_new_tokens=new, temperature=temp, seed=seed,
                        adapter_slot=slot if adapters else 0)
            # the step-level temperature, which rows without one inherit:
            # three steps each, so that a decode step can run on what the
            # step before staged, and the next change throws a staging away
            under_way = eng._ahead is not None
            before = getattr(eng, "step_temperature", None)
            eng.step_temperature = (temps[n] if temps is not None
                                    else 0.7 if n // 3 % 2 else 0.0)
            ran_under.append(before if under_way else eng.step_temperature)
            steps.append(eng.step(temperature=eng.step_temperature))
            n += 1
    finally:
        engine_mod.sample_rows = sample
    return steps, keys, ran_under, found_one


@pytest.fixture(scope="module", params=list(_LAYOUTS))
def one_copy_run(request, devices):
    """The run of ``_ARRIVALS`` on an engine of each layout, watched, and the
    same run on its twin that brings the inputs over the parent's way."""
    from deepspeed_tpu.inference.v2 import programs as programs_mod
    from deepspeed_tpu.inference.v2.programs import build_unpack
    from deepspeed_tpu.observability.trace import tracer

    name = request.param
    build = _one_copy_engine(name)
    seen = {"copies": [], "staged": [], "ahead": [], "explicit": [],
            "bufs": []}

    def watch(eng):
        # from a step's start to the call of its program: implicit copies
        # (a NumPy array handed to a jitted program) are refused, explicit
        # ones (jax.device_put, jnp.asarray, jnp.array) counted, and the one
        # copy function allowed its one; the copy a step makes for the NEXT
        # decode step, be it once its own work is done (``_stage_next``) or
        # with that step's program, ahead of its own fetch (``out``: the
        # tokens it has not fetched yet), is counted apart
        to_device, impl, stage = (eng._to_device, eng._step_impl,
                                  eng._stage_next)
        programs = {"_fwd": eng._fwd, "_decode_fwd": eng._decode_fwd}
        guard = [None]

        def shut():
            seen["open"] = False
            if guard[0] is not None:
                guard[0].__exit__(None, None, None)
                guard[0] = None

        def counted(layout, buf, out=None):
            seen["bufs"].append((type(buf), buf.dtype, buf.nbytes))
            seen["staged" if seen.get("staging") else
                 "copies" if out is None else "ahead"][-1] += 1
            with jax.transfer_guard_host_to_device("allow"):
                return to_device(layout, buf, out)

        def stage_next(*args):
            seen["staging"] = True
            try:
                return stage(*args)
            finally:
                seen["staging"] = False

        def step_impl(*args):
            seen["copies"].append(0)
            seen["staged"].append(0)
            seen["ahead"].append(0)
            seen["explicit"].append(0)
            guard[0] = jax.transfer_guard_host_to_device("disallow")
            guard[0].__enter__()
            seen["open"] = True
            try:
                return impl(*args)
            finally:
                shut()

        def program(fn):
            def call(*args):
                shut()  # the step's program is being called
                return fn(*args)
            return call

        def sample(*args):
            # a mixed program's sampler: behind its call, or, where the
            # program was called ahead (ISSUE 54), at the head of the step
            # that takes it; its two arguments are no step's inputs
            was, seen["open"] = seen.get("open"), False
            try:
                with jax.transfer_guard_host_to_device("allow"):
                    return sampler(*args)
            finally:
                seen["open"] = was

        sampler, eng._sample = eng._sample, sample
        eng._to_device, eng._step_impl = counted, step_impl
        eng._stage_next = stage_next
        for attr, fn in programs.items():
            setattr(eng, attr, program(fn))

    explicit = {}
    for mod, fn in ((jax, "device_put"), (jnp, "asarray"), (jnp, "array")):
        real = getattr(mod, fn)

        def counting(*a, _real=real, **k):
            import sys
            if (seen.get("open") and sys._getframe(1).f_globals.get(
                    "__name__", "").startswith("deepspeed_tpu.inference.v2")):
                seen["explicit"][-1] += 1
            return _real(*a, **k)

        explicit[(mod, fn)] = real
        setattr(mod, fn, counting)
    tracer.clear()
    thread = threading.current_thread().name
    try:
        eng = build()
        # this run's own unpack programs: an engine of another model with the
        # same layout (plain / state_slots) shares them by the memo, and its
        # decode program's output has another shape
        for layout in (eng._decode_layout, eng.builder.layout):
            programs_mod._BUILD_CACHE.pop(("unpack", layout), None)
        steps, keys, temps, late = _drive(eng, name == "adapters", watch)
    finally:
        for (mod, fn), real in explicit.items():
            setattr(mod, fn, real)
    spans = [s for s in tracer.spans()
             if s.thread == thread and s.name == "engine/step"]
    ref_steps, ref_keys, _, _ = _drive(_parents_argument_path(build()),
                                       name == "adapters", temps=temps,
                                       late=late)
    sizes = {step: build_unpack(layout)._cache_size()
             for step, layout in (("decode", eng._decode_layout),
                                  ("mixed", eng.builder.layout))}

    return dict(name=name, eng=eng, steps=steps, keys=keys, spans=spans,
                late=late,
                ref_steps=ref_steps, ref_keys=ref_keys, seen=seen,
                unpack_programs=sizes)


def test_a_step_makes_one_host_to_device_copy(one_copy_run):
    """A decode step's and a mixed step's program each run on exactly one
    host-to-device copy: the step's span says so (``h2d_copies``,
    ``h2d_bytes``: the buffer's), the one copy function was called once for
    it with one NumPy int32 buffer (in the step, or at the end of the step
    before for a decode step that says ``staged="used"``), and between a
    step's start and its program nothing else was copied, neither
    explicitly nor by handing a jitted program a NumPy array."""
    run = one_copy_run
    eng = run["eng"]
    ran = ["device_ms" in s.attrs for s in run["spans"]]  # not an idle step
    spans = [s for s, on in zip(run["spans"], ran) if on]
    kinds = [s.attrs["kind"] for s in spans]
    assert len(spans) >= 50 and {"decode", "mixed"} == set(kinds)
    assert kinds.count("decode") >= 10 and kinds.count("mixed") >= 6
    nbytes = {"decode": eng._decode_layout.size * 4,
              "mixed": eng.builder.layout.size * 4}
    for s in spans:
        assert s.attrs["h2d_copies"] == 1
        assert s.attrs["h2d_bytes"] == nbytes[s.attrs["kind"]]
    # a step that runs on the staged copy makes none before its program,
    # nor does one that found its program under way (ISSUE 50): that one's
    # copy was made in the step before, with its program, ahead of that
    # step's own fetch
    used = [s.attrs.get("staged") == "used" for s in run["spans"]]
    found = [s.attrs.get("ahead") == 1 for s in run["spans"]]
    assert [s.attrs.get("staged") == "ahead" for s in run["spans"]] == [
        f and s.attrs["kind"] == "decode" for f, s in zip(found,
                                                          run["spans"])]
    assert run["seen"]["copies"] == [int(on and not u and not f)
                                     for on, u, f in zip(ran, used, found)]
    assert used.count(True) >= 5 and not used[0]
    assert found.count(True) >= 10 and not found[0]
    assert all(run["seen"]["staged"][i - 1] == 1
               for i, u in enumerate(used) if u)
    assert run["seen"]["ahead"] == found[1:] + [False]
    assert run["seen"]["ahead"] == [s.attrs.get("ahead_next", 0)
                                    for s in run["spans"]]
    assert set(run["seen"]["staged"]) == {0, 1}
    assert not any(a and st for a, st in zip(run["seen"]["ahead"],
                                             run["seen"]["staged"]))
    assert run["seen"]["explicit"] == [0] * len(ran)
    # the buffers in the order they were copied: a step's own, then the one
    # of the step it called ahead (of that step's kind) or the one it staged
    order = []
    kinds_after = [s.attrs["kind"] for s in run["spans"][1:]] + [None]
    for s, nxt, own, ahead, staged in zip(
            run["spans"], kinds_after, run["seen"]["copies"],
            run["seen"]["ahead"], run["seen"]["staged"]):
        order += ([s.attrs["kind"]] * own + [nxt] * ahead
                  + ["decode"] * staged)
    assert len(order) == len(run["seen"]["bufs"])
    assert all(b == (np.ndarray, np.dtype(np.int32), nbytes[k])
               for b, k in zip(run["seen"]["bufs"], order))


def test_tokens_and_step_keys_are_the_parents(one_copy_run):
    """Over a run that mixes decode and mixed steps, greedy and sampled
    rows, every step emits the tokens it emits when its inputs are brought
    over the parent's way, and the steps' keys are the same keys in the same
    order: the split moved to the device, the stream did not."""
    run = one_copy_run
    assert run["late"]  # some arrival found a step dispatched ahead
    assert len(run["steps"]) == len(run["ref_steps"])
    assert run["steps"] == run["ref_steps"]
    # a key a step that ran the device
    assert len(run["keys"]) == sum("device_ms" in s.attrs
                                   for s in run["spans"])
    np.testing.assert_array_equal(np.stack(run["keys"]),
                                  np.stack(run["ref_keys"]))
    assert len({k.tobytes() for k in run["keys"]}) == len(run["keys"])
    sampled = [t for out in run["steps"] for t in out.values()]
    assert sampled  # and the sampled rows did draw: greedy alone differs
    host = jax.random.PRNGKey(0)
    for key in run["keys"][:5]:  # the engine's stream from its first key
        host, want = jax.random.split(host)
        np.testing.assert_array_equal(key, np.asarray(want))


def test_the_unpack_programs_compile_once(one_copy_run):
    """Fifty steps and more with rows coming and going, steps of either kind
    called behind either: one compiled unpack program for the decode steps
    and one for the mixed steps, each handed the latest program's tokens
    beside the buffer whether the buffer points into them or not; and the
    shape without them of an engine's very first step, which has none.  No
    shape waits for the traffic to bring its case."""
    assert len(one_copy_run["keys"]) >= 50
    assert one_copy_run["unpack_programs"] == {"decode": 1, "mixed": 2}


def test_a_callers_key_is_used_as_it_is(devices, tiny_model):
    """``step(rng=...)``: the step's sampler draws under the caller's key,
    and the engine's own stream goes on from where it was."""
    cfg, params = tiny_model
    eng = InferenceEngineV2(cfg, params, V2Config(
        max_tokens_per_step=16, max_seqs=4, block_size=8, num_blocks=64,
        max_blocks_per_seq=8, dtype="float32"))
    eng.put([7, 8, 9], max_new_tokens=5, temperature=0.9)
    seen = []
    decode_fwd = eng._decode_fwd
    eng._decode_fwd = lambda p, c, *a: (seen.append(np.asarray(a[5])),
                                        decode_fwd(p, c, *a))[1]
    mine = jax.random.PRNGKey(123)
    eng.step(rng=mine)  # mixed
    eng.step(rng=mine)  # decode
    np.testing.assert_array_equal(seen[0], np.asarray(mine))
    eng.step()  # without one: the first two keys of the engine's stream
    eng.step()
    host = jax.random.PRNGKey(0)
    for key in seen[1:]:
        host, want = jax.random.split(host)
        np.testing.assert_array_equal(key, np.asarray(want))


@pytest.mark.parametrize("name", list(_LAYOUTS))
def test_the_step_programs_are_called_as_the_harness_expects(devices, name):
    """The benchmark's ``correct`` phase and its traced run replace
    ``engine._decode_fwd`` and ``engine._fwd`` (``benchmark/logit_tap.py``,
    ``logit_tap_donated.py``, ``routing_tap.py``, ``serve_moe.StepProgram``)
    and read their arguments by position: a stand-in for those wrappers sees
    the decode program called with ``(params, caches, token_ids,
    position_ids, block_tables, context_lens, temps, rng, seeds)`` (and the
    adapter pair behind them), every leaf an array with ``.shape`` and
    ``.dtype``, ``args[:4]`` what ``_decode_body`` takes, and ``(tokens,
    caches)`` back; the mixed program with its ten (a state model's
    ``state_slots`` behind two ``None``)."""
    from deepspeed_tpu.inference.v2.engine import _decode_body

    eng = _one_copy_engine(name)()
    S, B, T = _ROWS, _BLOCKS, _TOKENS
    i32, f32 = jnp.int32, jnp.float32
    table = ((S, B), i32)
    tables = (table, table) if name == "two_tables" else table
    calls = {"decode": [], "mixed": []}
    fwd, decode_fwd = eng._fwd, eng._decode_fwd

    def shapes(args):
        return jax.tree.map(
            lambda a: None if a is None else (a.shape, a.dtype), args,
            is_leaf=lambda a: a is None)

    def tapped_decode(params, caches, *args):
        logits = jax.jit(lambda p, c, *a: _decode_body(
            p, c, *a, eng.model_cfg, eng.cfg)[0])(params, caches, *args[:4])
        out = decode_fwd(params, caches, *args)
        calls["decode"].append((shapes(args), logits.shape, len(out),
                                out[0].shape, sorted(out[1])))
        return out

    def tapped_fwd(params, caches, *args):
        out = fwd(params, caches, *args)
        calls["mixed"].append((shapes(args), out[0].shape, sorted(out[2])))
        return out

    eng._fwd, eng._decode_fwd = tapped_fwd, tapped_decode
    eng.put(list(range(1, 31)), max_new_tokens=3,
            adapter_slot=1 if name == "adapters" else 0)
    while eng.running or eng.waiting:
        eng.step()
    rows = ((S,), i32)
    stack = shapes(eng.adapter_stack) if name == "adapters" else None
    decode_args = (rows, rows, tables, rows, ((S,), f32),
                   ((2,), jnp.uint32), rows)
    mixed_args = (((T,), i32),) * 3 + (tables,) + (rows,) * 4
    if name == "adapters":
        decode_args += (stack, rows)
        mixed_args += (stack, rows)
    if name == "state_slots":
        mixed_args += (None, None, rows)
    vocab = eng.model_cfg.vocab_size
    pools = sorted(eng.caches)
    moe = 2 if eng.model_cfg.num_experts else 0  # the stats behind the rows
    assert calls["decode"] and calls["mixed"]
    for got in calls["decode"]:
        assert got == (decode_args, (S, vocab), 2, (S + moe,), pools)
    for got in calls["mixed"]:
        assert got == (mixed_args, (S, vocab), pools)
