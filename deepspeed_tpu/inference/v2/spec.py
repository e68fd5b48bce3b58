"""Speculative decoding for the ragged v2 engine: in-graph draft/verify.

Two propose paths share one verify/accept core (Leviathan et al., "Fast
Inference from Transformers via Speculative Decoding", 2023):

* **draft-model** — a small model autoregressively proposes ``k`` tokens
  through its own paged KV cache (same block tables as the target, its own
  block pool array), then the target verifies all ``k+1`` positions in ONE
  multi-position ragged forward;
* **self-draft** — Medusa/EAGLE-style extra decode heads
  (``linear/spec_heads.py``) applied to the carried last-accepted hidden
  state propose all ``k`` tokens in one shot, no second model.

The whole propose → verify → accept/correct loop is ONE jitted program per
step: acceptance is computed with ``lax`` masks (no host sync), both KV
caches are donated and updated in place, and the host only reads back the
emitted tokens + accept lengths.  Greedy acceptance keeps the output
token-identical to non-speculative decode; sampled acceptance implements
the full accept/residual-resample scheme, which preserves the target
distribution exactly for any proposal distribution.

Rejected-suffix KV needs **no device-side rollback**: speculative writes
land at positions ``ctx .. ctx+k`` inside blocks the sequence already owns
(admission reserves the full budget), stale entries beyond the accepted
length are masked by ``context_lens`` in every later attention, and the
next step overwrites them starting at the new ``ctx``.  Rollback is
host-side bookkeeping only, so prefix-cache block sharing (refcounted
``BlockedAllocator``) is untouched.  Writes that would run past the
sequence's lifetime block reservation (``pos_limit = prompt + max_new``)
are parked in the scratch block — they can never touch another sequence's
blocks through a zeroed block-table entry.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ...linear.spec_heads import apply_spec_heads
from ...models import transformer as tfm
from ...ops.pallas.paged_attention import paged_prefill_attention
from .programs import _decode_body, _memo, _row_keys, serving_layers


def _leading_accepts(accept: jax.Array) -> jax.Array:
    """(S, k) bool accept flags → (S,) length of the leading all-True run."""
    return jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)


def _take_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x (S, Q, ...) gathered at per-row position idx (S,) → (S, ...)."""
    return jnp.take_along_axis(
        x, idx.reshape((-1,) + (1,) * (x.ndim - 1)), axis=1)[:, 0]


def verify_body(params, caches, tokens, ctx, block_tables, pos_limit,
                model_cfg: tfm.TransformerConfig, v2,
                adapters=None, row_adapter=None):
    """Multi-position decode forward: the target model processes ``Q = k+1``
    consecutive positions per sequence in one pass over the paged KV cache.

    ``adapters``/``row_adapter`` (optional): stacked per-slot LoRA factors
    and the (S,) per-row slot vector — verification reads the SAME
    adapter-augmented target the decode path serves, so acceptance is
    against each tenant's own model (slot 0 rows see a zero delta).

    ``tokens`` (S, Q): position ``ctx+j`` gets ``tokens[:, j]``; row ``s`` is
    active iff ``ctx[s] > 0``.  Writes at ``pos >= pos_limit`` park in the
    scratch block (the sequence's reservation ends there — a real write
    would dereference a zeroed block-table entry).  Attention covers keys
    ``< min(ctx+Q, pos_limit)``; logits rows at parked positions are
    garbage the caller must not use (the engine's budget clamp guarantees
    it never does).

    Returns (logits (S, Q, V) f32, hidden (S, Q, H), caches).
    """
    bs = v2.block_size
    Q = tokens.shape[1]
    pos = ctx[:, None] + jnp.arange(Q)[None, :]  # (S, Q)
    active = ctx > 0
    write_ok = active[:, None] & (pos < pos_limit[:, None])
    # one pool: the engine refuses speculation for a model whose layers
    # keep two (a window layer's table has no room for k tokens ahead)
    blk_col = jnp.clip(pos // bs, 0, block_tables.shape[1] - 1)
    blk_ids = (jnp.where(write_ok,
                         jnp.take_along_axis(block_tables, blk_col, axis=1),
                         caches["k"].shape[1] - 1),)
    # attention window per row: chunk [ctx, ctx+chunk_len) — clipped at the
    # reservation so parked (unwritten) key slots are never read
    chunk_len = jnp.where(active,
                          jnp.clip(pos_limit - ctx, 0, Q), 0).astype(jnp.int32)

    def attend(q, k_cache, v_cache, layer, kind):
        # the (S, Q) queries flat, row s from token s * Q on: a row whose
        # reservation ends inside its Q leaves a gap, which comes out zero
        with jax.named_scope("prefill_attention"):
            return paged_prefill_attention(
                q.reshape((-1,) + q.shape[2:]), k_cache, v_cache, layer,
                block_tables, jnp.arange(q.shape[0], dtype=jnp.int32) * Q,
                ctx * active, chunk_len, window=kind.window).reshape(q.shape)

    x = tfm.embed_tokens(params, tokens, model_cfg, position_ids=pos)  # (S,Q,H)
    x, caches, _ = serving_layers(
        params, caches, x, pos, (blk_ids, pos % bs), attend, model_cfg, v2,
        adapters, row_adapter)
    return tfm.lm_logits(params, x, model_cfg).astype(jnp.float32), x, caches


def _accept_and_emit(logits, draft, draft_probs, rng, temps, seeds):
    """The accept/correct core shared by both propose paths — per row.

    logits (S, k+1, V) f32 — target logits at positions ctx..ctx+k;
    draft (S, k) int32 — proposed tokens for positions ctx+1..ctx+k;
    draft_probs (S, k, V) f32 — the proposal distributions the drafts were
    actually sampled from (ignored for greedy rows);
    temps/seeds (S,) — per-row temperature and request seed.

    Greedy rows (``temps <= 0``): accept the longest prefix where the draft
    matches the target argmax; the token after it is the target's own
    argmax — output is token-identical to non-speculative greedy decode.

    Sampled rows: accept ``d_i`` with prob ``min(1, p_i(d_i)/q_i(d_i))``;
    on the first rejection sample the correction from
    ``norm(max(p_i - q_i, 0))``; if all accepted, sample the bonus from
    ``p_k`` — exactly the target distribution, per the
    speculative-sampling identity.  Both lanes are computed and selected
    per row with ``jnp.where`` (no scalar ``cond`` — one batch can mix
    greedy and sampled rows with zero host syncs).

    Returns (emitted (S, k+1) int32, accept_len (S,) int32) where
    ``emitted[:, :a+1]`` = accepted drafts + 1 correction/bonus token.
    """
    S, Qk, _ = logits.shape
    k = Qk - 1

    # greedy lane — untouched math, so greedy rows stay bit-identical
    g = logits.argmax(-1).astype(jnp.int32)  # (S, k+1)
    a_g = _leading_accepts(draft == g[:, :k]) if k else \
        jnp.zeros((S,), jnp.int32)
    fin_g = _take_rows(g, a_g)

    # sampled lane — per-row keys (fold_in of request seed + row index)
    u_rng, fix_rng = jax.random.split(rng)
    p = jax.nn.softmax(logits / jnp.maximum(temps, 1e-6)[:, None, None],
                       axis=-1)
    if k:
        q = draft_probs
        p_d = jnp.take_along_axis(p[:, :k], draft[..., None], -1)[..., 0]
        q_d = jnp.take_along_axis(q, draft[..., None], -1)[..., 0]
        u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(
            _row_keys(u_rng, seeds))
        a_s = _leading_accepts(u * q_d < p_d)
        # correction dist at every position, then select position a:
        # i < k → norm(max(p_i − q_i, 0)) (fallback p_i if zero mass);
        # i = k → p_k (bonus)
        res = jnp.maximum(p[:, :k] - q, 0.0)
        mass = res.sum(-1, keepdims=True)
        res = jnp.where(mass > 0, res / jnp.maximum(mass, 1e-20),
                        p[:, :k])
        res = jnp.concatenate([res, p[:, k:]], axis=1)  # (S, k+1, V)
    else:
        a_s = jnp.zeros((S,), jnp.int32)
        res = p
    fix = jax.vmap(jax.random.categorical)(
        _row_keys(fix_rng, seeds),
        jnp.log(_take_rows(res, a_s) + 1e-20)).astype(jnp.int32)

    sampled_row = temps > 0.0
    a = jnp.where(sampled_row, a_s, a_g).astype(jnp.int32)
    final = jnp.where(sampled_row, fix, fin_g)
    cols = jnp.arange(k + 1)[None, :]
    d_pad = jnp.concatenate([draft, jnp.zeros((S, 1), jnp.int32)], axis=1)
    emitted = jnp.where(cols < a[:, None], d_pad, final[:, None])
    return emitted.astype(jnp.int32), a


def build_self_draft_step(model_cfg: tfm.TransformerConfig, v2):
    """Self-draft (Medusa-style) speculative step, jitted once.

    ``last_hidden`` (S, H) is the target's final-norm hidden state at the
    position just before the pending token (the state whose lm-head argmax
    produced ``next_tok``) — head ``i`` applied to it proposes the token at
    offset ``i+2``, i.e. drafts for positions ``ctx+1 .. ctx+k``.

    Returns (emitted (S, k+1), accept_len (S,), new_hidden (S, H), caches).
    """
    def spec_step(params, heads, caches, next_tok, ctx, block_tables,
                  pos_limit, last_hidden, rng, temps, seeds, *adapter_args):
        head_logits = apply_spec_heads(heads, last_hidden)  # (S, k, V) f32
        d_rng, v_rng = jax.random.split(rng)
        q = jax.nn.softmax(
            head_logits / jnp.maximum(temps, 1e-6)[:, None, None], -1)
        cat = jax.vmap(lambda kk, lg: jax.random.categorical(kk, lg, axis=-1))(
            _row_keys(d_rng, seeds), jnp.log(q + 1e-20)).astype(jnp.int32)
        draft = jnp.where((temps > 0.0)[:, None], cat,
                          head_logits.argmax(-1).astype(jnp.int32))
        tokens = jnp.concatenate([next_tok[:, None], draft], axis=1)
        # the heads propose adapter-less; verification runs the adapter-
        # augmented target, so greedy rows still emit the (per-tenant)
        # target argmax — identity holds, only acceptance rate moves
        logits, hidden, caches = verify_body(
            params, caches, tokens, ctx, block_tables, pos_limit,
            model_cfg, v2, *adapter_args)
        emitted, a = _accept_and_emit(logits, draft, q, v_rng, temps, seeds)
        new_hidden = _take_rows(hidden, a).astype(jnp.float32)  # (S, H)
        return emitted, a, new_hidden, caches

    return _memo(("spec_self_draft", model_cfg, dataclasses.astuple(v2)),
                 lambda: jax.jit(spec_step, donate_argnums=(2,)))


def build_draft_spec_step(model_cfg: tfm.TransformerConfig,
                          draft_cfg: tfm.TransformerConfig, v2):
    """Draft-model speculative step, jitted once.

    The draft scan runs ``k+1`` single-token decodes through the DRAFT
    paged cache (shared block tables, separate pool array): iterations
    ``0..k-1`` propose ``d_1..d_k``; iteration ``k`` only writes ``d_k``'s
    draft KV so the draft cache stays complete when all ``k`` drafts are
    accepted (next step starts at ``ctx+k+1``).  Rejected-suffix draft KV
    is stale-but-masked, same as the target cache.

    Returns (emitted (S, k+1), accept_len (S,), caches, draft_caches).
    """
    k = v2.spec_k

    def spec_step(params, draft_params, caches, draft_caches, next_tok, ctx,
                  block_tables, pos_limit, rng, temps, seeds):
        active = ctx > 0
        sampled_row = temps > 0.0

        def draft_iter(carry, i):
            dcaches, tok, it_rng = carry
            pos = ctx + i
            ok = active & (pos < pos_limit)
            dlogits, dcaches, _ = _decode_body(
                draft_params, dcaches, tok, pos, block_tables,
                (pos + 1) * ok, draft_cfg, v2)
            it_rng, s_rng = jax.random.split(it_rng)
            qi = jax.nn.softmax(
                dlogits / jnp.maximum(temps, 1e-6)[:, None], axis=-1)
            cat = jax.vmap(jax.random.categorical)(
                _row_keys(s_rng, seeds),
                jnp.log(qi + 1e-20)).astype(jnp.int32)
            nxt = jnp.where(sampled_row, cat,
                            dlogits.argmax(-1).astype(jnp.int32))
            return (dcaches, nxt, it_rng), (nxt, qi)

        d_rng, v_rng = jax.random.split(rng)
        (draft_caches, _, _), (proposals, qs) = jax.lax.scan(
            draft_iter, (draft_caches, next_tok, d_rng), jnp.arange(k + 1))
        draft = proposals[:k].T  # (S, k): d_1..d_k (last iter writes KV only)
        q = jnp.moveaxis(qs[:k], 0, 1)  # (S, k, V)
        tokens = jnp.concatenate([next_tok[:, None], draft], axis=1)
        logits, _, caches = verify_body(
            params, caches, tokens, ctx, block_tables, pos_limit,
            model_cfg, v2)
        emitted, a = _accept_and_emit(logits, draft, q, v_rng, temps, seeds)
        return emitted, a, caches, draft_caches

    return _memo(("spec_draft", model_cfg, draft_cfg,
                  dataclasses.astuple(v2)),
                 lambda: jax.jit(spec_step, donate_argnums=(2, 3)))
