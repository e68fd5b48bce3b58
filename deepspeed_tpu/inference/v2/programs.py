"""The v2 engine's step programs: what is traced and runs on the chip.

What KIND of model is served is decided once (``kind_of`` → ``ServedKind``:
what it caches, how its layers run, what a step's rows tell them, what it
is refused, what its step counts).  ``serving_layers`` is the kind's body
(``attention_layers``, ``hybrid_layers``, ``latent_layers``, ``eva_layers``,
``linear_latent_layers``) under three callers: the mixed step (``build_ragged_forward``: chunks of prefill and
decode tokens in one ragged batch), the decode step (``_decode_body``: one
token a row) and the verify step of speculation (``spec.py:verify_body``:
``Q`` positions a row); its docstring is the contract of a caller.  The
head is ``tfm.lm_logits``.

This module imports neither ``engine.py`` (allocator, scheduler, prefix
cache, paging, adapters: the host side) nor ``spec.py``; both import it.
``V2Config`` reaches the builders as a value only (attribute reads,
``dataclasses.astuple``).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp

from ...models import latent_sparse, selective_ssm, ssm_hybrid
from ...models import transformer as tfm
from ...moe.dropless import serving_moe_block
from ...ops.pallas import latent_attention
from ...ops.pallas.mixed_gemm import LayerOf, QuantizedWeight
from ...ops.pallas.selective_scan import selective_decode_update
from ...ops.pallas.ssm import ssm_decode_update
from ...ops.pallas.paged_attention import (PrefillTiles,
                                           paged_decode_attention,
                                           paged_prefill_attention,
                                           pick_prefill_tiles)
from .ragged import window_bound

# Built forward functions are memoized per (builder, configs): every engine
# over the same shapes — serving replicas, test fixtures — shares ONE jitted
# callable, so XLA compiles each program once per process instead of once
# per engine.  Params/caches are call arguments, never closed over, so
# sharing is safe (donation is per-call).
_BUILD_CACHE: dict = {}


def _memo(key, build):
    if key not in _BUILD_CACHE:
        _BUILD_CACHE[key] = build()
    return _BUILD_CACHE[key]


# ---------------------------------------------------------------------------
# per-row sampling (in-graph: the decode programs emit token ids, not logits)
# ---------------------------------------------------------------------------


def _row_keys(rng, seeds):
    """One PRNG key per row: fold the request seed AND the row index into
    the step key.  Folding the row index means two requests that picked the
    same seed still draw independently within a batch; folding the request
    seed means a request's sample stream survives row reassignment."""
    rows = jnp.arange(seeds.shape[0])
    return jax.vmap(
        lambda s, r: jax.random.fold_in(jax.random.fold_in(rng, s), r)
    )(seeds, rows)


def sample_rows(logits, temps, rng, seeds):
    """Per-row next-token selection: rows with ``temps <= 0`` take the
    argmax (bit-identical to the pre-vectorization greedy path — the same
    f32 logits through the same argmax); rows with ``temps > 0`` draw from
    ``categorical(logits / temp)`` under their own fold_in key.  Both lanes
    are computed and selected with ``jnp.where`` — no host sync, no
    per-row control flow."""
    with jax.named_scope("sampler"):
        greedy = logits.argmax(-1).astype(jnp.int32)
        keys = _row_keys(rng, seeds)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(
            keys, scaled).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)



def _adapter_proj_delta(x, ab, slots):
    """Per-row gathered low-rank delta for one projection: row ``s`` adds
    ``(x_s @ A[slots_s]) @ B[slots_s]`` (scaling folded into B at load).

    ``x``: (S, K) or (S, Q, K) activations; ``ab``: this layer's stacked
    factors {"a": (slots, K, r), "b": (slots, r, N)}; ``slots``: (S,)
    int32.  Gather + two thin batched matmuls — in-graph, no host sync;
    rows on the all-zero null slot add an exact zero."""
    a_sel = ab["a"][slots]  # (S, K, r)
    b_sel = ab["b"][slots]  # (S, r, N)
    if x.ndim == 2:
        return jnp.einsum("sr,srn->sn",
                          jnp.einsum("sk,skr->sr", x, a_sel), b_sel)
    return jnp.einsum("sqr,srn->sqn",
                      jnp.einsum("sqk,skr->sqr", x, a_sel), b_sel)



# ---------------------------------------------------------------------------
# ragged forward (jitted once; static shapes from V2Config)
# ---------------------------------------------------------------------------


def ragged_attention_xla(q, k_cache, v_cache, block_tables, context_lens,
                         seq_index, position_ids, cfg: tfm.TransformerConfig,
                         block_size: int, window: int = 0):
    """Correct-for-everything gather path. q: (T, H, D); caches
    (num_blocks, bs, KV, D); returns (T, H, D).  ``window`` (0: none): a
    token sees the keys less than ``window`` positions behind it."""
    T, H, D = q.shape
    KV = k_cache.shape[2]
    max_blocks = block_tables.shape[1]
    S_max = max_blocks * block_size

    # gather each sequence's cache: (max_seqs, S_max, KV, D)
    k_seq = k_cache[block_tables].reshape(block_tables.shape[0], S_max, KV, D)
    v_seq = v_cache[block_tables].reshape(block_tables.shape[0], S_max, KV, D)
    # per-token views (T, S_max, KV, D)
    row = jnp.clip(seq_index, 0, block_tables.shape[0] - 1)
    k_t = k_seq[row]
    v_t = v_seq[row]
    if KV != H:
        rep = H // KV
        k_t = jnp.repeat(k_t, rep, axis=2)
        v_t = jnp.repeat(v_t, rep, axis=2)
    scores = jnp.einsum("thd,tshd->ths", q.astype(jnp.float32),
                        k_t.astype(jnp.float32)) / math.sqrt(D)
    key_pos = jnp.arange(S_max)[None, None, :]
    valid = key_pos <= position_ids[:, None, None]  # causal within sequence
    valid &= key_pos < context_lens[row][:, None, None]
    if window:
        valid &= key_pos > position_ids[:, None, None] - window
    valid &= (seq_index >= 0)[:, None, None]
    scores = jnp.where(valid, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ths,tshd->thd", probs, v_t.astype(jnp.float32))
    return out.astype(q.dtype)


def _ffn(m_in, lp, model_cfg: tfm.TransformerConfig, valid=None):
    """The feed-forward half of a serving layer, the one place the three step
    bodies (mixed, decode, verify) get it: ``m_in (..., H)`` → ``(out, moe
    stats or None)``.  An MoE model runs ``moe/dropless.serving_moe_block``:
    every top-k assignment is computed, so a row's output does not depend on
    the rows beside it.  ``valid`` marks the rows the stats count."""
    if model_cfg.num_experts > 0:
        return serving_moe_block(m_in, lp["moe"], model_cfg, valid=valid)
    if m_in.ndim == 2:  # as the dense programs were always traced: one
        # batch of all rows (a dense model's step programs stay bit for bit
        # the parent's, and hit its entries in the compile cache)
        return tfm._mlp_block(m_in[None], lp["mlp"], model_cfg)[0], None
    return tfm._mlp_block(m_in, lp["mlp"], model_cfg), None


def _moe_step_stats(per_layer):
    """Per-layer ``(L, 2)`` stats of ``_ffn`` → int32 ``(2,)`` for the step:
    experts hit summed over layers (the host divides by L), and the largest
    rows-per-expert of any layer.  None for a dense model."""
    if per_layer is None or per_layer.ndim == 1:  # a latent model's: made
        return per_layer
    stats = jnp.stack([per_layer[:, 0].sum(), per_layer[:, 1].max()])
    if per_layer.shape[1] > 2:  # cfg.moe_tap_choices: tooling only
        stats = jnp.concatenate([stats, per_layer[:, 2:].reshape(-1)])
    return stats


def _with_stats(tokens, moe_stats):
    """An MoE model's step stats behind the sampled tokens, in the one int32
    array the step fetches anyway: ``(max_seqs,)`` for a dense model,
    ``(max_seqs + 2,)`` for an MoE model."""
    if moe_stats is None:
        return tokens
    return jnp.concatenate([tokens, moe_stats])


# ---------------------------------------------------------------------------
# the serving decoder layer, written once
# ---------------------------------------------------------------------------


def hoist_quantized(layers):
    """Split a stacked ``layers`` tree for a layer scan: → (``xs``, what the
    scan slices: the tree's nodes with None for every ``QuantizedWeight``, be
    it an attention or MLP projection or the experts; ``layer_params(sliced,
    layer)``, which the scan's body calls for its layer's tree, with a
    ``LayerOf`` the whole stack at ``layer`` where a node was kept out:
    ``tfm._lin`` and the routed FFN hand those to kernels that read the stack
    in place)."""
    def is_q(node):
        return isinstance(node, QuantizedWeight)

    nodes, treedef = jax.tree.flatten(layers, is_leaf=is_q)

    def layer_params(sliced, layer):
        return treedef.unflatten([LayerOf(n, layer) if is_q(n) else s
                                  for n, s in zip(nodes, sliced)])

    return [None if is_q(n) else n for n in nodes], layer_params


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What is static about one layer of the pattern's period: its active
    ``window`` (0: none), the ``pool`` its K and V live in (0: ``k`` / ``v``,
    1: ``k_win`` / ``v_win``; also the index of its block table) and its
    RoPE."""
    window: int
    pool: int
    rope: tfm.RopeParams


def active_window(window: int, v2) -> int:
    """The one rule: a window counts only where it is smaller than the
    engine's longest context.  At or past it no query ever looks back that
    far, and the layer is served as full attention was, program for
    program."""
    return window if 0 < window < v2.max_blocks_per_seq * v2.block_size else 0


def layer_plan(model_cfg: tfm.TransformerConfig, v2) -> tuple:
    """One ``LayerKind`` a layer of ``model_cfg.layer_period``.  Layers
    without an active window share pool 0 with every layer when the model
    has one kind only; with both kinds the windowed layers get pool 1."""
    windows = [active_window(model_cfg.window_of(kind), v2)
               for kind in model_cfg.layer_period]
    two = len(set(windows)) > 1
    return tuple(LayerKind(w, int(two and w > 0), model_cfg.rope_of(kind))
                 for w, kind in zip(windows, model_cfg.layer_period))


def _kv_pool(c: tfm.TransformerConfig, v2, layers: int, blocks: int):
    """(shape, dtype) of a K or a V pool: the one geometry of a K/V block."""
    return ((layers, blocks, v2.block_size, c.kv_heads, c.head_dim),
            jnp.dtype(v2.dtype))


def kv_arrays(model_cfg: tfm.TransformerConfig, v2) -> dict:
    """What a model of attention + FFN layers caches: ``k`` / ``v`` for the
    layers of ``layer_plan``'s pool 0 (all, with one kind of layer) and,
    with both kinds, ``k_win`` / ``v_win`` for the windowed ones, of
    ``num_window_blocks`` blocks (0: what ``max_seqs`` sequences hold at
    most, ``ragged.window_bound``, and the scratch block)."""
    plan = layer_plan(model_cfg, v2)
    periods = model_cfg.num_layers // len(plan)

    def pool(p, blocks):
        return _kv_pool(model_cfg, v2,
                        periods * sum(k.pool == p for k in plan), blocks)

    arrays = {"k": pool(0, v2.num_blocks), "v": pool(0, v2.num_blocks)}
    if any(k.pool for k in plan):
        blocks = v2.num_window_blocks or 1 + v2.max_seqs * window_bound(
            max(k.window for k in plan), v2.max_tokens_per_step,
            v2.block_size, v2.max_blocks_per_seq)
        arrays.update(k_win=pool(1, blocks), v_win=pool(1, blocks))
    return arrays


def tables_of(block_tables) -> tuple:
    """A step program's ``block_tables`` argument as one table a pool: the
    array of a model with one pool, or the pair (global, windowed)."""
    return block_tables if isinstance(block_tables, tuple) else (block_tables,)


def pools_of(caches) -> list:
    """``caches`` as one (K, V) pair a pool (a latent model: its one pool of
    latents and the indexer's keys, which share the block table; an EVA
    model: the summaries', then the window's), in the tables' order."""
    if "latent" in caches:  # (a model without an indexer: the latents alone)
        return [(caches["latent"], caches.get("index"))]
    if "k_sum" in caches:
        return [(caches["k_sum"], caches["v_sum"]),
                (caches["k_win"], caches["v_win"])]
    return [(caches["k"], caches["v"])] + (
        [(caches["k_win"], caches["v_win"])] if "k_win" in caches else [])


def write_blocks(caches, block_tables, rows, positions, ok, block_size: int):
    """Where each of a step's rows writes its K and V, one array of block
    ids a pool: its table's entry for the row's position, or the pool's
    scratch block (its last) where ``ok`` is false."""
    return tuple(
        jnp.where(ok, table[rows, positions // block_size], k.shape[1] - 1)
        for table, (k, _) in zip(tables_of(block_tables), pools_of(caches)))


@dataclasses.dataclass(frozen=True)
class StepRows:
    """What a model with state layers has to know of a step's rows.  A decode
    step (``row is None``): rows are the engine's table rows, which ARE the
    state slots; ``active (R,)`` marks those that take the step and ``fresh``
    those at position 0.  A mixed step: token ``t`` is the ``offset[t]``-th
    of row ``row[t]`` (clipped for padding tokens, which ``valid`` excludes),
    whose ``row_len`` tokens begin at ``row_start``; ``slots (R,)`` is where
    each row's state lives (unused rows: the scratch slot, the last) and
    ``fresh`` marks the rows whose chunk starts a sequence: they start from
    zeros whatever the slot holds."""
    active: jax.Array
    fresh: jax.Array
    row: jax.Array = None
    offset: jax.Array = None
    row_start: jax.Array = None
    row_len: jax.Array = None
    slots: jax.Array = None
    valid: jax.Array = None


def state_arrays(model_cfg: tfm.TransformerConfig, v2) -> dict:
    """What a model of one mixer a layer caches: the one K/V pool of its
    attention layers, and beside it the per-sequence state of its Mamba-2
    layers: ``ssm (L_M, slots + 1, H, P, N)`` float32 and the conv's kept
    inputs ``conv (L_M, slots + 1, K - 1, C)`` in the activation dtype
    (columns on the lanes: a last dimension of K - 1 = 3 would be padded to
    128 in HBM).  A slot a row of the engine's table, and one scratch slot.
    A model of Mamba-1 layers keeps ``ssm (L_S, slots + 1, N, d_inner)``
    (one decay a (channel, state) pair: the channels on the lanes) and the
    conv's inputs over ``x`` alone, ``conv (L_S, slots + 1, K - 1,
    d_inner)``."""
    c, dt = model_cfg, jnp.dtype(v2.dtype)
    L, kv_layers = c.layers_of("M") or c.layers_of("S"), c.layers_of("*")
    if not (L and kv_layers):
        raise NotImplementedError(
            "a mixer_pattern model is served with at least one Mamba "
            "and one attention layer")
    pool = _kv_pool(c, v2, kv_layers, v2.num_blocks)
    if c.layers_of("S"):  # Mamba-1: no heads, the channels on the lanes
        return {"k": pool, "v": pool,
                "ssm": ((L, v2.max_seqs + 1, c.mamba_state_size,
                         c.mamba_d_inner), jnp.float32),
                "conv": ((L, v2.max_seqs + 1, c.mamba_conv_kernel - 1,
                          c.mamba_d_inner), dt)}
    return {"k": pool, "v": pool,
            "ssm": ((L, v2.max_seqs + 1, c.mamba_num_heads, c.mamba_head_dim,
                     c.mamba_state_size), jnp.float32),
            "conv": ((L, v2.max_seqs + 1, c.mamba_conv_kernel - 1,
                      c.mamba_conv_dim), dt)}


def state_rows(tables, start, n, flat=None) -> StepRows:
    """``StepRows`` of a step whose rows begin at positions ``start``: a
    decode step's rows where ``n`` take their one token; a mixed step's of
    ``n`` tokens each, ``flat = (q_start, row, valid, slots)``: where a row's
    tokens begin, each token's row, the real tokens, each row's slot."""
    if flat is None:
        return StepRows(n, n & (start == 0))
    q_start, row, valid, slots = flat
    return StepRows(
        active=n > 0, fresh=(n > 0) & (start == 0), row=row,
        offset=jnp.arange(row.shape[0]) - q_start[row], row_start=q_start,
        row_len=n, slots=slots, valid=valid)


def _conv_one_token(xbc, p, conv, layer, rows: StepRows):
    """The causal conv on one token a slot ``xbc (R, C)``: over the slot's
    kept columns (zeros for a slot that starts) and the new one, the kept
    columns moved on in place for the slots that take the step → (the conv's
    output, conv)."""
    R = xbc.shape[0]
    held = jax.lax.dynamic_index_in_dim(conv, layer, 0, False)[:R]
    cols = jnp.where(rows.fresh[:, None, None], 0, held)
    out = ssm_hybrid.conv_taps(
        [cols[:, j] for j in range(cols.shape[1])] + [xbc], p)
    new = jnp.concatenate([cols[:, 1:], xbc[:, None]], axis=1)
    return out, conv.at[layer, :R].set(
        jnp.where(rows.active[:, None, None], new, held))


def _pad_scratch(a):
    """A row more, for the scratch slot, which takes no step."""
    return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))


def _mamba_decode(a_in, p, cfg, ssm, conv, layer, rows: StepRows):
    """A Mamba-2 layer on one token a slot: the conv over the slot's kept
    columns and the new one, one recurrence step, both states in place."""
    R = a_in.shape[0]
    z, xbc, dt = ssm_hybrid.mamba_in_proj(a_in, p)
    with jax.named_scope("ssm_conv"):
        out, conv = _conv_one_token(xbc, p, conv, layer, rows)
    x, B, C, dt, A, D = ssm_hybrid.ssm_inputs(out, dt, p, cfg)
    pad = _pad_scratch
    with jax.named_scope("ssm_scan"):
        y, ssm = ssm_decode_update(ssm, layer, pad(x), pad(dt), A, pad(B),
                                   pad(C), D, pad(rows.active),
                                   pad(rows.fresh))
    return ssm_hybrid.mamba_out(y[:R], z, p, cfg), ssm, conv


def _selective_decode(a_in, p, cfg, ssm, conv, layer, rows: StepRows):
    """A Mamba-1 mixer on one token a slot: ``_mamba_decode`` with the other
    recurrence (the conv over ``x`` alone, dt, B and C from its output)."""
    R = a_in.shape[0]
    x, z = selective_ssm.in_proj(a_in, p, cfg)
    with jax.named_scope("sel_conv"):
        out, conv = _conv_one_token(x, p, conv, layer, rows)
    delta, A, B, C, D = selective_ssm.scan_inputs(out, p, cfg)
    pad = _pad_scratch
    with jax.named_scope("sel_scan"):
        y, ssm = selective_decode_update(
            ssm, layer, pad(out), pad(delta), A, pad(B), pad(C), D,
            pad(rows.active), pad(rows.fresh))
    return selective_ssm.gate_out(y[:R], z, p), ssm, conv


def _selective_mixed(a_in, p, cfg, ssm, conv, layer, rows: StepRows):
    """A Mamba-1 mixer on a mixed step's flat rows: the rows of two tokens
    and more through ``selective_scan``, each from its slot's state; the
    rows of one token in one dense pass over the slots, as ``_mamba_mixed``
    does."""
    S1 = ssm.shape[1]
    many = rows.row_len >= 2
    (z, x, delta, A, B, C, D), y, ssm, kept = selective_ssm.selective_rows(
        a_in, p, cfg, ssm, conv, layer, rows.row, rows.offset, rows.row_start,
        rows.row_len, rows.slots, rows.fresh, many)
    with jax.named_scope("sel_scan"):
        one = rows.row_len == 1
        at = jnp.where(one, rows.slots, S1)  # past the end: dropped
        first = jnp.clip(rows.row_start, 0, x.shape[0] - 1)

        def by_slot(a):
            return _by_slot(a, at, S1)

        y1, ssm = selective_decode_update(
            ssm, layer, by_slot(x[first]), by_slot(delta[first]), A,
            by_slot(B[first]), by_slot(C[first]), D, by_slot(one),
            by_slot(rows.fresh))
        y = jnp.where((one[rows.row] & rows.valid)[:, None],
                      y1[rows.slots[rows.row]], y)
    with jax.named_scope("sel_conv"):
        conv = conv.at[layer, rows.slots].set(kept)
    return selective_ssm.gate_out(y, z, p), ssm, conv


def _by_slot(a, at, slots: int, fill=0):
    """``a (R, ...)`` laid out a state slot: row ``r`` at ``at[r]`` (past
    the end: dropped), ``fill`` elsewhere."""
    return jnp.full((slots,) + a.shape[1:], fill, a.dtype
                    ).at[at].set(a, mode="drop")


def _mamba_mixed(a_in, p, cfg, ssm, conv, layer, rows: StepRows):
    """A Mamba-2 layer on a mixed step's flat rows: the rows of two tokens
    and more through the chunked scan, each from its slot's state; the rows
    of one token (the decode rows) in one dense pass over the slots."""
    S1 = ssm.shape[1]
    many = rows.row_len >= 2
    (z, x, B, C, dt, A, D), y, ssm, kept = ssm_hybrid.mamba_rows(
        a_in, p, cfg, ssm, conv, layer, rows.row, rows.offset, rows.row_start,
        rows.row_len, rows.slots, rows.fresh, many)
    with jax.named_scope("ssm_scan"):
        one = rows.row_len == 1
        at = jnp.where(one, rows.slots, S1)  # past the end: dropped
        first = jnp.clip(rows.row_start, 0, x.shape[0] - 1)

        def by_slot(a):
            return _by_slot(a, at, S1)

        y1, ssm = ssm_decode_update(
            ssm, layer, by_slot(x[first]), by_slot(dt[first]), A,
            by_slot(B[first]), by_slot(C[first]), D,
            by_slot(one), by_slot(rows.fresh))
        y = jnp.where((one[rows.row] & rows.valid)[:, None, None],
                      y1[rows.slots[rows.row]], y)
    with jax.named_scope("ssm_conv"):
        conv = conv.at[layer, rows.slots].set(kept)
    return ssm_hybrid.mamba_out(y, z, p, cfg), ssm, conv


#: the one kind of attention layer a mixer-pattern model has: no window, the
#: one pool, no rotation
_PLAIN_ATTENTION = LayerKind(0, 0, tfm.RopeParams())


def stacked_layers(layers):
    """``params["layers"]`` of a model with one stack a kind of layer →
    ``of(kind, idx)``: layer ``idx`` of that stack, its quantized
    projections read in place at THAT stack's index (``hoist_quantized``)."""
    stacks = {kind: hoist_quantized(tree) for kind, tree in layers.items()}

    def of(kind, idx):
        kept, layer_params = stacks[kind]
        return layer_params(jax.tree.map(lambda a: a[idx], kept), idx)

    return of


def walk_pattern(pattern, draws, one_layer, carry):
    """The layers of a model whose parameters are stacked by kind, in pattern
    order as ``ssm_hybrid.segments`` cuts it: each run of a repeated unit
    (``E M``; a period of four) is one ``lax.scan`` over its repeats with the
    unit's layers unrolled inside, so a start traces a handful of bodies
    whatever the depth; a unit that comes once is unrolled where it stands.

    ``draws(letter) -> {stack: bool}``: the stacks whose index a layer of
    that letter is handed, and whether it is one of the stack's layers (its
    count moves on).  ``one_layer(letter, idx, carry) -> (carry, outs)``:
    ``idx[stack]`` is the layer's place in that stack and ``outs`` a tuple
    with an array or None a slot.  → (the carry, a list a slot: each run's
    outputs, stacked ``(layers, ...)``)."""
    done, outs = collections.Counter(), None
    for unit, reps in ssm_hybrid.segments(tuple(pattern)):
        per_unit = collections.Counter(
            stack for letter in unit
            for stack, own in draws(letter).items() if own)
        base = dict(done)

        def unit_body(carry, rep, unit=unit, per_unit=per_unit, base=base):
            seen, got = collections.Counter(), None
            for letter in unit:
                drawn = draws(letter)
                idx = {stack: base.get(stack, 0) + rep * per_unit[stack]
                       + seen[stack] for stack in drawn}
                seen.update(stack for stack, own in drawn.items() if own)
                carry, out = one_layer(letter, idx, carry)
                got = got or [[] for _ in out]
                for slot, o in zip(got, out):
                    if o is not None:
                        slot.append(o)
            return carry, tuple(jnp.stack(slot) if slot else None
                                for slot in got)

        if reps == 1:
            carry, got = unit_body(carry, jnp.int32(0))
        else:
            carry, got = jax.lax.scan(unit_body, carry,
                                      jnp.arange(reps, dtype=jnp.int32))
        outs = outs or [[] for _ in got]
        for slot, o in zip(outs, got):
            if o is not None:  # (repeats,) (unit's layers, ...) → (layers, ...)
                slot.append(o.reshape((-1,) + o.shape[1 + (reps > 1):]))
        for stack, n in per_unit.items():
            done[stack] += reps * n
    return carry, outs


def hybrid_layers(params, caches, x, positions, write_at, attend,
                  model_cfg: tfm.TransformerConfig, v2, adapters=None,
                  slots=None, valid=None, rows: StepRows = None):
    """``serving_layers`` for a model of one mixer a layer
    (``model_cfg.mixer_pattern``): norm, the layer's mixer, residual, in
    pattern order (``walk_pattern``).  Each kind's parameters are its own
    stack; the K/V pool (the attention layers only) and both state arrays
    ride the carry whole and are updated in place.  ``rows`` is what its
    state layers need to know of the step."""
    if rows is None:
        raise ValueError("a model with state layers is served by the mixed "
                         "and decode steps only")
    blk_ids, offsets = write_at
    lead = x.shape[:-1]
    nh, nkv, hd = model_cfg.num_heads, model_cfg.kv_heads, model_cfg.head_dim
    of = stacked_layers(params["layers"])
    mamba = _mamba_decode if rows.row is None else _mamba_mixed
    selective = _selective_decode if rows.row is None else _selective_mixed

    def one_layer(kind, idx, carry):
        x, k_cache, v_cache, ssm, conv = carry
        idx = idx[kind]
        lp = of(kind, idx)
        a_in = tfm._norm(x, lp["norm"], "rmsnorm", model_cfg.norm_eps)
        stats = None
        if kind == "M":
            out, ssm, conv = mamba(a_in, lp["mamba"], model_cfg, ssm, conv,
                                   idx, rows)
        elif kind == "S":
            out, ssm, conv = selective(a_in, lp["mamba"], model_cfg, ssm,
                                       conv, idx, rows)
        elif kind == "F":
            out = selective_ssm.ffn(a_in, lp["mlp"], model_cfg)
        elif kind == "E":
            out, stats = serving_moe_block(a_in, lp["moe"], model_cfg,
                                           valid=valid)
        else:
            q, k, v = (tfm._lin(a_in, lp["attn"], w, b) for w, b in
                       (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
            k, v = k.reshape(lead + (nkv, hd)), v.reshape(lead + (nkv, hd))
            with jax.named_scope("cache_write"):
                k_cache = k_cache.at[idx, blk_ids[0], offsets].set(
                    k.astype(k_cache.dtype))
                v_cache = v_cache.at[idx, blk_ids[0], offsets].set(
                    v.astype(v_cache.dtype))
            o = attend(q.reshape(lead + (nh, hd)), k_cache, v_cache, idx,
                       _PLAIN_ATTENTION)
            out = tfm._lin(o.reshape(lead + (nh * hd,)), lp["attn"], "wo",
                           "bo")
        return (x + out, k_cache, v_cache, ssm, conv), (stats,)

    carry = (x, caches["k"], caches["v"], caches["ssm"], caches["conv"])
    (x, k_cache, v_cache, ssm, conv), (moe_stats,) = walk_pattern(
        model_cfg.mixer_pattern, lambda kind: {kind: True}, one_layer, carry)
    x = tfm._norm(x, params["final_norm"], "rmsnorm", model_cfg.norm_eps)
    return (x, {"k": k_cache, "v": v_cache, "ssm": ssm, "conv": conv},
            jnp.concatenate(moe_stats) if moe_stats else None)


def _share_step_stats(moe_stats, taps=()):
    """The step's int32 stats of a model whose routed layers hold a SHARE of
    their experts, from ``walk_pattern``'s per-run ``(layers, 3 + ...)``:
    held experts hit summed over the routed layers, the largest rows of one,
    the assignments that were local; behind them what a tapped config asks
    for (the rows' chosen experts, then ``taps``).  None without a routed
    layer."""
    if not moe_stats:
        return None
    per_layer = jnp.concatenate(moe_stats)
    return jnp.concatenate(
        [jnp.stack([per_layer[:, 0].sum(), per_layer[:, 1].max(),
                    per_layer[:, 2].sum()]),
         per_layer[:, 3:].reshape(-1)] + [t.reshape(-1) for t in taps])


@dataclasses.dataclass(frozen=True)
class LatentRows:
    """What a latent-attention model's layers have to know of a step's rows:
    the block table, which rows hold ONE token this step (``single``: every
    active row of a decode step, the decode rows riding in a mixed step) and
    the position of that token; of a mixed step also where each row's tokens
    begin in the flat batch, their first position and how many there are
    (``paged_prefill_attention``'s three).  The rows of one token take the
    decode path, the rows of two and more the prefill path."""
    tables: jax.Array
    positions: jax.Array
    single: jax.Array
    q_start: jax.Array = None
    chunk_start: jax.Array = None
    chunk_len: jax.Array = None


def latent_arrays(model_cfg: tfm.TransformerConfig, v2) -> dict:
    """What a model with latent attention caches, in place of K and V heads:
    ``latent (L, blocks, block, W)``, a token's latent and rotated key
    (``latent_attention.pool_width``), and ``index (L_full, blocks, block,
    index_head_dim)``, the indexer's key of the layers that pick.  Both grow
    with the context and are read through the one block table."""
    c, dt = model_cfg, jnp.dtype(v2.dtype)
    if not c.index_topk:
        raise NotImplementedError(
            "a latent model without an indexer (index_topk 0, no query "
            "compression) is trained, not served: the absorbed step "
            "programs (programs.latent_layers) read the indexer's stack "
            "and the compressed query (ROADMAP R2b)")
    width = latent_attention.pool_width(c.kv_lora_rank, c.qk_rope_head_dim)
    return {"latent": ((c.num_layers, v2.num_blocks, v2.block_size, width),
                       dt),
            "index": ((latent_sparse.layers_of(c, "I"), v2.num_blocks,
                       v2.block_size, c.index_head_dim), dt)}


def latent_rows(tables, start, n, flat=None) -> LatentRows:
    """``LatentRows`` of a step: ``state_rows``' arguments, of which a mixed
    step's rows of one token are its ``single`` ones."""
    if flat is None:
        return LatentRows(tables[0], start, n)
    return LatentRows(tables[0], start, n == 1, flat[0], start, n)


def latent_layers(params, caches, x, positions, write_at, attend,
                  model_cfg: tfm.TransformerConfig, v2, adapters=None,
                  slots=None, valid=None, rows: LatentRows = None):
    """``serving_layers`` for a model with latent attention
    (``models/latent_sparse.py``); it takes no ``attend``: a pick of keys
    comes between the write and the attention, and ``rows`` is what its two
    attention paths need.  The layers run as ``walk_pattern`` cuts the
    pattern of (picks its own keys or shares, dense or routed FFN): the
    leading layers unrolled, then the periods of four.  Both
    pools ride the carry whole, and so does THE SELECTION: the keys a "full"
    layer picked for every query of the step, which the "shared" layers
    behind it attend over (a mask ``(T, S)`` for the prefill rows' queries;
    for the rows of one token a mask ``(R, S)`` that the paged decode kernel
    reads their context under, or ``index_topk`` positions a row where the
    static shapes say gather: ``latent_attention.decode_gathers``).

    → (hidden state after the final norm, the pools, int32 stats of the
    step: held experts hit summed over the routed layers, the largest rows of
    one, the assignments that were local; behind them what a tapped config
    asks for)."""
    cfg, la, ls = model_cfg, latent_attention, latent_sparse
    if rows is None:
        raise ValueError("a model with latent attention is served by the "
                         "mixed and decode steps only")
    blk_ids, offsets = write_at
    mixed = rows.q_start is not None
    T = x.shape[0]
    R, blocks = rows.tables.shape
    S = blocks * v2.block_size
    W = caches["latent"].shape[-1]
    K = min(cfg.index_topk, S)
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    rope = ls.rope_tables(cfg, S)
    scale = ls.softmax_scale(cfg)
    of = stacked_layers(params["layers"])
    tiles = la.prefill_tiles(rows.chunk_len, T) if mixed else None
    gathers = la.decode_gathers(blocks, caches["latent"], rkv, K)
    pick_rows = la.select_rows if gathers else la.select_rows_mask
    # the rows of one token read ctx keys: their own, just written, the last
    ctx = jnp.where(rows.single, rows.positions + 1, 0).astype(jnp.int32)

    def per_row(a):  # the single rows' tokens out of the flat batch
        return a[jnp.clip(rows.q_start, 0, T - 1)] if mixed else a

    def one_layer(letter, idx, carry):
        x, latent, index, sel = carry
        lp = of("A", idx["A"])
        p = lp["attn"]
        a_in = tfm._norm(x, lp["ln1"], "rmsnorm", cfg.norm_eps)
        c_q, q_nope, q_rope = ls.queries(a_in, p, cfg, rope, positions)
        entry = ls.cache_entry(a_in, p, cfg, rope, positions, W)
        with jax.named_scope("cache_write"):
            latent = latent.at[idx["A"], blk_ids[0], offsets].set(
                entry.astype(latent.dtype))
        tap = None
        if letter.isupper():  # picks its own keys, for itself and the shared
            ip = of("I", idx["I"])["index"]
            ki = ls.index_key(a_in, ip, cfg, rope, positions)
            with jax.named_scope("cache_write"):
                index = index.at[idx["I"], blk_ids[0], offsets].set(
                    ki.astype(index.dtype))
            qi, w = ls.index_queries(c_q, a_in, ip, cfg, rope, positions)
            r_sel = pick_rows(
                per_row(qi), per_row(w), index, idx["I"], rows.tables,
                rows.positions, rows.single, K)
            mask = sel[0]
            if mixed:
                mask = la.select_tiles(qi, w, index, idx["I"], rows.tables,
                                       tiles, rows.q_start, rows.chunk_start,
                                       K)
            sel = (mask, r_sel)
            if cfg.dsa_tap:  # tooling only: every query's pick, packed
                picks = la.rows_as_mask(*r_sel, S) if gathers else r_sel
                if mixed:
                    picks = mask.at[jnp.where(rows.single, rows.q_start, T)
                                    ].set(picks, mode="drop")
                tap = la.pack_mask(picks)
        mask, r_sel = sel
        q_lat = ls.absorb(q_nope, q_rope, p["w_kvb"], W)
        if gathers:
            o = la.latent_decode_attention(
                per_row(q_lat), latent, idx["A"], rows.tables, *r_sel,
                scale=scale, latent=rkv)
        else:
            o = la.latent_decode_attention_masked(
                per_row(q_lat), latent, idx["A"], rows.tables, r_sel, ctx,
                scale=scale, latent=rkv, k=K)
        if mixed:
            o = la.latent_prefill_attention(
                q_lat, latent, idx["A"], rows.tables, mask, tiles,
                rows.q_start, rows.chunk_start, scale=scale, latent=rkv
            ).at[jnp.where(rows.single, rows.q_start, T)].set(o, mode="drop")
        x = x + tfm._lin(ls.unabsorb(o, p["w_kvb"], dn, x.dtype), p, "wo",
                         "bo")
        m_in = tfm._norm(x, lp["ln2"], "rmsnorm", cfg.norm_eps)
        stats = None
        if letter in "Dd":
            out = tfm._mlp_block(m_in[None], of("D", idx["D"])["mlp"],
                                 cfg)[0]
        else:
            out, stats = serving_moe_block(m_in, of("S", idx["S"])["moe"],
                                           cfg, valid=valid)
        return (x + out, latent, index, sel), (stats, tap)

    def stack_of(letter):  # every stack's index is made, as it always was:
        # lowering drops the unused ones, and the pinned counts hold them
        return {"A": True, "I": letter.isupper(), "D": letter in "Dd",
                "S": letter in "Ss"}

    sel = (jnp.zeros((T, S) if mixed else (1, 1), bool),
           (jnp.zeros((R, K), jnp.int32), jnp.zeros((R, K), bool)) if gathers
           else jnp.zeros((R, S), bool))
    carry, (moe_stats, taps) = walk_pattern(
        ls.pattern(cfg), stack_of, one_layer,
        (x, caches["latent"], caches["index"], sel))
    x, latent, index, _ = carry
    x = tfm._norm(x, params["final_norm"], "rmsnorm", cfg.norm_eps)
    return (x, {"latent": latent, "index": index},
            _share_step_stats(moe_stats, taps))


@dataclasses.dataclass(frozen=True)
class LinearLatentRows:
    """What a model of KDA layers beside latent attention has to know of a
    step's rows: what its state layers need (``StepRows``) and what its
    latent layers need (``LatentRows``), of the same step."""
    state: StepRows
    latent: LatentRows


def linear_latent_arrays(model_cfg: tfm.TransformerConfig, v2) -> dict:
    """What a model of KDA layers beside latent attention caches: ``latent
    (L_A, blocks, block, W)``, a token's latent and unrotated shared key in
    the latent layers alone (``latent_attention.pool_width``), which grows
    with the context through the one block table; and beside it the
    per-sequence state of the KDA layers: ``kda (L_K, slots + 1, H, d_k,
    d_v)`` float32 and the short conv's kept inputs ``conv (L_K, slots + 1,
    K - 1, 3 x H x d_k)`` in the activation dtype (q, k and v side by side,
    columns on the lanes).  A slot a row of the engine's table, and one
    scratch slot."""
    from ...models import kimi_linear

    c, dt = model_cfg, jnp.dtype(v2.dtype)
    width = latent_attention.pool_width(c.kv_lora_rank, c.qk_rope_head_dim)
    Lk, La = (kimi_linear.layers_of(c, k) for k in "KA")
    H, dk = c.kda_num_heads, c.kda_head_dim
    return {"latent": ((La, v2.num_blocks, v2.block_size, width), dt),
            "kda": ((Lk, v2.max_seqs + 1, H, dk, dk), jnp.float32),
            "conv": ((Lk, v2.max_seqs + 1, c.kda_conv_kernel - 1,
                      3 * H * dk), dt)}


def linear_latent_rows(tables, start, n, flat=None) -> LinearLatentRows:
    """``LinearLatentRows`` of a step: ``state_rows``' arguments."""
    return LinearLatentRows(state_rows(tables, start, n, flat),
                            latent_rows(tables, start, n, flat))


def _kda_decode(a_in, p, cfg, kda, conv, layer, rows: StepRows):
    """A KDA layer on one token a slot: the conv over the slot's kept
    columns and the new one, one delta-rule step, both states in place."""
    from ...models import kimi_linear
    from ...ops.pallas.kda import kda_decode_update

    R = a_in.shape[0]
    qkv = kimi_linear.kda_in_proj(a_in, p)
    with jax.named_scope("kda_conv"):
        held = jax.lax.dynamic_index_in_dim(conv, layer, 0, False)[:R]
        cols = jnp.where(rows.fresh[:, None, None], 0, held)
        out = ssm_hybrid.conv_taps(
            [cols[:, j] for j in range(cols.shape[1])] + [qkv], p)
        new = jnp.concatenate([cols[:, 1:], qkv[:, None]], axis=1)
        conv = conv.at[layer, :R].set(
            jnp.where(rows.active[:, None, None], new, held))
    inputs = kimi_linear.kda_inputs(out, a_in, p, cfg)

    def pad(a):  # the scratch slot takes no step
        return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))

    o, kda = kda_decode_update(kda, layer, *(pad(a) for a in inputs),
                               pad(rows.active), pad(rows.fresh))
    return kimi_linear.kda_out(o[:R], a_in, p, cfg), kda, conv


def _kda_mixed(a_in, p, cfg, kda, conv, layer, rows: StepRows):
    """A KDA layer on a mixed step's flat rows: the rows of two tokens and
    more through the chunked form, each from its slot's state; the rows of
    one token (the decode rows) in one dense pass over the slots."""
    from ...models import kimi_linear
    from ...ops.pallas.kda import kda_chunk_scan, kda_decode_update

    S1, T = kda.shape[1], a_in.shape[0]
    qkv = kimi_linear.kda_in_proj(a_in, p)
    with jax.named_scope("kda_conv"):
        kept = jnp.where(rows.fresh[:, None, None], 0,
                         conv[layer, rows.slots])
        qkv, kept = ssm_hybrid.conv_ragged(qkv, kept, p, rows.row,
                                           rows.offset, rows.row_start,
                                           rows.row_len)
    inputs = kimi_linear.kda_inputs(qkv, a_in, p, cfg)
    o, kda = kda_chunk_scan(kda, layer, *inputs, rows.row_start,
                            rows.row_len, rows.slots, rows.fresh,
                            rows.row_len >= 2, cfg.kda_chunk_size)
    one = rows.row_len == 1
    at = jnp.where(one, rows.slots, S1)  # past the end: dropped
    first = jnp.clip(rows.row_start, 0, T - 1)
    o1, kda = kda_decode_update(
        kda, layer, *(_by_slot(a[first], at, S1) for a in inputs),
        _by_slot(one, at, S1), _by_slot(rows.fresh, at, S1))
    o = jnp.where((one[rows.row] & rows.valid)[:, None, None],
                  o1[rows.slots[rows.row]], o)
    with jax.named_scope("kda_conv"):
        conv = conv.at[layer, rows.slots].set(kept)
    return kimi_linear.kda_out(o, a_in, p, cfg), kda, conv


def linear_latent_layers(params, caches, x, positions, write_at, attend,
                         model_cfg: tfm.TransformerConfig, v2, adapters=None,
                         slots=None, valid=None,
                         rows: LinearLatentRows = None):
    """``serving_layers`` for a model of KDA layers beside latent attention
    (``models/kimi_linear.py``): a mixer and an FFN a layer, in pattern
    order (``walk_pattern``; each kind's parameters its own stack).  It takes
    no ``attend``: a latent layer's rows of one token read their WHOLE
    context through the table (``latent_decode_attention_full``), its rows of
    two and more the prefill kernel under a causal selection; nothing is
    rotated.  The latent pool (the latent layers only) and both state arrays
    ride the carry whole and are updated in place.

    → (hidden state after the final norm, the arrays, int32 stats of the
    step as ``latent_layers`` gives them)."""
    from ...models import kimi_linear

    cfg, la, ls = model_cfg, latent_attention, latent_sparse
    if rows is None:
        raise ValueError("a model of KDA layers beside latent attention is "
                         "served by the mixed and decode steps only")
    blk_ids, offsets = write_at
    st, lr = rows.state, rows.latent
    mixed = lr.q_start is not None
    T = x.shape[0]
    W = caches["latent"].shape[-1]
    dn, rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    scale = ls.softmax_scale(cfg)
    of = stacked_layers(params["layers"])
    kda_layer = _kda_mixed if mixed else _kda_decode
    # the rows of one token read ctx keys: their own, just written, the last
    ctx = jnp.where(lr.single, lr.positions + 1, 0).astype(jnp.int32)
    if mixed:
        tiles = la.prefill_tiles(lr.chunk_len, T)
        # THE SELECTION of a model without an indexer: every earlier key,
        # made once a step for all the latent layers
        S = lr.tables.shape[1] * v2.block_size
        causal = jnp.arange(S)[None, :] <= positions[:, None]

    def per_row(a):  # the single rows' tokens out of the flat batch
        return a[jnp.clip(lr.q_start, 0, T - 1)] if mixed else a

    def one_layer(letter, idx, carry):
        x, latent, kda, conv = carry
        if letter in "Kk":
            lp = of("K", idx["K"])
            a_in = tfm._norm(x, lp["ln1"], "rmsnorm", cfg.norm_eps)
            out, kda, conv = kda_layer(a_in, lp["kda"], cfg, kda, conv,
                                       idx["K"], st)
        else:
            lp = of("A", idx["A"])
            p = lp["attn"]
            a_in = tfm._norm(x, lp["ln1"], "rmsnorm", cfg.norm_eps)
            _, q_nope, q_rope = ls.queries(a_in, p, cfg, None, positions)
            entry = ls.cache_entry(a_in, p, cfg, None, positions, W)
            with jax.named_scope("cache_write"):
                latent = latent.at[idx["A"], blk_ids[0], offsets].set(
                    entry.astype(latent.dtype))
            q_lat = ls.absorb(q_nope, q_rope, p["w_kvb"], W)
            o = la.latent_decode_attention_full(
                per_row(q_lat), latent, idx["A"], lr.tables, ctx,
                scale=scale, latent=rkv)
            if mixed:
                o = la.latent_prefill_attention(
                    q_lat, latent, idx["A"], lr.tables, causal, tiles,
                    lr.q_start, lr.chunk_start, scale=scale, latent=rkv
                ).at[jnp.where(lr.single, lr.q_start, T)].set(o, mode="drop")
            out = tfm._lin(ls.unabsorb(o, p["w_kvb"], dn, x.dtype), p, "wo",
                           "bo")
        x = x + out
        stats = None
        if letter.islower():
            fp = of("D", idx["D"])
            m_in = tfm._norm(x, fp["ln2"], "rmsnorm", cfg.norm_eps)
            out = tfm._mlp_block(m_in[None], fp["mlp"], cfg)[0]
        else:
            fp = of("S", idx["S"])
            m_in = tfm._norm(x, fp["ln2"], "rmsnorm", cfg.norm_eps)
            out, stats = serving_moe_block(m_in, fp["moe"], cfg, valid=valid)
        return (x + out, latent, kda, conv), (stats,)

    carry, (moe_stats,) = walk_pattern(
        kimi_linear.pattern(cfg), kimi_linear.stacks_of, one_layer,
        (x, caches["latent"], caches["kda"], caches["conv"]))
    x, latent, kda, conv = carry
    x = tfm._norm(x, params["final_norm"], "rmsnorm", cfg.norm_eps)
    return (x, {"latent": latent, "kda": kda, "conv": conv},
            _share_step_stats(moe_stats))


@dataclasses.dataclass(frozen=True)
class EvaRows:
    """What an EVA model's layers have to know of a step's rows: both block
    tables, each row's first position and its tokens this step (a decode
    step: one where the row is active), and of a mixed step where each row's
    tokens begin in the flat batch."""
    win_tables: jax.Array
    sum_tables: jax.Array
    start: jax.Array
    n: jax.Array
    q_start: jax.Array = None


def eva_arrays(model_cfg: tfm.TransformerConfig, v2) -> dict:
    """What a model with EVA attention caches, two pools in EVERY layer:
    ``k_win`` / ``v_win``, the K and V of a sequence's current window by
    token (``num_window_blocks`` blocks; 0: ``max_seqs`` whole windows and
    the scratch block: a row's chunk ends at the window's edge, so it never
    holds more), and ``k_sum`` / ``v_sum``, one entry a chunk of every window
    it has closed (``num_blocks`` blocks).  The engine's main table is the
    summaries', its second the window's."""
    from ...ops.pallas.eva_attention import check_geometry

    c, bs = model_cfg, v2.block_size
    check_geometry(c.eva_window, c.eva_chunk, bs)
    win = _kv_pool(c, v2, c.num_layers, v2.num_window_blocks
                   or 1 + v2.max_seqs * (c.eva_window // bs))
    summ = _kv_pool(c, v2, c.num_layers, v2.num_blocks)
    return {"k_sum": summ, "v_sum": summ, "k_win": win, "v_win": win}


def eva_rows(tables, start, n, flat=None) -> EvaRows:
    """``EvaRows`` of a step: ``state_rows``' arguments."""
    return EvaRows(tables[1], tables[0], start, n.astype(jnp.int32),
                   None if flat is None else flat[0])


def eva_layers(params, caches, x, positions, write_at, attend,
               model_cfg: tfm.TransformerConfig, v2, adapters=None,
               slots=None, valid=None, rows: EvaRows = None):
    """``serving_layers`` for a model with EVA attention (``models/eva.py``):
    one ``lax.scan`` over identical layers, the four pools on the carry.  It
    takes no ``attend``: a layer writes the step's K and V into the window
    pool, attends over the window and the summaries behind it under one
    softmax (``ops/pallas/eva_attention.py``), and, for the rows whose
    window this step completes, makes that window's summaries from the pool
    it has just written (the host frees the window's blocks after the
    step)."""
    from ...ops.pallas import eva_attention as ea

    cfg = model_cfg
    if rows is None:
        raise ValueError("a model with EVA attention is served by the mixed "
                         "and decode steps only")
    blk_ids, offsets = write_at
    lead = x.shape[:-1]
    nh, hd = cfg.num_heads, cfg.head_dim
    W, C = cfg.eva_window, cfg.eva_chunk
    cos_full, sin_full = tfm.rope_table_of(
        v2.max_blocks_per_seq * v2.block_size, cfg.rot_dim,
        cfg.rope_of("full"))
    # the window a row's last token of this step completes, or -1
    ends = rows.start + rows.n
    closing = jnp.where((rows.n > 0) & (ends % W == 0), ends // W - 1, -1)
    both = (rows.win_tables, rows.sum_tables)
    size = dict(window=W, chunk=C)
    layers, layer_params = hoist_quantized(params["layers"])

    def layer_body(carry, inp):
        x, k_win, v_win, k_sum, v_sum = carry
        sliced, layer = inp
        lp = layer_params(sliced, layer)
        p = lp["attn"]
        a_in = tfm._norm(x, lp["ln1"], cfg.norm, cfg.norm_eps)
        q, k, v = (tfm._lin(a_in, p, w, b).reshape(lead + (nh, hd))
                   for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
        q = tfm.rope_at(q, cos_full, sin_full, positions)
        k = tfm.rope_at(k, cos_full, sin_full, positions)
        with jax.named_scope("cache_write"):
            k_win = k_win.at[layer, blk_ids[1], offsets].set(
                k.astype(k_win.dtype))
            v_win = v_win.at[layer, blk_ids[1], offsets].set(
                v.astype(v_win.dtype))
        pools = (k_win, v_win, k_sum, v_sum)
        if rows.q_start is None:
            with jax.named_scope("eva_attention_decode"):
                o = ea.eva_decode_attention(q, *pools, layer, *both,
                                            rows.start, rows.n > 0, **size)
        else:
            with jax.named_scope("eva_attention_prefill"):
                o = ea.eva_prefill_attention(q, *pools, layer, *both,
                                             rows.q_start, rows.start,
                                             rows.n, **size)
        with jax.named_scope("eva_summarize"):
            k_sum, v_sum = ea.eva_summarize(
                *pools, layer, *both, closing, p["eva_phi"], p["eva_mu"],
                **size)
        x = x + tfm._lin(o.reshape(lead + (nh * hd,)), p, "wo", "bo")
        m_in = tfm._norm(x, lp["ln2"], cfg.norm, cfg.norm_eps)
        x = x + _ffn(m_in, lp, cfg, valid)[0]
        return (x, k_win, v_win, k_sum, v_sum), None

    num_layers = jax.tree.leaves(layers)[0].shape[0]
    (x, k_win, v_win, k_sum, v_sum), _ = jax.lax.scan(
        layer_body, (x, caches["k_win"], caches["v_win"], caches["k_sum"],
                     caches["v_sum"]),
        (layers, jnp.arange(num_layers, dtype=jnp.int32)))
    x = tfm._norm(x, params["final_norm"], cfg.norm, cfg.norm_eps)
    return x, {"k_sum": k_sum, "v_sum": v_sum, "k_win": k_win,
               "v_win": v_win}, None


def attention_layers(params, caches, x, positions, write_at, attend,
                     model_cfg: tfm.TransformerConfig, v2, adapters=None,
                     slots=None, valid=None, rows=None):
    """``serving_layers`` for a model of attention + FFN layers over one or
    two paged K/V pools: one ``lax.scan`` over identical layers, or over the
    periods of ``layer_plan`` with a period's layers unrolled inside."""
    blk_ids, offsets = write_at
    rows = x.shape[:-1]
    nh, nkv, hd = model_cfg.num_heads, model_cfg.kv_heads, model_cfg.head_dim
    plan = layer_plan(model_cfg, v2)
    ropes = {}
    if model_cfg.position == "rope":
        max_len = v2.max_blocks_per_seq * v2.block_size
        for kind in plan:  # one table a distinct RoPE, made outside the scan
            if kind.rope not in ropes:
                ropes[kind.rope] = tfm.rope_table_of(
                    max_len, model_cfg.rot_dim, kind.rope)
    # the quantized codes and scales stay whole, out of what the scan slices
    layers, layer_params = hoist_quantized(params["layers"])

    def one_layer(x, pools, sliced, layer, ad, kind, pool_layer):
        lp = layer_params(sliced, layer)

        def proj(h, w_key, b_key):
            out = tfm._lin(h, lp["attn"], w_key, b_key)
            if w_key in ad:
                out = out + _adapter_proj_delta(h, ad[w_key], slots)
            return out

        a_in = tfm._norm(x, lp["ln1"], model_cfg.norm, model_cfg.norm_eps)
        q, k, v = (proj(a_in, w_key, b_key) for w_key, b_key in
                   (("wq", "bq"), ("wk", "bk"), ("wv", "bv")))
        q = tfm.qk_norm(q, lp["attn"], "q_norm", model_cfg
                        ).reshape(rows + (nh, hd))
        k = tfm.qk_norm(k, lp["attn"], "k_norm", model_cfg
                        ).reshape(rows + (nkv, hd))
        v = v.reshape(rows + (nkv, hd))
        if model_cfg.position == "rope":
            cos_full, sin_full = ropes[kind.rope]
            q = tfm.rope_at(q, cos_full, sin_full, positions)
            k = tfm.rope_at(k, cos_full, sin_full, positions)
        k_cache, v_cache = pools[kind.pool]
        with jax.named_scope("cache_write"):
            k_cache = k_cache.at[pool_layer, blk_ids[kind.pool], offsets].set(
                k.astype(k_cache.dtype))
            v_cache = v_cache.at[pool_layer, blk_ids[kind.pool], offsets].set(
                v.astype(v_cache.dtype))
        o_flat = attend(q, k_cache, v_cache, pool_layer, kind
                        ).reshape(rows + (nh * hd,))
        attn_out = proj(o_flat, "wo", "bo")
        m_src = x if model_cfg.parallel_residual else x + attn_out
        m_in = tfm._norm(m_src, lp["ln2"], model_cfg.norm, model_cfg.norm_eps)
        mlp_out, moe_stats = _ffn(m_in, lp, model_cfg, valid)
        x = (x + attn_out + mlp_out) if model_cfg.parallel_residual \
            else (m_src + mlp_out)
        pools = [(k_cache, v_cache) if p == kind.pool else pool
                 for p, pool in enumerate(pools)]
        return x, pools, moe_stats

    # The scan steps over (the layer's parameters, its index, its adapter
    # factors or {}).  The quantized projections are NOT there: a kernel
    # cannot fuse the slice of its operand, so a sliced layer's codes would be
    # copied before every GEMM (more than half of a decode step's device time
    # at Mistral-7B's widths); the kernels read the stacks at (layer, k, n).
    # The K/V pools are NOT there either: they ride the carry whole,
    # each layer scatters the step's rows into them at [layer, block, offset]
    # and the paged kernels read them at (layer, block), so a step program
    # holds a pool in no form but the one donated buffer (a pool handed to the
    # scan as ``xs`` is sliced a layer at a time, re-stacked into ``ys`` and
    # copied: six passes over 1.7 GB a step at the serving cells' sizes).
    num_layers = jax.tree.leaves(layers)[0].shape[0]
    adapters = {} if adapters is None else adapters
    if len(plan) == 1:
        def layer_body(carry, inp):
            x, pools = carry
            sliced, layer, ad = inp
            x, pools, moe_stats = one_layer(x, pools, sliced, layer, ad,
                                            plan[0], layer)
            return (x, pools), moe_stats

        xs = (layers, jnp.arange(num_layers, dtype=jnp.int32), adapters)
    else:
        # Kinds differ: the scan steps over PERIODS of the pattern, a period's
        # layers unrolled inside with their kinds static, so each picks its
        # RoPE table, its pool, its block table and its window without a
        # ``cond``.  A layer's index in its pool counts the pool's layers of
        # the periods before and of this one before it.
        p = len(plan)
        in_pool = [sum(k.pool == kind.pool for k in plan) for kind in plan]
        before = [sum(k.pool == kind.pool for k in plan[:i])
                  for i, kind in enumerate(plan)]

        def by_period(tree):
            return jax.tree.map(
                lambda a: a.reshape((-1, p) + a.shape[1:]), tree)

        def layer_body(carry, inp):
            x, pools = carry
            sliced, period, ad = inp
            stats = []
            for i, kind in enumerate(plan):
                x, pools, moe_stats = one_layer(
                    x, pools, jax.tree.map(lambda a: a[i], sliced),
                    period * p + i, jax.tree.map(lambda a: a[i], ad), kind,
                    period * in_pool[i] + before[i])
                stats.append(moe_stats)
            return (x, pools), (None if stats[0] is None
                                else jnp.stack(stats))

        xs = (by_period(layers),
              jnp.arange(num_layers // p, dtype=jnp.int32),
              by_period(adapters))
    (x, pools), moe_stats = jax.lax.scan(layer_body, (x, pools_of(caches)),
                                         xs)
    if moe_stats is not None and len(plan) > 1:
        moe_stats = moe_stats.reshape((num_layers,) + moe_stats.shape[2:])
    x = tfm._norm(x, params["final_norm"], model_cfg.norm, model_cfg.norm_eps)
    new = {"k": pools[0][0], "v": pools[0][1]}
    if len(pools) > 1:
        new["k_win"], new["v_win"] = pools[1]
    return x, new, moe_stats


# ---------------------------------------------------------------------------
# the kinds of served model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServedKind:
    """One kind of served model and all that depends on which it is: the
    engines and the step programs ask IT, never the configuration's fields.
    Five today: ``KV`` (attention + FFN over one or two paged K/V pools, a
    window that slides), ``STATE`` (state slots beside paged K/V),
    ``LATENT`` (a latent pool and an indexer's keys), ``EVA`` (a window
    pool that tumbles and a pool of the closed windows' summaries, in every
    layer) and ``LINEAR_LATENT`` (state slots of a delta-rule matrix state
    beside a latent pool read whole, without an indexer)."""
    name: str  # as a refusal names the model
    #: ``(model_cfg, v2) -> {name: (shape, dtype)}``, every array of
    #: ``caches``: the engine allocates them as they come and reads its
    #: managers' needs off them (``k_win``: a second pool; ``state`` below
    #: names the ones that live in slots)
    arrays: Callable
    layers: Callable  # the body: ``serving_layers``' arguments and results
    #: ``(tables, start, n, flat=None) ->`` the body's ``rows`` argument
    step_rows: Callable
    moe_layers: Callable  # ``model_cfg ->`` how many layers route
    #: the engine's method that counts a step: ``(start, n, mixed) ->``
    #: attributes of ``engine/step``
    counters: str
    #: ``(model_cfg, v2) ->`` its rows of ``REFUSED``, and the sentence why
    #: (``{does}``: what the option does, ``{window}``: the active window)
    refuses: Callable
    because: str
    #: ``generate_all`` may decode several tokens in one program (a kind
    #: whose every step counts may not)
    bursts: bool = False
    #: the arrays that hold per-SEQUENCE state, a slot a row of the engine's
    #: table and one scratch slot (empty: the kind needs no slots); the first
    #: is the one a step reads and writes whole (its counters' bytes)
    state: tuple = ()
    #: ``(model_cfg, v2) ->`` the window whose blocks go back to their pool
    #: while the sequence runs (0: none is active)
    window: Callable = lambda c, v2: max(
        k.window for k in layer_plan(c, v2))
    #: ``model_cfg ->`` the entries a window leaves in the MAIN pool when it
    #: closes (0: the window slides a token at a time and leaves nothing;
    #: else it TUMBLES: freed whole, at the step that wrote its last token)
    closes: Callable = lambda c: 0


def kind_of(model_cfg: tfm.TransformerConfig) -> ServedKind:
    """The kind of model ``model_cfg`` is: the one place under ``inference/``
    that reads the configuration to tell."""
    if model_cfg.kda_pattern:
        return LINEAR_LATENT
    if model_cfg.kv_lora_rank:
        return LATENT
    if model_cfg.attn_gate or model_cfg.post_branch_norm \
            or model_cfg.mlp_layer_types:
        from ...models.mixed_ffn import NOT_SERVED

        raise NotImplementedError(NOT_SERVED)
    if model_cfg.eva_window:
        return EVA
    return STATE if model_cfg.mixer_pattern else KV


#: What some kind of model cannot be combined with yet: the fields of
#: ``V2Config`` as a refusal names them (one set is enough) -> what the option
#: does to a cache.  A kind's ``refuses`` names its rows, its ``because``
#: says what of that the model cannot bear.
REFUSED = {
    "enable_prefix_cache":
        "the prefix cache (with it prefix export / import and copy-on-write "
        "forks) shares and copies a finished sequence's K and V blocks and "
        "starts another behind them",
    "kv_host_pool_mb / kv_host_pool_bytes":
        "the host paging tier demotes and promotes K and V blocks of a prefix",
    "kv_spill_dir": "the spill tier holds demoted K and V blocks",
    "kv_coldstore_dir": "the cold store holds demoted K and V blocks",
    "spec_mode":
        "speculation writes k tokens ahead of the context, attends over K "
        "and V heads to verify them and rolls the rejected ones back by "
        "masking their K/V (and a draft model's cache shares the target's "
        "block tables)",
    "adapter_slots":
        "the adapter stack is laid out for the q, k, v and o projections of "
        "one attention block a layer",
}
_MOVES_BLOCKS = tuple(name for name in REFUSED if name != "adapter_slots")

KV = ServedKind(
    name="a model of attention + FFN layers over paged K/V",
    arrays=kv_arrays, layers=attention_layers,
    step_rows=lambda tables, start, n, flat=None: None,
    moe_layers=lambda c: c.num_layers, counters="_count_kv",
    # what moves KV bytes by block id does not know that a window layer's
    # blocks go back to their pool while the sequence runs, or of two pools
    refuses=lambda c, v2: _MOVES_BLOCKS if any(
        k.window for k in layer_plan(c, v2)) else (),
    because="a model whose attention layers have an active sliding window "
            "({window} < the engine's longest context): {does}, and a window "
            "layer's blocks are freed behind the window while the sequence "
            "runs",
    bursts=True)
STATE = ServedKind(
    name="a model of one mixer a layer (mixer_pattern: state-space layers "
         "with per-sequence state beside the paged K/V)",
    arrays=state_arrays, layers=hybrid_layers, step_rows=state_rows,
    moe_layers=lambda c: c.layers_of("E"), counters="_count_state",
    refuses=lambda c, v2: tuple(REFUSED),
    because="a model that has state layers (mixer_pattern with 'M' or 'S'): "
            "{does}, "
            "and a sequence's state at an earlier position is kept nowhere "
            "(that would take a snapshot)",
    state=("ssm", "conv"))
LATENT = ServedKind(
    name="a model with latent attention (kv_lora_rank > 0: a latent a "
         "token, a learned selection of keys)",
    arrays=latent_arrays, layers=latent_layers, step_rows=latent_rows,
    moe_layers=lambda c: latent_sparse.layers_of(c, "S"),
    counters="_count_latent",
    refuses=lambda c, v2: tuple(REFUSED),
    because="a model with latent attention (kv_lora_rank > 0), whose pools "
            "hold a latent a token and the indexer's keys and no K or V "
            "heads: {does}")
EVA = ServedKind(
    name="a model with EVA attention (eva_window > 0: a tumbling window of "
         "exact keys and a learned summary a chunk behind it)",
    arrays=eva_arrays, layers=eva_layers, step_rows=eva_rows,
    moe_layers=lambda c: 0, counters="_count_eva",
    refuses=lambda c, v2: tuple(REFUSED),
    because="a model with EVA attention (eva_window > 0), which keeps the K "
            "and V of its current window only and one summary a chunk of "
            "the windows it has closed: {does}, and a closed window has no "
            "K or V blocks left to share, move or mask (a summary cannot be "
            "unmade, and is no prefix's alone to reuse)",
    window=lambda c, v2: c.eva_window,
    closes=lambda c: c.eva_window // c.eva_chunk)


def _routed_layers(model_cfg: tfm.TransformerConfig) -> int:
    from ...models import kimi_linear

    return kimi_linear.layers_of(model_cfg, "S")


LINEAR_LATENT = ServedKind(
    name="a model of KDA layers beside latent attention (kda_pattern: a "
         "delta-rule matrix state a sequence in state slots, a latent a "
         "token in a paged pool read whole)",
    arrays=linear_latent_arrays, layers=linear_latent_layers,
    step_rows=linear_latent_rows,
    moe_layers=_routed_layers,
    counters="_count_linear_latent",
    refuses=lambda c, v2: tuple(REFUSED),
    because="a model of KDA layers beside latent attention (kda_pattern), "
            "which keeps a delta-rule matrix state a sequence in state "
            "slots and a latent a token in a paged pool, and no K or V "
            "heads: {does}, and a sequence's KDA state at an earlier "
            "position is kept nowhere (that would take a snapshot), so no "
            "block of the latent pool can start another sequence",
    state=("kda", "conv"))


def serving_layers(params, caches, x, positions, write_at, attend,
                   model_cfg: tfm.TransformerConfig, v2, adapters=None,
                   slots=None, valid=None, rows=None):
    """Every layer of the served model over one step's rows, then the final
    norm: the one layer body of the mixed, decode and verify steps, which is
    the body of the model's kind (``kind_of``).

    ``x (..., H)`` are the embedded rows and ``positions`` (``x.shape[:-1]``)
    their places in their sequences; ``write_at = (blk_ids, offsets)`` is
    where each row's K and V go in its layer of the pools (``blk_ids``: one
    array a pool, as ``write_blocks`` makes them), which the caller
    has already pointed at the scratch block (the pool's last) for every row
    that must not write; ``attend(q, k_pool, v_pool, layer, kind) -> o`` is
    the caller's paged attention over the layer's pool with the step's rows
    written (``q`` and ``o`` are ``(..., heads, head_dim)``; ``layer`` counts
    within the pool and ``kind`` is the layer's ``LayerKind``: which table,
    which window); ``adapters`` is the
    per-slot LoRA stack with ``slots``, the slot each row reads (shaped as
    ``_adapter_proj_delta`` takes it), or None; ``valid`` marks the rows an
    MoE model's stats count; ``rows`` is what the kind's ``step_rows`` made
    of the step (None: the verify step, which the kinds that need it are
    refused).

    → (hidden state after the final norm, the pools as ``caches`` names
    them, an MoE model's per-layer stats ``(L, 2)`` or None)."""
    return kind_of(model_cfg).layers(params, caches, x, positions, write_at,
                                     attend, model_cfg, v2, adapters, slots,
                                     valid, rows)


def _decode_body(params, caches, token_ids, position_ids, block_tables,
                 context_lens, model_cfg, v2, adapters=None,
                 row_adapter=None):
    """Single-token decode shared by build_decode_forward and the multi-step
    scan (context_lens INCLUDE the current token); → (logits, caches, an MoE
    model's step stats or None).  With ``adapters`` (the
    stacked per-slot LoRA factors) and ``row_adapter`` (per-row slot
    vector), each row's attention projections add its adapter's gathered
    low-rank delta on top of the unchanged base path."""
    bs = v2.block_size
    x = tfm.embed_tokens(params, token_ids, model_cfg,
                         position_ids=position_ids)
    active = context_lens > 0
    blk_ids = write_blocks(caches, block_tables,
                           jnp.arange(token_ids.shape[0]), position_ids,
                           active, bs)
    tables = tables_of(block_tables)

    def attend(q, k_cache, v_cache, layer, kind):
        with jax.named_scope("decode_attention"):
            return paged_decode_attention(q, k_cache, v_cache, layer,
                                          tables[kind.pool], context_lens,
                                          window=kind.window)

    rows = kind_of(model_cfg).step_rows(tables, position_ids, active)
    x, caches, moe_stats = serving_layers(
        params, caches, x, position_ids, (blk_ids, position_ids % bs), attend,
        model_cfg, v2, adapters, row_adapter, active, rows)
    return (tfm.lm_logits(params, x, model_cfg).astype(jnp.float32), caches,
            _moe_step_stats(moe_stats))


# ---------------------------------------------------------------------------
# the builders (each jitted once; static shapes from V2Config)
# ---------------------------------------------------------------------------


def mixed_step_attn_tiles(model_cfg: tfm.TransformerConfig, v2) -> PrefillTiles:
    """The tiling of the mixed step's prefill attention: the kernel's picker
    on the sizes its queries have in ``build_ragged_forward``'s program, for
    the engine to count ``attn_q_slots`` from (the kernel leaves what it
    picked in its ring event; ``tests/test_inference_v2.py`` holds the two
    together)."""
    return pick_prefill_tiles(v2.max_tokens_per_step, model_cfg.num_heads,
                              model_cfg.kv_heads, model_cfg.head_dim,
                              v2.block_size, jnp.dtype(model_cfg.dtype))


def build_ragged_forward(model_cfg: tfm.TransformerConfig, v2):
    bs = v2.block_size
    kind = kind_of(model_cfg)

    def mixed_step(params, caches, token_ids, position_ids, seq_index,
                   block_tables, context_lens, logits_rows, chunk_start,
                   chunk_len, adapters=None, row_adapter=None,
                   state_slots=None):
        # ``adapters`` / ``row_adapter``: the adapter stack and each row's
        # slot of it; ``state_slots (max_seqs,)``: each row's state slot (a
        # model with state layers, which is refused adapters)
        x = tfm.embed_tokens(params, token_ids, model_cfg,
                             position_ids=position_ids)  # (T, H)
        # KV write positions: token t → (block_tables[seq, pos//bs], pos%bs);
        # invalid tokens' writes park in a scratch block (last block id is
        # reserved by the engine for this)
        valid = seq_index >= 0
        tables = tables_of(block_tables)
        max_seqs = tables[0].shape[0]
        row = jnp.clip(seq_index, 0, max_seqs - 1)
        blk_ids = write_blocks(caches, block_tables, row, position_ids, valid,
                               bs)
        # the builder lays each row's tokens end to end in row order, so a
        # row's queries begin where the rows before it end
        q_start = jnp.cumsum(chunk_len) - chunk_len

        # per-token adapter slot: each ragged token reads its row's slot
        # (padding tokens pin to the null slot — their outputs are dropped
        # and their KV writes park in scratch, but exact-zero is cheapest)
        tok_slot = None
        if adapters is not None:
            tok_slot = jnp.where(valid, row_adapter[row], 0)

        def attend(q, k_cache, v_cache, layer, kind):
            # chunked-prefill attention over paged KV on the ragged (T, H, D)
            # q as the layer made it: the kernel walks each row's tokens
            # where they lie (a padding token comes out zero)
            with jax.named_scope("prefill_attention"):
                return paged_prefill_attention(q, k_cache, v_cache, layer,
                                               tables[kind.pool], q_start,
                                               chunk_start, chunk_len,
                                               window=kind.window)

        rows = kind.step_rows(tables, chunk_start, chunk_len,
                              (q_start, row, valid, state_slots))
        x, caches, moe_stats = serving_layers(
            params, caches, x, position_ids, (blk_ids, position_ids % bs),
            attend, model_cfg, v2, adapters, tok_slot, valid, rows)
        last_hidden = x[logits_rows]  # (max_seqs, H)
        logits = tfm.lm_logits(params, last_hidden, model_cfg)
        # last_hidden rides along for the self-draft speculation heads (the
        # carried state their next proposals are computed from); an MoE
        # model's step stats ride fourth (a dense model returns three)
        out = (logits.astype(jnp.float32), last_hidden.astype(jnp.float32),
               caches)
        if moe_stats is not None:
            out += (_moe_step_stats(moe_stats),)
        return out

    return _memo(("ragged_fwd", model_cfg, dataclasses.astuple(v2)),
                 lambda: jax.jit(mixed_step, donate_argnums=(1,)))


def build_decode_forward(model_cfg: tfm.TransformerConfig, v2):
    """Pure-decode step: one token per sequence, attention through the paged
    Pallas kernel (ops/pallas/paged_attention.py) — the FastGen decode hot
    loop.  tokens/positions: (max_seqs,); context_lens INCLUDE the new token.

    Sampling happens IN-GRAPH per row (``sample_rows``): the program takes a
    (max_seqs,) temperature vector + step rng + per-row seeds and returns the
    selected token ids, so a mixed greedy/sampled batch is one host-sync-free
    program (the ``decode_step@v2`` budget proves it).  ``adapter_args`` are
    empty, or the adapter stack and the per-row slot vector."""

    def decode_step(params, caches, token_ids, position_ids, block_tables,
                    context_lens, temps, rng, seeds, *adapter_args):
        logits, caches, moe_stats = _decode_body(
            params, caches, token_ids, position_ids, block_tables,
            context_lens, model_cfg, v2, *adapter_args)
        return _with_stats(
            sample_rows(tfm.next_token_logits(logits, model_cfg), temps, rng,
                        seeds), moe_stats), caches

    return _memo(("decode_fwd", model_cfg, dataclasses.astuple(v2)),
                 lambda: jax.jit(decode_step, donate_argnums=(1,)))


def build_multi_decode_forward(model_cfg: tfm.TransformerConfig, v2,
                               num_steps: int):
    """Decode ``num_steps`` tokens per sequence inside ONE jitted program (an
    outer ``lax.scan`` over single-token decodes) — eliminates the per-token
    host roundtrip that dominates small-model decode.  Safe because admission
    reserves each sequence's whole block budget up front.

    Per-row sampling (``temps``/``seeds`` vectors, see ``sample_rows``) with
    a per-step split of ``rng`` carried through the scan; rows with
    ``temps <= 0`` stay greedy-argmax.

    Returns (tokens_out (num_steps, max_seqs), caches)."""

    def multi_decode_step(params, caches, token_ids, position_ids,
                          block_tables, context_lens, rng, temps, seeds,
                          *adapter_args):
        # rows inactive at entry must STAY inactive: advancing their ctx/pos
        # would flip them "active" with a zeroed block table and corrupt
        # block 0 of a real sequence
        alive = (context_lens > 0).astype(jnp.int32)

        def step(carry, _):
            caches, tok, pos, ctx, rng = carry
            logits, caches, _ = _decode_body(params, caches, tok, pos,
                                             block_tables, ctx, model_cfg, v2,
                                             *adapter_args)
            rng, step_rng = jax.random.split(rng)
            nxt = sample_rows(tfm.next_token_logits(logits, model_cfg),
                              temps, step_rng, seeds)
            return (caches, nxt, pos + alive, ctx + alive, rng), nxt

        (caches, _, _, _, _), toks = jax.lax.scan(
            step, (caches, token_ids, position_ids, context_lens, rng), None,
            length=num_steps)
        return toks, caches

    return _memo(("multi_decode", model_cfg, dataclasses.astuple(v2),
                  num_steps),
                 lambda: jax.jit(multi_decode_step, donate_argnums=(1,)))


def build_unpack(layout):
    """The program that takes a step's one buffer apart on the device
    (``layout``: a ``ragged.StepLayout``; hashable, so engines over the same
    sizes share the program): ``buf -> {field: array}``.  Slices, reshapes
    and a bitcast where a field is no int32, nothing else: what it traces
    and lowers at every start is a few milliseconds (the engine's key is NOT
    split here: threefry inside a jitted program lowers in 0.2-0.8 s on the
    chip's host at every start, PERF.md section 6, PR 34).

    A step that is called behind a program still under way hands it, beside
    the buffer, what that program returned (``out``, ``_with_stats``: its
    tokens, an MoE model's stats behind them; a second shape of the one
    jitted function): where the buffer's ``token_ids`` hold a NEGATIVE id the
    token is still on the device, and ``-1 - id`` says where in ``out`` (the
    engine's ``_promise``: a decode program's output lies in the table's row
    order, a mixed program's in its batch's pick order, and the step behind
    reads its decode rows' ids in ITS order).  The other ids (a prompt's
    chunk, a token the host has fetched, 0 in the rows that are not in the
    step) pass as they are, so a buffer that points at nothing may be handed
    any ``out``."""

    def unpack_step_inputs(buf, out=None):
        fields = {}
        for name, at, shape, dtype in layout.fields:
            x = jax.lax.slice(buf, (at,), (at + math.prod(shape),)
                              ).reshape(shape)
            if dtype != "int32":
                x = jax.lax.bitcast_convert_type(x, jnp.dtype(dtype))
            fields[name] = x
        if out is not None:
            ids = fields["token_ids"]
            fields["token_ids"] = jnp.where(
                ids < 0, jnp.take(out, -1 - ids, mode="clip"), ids)
        return fields

    # (``out`` is the array the next fetch reads: not donated either)
    # lint: allow(jit-no-donate) — its one argument is the host's NumPy buffer
    return _memo(("unpack", layout), lambda: jax.jit(unpack_step_inputs))


def build_cow_copy():
    """Copy one KV block to another across every layer — the copy-on-write
    fork for partial-block prefix sharing.  ``src``/``dst`` are traced int32
    scalars so every (src, dst) pair reuses one compiled program; positions
    past the shared prefix carry stale KV that the paged kernels never read
    (prefill overwrites the chunk before attention, and keys beyond
    ``context_lens`` are masked)."""

    def cow_copy(caches, src, dst):
        k, v = caches["k"], caches["v"]
        return {"k": k.at[:, dst].set(k[:, src]),
                "v": v.at[:, dst].set(v[:, src])}

    return _memo(("cow_copy",),
                 lambda: jax.jit(cow_copy, donate_argnums=(0,)))
