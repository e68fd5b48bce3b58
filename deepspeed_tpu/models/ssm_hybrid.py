"""A decoder whose layers are ONE mixer each, of three kinds (nemotron_h:
NVIDIA-Nemotron-3-Nano): ``x <- x + mixer_i(RMSNorm(x))`` with ``mixer_i`` a
Mamba-2 layer (``"M"``), a routed MoE with a shared expert (``"E"``) or
attention without positions (``"*"``), by ``cfg.mixer_pattern``, which is not
one period repeated.

The parameters are stacked BY KIND: ``params["layers"][kind]`` holds the
kind's layers on a leading axis, in pattern order, and :func:`layer_plan` says
layer by layer which stack and which index.  ``transformer.init_params``,
``param_axes``, ``num_params`` and ``forward_hidden`` dispatch here for a
config with a pattern; the v2 engine's step programs
(``inference/v2/programs.py:hybrid_layers``) call the same mixer pieces, so
the Mamba layer's mathematics is written once:

    [z | xBC | dt] = a W_in          (three projections: W_in's width, 10304
                                      for the published model, is no multiple
                                      of a lane tile; the slices are)
    xBC = silu(causal depthwise conv(xBC) + conv bias)       ``ssm_conv``
    dt = softplus(dt + dt_bias), A = -exp(A_log), the recurrence of
    ``ops/pallas/ssm.py``                                      ``ssm_scan``
    y = GroupRMSNorm(y * silu(z)), groups of d_inner / G       ``ssm_gate_norm``
    out = y W_out                                              ``ssm_out_proj``

Which recurrence: this file holds Mamba-2's layer (one decay a head).  A
pattern may instead hold ``"S"``, a Mamba-1 mixer (one decay a (channel,
state) pair), and ``"F"``, a dense gated FFN as a sub-layer of its own
(jamba); their pieces are ``models/selective_ssm.py``'s, and a model holds
``"S"`` or ``"M"``, not both.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.pallas.ssm import ssd_chunk_scan
from . import selective_ssm
from . import transformer as tfm

KINDS = ("M", "E", "*", "S", "F")


def layer_plan(cfg) -> Tuple[Tuple[str, int], ...]:
    """(kind, index in the kind's stack) of every layer."""
    seen = {k: 0 for k in KINDS}
    plan = []
    for kind in cfg.mixer_pattern:
        plan.append((kind, seen[kind]))
        seen[kind] += 1
    return tuple(plan)


def segments(pattern: Tuple[str, ...]) -> List[Tuple[Tuple[str, ...], int]]:
    """The pattern as runs ``(unit, repeats)``: at each place the unit of one
    to four layers whose repeats cover most, so that a layer loop scans the
    repeats and traces the unit once (``EMEMEM*EMEMEMEM*`` → ``EM`` x 3,
    ``*``, ``EM`` x 4, ``*``: four bodies, not sixteen)."""
    out, i = [], 0
    while i < len(pattern):
        best = (1, 1)
        for p in range(1, 5):
            unit, r = pattern[i:i + p], 1
            while pattern[i + r * p:i + (r + 1) * p] == unit:
                r += 1
            if r > 1 and p * r > best[0] * best[1]:
                best = (p, r)
        out.append((tuple(pattern[i:i + best[0]]), best[1]))
        i += best[0] * best[1]
    return out


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg) -> Dict[str, Any]:
    pd = jnp.dtype(cfg.param_dtype)
    h, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    hd, nh, nkv = cfg.head_dim, cfg.num_heads, cfg.kv_heads
    H, di, cd = cfg.mamba_num_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    kc, fs = cfg.mamba_conv_kernel, cfg.moe_shared_size
    Lm, Le, La = (cfg.layers_of(k) for k in "ME*")
    keys = iter(jax.random.split(rng, 24))
    dense = tfm._dense_init

    def norm(L):
        return {"scale": jnp.ones((L, h), pd)}

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    # dt_bias: the inverse softplus of a step drawn log-uniformly between
    # time_step_min and time_step_max (the family's initialisation); A_log:
    # log of uniform [1, 16); both stay float32 whatever param_dtype is
    step = jnp.exp(uniform((Lm, H), math.log(1e-3), math.log(1e-1)))
    layers: Dict[str, Any] = {
        "M": {"norm": norm(Lm), "mamba": {
            "w_z": dense(next(keys), (Lm, h, di), h, pd),
            "w_xbc": dense(next(keys), (Lm, h, cd), h, pd),
            "w_dt": dense(next(keys), (Lm, h, H), h, pd),
            "conv_w": dense(next(keys), (Lm, kc, cd), kc, pd),
            "conv_b": (0.1 * jax.random.normal(next(keys), (Lm, cd))
                       ).astype(pd),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(uniform((Lm, H), 1.0, 16.0)),
            "D": jnp.ones((Lm, H), jnp.float32),
            "norm_w": jnp.ones((Lm, di), pd),
            "w_out": dense(next(keys), (Lm, di, h), di, pd)}},
        "E": {"norm": norm(Le), "moe": {
            "router": dense(next(keys), (Le, h, E), h, pd),
            # a checkpoint tensor; drawn small, so that it changes some
            # choices and a program that drops it is seen
            "router_bias": 0.05 * jax.random.normal(next(keys), (Le, E)),
            "w_in": dense(next(keys), (Le, E, h, f), h, pd),
            "w_out": dense(next(keys), (Le, E, f, h), f, pd)}},
        "*": {"norm": norm(La), "attn": {
            "wq": dense(next(keys), (La, h, nh * hd), h, pd),
            "wk": dense(next(keys), (La, h, nkv * hd), h, pd),
            "wv": dense(next(keys), (La, h, nkv * hd), h, pd),
            "wo": dense(next(keys), (La, nh * hd, h), nh * hd, pd)}},
    }
    if fs:
        layers["E"]["moe"]["sh_w_in"] = dense(next(keys), (Le, h, fs), h, pd)
        layers["E"]["moe"]["sh_w_out"] = dense(next(keys), (Le, fs, h), fs, pd)
    if selective_ssm.has_sublayers(cfg):
        # a pattern of sub-layers holds the stacks it names and no other
        layers = {k: v for k, v in {**layers, **selective_ssm.init_layers(
            jax.random.fold_in(rng, 0x5E1), cfg)}.items()
            if k in cfg.mixer_pattern}
    params = {
        "embed": {"tokens": dense(next(keys), (cfg.vocab_size, h), h, pd)},
        "layers": layers,
        "final_norm": {"scale": jnp.ones((h,), pd)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": dense(next(keys), (h, cfg.vocab_size), h, pd)}
    return params


def param_axes(cfg) -> Dict[str, Any]:
    ln = {"scale": ("layers", "embed")}
    moe = {"router": ("layers", "embed", None),
           "router_bias": ("layers", None),
           "w_in": ("layers", "expert", "embed", "mlp"),
           "w_out": ("layers", "expert", "mlp", "embed")}
    if cfg.moe_shared_size:
        moe["sh_w_in"] = ("layers", "embed", "mlp")
        moe["sh_w_out"] = ("layers", "mlp", "embed")
    layers = {
        "M": {"norm": dict(ln), "mamba": {
            "w_z": ("layers", "embed", "mlp"),
            "w_xbc": ("layers", "embed", "mlp"),
            "w_dt": ("layers", "embed", None),
            "conv_w": ("layers", None, "mlp"),
            "conv_b": ("layers", "mlp"),
            "dt_bias": ("layers", None), "A_log": ("layers", None),
            "D": ("layers", None), "norm_w": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed")}},
        "E": {"norm": dict(ln), "moe": moe},
        "*": {"norm": dict(ln), "attn": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed")}},
    }
    if selective_ssm.has_sublayers(cfg):
        layers = {k: v for k, v in {**layers,
                                    **selective_ssm.layer_axes()}.items()
                  if k in cfg.mixer_pattern}
    axes = {"embed": {"tokens": ("vocab", "embed")}, "layers": layers,
            "final_norm": {"scale": ("embed",)}}
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"w": ("embed", "vocab")}
    return axes


def num_params(cfg, include_embed: bool = True) -> int:
    h, f, E = cfg.hidden_size, cfg.intermediate_size, cfg.num_experts
    H, di, cd = cfg.mamba_num_heads, cfg.mamba_d_inner, cfg.mamba_conv_dim
    qh, kvh = cfg.num_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    per = {
        "M": h + h * (di + cd + H) + cfg.mamba_conv_kernel * cd + cd
        + 3 * H + di + di * h,
        "E": h + h * E + E + 2 * E * h * f + 2 * h * cfg.moe_shared_size,
        "*": h + h * qh + 2 * h * kvh + qh * h,
    }
    if selective_ssm.has_sublayers(cfg):
        per.update(selective_ssm.params_per_layer(cfg))
    total = sum(per[k] for k in cfg.mixer_pattern) + h
    if include_embed:
        total += (1 if cfg.tie_embeddings else 2) * cfg.vocab_size * h
    return total


# ---------------------------------------------------------------------------
# the Mamba-2 layer's pieces (the step programs call them too)
# ---------------------------------------------------------------------------


def mamba_in_proj(a, p):
    """``a (..., h)`` → ``z (..., d_inner)``, ``xBC (..., conv_dim)``, ``dt
    (..., H)`` before its bias."""
    with jax.named_scope("ssm_in_proj"):
        return (tfm._lin(a, p, "w_z", "b_z"), tfm._lin(a, p, "w_xbc", "b_xbc"),
                tfm._lin(a, p, "w_dt", "b_dt"))


def conv_taps(taps, p):
    """``silu(sum_j cw[j] * taps[j] + cb)`` over the ``conv_kernel`` shifted
    copies ``taps`` of the conv's input (oldest first), in float32 →
    the input's dtype (a conv without a bias: ``p`` holds no ``conv_b``)."""
    w = p["conv_w"].astype(jnp.float32)
    acc = p["conv_b"].astype(jnp.float32) if "conv_b" in p else 0.0
    for j, tap in enumerate(taps):
        acc = acc + w[j] * tap.astype(jnp.float32)
    return jax.nn.silu(acc).astype(taps[-1].dtype)


def conv_ragged(xbc, kept, p, row, offset, row_start, row_len):
    """The causal conv over the flat ``xbc (T, C)`` rows of a step: token
    ``t`` is the ``offset[t]``-th token of row ``row[t]`` this step; what lies
    before a row's first token are the row's kept columns ``kept (R, K - 1,
    C)`` (the conv's last ``K - 1`` inputs of the sequence, oldest first;
    zeros for a row that starts one).  → ``(conv output (T, C), the kept
    columns after the step (R, K - 1, C))``.

    The step's tokens are read in ONE pass: the input ``s`` tokens back is
    ``xbc`` shifted by ``s`` rows (static slices, which fuse into
    ``conv_taps``) for every token but a row's first ``K - 1``, which reach
    into the kept columns.  Those ``R (K - 1)`` edge outputs are computed
    beside the pass, from the same values in the same order, and laid over
    it.  Rows are moved by one-hot products and not by gathers, which walk
    their rows one at a time (six of all ``T`` rows were twice the scan
    kernel's time): one non-zero term a sum selects exactly in bfloat16
    (float32 takes ``HIGHEST``, or the chip's dot would round).  The few rows
    a column are held as ``(R, C)`` arrays, stacked column-major: with ``K -
    1`` on the sublanes every operation on them pads and relays."""
    T, C = xbc.shape
    R, K1 = kept.shape[:2]
    exact = None if xbc.dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST

    def move(hot, rows):
        return jnp.dot(hot.astype(xbc.dtype), rows, precision=exact,
                       preferred_element_type=jnp.float32).astype(xbc.dtype)

    out = conv_taps([jnp.pad(xbc[:max(T - s, 0)], ((min(s, T), 0), (0, 0)))
                     for s in range(K1, 0, -1)] + [xbc], p)
    # each row's first K1 tokens, then the tokens its new kept columns are:
    # column i is the input K1 - i before the row's end, its back[i]-th token
    # this step where that is not negative
    i = jnp.arange(K1)[:, None]
    back = row_len[None, :] - K1 + i  # (K1, R)
    at = jnp.concatenate([row_start[None, :] + i,
                          row_start[None, :] + back]).reshape(-1, 1)
    own = move(at == jnp.arange(T)[None, :], xbc)  # (2 K1 R, C)
    first = [own[j * R:(j + 1) * R] for j in range(K1)]
    last = [own[(K1 + j) * R:(K1 + j + 1) * R] for j in range(K1)]
    old = [kept[:, j] for j in range(K1)]
    ext = old + first  # the j-th token's input s back is column K1 + j - s
    edge = jnp.concatenate([
        conv_taps([ext[K1 + j - s] for s in range(K1, -1, -1)], p)
        for j in range(K1)])  # (K1 R, C), row j R + r
    at_edge = (offset >= 0) & (offset < K1)
    pick = at_edge[:, None] & ((offset * R + row)[:, None]
                               == jnp.arange(K1 * R)[None, :])
    out = jnp.where(at_edge[:, None], move(pick, edge), out)
    cols = []
    for j in range(K1):  # a row of n < K1 - j tokens: the old column j + n
        col = old[K1 - 1]
        for n in range(K1 - 2 - j, -1, -1):
            col = jnp.where((row_len == n)[:, None], old[j + n], col)
        cols.append(jnp.where((back[j] >= 0)[:, None], last[j], col))
    return out, jnp.stack(cols, axis=1)


def ssm_inputs(xbc, dt, p, cfg):
    """The conv's output and the raw ``dt`` → ``x (..., H, P)``, ``B, C (...,
    G, N)``, ``dt`` float32 after bias and softplus (no clamp: the published
    config gives no ``time_step_limit``), ``A (H,)`` negative, ``D (H,)``."""
    H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, di = cfg.mamba_n_groups, cfg.mamba_state_size, cfg.mamba_d_inner
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(lead + (H, P))
    B = xbc[..., di:di + G * N].reshape(lead + (G, N))
    C = xbc[..., di + G * N:].reshape(lead + (G, N))
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    return x, B, C, dt, A, p["D"].astype(jnp.float32)


def mamba_out(y, z, p, cfg):
    """``y (..., H, P)`` float32 from the recurrence and the gate ``z`` →
    the layer's output ``(..., h)``: the gate first, then RMSNorm inside
    each group of ``d_inner / G`` channels, then the out projection."""
    G, di = cfg.mamba_n_groups, cfg.mamba_d_inner
    lead = z.shape[:-1]
    with jax.named_scope("ssm_gate_norm"):
        g = y.reshape(lead + (di,)) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(lead + (G, di // G))
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.norm_eps)
        g = (g.reshape(lead + (di,)) * p["norm_w"].astype(jnp.float32)
             ).astype(z.dtype)
    with jax.named_scope("ssm_out_proj"):
        return tfm._lin(g, p, "w_out", "b_out")


def mamba_rows(a, p, cfg, ssm, conv, layer, row, offset, row_start, row_len,
               slots, fresh, scanned):
    """A Mamba-2 layer over a flat batch ``a (T, h)`` of rows lying end to
    end, each from the state of its slot, up to the recurrence: → ``((z, x,
    B, C, dt, A, D): the gate and the recurrence's inputs, y (T, H, P), ssm,
    the rows' kept conv columns (R, K - 1, C))`` with the rows ``scanned``
    marks walked by ``ssd_chunk_scan`` (their states written to ``ssm``);
    ``y`` of the other rows is zero, for the caller to fill from the inputs
    (a mixed step's rows of one token) before ``mamba_out``."""
    z, xbc, dt = mamba_in_proj(a, p)
    with jax.named_scope("ssm_conv"):
        kept = jnp.where(fresh[:, None, None], 0, conv[layer, slots])
        xbc, kept = conv_ragged(xbc, kept, p, row, offset, row_start, row_len)
    x, B, C, dt, A, D = ssm_inputs(xbc, dt, p, cfg)
    with jax.named_scope("ssm_scan"):
        y, ssm = ssd_chunk_scan(ssm, layer, x, dt, A, B, C, D, row_start,
                                row_len, slots, fresh, scanned,
                                cfg.mamba_chunk_size)
    return (z, x, B, C, dt, A, D), y, ssm, kept


# ---------------------------------------------------------------------------
# the whole-sequence forward (training forward, v1 engine, tests)
# ---------------------------------------------------------------------------


def forward_hidden(params: Dict[str, Any], tokens: jax.Array, cfg,
                   attn_fn: Optional[tfm.AttentionFn] = None) -> jax.Array:
    """tokens (B, S) → hidden states (B, S, h) after the final norm: every
    sequence from an empty state, the layers unrolled in pattern order."""
    from ..moe.dropless import serving_moe_block

    if cfg.position != "none":
        raise ValueError("a mixer_pattern model's attention layers take no "
                         "positional embedding: position must be 'none'")
    Bn, S = tokens.shape
    T = Bn * S
    x = tfm.embed_tokens(params, tokens, cfg)
    attn = attn_fn or tfm.resolve_attention(cfg.attn_impl)
    rows = jnp.arange(Bn, dtype=jnp.int32)
    row = jnp.repeat(rows, S)
    offset = jnp.tile(jnp.arange(S, dtype=jnp.int32), Bn)
    row_start, row_len = rows * S, jnp.full((Bn,), S, jnp.int32)
    every = jnp.ones((Bn,), bool)
    H, P, N = cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_state_size
    layers = params["layers"]
    for kind, idx in layer_plan(cfg):
        lp = jax.tree.map(lambda a: a[idx], layers[kind])
        a_in = tfm._norm(x, lp["norm"], "rmsnorm", cfg.norm_eps)
        if kind == "M":
            ssm = jnp.zeros((1, Bn + 1, H, P, N), jnp.float32)
            conv = jnp.zeros((1, Bn + 1, cfg.mamba_conv_kernel - 1,
                              cfg.mamba_conv_dim), a_in.dtype)
            (z, *_), y, _, _ = mamba_rows(
                a_in.reshape(T, -1), lp["mamba"], cfg, ssm, conv,
                jnp.int32(0), row, offset, row_start, row_len, rows, every,
                every)
            out = mamba_out(y, z, lp["mamba"], cfg).reshape(x.shape)
        elif kind == "S":
            di = cfg.mamba_d_inner
            ssm = jnp.zeros((1, Bn + 1, N, di), jnp.float32)
            conv = jnp.zeros((1, Bn + 1, cfg.mamba_conv_kernel - 1, di),
                             a_in.dtype)
            (z, *_), y, _, _ = selective_ssm.selective_rows(
                a_in.reshape(T, -1), lp["mamba"], cfg, ssm, conv,
                jnp.int32(0), row, offset, row_start, row_len, rows, every,
                every)
            out = selective_ssm.gate_out(y, z, lp["mamba"]).reshape(x.shape)
        elif kind == "F":
            out = selective_ssm.ffn(a_in, lp["mlp"], cfg)
        elif kind == "E":
            out, _ = serving_moe_block(a_in, lp["moe"], cfg)
        else:
            out = tfm._attention_block(a_in, lp["attn"], cfg, None, None,
                                       attn)
        x = x + out
    return tfm._norm(x, params["final_norm"], "rmsnorm", cfg.norm_eps)
