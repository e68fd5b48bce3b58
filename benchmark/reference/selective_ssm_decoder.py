"""Plain reference for a decoder whose layers are a mixer AND a dense FFN, the
mixer a Mamba-1 selective-scan layer or attention without positions
(AI21-Jamba2-3B, ``model_type: jamba``).

Written from the model's ``config.json`` and the family's publications (Jamba,
arXiv:2403.19887; Mamba, arXiv:2312.00752), not from the program's model
file.  On one sequence ``x (S, hidden)``, ``a = RMSNorm(x)`` with the layer's
weight, eps 1e-6:

    layer i (0..L-1):  x <- x + mixer_i(RMSNorm(x; w_in_i))
                       x <- x + FFN_i(RMSNorm(x; w_ff_i))
    mixer_i is attention where i % attn_layer_period == attn_layer_offset
      (layers 7 and 21 of 28), Mamba elsewhere
    Mamba:  [x | z] = a W_in                  (hidden -> 2 d_inner, no bias;
                                               x first, the gate second)
            x_t = silu(sum_{j<4} cw[j] x_{t-3+j} + cb)   depthwise, causal,
                                                         zeros before t = 0
            [dt | B | C] = x W_x              (d_inner -> dt_rank + N + N)
            dt = RMSNorm(dt; w_dt), B = RMSNorm(B; w_B), C = RMSNorm(C; w_C)
            delta = softplus(dt W_dt + b_dt)  (the bias INSIDE the softplus)
            A = -exp(A_log)                   (d_inner x N)
            h_t[c, n] = exp(delta_t[c] A[c, n]) h_{t-1}[c, n]
                        + delta_t[c] B_t[n] x_t[c]              h_{-1} = 0
            y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]
            out = (y * silu(z)) W_out         (d_inner -> hidden, no bias)
    attention: q, k, v = a Wq, a Wk, a Wv (20 heads on ONE K/V head, no
            bias), causal softmax(q k^T / sqrt(head)) v, Wo; NO positional
            embedding of any kind
    FFN:    (silu(a W_gate) * (a W_up)) W_down          (dense in every layer)
    logits = RMSNorm(x_L; w_f) E^T            (tie_word_embeddings)

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence is a ``lax.scan``
over single tokens, the conv four shifted adds, the ``(S, S)`` causal mask is
computed in blocks of ``ATTN_BLOCK`` queries (so that a prompt of 33k fits);
no kernel, no cache, no batching.  It imports nothing of ``deepspeed_tpu``.

Departures from the publication, all of them:

* The layer order is the family's rule (period and offset), which the
  catalog's row does not spell out: the configuration file's ``assumed``.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights`` (the program stacks a kind's layers: ``"S"`` the Mamba
  mixers, ``"*"`` the attention mixers, ``"F"`` the FFNs, in layer order).
* ``faults`` (a frozenset of names, empty for the model) turns the reference
  into a named WRONG program, one fault each: what the comparison that
  decides ``correct`` is sized against (``tests/test_jamba2.py``,
  ``benchmark/tests/jamba2_wrong_programs.py``).
* ``length`` (None for the model): the Mamba layers' states stand still from
  that position on (``delta`` 0: no decay, no input), so that a pass over a
  padded sequence also gives every layer's state after ``length`` tokens:
  what a server holds in a sequence's slot.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, FrozenSet, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTN_BLOCK = 1024
FAULTS = ("dt_norm", "b_norm", "c_norm", "dt_bias_outside", "no_softplus",
          "gate_first_half", "conv_bias", "conv_over_gate", "no_D",
          "A_positive", "state_bf16", "attn_positions", "untied_head",
          "recurrence_bf16")
NONE: FrozenSet[str] = frozenset()


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x: jax.Array, theta: float = 10000.0) -> jax.Array:
    """The ``attn_positions`` fault's rotation: ``x (S, heads, d)``, the
    halves ``(i, i + d / 2)`` rotated by ``p theta^(-2i / d)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def recurrence(x, delta, A, B, C, D, state, faults: FrozenSet[str] = NONE):
    """The selective recurrence, one token at a time: ``x, delta (S,
    d_inner)``, ``A (d_inner, N)``, ``B, C (S, N)``, ``D (d_inner,)``,
    ``state (d_inner, N)`` → ``(y (S, d_inner), the last state)``, float32.
    ``state_bf16`` keeps the state in bfloat16 between tokens and
    ``recurrence_bf16`` rounds every product of the update to bfloat16: the
    wrong programs of those names."""
    # (``reduce_precision``, not a cast there and back: XLA on the TPU keeps
    # excess precision through a pair of converts, and the fault vanishes)
    def bf16(v):
        return jax.lax.reduce_precision(v, 8, 7)

    keep = bf16 if "state_bf16" in faults or "recurrence_bf16" in faults \
        else (lambda v: v)
    low = bf16 if "recurrence_bf16" in faults else (lambda v: v)

    def step(h, inp):
        x_t, d_t, B_t, C_t = inp
        decay = low(jnp.exp(low(d_t[:, None] * A)))
        h = keep(low(decay * h) + low(low(d_t * x_t)[:, None] * B_t[None, :]))
        return h, jnp.sum(low(h * C_t[None, :]), axis=1) + D * x_t

    h, y = jax.lax.scan(step, state.astype(F32), (x, delta, B, C))
    return y, h


@partial(jax.jit, static_argnames=("dt_rank", "eps", "faults"))
def mamba(a: jax.Array, w: Dict[str, Any], length: Optional[jax.Array] = None,
          *, dt_rank: int, eps: float, faults: FrozenSet[str] = NONE
          ) -> Tuple[jax.Array, jax.Array]:
    """The Mamba-1 mixer on one sequence ``a (S, hidden)`` from an empty
    state → (its output, the state ``(d_inner, N)`` after the last position,
    or after ``length`` positions)."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        xz = a @ w["w_in"].astype(F32)
        d_inner = xz.shape[1] // 2
        x, z = xz[:, :d_inner], xz[:, d_inner:]
        if "gate_first_half" in faults:
            x, z = z, x
        cw, cb = w["conv_w"].astype(F32), w["conv_b"].astype(F32)
        taps = cw.shape[0]

        def conv(v):
            padded = jnp.concatenate([jnp.zeros((taps - 1, v.shape[1]), F32),
                                      v])
            out = sum(cw[j] * padded[j:j + s] for j in range(taps))
            return out if "conv_bias" in faults else out + cb

        x = jax.nn.silu(conv(x))
        if "conv_over_gate" in faults:
            z = jax.nn.silu(conv(z))
        dbc = x @ w["w_x"].astype(F32)
        n = (dbc.shape[1] - dt_rank) // 2
        dt, B, C = dbc[:, :dt_rank], dbc[:, dt_rank:dt_rank + n], \
            dbc[:, dt_rank + n:]
        if "dt_norm" not in faults:
            dt = rms_norm(dt, w["dt_norm"], eps)
        if "b_norm" not in faults:
            B = rms_norm(B, w["b_norm"], eps)
        if "c_norm" not in faults:
            C = rms_norm(C, w["c_norm"], eps)
        up, bias = dt @ w["w_dt"].astype(F32), w["dt_bias"].astype(F32)
        if "dt_bias_outside" in faults:
            delta = jax.nn.softplus(up) + bias
        elif "no_softplus" in faults:
            delta = up + bias
        else:
            delta = jax.nn.softplus(up + bias)
        if length is not None:
            delta = jnp.where(jnp.arange(s)[:, None] < length, delta, 0.0)
        A = jnp.exp(w["A_log"].astype(F32))
        A = A if "A_positive" in faults else -A
        D = jnp.zeros((d_inner,), F32) if "no_D" in faults \
            else w["D"].astype(F32)
        y, state = recurrence(x, delta, A, B, C, D,
                              jnp.zeros((d_inner, n), F32), faults)
        return (y * jax.nn.silu(z)) @ w["w_out"].astype(F32), state


@partial(jax.jit, static_argnames=("heads", "kv_heads", "faults"))
def attention(a: jax.Array, w: Dict[str, Any], *, heads: int, kv_heads: int,
              faults: FrozenSet[str] = NONE) -> jax.Array:
    """Causal grouped-query attention without positions on one sequence, the
    ``(S, S)`` mask a block of queries at a time."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        q = (a @ w["wq"].astype(F32)).reshape(s, heads, -1)
        k = (a @ w["wk"].astype(F32)).reshape(s, kv_heads, -1)
        v = (a @ w["wv"].astype(F32)).reshape(s, kv_heads, -1)
        if "attn_positions" in faults:
            q, k = rope(q), rope(k)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        scale = q.shape[-1] ** -0.5
        block = min(ATTN_BLOCK, s)
        pad = -(-s // block) * block
        qb = jnp.pad(q, ((0, pad - s), (0, 0), (0, 0))).reshape(
            pad // block, block, heads, -1)

        def one(args):
            q_blk, first = args
            scores = jnp.einsum("qhd,khd->hqk", q_blk, k) * scale
            seen = (first + jnp.arange(block))[:, None] >= jnp.arange(s)[None]
            p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", p, v)

        o = jax.lax.map(one, (qb, jnp.arange(pad // block) * block))
        return o.reshape(pad, -1)[:s] @ w["wo"].astype(F32)


@jax.jit
def ffn(a: jax.Array, w: Dict[str, Any]) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(a @ w["w_gate"].astype(F32))
                * (a @ w["w_in"].astype(F32))) @ w["w_out"].astype(F32)


@partial(jax.jit, static_argnames=("eps", "untied"))
def head_logits(x: jax.Array, norm_scale: jax.Array, embed: jax.Array,
                other: jax.Array, *, eps: float, untied: bool = False
                ) -> jax.Array:
    """``RMSNorm(x) E^T``; ``untied``: the wrong program whose head is a
    matrix of its own (``other``, drawn from the embedding's key)."""
    with jax.default_matmul_precision("highest"):
        e = other if untied else embed.astype(F32)
        return rms_norm(x, norm_scale, eps) @ e.T


def is_attention(model: Mapping[str, Any], i: int) -> bool:
    return i % model["attn_layer_period"] == model["attn_layer_offset"]


def layer_weights(params: Mapping[str, Any], model: Mapping[str, Any], i: int
                  ) -> Tuple[str, Dict[str, Any], Dict[str, Any]]:
    """Published layer ``i`` out of the program's stacks → (``"*"`` or
    ``"S"``, the mixer's weights with its norm under ``"norm"``, the FFN's
    likewise)."""
    attn = sum(is_attention(model, j) for j in range(i))
    kind, idx = ("*", attn) if is_attention(model, i) else ("S", i - attn)
    stack = params["layers"][kind]
    inner = stack["attn" if kind == "*" else "mamba"]
    mixer = {k: v[idx] for k, v in inner.items()}
    mixer["norm"] = stack["norm"]["scale"][idx]
    f = params["layers"]["F"]
    mlp = {k: v[i] for k, v in f["mlp"].items()}
    mlp["norm"] = f["norm"]["scale"][i]
    return kind, mixer, mlp


def whole_pass(params: Mapping[str, Any], model: Mapping[str, Any],
               tokens: jax.Array, last: int = 0,
               faults: FrozenSet[str] = NONE,
               length: Optional[int] = None) -> Dict[str, jax.Array]:
    """One uncached pass over ``tokens (S,)`` → ``{"logits": (S, vocab), or
    from the ``last``-th position from the end on, in both cases up to
    ``length`` where it is given; "states": (Mamba layers, d_inner, N),
    every Mamba layer's state after the last position, or after ``length``
    positions; "hidden": (S, hidden) before the final norm}``."""
    faults = frozenset(faults)
    eps = float(model["rms_norm_eps"])
    x = params["embed"]["tokens"][tokens].astype(F32)
    at = None if length is None else jnp.asarray(length, jnp.int32)
    states = []
    for i in range(model["num_hidden_layers"]):
        kind, mixer, mlp = layer_weights(params, model, i)
        a = rms_norm(x, mixer["norm"], eps)
        if kind == "S":
            out, state = mamba(a, mixer, at, dt_rank=model["mamba_dt_rank"],
                               eps=eps, faults=faults)
            states.append(state)
        else:
            out = attention(a, mixer, heads=model["num_attention_heads"],
                            kv_heads=model["num_key_value_heads"],
                            faults=faults)
        x = x + out
        x = x + ffn(rms_norm(x, mlp["norm"], eps), mlp)
    embed = params["embed"]["tokens"]
    other = jax.random.normal(jax.random.PRNGKey(0x7E1), embed.shape, F32) \
        * embed.shape[1] ** -0.5 if "untied_head" in faults else embed
    stop = None if length is None else int(length)  # no row of the padding
    return {"logits": head_logits(x[-last:stop] if last else x[:stop],
                                  params["final_norm"]["scale"], embed, other,
                                  eps=eps, untied="untied_head" in faults),
            "states": jnp.stack(states), "hidden": x}


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array, faults: FrozenSet[str] = NONE) -> jax.Array:
    """tokens ``(S,)`` → float32 logits ``(S, vocab)``."""
    return whole_pass(params, model, tokens, faults=faults)["logits"]


def hidden_states(params: Mapping[str, Any], model: Mapping[str, Any],
                  tokens: jax.Array, faults: FrozenSet[str] = NONE
                  ) -> jax.Array:
    """tokens ``(S,)`` → the hidden states after the final norm ``(S,
    hidden)``: what the program's ``forward_hidden`` gives."""
    out = whole_pass(params, model, tokens, last=1, faults=faults)
    return rms_norm(out["hidden"], params["final_norm"]["scale"],
                    float(model["rms_norm_eps"]))
