"""Plain reference for a dense decoder-only transformer (Mistral-7B's family).

Written from the published architecture (Jiang et al. 2023, "Mistral 7B",
and the released ``mistral-src`` model file), not from the program's model
file: pre-norm residual blocks, RMSNorm, grouped-query attention with rotary
position embedding on adjacent pairs (the released code's complex form; the
Hugging Face port permutes the projections to use the half-split form, which
is the same function of differently ordered weights), a causal mask under a
sliding window, a SwiGLU MLP and an untied output head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in bf16 passes): no Pallas kernel, no cache, no batching of
requests, one sequence and one layer at a time.  Departures from the
publication: none in the mathematics; weights are whatever tree the caller
hands in, read through ``layer_weights`` so that int8 codes are dequantized
here, a layer at a time, by this file's own arithmetic.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping

import jax
import jax.numpy as jnp

F32 = jnp.float32


def dense_weight(w: Any) -> jax.Array:
    """A projection as a float32 ``(K, N)`` matrix.  A plain array is cast;
    a weight-only-quantized node (anything with ``codes`` int8 ``(K, N)``
    and ``scales`` ``(K / group, N)``) is ``codes * scale`` per K-group."""
    if hasattr(w, "codes"):
        if w.bits != 8:
            raise NotImplementedError("the reference dequantizes int8 only")
        k, n = w.codes.shape
        groups = w.scales.shape[0]
        codes = w.codes.astype(F32).reshape(groups, k // groups, n)
        return (codes * w.scales.astype(F32)[:, None, :]).reshape(k, n)
    return w.astype(F32)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x ``(S, heads, head_dim)``; position p rotates the adjacent pair
    ``(2i, 2i+1)`` by the angle ``p * theta ** (-2i / head_dim)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps",
                                   "window"))
def layer(x: jax.Array, w: Dict[str, Any], *, heads: int, kv_heads: int,
          theta: float, eps: float, window: int) -> jax.Array:
    """One block on one sequence ``x (S, hidden)``, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        s, _ = x.shape
        a = rms_norm(x, w["ln1"], eps)
        q = (a @ dense_weight(w["wq"])).reshape(s, heads, -1)
        k = (a @ dense_weight(w["wk"])).reshape(s, kv_heads, -1)
        v = (a @ dense_weight(w["wv"])).reshape(s, kv_heads, -1)
        q, k = rope(q, theta), rope(k, theta)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        logits = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(F32(q.shape[-1]))
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        seen = j <= i
        if window:
            seen &= i - j < window
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        o = jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)
        x = x + o @ dense_weight(w["wo"])
        m = rms_norm(x, w["ln2"], eps)
        gate = jax.nn.silu(m @ dense_weight(w["w_gate"]))
        return x + (gate * (m @ dense_weight(w["w_in"]))) @ dense_weight(
            w["w_out"])


@partial(jax.jit, static_argnames=("eps",))
def head_logits(x: jax.Array, norm_scale: jax.Array, w_head: jax.Array,
                *, eps: float) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, norm_scale, eps) @ w_head.astype(F32)


def layer_weights(params: Mapping[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's parameter tree (projections stacked on a
    leading layer axis; a quantized projection keeps its node type) under
    this file's names.  The only place that knows the program's layout."""
    lay = params["layers"]
    take = partial(jax.tree.map, lambda a: a[i])
    return {"ln1": lay["ln1"]["scale"][i], "ln2": lay["ln2"]["scale"][i],
            "wq": take(lay["attn"]["wq"]), "wk": take(lay["attn"]["wk"]),
            "wv": take(lay["attn"]["wv"]), "wo": take(lay["attn"]["wo"]),
            "w_gate": take(lay["mlp"]["w_gate"]),
            "w_in": take(lay["mlp"]["w_in"]),
            "w_out": take(lay["mlp"]["w_out"])}


def hidden_states(params: Mapping[str, Any], model: Mapping[str, Any],
                  tokens: jax.Array) -> jax.Array:
    """``tokens (S,)`` → the last block's output ``(S, hidden)``."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    for i in range(model["num_hidden_layers"]):
        x = layer(x, layer_weights(params, i),
                  heads=model["num_attention_heads"],
                  kv_heads=model["num_key_value_heads"],
                  theta=float(model["rope_theta"]),
                  eps=float(model["rms_norm_eps"]),
                  window=int(model.get("sliding_window") or 0))
    return x


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array) -> jax.Array:
    """``tokens (S,)`` → next-token logits ``(S, vocab)`` in float32."""
    return head_logits(hidden_states(params, model, tokens),
                       params["final_norm"]["scale"], params["lm_head"]["w"],
                       eps=float(model["rms_norm_eps"]))


@jax.jit
def _nll_sum(lg: jax.Array, tokens: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(lg[:-1], -1)
    return -jnp.take_along_axis(logp, tokens[1:, None], -1).sum()


def next_token_loss(params: Mapping[str, Any], model: Mapping[str, Any],
                    batch: jax.Array) -> float:
    """Mean next-token cross entropy of ``batch (B, S)``: every position but
    each sequence's last predicts its successor."""
    total = sum(float(_nll_sum(logits(params, model, seq), seq))
                for seq in batch)
    return total / (batch.shape[0] * (batch.shape[1] - 1))


@jax.jit
def _margins(lg: jax.Array, served: jax.Array):
    """How far the reference logit of each served token lies under the
    reference maximum, and how many tokens the reference ranks above it.
    One materialised float32 copy, so both read the same values (XLA's
    excess precision otherwise lets them read differently rounded ones)."""
    lg = jax.lax.optimization_barrier(lg)
    got = jnp.take_along_axis(lg, served[:, None], -1)
    return lg.max(-1) - got[:, 0], (lg > got).sum(-1)


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference, which reads
    the whole sequence in one uncached pass."""
    lg = logits(params, model, sequence)
    return _margins(lg[n_prompt - 1:-1], sequence[n_prompt:])
