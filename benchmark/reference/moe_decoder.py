"""Plain reference for a sparse-expert decoder-only transformer (OLMoE-1B-7B).

Written from the published architecture (Muennighoff et al. 2024, "OLMoE:
Open Mixture-of-Experts Language Models", and the model's ``config.json``),
not from the program's model file.  One block, on one sequence ``x (S, H)``:

    a   = RMSNorm(x; ln1)
    q   = RMSNorm(a Wq; q_norm)      # over the whole projection, before the
    k   = RMSNorm(a Wk; k_norm)      # split into heads and before RoPE
    v   = a Wv                       # no norm
    h   = x + Wo . causal_softmax_attention(RoPE(q), RoPE(k), v)
    m   = RMSNorm(h; ln2)
    p   = softmax(m Wr)              # float32, over all experts
    S,w = top-k of p                 # norm_topk_prob false: w stays the raw
                                     # probabilities, NOT renormalised
    y   = h + sum_{e in S} w_e . W_down,e (silu(m W_gate,e) * (m W_up,e))

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no Pallas kernel, no cache, no
batching of requests, no routing layout.  Every expert is computed for every
position, one expert at a time, and the top-k mask picks which count.

Departures from the publication, all of them:

* RoPE rotates adjacent pairs ``(2i, 2i+1)`` (``dense_decoder.rope``), where
  the Hugging Face port rotates the half-split pairs ``(i, i + d/2)``.  It is
  the same function of differently ordered ``Wq`` / ``Wk`` columns (and
  ``q_norm`` / ``k_norm`` entries: an RMS over the whole projection does not
  see the order); with random weights the order means nothing.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights``; int8 codes are dequantized here, an expert at a time, by
  ``dense_decoder.dense_weight``'s arithmetic (``codes * scale`` per K-group).
* A top-k tie (two equal probabilities at the k-th place) goes to the lower
  expert index, as ``jax.lax.top_k`` breaks it.  ``router_margin`` reports how
  far each position is from such a tie, so that a comparison can leave out
  the positions where a lower-precision router may legitimately pick the
  other expert.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _margins, dense_weight,
                                               head_logits, rms_norm, rope)


def _expert_ffn(m: jax.Array, w: Dict[str, Any]) -> jax.Array:
    """Every expert on every position: ``m (S, H)`` → ``(E, S, H)``, one
    expert at a time (``lax.map``), each dequantized where it is used."""
    def one(e):
        gate, up, down = (dense_weight(jax.tree.map(lambda a: a[e], w[k]))
                          for k in ("w_gate", "w_in", "w_out"))
        return (jax.nn.silu(m @ gate) * (m @ up)) @ down

    return jax.lax.map(one, jnp.arange(w["router"].shape[-1]))


@partial(jax.jit, static_argnames=("top_k", "norm_topk"))
def router(m: jax.Array, w_router: jax.Array, *, top_k: int, norm_topk: bool
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``m (S, hidden)`` → (probabilities over all experts ``(S, E)``, the
    top-k gate weights ``(S, k)``, their experts ``(S, k)``, each position's
    margin: the k-th largest probability less the (k+1)-th).  Float32."""
    with jax.default_matmul_precision("highest"):
        p = jax.nn.softmax(m.astype(F32) @ w_router.astype(F32), -1)
        top, idx = jax.lax.top_k(p, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        top, idx = top[:, :top_k], idx[:, :top_k]
        if norm_topk:
            top = top / top.sum(-1, keepdims=True)
        return p, top, idx, margin


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "eps",
                                   "top_k", "norm_topk"))
def layer(x: jax.Array, w: Dict[str, Any], *, heads: int, kv_heads: int,
          theta: float, eps: float, top_k: int, norm_topk: bool
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One block on one sequence ``x (S, hidden)``, float32 throughout.
    → (the block's output, each position's router margin: the k-th largest
    probability less the (k+1)-th, the router's input ``m (S, hidden)``)."""
    with jax.default_matmul_precision("highest"):
        s, _ = x.shape
        a = rms_norm(x, w["ln1"], eps)
        q = rms_norm(a @ dense_weight(w["wq"]), w["q_norm"], eps)
        k = rms_norm(a @ dense_weight(w["wk"]), w["k_norm"], eps)
        v = a @ dense_weight(w["wv"])
        q = rope(q.reshape(s, heads, -1), theta)
        k = rope(k.reshape(s, kv_heads, -1), theta)
        v = v.reshape(s, kv_heads, -1)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        logits = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(F32(q.shape[-1]))
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        o = jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)
        h = x + o @ dense_weight(w["wo"])

        m = rms_norm(h, w["ln2"], eps)
        p, top, idx, margin = router(m, w["router"], top_k=top_k,
                                     norm_topk=norm_topk)
        gates = jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(top)
        y = jnp.einsum("se,esh->sh", gates, _expert_ffn(m, w))
        return h + y, margin, m


def layer_weights(params: Mapping[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's parameter tree (leaves stacked on a
    leading layer axis; a quantized leaf keeps its node type) under this
    file's names.  The only place that knows the program's layout."""
    lay = params["layers"]
    take = partial(jax.tree.map, lambda a: a[i])
    attn, moe = lay["attn"], lay["moe"]
    return {"ln1": lay["ln1"]["scale"][i], "ln2": lay["ln2"]["scale"][i],
            "q_norm": attn["q_norm"]["scale"][i],
            "k_norm": attn["k_norm"]["scale"][i],
            "wq": take(attn["wq"]), "wk": take(attn["wk"]),
            "wv": take(attn["wv"]), "wo": take(attn["wo"]),
            "router": moe["router"][i],
            "w_gate": take(moe["w_gate"]), "w_in": take(moe["w_in"]),
            "w_out": take(moe["w_out"])}


def _blocks(params: Mapping[str, Any], model: Mapping[str, Any],
            tokens: jax.Array, layers: int):
    """The first ``layers`` blocks on ``tokens (S,)`` → (the last one's
    output, the smallest router margin of each position over them, the last
    one's router input)."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    margin, m = jnp.full(tokens.shape, jnp.inf, F32), None
    for i in range(layers):
        x, mg, m = layer(x, layer_weights(params, i),
                         heads=model["num_attention_heads"],
                         kv_heads=model["num_key_value_heads"],
                         theta=float(model["rope_theta"]),
                         eps=float(model["rms_norm_eps"]),
                         top_k=model["num_experts_per_tok"],
                         norm_topk=bool(model["norm_topk_prob"]))
        margin = jnp.minimum(margin, mg)
    return x, margin, m


def hidden_states(params: Mapping[str, Any], model: Mapping[str, Any],
                  tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``tokens (S,)`` → (the last block's output ``(S, hidden)``, the
    smallest router margin of each position over the layers ``(S,)``)."""
    return _blocks(params, model, tokens, model["num_hidden_layers"])[:2]


def router_input(params: Mapping[str, Any], model: Mapping[str, Any],
                 tokens: jax.Array, layer_index: int) -> jax.Array:
    """``tokens (S,)`` → what block ``layer_index``'s router reads:
    ``RMSNorm(h; ln2)`` of that block, ``(S, hidden)`` in float32."""
    return _blocks(params, model, tokens, layer_index + 1)[2]


def logits_and_margin(params: Mapping[str, Any], model: Mapping[str, Any],
                      tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """``tokens (S,)`` → (next-token logits ``(S, vocab)`` in float32, each
    position's smallest router margin ``(S,)``)."""
    x, margin = hidden_states(params, model, tokens)
    return head_logits(x, params["final_norm"]["scale"],
                       params["lm_head"]["w"],
                       eps=float(model["rms_norm_eps"])), margin


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array) -> jax.Array:
    return logits_and_margin(params, model, tokens)[0]


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference, which reads
    the whole sequence in one uncached pass (``dense_decoder``'s rule)."""
    lg = logits(params, model, sequence)
    return _margins(lg[n_prompt - 1:-1], sequence[n_prompt:])
