"""Plain reference for a sparse-expert decoder whose attention layers are of
two kinds, sliding-window and global (Mellum2-12B-A2.5B).

Written from the model's published ``config.json`` (``model_type: mellum``:
``layer_types``, ``sliding_window``, ``rope_parameters`` by layer kind,
``mlp_layer_types`` all sparse, ``norm_topk_prob``) and the YaRN paper (Peng
et al. 2023, "YaRN: Efficient Context Window Extension of Large Language
Models") as Hugging Face's ``rope_type: "yarn"`` states it, not from the
program's model file.  One block ``i`` on one sequence ``x (S, H)``:

    kind = layer_types[i]                        # sliding_attention | full_attention
    a   = RMSNorm(x; ln1)
    q, k, v = a Wq, a Wk, a Wv                   # no bias, no q/k norm
    q, k = RoPE_kind(q), RoPE_kind(k)            # over all head_dim dims
      sliding: inv_freq_j = theta ** (-2j / d),  j = 0 .. d/2 - 1
      full (YaRN, static): e_j = theta ** (-2j / d)
        turns(n) = d ln(original_max / (2 pi n)) / (2 ln theta)
        low = floor(turns(beta_fast)), high = ceil(turns(beta_slow)),
        both clamped to [0, d - 1]
        r_j = 1 - clip((j - low) / (high - low), 0, 1)
        inv_freq_j = r_j e_j + (1 - r_j) e_j / factor
        cos and sin are multiplied by attention_factor
    h   = x + Wo . softmax(q kT / sqrt(d)) v     # grouped-query; query p sees
                                                 # key j iff j <= p and (kind
                                                 # full or p - j < window)
    m   = RMSNorm(h; ln2)
    p   = softmax(m Wr)                          # float32, over all experts
    S,w = top-k of p;  w <- w / sum(w)           # norm_topk_prob true
    y   = h + sum_{e in S} w_e . W_down,e (silu(m W_gate,e) * (m W_up,e))

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no Pallas kernel, no cache, no
batching of requests, no routing layout.  Each layer kind has its explicit
``(S, S)`` mask; every expert is computed for every position and the top-k
mask picks which count.  Only to bound memory at a context of thousands of
positions, a layer walks its query rows (and the expert FFN its positions)
in slices of ``ROWS``: each slice reads its rows of the same mask against
all keys.

Departures from the publication, all of them:

* RoPE rotates adjacent pairs ``(2j, 2j+1)`` where the Hugging Face port
  rotates the half-split pairs ``(j, j + d/2)``: the same function of
  differently ordered ``Wq`` / ``Wk`` columns; with random weights the order
  means nothing.
* What the config is silent on is read as the plain pre-norm decoder: no q/k
  norm, no biases, a softmax router in float32, no shared expert; the
  multi-token head the model card mentions is not computed.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights``; int8 codes are dequantized here, an expert at a time
  (``dense_decoder.dense_weight``: ``codes * scale`` per K-group).
* A top-k tie goes to the lower expert index, as ``jax.lax.top_k`` breaks it
  (``moe_decoder.router``, whose margin says how far a position is from one).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _margins, dense_weight,
                                               head_logits, rms_norm)
from benchmark.reference.moe_decoder import _expert_ffn, router

#: query rows (and expert-FFN positions) computed at a time
ROWS = 512


def inv_freq(rope: Mapping[str, Any], d: int) -> Tuple[jax.Array, float]:
    """One layer kind's ``rope_parameters`` entry → (``d / 2`` inverse
    frequencies, the factor on cos and sin)."""
    theta = float(rope["rope_theta"])
    j = jnp.arange(d // 2, dtype=F32)
    e = theta ** (-2.0 * j / d)
    if rope["rope_type"] == "default":
        return e, 1.0
    if rope["rope_type"] != "yarn":
        raise NotImplementedError(f"rope_type {rope['rope_type']!r}")

    def turns(n: float) -> float:
        return d * math.log(rope["original_max_position_embeddings"]
                            / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(turns(rope["beta_fast"])), 0)
    high = min(math.ceil(turns(rope["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    r = 1.0 - jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return (r * e + (1.0 - r) * e / float(rope["factor"]),
            float(rope["attention_factor"]))


def rotate(x: jax.Array, freq: jax.Array, scale: float) -> jax.Array:
    """x ``(S, heads, d)``: position p rotates the adjacent pair
    ``(2j, 2j+1)`` by the angle ``p * freq[j]``; cos and sin times
    ``scale``."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[
        :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def band_mask(s: int, window: int) -> jax.Array:
    """The ``(S, S)`` mask of one layer kind: query p (row) sees key j
    (column) iff ``j <= p`` and, with a window, ``p - j < window``."""
    p, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= p
    if window:
        seen &= p - j < window
    return seen


def _rope_key(rope: Mapping[str, Any]) -> tuple:
    return tuple(sorted(rope.items()))


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "window", "rope", "top_k", "norm_topk"))
def layer(x: jax.Array, w: Dict[str, Any], *, heads: int, kv_heads: int,
          head_dim: int, eps: float, window: int, rope: tuple, top_k: int,
          norm_topk: bool) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One block on one sequence ``x (S, hidden)``, float32 throughout;
    ``window`` 0 is a global layer; ``rope`` is the kind's
    ``rope_parameters`` entry as sorted items.  → (the block's output, each
    position's router margin, the router's input ``m (S, hidden)``)."""
    with jax.default_matmul_precision("highest"):
        s, _ = x.shape
        freq, scale = inv_freq(dict(rope), head_dim)
        a = rms_norm(x, w["ln1"], eps)
        q = rotate((a @ dense_weight(w["wq"])).reshape(s, heads, head_dim),
                   freq, scale)
        k = rotate((a @ dense_weight(w["wk"])).reshape(s, kv_heads, head_dim),
                   freq, scale)
        v = (a @ dense_weight(w["wv"])).reshape(s, kv_heads, head_dim)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        seen = band_mask(s, window)
        outs = []
        for r0 in range(0, s, ROWS):  # query rows r0 .. against all keys
            rows = slice(r0, min(r0 + ROWS, s))
            logits = jnp.einsum("shd,thd->hst", q[rows], k) / jnp.sqrt(
                F32(head_dim))
            probs = jax.nn.softmax(
                jnp.where(seen[rows][None], logits, -jnp.inf), -1)
            outs.append(jnp.einsum("hst,thd->shd", probs, v))
        o = jnp.concatenate(outs, 0).reshape(s, -1)
        h = x + o @ dense_weight(w["wo"])

        m = rms_norm(h, w["ln2"], eps)
        p, top, idx, margin = router(m, w["router"], top_k=top_k,
                                     norm_topk=norm_topk)
        gates = jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(top)
        y = jnp.concatenate([
            jnp.einsum("se,esh->sh", gates[r0:r0 + ROWS],
                       _expert_ffn(m[r0:r0 + ROWS], w))
            for r0 in range(0, s, ROWS)], 0)
        return h + y, margin, m


def layer_weights(params: Mapping[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's parameter tree (leaves stacked on a
    leading layer axis; a quantized leaf keeps its node type) under this
    file's names.  The only place that knows the program's layout."""
    lay = params["layers"]
    take = partial(jax.tree.map, lambda a: a[i])
    attn, moe = lay["attn"], lay["moe"]
    return {"ln1": lay["ln1"]["scale"][i], "ln2": lay["ln2"]["scale"][i],
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
        kind = model["layer_types"][i]
        x, mg, m = layer(
            x, layer_weights(params, i),
            heads=model["num_attention_heads"],
            kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], eps=float(model["rms_norm_eps"]),
            window=(int(model["sliding_window"])
                    if kind == "sliding_attention" else 0),
            rope=_rope_key(model["rope_parameters"][kind]),
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
    """``tokens (S,)`` → what block ``layer_index``'s router reads."""
    return _blocks(params, model, tokens, layer_index + 1)[2]


def logits_and_margin(params: Mapping[str, Any], model: Mapping[str, Any],
                      tokens: jax.Array, last: Optional[int] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """``tokens (S,)`` → (next-token logits in float32, each position's
    smallest router margin).  Every block reads the whole sequence; with
    ``last`` only the final ``last`` positions go through the head (a long
    context's logits of every position are gigabytes nobody compares)."""
    x, margin = hidden_states(params, model, tokens)
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    return head_logits(x, params["final_norm"]["scale"],
                       params["lm_head"]["w"],
                       eps=float(model["rms_norm_eps"])), margin


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array, last: Optional[int] = None) -> jax.Array:
    return logits_and_margin(params, model, tokens, last)[0]


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference, which reads
    the whole sequence in one uncached pass (``dense_decoder``'s rule)."""
    lg = logits(params, model, sequence, last=len(sequence) - n_prompt + 1)
    return _margins(lg[:-1], sequence[n_prompt:])
