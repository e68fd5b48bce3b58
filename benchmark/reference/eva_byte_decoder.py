"""Plain reference for EvaByte-6.5B: a dense decoder whose attention is EVA
(Zheng et al., "Efficient Attention via Control Variates", arXiv:2302.04542,
section 4) in the causal, deterministic form the EvaByte release describes.

Written from the published config's keys (``attention_class: eva``,
``window_size`` W, ``chunk_size`` C, ``num_pred_heads``,
``norm_add_unit_offset``, ``rope_theta``) and the paper, not from the
program's model file.  Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``: no cache, no kernel, no
batching, one sequence and one layer at a time, weights the dequantized
codes (``dense_decoder.dense_weight``); attention is blocked over the
queries of one window at a time so that 16k bytes fit the chip.

For a layer with input ``x`` and positions ``t``, ``s = 1 / sqrt(d)``:

* block: ``h = x / rms(x) * (1 + g)``; ``x <- x + Attn(h) W_o``;
  ``x <- x + (silu(h' W_gate) * (h' W_up)) W_down`` with ``h'`` the same
  norm of the new ``x``; no bias.  After the last layer the same norm and
  ``logits = norm(x) W_head``, ``W_head`` hidden x (heads x vocab);
* ``q_t, k_t, v_t``: heads of ``d`` from ``h W_q, h W_k, h W_v``, RoPE on
  all ``d`` dims of ``q`` and ``k``;
* position ``m`` lies in window ``m // W`` and chunk ``m // C``;
* a chunk's summary, from the layer's per-head vectors ``phi_h``, ``mu_h``:
  ``a_m = softmax_{m in chunk j}(s phi_h . k_m)``, ``v~_j = sum_m a_m v_m``,
  ``k~_j = mean_m k_m + mu_h``;
* query ``t``, head ``h``: exact keys ``E_t = {m : m // W = t // W, m <=
  t}``, summaries ``R_t = {j : j < (t // W) (W / C)}``; ONE softmax over the
  union: ``o = (sum_E exp(s q.k_m) v_m + sum_R exp(s q.k~_j) v~_j) / Z``.

What the config's keys do not settle is read as listed below, each with a
switch (``faults``) that reads it the other way, so that what a wrong
reading would change can be shown (``FAULTS``; the tier-1 tests hold each
to moving the logits past the comparison's tolerance):

(a) ``weighted_key``: ``k~`` the ``a``-weighted key, not the mean key;
(b) ``rf_norm``: pooling weights with the random-feature form's
    ``- |k_m|^2 / 2`` term;
(c) ``sliding``: windows slide (exact keys ``t - W < m <= t``, summaries of
    the chunks wholly behind them) and do not tumble;
(d) ``heads_tied``: every head scores with head 0's map (the reading here:
    eight independent linear maps of the final norm's output, head ``i``
    scoring byte ``t + 1 + i``, no block of their own);
(e) ``rope_half``: rotate-half over the whole head, on the SAME weights
    (the published form; with seeded weights it is the reading here, adjacent
    pairs, of projections whose columns are permuted);
and three that mimic a program that dropped a part: ``no_mu``,
``uniform_pool`` (``phi`` unused), ``no_unit_offset``.  ``ROUNDINGS`` are no
faults: ``bf16_stream`` rounds the residual stream to bfloat16 after each
add, which is (f) ``fp32_skip_add`` as the program runs it (the sum formed in
float32, rounded to the activation type), and moves the logits by what the
comparison allows for.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference import dense_decoder
from benchmark.reference.dense_decoder import _margins

F32 = jnp.float32

FAULTS = ("weighted_key", "rf_norm", "sliding", "heads_tied", "rope_half",
          "no_mu", "uniform_pool", "no_unit_offset")
ROUNDINGS = ("bf16_stream",)
#: the nearest precision below the configuration's: the int8 codes rounded
#: to 6 bits before they are dequantized.  What a limit of the comparison
#: has to call not correct
CONTROLS = ("int6",)


def _weights(faults: Tuple[str, ...]):
    """``dense_decoder.dense_weight``, under ``int6`` on codes that keep
    their upper six bits."""
    if "int6" not in faults:
        return dense_decoder.dense_weight

    def coarse(w):
        if hasattr(w, "codes"):
            w = dataclasses.replace(w, codes=(
                jnp.round(w.codes.astype(F32) / 4.0) * 4.0
            ).astype(w.codes.dtype))
        return dense_decoder.dense_weight(w)

    return coarse


def rms_norm(x, g, eps: float, offset: bool = True):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * ((1.0 if offset else 0.0) + g.astype(F32))


def rope(x, theta: float, half: bool = False):
    """``x (S, heads, d)``: position p turns pair i by ``p theta^(-2i/d)``;
    the pair is ``(2i, 2i + 1)``, or with ``half`` ``(i, i + d/2)``."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if half:
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape)


def summaries(k, v, phi, mu, chunk: int, faults: Tuple[str, ...] = ()):
    """``k, v (S, H, d)``, S whole chunks → ``k~, v~ (S / chunk, H, d)``."""
    s_len, h, d = k.shape
    scale = 1.0 / jnp.sqrt(F32(d))
    kc = k.reshape(s_len // chunk, chunk, h, d)
    vc = v.reshape(kc.shape)
    pool = scale * jnp.einsum("jchd,hd->jch", kc, phi.astype(F32))
    if "uniform_pool" in faults:
        pool = jnp.zeros_like(pool)
    if "rf_norm" in faults:
        pool = pool - 0.5 * scale * jnp.sum(kc * kc, -1)
    a = jax.nn.softmax(pool, axis=1)
    v_s = jnp.einsum("jch,jchd->jhd", a, vc)
    k_s = (jnp.einsum("jch,jchd->jhd", a, kc) if "weighted_key" in faults
           else kc.mean(1))
    if "no_mu" not in faults:
        k_s = k_s + mu.astype(F32)
    return k_s, v_s


def _softmax_two(e, r, v_e, v_r):
    """One softmax over exact scores ``e (H, Q, K)`` and summary scores ``r
    (H, Q, J)`` (masked entries -inf) → ``(Q, H, d)``."""
    p = jax.nn.softmax(jnp.concatenate([e, r], -1), -1)
    return (jnp.einsum("hqk,khd->qhd", p[..., :e.shape[-1]], v_e)
            + jnp.einsum("hqj,jhd->qhd", p[..., e.shape[-1]:], v_r))


def eva_attention(q, k, v, phi, mu, window: int, chunk: int,
                  faults: Tuple[str, ...] = ()):
    """``q, k, v (S, H, d)``, S whole windows → ``(S, H, d)``."""
    s_len, _, d = q.shape
    scale = 1.0 / jnp.sqrt(F32(d))
    k_s, v_s = summaries(k, v, phi, mu, chunk, faults)
    per = window // chunk
    j = jnp.arange(k_s.shape[0])
    if "sliding" in faults:  # small sizes only: every key against every query
        t, m = jnp.arange(s_len)[:, None], jnp.arange(s_len)[None, :]
        e = scale * jnp.einsum("qhd,khd->hqk", q, k)
        r = scale * jnp.einsum("qhd,jhd->hqj", q, k_s)
        e = jnp.where(((m <= t) & (m > t - window))[None], e, -jnp.inf)
        r = jnp.where((((j[None, :] + 1) * chunk - 1) <= t - window)[None],
                      r, -jnp.inf)
        return _softmax_two(e, r, v, v_s)
    causal = jnp.arange(window)[None, :] <= jnp.arange(window)[:, None]

    def one_window(w):
        at = w * window
        q_w, k_w, v_w = (jax.lax.dynamic_slice_in_dim(a, at, window)
                         for a in (q, k, v))
        e = scale * jnp.einsum("qhd,khd->hqk", q_w, k_w)
        r = scale * jnp.einsum("qhd,jhd->hqj", q_w, k_s)
        e = jnp.where(causal[None], e, -jnp.inf)
        r = jnp.where((j < w * per)[None, None, :], r, -jnp.inf)
        return _softmax_two(e, r, v_w, v_s)

    out = jax.lax.map(one_window, jnp.arange(s_len // window))
    return out.reshape(q.shape)


@partial(jax.jit, static_argnames=("heads", "theta", "eps", "window", "chunk",
                                   "faults"))
def layer(x, w: Dict[str, Any], *, heads: int, theta: float, eps: float,
          window: int, chunk: int, faults: Tuple[str, ...] = ()):
    """One block on one sequence ``x (S, hidden)``, S whole windows."""
    with jax.default_matmul_precision("highest"):
        s_len, _ = x.shape
        dense_weight = _weights(faults)
        offset = "no_unit_offset" not in faults
        stream = ((lambda a: a.astype(jnp.bfloat16).astype(F32))
                  if "bf16_stream" in faults else (lambda a: a))
        a = rms_norm(x, w["ln1"], eps, offset)
        q, k, v = ((a @ dense_weight(w[n])).reshape(s_len, heads, -1)
                   for n in ("wq", "wk", "wv"))
        half = "rope_half" in faults
        q, k = rope(q, theta, half), rope(k, theta, half)
        o = eva_attention(q, k, v, w["phi"], w["mu"], window, chunk, faults)
        x = stream(x + o.reshape(s_len, -1) @ dense_weight(w["wo"]))
        m = rms_norm(x, w["ln2"], eps, offset)
        gate = jax.nn.silu(m @ dense_weight(w["w_gate"]))
        return stream(x + (gate * (m @ dense_weight(w["w_in"])))
                      @ dense_weight(w["w_out"]))


@partial(jax.jit, static_argnames=("heads", "theta", "eps", "chunk",
                                   "faults"))
def layer_summaries(x, w: Dict[str, Any], *, heads: int, theta: float,
                    eps: float, chunk: int, faults: Tuple[str, ...] = ()):
    """The summaries one block makes of its input ``x (S, hidden)``, S whole
    chunks: ``k~, v~ (S / chunk, heads, d)``."""
    with jax.default_matmul_precision("highest"):
        dense_weight = _weights(faults)
        a = rms_norm(x, w["ln1"], eps, "no_unit_offset" not in faults)
        k, v = ((a @ dense_weight(w[n])).reshape(x.shape[0], heads, -1)
                for n in ("wk", "wv"))
        return summaries(rope(k, theta, "rope_half" in faults), v, w["phi"],
                         w["mu"], chunk, faults)


@partial(jax.jit, static_argnames=("eps", "vocab", "faults"))
def head_logits(x, g, w_head, *, eps: float, vocab: int,
                faults: Tuple[str, ...] = ()):
    """Every head's logits, ``(S, heads x vocab)``, head 0's columns first."""
    with jax.default_matmul_precision("highest"):
        w_head = w_head.astype(F32)
        if "heads_tied" in faults:
            w_head = jnp.tile(w_head[:, :vocab], (1, w_head.shape[1] // vocab))
        return rms_norm(x, g, eps, "no_unit_offset" not in faults) @ w_head


def layer_weights(params: Mapping[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i`` of the program's parameter tree under this file's names:
    the only place that knows the program's layout."""
    lay = params["layers"]
    take = partial(jax.tree.map, lambda a: a[i])
    attn, mlp = lay["attn"], lay["mlp"]
    return {"ln1": lay["ln1"]["scale"][i], "ln2": lay["ln2"]["scale"][i],
            "phi": attn["eva_phi"][i], "mu": attn["eva_mu"][i],
            **{n: take(attn[n]) for n in ("wq", "wk", "wv", "wo")},
            **{n: take(mlp[n]) for n in ("w_gate", "w_in", "w_out")}}


def hidden_states(params, model: Mapping[str, Any], tokens,
                  faults: Tuple[str, ...] = ()):
    """``tokens (S,)``, S whole windows → the last block's output."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    for i in range(model["num_hidden_layers"]):
        x = layer(x, layer_weights(params, i),
                  heads=model["num_attention_heads"],
                  theta=float(model["rope_theta"]),
                  eps=float(model["rms_norm_eps"]),
                  window=int(model["window_size"]),
                  chunk=int(model["chunk_size"]), faults=tuple(faults))
    return x


def closed_summaries(params, model: Mapping[str, Any], tokens, layers,
                     faults: Tuple[str, ...] = ()):
    """What a sequence's cache holds behind its current window: for each of
    ``layers`` (ascending), ``(k~, v~)`` of every chunk of the windows that
    ``tokens (S,)`` has closed, ``(S // W * W / C, heads, d)`` each."""
    window = int(model["window_size"])
    tokens = tokens[:tokens.shape[0] // window * window]
    sizes = dict(heads=model["num_attention_heads"],
                 theta=float(model["rope_theta"]),
                 eps=float(model["rms_norm_eps"]),
                 chunk=int(model["chunk_size"]), faults=tuple(faults))
    x = params["embed"]["tokens"][tokens].astype(F32)
    out = []
    for i in range(max(layers) + 1):
        w = layer_weights(params, i)
        if i in layers:
            out.append(layer_summaries(x, w, **sizes))
        if i < max(layers):
            x = layer(x, w, window=window, **sizes)
    return out


def logits(params, model: Mapping[str, Any], tokens,
           last: Optional[int] = None, faults: Tuple[str, ...] = ()):
    """``tokens (S,)`` → every head's logits ``(S, heads x vocab)`` in
    float32 (``last``: of the last that many positions only).  The sequence
    is padded to whole windows here: causal, the padding changes nothing."""
    n, window = tokens.shape[0], int(model["window_size"])
    padded = jnp.zeros(-(-n // window) * window, tokens.dtype).at[:n].set(
        tokens)
    x = hidden_states(params, model, padded, faults)[:n]
    if last is not None:
        x = x[n - last:]
    return head_logits(x, params["final_norm"]["scale"],
                       params["lm_head"]["w"],
                       eps=float(model["rms_norm_eps"]),
                       vocab=int(model["vocab_size"]), faults=tuple(faults))


def served_margins(params, model: Mapping[str, Any], sequence, n_prompt: int):
    """For one served sequence (prompt, then the bytes the server sent, which
    it draws from head 0): how far the reference's head-0 logit of each
    served byte lies under head 0's maximum, and its rank there."""
    lg = logits(params, model, sequence,
                last=sequence.shape[0] - n_prompt + 1)
    return _margins(lg[:-1, :int(model["vocab_size"])], sequence[n_prompt:])
