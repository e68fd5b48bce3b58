"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542) in the causal, deterministic form EvaByte-6.5B is released
in: what a layer of such a model adds to ``models/transformer.py``.

Positions are cut into TUMBLING windows of ``W = eva_window`` and chunks of
``C = eva_chunk``.  A query at ``t`` reads, under one softmax,

* the keys of its own window, ``{m : m // W == t // W, m <= t}``, exactly;
* one SUMMARY ``(k~_j, v~_j)`` for every chunk ``j`` of every closed window
  (``j < (t // W) * (W // C)``), made once, when the window closes, from the
  layer's two learned vectors a head: ``a_m = softmax_{m in chunk j}(s *
  phi_h . k_m)``, ``v~_j = sum_m a_m v_m``, ``k~_j = mean_m k_m + mu_h``
  (``s = 1 / sqrt(head_dim)``).

So a sequence keeps, a layer, the K and V of at most ``W`` tokens and ``1 /
C`` of a key and a value a token behind them, which is all the model is for.
This file holds the arithmetic that is no kernel's own (the checks, the
seeded vectors, a window's summaries in plain ``jnp``); the served paths are
``ops/pallas/eva_attention.py`` under ``inference/v2/programs.eva_layers``.
The model is served, not trained: nothing differentiates through the
summariser or the two-source softmax (ROADMAP R4).
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

#: what the trainer and every forward that is no served step program say
NOT_TRAINED = (
    "a model with EVA attention (eva_window > 0: a tumbling window of exact "
    "keys and a learned summary a chunk behind it) is served by the v2 "
    "engine (inference/v2) only: the training forward has no summariser "
    "and nothing differentiates through one (ROADMAP R4)")


def check_config(cfg) -> None:
    """What the step programs compute, or a refusal by name."""
    w, c = cfg.eva_window, cfg.eva_chunk
    if c <= 0 or w % c:
        raise ValueError(f"eva_window {w} is not whole chunks of eva_chunk "
                         f"{c}")
    if cfg.kv_heads != cfg.num_heads:
        raise ValueError("EVA attention is written for one K/V head a query "
                         "head (phi and mu are a query head's)")
    for name in ("sliding_window", "layer_types", "mixer_pattern",
                 "kv_lora_rank", "num_experts", "qk_norm",
                 "parallel_residual"):
        if getattr(cfg, name):
            raise ValueError(f"EVA attention with {name} is not something "
                             f"the program computes")
    if cfg.position != "rope" or cfg.rot_dim != cfg.head_dim:
        raise ValueError("EVA attention rotates the whole head (RoPE)")
    if cfg.tie_embeddings and cfg.num_pred_heads > 1:
        raise ValueError("several output heads cannot be tied to the "
                         "embedding")


def init_vectors(key, cfg, dtype) -> Dict[str, jax.Array]:
    """A layer stack's ``eva_phi`` and ``eva_mu``, ``(L, heads, head_dim)``:
    normal, clipped to [-1, 1], times ``1 / sqrt(head_dim)`` (what a trained
    model holds is not published with the config; small, so that a summary
    key stays near its chunk's mean key, and not zero, so that a program
    that dropped either reads wrong)."""
    shape = (cfg.num_layers, cfg.num_heads, cfg.head_dim)
    s = 1.0 / math.sqrt(cfg.head_dim)
    k_phi, k_mu = jax.random.split(key)
    return {name: (jnp.clip(jax.random.normal(k, shape), -1.0, 1.0) * s
                   ).astype(dtype)
            for name, k in (("eva_phi", k_phi), ("eva_mu", k_mu))}


def summarize(k, v, phi, mu, chunk: int):
    """The summaries of whole chunks: ``k, v (..., n * chunk, H, D)`` →
    ``k~, v~ (..., n, H, D)`` in float32, by the module text's rule."""
    *lead, n, H, D = k.shape
    kc = k.astype(jnp.float32).reshape(*lead, n // chunk, chunk, H, D)
    vc = v.astype(jnp.float32).reshape(kc.shape)
    phi, mu = phi.astype(jnp.float32), mu.astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.einsum("...chd,hd->...ch", kc, phi) / math.sqrt(D), axis=-2)
    return kc.mean(-3) + mu, jnp.einsum("...ch,...chd->...hd", a, vc)
