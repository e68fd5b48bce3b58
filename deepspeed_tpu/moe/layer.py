"""Mixture-of-Experts layer.

Capability analogue of the reference's ``deepspeed/moe`` (``MoE`` layer.py:17,
``TopKGate`` sharded_moe.py:452, ``MOELayer:536`` with ``_AllToAll`` dispatch).
TPU-first design:

* **gating** — top-k softmax routing with capacity-factor token dropping,
  load-balancing auxiliary loss (Switch/GShard style, matching the reference's
  top-1/2/k gates at ``sharded_moe.py:184,291,375``) and router z-loss;
* **dense dispatch path** (`dense_moe_block`) — capacity-bucketed einsum
  dispatch/combine: one-hot dispatch masks contracted on the MXU.  With the
  expert axis of the weights sharded over the ``ep`` mesh axis, XLA's SPMD
  partitioner lowers the dispatch einsum into exactly the all-to-all the
  reference hand-codes;
* **explicit all-to-all path** (`deepspeed_tpu/moe/sharded_moe.py`) — a
  shard_map implementation where the token shuffle is a visible
  ``lax.all_to_all`` over ``ep``, for when manual overlap is wanted.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GateOutput(NamedTuple):
    combine_weights: jax.Array  # (B, S, E, C) float
    dispatch_mask: jax.Array  # (B, S, E, C) bool
    aux_loss: jax.Array  # scalar
    z_loss: jax.Array  # scalar
    load: jax.Array  # (E,) fraction of tokens routed per expert


def top_k_gating(logits: jax.Array, num_experts: int, top_k: int,
                 capacity_factor: float, min_capacity: int = 4,
                 rng: Optional[jax.Array] = None,
                 noise_std: float = 0.0,
                 norm_topk: bool = True) -> GateOutput:
    """logits: (B, S, E). Returns capacity-bucketed dispatch/combine tensors.

    Reference: ``sharded_moe.py`` topkgating — same capacity math
    (capacity = S * k * cf / E, floored at min_capacity).
    """
    B, S, E = logits.shape
    capacity = max(int(S * top_k * capacity_factor / num_experts), min_capacity)

    if noise_std > 0.0 and rng is not None:
        logits = logits + jax.random.normal(rng, logits.shape) * noise_std

    raw_probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (B,S,E)
    # router z-loss (St-MoE): discourage huge logits
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    z_loss = jnp.mean(z ** 2)

    # top-k selection
    gate_vals, gate_idx = jax.lax.top_k(raw_probs, top_k)  # (B,S,k)
    if norm_topk:  # renormalize the selected gates (``cfg.moe_norm_topk``)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing aux loss (Switch eq.4): E * sum_e f_e * P_e
    me = jnp.mean(raw_probs, axis=(0, 1))  # (E,) mean router prob
    top1_mask = jax.nn.one_hot(gate_idx[..., 0], E)  # (B,S,E)
    ce = jnp.mean(top1_mask, axis=(0, 1))  # (E,) fraction of tokens
    aux_loss = num_experts * jnp.sum(me * ce)

    # Slot assignment (GShard-style): a token's position in its expert's
    # capacity bucket = tokens routed to that expert earlier in the sequence
    # this round + all slots consumed by earlier top-k rounds.
    combine = jnp.zeros((B, S, E, capacity), jnp.float32)
    dispatch = jnp.zeros((B, S, E, capacity), bool)
    for slot in range(top_k):
        idx = gate_idx[..., slot]  # (B,S)
        val = gate_vals[..., slot]  # (B,S)
        onehot = jax.nn.one_hot(idx, E)  # (B,S,E)
        before = jnp.cumsum(onehot, axis=1) - onehot  # same-round tokens ahead
        prev_used = dispatch.sum(axis=(1, 3)).astype(jnp.float32)[:, None, :]  # (B,1,E)
        pos = before + prev_used  # (B,S,E)
        keep = (pos < capacity) & (onehot > 0)
        pos_cl = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        sel = jax.nn.one_hot(pos_cl, capacity) * keep[..., None]  # (B,S,E,C)
        dispatch = dispatch | (sel > 0)
        combine = combine + sel * val[..., None, None]

    load = dispatch.any(-1).astype(jnp.float32).mean(axis=(0, 1))
    return GateOutput(combine, dispatch, aux_loss, z_loss, load)


def expert_choice_gating(logits: jax.Array, num_experts: int,
                         capacity_factor: float, min_capacity: int = 4
                         ) -> GateOutput:
    """Expert-choice routing (Zhou et al. 2022; ROADMAP item): EXPERTS pick
    their top-C tokens instead of tokens picking top-k experts.  Perfectly
    load-balanced by construction — every expert processes exactly C tokens
    — so no auxiliary loss is needed (aux_loss = 0); a token may be chosen
    by several experts or by none (dropped for that layer, residual carries
    it).  Reuses the (B, S, E, C) dispatch/combine layout so the einsum
    dispatch path and ep sharding apply unchanged.

    NON-CAUSAL by design (the paper's known caveat): an expert's top-C
    selection sees the whole sequence, so token t's routing depends on
    later tokens.  This is a TRAINING-TIME router (encoders, prefix-LM,
    distillation targets); autoregressive DECODE with it is incoherent —
    the inference engines refuse it (they serve every other MoE model
    through ``moe/dropless.routed_ffn``, whatever it was trained with)."""
    B, S, E = logits.shape
    capacity = max(int(S * capacity_factor / num_experts), min_capacity)
    capacity = min(capacity, S)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (B,S,E)
    z = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    z_loss = jnp.mean(z ** 2)
    # per (batch, expert): top-C tokens by that expert's column
    col = probs.transpose(0, 2, 1)                    # (B, E, S)
    vals, idx = jax.lax.top_k(col, capacity)          # (B, E, C)
    onehot = jax.nn.one_hot(idx, S)                   # (B, E, C, S)
    # (B, S, E, C): token s fills expert e's slot c iff idx[b,e,c] == s
    dispatch = onehot.transpose(0, 3, 1, 2) > 0
    combine = dispatch * vals[:, None, :, :]          # weight = router prob
    load = dispatch.any(-1).astype(jnp.float32).mean(axis=(0, 1))
    return GateOutput(combine.astype(jnp.float32), dispatch,
                      jnp.zeros((), jnp.float32), z_loss, load)


def dense_moe_block(x: jax.Array, p: Dict[str, Any], cfg) -> jax.Array:
    """Einsum-dispatch MoE FFN (router losses discarded — use
    ``moe_block_with_losses`` in training forwards that need them).

    The GSPMD path: the dispatch einsum creates (E, B, C, H) activations whose
    expert axis is sharded over mesh ``ep`` → XLA inserts the all-to-all the
    reference hand-codes; the expert FFN is a batched matmul on the MXU.
    """
    y, _, _ = moe_block_with_losses(x, p, cfg)
    return y


def moe_block_with_losses(x: jax.Array, p: Dict[str, Any], cfg
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Like dense_moe_block but returns (y, aux_loss, z_loss) explicitly —
    used by model forwards that accumulate the router losses."""
    if getattr(cfg, "moe_routing", "capacity") == "dropless":
        from .dropless import dropless_moe_block_with_losses

        # the shared expert and the PR-MoE mix are inside
        y, aux, z, _ = dropless_moe_block_with_losses(x, p, cfg)
        return y, aux, z
    dt = x.dtype
    E = cfg.num_experts
    logits = x.astype(jnp.float32) @ p["router"].astype(jnp.float32)
    if getattr(cfg, "moe_routing", "capacity") == "expert_choice":
        gate = expert_choice_gating(logits, E, cfg.moe_capacity_factor)
    else:
        gate = top_k_gating(logits, E, cfg.moe_top_k,
                            cfg.moe_capacity_factor,
                            norm_topk=getattr(cfg, "moe_norm_topk", True))
    disp = gate.dispatch_mask.astype(dt)
    comb = gate.combine_weights.astype(dt)
    xe = jnp.einsum("bsec,bsh->ebch", disp, x)
    w_in = p["w_in"].astype(dt)
    w_out = p["w_out"].astype(dt)
    if "w_gate" in p:
        hmid = jax.nn.silu(jnp.einsum("ebch,ehf->ebcf", xe, p["w_gate"].astype(dt))) * \
            jnp.einsum("ebch,ehf->ebcf", xe, w_in)
    else:
        hmid = jax.nn.gelu(jnp.einsum("ebch,ehf->ebcf", xe, w_in), approximate=True)
    ye = jnp.einsum("ebcf,efh->ebch", hmid, w_out)
    y = jnp.einsum("bsec,ebch->bsh", comb, ye)
    if getattr(cfg, "moe_use_residual", False):
        y = _prmoe_combine(x, y, p, cfg)
    return y, gate.aux_loss, gate.z_loss


def _prmoe_combine(x: jax.Array, moe_out: jax.Array, p: Dict[str, Any],
                   cfg) -> jax.Array:
    """PR-MoE / residual MoE (reference ``deepspeed/moe/layer.py:17``
    ``use_residual``): a dense "shared expert" MLP runs on every token and a
    learned per-token 2-way softmax coefficient mixes it with the sparse MoE
    output — ``out = mlp·c₀ + moe·c₁``.  Every token gets the shared
    expert's capacity even when the router drops it."""
    dt = x.dtype
    xin = x.astype(dt)
    if "res_w_gate" in p:
        hmid = jax.nn.silu(xin @ p["res_w_gate"].astype(dt)) * \
            (xin @ p["res_w_in"].astype(dt))
    else:
        hmid = jax.nn.gelu(xin @ p["res_w_in"].astype(dt), approximate=True)
    mlp_out = hmid @ p["res_w_out"].astype(dt)
    coef = jax.nn.softmax(
        x.astype(jnp.float32) @ p["coef"].astype(jnp.float32), axis=-1)
    return (mlp_out * coef[..., 0:1].astype(dt)
            + moe_out * coef[..., 1:2].astype(dt))
