"""Sequence-tiled compute (ALST).

Capability analogue of the reference's Arctic Long Sequence Training pieces
(``runtime/sequence_parallel/ulysses_sp.py`` — ``SequenceTiledCompute:774``,
``TiledMLP:943``, ``TiledFusedLogitsLoss:1065``): cap activation memory by
computing position-wise blocks (MLP, logits+loss) one sequence tile at a
time.  TPU-native form: ``lax.scan`` over tiles with rematerialisation —
the scan body is recomputed in backward, so peak activation memory is
O(tile) instead of O(S).

The logits+loss tile is the big win: a (B, S, V) logits tensor for V=128k at
S=128k is terabytes; tiling folds the cross-entropy into each tile so full
logits never exist.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def tiled_map(fn: Callable[[jax.Array], jax.Array], x: jax.Array,
              tile_size: int, axis: int = 1) -> jax.Array:
    """Apply a position-wise ``fn`` over tiles of ``x`` along ``axis``.

    ``fn`` must be shape-preserving on the tiled axis. The scan body is
    checkpointed: backward recomputes each tile instead of saving all
    intermediates (reference TiledMLP's ``torch.utils.checkpoint`` role).
    """
    S = x.shape[axis]
    if tile_size >= S:
        return fn(x)
    if S % tile_size != 0:
        raise ValueError(
            f"tiled_map: sequence length {S} not divisible by tile_size "
            f"{tile_size}; pick a divisor (silent untiled fallback would "
            "defeat the memory cap)")
    n = S // tile_size
    xt = jnp.moveaxis(x, axis, 0).reshape((n, tile_size) + x.shape[:axis] +
                                          x.shape[axis + 1:])

    def body(_, tile):
        # tile: (tile_size, ...) with original axis order restored for fn
        t = jnp.moveaxis(tile, 0, axis)
        return None, jnp.moveaxis(fn(t), axis, 0)

    _, out = lax.scan(jax.checkpoint(body), None, xt)
    out = out.reshape((S,) + out.shape[2:])
    return jnp.moveaxis(out, 0, axis)


def tiled_mlp(x: jax.Array, p: Dict[str, Any], cfg, tile_size: int) -> jax.Array:
    """Tiled SwiGLU/GELU MLP. x: (B, S, H)."""
    from ..models.transformer import _mlp_block

    return tiled_map(lambda t: _mlp_block(t, p, cfg), x, tile_size, axis=1)


@jax.named_scope("tiled_loss")  # the trace's op_name says whose these are
def tiled_logits_loss(x: jax.Array, embed_or_head: jax.Array,
                      labels: jax.Array, tile_size: int,
                      mask: Optional[jax.Array] = None,
                      transpose_head: bool = False,
                      head_bias: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Fused tiled cross-entropy. x: (B, S, H) final hidden states;
    ``embed_or_head``: (V, H) embedding (tied, ``transpose_head=True``) or
    (H, V) head.  Returns (sum_nll, sum_correct) without materializing
    (B, S, V) logits. Reference: ``TiledFusedLogitsLoss``.
    """
    B, S, H = x.shape
    if tile_size > S:
        tile_size = S
    elif S % tile_size != 0:
        raise ValueError(
            f"tiled_logits_loss: sequence length {S} not divisible by "
            f"tile_size {tile_size}; pick a divisor (an untiled fallback "
            "would materialize the full (B,S,V) logits)")
    n = S // tile_size

    xt = x.reshape(B, n, tile_size, H).swapaxes(0, 1)  # (n, B, t, H)
    lt = labels.reshape(B, n, tile_size).swapaxes(0, 1)
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)
    mt = mask.astype(jnp.float32).reshape(B, n, tile_size).swapaxes(0, 1)

    w = embed_or_head

    def body(carry, inp):
        nll_sum, correct_sum = carry
        xi, li, mi = inp
        logits = xi @ w.T if transpose_head else xi @ w
        if head_bias is not None:  # gpt-j untied head carries a bias
            logits = logits + head_bias.astype(logits.dtype)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, li[..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + (nll * mi).sum()
        correct = (logits.argmax(-1) == li).astype(jnp.float32)
        correct_sum = correct_sum + (correct * mi).sum()
        return (nll_sum, correct_sum), None

    (nll_sum, correct_sum), _ = lax.scan(
        jax.checkpoint(body),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xt, lt, mt))
    return nll_sum, correct_sum


def tiled_loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array], cfg,
                  tile_size: int = 2048, attn_fn=None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Drop-in replacement for ``models.transformer.loss_fn`` with the final
    logits+CE computed tile-by-tile (128K-ctx memory recipe)."""
    from ..models import transformer as tfm

    tokens = batch["input_ids"]
    labels, mask = tfm.shift_labels(batch)
    if mask is None:
        mask = jnp.ones_like(labels, jnp.float32)

    # forward up to final norm, but not the lm head
    dt = jnp.dtype(cfg.dtype)
    extras: Dict[str, jax.Array] = {}
    if cfg.kv_lora_rank and not cfg.index_topk:
        # a trained latent model: the routed layers' balance loss and their
        # counters come out beside the hidden states
        from ..models.latent_sparse import forward_train

        x, extras = forward_train(params, tokens, cfg, attn_fn=attn_fn)
    elif cfg.mlp_layer_types and not cfg.kv_lora_rank:
        # dense layers ahead of routed ones: the routed layers' counters and
        # every expert's assignments come out beside the hidden states
        from ..models.mixed_ffn import forward_train as mixed_train

        x, extras = mixed_train(params, tokens, cfg, attn_fn=attn_fn)
    else:
        x = tfm.forward_hidden(params, tokens, cfg, attn_fn=attn_fn)
    if cfg.tie_embeddings:
        w, transpose, hb = params["embed"]["tokens"].astype(dt), True, None
    else:
        w, transpose = params["lm_head"]["w"].astype(dt), False
        hb = params["lm_head"].get("b")
    nll_sum, correct_sum = tiled_logits_loss(x, w, labels, tile_size,
                                             mask=mask, transpose_head=transpose,
                                             head_bias=hb)
    denom = jnp.maximum(mask.astype(jnp.float32).sum(), 1.0)
    loss = nll_sum / denom
    metrics = {"accuracy": correct_sum / denom, "tokens": denom}
    if "moe_aux_loss" in extras:
        # the loss that is differentiated is the cross-entropy plus the
        # balance loss times its coefficient; the metrics carry the parts
        # (``ce_loss``, ``moe_aux_loss``) and the layers' counters
        metrics.update(extras, ce_loss=loss)
        with jax.named_scope("moe_aux"):
            loss = loss + cfg.moe_aux_loss_coef * extras["moe_aux_loss"]
    else:  # counters alone: no balance loss is trained
        metrics.update(extras)
    metrics["loss"] = loss
    return loss, metrics
