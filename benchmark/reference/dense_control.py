"""The control of driver ``serve``'s ``correct``: ``dense_decoder`` put in the
program's place one precision below the configuration's W8A16.

The int8 projections are rounded once more, to ``bits`` (4 on the chip),
symmetric, a scale per K-group and column as the configuration's own
quantizer keeps them, and handed to the unchanged reference as the int8 nodes
it reads: what a later PR would serve if it took the next precision down.
Layer by layer, because a second copy of every layer's codes does not fit
beside the first on the chip.  The control need not decode: at each served
position of the same prompt and tokens, the token that it puts first is read
under the reference proper, margin and rank as a served token's are.  Never
part of a run of a committed cell: tools and tests ask for it
(``check.control_bits``), and a run that has it comes out as not correct.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Mapping

import jax
import jax.numpy as jnp

from benchmark.reference import dense_decoder as reference

F32 = jnp.float32


@partial(jax.jit, static_argnames=("bits",))
def rounded(w: Any, bits: int) -> Any:
    """An int8 node ``(K, N)`` as the same kind of node at ``bits``: codes in
    ``[-2**(bits-1), 2**(bits-1) - 1]``, the scale the K-group's and
    column's largest dense value over the top code."""
    k, n = w.codes.shape
    groups = w.scales.shape[0]
    dense = (w.codes.astype(F32).reshape(groups, k // groups, n)
             * w.scales.astype(F32)[:, None, :])
    top = F32(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.abs(dense).max(1, keepdims=True), F32(1e-30)) / top
    codes = jnp.clip(jnp.round(dense / scale), -top - 1, top)
    return dataclasses.replace(w, codes=codes.astype(jnp.int8).reshape(k, n),
                               scales=scale[:, 0, :])


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array, bits: int) -> jax.Array:
    """``dense_decoder.logits`` over the projections at ``bits``."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    for i in range(model["num_hidden_layers"]):
        w = {name: rounded(v, bits) if hasattr(v, "codes") else v
             for name, v in reference.layer_weights(params, i).items()}
        x = reference.layer(x, w, heads=model["num_attention_heads"],
                            kv_heads=model["num_key_value_heads"],
                            theta=float(model["rope_theta"]),
                            eps=float(model["rms_norm_eps"]),
                            window=int(model.get("sliding_window") or 0))
    return reference.head_logits(x, params["final_norm"]["scale"],
                                 params["lm_head"]["w"],
                                 eps=float(model["rms_norm_eps"]))


def control_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                    sequence: jax.Array, n_prompt: int, bits: int):
    """``dense_decoder.served_margins`` for the tokens the control puts first
    at the served positions of ``sequence``, under the reference proper."""
    first = logits(params, model, sequence, bits).argmax(-1)
    lg = reference.logits(params, model, sequence)
    return reference._margins(lg[n_prompt - 1:-1], first[n_prompt - 1:-1])
