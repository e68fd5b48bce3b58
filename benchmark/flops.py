"""Operations a dense decoder *requires*, from its published sizes.

The yardstick's own arithmetic (the program's ``flops_per_token`` charges the
attention term at ``max_seq_len`` and leaves the output head out; see
PERF.md).  Everything is counted from the configuration file's published
keys, so a change to the program cannot move it.  Nothing recomputed is
counted: rematerialisation is the program's choice, not the model's need.
"""

from __future__ import annotations

from typing import Any, Mapping


def matmul_params(model: Mapping[str, Any], with_head: bool = True) -> int:
    """Parameters that sit in a matrix multiplication on every token: the
    q/k/v/o projections and the gated MLP of each layer, and the output
    head.  The embedding is a gather and the norms are elementwise."""
    h = model["hidden_size"]
    hd = model.get("head_dim") or h // model["num_attention_heads"]
    q = model["num_attention_heads"] * hd
    kv = model["num_key_value_heads"] * hd
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * model["intermediate_size"]
    total = model["num_hidden_layers"] * per_layer
    if with_head:
        total += h * model["vocab_size"]
    return total


def mean_attended_keys(seq_len: int, window: int = 0) -> float:
    """Mean number of keys a query attends to under a causal mask, position
    t seeing ``min(t + 1, window)`` keys."""
    if not window or window >= seq_len:
        return (seq_len + 1) / 2
    full = window * (window + 1) / 2 + (seq_len - window) * window
    return full / seq_len


def attention_flops_per_token(model: Mapping[str, Any], seq_len: int,
                              passes: int) -> float:
    """QK^T and PV: 2 multiply-adds a key a head-dimension a query head,
    2 FLOPs each, times ``passes`` (1 forward; 3 forward + backward)."""
    h = model["hidden_size"]
    hd = model.get("head_dim") or h // model["num_attention_heads"]
    keys = mean_attended_keys(seq_len, model.get("sliding_window") or 0)
    per_layer = 4 * model["num_attention_heads"] * hd * keys
    return passes * model["num_hidden_layers"] * per_layer


def train_flops_per_token(model: Mapping[str, Any], seq_len: int) -> float:
    """Forward and backward: 6 FLOPs a matmul parameter a token, and the
    causal attention at the trained length, three passes."""
    return 6 * matmul_params(model) + attention_flops_per_token(
        model, seq_len, passes=3)


def forward_flops_per_token(model: Mapping[str, Any], context: int) -> float:
    """One forward token at a given context (serving): 2 FLOPs a matmul
    parameter and attention over ``context`` keys under the window."""
    window = model.get("sliding_window") or 0
    keys = min(context, window) if window else context
    h = model["hidden_size"]
    hd = model.get("head_dim") or h // model["num_attention_heads"]
    attn = 4 * model["num_attention_heads"] * hd * keys
    return 2 * matmul_params(model) + model["num_hidden_layers"] * attn


def mfu(tokens_per_s: float, flops_per_token: float, chips: int,
        peak_flops_per_s: float) -> float:
    """Model FLOP/s utilisation as a fraction."""
    return tokens_per_s * flops_per_token / (chips * peak_flops_per_s)
