"""Operations and bytes the paged attention kernels *require*, from the
published sizes: the yardstick of ``prefill_attn_roofline_pct``, kept with
the benchmark so that a change to the program cannot move it.

A layer's attention over one step reads each K/V block a row's queries can
see once (the engine's ``kv_blocks_read`` counts them: from the block of the
oldest key the row's oldest query sees to the block of its newest token,
summed over rows and layers), and multiplies every query with every key it
sees twice (scores, then values; ``kv_query_keys`` counts the pairs, summed
over rows and layers).  What a kernel reads again for each tile of queries,
and what it multiplies under a mask, is its own choice and counts as
neither.
"""

from __future__ import annotations

from typing import Any, Mapping


def head_dim(model: Mapping[str, Any]) -> int:
    return model.get("head_dim") or (model["hidden_size"]
                                     // model["num_attention_heads"])


def block_bytes(model: Mapping[str, Any], block_size: int,
                kv_bytes: int = 2) -> float:
    """One block of one layer, K and V."""
    return 2.0 * block_size * model["num_key_value_heads"] * head_dim(
        model) * kv_bytes


def attention_flops(model: Mapping[str, Any], query_keys: float) -> float:
    """``query_keys`` (query, key) pairs over all heads: q.k and p.v, a
    multiply and an add each per head dimension."""
    return 4.0 * query_keys * model["num_attention_heads"] * head_dim(model)
