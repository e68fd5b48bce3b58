"""Operations and bytes the routed experts of a sparse-expert decoder
*require*, from the published sizes: the yardstick of the grouped W8A16
GEMM's roofline share (``moe_gemm_roofline_pct``), kept with the benchmark so
that a change to the program cannot move it.

A layer's routed FFN is three grouped GEMMs (gate and up ``hidden x expert
width``, down ``expert width x hidden``) over T = tokens x experts-per-token
assignments.  Padding rows of the program's layout are its own choice and
count as neither operations nor bytes.
"""

from __future__ import annotations

from typing import Any, Mapping


def expert_matrices(model: Mapping[str, Any]):
    """``(K, N)`` of the three expert projections (gate, up, down);
    ``intermediate_size`` is the width of one expert."""
    h, f = model["hidden_size"], model["intermediate_size"]
    return ((h, f), (h, f), (f, h))


def grouped_gemm_flops(model: Mapping[str, Any], assignments: int) -> float:
    """One layer's three grouped GEMMs: 2 FLOPs a weight an assignment."""
    return sum(2.0 * assignments * k * n for k, n in expert_matrices(model))


def grouped_gemm_bytes(model: Mapping[str, Any], assignments: int,
                       experts_hit: float, weight_bits: int,
                       weight_group: int, act_bytes: int = 2) -> float:
    """One layer's three grouped GEMMs, bytes that must cross HBM: the codes
    and float32 scales of the experts that got a row (each once), and every
    assignment's activations in and out."""
    total = 0.0
    for k, n in expert_matrices(model):
        weights = k * n * weight_bits / 8 + (k // weight_group) * n * 4
        total += experts_hit * weights + assignments * (k + n) * act_bytes
    return total
