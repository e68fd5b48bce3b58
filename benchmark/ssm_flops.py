"""Operations and bytes that the state-space layers and the routed experts of
a hybrid decoder (NVIDIA-Nemotron-3-Nano) *require*, from the published
sizes: the yardstick of ``ssm_decode_roofline_pct``,
``ssd_scan_roofline_pct`` and the grouped GEMM's shares at 128 experts, kept
with the benchmark so that a change to the program cannot move it.

A Mamba-2 layer keeps, a sequence, a float32 state of ``heads x head_dim x
state`` (64 x 64 x 128: 2,097,152 bytes).  A decode step reads and writes it
once a row; a chunk of prefill reads it once and writes it once a row
however many tokens the row has, and multiplies it twice a token.

The experts' inner width 1856 = 29 x 64 is STORED zero-padded to 1920 (a
multiple of the lane tile): the padded codes are fetched, so they count as
bytes; they are zeros, so they count as no operations.
"""

from __future__ import annotations

from typing import Any, Mapping

STATE_ITEMSIZE = 4  # float32


def state_bytes(model: Mapping[str, Any]) -> int:
    """One sequence's SSM state in one layer."""
    return (model["mamba_num_heads"] * model["mamba_head_dim"]
            * model["ssm_state_size"] * STATE_ITEMSIZE)


def mamba_layers(model: Mapping[str, Any]) -> int:
    return model["hybrid_override_pattern"].count("M")


def moe_layers(model: Mapping[str, Any]) -> int:
    return model["hybrid_override_pattern"].count("E")


def _quantized_bytes(k: int, n: int, bits: int, group: int) -> float:
    return k * n * bits / 8 + (k // group) * n * 4


def mamba_projection_bytes(model: Mapping[str, Any], bits: int, group: int
                           ) -> float:
    """One Mamba layer's quantized projections (z, xBC, out; the 64-wide dt
    projection is bf16), each read once a step."""
    h = model["hidden_size"]
    d_inner = model["mamba_num_heads"] * model["mamba_head_dim"]
    conv_dim = d_inner + 2 * model["n_groups"] * model["ssm_state_size"]
    return (_quantized_bytes(h, d_inner, bits, group)
            + _quantized_bytes(h, conv_dim, bits, group)
            + _quantized_bytes(d_inner, h, bits, group)
            + h * model["mamba_num_heads"] * 2)


def decode_update_bytes(model: Mapping[str, Any], rows: float) -> float:
    """One layer's decode update: every row's state read and written."""
    return 2.0 * rows * state_bytes(model)


def scan_flops(model: Mapping[str, Any], tokens: float, pieces: float
               ) -> float:
    """One layer's chunked scan over ``tokens`` tokens in ``pieces`` pieces:
    the state multiplied twice a token (read for ``y``, updated), and inside
    a piece of ``n`` tokens the ``n (n + 1) / 2`` causal pairs, each a
    ``C . B`` product a group and a weighted sum of ``x`` a head."""
    H, P, N = (model["mamba_num_heads"], model["mamba_head_dim"],
               model["ssm_state_size"])
    G = model["n_groups"]
    pairs = tokens * (tokens / max(pieces, 1.0) + 1.0) / 2.0
    return 4.0 * tokens * H * P * N + 2.0 * pairs * (G * N + H * P)


def scan_bytes(model: Mapping[str, Any], tokens: float, rows: float,
               act_bytes: int = 2) -> float:
    """One layer's chunked scan: each row's state in and out, each token's
    ``x``, ``B``, ``C`` (activation type) and ``dt`` in, its ``y`` (float32)
    out."""
    H, P, N = (model["mamba_num_heads"], model["mamba_head_dim"],
               model["ssm_state_size"])
    G = model["n_groups"]
    per_token = (H * P + 2 * G * N) * act_bytes + H * 4 + H * P * 4
    return 2.0 * rows * state_bytes(model) + tokens * per_token


def stored_expert_width(model: Mapping[str, Any]) -> int:
    """The experts' inner width as the quantizer stores it."""
    f = model["moe_intermediate_size"]
    return f if f <= 128 else -(-f // 128) * 128


def expert_matrices(model: Mapping[str, Any]):
    """``(K, N)`` of the two expert projections (up, down: ungated), at the
    published width."""
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    return ((h, f), (f, h))


def grouped_gemm_flops(model: Mapping[str, Any], assignments: int) -> float:
    """One layer's two grouped GEMMs: 2 FLOPs a weight an assignment, at the
    published width."""
    return sum(2.0 * assignments * k * n for k, n in expert_matrices(model))


def grouped_gemm_bytes(model: Mapping[str, Any], assignments: int,
                       experts_hit: float, weight_bits: int,
                       weight_group: int, act_bytes: int = 2) -> float:
    """One layer's two grouped GEMMs, bytes that cross HBM: the codes and
    float32 scales of the experts that got a row at the STORED width (each
    once), every assignment's activations in and out."""
    h, f = model["hidden_size"], stored_expert_width(model)
    total = 0.0
    for k, n in ((h, f), (f, h)):
        total += (experts_hit * _quantized_bytes(k, n, weight_bits,
                                                 weight_group)
                  + assignments * (k + n) * act_bytes)
    return total
