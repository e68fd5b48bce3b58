"""Operations and bytes that the Mamba-1 (selective scan) layers of a hybrid
decoder (AI21-Jamba2) *require*, from the published sizes: the yardstick of
``sel_scan_roofline_pct`` and ``sel_decode_roofline_pct``, kept with the
benchmark so that a change to the program cannot move it.

A Mamba-1 layer keeps, a sequence, a float32 state of ``d_inner x d_state``
(5120 x 16: 327,680 bytes), one decay a (channel, state) pair.  A scanned
token has to read its ``x`` and its gate ``z``, the rank-``dt_rank`` source
of ``delta``, ``B`` and ``C``, and to write its ``y``, once each; a row's
state is read once and written once a step however many tokens the row has;
a token costs about nine operations a (channel, state) pair: ``delta A``,
the exponential, the decay's product, ``delta x B`` (two), the sum, ``h C``
and its reduction (two).  A decode update reads and writes every live row's
state.  What the kernel moves beside that (``delta`` spread to ``d_inner``
in float32, ``B`` and ``C`` along the sublanes) is the kernel's own cost and
counts as nothing here, so a share reads low where the kernel moves more than
it must and never above 100.
"""

from __future__ import annotations

from typing import Any, Mapping

STATE_ITEMSIZE = 4  # float32
OPS_PER_PAIR = 9.0


def d_inner(model: Mapping[str, Any]) -> int:
    return model["mamba_expand"] * model["hidden_size"]


def state_bytes(model: Mapping[str, Any]) -> int:
    """One sequence's state in one Mamba layer."""
    return d_inner(model) * model["mamba_d_state"] * STATE_ITEMSIZE


def mamba_layers(model: Mapping[str, Any]) -> int:
    """Mamba layers of the model: every layer but the attention layers, one
    a period from the offset on."""
    L = model["num_hidden_layers"]
    period, offset = model["attn_layer_period"], model["attn_layer_offset"]
    return L - sum(1 for i in range(L) if i % period == offset)


def token_bytes(model: Mapping[str, Any], act_bytes: int = 2) -> float:
    """What one token through the recurrence has to move: ``x`` and ``z``
    in, ``y`` out (activation type), the rank-``dt_rank`` source of
    ``delta``, ``B`` and ``C`` in (float32, after their norms)."""
    return (3 * d_inner(model) * act_bytes
            + (model["mamba_dt_rank"] + 2 * model["mamba_d_state"]) * 4)


def scan_flops(model: Mapping[str, Any], tokens: float) -> float:
    """One layer's scan over ``tokens`` tokens."""
    return OPS_PER_PAIR * tokens * d_inner(model) * model["mamba_d_state"]


def scan_bytes(model: Mapping[str, Any], tokens: float, rows: float,
               act_bytes: int = 2) -> float:
    """One layer's scan: each row's state in and out, each token's inputs in
    and its ``y`` out."""
    return (2.0 * rows * state_bytes(model)
            + tokens * token_bytes(model, act_bytes))


def decode_update_bytes(model: Mapping[str, Any], rows: float,
                        act_bytes: int = 2) -> float:
    """One layer's decode update: every live row's state read and written,
    and its one token's inputs and output."""
    return rows * (2.0 * state_bytes(model) + token_bytes(model, act_bytes))


def least_s(flops: float, nbytes: float, peaks: Mapping[str, float]) -> float:
    """The repo's rule: the larger of the bytes at the HBM rate and the
    operations at the bfloat16 peak.  ``peaks.json`` gives no vector-unit
    peak, and these operations run on the vector unit, so the shares read
    against HBM in effect."""
    return max(nbytes / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops_per_s"])
