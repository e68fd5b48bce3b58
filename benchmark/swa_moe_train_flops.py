"""Operations that TRAINING a decoder of window and full attention layers
mixed (grouped queries, a gate on the attention's output), leading dense
layers, a shared expert and a chip's share of sigmoid-routed experts
*requires*, from the configuration file's published keys and the step's own
counters: the yardstick of ``mfu_pct`` in the cell and of the flash kernels'
roofline shares by kind of layer, kept with the benchmark so that a change to
the program cannot move it.  Nothing recomputed is counted in a token's FLOPs;
a window layer's pairs are the BAND's, not the triangle's; a kernel's share
counts what each of its CALLS must compute.

``model``: the published keys as run (``drivers/train_swa_moe.model_of``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

from benchmark.latent_moe_flops import (busy_share,  # noqa: F401 (readers)
                                        kernel_seconds, whole_steps)

#: the flash kernels a layer of each kind runs through, as the program names
#: them: a call whose band cuts something carries ``_band``
FLASH = {
    "full": ("flash_attention_fwd",
             ("flash_attention_bwd_dkv", "flash_attention_bwd_dq")),
    "sliding": ("flash_attention_fwd_band",
                ("flash_attention_bwd_dkv_band",
                 "flash_attention_bwd_dq_band")),
}
ALL_FLASH = tuple(n for fwd, bwd in FLASH.values() for n in (fwd, *bwd))


def kinds_of(model: Mapping[str, Any]) -> Tuple[str, ...]:
    """"sliding" or "full", a layer as run."""
    return tuple(t.split("_")[0] for t in model["layer_types"])


def attention_params(model: Mapping[str, Any]) -> int:
    """A layer's attention matrices: Wq, Wk, Wv, the gate's Wg, Wo."""
    h, d = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    return h * q + 2 * h * kv + h * q + q * h


def expert_params(model: Mapping[str, Any]) -> int:
    """One routed expert (the shared expert is as wide): gate, up, down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def matmul_params_per_token(model: Mapping[str, Any],
                            local_per_token: float) -> Dict[str, float]:
    """Parameters that sit in a matrix multiplication a token passes, by
    part.  ``local_per_token``: assignments to a held expert a token a routed
    layer (``moe_local_rows`` over the step's tokens; 1 with 16 of 128 held,
    top 8 and a uniform router).  The embedding is a gather; norms, the
    gate's sigmoid and its product are elementwise."""
    h = model["hidden_size"]
    L, dense = model["num_hidden_layers"], model["num_dense_layers"]
    routed = L - dense
    return {
        "attention": L * attention_params(model),
        "dense_mlp": dense * 3 * h * model["intermediate_size"],
        "shared_experts": routed * model["num_shared_experts"]
        * expert_params(model),
        "router": routed * h * model["num_experts"],
        "routed_experts": routed * local_per_token * expert_params(model),
        "head": h * model["vocab_size"],
    }


def pairs(model: Mapping[str, Any], kind: str, seq_len: int) -> float:
    """(query, key) pairs a head of a layer of ``kind`` attends over in one
    sequence: the causal triangle, or under a window that cuts the band in
    it (a query sees ``window`` keys, itself among them)."""
    w = model["sliding_window"] if kind == "sliding" else 0
    if not 0 < w < seq_len:
        return seq_len * (seq_len + 1) / 2
    return w * (w + 1) / 2 + (seq_len - w) * w


def attention_flops(model: Mapping[str, Any], seq_len: int) -> Dict[str, float]:
    """QK^T and PV of one sequence, forward, summed over the layers of each
    kind: 2 FLOPs a multiply-add over a head's width, twice."""
    per_pair = 4.0 * model["head_dim"] * model["num_attention_heads"]
    out: Dict[str, float] = {}
    for kind in kinds_of(model):
        out[kind] = out.get(kind, 0.0) + pairs(model, kind, seq_len) * per_pair
    return out


def train_flops_per_token(model: Mapping[str, Any], seq_len: int,
                          local_per_token: float) -> float:
    """Forward and backward: 6 FLOPs a matmul parameter a token, and three
    passes of the attention's pairs at the trained length, the band counted
    as the band."""
    matmul = sum(matmul_params_per_token(model, local_per_token).values())
    attn = sum(attention_flops(model, seq_len).values()) / seq_len
    return 6.0 * matmul + 3.0 * attn


def forward_flops(model: Mapping[str, Any], seq_len: int,
                  local_per_token: float) -> Dict[str, float]:
    """One sequence's forward by part (the figures of PERF.md section 4)."""
    out = {k: 2.0 * seq_len * v for k, v in
           matmul_params_per_token(model, local_per_token).items()}
    out.update({f"scores_{k}": v
                for k, v in attention_flops(model, seq_len).items()})
    return out


def flash_call_flops(model: Mapping[str, Any], kind: str, rows: int,
                     seq_len: int) -> Tuple[float, float]:
    """→ (a forward call, a backward = one dK/dV call and one dQ call) of the
    flash kernel of a layer of ``kind`` over ``rows`` sequences: the pairs in
    the band times the mathematics' own products.  Backward: S = QK^T once
    more, dV = P^T dO, dP = dO V^T, dK = dS^T Q, dQ = dS K; that the two
    kernels each make S and dP is the program's choice and is not counted."""
    n = rows * model["num_attention_heads"] * pairs(model, kind, seq_len)
    d = model["head_dim"]
    return n * 2.0 * (2 * d), n * 2.0 * (5 * d)


def flash_roofline(obs, kind: str, backward: bool) -> Optional[float]:
    """100 x the least time the MXU could take for the calls of the flash
    kernel of layers of ``kind`` in the traced window's whole steps (bf16
    peak of ``peaks.json``) over the time they took; the rematerialised
    forward's calls are calls."""
    train = obs.get("train") or {}
    model = obs.get("model") or {}
    if "layer_types" not in model or "rows" not in train:
        return None
    fwd, bwd = flash_call_flops(model, kind, train["rows"], train["seq_len"])
    names = FLASH[kind]
    timed = names[1] if backward else (names[0],)
    steps = whole_steps(obs)
    seconds = sum(kernel_seconds(step, timed)[0] for step in steps)
    calls = sum(kernel_seconds(step, timed[-1:])[1] for step in steps)
    if not seconds or not calls:
        return None
    peak = obs["device"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * calls * (bwd if backward else fwd) / peak / seconds
