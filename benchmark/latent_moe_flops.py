"""Operations and bytes that TRAINING a decoder with latent attention (MLA, no
query compression), a leading dense layer, shared experts and a chip's share of
the routed experts *requires*, from the configuration file's published keys
and the step's own counters: the yardstick of ``mfu_pct`` in the cell and of
the new kernels' roofline shares, kept with the benchmark so that a change to
the program cannot move it.  Nothing recomputed is counted in a token's
FLOPs; a kernel's share counts what each of its CALLS must compute.

``model``: the published keys as run (``drivers/train_latent_moe.model_of``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

FLASH_FWD = "flash_attention_fwd"
FLASH_BWD = ("flash_attention_bwd_dkv", "flash_attention_bwd_dq")
GROUPED = ("grouped_matmul", "grouped_matmul_dlhs", "grouped_matmul_drhs")


def attention_params(model: Mapping[str, Any]) -> int:
    """A layer's attention matrices: W_q, [W_kva | W_kr], W_kvb, W_o."""
    h, H = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    rkv = model["kv_lora_rank"]
    return h * H * (dn + dr) + h * (rkv + dr) + rkv * H * (dn + dv) \
        + H * dv * h


def expert_params(model: Mapping[str, Any]) -> int:
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def matmul_params_per_token(model: Mapping[str, Any],
                            local_per_token: float) -> Dict[str, float]:
    """Parameters that sit in a matrix multiplication a token passes, by
    part.  ``local_per_token``: assignments to a held expert a token a routed
    layer (the counters' ``moe_local_rows`` over the step's tokens; 0.75 with
    8 of 64 experts held, top 6 and a uniform router).  The embedding is a
    gather, the norms are elementwise."""
    h = model["hidden_size"]
    L, dense = model["num_hidden_layers"], model["first_k_dense_replace"]
    routed = L - dense
    return {
        "attention": L * attention_params(model),
        "dense_mlp": dense * 3 * h * model["intermediate_size"],
        "shared_experts": routed * model["n_shared_experts"]
        * expert_params(model),
        "router": routed * h * model["n_routed_experts"],
        "routed_experts": routed * local_per_token * expert_params(model),
        "head": h * model["vocab_size"],
    }


def attention_pair_flops(model: Mapping[str, Any]) -> float:
    """QK^T and PV of one (query, key) pair of one head, forward: 2 FLOPs a
    multiply-add over the query-key width (nope + rope) and the value
    width."""
    return 2.0 * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                  + model["v_head_dim"])


def train_flops_per_token(model: Mapping[str, Any], seq_len: int,
                          local_per_token: float) -> float:
    """Forward and backward: 6 FLOPs a matmul parameter a token, and causal
    attention at the trained length (a query sees (S + 1) / 2 keys on
    average), three passes."""
    matmul = sum(matmul_params_per_token(model, local_per_token).values())
    attn = (model["num_hidden_layers"] * model["num_attention_heads"]
            * (seq_len + 1) / 2 * attention_pair_flops(model))
    return 6.0 * matmul + 3.0 * attn


def flash_call_flops(model: Mapping[str, Any], rows: int, seq_len: int
                     ) -> Tuple[float, float]:
    """→ (a forward call, a backward = one dK/dV call and one dQ call) of the
    flash kernel over ``rows`` sequences of ``seq_len``: the causal pairs
    times the mathematics' own products.  Backward: S = QK^T once more (the
    algorithm keeps no scores), dV = P^T dO, dP = dO V^T, dK = dS^T Q, dQ =
    dS K; that the two kernels each make S and dP is the program's choice
    and is not counted."""
    pairs = rows * model["num_attention_heads"] * seq_len * (seq_len + 1) / 2
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    v = model["v_head_dim"]
    return pairs * 2.0 * (qk + v), pairs * 2.0 * (3 * qk + 2 * v)


def grouped_call(model: Mapping[str, Any], local_rows: float,
                 experts_hit: float) -> Tuple[float, float]:
    """→ (FLOPs, bytes) one grouped GEMM call must do over a routed layer's
    ``local_rows`` assignments (forward, dlhs and drhs alike: rows x hidden x
    expert width, 2 FLOPs each; bf16 rows in and out and the hit experts'
    matrix once).  Padding rows of the layout count as nothing."""
    h, f = model["hidden_size"], model["moe_intermediate_size"]
    return (2.0 * local_rows * h * f,
            2.0 * (local_rows * (h + f) + experts_hit * h * f))


def kernel_seconds(by_name: Optional[Mapping[str, Any]], names) -> Tuple[
        float, float]:
    """→ (seconds, calls) of the kernels ``names`` in a traced window's
    reduction by name (``kernel_time.reduce``), whatever program they ran
    in."""
    if not by_name:
        return 0.0, 0.0
    keys = [k for k in by_name["kernel_s"] if k.rsplit("/", 1)[-1] in names]
    return (sum(by_name["kernel_s"][k] for k in keys),
            sum(by_name["kernel_calls"][k] for k in keys))


def by_name(obs) -> Optional[Mapping[str, Any]]:
    return (obs.get("trace") or {}).get("by_name")


def busy_share(obs, names=(), scopes=()) -> Optional[float]:
    """100 x the window's busy time inside the kernels ``names`` or under the
    scopes ``scopes``; None where the trace holds none of them."""
    t = by_name(obs)
    if not t or not t["busy_s"]:
        return None
    inside = kernel_seconds(t, names)[0] + sum(
        s for k, s in t["scope_s"].items() if k.rsplit("/", 1)[-1] in scopes)
    return 100.0 * inside / t["busy_s"] if inside else None


def flash_roofline(obs, backward: bool) -> Optional[float]:
    """100 x the least time the MXU could take for the flash kernel's calls
    in the window (bf16 peak of ``peaks.json``) over the time they took."""
    t, train = by_name(obs), obs.get("train") or {}
    if not t or "model" not in obs or "rows" not in train:
        return None
    fwd, bwd = flash_call_flops(obs["model"], train["rows"],
                                train["seq_len"])
    if backward:
        seconds = kernel_seconds(t, FLASH_BWD)[0]
        calls = kernel_seconds(t, FLASH_BWD[1:])[1]
    else:
        seconds, calls = kernel_seconds(t, (FLASH_FWD,))
    if not seconds or not calls:
        return None
    peak = obs["device"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * calls * (bwd if backward else fwd) / peak / seconds


def grouped_roofline(obs) -> Optional[float]:
    """100 x the least time the chip could take for the grouped GEMM's calls
    (forward, dlhs, drhs) in the window, the larger of FLOPs over the bf16
    peak and bytes over the HBM rate a call, over the time they took."""
    t, train = by_name(obs), obs.get("train") or {}
    c = train.get("counters")
    if not t or not c or "model" not in obs:
        return None
    seconds, calls = kernel_seconds(t, GROUPED)
    if not seconds or not calls:
        return None
    flops, nbytes = grouped_call(obs["model"], c["moe_local_rows"],
                                 c["moe_experts_hit"])
    peaks = obs["device"]["peaks"]
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
