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

from typing import Any, Dict, Mapping, Optional, Sequence, Tuple

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
    """→ (seconds, calls) of the kernels ``names`` in a reduction by name,
    whatever program they ran in: the traced window's (``kernel_time.reduce``:
    a share of the window's busy time) or ONE whole execution of the step
    program (an entry of ``kernel_time.whole_steps``: what a roofline share
    counts)."""
    if not by_name:
        return 0.0, 0.0
    keys = [k for k in by_name["kernel_s"] if k.rsplit("/", 1)[-1] in names]
    return (sum(by_name["kernel_s"][k] for k in keys),
            sum(by_name["kernel_calls"][k] for k in keys))


def by_name(obs) -> Optional[Mapping[str, Any]]:
    return (obs.get("trace") or {}).get("by_name")


def whole_steps(obs) -> Sequence[Mapping[str, Any]]:
    """The step program's executions that lie wholly inside the traced
    window (``kernel_time.whole_steps``, under ``by_name["steps"]``).  A
    roofline share is taken over these alone, on both sides: a call that the
    window's edge cuts is neither counted nor timed."""
    return (by_name(obs) or {}).get("steps") or ()


def counters_of(obs, step: Mapping[str, Any]) -> Optional[Mapping[str, float]]:
    """The counters of the very step whose execution ``step`` is: the driver
    hands every step's (``train["step_counters"]``, in the order fetched) and
    how many it had fetched when the profiler started
    (``train["traced_from"]``); the trace says which of its fetches brought
    this execution's metrics (``step["fetch"]``).  None where any of the
    three is missing."""
    train = obs.get("train") or {}
    counters, first = train.get("step_counters"), train.get("traced_from")
    if not counters or first is None or step.get("fetch") is None:
        return None
    i = first + step["fetch"]
    return counters[i] if i < len(counters) else None


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
    in the traced window's whole steps (bf16 peak of ``peaks.json``) over the
    time they took."""
    train = obs.get("train") or {}
    if "model" not in obs or "rows" not in train:
        return None
    fwd, bwd = flash_call_flops(obs["model"], train["rows"],
                                train["seq_len"])
    # the dK/dV and the dQ kernel together are ONE backward
    timed = FLASH_BWD if backward else (FLASH_FWD,)
    steps = whole_steps(obs)
    seconds = sum(kernel_seconds(step, timed)[0] for step in steps)
    calls = sum(kernel_seconds(step, timed[-1:])[1] for step in steps)
    if not seconds or not calls:
        return None
    peak = obs["device"]["peaks"]["bf16_flops_per_s"]
    return 100.0 * calls * (bwd if backward else fwd) / peak / seconds


def grouped_passes(step: Mapping[str, Any]) -> float:
    """The grouped GEMM's calls over ONE routed layer's rows in a step, from
    the step's own calls by name: every round of every layer makes one
    ``grouped_matmul_dlhs`` call for each of an expert's three matrices
    (gate, up, down: ``expert_params``), so a third of those calls is the
    ROUNDS that ran, and all the calls (forward, each rematerialised forward,
    dlhs, drhs) over the rounds are the calls a layer's rows pass through
    when one round holds them: 12 with the forward rematerialised once.  A
    second round (``moe/dropless._share_in_rounds``: a layer whose local rows
    pass 22,528 with their padding) adds calls and no rows, and leaves this
    number where it was."""
    rounds = kernel_seconds(step, GROUPED[1:2])[1] / 3.0
    return kernel_seconds(step, GROUPED)[1] / rounds if rounds else 0.0


def grouped_roofline(obs) -> Optional[float]:
    """100 x the least time the chip could take for the grouped GEMM's work
    (forward, dlhs, drhs) in the traced window's whole steps over the time
    its calls took there.  The work of a step: its routed layers x
    :func:`grouped_passes` x one call over the rows THAT STEP's router made
    local (the step's own ``moe_local_rows`` and ``moe_experts_hit``, means
    over its routed layers; ``grouped_call``: the larger of FLOPs over the
    bf16 peak and bytes over the HBM rate).  A layer's rows are credited once
    a pass however many rounds the program walked them in; that a second
    round fetches the experts' matrices again is the program's choice and is
    not counted.  A step whose counters the trace cannot place counts on
    neither side."""
    if "model" not in obs:
        return None
    model, peaks = obs["model"], obs["device"]["peaks"]
    layers = model["num_hidden_layers"] - model["first_k_dense_replace"]
    least = seconds = 0.0
    for step in whole_steps(obs):
        c, passes = counters_of(obs, step), grouped_passes(step)
        if not c or not passes:
            continue
        flops, nbytes = grouped_call(model, c["moe_local_rows"],
                                     c["moe_experts_hit"])
        least += layers * passes * max(flops / peaks["bf16_flops_per_s"],
                                       nbytes / peaks["hbm_bytes_per_s"])
        seconds += kernel_seconds(step, GROUPED)[0]
    return 100.0 * least / seconds if seconds else None
