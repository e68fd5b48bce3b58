"""Operations and bytes that Kimi Delta Attention (a delta-rule matrix state a
head under a gate a channel) and latent attention over a row's WHOLE context
*require*, from the published sizes and the host's counters alone, whatever
implements them: the yardstick of ``kda_decode_roofline_pct``,
``kda_chunk_roofline_pct`` and ``latent_full_decode_roofline_pct``, kept with
the benchmark so that a change to the program cannot move it.

A KDA layer keeps, a sequence, a float32 state of ``heads x d_k x d_v`` (32 x
128 x 128: 2,097,152 bytes).  A decode step reads and writes it once a row
(``kda_state_bytes`` on the program's ``engine/step`` spans: all KDA layers, the
rows in the step); a chunk of prefill reads it once and writes it once a row
however many tokens the row has.  The recurrence multiplies the state three
times a token (``S~^T k`` for the correction, the rank-one update, ``S^T q``
for the output: 2 ``d_k d_v`` operations each); the chunked form does the
same three products a token against the piece's first state and, inside a
piece of ``n`` tokens, ``n (n + 1) / 2`` causal pairs, each a ``k . k`` and a
``q . k`` under the pair's decay (``d_k`` wide) and a weighted sum of pseudo-
values (``d_v`` wide), twice (the triangular system and the outputs).  What
an implementation multiplies beyond that (a float32 product in six bfloat16
passes, the pairwise decays' exponentials) is its own choice and counts as
nothing.

Latent attention without an indexer: a row's whole context is read once a
layer a step (``kv_lora_rank + qk_rope_head_dim`` values a key:
``latent_keys_single`` for the rows of one token), and every (query, key)
pair costs the mathematics' own ``q . k`` (``qk_nope_head_dim +
qk_rope_head_dim``) and ``p . v`` (``v_head_dim``) a head; a row of one
token has as many pairs as keys.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

from benchmark.dsa_flops import GEMM, scope_seconds  # noqa: F401 (readers')

STATE_ITEMSIZE = 4  # float32


def heads_dk_dv(model: Mapping[str, Any]):
    lin = model["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["head_dim"]


def state_bytes(model: Mapping[str, Any]) -> int:
    """One sequence's KDA state in one layer."""
    H, dk, dv = heads_dk_dv(model)
    return H * dk * dv * STATE_ITEMSIZE


def kda_layers(model: Mapping[str, Any]) -> int:
    return len(model["linear_attn_config"]["kda_layers"])


def latent_layers(model: Mapping[str, Any]) -> int:
    return len(model["linear_attn_config"]["full_attn_layers"])


def routed_layers(model: Mapping[str, Any]) -> int:
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


def decode_update_bytes(model: Mapping[str, Any], rows: float) -> float:
    """One layer's decode update: every row's state read and written."""
    return 2.0 * rows * state_bytes(model)


def decode_update_flops(model: Mapping[str, Any], rows: float) -> float:
    """One layer's decode update: the state multiplied three times a row."""
    H, dk, dv = heads_dk_dv(model)
    return 6.0 * rows * H * dk * dv


def scan_flops(model: Mapping[str, Any], tokens: float, pieces: float
               ) -> float:
    """One layer's chunked form over ``tokens`` tokens in ``pieces`` pieces
    (see the module text)."""
    H, dk, dv = heads_dk_dv(model)
    pairs = tokens * (tokens / max(pieces, 1.0) + 1.0) / 2.0
    return 6.0 * tokens * H * dk * dv + pairs * H * (4.0 * dk + 4.0 * dv)


def scan_bytes(model: Mapping[str, Any], tokens: float, rows: float,
               act_bytes: int = 2) -> float:
    """One layer's chunked form: each row's state in and out; each token's
    q, k and v (activation type), its gate (float32 a channel) and ``b`` in,
    its output (float32) out."""
    H, dk, dv = heads_dk_dv(model)
    per_token = (2 * H * dk + H * dv) * act_bytes + H * dk * 4 + H * 4 \
        + H * dv * 4
    return 2.0 * rows * state_bytes(model) + tokens * per_token


def entry_values(model: Mapping[str, Any]) -> int:
    """Values of one token's entry of the latent cache."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def attention_flops(model: Mapping[str, Any], pairs: float) -> float:
    """``pairs`` (query, key) pairs over all heads: ``q . k`` and ``p . v``."""
    width = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
             + model["v_head_dim"])
    return 2.0 * pairs * model["num_attention_heads"] * width


def attention_bytes(model: Mapping[str, Any], keys: float, kv_bytes: int = 2
                    ) -> float:
    return float(keys) * entry_values(model) * kv_bytes


def steps_traced(t: Mapping[str, Any], model: Mapping[str, Any],
                 program: str) -> float:
    """Steps of ``program`` inside the traced window: its grouped-GEMM calls
    over three a routed layer."""
    return t["kernel_calls"].get(f"{program}/{GEMM}", 0) / (
        3.0 * max(routed_layers(model), 1))


def kda_steps(obs, kind: Optional[str] = None) -> list:
    """The attributes of the ``engine/step`` spans (of one kind of step) that
    carry the KDA counters: none on a program without them.  Where the
    driver says when the traced interval began and ended
    (``obs["traced_interval"]``, on the spans' clock), the steps that ended
    INSIDE IT: the counters are then of the steps whose device time the
    trace holds (a row's context, and with it ``latent_keys_*``, climbs
    while the row lives, so the window's mean is not the interval's); the
    whole window's where it does not say, or where no such step ended
    there."""
    from benchmark import stats

    if "spans" not in obs:
        return []
    spans = [s for s in stats.spans_named(
        obs, "engine/step", **({"kind": kind} if kind else {}))
        if "kda_state_bytes" in s["attrs"]]
    t0, t1 = obs.get("traced_interval") or (0.0, 0.0)
    inside = [s for s in spans if t0 <= s["t_end"] <= t1]
    return [s["attrs"] for s in inside or spans]


def traced(obs):
    """The traced run's reduction by kernel and scope name and the model's
    published sizes, or None where either lacks what these readers take (a
    program, driver or configuration from before them)."""
    t = (obs.get("trace") or {}).get("by_name")
    model = obs.get("model") or {}
    if not t or not t.get("busy_s") or "linear_attn_config" not in model:
        return None
    return t, model, obs["device"]["peaks"]
