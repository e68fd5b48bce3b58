"""Operations and bytes that a learned selection of keys (the indexer) and
latent attention over the selected keys *require*, from the published sizes
and the host's counters alone, whatever implements them: the yardstick of
``dsa_index_roofline_pct``, ``latent_prefill_roofline_pct`` and
``latent_decode_roofline_pct``, kept with the benchmark so that a change to
the program cannot move it.

The indexer of a layer that picks: every key a row's queries can see is read
once a step (``index_head_dim`` values), and every (query, visible key) pair
costs ``index_n_heads`` dot products of ``index_head_dim`` (``dsa_index_pairs``
and ``dsa_index_keys`` on the program's ``engine/step`` spans, summed over
rows and picking layers).

Attention of EVERY layer over the selection: a picked key's cache entry is
read once a row a step (``kv_lora_rank + qk_rope_head_dim`` values:
``latent_keys_single`` / ``latent_keys_prefill``, the smaller of a row's
picks and its context, summed over rows and layers), and every (query,
picked key) pair costs the mathematics' own ``q . k`` (``qk_nope_head_dim +
qk_rope_head_dim``) and ``p . v`` (``v_head_dim``) a head
(``dsa_selected_single`` / ``dsa_selected_prefill``).  What an absorbed form
multiplies beyond that (576 + 512 a pair a head), what a masked pass
multiplies to throw away and what a tile reads again are the program's own
choice and count as neither.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

GEMM = "grouped_mixed_gemm"


def picking_layers(model: Mapping[str, Any]) -> int:
    return sum(t == "full" for t in model["indexer_types"])


def routed_layers(model: Mapping[str, Any]) -> int:
    return sum(t == "sparse" for t in model["mlp_layer_types"])


def index_flops(model: Mapping[str, Any], pairs: float) -> float:
    """``pairs`` scored (query, key) pairs: a multiply and an add a head
    dimension a head."""
    return 2.0 * pairs * model["index_n_heads"] * model["index_head_dim"]


def index_bytes(model: Mapping[str, Any], keys: float, key_bytes: int = 2
                ) -> float:
    return float(keys) * model["index_head_dim"] * key_bytes


def entry_values(model: Mapping[str, Any]) -> int:
    """Values of one token's entry of the latent cache."""
    return model["kv_lora_rank"] + model["qk_rope_head_dim"]


def attention_flops(model: Mapping[str, Any], pairs: float) -> float:
    """``pairs`` (query, picked key) pairs over all heads: ``q . k`` and ``p
    . v``."""
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


def scope_seconds(t: Mapping[str, Any], scopes, program: Optional[str] = None
                  ) -> float:
    """Device seconds under ``scopes`` (of one step program, or of all): a
    kernel's call carries its scope, so a kernel by one of these names is
    counted where it has no scope."""
    total = 0.0
    for table in ("scope_s", "kernel_s"):
        for key, s in (t.get(table) or {}).items():
            prog, name = key.rsplit("/", 1)
            if name in scopes and program in (None, prog):
                if table == "kernel_s" and f"{prog}/{name}" in t["scope_s"]:
                    continue
                total += s
    return total


def busy_share(obs, scopes) -> Optional[float]:
    """100 x the device seconds under ``scopes`` over the busy seconds of the
    traced window; None where the trace has no such scope."""
    from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

    t = by_name(obs)
    if not t or not t["busy_s"]:
        return None
    inside = scope_seconds(t, scopes)
    return 100.0 * inside / t["busy_s"] if inside else None


def roofline_share(obs, scopes, pairs_key: str, keys_key: str, flops, nbytes
                   ) -> Optional[float]:
    """100 x (the least time the traced steps' work could take) / (the time
    it took): the larger of ``nbytes(model, keys)`` at the HBM rate and
    ``flops(model, pairs)`` at the bfloat16 peak, of a mean step of each
    kind, times the steps of that kind the trace holds, over the device
    seconds under ``scopes``.  None where the program has no such counter or
    the trace no such scope."""
    from benchmark import stats
    from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

    t = by_name(obs)
    if not t or "indexer_types" not in obs.get("model", {}):
        return None
    model, peaks = obs["model"], obs["device"]["peaks"]
    least = taken = 0.0
    for kind, program in (("mixed", "jit_mixed_step"),
                          ("decode", "jit_decode_step")):
        steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                       kind=kind)
                 if pairs_key in s["attrs"]]
        n = steps_traced(t, model, program)
        if not steps or not n:
            continue
        mean_pairs = sum(a[pairs_key] for a in steps) / len(steps)
        mean_keys = sum(a[keys_key] for a in steps) / len(steps)
        least += n * max(
            nbytes(model, mean_keys) / peaks["hbm_bytes_per_s"],
            flops(model, mean_pairs) / peaks["bf16_flops_per_s"])
        taken += scope_seconds(t, scopes, program)
    if not taken or not least:
        return None
    return 100.0 * least / taken
