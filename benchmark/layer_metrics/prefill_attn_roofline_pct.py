"""Kernels: the paged prefill attention kernel's share of its roofline in
the mixed steps.  The least time the chip could take is the larger of the
HBM time of the K/V blocks the steps had to read (``kv_blocks_read`` x
``attn_flops.block_bytes`` at ``peaks.json``'s HBM rate) and the MXU time of
the (query, key) pairs they multiply (``kv_query_keys``,
``attn_flops.attention_flops``); the time it took is the device time of
``jit_mixed_step/paged_attention_prefill`` in the traced window, scaled from
the calls the trace holds to the steps counted.  The kernel multiplies in
float32, which the MXU runs as several bfloat16 passes; ``peaks.json`` has
the bfloat16 peak only, so that is the peak used and the share is a lower
bound."""

from benchmark import attn_flops, stats
from benchmark.layer_metrics.moe_gemm_busy_pct import by_name

KEY = "jit_mixed_step/paged_attention_prefill"


def read(obs):
    t = by_name(obs)
    steps = [s["attrs"] for s in stats.spans_named(obs, "engine/step",
                                                   kind="mixed")
             if "kv_query_keys" in s["attrs"]]
    if not t or not steps or not t["kernel_s"].get(KEY):
        return None
    model, peaks = obs["model"], obs["device"]["peaks"]
    # a mean step's attention, all layers: one call a layer a step
    hbm_s = (sum(a["kv_blocks_read"] for a in steps) / len(steps)
             * attn_flops.block_bytes(model, obs["engine"]["v2"]["block_size"])
             / peaks["hbm_bytes_per_s"])
    mxu_s = (attn_flops.attention_flops(
        model, sum(a["kv_query_keys"] for a in steps) / len(steps))
        / peaks["bf16_flops_per_s"])
    steps_traced = t["kernel_calls"][KEY] / model["num_hidden_layers"]
    return 100.0 * max(hbm_s, mxu_s) * steps_traced / t["kernel_s"][KEY]
