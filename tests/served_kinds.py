"""Shared by the tests of the served kinds (``programs.ServedKind``): the
settings that turn a refused option on, and the attributes ``engine/step``
carried on the parent of the PR that named the kinds (bb36f0b), which
``benchmark/layer_metrics/`` divides by."""

#: V2Config field -> the settings that turn it on (a field of
#: ``programs.REFUSED`` that is not here fails the collection of the refusal
#: tests: add it)
TURNED_ON = {
    "enable_prefix_cache": [dict(enable_prefix_cache=True),
                            dict(enable_prefix_cache=True, kv_host_pool_mb=1)],
    "kv_host_pool_mb": [dict(kv_host_pool_mb=1)],
    "kv_host_pool_bytes": [dict(kv_host_pool_bytes=4096)],
    "kv_spill_dir": [dict(kv_spill_dir="/tmp/x")],
    "kv_coldstore_dir": [dict(kv_coldstore_dir="/tmp/x")],
    "spec_mode": [dict(spec_mode="self_draft"), dict(spec_mode="draft")],
    "adapter_slots": [dict(adapter_slots=2, adapter_rank=4)],
}


#: one tiny model a served kind, the KV kind's three shapes apart (dense,
#: routed experts, a window pool beside the global one): preset, and what its
#: engine is built with beyond the sizes a test file shares
TINY_KINDS = {
    "kv-dense": ("tiny", {}),
    "kv-moe": ("tiny-olmoe", {}),
    "kv-two-pools": ("tiny-mellum2", {}),
    "state": ("tiny-nemotron3", {}),
    "latent": ("tiny-glm52", dict(num_blocks=65)),
    "eva": ("tiny-evabyte", {}),
    "linear-latent": ("tiny-kimi-linear", dict(num_blocks=65)),
}


def conv_ragged_gather(xbc, kept, p, row, offset, row_start, row_len):
    """``ssm_hybrid.conv_ragged`` as it stood before PR 59, the reference of
    its tests and of ``scripts/selective_kernels_alone.py --cases conv``: the
    input ``s`` tokens back as two gathers of all ``T`` rows a tap (the
    step's own tokens; the rows' kept columns) and a ``where`` between
    them."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.ssm_hybrid import conv_taps

    T = xbc.shape[0]
    K1 = kept.shape[1]
    t = jnp.arange(T)
    taps = []
    for s in range(K1, 0, -1):  # the input s tokens back
        own = xbc[jnp.maximum(t - s, 0)]
        old = kept[row, jnp.clip(K1 - s + offset, 0, K1 - 1)]
        taps.append(jnp.where((offset >= s)[:, None], own, old))
    out = conv_taps(taps + [xbc], p)
    back = K1 - jnp.arange(K1)[None, :]  # (1, K1)
    j = row_len[:, None] - back  # its offset in this step's row, or < 0
    own = xbc[jnp.clip(row_start[:, None] + j, 0, T - 1)]
    old = kept[jnp.arange(kept.shape[0])[:, None],
               jnp.clip(jnp.arange(K1)[None, :] + row_len[:, None], 0, K1 - 1)]
    return out, jnp.where((j >= 0)[..., None], own, old)


def refusal_cases(kind, model_cfg, v2) -> list:
    """(V2Config overrides, the field the refusal names) for every row of the
    refusal table that ``kind`` holds for this model, every field of the row
    and every setting that turns the field on."""
    return [(over, field) for name in kind.refuses(model_cfg, v2)
            for field in name.split(" / ") for over in TURNED_ON[field]]


#: every step that reached the device, with tracing on
_STEP = {"kind", "step", "running", "waiting", "prefilling", "emitted",
         "tokens", "budget", "h2d_copies", "h2d_bytes", "pre_ms", "device_ms",
         "post_ms", "pre_cpu_ms", "post_cpu_ms"}
#: a decode step: whose copy its program ran on
_DECODE = {"staged"}
#: a decode step since ISSUE 50, a mixed step since ISSUE 54: whether it found
#: its program under way, whether it called its successor before its own
#: fetch, and the rows whose token it dropped
_AHEAD = {"ahead", "ahead_next", "ahead_dropped"}
#: a step that found a staged copy it could not use
_SOMETIMES = {"stage_discarded", "stage_bytes"}
#: by what the model is, beyond the above: on every step, on mixed steps only
STEP_ATTRS = {
    "moe": ({"moe_rows", "moe_rows_padded", "moe_experts_hit",
             "moe_rows_max"}, set()),
    "window": ({"kv_blocks_read", "kv_blocks_full", "kv_query_keys",
                "window_blocks_freed", "blocks_used_global",
                "blocks_used_window"}, set()),
    # ``kv_blocks_used``: new with PR 58 (the attention layers' pool beside
    # the state slots), on every step of the kind
    "state": ({"state_slots_used", "state_rows_started", "ssm_tokens",
               "ssm_state_bytes", "kv_blocks_used"},
              {"ssm_scan_rows", "ssm_scan_tokens", "ssm_scan_pieces"}),
    "latent": ({"dsa_keys_visible", "dsa_keys_selected",
                "dsa_selected_single", "dsa_selected_prefill",
                "latent_keys_single", "latent_keys_prefill",
                "dsa_index_pairs", "dsa_index_keys", "latent_blocks_used",
                "moe_assignments", "moe_assignments_local"}, set()),
    # new with the kind (PR 48): no parent carried them
    "eva": ({"eva_window_keys", "eva_summary_keys", "eva_keys_full",
             "eva_query_keys", "eva_windows_closed", "eva_chunks_written",
             "blocks_used_window", "blocks_used_summary"}, set()),
    # new with the kind (PR 51): no parent carried them
    "linear_latent": ({"state_slots_used", "state_rows_started", "kda_tokens",
                       "kda_state_bytes", "latent_keys_read",
                       "latent_keys_single", "latent_keys_prefill",
                       "latent_query_keys", "blocks_used_latent",
                       "moe_assignments", "moe_assignments_local"},
                      {"kda_scan_rows", "kda_scan_tokens",
                       "kda_scan_pieces"}),
}


def assert_step_attrs(steps, *what) -> None:
    """Every ``engine/step`` span's attributes in ``steps`` (a decode and a
    mixed step among them) are the parent's for a model that is ``what``:
    the same names on the same kind of step, none more and none fewer."""
    assert {"mixed", "decode"} <= {a["kind"] for a in steps}
    for a in steps:
        mixed = a["kind"] == "mixed"
        want = _STEP | _AHEAD | ({"attn_q_slots"} if mixed else _DECODE)
        for name in what:
            always, on_mixed = STEP_ATTRS[name]
            want |= always | (on_mixed if mixed else set())
        assert set(a) - _SOMETIMES == want, (a["kind"], set(a) ^ want)
