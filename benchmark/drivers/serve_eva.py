"""Driver ``serve_eva``: driver ``serve_moe`` for a dense byte model with EVA
attention (EvaByte-6.5B): a tumbling window of exact keys and one learned
summary a chunk behind it, two pools in every layer, eight output heads.

Everything ``serve_moe.run`` does is done by it, imported: the server, the
load generator, the spans, the trace reduced by kernel and scope name, the
count of kernel fallbacks (which takes in the ring's
``kernel/eva_attention_tiles`` and ``kernel/eva_summarize_tiles`` events).
What this configuration changes is handed to it for the run, as
``serve_swa_moe`` hands its own (``serve_moe`` looks these up in its own
module when it runs):

* ``program_config``: ``serve_moe``'s, unchanged: the file's ``program`` key
  lists the EVA keys (``window_size``, ``chunk_size``, ``num_pred_heads``,
  ``fp32_logits``) against the program's attributes, what ``model_type``
  implies (the norm with a unit offset, RoPE, no q/k norm) and what must be
  off;
* ``reference``: ``benchmark/reference/eva_byte_decoder.py``;
* ``make_params``: ``serve.make_params``, then every norm's offset ``g``
  drawn from the seed in [-0.5, 0.5) (at ``g`` = 0, which ``init_params``
  gives, a program that added no unit offset would read zeros, but one that
  added it twice like the right one); ``phi`` and ``mu`` are the program's
  own seeded draws (``models/eva.py:init_vectors``);
* ``tap_logits``: ``DonatedLogitTap`` (no second copy of the pools) whose
  decode step draws the next byte from head 0's columns as the served one
  does (``benchmark/logit_tap_donated.py`` samples from every column it is
  handed, which for eight heads side by side is no byte);
* ``check_logits``: every tapped row against the reference's whole forward
  pass on ALL heads' logits; the sample's first prompt ends short of its
  second window's edge, so that prefill closes one window and decode the
  next: two closes a sequence;
* ``check_served``: the served bytes' margins under head 0, each sequence
  padded to whole windows and no further (a pass over 8k bytes is 5 s in
  float32): the warm-up request and the window's picks, which
  ``serve.pick_sequences`` draws from those that fit ``reference_len``;
* ``check_router``: there is no router; in its place ``check_summaries``
  compares WHAT THE CACHE HOLDS with the reference directly: the summary
  keys and values the engine's own steps wrote into the summary pool for the
  sample's first sequence (its closed windows, the layers
  ``check.summary_layers``), against the reference's summaries of the same
  bytes.  Logits cannot see them: with seeded weights a query's softmax
  spreads over thousands of keys, and a summary key read the other way
  (the weighted key, no ``mu``, uniform pooling) moves the logits by less
  than bfloat16 does through 32 layers (measured: PERF.md section 6, PR 48);
* ``MOE_SCOPES``: the EVA kernels' scopes; ``HERE``: the ordered-start
  generator (the prompts prefill in the order of arrival).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Mapping, Tuple
from unittest import mock

import numpy as np

from benchmark.drivers import serve, serve_moe
from benchmark.drivers.serve_swa_moe import ORDERED_START
from benchmark.reference import eva_byte_decoder as reference

#: the scopes ``programs.eva_layers`` names, for the trace's reduction
SCOPES = ("eva_attention_decode", "eva_attention_prefill", "eva_summarize",
          "cache_write")


def published_model(cfg) -> Dict[str, Any]:
    """The published keys the reference reads, from a program configuration
    (the tier-1 tests and ``chip_smoke.py`` start from a preset)."""
    return dict(num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
                window_size=cfg.eva_window, chunk_size=cfg.eva_chunk,
                num_pred_heads=cfg.num_pred_heads, vocab_size=cfg.vocab_size)


def draw_norm_offsets(params, seed: int):
    """Every norm's ``g`` uniform in [-0.5, 0.5), from the seed."""
    import jax
    import jax.numpy as jnp

    def draw(tree, salt):
        old = tree["scale"]
        tree["scale"] = jax.random.uniform(
            jax.random.fold_in(jax.random.PRNGKey(seed), salt), old.shape,
            jnp.float32, -0.5, 0.5).astype(old.dtype)

    draw(params["layers"]["ln1"], 0xE1)
    draw(params["layers"]["ln2"], 0xE2)
    draw(params["final_norm"], 0xE3)
    return params


def make_params(cfg, seed: int, bits: int, group: int):
    return draw_norm_offsets(serve.make_params(cfg, seed, bits, group), seed)


def build_decode_with_logits(model_cfg, v2):
    """``logit_tap_donated.build_decode_with_logits`` drawing the next byte
    from head 0's columns, as ``build_decode_forward`` does."""
    import dataclasses

    import jax

    from deepspeed_tpu.inference.v2.engine import (_decode_body, _memo,
                                                   sample_rows)
    from deepspeed_tpu.models import transformer as tfm

    def decode_step(params, caches, token_ids, position_ids, block_tables,
                    context_lens, temps, rng, seeds):
        logits, caches, _ = _decode_body(
            params, caches, token_ids, position_ids, block_tables,
            context_lens, model_cfg, v2)
        return (sample_rows(tfm.next_token_logits(logits, model_cfg), temps,
                            rng, seeds), caches, logits)

    return _memo(("eva_decode_with_logits", model_cfg,
                  dataclasses.astuple(v2)),
                 lambda: jax.jit(decode_step, donate_argnums=(1,)))


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]
               ) -> List[Tuple[List[int], List[int], list]]:
    """A seeded sample through the drained engine's own step programs, the
    logits of every head tapped: → [(prompt, bytes, [(position, logits)])]."""
    from benchmark import logit_tap_donated

    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    with mock.patch.object(logit_tap_donated, "build_decode_with_logits",
                           build_decode_with_logits):
        tap = logit_tap_donated.DonatedLogitTap(engine)
    chains, finish = {}, engine._finish

    def noting(seq):  # where its summaries lie, before the blocks go back
        chains[seq.uid] = list(seq.blocks)
        return finish(seq)

    engine._finish = noting
    try:
        uids = [engine.put(p, max_new_tokens=check["logit_tokens"])
                for p in prompts]
        out = engine.generate_all(burst=1)
    finally:
        tap.remove()
        engine._finish = finish
    tapped = Tapped((p, out[u][len(p):], tap.logits[u])
                    for p, u in zip(prompts, uids))
    # the first sequence's summaries as the pool holds them (freed, and
    # written by nobody since: every sequence of the sample ran to the end)
    closed = len(out[uids[0]]) // cfg.eva_window * (
        cfg.eva_window // cfg.eva_chunk)
    blocks = np.asarray(chains[uids[0]], np.int32)
    tapped.summaries = {
        layer: tuple(np.asarray(engine.caches[name][layer, blocks]
                                .astype("float32")).reshape(
            (-1,) + engine.caches[name].shape[3:])[:closed]
            for name in ("k_sum", "v_sum"))
        for layer in check.get("summary_layers", ())}
    return tapped


class Tapped(list):
    """``tap_logits``' sequences, and beside them what the summary pool held
    for the first: ``summaries[layer] = (k~, v~)``, ``(entries, H, D)``."""
    summaries: Dict[int, tuple] = {}


def reference_rows(params, model, tapped, faults=()) -> List[np.ndarray]:
    """A sequence at a time: the reference's logits, every head's, at the
    positions the tap read (one uncached pass over prompt and bytes)."""
    import jax.numpy as jnp

    out = []
    for prompt, tokens, rows in tapped:
        seq = jnp.asarray(prompt + tokens, jnp.int32)
        first = len(prompt) - 1  # the first tapped row reads this position
        want = np.asarray(reference.logits(
            params, model, seq, last=len(prompt) + len(tokens) - first,
            faults=tuple(faults)))
        out.append(np.stack([want[pos - first] for pos, _ in rows]))
    return out


def row_errors(params, model, tapped, faults=()) -> List[List[float]]:
    """A sequence at a time: each tapped row's largest |engine - reference|
    over every head's logits."""
    return [[float(np.abs(row - want).max())
             for (_, row), want in zip(rows, wanted)]
            for (_, _, rows), wanted in zip(
                tapped, reference_rows(params, model, tapped, faults))]


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The two bounds of ``serve_moe.check_logits`` on all eight heads: the
    median row for a systematic fault (a summary's rule, the norm's offset,
    a head's map), the worst row for a local one (a freed block read, a
    summary a window late)."""
    by_seq = row_errors(params, model, tapped)
    errs = np.asarray([e for seq in by_seq for e in seq])
    median, worst = float(np.median(errs)), float(errs.max())
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"])
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(p) for p, _, _ in tapped]}, bytes "
        f"{[len(t) for _, t, _ in tapped]}, median a sequence "
        f"{[round(float(np.median(e)), 4) for e in by_seq]}), all "
        f"{model['num_pred_heads']} heads; |engine - reference| median "
        f"{median:.4f} (allowed {check['logit_tol_median']}), worst "
        f"{worst:.4f} (allowed {check['logit_tol']})")
    return {"rows": len(errs), "median": median, "worst": worst,
            "ok": bool(ok)}


def check_served(params, model, sequences, pad_to: int, margin: float,
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """``serve_moe.check_served`` with each sequence at its own length (the
    reference pads to whole windows itself)."""
    import jax.numpy as jnp

    del pad_to  # the bound on what is picked, not a length to pad to
    worst, exact, checked, lengths = 0.0, 0, 0, []
    for prompt, served in sequences:
        m, rank = reference.served_margins(
            params, model, jnp.asarray(prompt + served, jnp.int32),
            len(prompt))
        m, rank = np.asarray(m), np.asarray(rank)
        worst = max(worst, float(m.max()) if np.isfinite(m).all()
                    else float("inf"))
        exact += int((rank == 0).sum())
        checked += len(served)
        lengths.append(len(prompt) + len(served))
    log(f"reference: {checked} served bytes of {len(sequences)} sequences "
        f"(lengths {lengths}), {exact} are head 0's argmax, worst margin "
        f"{worst:.4f} (allowed {margin})")
    return {"tokens_checked": checked, "argmax_equal": exact,
            "worst_margin": worst, "ok": checked > 0 and worst <= margin}


def summary_errors(params, model, tapped, faults=()) -> np.ndarray:
    """Each closed chunk's largest |engine - reference| over its summary key
    and value (all heads), on the layers the tap kept."""
    import jax.numpy as jnp

    layers = sorted(tapped.summaries)
    if not layers:
        return np.zeros(0)
    prompt, tokens, _ = tapped[0]
    wanted = reference.closed_summaries(
        params, model, jnp.asarray(prompt + tokens, jnp.int32), layers,
        tuple(faults))
    return np.concatenate([
        np.maximum(np.abs(got_k - np.asarray(k)).max((1, 2)),
                   np.abs(got_v - np.asarray(v)).max((1, 2)))
        for layer, (k, v) in zip(layers, wanted)
        for got_k, got_v in [tapped.summaries[layer]]])


def check_summaries(params, model, cfg, tapped, check, log) -> Dict[str, Any]:
    """The summary pool against the reference, directly (see the module
    text): two bounds as the logits', the median chunk for a rule read the
    other way, the worst for a local fault (a chunk's tokens of another
    block, a summary written a window late)."""
    errs = summary_errors(params, model, tapped)
    if not len(errs):
        raise ValueError("check.summary_layers kept no summary: the logit "
                         "sample's first sequence closes no window")
    median, worst = float(np.median(errs)), float(errs.max())
    ok = (np.isfinite(errs).all() and median <= check["summary_tol_median"]
          and worst <= check["summary_tol"])
    log(f"summaries: {len(errs)} chunks of the first sequence on layers "
        f"{sorted(tapped.summaries)}; |pool - reference| median {median:.4f} "
        f"(allowed {check['summary_tol_median']}), worst {worst:.4f} "
        f"(allowed {check['summary_tol']})")
    return {"chunks": len(errs), "median": median, "worst": worst,
            "ok": bool(ok)}


def run(**kwargs) -> Dict[str, Any]:
    with mock.patch.multiple(
            serve_moe, reference=reference, make_params=make_params,
            tap_logits=tap_logits, check_logits=check_logits,
            check_served=check_served, check_router=check_summaries,
            MOE_SCOPES=SCOPES, HERE=ORDERED_START):
        return serve_moe.run(**kwargs)
