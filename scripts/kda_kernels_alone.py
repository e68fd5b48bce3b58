"""The KDA state updates and the full latent decode alone on the chip, at the
cell's shapes (``kimilinear-reason-sat``: 32 heads of 128 x 128, 49 slots,
pieces of 64; a latent pool 640 wide in blocks of 64, 48 rows, tables of
128): device time a call beside the HBM time of what the rows have to move
(``benchmark/kda_flops.py``'s arithmetic, the yardstick of
``kda_*_roofline_pct`` and ``latent_full_decode_roofline_pct``), and the
largest difference from the oracle on the same operands (the recurrence a
token at a time; the XLA twin of the latent kernel).

    chiprun -- python scripts/kda_kernels_alone.py [--cases decode ...]

* ``decode``: the Pallas decode update on 48 running slots and the scratch
  slot, one of them fresh, one inactive; also ``steps`` consecutive steps
  against the recurrence, so that what a rounded gate would compound shows;
* ``chunk``: the chunked form on a step of 512 tokens (a row of 449 from its
  slot's state, a fresh row of 60, three rows of one token the scan leaves
  alone), then the same slots again from the state the first call left
  (carried across two mixed steps) and with keys that are nine tenths one
  direction, against the recurrence;
* ``latent``: 48 rows' one query each over contexts of 1k-8k and of 1k-3k
  (one row without a context), the kernel against its XLA twin; then
  GLM-5.2's rows of one token (``glm52-ctx8k-sat``: 16 rows of which 12
  live, 64 heads, 2,048 picks drawn uniformly and in runs of 64), nine
  layers in one program: the kernel under the pick's mask against the gather
  under tables of 8k, 17k (the cell's), 32k and 64k keys, contexts of a
  quarter of the table up to all of it and, for the crossing, all at the
  table's end; and what turns a row's scores into its picks, as positions
  (``lax.top_k``) and as the kernel's mask (``topk_mask``);
* ``conv`` (on request): the mixed step's ragged causal conv alone, at
  ``kda_conv``'s shape among the three cells' (``ssm_hybrid.conv_ragged``;
  the case is ``scripts/selective_kernels_alone.py``'s).

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/kda_kernels_alone.jsonl``, the device's line first.
"""

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import dsa_flops, kda_flops
from deepspeed_tpu.ops.pallas import kda
from deepspeed_tpu.ops.pallas import latent_attention as la

H, DK, S1, LAYERS, Q = 32, 128, 49, 2, 64
MODEL = {"linear_attn_config": {"num_heads": H, "head_dim": DK,
                                "kda_layers": [1], "full_attn_layers": [2]},
         "kv_lora_rank": 512, "qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
         "v_head_dim": 128, "num_attention_heads": 32}


def inputs(key, n, alike=0.0):
    """``n`` tokens' q, k, v, log a, b as the layer makes them; ``alike``:
    the share of every key that is one common direction (behind an attention
    layer a piece's keys are alike, and its triangular system far from the
    identity)."""
    ks = jax.random.split(key, 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (n, H, DK))) * DK ** -0.5
    k = unit(alike * jax.random.normal(ks[5], (1, H, DK))
             + (1 - alike ** 2) ** 0.5 * jax.random.normal(ks[1], (n, H, DK)))
    v = jax.random.normal(ks[2], (n, H, DK))
    log_a = -0.002 * jnp.exp(jax.random.normal(ks[3], (n, H, DK)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (n, H)))
    return q, k, v, log_a, b


def timed(fn, *args, repeats=10):
    """→ (the result, milliseconds a call): the calls are enqueued one behind
    the other and the last is waited for, so the host's turn-round of a call
    (0.1-0.2 ms, a third of these kernels' time) is paid once, not a call."""
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        last = fn(*args)
    jax.block_until_ready(last)
    return out, (time.perf_counter() - t0) / repeats * 1e3


def case_decode(peaks, steps=64):
    key = jax.random.PRNGKey(1)
    state = jax.random.normal(key, (LAYERS, S1, H, DK, DK), jnp.float32)
    active = jnp.ones((S1,), bool).at[5].set(False).at[S1 - 1].set(False)
    fresh = jnp.zeros((S1,), bool).at[7].set(True)
    layer = jnp.int32(1)
    step = jax.jit(kda.kda_decode_update)
    twin = jax.jit(kda._decode_update_xla)
    one = inputs(jax.random.fold_in(key, 0), S1)
    o, new = step(state, layer, *one, active, fresh)
    o_x, new_x = twin(state, layer, *one, active, fresh)
    # timed as a step program calls it: the state donated, carried from call
    # to call (a call that keeps its argument copies 205 MB beside the work)
    carried = jax.jit(kda.kda_decode_update, donate_argnums=(0,))
    run = jax.block_until_ready(carried(state + 0.0, layer, *one, active,
                                        fresh)[1])
    t0 = time.perf_counter()
    for _ in range(20):
        _, run = carried(run, layer, *one, active, fresh)
    jax.block_until_ready(run)
    ms = (time.perf_counter() - t0) / 20 * 1e3
    line = {"case": "decode", "ms": ms,
            "hbm_ms": kda_flops.decode_update_bytes(MODEL, S1 - 2)
            / peaks["hbm_bytes_per_s"] * 1e3,
            "o_diff": float(jnp.abs(o - o_x)[active].max()),
            "state_diff": float(jnp.abs(new - new_x).max()),
            "inactive_kept": bool((new[1, 5] == state[1, 5]).all()),
            "other_layer_kept": bool((new[0] == state[0]).all())}
    # many steps: the kernel's state against the recurrence's, a slot
    toks = inputs(jax.random.fold_in(key, 1), steps)
    run = state
    for t in range(steps):
        tok = [jnp.broadcast_to(x[t], (S1,) + x.shape[1:]) for x in toks]
        _, run = step(run, layer, *tok, jnp.ones((S1,), bool),
                      jnp.zeros((S1,), bool))
    _, want = kda.kda_recurrence(*toks, state[1, 3])
    line["steps"] = steps
    line["state_diff_after_steps"] = float(
        jnp.abs(run[1, 3] - want).max() / jnp.abs(want).max())
    return line


def case_chunk(peaks):
    key = jax.random.PRNGKey(2)
    state = 0.1 * jax.random.normal(key, (LAYERS, S1, H, DK, DK), jnp.float32)
    layer = jnp.int32(0)
    row_len = jnp.zeros((48,), jnp.int32).at[:5].set(
        jnp.array([449, 1, 60, 1, 1]))
    row_start = jnp.cumsum(row_len) - row_len
    slots = jnp.full((48,), S1 - 1, jnp.int32).at[:5].set(
        jnp.array([3, 9, 11, 12, 13]))
    scan = jax.jit(kda.kda_chunk_scan, static_argnames="chunk")
    lines, run, want = [], state, [state[0, 3], jnp.zeros((H, DK, DK))]
    for call in range(2):
        fresh = jnp.zeros((48,), bool).at[2].set(call == 0)
        toks = inputs(jax.random.fold_in(key, call), 512, alike=0.9 * call)
        args = (run, layer, *toks, row_start, row_len, slots, fresh,
                row_len >= 2)
        (o, run), ms = timed(lambda *a: scan(*a, chunk=Q), *args, repeats=3)
        diffs = []
        for r, (a, n) in enumerate(((0, 449), (450, 60))):
            part = [x[a:a + n] for x in toks]
            o_r, want[r] = kda.kda_recurrence(*part, want[r])
            diffs.append((float(jnp.abs(o[a:a + n] - o_r).max()
                                / jnp.abs(o_r).max()),
                          float(jnp.abs(run[0, (3, 11)[r]] - want[r]).max()
                                / jnp.abs(want[r]).max())))
        lines.append({
            "case": "chunk", "call": call, "ms": ms,
            "least_ms": max(
                kda_flops.scan_bytes(MODEL, 509, 2) / peaks["hbm_bytes_per_s"],
                kda_flops.scan_flops(MODEL, 509, 9)
                / peaks["bf16_flops_per_s"]) * 1e3,
            "o_and_state_diff_row_of_449": diffs[0],
            "o_and_state_diff_row_of_60": diffs[1],
            "single_rows_zero": float(jnp.abs(o[449]).max()) == 0.0})
    return lines


def case_latent(peaks):
    R, W, BS, blocks = 48, 640, 64, 128
    nb = R * blocks + 1
    key = jax.random.PRNGKey(3)
    pool = jax.random.normal(key, (LAYERS, nb, BS, W), jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(nb - 1).reshape(R, blocks), jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1), (R, H, W), jnp.bfloat16)
    kw = dict(scale=192 ** -0.5, latent=512)
    kernel = jax.jit(lambda *a: la.latent_decode_attention_full(*a, **kw))
    twin = jax.jit(lambda *a: la._decode_full_xla(*a, **kw))
    lines = []
    # the contexts of the issue's sizing (1k-8k) and of the cell's window (a
    # prompt of 256-4k and the answer so far: 1k-5k, most under 3k)
    for name, lo, hi in (("1k-8k", 1024, 8192), ("1k-3k", 1024, 3072)):
        ctx = rng.integers(lo, hi, size=R)
        ctx[5] = 0
        keys = float(ctx.sum())
        fetched = float((-(-ctx // BS) * BS).sum())
        ctx = jnp.asarray(ctx, jnp.int32)
        args = (q, pool, jnp.int32(1), tables, ctx)
        got, ms = timed(kernel, *args, repeats=20)
        want, ms_xla = timed(twin, *args, repeats=2)
        hbm_ms = kda_flops.attention_bytes(MODEL, keys) \
            / peaks["hbm_bytes_per_s"] * 1e3
        lines.append({
            "case": "latent", "contexts": name, "keys": keys, "ms": ms,
            "xla_ms": ms_xla, "hbm_ms": hbm_ms,
            # what the kernel fetches: whole blocks, the pool's rows whole
            "hbm_ms_as_fetched": hbm_ms * fetched / keys
            * W / kda_flops.entry_values(MODEL),
            "mxu_ms": kda_flops.attention_flops(MODEL, keys)
            / peaks["bf16_flops_per_s"] * 1e3,
            "diff": float(jnp.abs(got - want).max()),
            "largest": float(jnp.abs(want).max()),
            "no_context_is_zero": float(jnp.abs(got[5]).max()) == 0.0})
    return lines + glm52_lines(peaks)


def glm52_lines(peaks, layers=9, widths=(128, 272, 512, 1024)):
    """GLM-5.2's rows of one token, ``layers`` layers in one program (a call
    is 0.1-0.2 ms of the host here, a layer's kernel 0.2-0.5 ms of the
    device) under tables of ``widths`` blocks."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-5.2-ep16-w8.json")) as f:
        model = json.load(f)
    R, live, Hq, W, BS = 16, 12, 64, 640, 64
    nb = R * max(widths) + 1
    key = jax.random.PRNGKey(4)
    pool = jax.random.normal(key, (LAYERS, nb, BS, W), jnp.bfloat16)
    rng = np.random.default_rng(1)
    # a layer its own queries: XLA folds calls alike into one
    q = jax.random.normal(jax.random.fold_in(key, 1), (layers, R, Hq, W),
                          jnp.bfloat16)
    kw = dict(scale=256 ** -0.5, latent=model["kv_lora_rank"])

    # the layer's index rides in as the layer scan hands it over, traced: the
    # gather reads a pool of many layers at a STATIC index three times faster
    # (0.19 against 0.58 ms a layer, PERF.md section 5), and no step program
    # has one
    layer_ids = jnp.arange(layers, dtype=jnp.int32) % LAYERS

    def stack(attend):
        def run(layer_ids, q, pool, tables, *picks):
            return sum(attend(q[i], pool, layer_ids[i], tables, *picks, **kw)
                       for i in range(layers))
        return functools.partial(jax.jit(run), layer_ids)

    gather = stack(la.latent_decode_attention)
    lines = []
    for blocks in widths:
        S = blocks * BS
        k = min(model["index_topk"], S)
        kernel = stack(functools.partial(la.latent_decode_attention_masked,
                                         k=k))
        tables = jnp.asarray(
            rng.permutation(nb - 1)[:R * blocks].reshape(R, blocks),
            jnp.int32)
        for contexts in ("quarter-to-all", "all"):
            ctx = np.full(R, S) if contexts == "all" \
                else rng.integers(S // 4, S + 1, size=R)
            ctx[rng.permutation(R)[:R - live]] = 0
            for drawn in ("uniform", "runs-of-64"):
                mask = np.zeros((R, S), bool)
                for r in np.flatnonzero(ctx):
                    if drawn == "uniform" or ctx[r] <= k:
                        at = rng.permutation(ctx[r])[:k]
                    else:
                        starts = rng.permutation(ctx[r] // 64)[:k // 64] * 64
                        at = (starts[:, None] + np.arange(64)).reshape(-1)
                    mask[r, at] = True
                picked = mask.sum(axis=1)
                idx = np.argsort(~mask, axis=1, kind="stable")[:, :k]
                ok = np.arange(k)[None] < picked[:, None]
                args = (q, pool, tables)
                got, ms = timed(kernel, *args, jnp.asarray(mask),
                                jnp.asarray(ctx, jnp.int32))
                want, ms_xla = timed(gather, *args,
                                     jnp.asarray(idx, jnp.int32),
                                     jnp.asarray(ok))
                fetched = float((-(-ctx // BS) * BS).sum())
                lines.append({
                    "case": "latent-glm52", "s_max": S, "contexts": contexts,
                    "picks": drawn, "layers": layers,
                    "engages": la.decode_gathers(blocks, pool, kw["latent"],
                                                 k) or "masked, pallas",
                    "keys_fetched": fetched, "keys_picked": float(picked.sum()),
                    "ms_a_layer": ms / layers,
                    "gather_ms_a_layer": ms_xla / layers,
                    "hbm_ms_as_fetched": fetched * W * 2
                    / peaks["hbm_bytes_per_s"] * 1e3,
                    "hbm_ms_as_picked": dsa_flops.attention_bytes(
                        model, float(picked.sum()))
                    / peaks["hbm_bytes_per_s"] * 1e3,
                    "mxu_ms_as_multiplied": 2.0 * fetched * Hq
                    * (W + kw["latent"]) / peaks["bf16_flops_per_s"] * 1e3,
                    "mxu_ms_as_picked": dsa_flops.attention_flops(
                        model, float(picked.sum()))
                    / peaks["bf16_flops_per_s"] * 1e3,
                    "diff": float(jnp.abs(got - want).max()),
                    "largest": float(jnp.abs(want).max()),
                    "idle_rows_zero": float(
                        jnp.abs(got[np.flatnonzero(ctx == 0)]).max()) == 0.0})
        # a picking layer's scores into the rows' picks, both ways
        scores = jnp.where(
            jnp.arange(S)[None] < jnp.asarray(ctx)[:, None],
            jax.random.normal(jax.random.fold_in(key, blocks), (R, S)),
            -jnp.inf)
        as_idx = jax.jit(lambda x: jax.lax.top_k(x, k))
        as_mask = jax.jit(lambda x: la.topk_mask(x, k))

        def scattered(x):
            vals, idx = jax.lax.top_k(x, k)
            return la.rows_as_mask(idx, vals > -jnp.inf, S)

        back = jax.jit(scattered)
        _, ms_idx = timed(as_idx, scores)
        m, ms_mask = timed(as_mask, scores)
        m2, ms_back = timed(back, scores)
        lines.append({"case": "latent-glm52-pick", "s_max": S,
                      "top_k_ms": ms_idx, "topk_mask_ms": ms_mask,
                      "top_k_then_scatter_ms": ms_back,
                      "same_set": bool((m == m2).all())})
    return lines


def case_conv(peaks):
    """The mixed step's ragged conv alone at the three cells' shapes (Kimi's
    ``kda_conv`` is the second): ``selective_kernels_alone.py``'s case."""
    from scripts.selective_kernels_alone import case_conv as conv

    return conv(peaks)


CASES = {"decode": case_decode, "chunk": case_chunk, "latent": case_latent,
         "conv": case_conv}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", nargs="*", choices=list(CASES),
                    default=["decode", "chunk", "latent"])
    args = ap.parse_args()
    dev = jax.devices()[0]
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if dev.platform != "tpu" or dev.device_kind not in peaks:
        print(f"kda_kernels_alone: needs a TPU that benchmark/peaks.json "
              f"names; JAX found {dev.platform!r} ({dev.device_kind})",
              file=sys.stderr)
        return 1
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kda_kernels_alone.jsonl"),
              "w") as out:
        def emit(line):
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")

        emit({"device": dev.device_kind})
        for name in args.cases:
            got = CASES[name](peaks[dev.device_kind])
            for line in got if isinstance(got, list) else [got]:
                emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
