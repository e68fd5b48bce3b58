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
  (one row without a context), the kernel against its XLA twin.

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/kda_kernels_alone.jsonl``, the device's line first.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import kda_flops
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
    return lines


CASES = {"decode": case_decode, "chunk": case_chunk, "latent": case_latent}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", nargs="*", default=list(CASES))
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
