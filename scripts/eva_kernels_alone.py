"""The three EVA kernels alone on the chip, at the cell's shapes
(``evabyte-doc-bytes-sat``: 32 heads of 128, a window of 2,048 in chunks of
16, blocks of 64, four rows; PERF.md section 5): device time a call beside
the HBM time of what the rows have to read and the MXU time of the (query,
key) pairs they multiply (``benchmark/eva_flops.py``'s arithmetic, the
yardstick of ``eva_*_roofline_pct``), and the largest difference from the
blockwise XLA twin on the same operands.

    chiprun -- python scripts/eva_kernels_alone.py [--cases decode ...]

* ``decode``: four rows' one query each, at contexts the traffic gives (a
  part-filled window and 128-896 summaries);
* ``mixed``: a chunk of 509 tokens ending at 6,144 (a window's edge, behind
  two closed windows) beside three decode rows, as a mixed step holds them;
* ``prefill-first``: a chunk of 512 from position 0 (no summary yet);
* ``summarize``: no row, one row and four rows closing a window.  The
  summariser writes the summary pool in place, and this harness's loop
  carries that pool from call to call, which XLA copies (0.15 ms for the
  69 MB held here; a served step program donates the pools and copies
  nothing: ``tests/test_tpu_compile.py``), so a case's time is read OVER the
  call that closes nothing (``ms_over_empty``); what an empty call costs in
  a step program is in the cell's trace (about a microsecond a layer).

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/eva_kernels_alone.jsonl``, the device's line first.
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

from benchmark import eva_flops
from deepspeed_tpu.ops.pallas import backend, eva_attention as ea

H, D, W, C, BS, R, LAYERS = 32, 128, 2048, 16, 64, 4, 2
MODEL = {"hidden_size": H * D, "window_size": W, "chunk_size": C,
         "num_hidden_layers": 1}
SIZE = dict(window=W, chunk=C)


def pools_and_tables(rng, ends):
    """Both pools (``LAYERS`` layers, random), and tables that give each of
    ``R`` rows whose newest token sits at ``ends[r] - 1`` the blocks of its
    current window and of its closed windows' summaries, scattered."""
    nb, per = W // BS, W // C // BS
    n_win, n_sum = R * nb + 1, R * 8 * per + 1
    key = jax.random.split(jax.random.PRNGKey(0), 4)
    pools = [jax.random.normal(k, (LAYERS, n, BS, H, D), jnp.bfloat16)
             for k, n in zip(key, (n_win, n_win, n_sum, n_sum))]
    win_t, sum_t = (np.zeros((R, 256), np.int32) for _ in range(2))
    win_ids = rng.permutation(n_win - 1).reshape(R, nb)
    sum_ids = rng.permutation(n_sum - 1).reshape(R, 8 * per)
    for r, end in enumerate(ends):
        w = max(end - 1, 0) // W
        win_t[r, w * nb:(w + 1) * nb] = win_ids[r]
        sum_t[r, :8 * per] = sum_ids[r]
    return pools, jnp.asarray(win_t), jnp.asarray(sum_t)


def timed(fn, x, calls):
    """Device time a call: ``calls`` in one program, each reading the one
    before it."""
    many = jax.jit(lambda x: jax.lax.fori_loop(
        0, calls, lambda i, x: fn(x, i % LAYERS), x))
    jax.block_until_ready(many(x))
    t0 = time.perf_counter()
    jax.block_until_ready(many(x))
    return (time.perf_counter() - t0) / calls * 1e3


def attention_case(name, starts, lens, peaks, calls=20):
    rng = np.random.default_rng(0)
    starts, lens = np.asarray(starts), np.asarray(lens)
    pools, win_t, sum_t = pools_and_tables(rng, starts + lens)
    last = (starts + lens - 1)[lens > 0]
    pos = np.concatenate([s + np.arange(n) for s, n in zip(starts, lens)])
    keys = int((last % W + 1).sum() + (last // W * (W // C)).sum())
    pairs = int((pos % W + 1 + pos // W * (W // C)).sum())
    if name == "decode":
        q = jax.random.normal(jax.random.PRNGKey(1), (R, H, D), jnp.bfloat16)
        args = (jnp.asarray(starts, jnp.int32), jnp.asarray(lens > 0))
        kernel, twin = ea.eva_decode_attention, None
    else:
        q = jax.random.normal(jax.random.PRNGKey(1), (512, H, D),
                              jnp.bfloat16)
        args = tuple(jnp.asarray(a, jnp.int32) for a in (
            np.cumsum(lens) - lens, starts, lens))
        kernel, twin = ea.eva_prefill_attention, ea._attention_xla

    def call(x, layer):
        return kernel(x, *pools, layer, win_t, sum_t, *args, **SIZE)

    err = None
    if twin is not None:
        err = float(jnp.abs(
            jax.jit(call)(q, 1).astype(jnp.float32)
            - jax.jit(lambda x: twin(x, *pools, 1, win_t, sum_t, *args,
                                     **SIZE))(q).astype(jnp.float32)).max())
    ms = timed(call, q, calls)
    hbm_ms = eva_flops.key_bytes(MODEL, keys) / peaks["hbm_bytes_per_s"] * 1e3
    mxu_ms = eva_flops.pair_flops(MODEL, pairs) / peaks["bf16_flops_per_s"] \
        * 1e3
    return {"case": name, "rows": [[int(s), int(n)] for s, n in
                                   zip(starts, lens)],
            "keys_read": keys, "query_keys": pairs,
            "ms_a_call": round(ms, 4), "hbm_ms": round(hbm_ms, 4),
            "mxu_ms": round(mxu_ms, 4),
            "roofline_pct": round(100 * max(hbm_ms, mxu_ms) / ms, 2),
            "max_abs_err_vs_xla": err}


def summarize_case(closing, peaks, empty_ms=None, calls=10):
    rng = np.random.default_rng(1)
    closing = np.asarray(closing, np.int32)
    pools, win_t, sum_t = pools_and_tables(
        rng, (np.maximum(closing, 0) + 1) * W)
    phi, mu = (jax.random.normal(k, (H, D), jnp.bfloat16) / np.sqrt(D)
               for k in jax.random.split(jax.random.PRNGKey(2)))
    at = jnp.asarray(closing)

    def call(both, layer):
        return ea.eva_summarize(pools[0], pools[1], *both, layer, win_t,
                                sum_t, at, phi, mu, **SIZE)

    got = jax.jit(call)((pools[2], pools[3]), 1)
    want = jax.jit(lambda both: ea._summarize_xla(
        pools[0], pools[1], *both, 1, win_t, sum_t, at, phi, mu,
        **SIZE))((pools[2], pools[3]))
    live = np.asarray(sum_t)[closing >= 0][:, :8 * (W // C // BS)].ravel()
    err = max(float(jnp.abs(g[1, live].astype(jnp.float32)
                            - w[1, live].astype(jnp.float32)).max())
              for g, w in zip(got, want)) if len(live) else 0.0
    ms = timed(call, (pools[2], pools[3]), calls)
    hbm_ms = eva_flops.window_bytes(MODEL, int((closing >= 0).sum())) \
        / peaks["hbm_bytes_per_s"] * 1e3
    line = {"case": "summarize", "closing": closing.tolist(),
            "ms_a_call": round(ms, 4), "hbm_ms": round(hbm_ms, 4),
            "max_abs_err_vs_xla": err}
    if empty_ms is not None:
        line["ms_over_empty"] = round(ms - empty_ms, 4)
        line["roofline_pct"] = round(100 * hbm_ms / (ms - empty_ms), 2)
    return line


CASES = {
    "decode": lambda p: [attention_case(
        "decode", [2 * W + 700, 4 * W + 1500, W + 90, 7 * W + 2000],
        [1, 1, 1, 1], p)],
    "mixed": lambda p: [attention_case(
        "mixed", [2 * W + 700, 4 * W + 1500, W + 90, 3 * W - 509],
        [1, 1, 1, 509], p)],
    "prefill-first": lambda p: [attention_case(
        "prefill-first", [0, 0, 0, 0], [512, 0, 0, 0], p)],
    "summarize": lambda p: summarize_cases(p),
}


def summarize_cases(peaks):
    empty = summarize_case([-1, -1, -1, -1], peaks)
    return [empty] + [summarize_case(c, peaks, empty["ms_a_call"])
                      for c in ([1, -1, -1, -1], [0, 1, 2, 3])]


def the_chip() -> dict:
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if jax.default_backend() != "tpu" or kind not in peaks:
        sys.exit(f"eva_kernels_alone: backend {jax.default_backend()!r}, "
                 f"device {kind!r}: not a TPU that benchmark/peaks.json "
                 f"names; this script measures the chip and nothing else")
    return {"device": kind, "backend": jax.default_backend(),
            "interpret": backend.interpret(), **peaks[kind]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="*", default=list(CASES))
    opts = ap.parse_args()
    chip = the_chip()
    assert not chip["interpret"], "the kernels would run in the interpreter"
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "eva_kernels_alone.jsonl"),
               "w")
    for line in [chip] + [r for c in opts.cases for r in CASES[c](chip)]:
        for f in (sys.stdout, out):
            print(json.dumps(line), file=f, flush=True)


if __name__ == "__main__":
    main()
