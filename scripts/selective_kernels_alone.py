"""The two Mamba-1 state updates alone on the chip, at the cell's shapes
(``jamba2-doc-long-sat``: d_inner 5120, 16 states a channel, 33 slots, 26
layers' states in one array): device time a call beside the XLA formulation
of the same mathematics and beside the HBM time of what the rows have to move
(``benchmark/selective_flops.py``, the yardstick of ``sel_scan_roofline_pct``
and ``sel_decode_roofline_pct``), and the largest difference from the oracle
(the recurrence a token at a time) on the same operands.

    chiprun -- python scripts/selective_kernels_alone.py [--cases scan decode conv]

* ``scan``: a step of 512 tokens holding one long row (499 tokens from its
  slot's state behind 13 rows of one token, which the scan leaves alone),
  then a fresh row of 300 beside a row of 199 carried from the first call's
  state (rows whose lengths are no multiple of the block);
* ``decode``: 16 rows of one token over the 33 slots, one of them fresh;
* ``conv``: the mixed step's ragged causal conv alone
  (``ssm_hybrid.conv_ragged``, not a kernel: XLA's one pass over the step's
  tokens) at the three cells' ``(T, C, K, R)``, a row of 499 behind 13 rows of
  one token, beside the form it had before PR 59 (two ``(T, C)`` gathers a
  tap, kept in ``tests/served_kinds.py``): microseconds a call of each,
  whether the two agree bit for bit on the step's real tokens and on the kept
  columns, and float32 operands once (the one-hot product's ``HIGHEST``).

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/selective_kernels_alone.jsonl``, the device's line first.
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

from benchmark import selective_flops
from deepspeed_tpu.ops.pallas import selective_scan as ss

DI, N, S1, LAYERS, T = 5120, 16, 33, 26, 512
MODEL = {"hidden_size": 2560, "mamba_expand": 2, "mamba_d_state": N,
         "mamba_dt_rank": 160}


def inputs(key, n):
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (n, DI)).astype(jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (n, DI)) - 4.0)
    B = jax.random.normal(ks[2], (n, N))
    C = jax.random.normal(ks[3], (n, N))
    return x, delta, B, C


def constants(key):
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, DI))
    return A, jax.random.uniform(key, (DI,), jnp.float32, 0.5, 1.5)


def timed(fn, state, layer, *args, repeats=5):
    """→ (y, the state after ONE call at ``layer``, milliseconds a call).
    Timed as a step program calls it: ONE program that walks the 26 layers'
    states, a call a layer (the host's dispatch is 0.3 ms a program here,
    the size of these kernels' time), the state donated and carried."""
    y, first = jax.block_until_ready(
        jax.jit(fn)(state + 0.0, layer, *args))

    def every_layer(state):
        def one(i, carry):
            state, acc = carry
            y, state = fn(state, i, *args)
            return state, acc + y[0, 0]

        return jax.lax.fori_loop(0, LAYERS, one, (state, jnp.float32(0)))

    program = jax.jit(every_layer, donate_argnums=(0,))
    run, _ = jax.block_until_ready(program(state + 0.0))
    t0 = time.perf_counter()
    for _ in range(repeats):
        run, acc = program(run)
    jax.block_until_ready(acc)
    return y, first, (time.perf_counter() - t0) / (repeats * LAYERS) * 1e3


def rows_of(lengths, slots, fresh):
    n = np.zeros(32, np.int32)
    n[:len(lengths)] = lengths
    s = np.full(32, S1 - 1, np.int32)
    s[:len(slots)] = slots
    f = np.zeros(32, bool)
    f[:len(fresh)] = fresh
    return (jnp.asarray(np.cumsum(n) - n, jnp.int32), jnp.asarray(n),
            jnp.asarray(s), jnp.asarray(f))


def oracle(state, layer, x, delta, A, B, C, D, start, n, slots, fresh):
    y = np.zeros((T, DI), np.float32)
    new = np.array(state[layer])
    walk = jax.jit(ss.selective_recurrence)
    for r in np.nonzero(np.asarray(n) >= 2)[0]:
        a, m, slot = int(start[r]), int(n[r]), int(slots[r])
        h0 = jnp.zeros((N, DI)) if bool(fresh[r]) else state[layer, slot]
        yy, h = walk(x[a:a + m], delta[a:a + m], A, B[a:a + m], C[a:a + m],
                     D, h0)
        y[a:a + m], new[slot] = np.asarray(yy), np.asarray(h)
    return y, new


def case_scan(peaks):
    key = jax.random.PRNGKey(2)
    state = jax.random.normal(key, (LAYERS, S1, N, DI), jnp.float32)
    A, D = constants(jax.random.fold_in(key, 9))
    layer = jnp.int32(3)
    kernel = ss.selective_scan

    def xla(state, layer, x, delta, *rest):
        xf = x.astype(jnp.float32)
        return ss._scan_xla(state, layer, delta, delta * xf, *rest)

    def xla_args(x, delta, B, C, rows):
        start, n, slots, fresh = rows
        return (x, delta, B, C, A, start, n, slots, fresh, n >= 2)

    twin = xla
    lines = []
    for name, lengths, slots, fresh in (
            ("one row of 499 behind 13 of one", [1] * 13 + [499],
             list(range(13)) + [20], [False] * 14),
            ("a fresh row of 300, a carried row of 199", [300, 199, 1],
             [5, 20, 7], [True, False, False])):
        x, delta, B, C = inputs(jax.random.fold_in(key, len(lines)), T)
        rows = rows_of(lengths, slots, fresh)
        start, n, slots_a, fresh_a = rows
        args = (x, delta, A, B, C, D, start, n, slots_a, fresh_a, n >= 2)
        want_y, want_state = oracle(state, 3, x, delta, A, B, C, D, start, n,
                                    slots_a, fresh_a)
        y, new, ms = timed(kernel, state, layer, *args)
        _, new_x, ms_x = timed(twin, state, layer,
                               *xla_args(x, delta, B, C, rows), repeats=1)
        many = np.asarray(n) >= 2
        tokens, nrows = int(np.asarray(n)[many].sum()), int(many.sum())
        least = selective_flops.least_s(
            selective_flops.scan_flops(MODEL, tokens),
            selective_flops.scan_bytes(MODEL, tokens, nrows), peaks)
        scale = float(np.abs(want_state).max())
        lines.append({
            "case": "scan", "rows": name, "ms": ms, "xla_ms": ms_x,
            "least_ms": least * 1e3, "share_pct": 100 * least * 1e3 / ms,
            "y_diff": float(np.abs(np.asarray(y) - want_y).max()),
            "y_size": float(np.abs(want_y).max()),
            "state_diff": float(np.abs(np.asarray(new[3]) - want_state).max())
            / scale,
            "xla_state_diff": float(
                np.abs(np.asarray(new_x[3]) - want_state).max()) / scale,
            "other_layer_kept": bool((new[2] == state[2]).all())})
        state = new
    return lines


def case_decode(peaks):
    key = jax.random.PRNGKey(3)
    state = jax.random.normal(key, (LAYERS, S1, N, DI), jnp.float32)
    A, D = constants(jax.random.fold_in(key, 9))
    layer = jnp.int32(5)
    x, delta, B, C = inputs(key, S1)
    active = jnp.zeros((S1,), bool).at[:16].set(True)
    fresh = jnp.zeros((S1,), bool).at[7].set(True)
    args = (x, delta, A, B, C, D, active, fresh)
    y, new, ms = timed(ss.selective_decode_update, state, layer, *args)

    def xla(state, layer, x, delta, A, B, C, D, active, fresh):
        xf = x.astype(jnp.float32)
        return ss._decode_update_xla(state, layer, delta, delta * xf, B, C,
                                     A, active, fresh)

    _, new_x, ms_x = timed(xla, state, layer, *args)
    diff = 0.0
    for r in range(S1):
        h0 = jnp.zeros((N, DI)) if bool(fresh[r]) else state[5, r]
        yy, h = ss.selective_recurrence(x[r:r + 1], delta[r:r + 1], A,
                                        B[r:r + 1], C[r:r + 1], D, h0)
        want = h if bool(active[r]) else state[5, r]
        diff = max(diff, float(jnp.abs(new[5, r] - want).max()))
        if bool(active[r]):
            diff = max(diff, float(jnp.abs(y[r] - yy[0]).max()))
    least = selective_flops.decode_update_bytes(MODEL, 16) \
        / peaks["hbm_bytes_per_s"]
    return [{"case": "decode", "rows": "16 live of 33 slots", "ms": ms,
             "xla_ms": ms_x, "least_ms": least * 1e3,
             "share_pct": 100 * least * 1e3 / ms, "diff": diff,
             "xla_state_diff": float(jnp.abs(new - new_x).max()),
             "other_layer_kept": bool((new[4] == state[4]).all())}]


#: the mixed step's conv in the three cells that run it: (cell, T, C, K, R)
CONV_SHAPES = (("jamba2-doc-long-sat", 512, 5120, 4, 32),
               ("kimilinear-reason-sat", 512, 12288, 4, 48),
               ("nemotron3-chat-wide-sat", 512, 6144, 4, 64))


def conv_us(fn, x, kept, p, meta, repeats=5):
    """Microseconds a call of ``fn`` (a ``conv_ragged``) timed as a step
    program calls it: ONE program of 26 calls, each on the one before's
    output (nothing to hoist), the host's dispatch paid once."""
    def every_layer(x, kept):
        def one(_, carry):
            return fn(*carry, p, *meta)

        return jax.lax.fori_loop(0, LAYERS, one, (x, kept))

    program = jax.jit(every_layer)
    jax.block_until_ready(program(x, kept))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = program(x, kept)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / (repeats * LAYERS) * 1e6


def case_conv(peaks):
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from served_kinds import conv_ragged_gather

    from deepspeed_tpu.models.ssm_hybrid import conv_ragged

    lines = []
    shapes = [s + (jnp.bfloat16,) for s in CONV_SHAPES]
    for cell, t, c, k, r, dtype in shapes + [CONV_SHAPES[0] + (jnp.float32,)]:
        key = jax.random.PRNGKey(c)
        x = jax.random.normal(key, (t, c)).astype(dtype)
        kept = jax.random.normal(jax.random.fold_in(key, 1),
                                 (r, k - 1, c)).astype(dtype)
        p = {"conv_w": jax.random.normal(jax.random.fold_in(key, 2), (k, c)),
             "conv_b": jax.random.normal(jax.random.fold_in(key, 3), (c,))}
        n = np.zeros(r, np.int32)
        n[:14] = [1] * 13 + [499]  # 512 tokens, none of them padding
        start = np.cumsum(n) - n
        row = np.minimum(np.searchsorted(np.cumsum(n), np.arange(t), "right"),
                         r - 1)
        meta = tuple(jnp.asarray(a, jnp.int32) for a in
                     (row, np.arange(t) - start[row], start, n))
        got, got_kept = jax.jit(conv_ragged)(x, kept, p, *meta)
        want, want_kept = jax.jit(conv_ragged_gather)(x, kept, p, *meta)
        once = t * c * x.dtype.itemsize * 2 / peaks["hbm_bytes_per_s"]
        lines.append({
            "case": "conv", "cell": cell, "shape": [t, c, k, r],
            "dtype": jnp.dtype(dtype).name,
            "us": conv_us(conv_ragged, x, kept, p, meta),
            "gather_us": conv_us(conv_ragged_gather, x, kept, p, meta),
            "read_write_once_us": once * 1e6,
            "out_identical": bool((got == want).all()),
            "kept_identical": bool((got_kept == want_kept).all())})
    return lines


CASES = {"scan": case_scan, "decode": case_decode, "conv": case_conv}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="+", default=["scan", "decode"],
                    choices=list(CASES))
    args = ap.parse_args()
    d = jax.devices()[0]
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        table = json.load(f)
    if d.platform != "tpu" or d.device_kind not in table:
        print(f"needs a TPU that benchmark/peaks.json names; found "
              f"{d.platform!r} ({d.device_kind})", file=sys.stderr)
        return 1
    peaks = table[d.device_kind]
    lines = [{"device": d.device_kind, "platform": d.platform}]
    for case in args.cases:
        lines += CASES[case](peaks)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           "selective_kernels_alone.jsonl"), "w") as f:
        for line in lines:
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
