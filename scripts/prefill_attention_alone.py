"""The paged attention kernels alone on the chip, at the serving cells'
shapes: the prefill kernel on a typical mixed step's rows of each (PERF.md
section 5, "The prefill kernel alone") and the decode kernel on a decode
step's ("The decode kernel alone").  Device time a call beside the HBM time
of the K/V blocks the rows read (the prefill kernel: and the MXU time of the
(query, key) pairs they multiply), and the largest difference from the
blockwise XLA path on the same operands.

    chiprun -- python scripts/prefill_attention_alone.py [--tiles 8/128:2 ...]
        [--decode-tiles 4:3 2:2 ...] [--parent .bench_checkout/parent]

``--tiles`` runs every prefill mix once more under each given tiling (the
small and the big tile ``/``-separated, then ``:`` and the blocks a fetch) in
place of the picker's: the measurements ``pick_prefill_tiles`` was chosen
from.  ``--decode-tiles`` does the same for the decode kernel (blocks a
fetch ``:`` DMA slots; ``pick_decode_tiles``), and ``--parent`` times the
decode kernel of another checkout's ``paged_attention.py`` on the same
operands beside this one's (``git archive <commit>`` unpacked into a
directory ``.gitignore`` lists).  ``--kernels`` names which of ``prefill``
and ``decode`` run.

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run (on a CPU the kernel would run in the
Pallas interpreter and the times would mean nothing).  The lines go to the
output and to ``chiprun_out/prefill_attention_alone.jsonl``, the device's
line first.
"""

import argparse
import dataclasses
import functools
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas import backend, paged_attention as pa

D, BS, S = 128, 64, 32


def mix(rng, decode_rows, decode_ctx, chunks, t=512):
    """→ (budget, chunk_start, chunk_len) of ``decode_rows`` rows of one
    token at contexts drawn from ``decode_ctx`` and the prefill ``chunks``
    (start, length), in row order as the scheduler lays them: running rows
    first."""
    start = [int(c) - 1 for c in rng.integers(*decode_ctx, decode_rows)]
    rows = [(s, 1) for s in start] + list(chunks)
    rows += [(0, 0)] * (S - len(rows))
    cs, cl = (np.asarray(x, np.int32) for x in zip(*rows))
    assert cl.sum() <= t
    return t, cs, cl


MIXES = {  # cell: heads, kv heads, windows of its layer kinds, rows
    "chat-decode-sat": (32, 8, (0,), lambda r: mix(
        r, 30, (200, 700), [(0, 300), (0, 182)])),
    "doc-prefill-rate": (32, 8, (0,), lambda r: mix(
        r, 8, (500, 2000), [(512, 504)])),
    "olmoe-decode-sat": (16, 16, (0,), lambda r: mix(
        r, 30, (200, 700), [(0, 300), (0, 182)])),
    "mellum2-code-sat": (32, 4, (1024, 0), lambda r: mix(
        r, 14, (1100, 8000), [(2048, 498)])),
    # no cell's: a budget whose queries pass what a grid step holds (four
    # spans of 1,024 tokens), chat's decode rows beside chunks of a new prompt
    "budget-4096": (32, 8, (0,), lambda r: mix(
        r, 30, (200, 700), [(0, 3000), (0, 1000)], t=4096)),
}


def operands(rng, blocks, queries, heads, kv, layers):
    """-> (tables, q, k, v): each row's ``blocks`` scattered over pools that
    hold them and one block more, and ``queries`` queries, in bfloat16."""
    nb = int(blocks.sum()) + 1
    tables = np.zeros((len(blocks), int(blocks.max()) + 1), np.int32)
    ids = rng.permutation(nb - 1)
    at = 0
    for r, n in enumerate(blocks):
        tables[r, :n] = ids[at:at + n]
        at += n
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (queries, heads, D), jnp.bfloat16)
    k = jax.random.normal(key[1], (layers, nb, BS, kv, D), jnp.bfloat16)
    v = jax.random.normal(key[2], (layers, nb, BS, kv, D), jnp.bfloat16)
    return tables, q, k, v


def run(cell, window, peaks, calls=20):
    heads, kv, _, rows = MIXES[cell]
    rng = np.random.default_rng(0)
    T, cs, cl = rows(rng)
    blocks = -(-(cs + cl) // BS)
    layers = 2
    tables, q, k, v = operands(rng, blocks, T, heads, kv, layers)
    args = tuple(map(jnp.asarray, (tables, np.cumsum(cl) - cl, cs, cl)))
    kernel = functools.partial(pa.paged_prefill_attention, window=window)

    @jax.jit
    def many(q, k, v):  # each call reads the one before it: nothing overlaps
        return jax.lax.fori_loop(
            0, calls, lambda i, x: kernel(x, k, v, i % layers, *args), q)

    err = float(jnp.abs(
        jax.jit(kernel)(q, k, v, 1, *args).astype(jnp.float32)
        - jax.jit(functools.partial(pa._prefill_attention_xla, window=window))(
            q, k, v, 1, *args).astype(jnp.float32)).max())
    many(q, k, v).block_until_ready()
    t0 = time.perf_counter()
    many(q, k, v).block_until_ready()
    ms = (time.perf_counter() - t0) / calls * 1e3
    # what the rows require, as the benchmark's reader counts it
    first = np.maximum(cs - window + 1, 0) // BS if window else 0
    read = int((blocks - first)[cl > 0].sum())
    pos = np.concatenate([s + 1 + np.arange(n) for s, n in zip(cs, cl)])
    pairs = int((np.minimum(pos, window) if window else pos).sum())
    hbm_ms = read * 2 * BS * kv * D * 2 / peaks["hbm_bytes_per_s"] * 1e3
    mxu_ms = 4 * pairs * heads * D / peaks["bf16_flops_per_s"] * 1e3
    picked = pa.pick_prefill_tiles(T, heads, kv, D, BS, q.dtype)
    return {"cell": cell, "window": window, "t": T,
            "tiles": dataclasses.astuple(picked),
            "ms_a_call": round(ms, 4), "hbm_ms": round(hbm_ms, 4),
            "mxu_ms": round(mxu_ms, 4),
            "roofline_pct": round(100 * max(hbm_ms, mxu_ms) / ms, 2),
            "q_slots": int(picked.slots(cl).sum()), "tokens": int(cl.sum()),
            "max_abs_err_vs_xla": err}


def decode_rows(rng, rows, live, ctx):
    """-> context_lens of ``rows`` rows, the first ``live`` of them at
    contexts drawn from ``ctx`` and the rest without one."""
    lens = np.zeros(rows, np.int32)
    lens[:live] = rng.integers(*ctx, live)
    return lens


DECODE_MIXES = {  # cell: heads, kv heads, windows of its layer kinds, rows
    "chat-decode-sat": (32, 8, (0,), lambda r: decode_rows(
        r, 32, 32, (256, 705))),
    "olmoe-decode-sat": (16, 16, (0,), lambda r: decode_rows(
        r, 32, 32, (256, 705))),
    # the loaded open loop decodes about 6 rows of its 32 a step
    "doc-prefill-loaded": (32, 8, (0,), lambda r: decode_rows(
        r, 32, 6, (600, 2200))),
    "mellum2-code-sat": (32, 4, (1024, 0), lambda r: decode_rows(
        r, 32, 32, (1100, 8000))),
    # two attention layers of sixteen, 64 rows, 2 KV heads
    "nemotron3-chat-wide-sat": (32, 2, (0,), lambda r: decode_rows(
        r, 64, 64, (256, 705))),
}


def parent_kernels(checkout: str):
    """Another checkout's ``paged_attention.py`` as a module beside this
    one's (its relative imports read this checkout's ``backend`` and
    tracer)."""
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas.paged_attention_parent",
        os.path.join(checkout, "deepspeed_tpu", "ops", "pallas",
                     "paged_attention.py"))
    module = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run_decode(cell, window, peaks, kernels=pa, calls=100):
    heads, kv, _, rows = DECODE_MIXES[cell]
    rng = np.random.default_rng(0)
    ctx = rows(rng)
    n_rows, blocks, layers = len(ctx), -(-ctx // BS), 2
    tables, q, k, v = operands(rng, blocks, n_rows, heads, kv, layers)
    args = jnp.asarray(tables), jnp.asarray(ctx)
    kernel = functools.partial(kernels.paged_decode_attention, window=window)

    @jax.jit
    def many(q, k, v):  # each call reads the one before it: nothing overlaps
        return jax.lax.fori_loop(
            0, calls, lambda i, x: kernel(x, k, v, i % layers, *args), q)

    err = float(jnp.abs(
        jax.jit(kernel)(q, k, v, 1, *args).astype(jnp.float32)
        - jax.jit(functools.partial(pa._decode_attention_xla, window=window))(
            q, k, v, 1, *args).astype(jnp.float32)).max())
    many(q, k, v).block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        many(q, k, v).block_until_ready()
        times.append((time.perf_counter() - t0) / calls * 1e6)
    first = np.maximum(ctx - window, 0) // BS if window else 0
    read = int((blocks - first).sum())
    hbm_us = read * 2 * BS * kv * D * 2 / peaks["hbm_bytes_per_s"] * 1e6
    us = float(np.median(times))
    line = {"kernel": "decode", "cell": cell, "window": window,
            "rows": n_rows, "live": int((ctx > 0).sum()), "blocks": read,
            "us_a_call": round(us, 2), "us_min": round(min(times), 2),
            "hbm_us": round(hbm_us, 2),
            "hbm_pct": round(100 * hbm_us / us, 2),
            "max_abs_err_vs_xla": err}
    if hasattr(kernels, "pick_decode_tiles"):
        line["tiles"] = dataclasses.astuple(kernels.pick_decode_tiles(
            n_rows, heads, kv, D, BS, q.dtype))
    return line


def the_chip() -> dict:
    """The attached chip's kind and its peaks from ``benchmark/peaks.json``
    (the roofline shares are shares of THESE), or no measurement at all."""
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if jax.default_backend() != "tpu" or kind not in peaks:
        sys.exit(f"prefill_attention_alone: backend {jax.default_backend()!r}"
                 f", device {kind!r}: not a TPU that benchmark/peaks.json "
                 f"names; this script measures the chip and nothing else")
    return {"device": kind, "backend": jax.default_backend(),
            "interpret": backend.interpret(), **peaks[kind]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", nargs="*", default=[])
    ap.add_argument("--decode-tiles", nargs="*", default=[])
    ap.add_argument("--parent")
    ap.add_argument("--kernels", nargs="*", default=["prefill", "decode"])
    ap.add_argument("--cells", nargs="*",
                    default=list(dict.fromkeys([*MIXES, *DECODE_MIXES])))
    opts = ap.parse_args()
    chip = the_chip()
    assert not chip["interpret"], "the kernels would run in the interpreter"
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out",
                            "prefill_attention_alone.jsonl"), "w")

    def say(line):
        for f in (sys.stdout, out):
            print(json.dumps(line), file=f, flush=True)

    say(chip)
    picker = pa.pick_prefill_tiles
    for given in ([None] + opts.tiles) * ("prefill" in opts.kernels):
        if given:  # the picker's span, the given tiles
            sizes, kb = given.split(":")
            small, big = map(int, sizes.split("/"))
            pa.pick_prefill_tiles = lambda *a: dataclasses.replace(
                picker(*a), small=small, big=big, kb=int(kb))
        for cell in opts.cells:
            for window in MIXES.get(cell, (0, 0, ()))[2]:
                say(run(cell, window, chip))
    picker = pa.pick_decode_tiles
    sides = {"change": pa}
    if opts.parent:
        sides["parent"] = parent_kernels(opts.parent)
    for given in ([None] + opts.decode_tiles) * ("decode" in opts.kernels):
        if given:  # the picker's span, the given blocks a fetch and slots
            kb, slots = map(int, given.split(":"))
            pa.pick_decode_tiles = lambda *a: dataclasses.replace(
                picker(*a), kb=kb, slots=slots)
        for cell in opts.cells:
            for window in DECODE_MIXES.get(cell, (0, 0, ()))[2]:
                for side, kernels in sides.items():
                    if side == "change" or not given:
                        say({"side": side,
                             **run_decode(cell, window, chip, kernels)})


if __name__ == "__main__":
    main()
