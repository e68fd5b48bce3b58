"""The routed experts' W8A16 kernel alone on the chip, at the four MoE cells'
decode and mixed shapes (PERF.md section 5, "The grouped GEMM alone"): device
time a call of each expert matrix (the median duration of the custom call in
a profile of 30 chained calls; ``us_loop`` is the host clock round the lot
over the calls, which holds what lies between two calls too) beside the HBM
time of the codes and scales of the experts that got a row and of the
assignments' activations (what ``benchmark/moe_flops.py`` counts), under the
tile ``pick_grouped_tiles`` gives and under any other asked for.

    chiprun -- python scripts/grouped_gemm_alone.py [--tiles 640:2688 ...]

``--tiles`` runs every shape once more under each given ``tn:tk`` that divides
it (``0`` is the whole of N or K), in place of the picker's, and ``--chunk``
sets the columns a grid step dequantizes at a time (``_CHUNK_N``): the
measurements the tile rule was chosen from.  The assignments are drawn as a
seeded router over random weights spreads them (each token picks its experts
uniformly, without repeats), laid out by ``tile_aligned_layout`` as
``moe/dropless.py`` lays them, a share's (GLM-5.2: 16 of 256 experts held)
with the rows that live elsewhere last.

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/grouped_gemm_alone.jsonl``, the device's line first.
"""

import argparse
import dataclasses
import glob
import json
import os
import shutil
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import (backend, grouped_mixed_gemm as gmm,
                                      mixed_gemm)
from deepspeed_tpu.ops.pallas.grouped_matmul import tile_aligned_layout
from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                 dequantize_gemm_weight)

KERNEL = "grouped_mixed_gemm"
LAYERS = 2  # the stack the kernel indexes; a call reads one layer's experts

CELLS = {  # experts, held, top k, hidden, expert width as stored, group,
    # tokens of a decode and of a mixed step
    "olmoe-decode-sat": (64, 64, 8, 2048, 1024, 256, (32, 512)),
    "mellum2-code-sat": (64, 64, 8, 2304, 896, 128, (32, 512)),
    "nemotron3-chat-wide-sat": (128, 128, 6, 2688, 1920, 128, (64, 512)),
    "glm52-ctx8k-sat": (256, 16, 8, 6144, 2048, 128, (16, 512)),
}


def layout(rng, tokens, experts, held, top_k):
    """→ (tile_m, rows, tile_group, sizes, used tiles, experts hit, local
    assignments) of one step's assignments, as ``routed_ffn`` (all experts
    held) or ``_routed_ffn_share`` lays them out."""
    picks = np.argsort(rng.random((tokens, experts)), axis=1)[:, :top_k]
    flat = jnp.asarray(picks.reshape(-1), jnp.int32)
    T = tokens * top_k
    if held == experts:
        tile_m, groups = dropless.moe_tile_m(T, experts), experts
    else:
        tile_m, groups = dropless.share_tile_m(T, experts, held), held + 1
        flat = jnp.where(flat < held, flat, held)
    _, tile_group, sizes, rows = tile_aligned_layout(flat, groups, T, tile_m)
    counts = np.bincount(np.asarray(flat), minlength=groups)[:held]
    used = int((-(-counts // tile_m)).sum())
    return (tile_m, rows, jnp.minimum(tile_group, held - 1), sizes[:held],
            used, int((counts > 0).sum()), int(counts.sum()))


def device_us(run) -> float:
    """Median device time of the kernel's custom call, in us, over a profile
    of ``run()`` (``benchmark/trace_reduce.py`` reads the trace)."""
    from benchmark import common, kernel_time, trace_reduce

    session = common.TraceSession(lambda msg: None)
    try:
        session.start()
        try:
            run()
        finally:
            session.stop()
        (path,) = glob.glob(os.path.join(
            session.dir, "plugins", "profile", "*", "*.xplane.pb"))
        ops = trace_reduce.load(path).device_ops.get(0, ())
    finally:
        shutil.rmtree(session.dir, ignore_errors=True)
    calls = [e.end - e.start for e in ops
             if (op := trace_reduce.describe(e.name)).pallas
             and kernel_time.kernel_name(op.name) == KERNEL]
    if not calls:
        sys.exit(f"grouped_gemm_alone: no {KERNEL} custom call among the "
                 f"profile's {len(ops)} device operations")
    return statistics.median(calls) / 1e3


def run(cell, step, k, n, tiles, peaks, calls=30, repeats=3):
    experts, held, top_k, _, _, group, tokens = CELLS[cell]
    rng = np.random.default_rng(0)
    tile_m, rows, tile_group, sizes, used, hit, local = layout(
        rng, tokens[step == "mixed"], experts, held, top_k)
    picked = gmm.pick_grouped_tiles(rows, tile_m, k, n, 8, group)
    if tiles is not None:
        tn, tk = tiles[0] or n, tiles[1] or k
        if n % tn or k % tk or tk % group:
            return None
        picked = dataclasses.replace(
            picked, tn=tn, tk=tk, code_bytes_per_step=tn * tk,
            grid_steps=rows // tile_m * (n // tn) * (k // tk))
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    codes = jax.random.randint(key[0], (LAYERS, held, k, n), -127, 128,
                               jnp.int8)
    scales = jax.random.uniform(key[1], (LAYERS, held, k // group, n),
                                jnp.float32, 1e-4, 2e-4)
    # rows past an expert's real ones would be the layout's zeros: the time
    # does not depend on what the rows hold
    x = jax.random.normal(key[2], (rows, k), jnp.bfloat16)

    def kernel(x, codes, scales, used, layer):
        return gmm._grouped_pallas(
            x, codes, scales, tile_group, jnp.reshape(used, (1,)),
            jnp.reshape(layer, (1,)), picked, group)

    @jax.jit
    def many(x, codes, scales, used):
        # each call waits for the one before it (its tile count passes
        # through a value the call wrote): nothing overlaps
        def body(i, used):
            y = kernel(x, codes, scales, used, i % LAYERS)
            return used + (y[0, 0] != y[0, 0]).astype(jnp.int32)

        return jax.lax.fori_loop(0, calls, body, used)

    got = jax.jit(kernel)(x, codes, scales, jnp.int32(used), jnp.int32(1))
    w = dequantize_gemm_weight(QuantizedWeight(
        codes[1], scales[1], 8, group)).astype(x.dtype)
    want = jax.lax.ragged_dot(x, w, sizes)
    live = used * tile_m
    err = float(jnp.abs(got[:live].astype(jnp.float32)
                        - want[:live].astype(jnp.float32)).max())
    scale = float(jnp.abs(want[:live].astype(jnp.float32)).max())
    del w, want
    many(x, codes, scales, jnp.int32(used)).block_until_ready()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        many(x, codes, scales, jnp.int32(used)).block_until_ready()
        times.append((time.perf_counter() - t0) / calls * 1e6)
    # what the call requires, as benchmark/moe_flops.py counts it
    need = hit * (k * n + (k // group) * n * 4) + local * (k + n) * 2
    hbm_us = need / peaks["hbm_bytes_per_s"] * 1e6
    mxu_us = 2.0 * local * k * n / peaks["bf16_flops_per_s"] * 1e6
    us = device_us(
        lambda: many(x, codes, scales, jnp.int32(used)).block_until_ready())
    return {"cell": cell, "step": step, "e": held, "k": k, "n": n,
            "group": group, "tile_m": tile_m, "rows": rows,
            "tiles": len(tile_group), "used_tiles": used, "experts_hit": hit,
            "local": local, "tn": picked.tn, "tk": picked.tk,
            "k_tiles": k // picked.tk, "grid_steps": picked.grid_steps,
            "us_a_call": round(us, 1), "us_loop": round(min(times), 1),
            "hbm_us": round(hbm_us, 1), "mxu_us": round(mxu_us, 1),
            "roofline_pct": round(100 * max(hbm_us, mxu_us) / us, 2),
            "max_abs_err": err, "max_abs_out": scale}


def the_chip() -> dict:
    """The attached chip's kind and its peaks from ``benchmark/peaks.json``
    (the roofline shares are shares of THESE), or no measurement at all."""
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if jax.default_backend() != "tpu" or kind not in peaks:
        sys.exit(f"grouped_gemm_alone: backend {jax.default_backend()!r}"
                 f", device {kind!r}: not a TPU that benchmark/peaks.json "
                 f"names; this script measures the chip and nothing else")
    return {"device": kind, "backend": jax.default_backend(),
            "interpret": backend.interpret(), **peaks[kind]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", nargs="*", default=[])
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--steps", nargs="*", default=["decode", "mixed"])
    ap.add_argument("--chunk", type=int, default=mixed_gemm._CHUNK_N)
    opts = ap.parse_args()
    mixed_gemm._CHUNK_N = opts.chunk
    chip = the_chip()
    assert not chip["interpret"], "the kernels would run in the interpreter"
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "grouped_gemm_alone.jsonl"),
               "w")

    def say(line):
        for f in (sys.stdout, out):
            print(json.dumps(line), file=f, flush=True)

    say({**chip, "root": ROOT, "chunk": opts.chunk})
    for given in [None] + opts.tiles:
        tiles = given and tuple(map(int, given.split(":")))
        for cell in opts.cells:
            h, f = CELLS[cell][3:5]
            for step in opts.steps:
                for k, n in ((h, f), (f, h)):
                    try:
                        line = run(cell, step, k, n, tiles, chip)
                    except Exception as e:  # a tile the compiler refuses
                        line = {"cell": cell, "step": step, "k": k, "n": n,
                                "asked": given, "error": repr(e)[:300]}
                    if line:
                        say(line)


if __name__ == "__main__":
    main()
