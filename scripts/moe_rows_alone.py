"""The routed experts' row mover ALONE on the chip, against what it replaced.

    chiprun -- python scripts/moe_rows_alone.py [--cells ...] [--steps ...]

For each MoE cell's decode and mixed step: one step's assignments (every
token picks ``top_k`` distinct experts uniformly), laid out as ``routed_ffn``
or ``_routed_ffn_share`` lays them out, and the time of one call of

``scatter``   the parent's ``zeros((M_pad, H)).at[positions].set(repeat(x))``
``kernel``    ``moe_rows``' kernel under the layout's ``src`` (``form`` says
              whether ``gather_rows`` takes it at this size, or scatters)
``xla``       the same gather as XLA writes it (``moe_rows._rows_xla``)
``src``       ``grouped_matmul.layout_sources`` (the argsort and its index
              arithmetic), which only the kernel's path pays
``layout``    ``tile_aligned_layout`` and the bincount, which both paths pay
``combine``   the combine's ``ys[positions]``, left in XLA

each as the mean of ``calls`` calls inside ONE jitted loop whose every call
waits for the one before it (the host dispatches once), in us, beside what
the HBM allows for the rows moved.  The kernel's output is compared with the
scatter's over the rows the GEMMs read.  Lines are kept in
``chiprun_out/moe_rows_alone.jsonl``.  It stops on a host without a TPU that
``benchmark/peaks.json`` names.
"""

from __future__ import annotations

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

from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops.pallas import backend, moe_rows
from deepspeed_tpu.ops.pallas.grouped_matmul import (layout_sources,
                                                     tile_aligned_layout)

CELLS = {  # experts, held, top k, hidden, tokens of a decode and a mixed step
    "olmoe-decode-sat": (64, 64, 8, 2048, (32, 512)),
    "mellum2-code-sat": (64, 64, 8, 2304, (32, 512)),
    "nemotron3-chat-wide-sat": (128, 128, 6, 2688, (64, 512)),
    "glm52-ctx8k-sat": (256, 16, 8, 6144, (16, 512)),
    "kimilinear-reason-sat": (256, 32, 8, 2304, (48, 512)),
}


def the_chip() -> dict:
    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if jax.default_backend() != "tpu" or kind not in peaks:
        sys.exit(f"moe_rows_alone: backend {jax.default_backend()!r}, device "
                 f"{kind!r}: not a TPU that benchmark/peaks.json names")
    return {"device": kind, **peaks[kind]}


def timed(fn, *args, calls: int, repeats: int = 3) -> float:
    """us a call of ``fn(c, *args) -> array``: ``calls`` of them in one
    jitted loop, each fed a scalar the call before wrote (``c`` is 0, which
    the compiler cannot know)."""
    @jax.jit
    def many(c, *args):
        def body(_, c):
            y = fn(c, *args)
            first = y.reshape(-1)[0]
            return c + (first != first).astype(jnp.int32)

        return jax.lax.fori_loop(0, calls, body, c)

    many(jnp.int32(0), *args).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        many(jnp.int32(0), *args).block_until_ready()
        best = min(best, (time.perf_counter() - t0) / calls * 1e6)
    return best


def run(cell: str, step: str, peaks: dict, calls: int) -> dict:
    E, held, k, H, tokens = CELLS[cell]
    N = tokens[step == "mixed"]
    T = N * k
    rng = np.random.default_rng(0)
    picks = np.argsort(rng.random((N, E)), axis=1)[:, :k].reshape(-1)
    flat = jnp.asarray(picks, jnp.int32)
    share = held != E
    if share:
        tile_m, groups = dropless.share_tile_m(T, E, held), held + 1
        flat = jnp.where(flat < held, flat, held)
    else:
        tile_m, groups = dropless.moe_tile_m(T, E), E
    x = jax.random.normal(jax.random.PRNGKey(0), (N, H), jnp.bfloat16)

    def layout(c, flat):
        positions, tile_group, pad_sizes, M_pad = tile_aligned_layout(
            flat + c, groups, T, tile_m)
        counts = jnp.bincount(flat + c, length=groups)[:held]
        used = jnp.sum(-(-counts // tile_m)).astype(jnp.int32)
        return positions, tile_group, pad_sizes, used

    positions, tile_group, pad_sizes, used = jax.jit(layout)(
        jnp.int32(0), flat)
    M_pad = tile_group.shape[0] * tile_m
    local = flat < held
    at = jnp.where(local, positions, M_pad)

    def sources(c, flat):
        return layout_sources(
            flat + c, jnp.bincount(flat + c, length=groups)[:held],
            jnp.minimum(tile_group, held - 1), pad_sizes[:held], tile_m)

    src = jax.jit(sources)(jnp.int32(0), flat)
    src_tok = jnp.where(src >= 0, src // k, -1)
    block = moe_rows.block_rows(M_pad, tile_m, N, H, x.dtype)

    def scatter(c, x):
        return jnp.zeros((M_pad, H), x.dtype).at[at + c].set(
            jnp.repeat(x, k, axis=0), mode="drop")

    def kernel(c, x):
        # the kernel whatever the size (``gather_rows`` scatters a decode
        # step's assignments, as the parent did)
        return moe_rows._rows_pallas(x, src_tok + c, used, tile_m=tile_m,
                                     rows=block, interpret=False)

    def xla(c, x):
        return moe_rows._rows_xla(x, src_tok + c)

    want = jax.jit(scatter)(jnp.int32(0), x)
    got = jax.jit(kernel)(jnp.int32(0), x)
    live_rows = int(used) * tile_m
    same = bool(jnp.array_equal(want[:live_rows], got[:live_rows]))
    ys = jax.random.normal(jax.random.PRNGKey(1), (M_pad, H), jnp.bfloat16)

    def combine(c, ys):
        return ys[jnp.minimum(at + c, M_pad - 1)]

    n_local = int(local.sum())
    moved = n_local * H * 2
    t0 = time.perf_counter()
    line = {
        "cell": cell, "step": step, "tokens": N, "assignments": T,
        "local": n_local, "rows": M_pad, "h": H, "tile_m": tile_m,
        "tiles": int(tile_group.shape[0]), "used_tiles": int(used),
        "block_rows": block,
        "form": "scatter" if T < moe_rows._MIN_LIVE else "pallas",
        "equal_to_scatter": same,
        "scatter_us": timed(scatter, x, calls=calls),
        "kernel_us": timed(kernel, x, calls=calls),
        "xla_us": timed(xla, x, calls=calls),
        "src_us": timed(sources, flat, calls=calls),
        "layout_us": timed(lambda c, f: layout(c, f)[0], flat, calls=calls),
        "combine_us": timed(combine, ys, calls=calls),
        # the live rows read once and the used tiles written once
        "hbm_us": (moved + live_rows * H * 2) / peaks["hbm_bytes_per_s"] * 1e6,
    }
    line["timing_took_s"] = time.perf_counter() - t0
    return {key: round(v, 1) if isinstance(v, float) else v
            for key, v in line.items()}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--steps", nargs="*", default=["decode", "mixed"])
    ap.add_argument("--calls", type=int, default=50)
    opts = ap.parse_args()
    chip = the_chip()
    assert not backend.interpret(), "the kernel would give way to XLA"
    print(json.dumps(chip), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "moe_rows_alone.jsonl"),
              "a") as out:
        for cell in opts.cells:
            for step in opts.steps:
                line = run(cell, step, chip, opts.calls)
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
