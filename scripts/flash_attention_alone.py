"""The three flash attention kernels alone on the chip, at the training cells'
shapes (PERF.md section 6, PR 45 and 61): milliseconds of the forward, and of
the dQ and the dK/dV kernel by difference (the gradient for q alone, or for k
and v alone, leaves XLA the other backward kernel to drop), beside the MXU
time of the forward's products over the pairs the band keeps.  The clock is
the host's round a jitted call of the public op, so the (B, S, H, D) -> (B,
H, S, D) transposes and the launch are in it: a cell's trace reads the
kernels' own device time.

    chiprun -- python scripts/flash_attention_alone.py [--parent DIR] [--blocks 512 ...]

``--parent DIR`` times the module of another checkout (``git archive`` of a
commit, unpacked inside the repo) on the same operands and says whether the
two agree bit for bit at equal blocks (this tree's choice): the output, the
saved logsumexp and the three gradients.  ``--blocks N`` runs this tree's
kernels once more at blocks of N x N in place of ``pick_block``'s choice.

A measurement of the chip: without a TPU whose kind ``benchmark/peaks.json``
names it stops before the first run.  The lines go to the output and to
``chiprun_out/flash_attention_alone.jsonl``, the device's line first.
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp

MODULE = "deepspeed_tpu/ops/pallas/flash_attention.py"
SHAPES = {  # cell: batch, sequence, heads, kv heads, d_qk, d_v, scale, window
    "dsv2lite-train-8k": (2, 8192, 16, 16, 192, 128, 0.1147, 0),
    "train-1chip": (2, 2048, 32, 8, 128, 128, 128 ** -0.5, 4096),
    # Trinity-Mini's four window layers and its one full layer
    "trinity-train-16k": (1, 16384, 32, 4, 128, 128, 128 ** -0.5, 2048),
    "trinity-train-16k.full": (1, 16384, 32, 4, 128, 128, 128 ** -0.5, 0),
}


def load(name: str, root: str):
    """``MODULE`` of the checkout at ``root`` as a module of its own (its
    relative imports resolve against this tree's package)."""
    spec = importlib.util.spec_from_file_location(
        f"deepspeed_tpu.ops.pallas.{name}", os.path.join(root, MODULE))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def best_ms(fn, *args, n: int = 8):
    jax.block_until_ready(fn(*args))  # compiles
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


def run(mod, q, k, v, scale, window, blocks=0):
    """→ {forward, dq, dkv: ms} of ``mod``'s kernels."""
    if blocks:  # straight into the custom-VJP op, past ``pick_block``
        def attn(q_, k_, v_):
            t = lambda x: x.transpose(0, 2, 1, 3)
            return t(mod._flash_attention_bhsd(
                t(q_), t(k_), t(v_), None, None, None, scale, True, blocks,
                blocks, window))
    else:
        def attn(q_, k_, v_):
            return mod.flash_attention(q_, k_, v_, causal=True,
                                       sm_scale=scale, window=window)

    def loss(q_, k_, v_):
        return attn(q_, k_, v_).astype(jnp.float32).sum()

    fwd = best_ms(jax.jit(attn), q, k, v)
    with_dq = best_ms(jax.jit(jax.grad(loss, argnums=0)), q, k, v)
    with_dkv = best_ms(jax.jit(jax.grad(loss, argnums=(1, 2))), q, k, v)
    return {"forward_ms": fwd, "dq_ms": with_dq - fwd,
            "dkv_ms": with_dkv - fwd}


def picked_blocks(mod, q, k, v, scale, window):
    """The blocks ``mod``'s ``pick_block`` gives the call, off its event."""
    from deepspeed_tpu.observability.trace import tracer

    tracer.clear()
    jax.eval_shape(lambda *a: mod.flash_attention(
        *a, causal=True, sm_scale=scale, window=window), q, k, v)
    event = [s.attrs for s in tracer.spans()
             if s.name == "kernel/flash_attention_tiles"][-1]
    return event["block_q"], event["block_k"]


def outputs(mod, q, k, v, scale, window, block_q, block_k):
    """→ (out, lse, dq, dk, dv) of ``mod``'s three kernels at the blocks
    given, for the comparison bit for bit."""
    t = lambda x: x.transpose(0, 2, 1, 3)
    args = (None, None, None, scale, True, block_q, block_k, window)

    @jax.jit
    def both(q_, k_, v_):
        out, lse = mod._flash_fwd(t(q_), t(k_), t(v_), *args)
        weight = jnp.cos(jnp.arange(out.size, dtype=jnp.float32)).reshape(
            out.shape)  # a dO that differs from element to element
        grads = jax.grad(lambda *a: (mod._flash_attention_bhsd(
            *a, *args).astype(jnp.float32) * weight).sum(),
            argnums=(0, 1, 2))(t(q_), t(k_), t(v_))
        return (out, lse) + grads

    return jax.block_until_ready(both(q, k, v))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="another checkout to time beside this")
    ap.add_argument("--blocks", type=int, nargs="*", default=[])
    args = ap.parse_args()
    device = jax.devices()[0]
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if device.platform != "tpu" or device.device_kind not in peaks:
        sys.exit(f"no TPU that benchmark/peaks.json names: {device}")
    peak = peaks[device.device_kind]["bf16_flops_per_s"]
    out_path = os.path.join(ROOT, "chiprun_out", "flash_attention_alone.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    lines = [{"device": device.device_kind, "backend": jax.default_backend()}]
    print(json.dumps(lines[0]), flush=True)
    mods = [("this tree", load("flash_here", ROOT), 0)]
    mods += [(f"this tree, blocks {b}", mods[0][1], b) for b in args.blocks]
    if args.parent:
        mods.append(("parent", load("flash_parent", os.path.join(
            ROOT, args.parent)), 0))
    for cell, (b, s, h, kv, d_qk, d_v, scale, window) in SHAPES.items():
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16) for key, shape in zip(keys, (
                (b, s, h, d_qk), (b, s, kv, d_qk), (b, s, kv, d_v))))
        # the kept pairs' products: forward q k^T and p v
        w = window if 0 < window < s else s
        pairs = w * (w + 1) / 2 + (s - w) * w
        mxu_ms = b * h * pairs * 2 * (d_qk + d_v) / peak * 1e3
        for name, mod, blocks in mods:
            lines.append({"cell": cell, "kernels": name,
                          "forward_mxu_ms": mxu_ms,
                          **run(mod, q, k, v, scale, window, blocks)})
            print(json.dumps(lines[-1]), flush=True)
        if args.parent:
            bq, bk = picked_blocks(mods[0][1], q, k, v, scale, window)
            here, parent = (outputs(mod, q, k, v, scale, window, bq, bk)
                            for mod in (mods[0][1], mods[-1][1]))
            lines.append({"cell": cell, "blocks": [bq, bk],
                          "parent_bit_identical": {
                what: bool((a == b_).all()) for what, a, b_ in zip(
                    ("out", "lse", "dq", "dk", "dv"), here, parent)}})
            print(json.dumps(lines[-1]), flush=True)
    with open(out_path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
