"""How the bounds of ``mellum2-12b-w8``'s on-chip logit check were sized: the
right program and programs that are wrong in one thing each, through the very
check the driver runs (``serve_swa_moe.tap_logits`` on an engine's own step
programs, ``check_logits`` against the right reference over the same codes),
and the reference itself in the nearest precision below the configuration's.

    chiprun -- python3 benchmark/tests/mellum2_wrong_programs.py --seed <n>

Not a test (no ``test_`` name): it needs the chip and the published widths.
One line of JSON a program on standard output; PERF.md section 6 holds the
readings.  A wrong program is a ``TransformerConfig`` the engine would serve
(``tests/test_mellum2.py::WRONG`` holds the same five at toy widths, in
float32, where each fails by four orders of magnitude); ``top-7-of-8`` is
PR 27's yardstick for the expert path.  ``reference-8-bit-activations`` is
the plain reference with every block's output rounded to three bits of
mantissa (bfloat16 keeps seven): the nearest float format below the
bfloat16 the configuration states for activations.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def variants(cfg):
    yarn = cfg.rope_of("full")
    rep = dataclasses.replace
    return {
        "right": cfg,
        "yarn-on-every-layer": rep(
            cfg, rope_params=cfg.rope_params + (("sliding", yarn),)),
        "yarn-on-none": rep(cfg, rope_params=()),
        "no-attention-factor": rep(cfg, rope_params=(
            ("full", rep(yarn, attention_factor=1.0)),)),
        "window-off-by-one": rep(cfg, sliding_window=cfg.sliding_window + 1),
        "window-on-global-layers": rep(cfg, layer_types=("sliding",)),
        "window-on-no-layer": rep(cfg, layer_types=("full",)),
        "top-7-of-8": rep(cfg, moe_top_k=cfg.moe_top_k - 1),
    }


def low_precision_reference(reference, params, model, tokens, last):
    """``reference.logits`` with every block's output rounded to 3 bits of
    mantissa."""
    import jax
    import jax.numpy as jnp

    x = params["embed"]["tokens"][tokens].astype(jnp.float32)
    for i in range(model["num_hidden_layers"]):
        kind = model["layer_types"][i]
        x, _, _ = reference.layer(
            x, reference.layer_weights(params, i),
            heads=model["num_attention_heads"],
            kv_heads=model["num_key_value_heads"],
            head_dim=model["head_dim"], eps=float(model["rms_norm_eps"]),
            window=(int(model["sliding_window"])
                    if kind == "sliding_attention" else 0),
            rope=reference._rope_key(model["rope_parameters"][kind]),
            top_k=model["num_experts_per_tok"],
            norm_topk=bool(model["norm_topk_prob"]))
        x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)
    return reference.head_logits(x[-last:], params["final_norm"]["scale"],
                                 params["lm_head"]["w"],
                                 eps=float(model["rms_norm_eps"]))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import common
    from benchmark.drivers import serve, serve_swa_moe
    from benchmark.reference import swa_moe_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config

    def log(msg):
        print(f"[{time.monotonic():8.1f}] {msg}", file=sys.stderr, flush=True)

    common.start_jax(log)
    with open(os.path.join(ROOT, "benchmark/configs/mellum2-12b-w8.json")) as f:
        config = json.load(f)
    cfg, model = serve_swa_moe.program_config(config)
    eng, check = config["engine"], config["check"]
    params = serve.make_params(cfg, args.seed, eng["weight_bits"],
                               eng["weight_group"])
    jax.block_until_ready(params)
    only = [n for n in args.only.split(",") if n]
    right_tapped = None
    for name, wrong in variants(cfg).items():
        if only and name not in only:
            continue
        v2 = dict(eng["v2"])
        if len(set(wrong.layer_kinds)) == 1:  # one pool of all 20 layers
            v2["num_blocks"] = 1200
        engine = InferenceEngineV2(wrong, params, V2Config(**v2))
        tapped = serve_swa_moe.tap_logits(engine, cfg, args.seed, check)
        free = engine.free_blocks == engine.total_blocks
        del engine
        gc.collect()
        if name == "right":
            right_tapped = tapped
        res = serve_swa_moe.check_logits(params, model, tapped, check, log)
        print(json.dumps({"program": name, "seed": args.seed,
                          "pools_free": free, **res}), flush=True)
    if right_tapped is not None:  # the reference against itself, rounded
        errs = []
        for prompt, tokens, rows in right_tapped:
            n, pad = len(prompt) + len(tokens), check["logit_pad"]
            seq = np.zeros(-(-n // pad) * pad, np.int32)
            seq[:n] = prompt + tokens
            last = len(seq) - (len(prompt) - 1)
            want = np.asarray(reference.logits(params, model,
                                               jnp.asarray(seq), last=last))
            low = np.asarray(low_precision_reference(
                reference, params, model, jnp.asarray(seq), last))
            errs += [float(np.abs(low[pos - len(prompt) + 1]
                                  - want[pos - len(prompt) + 1]).max())
                     for pos, _ in rows]
        print(json.dumps({"program": "reference-8-bit-activations",
                          "seed": args.seed, "rows": len(errs),
                          "median": float(np.median(errs)),
                          "worst": float(np.max(errs))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
