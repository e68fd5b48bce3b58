#!/usr/bin/env python3
"""Size the limits of ``trinity-mini-ep8-train``'s ``check`` on the chip:

    chiprun -- python3 benchmark/tests/trinity_wrong_references.py <seed> [fault ...]

The cell's own comparison (``drivers/train_swa_moe``: ``build``,
``own_gradient``, ``reference_side``, ``checks_of``) once with the right
reference, once with each named fault of
``benchmark/reference/gated_swa_moe_trainer.py`` put in the reference's place
and once with each fault planted on the program's side (``state_unchanged``;
all of ``FAULTS`` and ``PROGRAM_FAULTS`` when none is named), against ONE
engine: its first step and the gradient of its loss function are computed
once.  Prints, a reference, every compared number beside its limit and the
names of those over it: the right reference has to pass and every fault has
to fail on at least one.  The last line is one JSON object; a copy goes to
``chiprun_out/trinity_wrong_references.<seed>.json``.
"""

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import numpy as np

    import deepspeed_tpu
    from benchmark import common
    from benchmark.drivers import train_swa_moe as drv
    from benchmark.reference import gated_swa_moe_trainer as reference

    seed = int(argv[0])
    named = argv[1:] or list(reference.FAULTS) + list(drv.PROGRAM_FAULTS)
    t_start = time.monotonic()

    def log(msg):
        print(f"[{time.monotonic() - t_start:7.1f}s] {msg}", file=sys.stderr,
              flush=True)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity-mini-ep8-train.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "steps-16384.json")) as f:
        traffic = json.load(f)
    common.start_jax(log)
    optimizer = drv.optimizer_of(config)
    cfg, params, spec, ds, topo = drv.build(config, seed)
    model = drv.model_of(config)
    first = drv.make_batch(seed, 0, traffic["rows"], traffic["seq_len"],
                           cfg.vocab_size)
    made = drv.fingerprint(params)
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=ds,
                                               topo=topo)
    spec.params = None
    del params
    gc.collect()
    bias_shape = (cfg.mlp_layer_types.count("sparse"), cfg.num_experts)
    moments = sum(1 for a in jax.tree.leaves(engine.state.opt_state)
                  if getattr(a, "shape", None) == bias_shape)
    out = engine.train_batch(engine.place_batch(first))
    first_step = {k: float(out[k]) for k in ("loss", "grad_norm",
                                             *drv.COUNTERS)}
    first_counts = np.asarray(out["moe_expert_counts"]).astype(np.int64)
    log(f"first step {first_step}")
    after = jax.device_get(engine.state.params)
    engine.state = None
    del engine, out
    gc.collect()
    cfg, params, spec, _, _ = drv.build(config, seed)
    spec.params = None
    assert drv.fingerprint(params) == made
    before = jax.device_get(params)  # what a state left unchanged hands over
    mine = drv.own_gradient(spec.loss_fn, params, first["input_ids"], log)
    result = {"seed": seed, "first_step": first_step, "references": {}}
    ok = True
    for faults in [()] + [(f,) for f in named]:
        planted = set(faults) & set(drv.PROGRAM_FAULTS)
        ref, grads, router, bias, ref_norm, updates = drv.reference_side(
            params, cfg, model, optimizer, first["input_ids"],
            frozenset(faults) - planted, mine,
            before if "state_unchanged" in planted else after, first_counts,
            moments, log)
        checks = drv.checks_of(config["check"], first_step, grads, router,
                               bias, ref, ref_norm, updates)
        over = sorted(k for k, (v, lim) in checks.items()
                      if not (np.isfinite(v) and v <= lim))
        name = faults[0] if faults else "right"
        result["references"][name] = {
            "loss": ref["loss"], "grad_norm": ref_norm, "checks": checks,
            "over": over}
        log(f"{name}: over their limits {over}")
        for k, (v, lim) in checks.items():
            log(f"    {k}: {v:.3e} (limit {lim})")
        ok = ok and (bool(over) == bool(faults))
        del ref, grads, updates
        gc.collect()
    result["ok"] = ok
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"trinity_wrong_references.{seed}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": ok, "seed": seed, "over": {
        k: v["over"] for k, v in result["references"].items()}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
