"""How the bounds of ``glm-5.2-ep16-w8``'s on-chip checks were sized: the
program's step-program logits (``serve_latent_moe.tap_logits``) against the
right reference held to the program's selections and expert choices, against
the same reference left to its own (what near-ties of the 2,048th score and
of the 8th expert do), and against the reference's named wrong programs
(``latent_sparse_moe_decoder.FAULTS``), one fault each, through the very rows
the driver checks; and what ``check_indexer`` reads of the right program and
of the faults that are the indexer's (scores without ReLU, without the heads'
weights, in bfloat16, one key short, a key from the future).

    chiprun -- python3 benchmark/tests/glm52_wrong_programs.py \\
        <seed>[,<seed>...] [fault ...]

And how ``margin`` and ``served_min`` were: THE CELL ITSELF, run once as
``benchmark/run.py`` runs it, whose ``check_served`` then reads the same
served sequences (the warm-up's and the window's, at the window's lengths)
again under each named fault, through the driver's own ``served_readings``:

    chiprun -- python3 benchmark/tests/glm52_wrong_programs.py \\
        --served <seed> [fault ...]

Not a test (no ``test_`` name): it needs the chip and the published widths;
``tests/test_glm52.py`` holds the same at toy widths in float32 (``readings``
is shared).  The faults run on the first seed only.  PERF.md section 6 and
the configuration's ``check.why`` hold the readings.  (A wrong program here
is the REFERENCE with one thing changed, against the right program's logits:
the same distance as the wrong program against the right reference, and no
second engine to build.)"""
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import numpy as np  # noqa: E402

#: the faults ``check_indexer`` has to see (the logits are held to the
#: program's picks and cannot): a wrong pick is a fault of the PROGRAM's
#: selection, stood in for by the reference's selection under the fault
INDEXER_FAULTS = ("no_relu", "no_head_weights", "scores_bf16",
                  "one_key_short", "future_key", "rms_index_norm",
                  "far_keys_lost")


def readings(drv, reference, params, model, cfg, tapped, check, faults,
             log=print):
    """→ {name: (median, worst)} of the right program (held and left to its
    own) and of each fault of ``faults``; for the indexer's faults what
    ``check_indexer`` reads of picks made under the fault."""
    out = {}
    pad = check["logit_pad"]
    for name, fs, force in [("right", (), True), ("own", (), False)] + [
            (f, (f,), True) for f in faults if f not in INDEXER_FAULTS]:
        t0 = time.monotonic()
        errs, _ = drv.row_errors(params, model, tapped, pad, fs, force)
        out[name] = (float(np.median(errs)), float(errs.max()))
        log(f"{name}: {len(errs)} rows in {time.monotonic() - t0:.0f}s; "
            f"median {out[name][0]:.4f} worst {out[name][1]:.4f}")
    _, passes = drv.row_errors(params, model, tapped, pad)
    right = drv.check_indexer(params, model, cfg, tapped, passes, check, log)
    out["indexer"] = right
    for f in faults:
        if f not in INDEXER_FAULTS:
            continue
        # the picks a program with this fault would make: the reference's
        # own selections under the fault, along a pass held to the program's
        # experts
        import jax.numpy as jnp

        wrong = []
        for p, toks, rows, forced, picked in tapped:
            seq, n = drv._padded(p, toks, pad)
            held = np.full(forced.shape[:1] + (len(seq),) + forced.shape[2:],
                           -1, np.int32)
            held[:, :forced.shape[1]] = forced
            sel = np.stack([np.asarray(m)[:n, :n] for m in
                            reference.whole_pass(
                                params, model, jnp.asarray(seq), last=1,
                                faults=frozenset({f}),
                                forced=jnp.asarray(held))["picked"]])
            sel[:, n - 1] = False  # the last token was never read
            wrong.append((p, toks, rows, forced, sel))
        got = drv.check_indexer(params, model, cfg, wrong, passes, check, log)
        if f == "scores_bf16":  # what (1) reads of a program summing in bf16
            qi, w, ki, _ = passes[-1]["indexer"][0]
            dt = jnp.dtype(cfg.dtype)
            lo = np.asarray(reference.index_scores(
                qi.astype(dt).astype(jnp.float32), w,
                ki.astype(dt).astype(jnp.float32), frozenset({f})))
            hi = np.asarray(reference.index_scores(
                qi.astype(dt).astype(jnp.float32), w,
                ki.astype(dt).astype(jnp.float32)))
            got["score_rel"] = float(np.abs(lo - hi).max() / np.abs(hi).max())
            got["ok"] = got["ok"] and got["score_rel"] <= check["index_tol"]
        out[f] = got
        log(f"picks under {f}: {got}")
    return out


CELL = "glm52-ctx8k-sat"


def served_controls(seed: int, faults, seconds: float = 50.0) -> int:
    """``run.run_cell`` on the cell, with the driver's ``check_served``
    followed by the same reading under each of ``faults``; the result line
    as ``run.py`` prints it."""
    from benchmark import run

    load = run.load_module

    def load_with_controls(here, directory, name, what):
        mod = load(here, directory, name, what)
        if directory != "drivers":
            return mod
        right = mod.check_served

        def check_served(params, model, sequences, pad_to, margin, log):
            out = right(params, model, sequences, pad_to, margin, log)
            for f in faults:
                t0 = time.monotonic()
                got = mod.served_readings(params, model, sequences,
                                          mod._CHECK["logit_pad"], margin,
                                          (f,))
                log(f"served tokens under {f} "
                    f"({time.monotonic() - t0:.0f}s): "
                    f"{mod.served_summary(got)}")
            return out

        mod.check_served = check_served
        return mod

    run.load_module = load_with_controls
    result = run.run_cell(CELL, seed, seconds, trace=False)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


def main():
    if sys.argv[1] == "--served":
        sys.exit(served_controls(int(sys.argv[2]), sys.argv[3:]))
    from benchmark import common
    from benchmark.drivers import serve_latent_moe as drv
    from benchmark.reference import latent_sparse_moe_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config

    def log(m):
        print(f"[{time.monotonic():.1f}] {m}", flush=True)

    seeds = [int(s) for s in sys.argv[1].split(",")]
    faults = [a for a in sys.argv[2:] if a in reference.FAULTS]
    common.start_jax(log)
    config = json.load(open("benchmark/configs/glm-5.2-ep16-w8.json"))
    cfg, model = drv.program_config(config)
    eng, check = config["engine"], dict(config["check"])
    for i, seed in enumerate(seeds):
        params = drv.make_params(cfg, seed, eng["weight_bits"],
                                 eng["weight_group"])
        engine = InferenceEngineV2(cfg, params, V2Config(**eng["v2"]))
        t0 = time.monotonic()
        tapped = drv.tap_logits(engine, cfg, seed, check)
        log(f"seed {seed}: tapped in {time.monotonic() - t0:.1f}s; "
            f"{drv._NOTES}")
        del engine
        gc.collect()
        got = readings(drv, reference, params, model, cfg, tapped, check, (),
                       log)
        log(f"seed {seed}: {json.dumps(got, default=str)}")
        if i == 0 and faults:  # through the longest sequence's rows alone
            got = readings(drv, reference, params, model, cfg, tapped[:1],
                           check, faults, log)
            log(f"seed {seed}, faults, the first sequence: "
                f"{json.dumps(got, default=str)}")
        log(f"router: {drv.check_router(params, model, cfg, tapped, check, log)}")
        del params, tapped
        gc.collect()


if __name__ == "__main__":
    main()
