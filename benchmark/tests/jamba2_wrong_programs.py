"""How the bounds of ``jamba2-3b-bf16``'s on-chip checks were sized: the cell
is run ONCE as the driver runs it (``serve_selective.run``: the server, the
load, the window, the tap on the window's own mixed steps), and the rows and
states it kept are then compared with the reference's named wrong programs
(``selective_ssm_decoder.FAULTS``), one fault each, through the very
comparison the driver makes (``sequence_errors``): median and worst row, the
slots' states against the wrong program's final states; and the share of low
mantissa bits in the kept states beside that of the same states rounded to
bfloat16 (what a program keeping its state in bfloat16 would leave).

    chiprun -- python3 benchmark/tests/jamba2_wrong_programs.py <seed> \\
        [--seconds 30] [fault ...]

Not a test (no ``test_`` name): it needs the chip and the published widths.
PERF.md section 6 and the configuration's ``check.why`` hold the readings.
(A wrong program here is the REFERENCE with one thing changed, against the
right program's logits: the same distance as the wrong program against the
right reference, and no second engine to build.  ``tests/test_jamba2.py``
holds the same at toy widths in float32.)"""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import numpy as np
import jax.numpy as jnp
from benchmark import run as bench
from benchmark.drivers import serve_selective as drv
from benchmark.drivers.serve_ssm_moe import low_bits_share
from benchmark.reference import selective_ssm_decoder as reference

def log(m): print(f"[{time.monotonic():.1f}] {m}", flush=True)

args = sys.argv[1:]
seed = int(args[0])
seconds = float(args[args.index("--seconds") + 1]) if "--seconds" in args \
    else 30.0
faults = [a for a in args[1:] if a in reference.FAULTS] \
    or list(reference.FAULTS)
spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
cell = bench.by_name(spec["workloads"], "jamba2-doc-long-sat", "workload")
config = json.load(open(os.path.join(
    ROOT, "benchmark/configs/jamba2-3b-bf16.json")))
traffic = json.load(open(os.path.join(
    ROOT, "benchmark/traffic/doc-long-sat.json")))
device = bench.require_device(1)
drv.KEEP = {}
obs = drv.run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=False, device=device,
              t_ready=time.monotonic(), log=log)
kept, check = drv.KEEP, config["check"]
log(f"the run: correct {obs['correct']}, checks {obs['checks']}")
tapped = kept["tapped"]
limits = (check["logit_tol_median"], check["logit_tol"], check["state_tol"])
for name, fs in [("right", ())] + [(f, (f,)) for f in faults]:
    t0 = time.monotonic()
    rows, states = [], []
    for t in tapped:
        e, s = drv.sequence_errors(kept["params"], kept["model"], t,
                                   check["logit_pad"], fs)
        rows.append(e); states.append(s)
    rows, states = np.concatenate(rows), np.concatenate(states)
    got = (float(np.median(rows)), float(rows.max()), float(states.max()))
    fails = [n for n, v, lim in zip(("median", "worst", "state"), got, limits)
             if not v <= lim]
    log(f"{name}: {len(rows)} rows of {len(tapped)} sequences in "
        f"{time.monotonic() - t0:.0f}s; median {got[0]:.4f} worst "
        f"{got[1]:.4f} state {got[2]:.4f} (limits {limits}); fails {fails}; "
        f"margin over its nearest limit "
        f"{max(v / lim for v, lim in zip(got, limits)):.2f}x")
states = np.stack([t["state"] for t in tapped])
rounded = np.asarray(jnp.asarray(states).astype(jnp.bfloat16)
                     .astype(jnp.float32))
log(f"low mantissa bits: the kept states {low_bits_share(states)}, the same "
    f"rounded to bfloat16 {low_bits_share(rounded)} (at least "
    f"{check['state_low_bits_min']} asked)")
