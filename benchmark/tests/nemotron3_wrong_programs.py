"""How the bounds of ``nemotron3-nano-30b-w8``'s on-chip checks were sized:
the program's step-program logits (``serve_ssm_moe.tap_logits``) against the
right reference held to the program's routing choices, against the same
reference left to its own routing (what a router's near-ties do), and against
the reference's named wrong programs (``ssm_moe_decoder.FAULTS``), one fault
each, through the very rows the driver checks; the reference's wrong ROUTERS
(``ROUTER_FAULTS``) through the same rows' ``agree``; the tapped sequences'
slots of the engine's own state array against the reference pass's final
states, from the right program, from the reference with its state kept in
bfloat16, and from THE PROGRAM with its state rounded to bfloat16 at every
write-back (``bf16_state_engine``: the control of what a state array kept in
bfloat16 would read, with the share of state elements that hold low mantissa
bits); and the share of the sampled tokens that lie within 0.5 of each
reference's maximum under ITS OWN routing (``check_served``'s rule).

    chiprun -- python3 benchmark/tests/nemotron3_wrong_programs.py \\
        <seed>[,<seed>...] [fault ...] [bf16_state_engine]

Not a test (no ``test_`` name): it needs the chip and the published widths.
The faults run on the first seed only.  PERF.md section 6 and the
configuration's ``check.why`` hold the readings.  (A wrong program here is
the REFERENCE with one thing changed, against the right program's logits:
the same distance as the wrong program against the right reference, and no
second engine to build.  ``tests/test_nemotron3.py`` holds the same at toy
widths in float32.)"""
import gc, json, os, sys, time
from unittest import mock
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import numpy as np
import jax, jax.numpy as jnp
from benchmark import common
from benchmark.drivers import serve_ssm_moe as drv
from benchmark.reference import ssm_moe_decoder as reference
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import ssm_hybrid
from deepspeed_tpu.observability.trace import tracer

def log(m): print(f"[{time.monotonic():.1f}] {m}", flush=True)
def table(a): return [[float(f"{v:.2e}") for v in row] for row in a]

def rounding(update):
    """A state update whose layer is rounded to bfloat16 as it is written."""
    def wrapped(ssm, layer, *args, **kw):
        y, new = update(ssm, layer, *args, **kw)
        lay = jax.lax.dynamic_index_in_dim(new, layer, 0, keepdims=False)
        return y, jax.lax.dynamic_update_index_in_dim(
            new, jax.lax.reduce_precision(lay, 8, 7), layer, 0)
    return wrapped

def tapped_run(params, seed, low_state=False):
    if low_state:  # programs traced afresh, with both updates rounding
        programs._BUILD_CACHE.clear()
        patches = [mock.patch.object(programs, "ssm_decode_update",
                                     rounding(programs.ssm_decode_update)),
                   mock.patch.object(ssm_hybrid, "ssd_chunk_scan",
                                     rounding(ssm_hybrid.ssd_chunk_scan))]
        for p in patches: p.start()
    try:
        engine = InferenceEngineV2(cfg, params, V2Config(**eng["v2"]))
        t0 = time.monotonic()
        tapped = drv.tap_logits(engine, cfg, seed, check)
    finally:
        if low_state:
            for p in patches: p.stop()
            programs._BUILD_CACHE.clear()
    log(f"seed {seed}{' (state rounded to bfloat16 at every write)' if low_state else ''}: "
        f"tapped in {time.monotonic()-t0:.1f}s; {drv._SLOTS}; fallbacks "
        f"{[(s.name, s.attrs) for s in tracer.spans() if s.name.startswith('kernel/') and s.attrs.get('fallback')]}; "
        f"low mantissa bits in the tapped slots: {drv.low_bits_share(np.stack([t[4] for t in tapped]))}")
    del engine; gc.collect()
    return tapped

args = sys.argv[2:]
seeds = [int(s) for s in sys.argv[1].split(",")]
low_engine = "bf16_state_engine" in args
faults = [a for a in args if a in reference.FAULTS]
router_faults = [a for a in args if a in reference.ROUTER_FAULTS]
common.start_jax(log)
config = json.load(open("benchmark/configs/nemotron3-nano-30b-w8.json"))
cfg, model = drv.program_config(config)
eng = config["engine"]
check = dict(config["check"])
for i, seed in enumerate(seeds):
    params = drv.make_params(cfg, seed, eng["weight_bits"], eng["weight_group"])
    tapped = tapped_run(params, seed)
    first = i == 0
    for name, fs, force in [("right", (), True), ("right, own routing", (), False)] + ([(f, (f,), True) for f in faults] if first else []):
        t0 = time.monotonic()
        errs, agree, state = drv.row_errors(params, model, tapped, check["logit_pad"], fs, force)
        log(f"{name}: {len(errs)} rows in {time.monotonic()-t0:.0f}s; median {np.median(errs):.4f} p90 {np.quantile(errs,.9):.4f} worst {errs.max():.4f}; agree {agree.round(4).tolist()}; "
            f"slots against its final states, worst {state.max():.3e}, by sequence and Mamba layer {table(state)}")
    for f in router_faults if first else []:
        _, agree, _ = drv.row_errors(params, model, tapped, check["logit_pad"], router_faults=(f,))
        log(f"wrong router {f}: agree {agree.round(4).tolist()}")
    if first and low_engine:
        low = tapped_run(params, seed, low_state=True)
        errs, agree, state = drv.row_errors(params, model, low, check["logit_pad"])
        log(f"the program with its state rounded to bfloat16, against the right reference: median {np.median(errs):.4f} worst {errs.max():.4f}; agree {agree.round(4).tolist()}; "
            f"slots against its final states, worst {state.max():.3e}, {table(state)}")
        del low
    # served-token shares under the reference's own routing, right and wrong
    for name, fs in ([("right", ())] + [(f, (f,)) for f in faults]) if first and faults else []:
        within = exact = n_all = 0
        for prompt, tokens, *_ in tapped:
            n = len(prompt) + len(tokens)
            seq = np.zeros(-(-n // 256) * 256, np.int32); seq[:n] = prompt + tokens
            lg = np.asarray(reference.logits(params, model, jnp.asarray(seq), last=len(seq) - len(prompt) + 1, faults=frozenset(fs)))[:len(tokens)]
            under = lg.max(-1) - lg[np.arange(len(tokens)), tokens]
            within += int((under <= 0.5).sum()); exact += int((under == 0).sum()); n_all += len(tokens)
        log(f"served-like tokens under {name}: {exact}/{n_all} argmax, {within}/{n_all} within 0.5")
    del params, tapped; gc.collect()
