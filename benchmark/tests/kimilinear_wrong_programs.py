"""What ``serve_linear_latent_moe``'s comparisons read for a right program and
for the reference with one named fault (``linear_latent_moe_decoder.FAULTS``:
equally, a program that computed that): the readings the limits
``check.logit_tol_median`` / ``logit_tol`` / ``state_tol`` were set from.

    chiprun -- python3 benchmark/tests/kimilinear_wrong_programs.py <seed> [fault ...]

builds ONE engine over the cell's configuration (no server), serves the
check's logit sample through its step programs with the tap on, and prints a
JSON line a reading: the median and the worst row of |engine - reference|
(the reference held to the program's expert choices), the first and the
worst KDA layer's |slot - reference| as a share of the state's largest entry,
the first KDA layer's state and the conv's kept inputs that THE SERVED
DECODE-ONLY PROGRAM left in its slot (``decode_state`` / ``decode_conv``;
the wrong programs of a step's edge there lose between decode steps, a token
each), and, of the sample's sequences read as served ones, the share of
tokens within ``check.margin`` of the reference's maximum under its OWN
routing.
``readings`` is also run in tier 1, at the rehearsal's toy size
(``tests/test_kimilinear_cell.py``), where every fault has to fail one of the
rehearsal's limits and the right program to pass them all.
"""

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: the faults that leave the KDA state as it is (the state check cannot see
#: them; the logits must)
NOT_OF_THE_STATE = ("rope_applied", "scale_from_nope", "held_left_out",
                    "q_unscaled")


#: the faults of the recurrence itself, which ``kda_direct`` reads (the others
#: change what the recurrence is fed, or another layer)
SCAN_FAULTS = ("decay_after_delta", "state_bf16", "state_lost", "stale_start")


def readings(config, seed: int, faults, log=lambda m: None,
             served: bool = True) -> dict:
    """{"right" | fault: {"median", "worst", "state", "served"}} for
    ``config`` (a configuration file's object) on one engine."""
    from benchmark.drivers import serve_latent_moe
    from benchmark.drivers import serve_linear_latent_moe as drv
    from benchmark.reference import linear_latent_moe_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
    from unittest import mock

    cfg, model = drv.program_config(config)
    eng, check = config["engine"], config["check"]
    params = drv.make_params(cfg, seed, eng["weight_bits"],
                             eng["weight_group"])
    engine = InferenceEngineV2(cfg, params, V2Config(**eng["v2"]))
    tapped = drv.tap_logits(engine, cfg, seed, check)
    assert engine.drained()
    del engine
    sample = serve_latent_moe._NOTES["decode_sample"]
    out = {}
    for name in ("right", *faults):
        if name != "right" and name not in reference.FAULTS:
            raise ValueError(f"unknown fault {name!r}")
        wrong = () if name == "right" else (name,)
        errs, states, _ = drv.row_errors(params, model, tapped,
                                         check["logit_pad"], wrong)
        line = {"seed": seed, "reading": name, "rows": len(errs),
                "median": float(np.median(errs)), "worst": float(errs.max()),
                "state_first": max(s[0] for s in states),
                "state": max(max(s) for s in states)}
        prompt, tokens = tapped[-1][:2]
        line["kda_direct"] = drv.kda_direct(
            params, model, cfg, (prompt + tokens)[:-1],
            [f for f in wrong if f in SCAN_FAULTS])
        decoded = drv.decode_sample_errors(params, model, sample, wrong)
        line.update(decode_state=decoded["state"],
                    decode_conv=decoded["conv"],
                    decode_conv_late=decoded["conv_late"],
                    decode_steps=[sample["steps"], sample["ahead"]])
        if name == "right":
            line["state_by_layer"] = [[round(x, 4) for x in s]
                                      for s in states]
        if served:  # the sample's sequences as served ones, read whole
            with mock.patch.object(serve_latent_moe, "reference", reference):
                got = serve_latent_moe.served_readings(
                    params, model, [(p, t) for p, t, *_ in tapped],
                    check["logit_pad"], check["margin"], wrong)
            line["served"] = min(g["within"] / g["tokens"] for g in got)
        out[name] = line
        log(json.dumps(line))
    return out


def main(argv) -> int:
    import jax

    from benchmark import common
    from benchmark.reference import linear_latent_moe_decoder as reference

    seed = int(argv[0])
    faults = argv[1:] or list(reference.FAULTS)
    common.start_jax(lambda m: print(m, file=sys.stderr))
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-ep8-w8.json")) as f:
        config = json.load(f)
    readings(config, seed, faults, log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
