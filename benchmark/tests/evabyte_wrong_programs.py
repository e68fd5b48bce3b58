"""What ``serve_eva``'s comparison of logits reads for a right program, for
the reference read each other way (``eva_byte_decoder.FAULTS``: equally, a
program that computed that), for the program's own roundings
(``ROUNDINGS``) and for the reference at the nearest precision below the
configuration's (``CONTROLS``: int8 codes rounded to 6 bits): the readings
the limits ``check.logit_tol_median`` / ``logit_tol`` were set from.

    chiprun -- python3 benchmark/tests/evabyte_wrong_programs.py <seed> [fault ...]

builds ONE engine over the cell's configuration (no server), serves the
check's logit sample through its step programs with the tap on, and prints a
JSON line a reading: the median and the worst row of |engine - reference|
over all eight heads; for a control also ``control_margin``, the reading
``check.margin`` has to call not correct: at the tapped positions, how far
the byte the CONTROL puts first under head 0 lies under the reference's own
maximum there (the served bytes' own margin is on the ``right`` line).
``readings`` is also run in tier 1, at the rehearsal's
toy size (``tests/test_evabyte_cell.py``), where every fault has to fail the
rehearsal's limits and the right program and its roundings to pass.
"""

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def readings(config, seed: int, faults, log=lambda m: None) -> dict:
    """{"right" | fault: (median, worst of the logit rows, median, worst of
    the summary chunks)} for ``config`` (a configuration
    file's object) on one engine."""
    from benchmark.drivers import serve_eva, serve_moe
    from benchmark.reference import eva_byte_decoder as reference
    from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config

    cfg, model = serve_moe.program_config(config)
    eng = config["engine"]
    params = serve_eva.make_params(cfg, seed, eng["weight_bits"],
                                   eng["weight_group"])
    engine = InferenceEngineV2(cfg, params, V2Config(**eng["v2"]))
    tapped = serve_eva.tap_logits(engine, cfg, seed, config["check"])
    assert engine.drained()
    del engine
    out, vocab = {}, cfg.vocab_size
    right = serve_eva.reference_rows(params, model, tapped)

    def margin(rows_of_bytes):
        """The worst gap by which the given bytes' head-0 logits lie under
        the right reference's maximum."""
        return max(float((want[:, :vocab].max(-1) - want[
            np.arange(len(want)), picked]).max())
            for want, picked in zip(right, rows_of_bytes))

    for name in ("right", *faults):
        if name not in ("right", *reference.FAULTS, *reference.ROUNDINGS,
                        *reference.CONTROLS):
            raise ValueError(f"unknown fault {name!r}")
        wanted = right if name == "right" else serve_eva.reference_rows(
            params, model, tapped, (name,))
        errs = np.asarray([float(np.abs(row - want).max())
                           for (_, _, rows), ref in zip(tapped, wanted)
                           for (_, row), want in zip(rows, ref)])
        out[name] = (float(np.median(errs)), float(errs.max()))
        chunks = serve_eva.summary_errors(
            params, model, tapped, () if name == "right" else (name,))
        line = {"seed": seed, "reading": name, "rows": len(errs),
                "median": out[name][0], "worst": out[name][1],
                "summary_chunks": len(chunks),
                "summary_median": float(np.median(chunks)),
                "summary_worst": float(chunks.max())}
        out[name] += (line["summary_median"], line["summary_worst"])
        if name == "right":  # the bytes the tapped program itself drew
            line["served_margin"] = margin(
                [[int(row[:vocab].argmax()) for _, row in rows]
                 for _, _, rows in tapped])
        if name in reference.CONTROLS:
            line["control_margin"] = margin(
                [ref[:, :vocab].argmax(-1) for ref in wanted])
        log(json.dumps(line))
    return out


def main(argv) -> int:
    import jax

    from benchmark import common
    from benchmark.reference import eva_byte_decoder as reference

    seed = int(argv[0])
    faults = argv[1:] or [f for f in (*reference.CONTROLS,
                                      *reference.ROUNDINGS,
                                      *reference.FAULTS) if f != "sliding"]
    common.start_jax(lambda m: print(m, file=sys.stderr))
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "evabyte-6.5b-w8.json")) as f:
        config = json.load(f)
    readings(config, seed, faults, log=lambda m: print(m, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
