"""``LogitTap`` for an engine whose pools fill the chip: the decode logits
come out of the step that is served, which donates the pools as the served
decode step does.

``benchmark/logit_tap.py`` calls a second program (``_decode_body`` jitted
without donation) on a decode step's inputs before the step itself; that
program copies both pools every call, and beside 9.5 GB of weights and 3.5 GB
of pools the copy does not fit.  Here the decode step is replaced, while the
tap is installed, by the same body and the same sampler with the logits as a
third output and ``donate_argnums=(1,)``: one compilation more, no copy.
The mixed step returns its logits anyway and is tapped as ``LogitTap`` taps
it.  Test and benchmark tooling, like the module it extends.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from benchmark.logit_tap import LogitTap
from deepspeed_tpu.inference.v2.engine import (InferenceEngineV2,
                                               _decode_body, _memo,
                                               _with_stats, sample_rows)


def build_decode_with_logits(model_cfg, v2):
    """``build_decode_forward``'s program with the float32 logits
    ``(max_seqs, vocab)`` behind its two outputs."""
    def decode_step(params, caches, token_ids, position_ids, block_tables,
                    context_lens, temps, rng, seeds):
        logits, caches, moe_stats = _decode_body(
            params, caches, token_ids, position_ids, block_tables,
            context_lens, model_cfg, v2)
        return (_with_stats(sample_rows(logits, temps, rng, seeds),
                            moe_stats), caches, logits)

    return _memo(("decode_with_logits", model_cfg, dataclasses.astuple(v2)),
                 lambda: jax.jit(decode_step, donate_argnums=(1,)))


class DonatedLogitTap(LogitTap):
    """``LogitTap`` whose decode step is the tapped one (see the module)."""

    def __init__(self, engine: InferenceEngineV2):
        super().__init__(engine)
        step = build_decode_with_logits(engine.model_cfg, engine.cfg)

        def tapped_decode(params, caches, *args):
            out, caches, logits = step(params, caches, *args)
            rows, t = np.asarray(logits), engine.table
            for r in np.nonzero(t.active)[0]:
                self._record(t.seq_at[int(r)].uid, int(t.ctx[r]), rows[r])
            return out, caches

        engine._decode_fwd = tapped_decode
