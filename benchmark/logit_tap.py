"""Next-token logits of an engine's own step programs, for comparisons with a
reference (the tier-1 tests, the benchmark's ``correct`` phase).

The decode program samples in the graph and returns token ids, so its logits
are tapped one call earlier: ``build_decode_logits`` jits the very body the
decode step runs (``_decode_body``) without the sampler and without donating
the cache, and the tap calls it on a decode step's inputs before the step
itself.  The mixed step returns its logits; the tap reads the rows whose
prompt ends in the step.  So the mixed step's logits are the served
program's own, and the decode step's are those of a second compilation of the
same body on the same inputs.  Test and benchmark tooling: it reaches into
the engine's private attributes, which is why it lives here and not in the
product tree; an engine without a tap runs the programs it always ran.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import numpy as np

from deepspeed_tpu.inference.v2.engine import (InferenceEngineV2,
                                               _decode_body, _memo)


def build_decode_logits(model_cfg, v2):
    """The decode step up to its sampler: same arguments as far as
    ``context_lens``, → float32 logits ``(max_seqs, vocab)``."""
    def decode_logits(params, caches, token_ids, position_ids, block_tables,
                      context_lens):
        return _decode_body(params, caches, token_ids, position_ids,
                            block_tables, context_lens, model_cfg, v2)[0]

    return _memo(("decode_logits", model_cfg, dataclasses.astuple(v2)),
                 lambda: jax.jit(decode_logits))


class LogitTap:
    """While installed, every step of ``engine`` records, for each sequence
    that gets a token from it, ``(position of the last token read, float32
    logits (vocab,))`` under the sequence's uid.  Adapters and speculation
    are not tapped."""

    def __init__(self, engine: InferenceEngineV2):
        if engine.adapter_stack is not None or engine._spec_fwd is not None:
            raise ValueError("LogitTap: adapters and speculation not tapped")
        self.engine = engine
        self.logits: Dict[int, List[Tuple[int, np.ndarray]]] = {}
        self._saved = (engine._fwd, engine._decode_fwd, engine.builder.build)
        self._picks = None
        decode_logits = build_decode_logits(engine.model_cfg, engine.cfg)
        fwd, decode_fwd, build = self._saved

        def tapped_build(picks):
            self._picks = list(picks)
            return build(picks)

        def tapped_fwd(params, caches, *args):
            out = fwd(params, caches, *args)
            rows = np.asarray(out[0])
            for row, (seq, n) in enumerate(self._picks):
                if seq.seen_tokens + n >= seq.cur_len:
                    self._record(seq.uid, seq.cur_len - 1, rows[row])
            return out

        def tapped_decode(params, caches, *args):
            rows = np.asarray(decode_logits(params, caches, *args[:4]))
            t = engine.table
            for r in np.nonzero(t.active)[0]:
                self._record(t.seq_at[int(r)].uid, int(t.ctx[r]), rows[r])
            return decode_fwd(params, caches, *args)

        engine._fwd, engine._decode_fwd = tapped_fwd, tapped_decode
        engine.builder.build = tapped_build

    def _record(self, uid: int, position: int, row: np.ndarray) -> None:
        self.logits.setdefault(uid, []).append((position, row.copy()))

    def remove(self) -> None:
        e = self.engine
        e._fwd, e._decode_fwd, e.builder.build = self._saved
