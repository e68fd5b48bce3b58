"""``DonatedLogitTap`` that also reads, out of the engine's step programs, the
experts every position was routed to: what a comparison of logits needs for a
model whose seeded random router ties (``benchmark/reference/
ssm_moe_decoder.py``, ``forced``).

While the tap is installed the engine runs the two step programs BUILT FOR ITS
MODEL'S CONFIG WITH ``moe_tap_choices`` SET (``build_ragged_forward``, the
decode step with logits): the same bodies, with each MoE layer's ``(rows x
top-k)`` expert ids riding out behind the step's two MoE stats in the int32
array the step fetches anyway.  The engine reads its tokens and its two stats
where it always did; the tap reads the rest, and notes each sequence's state
slot.  The served programs (built for the config as served) are put back when
the tap is removed.  Test and benchmark tooling, like the modules it extends.
"""

from __future__ import annotations

from typing import Dict

import dataclasses

import numpy as np

from benchmark.logit_tap_donated import DonatedLogitTap
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2


class RoutedLogitTap(DonatedLogitTap):
    """``self.choices[uid][position]``: int array ``(MoE layers, top-k)``, the
    experts the engine's step programs used at that position."""

    def __init__(self, engine: InferenceEngineV2):
        self.choices: Dict[int, Dict[int, np.ndarray]] = {}
        self.slots: Dict[int, int] = {}  # uid -> the sequence's state slot
        self._served = (engine._fwd, engine._decode_fwd, engine.model_cfg)
        tapped_cfg = dataclasses.replace(engine.model_cfg,
                                         moe_tap_choices=True)
        engine._fwd = programs.build_ragged_forward(tapped_cfg, engine.cfg)
        engine.model_cfg = tapped_cfg  # what the decode step is built for
        try:
            super().__init__(engine)  # the decode step with logits
        except Exception:
            engine._fwd = self._served[0]
            raise
        finally:
            engine.model_cfg = self._served[2]
        layers, k = engine._moe_layers, engine.model_cfg.moe_top_k
        mixed, decode = engine._fwd, engine._decode_fwd
        n = engine.cfg.max_seqs

        def tapped_fwd(params, caches, *args):
            out = mixed(params, caches, *args)
            ids = np.asarray(out[3])[2:].reshape(layers, -1, k)
            cursor = 0
            for seq, count in self._picks:
                self.slots[seq.uid] = seq.state_slot
                at = self.choices.setdefault(seq.uid, {})
                for j in range(count):
                    at[seq.seen_tokens + j] = ids[:, cursor + j]
                cursor += count
            return out

        def tapped_decode(params, caches, *args):
            t = engine.table
            rows = [(int(r), t.seq_at[int(r)].uid, int(t.ctx[r]))
                    for r in np.nonzero(t.active)[0]]
            out, caches = decode(params, caches, *args)
            ids = np.asarray(out)[n + 2:].reshape(layers, n, k)
            for r, uid, position in rows:
                self.choices.setdefault(uid, {})[position] = ids[:, r]
            return out, caches

        engine._fwd, engine._decode_fwd = tapped_fwd, tapped_decode

    def forced(self, uid: int, length: int) -> np.ndarray:
        """``(MoE layers, length, k)`` for the reference: the engine's
        choices at the positions it computed, -1 past them."""
        at = self.choices[uid]
        layers, k = next(iter(at.values())).shape
        out = np.full((layers, length, k), -1, np.int32)
        for position, ids in at.items():
            out[:, position] = ids
        return out

    def remove(self) -> None:
        super().remove()
        self.engine._fwd, self.engine._decode_fwd = self._served[:2]
