"""``DonatedLogitTap`` that also reads, out of the engine's step programs, the
experts every position was routed to, for a model whose routed layers hold A
SHARE of their experts and that has no learned selection of keys
(``benchmark/reference/linear_latent_moe_decoder.py``, ``forced``): what
``routing_tap.py`` is to a model that holds every expert (two stats before
the choices) and ``selection_tap.py`` to one that also picks keys.

While the tap is installed the engine runs the two step programs BUILT FOR ITS
MODEL'S CONFIG WITH ``moe_tap_choices`` SET: the same bodies, with each routed
layer's ``(rows x top-k)`` expert ids riding out behind the step's three MoE
stats in the int32 array the step fetches anyway.  The tap also notes each
sequence's state slot.  The served programs are put back when the tap is
removed.  Test and benchmark tooling, like the modules it extends.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from benchmark.logit_tap_donated import DonatedLogitTap
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2

_STATS = 3  # experts hit, rows max, local assignments


class HeldChoiceTap(DonatedLogitTap):
    """``self.choices[uid][position]``: int ``(routed layers, top-k)``, the
    experts used; ``self.slots[uid]``: the sequence's state slot."""

    def __init__(self, engine: InferenceEngineV2):
        self.choices: Dict[int, Dict[int, np.ndarray]] = {}
        self.slots: Dict[int, int] = {}
        #: steps tapped, by the program that ran them
        self.steps = {"mixed": 0, "decode": 0}
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
            self.steps["mixed"] += 1
            ids = np.asarray(out[3])[_STATS:].reshape(layers, -1, k)
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
            self.steps["decode"] += 1
            ids = np.asarray(out)[n + _STATS:].reshape(layers, n, k)
            for r, uid, position in rows:
                self.choices.setdefault(uid, {})[position] = ids[:, r]
            return out, caches

        engine._fwd, engine._decode_fwd = tapped_fwd, tapped_decode

    def forced(self, uid: int, length: int) -> np.ndarray:
        """``(routed layers, length, k)`` for the reference: the engine's
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
