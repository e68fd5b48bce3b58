"""``DonatedLogitTap`` that also reads, out of the engine's step programs,
the experts every position was routed to AND the keys every query of a "full"
layer picked: what a comparison of logits needs for a model with a learned
selection of keys and a router, both of which tie under seeded random weights
(``benchmark/reference/latent_sparse_moe_decoder.py``: ``forced``,
``selected``).

While the tap is installed the engine runs the two step programs BUILT FOR
ITS MODEL'S CONFIG WITH ``moe_tap_choices`` AND ``dsa_tap`` SET: the same
bodies, with each routed layer's ``(rows x top-k)`` expert ids and each
"full" layer's picks (a bit a key, ``ops/pallas/latent_attention.pack_mask``)
riding out behind the step's three MoE stats in the int32 array the step
fetches anyway.  The engine reads its tokens and its stats where it always
did; the tap reads the rest.  The served programs are put back when the tap
is removed.  Test and benchmark tooling, like the modules it extends.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from benchmark.logit_tap_donated import DonatedLogitTap
from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2
from deepspeed_tpu.models import latent_sparse

_STATS = 3  # experts hit, rows max, local assignments


def unpack(words: np.ndarray, keys: int) -> np.ndarray:
    """int32 ``(..., W)`` of ``pack_mask`` → bool ``(..., keys)``: bit ``b``
    of word ``w`` is key ``32 w + b``.  Through the words' bytes, low byte
    first whatever the host's order (shifting int64 copies of a mixed step's
    512 x 3 x 544 words took 2.5 s a step on the sandbox's CPU)."""
    as_bytes = np.ascontiguousarray(words).astype("<i4", copy=False).view(
        np.uint8)
    return np.unpackbits(as_bytes, axis=-1, bitorder="little")[
        ..., :keys].view(bool)


class SelectionTap(DonatedLogitTap):
    """``self.choices[uid][position]``: int ``(routed layers, top-k)``, the
    experts used; ``self.picks[uid][position]``: bool ``(full layers,
    longest context)``, the keys picked."""

    def __init__(self, engine: InferenceEngineV2):
        self.choices: Dict[int, Dict[int, np.ndarray]] = {}
        self.picks: Dict[int, Dict[int, np.ndarray]] = {}
        #: steps tapped, by the program that ran them
        self.steps = {"mixed": 0, "decode": 0}
        self._served = (engine._fwd, engine._decode_fwd, engine.model_cfg)
        tapped_cfg = dataclasses.replace(
            engine.model_cfg, moe_tap_choices=True, dsa_tap=True)
        engine._fwd = programs.build_ragged_forward(tapped_cfg, engine.cfg)
        engine.model_cfg = tapped_cfg  # what the decode step is built for
        try:
            super().__init__(engine)  # the decode step with logits
        except Exception:
            engine._fwd = self._served[0]
            raise
        finally:
            engine.model_cfg = self._served[2]
        cfg, v2 = engine.model_cfg, engine.cfg
        routed = latent_sparse.layers_of(cfg, "S")
        fulls = latent_sparse.layers_of(cfg, "I")
        k, n = cfg.moe_top_k, v2.max_seqs
        keys = v2.max_blocks_per_seq * v2.block_size
        words = -(-keys // 32)
        mixed, decode = engine._fwd, engine._decode_fwd

        def split(extra, rows):
            ids = extra[:routed * rows * k].reshape(routed, rows, k)
            picks = unpack(extra[routed * rows * k:].reshape(
                fulls, rows, words), keys)
            return ids, picks

        def tapped_fwd(params, caches, *args):
            out = mixed(params, caches, *args)
            self.steps["mixed"] += 1
            ids, picks = split(np.asarray(out[3])[_STATS:],
                               v2.max_tokens_per_step)
            cursor = 0
            for seq, count in self._picks:
                at = self.choices.setdefault(seq.uid, {})
                sel = self.picks.setdefault(seq.uid, {})
                for j in range(count):
                    at[seq.seen_tokens + j] = ids[:, cursor + j]
                    sel[seq.seen_tokens + j] = picks[:, cursor + j]
                cursor += count
            return out

        def tapped_decode(params, caches, *args):
            t = engine.table
            rows = [(int(r), t.seq_at[int(r)].uid, int(t.ctx[r]))
                    for r in np.nonzero(t.active)[0]]
            out, caches = decode(params, caches, *args)
            self.steps["decode"] += 1
            ids, picks = split(np.asarray(out)[n + _STATS:], n)
            for r, uid, position in rows:
                self.choices.setdefault(uid, {})[position] = ids[:, r]
                self.picks.setdefault(uid, {})[position] = picks[:, r]
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

    def picked(self, uid: int, length: int) -> np.ndarray:
        """bool ``(full layers, length, length)``: the keys the engine's
        queries picked, nothing at the positions it did not compute."""
        at = self.picks[uid]
        layers = next(iter(at.values())).shape[0]
        out = np.zeros((layers, length, length), bool)
        for position, sel in at.items():
            out[:, position] = sel[:, :length]
        return out

    def remove(self) -> None:
        super().remove()
        self.engine._fwd, self.engine._decode_fwd = self._served[:2]
