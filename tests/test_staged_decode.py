"""The next decode step's inputs are staged at the end of the step before
(ISSUE 38, ``engine._stage_next``): an engine that uses them serves, token for
token, what an engine serves whose staged fields are thrown away before every
step, over random interleavings of everything that can change the table
between two steps; and what feeds a decode step's program is always what a
fresh pack of the table reads at that moment."""

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference.v2.engine import (InferenceEngineV2, V2Config,
                                               adapter_target_shapes)
from deepspeed_tpu.models import transformer as tfm
from deepspeed_tpu.observability.trace import tracer

# sizes of this file's own (its programs are traced here)
_V2 = dict(max_tokens_per_step=20, max_seqs=5, block_size=8, num_blocks=96,
           max_blocks_per_seq=10, dtype="float32")
# name: (preset, what the engine is built with besides)
_MODELS = {
    "dense": ("tiny", {}),
    "moe": ("tiny-olmoe", {}),
    "windowed-two-pools": ("tiny-mellum2", {}),
    "state-slots": ("tiny-nemotron3", {}),
    "adapter-row": ("tiny", dict(adapter_slots=3, adapter_rank=2)),
}
_STEPS = 70


def _build(name):
    preset, over = _MODELS[name]
    cfg = tfm.get_config(preset, dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def build():
        eng = InferenceEngineV2(cfg, params, V2Config(**{**_V2, **over}))
        if over:
            rs = np.random.default_rng(5)
            L = cfg.num_layers
            eng.set_adapter_slot(1, {
                t: (rs.standard_normal((L, K, 2)).astype(np.float32),
                    rs.standard_normal((L, 2, N)).astype(np.float32))
                for t, (K, N) in adapter_target_shapes(cfg).items()})
        return eng

    return build


def _watch(eng, fed):
    """Hold what feeds every decode step's program to a fresh pack of the
    table at the moment of the call: a staged buffer that differs from it
    must never be the one that is used.  A step called ahead (ISSUE 50; its
    buffer holds promises where the token ids would stand) is called before
    its token ids reach the host: what the unpack program made of them is
    kept in ``fed["ahead"]`` and held, once ``step`` has returned, to what
    the table reads then (``_drive``)."""
    decode_fwd = eng._decode_fwd
    names = [f[0] for f in eng._decode_layout.fields]

    def decode(params, caches, tok, pos, tables, ctx, temps, rng, seeds,
               *adapter):
        want = eng._decode_layout.views(
            eng._pack_decode(eng.step_temperature))
        got = {"token_ids": tok, "position_ids": pos, "context_lens": ctx,
               "temps": temps, "seeds": seeds}
        if isinstance(tables, tuple):
            got["block_tables"], got["win_tables"] = tables
        else:
            got["block_tables"] = tables
        if adapter:
            got["row_adapter"] = adapter[1]
        assert sorted(got) == sorted(names)
        if (want["token_ids"] < 0).any():
            fed["ahead"] = np.asarray(got.pop("token_ids"))
        for name, arr in got.items():
            np.testing.assert_array_equal(
                np.asarray(arr).view(np.int32), want[name].view(np.int32),
                err_msg=name)
        fed["n"] += 1
        return decode_fwd(params, caches, tok, pos, tables, ctx, temps, rng,
                          seeds, *adapter)

    eng._decode_fwd = decode


def _drive(build, seed, adapters, staging):
    """``_STEPS`` steps and then the drain, and between every two of them
    what a broker can do to the table, drawn from ``seed`` and from the
    tokens served (the same for both engines while they serve the same):
    a ``put`` (pinned or inherited temperature, an adapter row), a
    ``cancel`` of a running or a waiting request, a stop token (the
    broker's: ``cancel`` of the request that just emitted one), another
    step-level ``temperature``; budgets run out by themselves.  Without
    ``staging`` the staged fields are thrown away before every step, so
    every decode step takes the fresh path; with ``staging`` None nothing
    is staged in the first place, so a windowed pool's blocks are opened
    where the parent opened them → the tokens of every step."""
    eng = build()
    if staging is None:  # nothing is ever staged: the order before ISSUE 38
        eng._stage_next = lambda temperature, sub: None
    fed = {"n": 0, "ahead": None}
    _watch(eng, fed)
    rs = np.random.default_rng(seed)
    served, live = [], []
    eng.step_temperature = 0.0
    n = 0
    while n < _STEPS or eng.running or eng.waiting or eng._ahead is not None:
        draw = rs.random(4)
        if n < _STEPS and draw[0] < (0.9 if not live else 0.22) \
                and len(live) < _V2["max_seqs"]:
            live.append(eng.put(
                rs.integers(1, 200, int(rs.integers(2, 34))).tolist(),
                max_new_tokens=int(rs.integers(3, 22)),
                temperature=[None, 0.0, 0.8][int(rs.integers(3))],
                seed=int(rs.integers(1 << 20)),
                adapter_slot=int(rs.integers(2)) if adapters else 0))
        if live and draw[1] < 0.07:
            eng.cancel(live.pop(int(rs.integers(len(live)))))
        if draw[2] < 0.12:
            eng.step_temperature = [0.0, 0.7, 1.1][int(rs.integers(3))]
        if not staging:
            eng._staged = None
        fed["ahead"] = None
        out = eng.step(temperature=eng.step_temperature)
        # a decode program went ahead exactly where one is under way now (a
        # mixed one is not watched here: ``tests/test_steps_in_flight.py``),
        # and ran on the tokens this step has just recorded
        assert (fed["ahead"] is None) == (eng._ahead is None
                                          or eng._ahead.kind != "decode")
        if fed["ahead"] is not None:
            np.testing.assert_array_equal(fed["ahead"], eng.table.next_tok)
        served.append(out)
        for uid, toks in out.items():
            if toks[-1] % 11 == 0 and uid in eng.running:  # a stop token
                eng.cancel(uid)
        live = [u for u in live if u in eng.running
                or any(s.uid == u for s in eng.waiting)]
        n += 1
    assert all(m.drained() for m in eng._managers)
    return eng, served, fed["n"]


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("name", list(_MODELS))
def test_staged_serves_what_fresh_serves(devices, name, seed):
    build = _build(name)
    tracer.clear()
    staging, served, fed = _drive(build, seed, name == "adapter-row", True)
    steps = [s.attrs for s in tracer.spans(name="engine/step")]
    _, want, fed_fresh = _drive(build, seed, name == "adapter-row", False)
    assert served == want
    decode = [a for a in steps if a["kind"] == "decode"]
    assert fed == fed_fresh == len(decode) >= 20
    use = [a["staged"] for a in decode]
    # the interleaving reaches all three paths, and both ways of losing a
    # staging
    assert (use.count("used") >= 7 and use.count("fresh") >= 1
            and use.count("ahead") >= 25)
    # (ISSUE 50, 54) every program called ahead, of either kind, is the next
    # call's step, and the cancels and stop tokens between two calls dropped
    # tokens of some
    ran = [a for a in steps if "ahead" in a]
    assert [a["ahead"] for a in ran[1:]] == [
        a["ahead_next"] for a in ran[:-1]]
    assert all((a["staged"] == "ahead") == a["ahead"] for a in decode)
    assert sum(a["ahead_dropped"] for a in ran) >= 2
    assert not any(a["ahead_dropped"] for a in ran if not a["ahead"])
    dropped = [a for a in steps if a.get("stage_discarded")]
    assert {"decode"} <= {a["kind"] for a in dropped} <= {"decode", "mixed"}
    size = staging._decode_layout.size * 4
    assert all(a["stage_bytes"] == size for a in dropped)
    assert all("staged" not in a for a in steps if a["kind"] != "decode")
    # a used step dropped nothing; every step ran on one copy
    assert not any(a.get("stage_discarded") for a in decode
                   if a["staged"] == "used")
    assert all(a["h2d_copies"] == 1 for a in steps if "device_ms" in a)
    # nothing runs now: an idle step drops what a last cancel left staged
    assert staging.step() == {} and staging._staged is None


@pytest.mark.parametrize("name", ["dense", "windowed-two-pools",
                                  "state-slots"])
def test_staged_serves_what_an_engine_that_never_stages_serves(devices,
                                                               name):
    """The order of events before ISSUE 38 (no ``_stage_next`` at all, so
    a windowed pool's next block is opened at the head of the step that
    writes it and never a step early) serves the same tokens and drains
    the same pools."""
    build = _build(name)
    tracer.clear()
    _, served, fed = _drive(build, 21, False, True)
    never, want, fed_never = _drive(build, 21, False, None)
    assert served == want and fed == fed_never
    assert never._staged is None
