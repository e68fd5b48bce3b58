"""The seam between the engines and the kinds of model they serve
(``inference/v2/programs.py``: ``ServedKind``, ``kind_of``): which kind a
preset is, what it caches against what a built engine holds, and the step
programs of one tiny model a body, pinned equation by equation."""

import collections
import json
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference.v2 import programs
from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, V2Config
from deepspeed_tpu.models import transformer as tfm

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("preset, kind", [
    ("tiny", programs.KV), ("mistral-7b", programs.KV),
    ("olmoe-1b-7b", programs.KV), ("mellum2-12b-a2.5b", programs.KV),
    ("nemotron3-nano-30b-a3b", programs.STATE), ("glm-5.2", programs.LATENT),
    ("deepseek-v2-lite", programs.LATENT), ("evabyte-6.5b", programs.EVA),
    ("tiny-evabyte", programs.EVA),
    ("kimi-linear-48b", programs.LINEAR_LATENT),
    ("tiny-kimi-linear", programs.LINEAR_LATENT),
    ("jamba2-3b", programs.STATE), ("tiny-jamba2", programs.STATE)])
def test_kind_of_names_the_kind(preset, kind):
    cfg = tfm.get_config(preset)
    assert programs.kind_of(cfg) is kind
    assert kind.counters in vars(InferenceEngineV2)
    if preset == "deepseek-v2-lite":  # no indexer: trained, not served
        with pytest.raises(NotImplementedError, match="trained, not served"):
            kind.arrays(cfg, V2Config())


def _cell(name):
    with open(os.path.join(os.path.dirname(HERE), "benchmark", "configs",
                           name + ".json")) as f:
        conf = json.load(f)
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in conf["overrides"].items()}
    return tfm.get_config(conf["preset"], **over), \
        V2Config(**conf["engine"]["v2"])


@pytest.mark.parametrize("cell, shapes, moe_layers, window_default", [
    ("mistral-7b-w8", {"k": (32, 800, 64, 8, 128)}, 32, None),
    ("olmoe-1b-7b-w8", {"k": (16, 416, 64, 16, 128)}, 16, None),
    ("mellum2-12b-w8", {"k": (5, 3000, 64, 4, 128),
                        "k_win": (15, 801, 64, 4, 128)}, 20, 1 + 32 * 25),
    ("nemotron3-nano-30b-w8", {"k": (2, 2048, 64, 2, 128),
                               "ssm": (7, 65, 64, 64, 128),
                               "conv": (7, 65, 3, 6144)}, 7, None),
    ("glm-5.2-ep16-w8", {"latent": (9, 4353, 64, 640),
                         "index": (3, 4353, 64, 128)}, 8, None),
    # two pools in every layer: 16 summary blocks a row at 16,384 bytes, a
    # whole window of 32 blocks a row, a scratch block each
    ("evabyte-6.5b-w8", {"k_sum": (32, 65, 64, 32, 128),
                         "k_win": (32, 129, 64, 32, 128)}, 0, 1 + 4 * 32),
    # 48 rows at 8,192 tokens of latent in the 7 latent layers, 49 slots of
    # 2 MiB of float32 state in each of the 20 KDA layers
    ("kimi-linear-48b-ep8-w8", {"latent": (7, 6145, 64, 640),
                                "kda": (20, 49, 32, 128, 128),
                                "conv": (20, 49, 3, 12288)}, 26, None),
    # the other recurrence of the STATE kind: no heads, the channels on the
    # lanes; 32 rows at 33,280 tokens of ONE K/V head in 2 layers
    ("jamba2-3b-bf16", {"k": (2, 16640, 64, 1, 128),
                        "ssm": (26, 33, 16, 5120),
                        "conv": (26, 33, 3, 5120)}, 0, None)])
def test_arrays_of_the_served_cells(cell, shapes, moe_layers,
                                    window_default):
    """What each served configuration caches at its cell's sizes, on shapes
    alone (``benchmark/configs/*.json``: ``sizing``)."""
    cfg, v2 = _cell(cell)
    kind = programs.kind_of(cfg)
    arrays = kind.arrays(cfg, v2)
    for name in list(shapes):  # V beside K, the window layers' beside both
        if name == "k" or name.startswith("k_"):
            shapes["v" + name[1:]] = shapes[name]
    assert {n: shape for n, (shape, _) in arrays.items()} == shapes
    assert {str(dt) for n, (_, dt) in arrays.items()
            if n not in ("ssm", "kda")} == {"bfloat16"}
    assert all(arrays[n][1] == jnp.float32 for n in ("ssm", "kda")
               if n in arrays)
    assert kind.moe_layers(cfg) == moe_layers
    if "k_win" in shapes:  # the default: what the rows hold at most, and one
        v2.num_window_blocks = 0
        assert kind.arrays(cfg, v2)["k_win"][0][1] == window_default


def _count(jaxpr, c):
    for e in jaxpr.eqns:
        c[e.primitive.name] += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _count(inner, c)
    return c


@pytest.mark.parametrize("name, preset, over, kind, cached", [
    ("tiny-mistral", "tiny", dict(num_kv_heads=2, tie_embeddings=False),
     programs.KV, "k v"),
    ("tiny-olmoe", "tiny-olmoe", {}, programs.KV, "k v"),
    ("tiny-mellum2", "tiny-mellum2", {}, programs.KV, "k v k_win v_win"),
    # "mixed" re-pinned by PR 59 here, in ``tiny-kimi-linear`` and in
    # ``tiny-jamba2``: the mixers' ragged conv (``ssm_hybrid.conv_ragged``)
    # reads the step's tokens through shifted slices and moves its rows by
    # one-hot products, no gather; every "decode" entry is the parent's
    ("tiny-nemotron3", "tiny-nemotron3", {}, programs.STATE,
     "k v ssm conv"),
    # re-pinned by PR 56: its rows of one token read their picks through the
    # paged decode kernel (interpret mode here), not through a gather
    ("tiny-glm52", "tiny-glm52", {}, programs.LATENT, "latent index"),
    # pinned by the PR that brought the kind (48): no parent had it
    ("tiny-evabyte", "tiny-evabyte", {}, programs.EVA,
     "k_sum v_sum k_win v_win"),
    # pinned by the PR that brought the kind (51): no parent had it
    ("tiny-kimi-linear", "tiny-kimi-linear", {}, programs.LINEAR_LATENT,
     "latent kda conv"),
    # pinned by the PR that brought the sub-layers (58): the STATE kind's
    # other recurrence, beside ``tiny-nemotron3`` which stays the parent's
    ("tiny-jamba2", "tiny-jamba2", {}, programs.STATE, "k v ssm conv")])
def test_step_programs_are_the_parents(name, preset, over, kind, cached):
    """The lock on the three bodies: the mixed and the decode step of one tiny
    model a body (and a shape of the first) count, primitive by primitive, the
    equations they counted on the parent of the PR that last re-pinned them
    (``parent_step_program_eqns.json``: counted there by this function; a
    fold of ROADMAP D2 or D14 re-pins its entries deliberately).  A built
    engine holds exactly the arrays its kind declares, asks it how many
    layers route, and a program names no scope of a body it does not run."""
    with open(os.path.join(HERE, "parent_step_program_eqns.json")) as f:
        pinned = json.load(f)[name]
    cfg = tfm.get_config(preset, dtype="float32", **over)
    v2 = V2Config(max_tokens_per_step=32, max_seqs=4, block_size=8,
                  num_blocks=64, max_blocks_per_seq=16, dtype="float32")
    e = InferenceEngineV2(cfg, tfm.init_params(jax.random.PRNGKey(0), cfg),
                          v2)
    assert e.kind is kind and set(e.caches) == set(cached.split())
    assert {n: (a.shape, a.dtype) for n, a in e.caches.items()} == \
        kind.arrays(e.model_cfg, v2)
    assert e._moe_layers == kind.moe_layers(cfg)
    assert (e.kv.slots is not None) == bool(kind.state) == \
        bool(e.total_state_slots)
    assert set(kind.state) <= set(e.caches)
    assert (e.kv_win is not None) == ("k_win" in e.caches)
    T, S = 32, 4

    def i32(*s):
        return jnp.zeros(s, jnp.int32)

    tables = i32(S, 16)
    if e.kv_win is not None:
        tables = (tables, i32(S, 16))
    programs_ = {
        "mixed": jax.make_jaxpr(e._fwd)(
            e.params, e.caches, i32(T), i32(T), i32(T), tables, i32(S),
            i32(S), i32(S), i32(S),
            *((None, None, i32(S)) if kind.state else ())),
        "decode": jax.make_jaxpr(e._decode_fwd)(
            e.params, e.caches, i32(S), i32(S), tables, i32(S),
            jnp.zeros(S, jnp.float32), jax.random.PRNGKey(0), i32(S))}
    scopes = {programs.STATE: ("ssm_", "sel_"),
              programs.LATENT: ("dsa_",),
              programs.EVA: ("eva_",),
              programs.LINEAR_LATENT: ("kda_",)}
    # what two kinds share: a latent pool, a shared expert
    shared = {"latent": (programs.LATENT, programs.LINEAR_LATENT),
              "moe_shared": (programs.STATE, programs.LINEAR_LATENT)}
    for step, jaxpr in programs_.items():
        assert dict(_count(jaxpr.jaxpr, collections.Counter())) == \
            pinned[step], step
        text = str(jaxpr)
        for other, names in scopes.items():
            if other is not kind:
                assert not any(n in text for n in names), (step, names)
        for n, kinds in shared.items():
            assert kind in kinds or n not in text, (step, n)


def test_another_kind_s_start_loads_nothing_of_eva():
    """A served model of another kind imports and traces nothing of
    ``ops/pallas/eva_attention.py`` (or ``models/eva.py``) at its start:
    every kernel body traced there is set-up its cell pays (PERF.md section
    6, PR 32).  In a process of its own: this one has loaded both."""
    import subprocess
    import sys

    code = (
        "import sys, jax\n"
        "from deepspeed_tpu.inference.v2.engine import InferenceEngineV2, "
        "V2Config\n"
        "from deepspeed_tpu.models import transformer as tfm\n"
        "cfg = tfm.get_config('tiny-mellum2', dtype='float32')\n"
        "e = InferenceEngineV2(cfg, tfm.init_params(jax.random.PRNGKey(0), "
        "cfg), V2Config(max_tokens_per_step=32, max_seqs=4, block_size=8, "
        "num_blocks=64, max_blocks_per_seq=16, dtype='float32'))\n"
        "e.put(list(range(1, 30)), max_new_tokens=3)\n"
        "e.generate_all(burst=1)\n"
        "print([m for m in sys.modules if m.endswith(('eva_attention', "
        "'models.eva'))])\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=os.path.dirname(HERE),
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "[]"
