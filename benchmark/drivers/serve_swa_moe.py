"""Driver ``serve_swa_moe``: driver ``serve_moe`` for a sparse-expert model
whose attention layers are of two kinds, sliding-window and global, with
RoPE parameters by kind (Mellum2-12B-A2.5B).

Everything ``serve_moe.run`` does is done by it, imported: the server, the
load generator, the spans, the trace reduced by kernel and scope name, the
served tokens' margins, the router compared directly, the count of kernel
fallbacks (which takes in the ring's ``kernel/paged_attention_window``
events).  What this configuration changes is handed to it for the run
(``serve_moe`` looks these six up in its own module when it runs):

* ``program_config``: the file's ``program`` key checked against the
  program's ``TransformerConfig``: the expert keys and what ``model_type``
  implies as ``serve_moe`` checks them, and beyond them the layer pattern,
  the window and every RoPE parameter of each kind.  The published
  ``intermediate_size`` is a dense width no layer of this model has
  (``program.unused``); the program's and the reference's is the width of one
  expert, ``moe_intermediate_size``.
* ``reference``: ``benchmark/reference/swa_moe_decoder.py``.
* ``make_params``: ``serve.make_params`` (no q/k norm to draw scales for).
* ``tap_logits``: through ``DonatedLogitTap``, which holds no second copy of
  the pools.
* ``check_logits``: the sample has a prompt of thousands of tokens; the
  reference reads the whole of it and its head only the last positions.
* ``HERE``: where ``serve_moe.run`` finds the generator it starts as a child.
  Here that is ``benchmark/ordered_start/loadgen.py``: ``benchmark/loadgen.py``
  with a closed loop's clients starting in client order (``start_gap_s`` of
  the traffic file), because this cell's tokens a second follow the order in
  which the first prompts arrive and that order is otherwise a race.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, Mapping, Tuple
from unittest import mock

import numpy as np

from benchmark import common
from benchmark.drivers import serve, serve_moe
from benchmark.reference import swa_moe_decoder as reference

#: the directory of the generator ``serve_moe.run`` starts for this driver
ORDERED_START = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ordered_start")

#: what the reference reads of the file, beyond ``common.PUBLISHED``
MODEL_KEYS = ("head_dim", "hidden_act", "layer_types", "rope_parameters",
              "num_experts", "num_experts_per_tok", "norm_topk_prob",
              "moe_intermediate_size")


def program_config(config: Mapping[str, Any]):
    """→ (the program's configuration for this file, the published sizes as
    run, for the reference and the readers); refused if anything the file
    states differs from what the program's preset computes."""
    from deepspeed_tpu.models import transformer as tfm

    def refuse(what, said, gives):
        raise ValueError(f"configuration {config['name']}: the file says "
                         f"{what} = {said}, the program's preset gives "
                         f"{gives}")

    cfg = tfm.get_config(config["preset"], **config.get("overrides", {}))
    program = config["program"]
    model = {k: config[k] for k in (*common.PUBLISHED, *MODEL_KEYS)
             if k in config}
    for key in config.get("reduced", ()):
        model[key] = config["as_run"][key]
    model["layer_types"] = model["layer_types"][:model["num_hidden_layers"]]
    for key, attr in common.PUBLISHED.items():
        if key in model and key not in program["unused"] \
                and getattr(cfg, attr) != model[key]:
            refuse(key, model[key], getattr(cfg, attr))
    for key, attr in program["published"].items():
        if getattr(cfg, attr) != config[key]:
            refuse(key, config[key], getattr(cfg, attr))
    for attr, value in program["implied"].items():
        if getattr(cfg, attr) != value:
            raise ValueError(
                f"configuration {config['name']}: model_type "
                f"{config['model_type']} needs {attr} = {value}, the "
                f"program's preset gives {getattr(cfg, attr)}")
    for key in program["must_be_off"]:
        if config.get(key):
            raise ValueError(f"configuration {config['name']}: {key} = "
                             f"{config[key]} is not something the program "
                             f"computes")
    kinds = program["layer_kinds"]  # published kind -> the program's
    if tuple(kinds[k] for k in model["layer_types"]) != cfg.layer_kinds:
        refuse("layer_types", model["layer_types"], cfg.layer_kinds)
    for kind, rope in config["rope_parameters"].items():
        have = cfg.rope_of(kinds[kind])
        if (rope["rope_type"] == "yarn") != bool(have.factor):
            refuse(f"rope_parameters.{kind}.rope_type", rope["rope_type"],
                   have)
        for key, attr in program["rope"].items():
            if key in rope and getattr(have, attr) != rope[key]:
                refuse(f"rope_parameters.{kind}.{key}", rope[key],
                       getattr(have, attr))
    # the width of ONE expert, under the name the reference's and the
    # readers' arithmetic (benchmark/moe_flops.py) knows it by
    model["intermediate_size"] = config["moe_intermediate_size"]
    return cfg, model


def published_model(cfg) -> Dict[str, Any]:
    """The other way: the published keys the reference reads, from a
    program configuration (the tier-1 tests and ``chip_smoke.py``, which
    start from a preset and have no file)."""
    kinds = {"sliding": "sliding_attention", "full": "full_attention"}

    def rope(kind):
        r = cfg.rope_of(kind)
        if not r.factor:
            return {"rope_type": "default", "rope_theta": r.theta}
        return {"rope_type": "yarn", "rope_theta": r.theta,
                "factor": r.factor, "beta_fast": r.beta_fast,
                "beta_slow": r.beta_slow, "attention_factor":
                    r.attention_factor,
                "original_max_position_embeddings":
                    r.original_max_position_embeddings}

    return dict(num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                rms_norm_eps=cfg.norm_eps, sliding_window=cfg.sliding_window,
                layer_types=[kinds[k] for k in cfg.layer_kinds],
                rope_parameters={kinds[k]: rope(k) for k in kinds},
                num_experts_per_tok=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk)


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]
               ) -> List[Tuple[List[int], List[int], list]]:
    """``serve_moe.tap_logits`` through the tap that donates the pools."""
    from benchmark.logit_tap_donated import DonatedLogitTap

    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    tap = DonatedLogitTap(engine)
    try:
        uids = [engine.put(p, max_new_tokens=check["logit_tokens"])
                for p in prompts]
        out = engine.generate_all(burst=1)  # step by step: the tapped path
    finally:
        tap.remove()
    return [(p, out[u][len(p):], tap.logits[u])
            for p, u in zip(prompts, uids)]


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """``serve_moe.check_logits`` for contexts of thousands of tokens: each
    sequence is padded to the next multiple of ``logit_pad`` (causal: the
    padding changes nothing; few lengths, few compilations), the reference
    reads all of it, and its head computes the positions from the prompt's
    last on only, which hold every tapped row.  The two bounds are ``serve_moe``'s: the median row for a
    systematic fault (a window off by one, YaRN on the wrong layers, the
    attention factor left out), the worst row for a local one (a freed block
    read, a wrong position)."""
    import jax.numpy as jnp

    pad = check["logit_pad"]
    errs, by_prompt = [], []
    for prompt, tokens, rows in tapped:
        n = len(prompt) + len(tokens)
        seq = np.zeros(-(-n // pad) * pad, np.int32)
        seq[:n] = prompt + tokens
        first = len(prompt) - 1  # the first tapped row reads this position
        want = np.asarray(reference.logits(params, model, jnp.asarray(seq),
                                           last=len(seq) - first))
        mine = [float(np.abs(row - want[pos - first]).max())
                for pos, row in rows]
        by_prompt.append(round(float(np.median(mine)), 4))
        errs += mine
    errs = np.asarray(errs)
    median, worst = float(np.median(errs)), float(errs.max())
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"])
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(p) for p, _, _ in tapped]}, median a prompt {by_prompt}); "
        f"|engine - reference| median {median:.4f} (allowed "
        f"{check['logit_tol_median']}), worst {worst:.4f} (allowed "
        f"{check['logit_tol']}); quartiles "
        f"{np.percentile(errs, [25, 50, 75, 90]).round(4).tolist()}")
    return {"rows": len(errs), "median": median, "worst": worst,
            "ok": bool(ok)}


def run(**kwargs) -> Dict[str, Any]:
    # serve_moe's module-level names rebound for this run, then put back (a
    # process may rehearse both drivers)
    with mock.patch.multiple(
            serve_moe, program_config=program_config, reference=reference,
            make_params=serve.make_params, tap_logits=tap_logits,
            check_logits=check_logits, HERE=ORDERED_START):
        return serve_moe.run(**kwargs)
