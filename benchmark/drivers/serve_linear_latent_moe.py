"""Driver ``serve_linear_latent_moe``: driver ``serve_moe`` for a model of KDA
layers (a delta-rule matrix state a head under a gate a channel) beside
latent attention without an indexer and without positions, a leading dense
layer and A SHARE of its routed experts (Kimi-Linear-48B-A3B, ``model_type:
kimi_linear``).

Everything ``serve_moe.run`` does is done by it, imported: the server, the
load generator, the spans, the trace reduced by kernel and scope name, the
count of kernel fallbacks (which takes in the ring's ``kernel/kda_*`` and
``kernel/latent_attention_*`` events).  What this configuration changes is
handed to it for the run, as ``serve_latent_moe`` hands its own, and what
that driver already does for a latent model with held experts is ITS, with
this file's reference in its place:

* ``program_config``: the file's published keys (``program.published``), what
  ``model_type`` implies, the published 1-indexed lists of KDA and full
  attention layers against the program's pattern.
* ``reference``: ``benchmark/reference/linear_latent_moe_decoder.py``.
* ``make_params``: one jitted call that makes each stack (KDA, latent
  attention, dense FFN, routed FFN) a layer at a time; every norm's scale
  and the router's correction bias are drawn from the seed.
* ``tap_logits``: through ``benchmark/held_choice_tap.py`` (the pools
  donated; the experts every position was routed to read out of the step
  programs), and beside the logits THE KDA STATE the sample's sequences left
  in their slots.  Before the tap goes on, ``serve_decode_sample``: one
  request through the step programs AS SERVED, for what THE DECODE-ONLY
  PROGRAM (which the window runs nineteen steps in twenty, and the tapped
  sample never) left in its slot.
* ``check_logits``: against this reference HELD TO THE PROGRAM'S EXPERT
  CHOICES; and in it THE STATE: every KDA layer's state of the tapped
  sequences' slots against the reference's state after the same tokens,
  directly, and its low mantissa bits (the logits of a few positions cannot
  tell a float32 state from a bfloat16 one), the first KDA layer's state
  and the conv's kept inputs that the served decode program wrote
  (``decode_sample_errors``), and the arrays' types and widths as the file
  states them.
* ``TimedTraceSession``: when the traced interval began and ended on the
  spans' clock (``obs["traced_interval"]``): the readers count the work of
  the steps whose device time they divide by.
* ``check_router`` / ``check_served`` / ``pick_spread``: ``serve_latent_moe``'s.
* ``SCOPES``: the routed FFN's scopes, the KDA layer's and the latent
  attention's.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Mapping
from unittest import mock

import numpy as np

from benchmark.drivers import serve_latent_moe, serve_moe, serve_ssm_moe
from benchmark.drivers.serve_latent_moe import (_CHECK, _NOTES, ORDERED_START,
                                                ROUTER_BIAS_STD, _padded)
from benchmark.reference import linear_latent_moe_decoder as reference

#: the scopes the traced run reduces by (``ops/pallas/kda.py``,
#: ``ops/pallas/latent_attention.py``, ``models/kimi_linear.py``,
#: ``models/latent_sparse.py``, ``moe/dropless.py``)
SCOPES = ("kda_decode_update", "kda_chunk_scan", "kda_conv", "kda_gate_in",
          "kda_gate_out", "kda_in_proj", "kda_out_proj",
          "latent_attention_prefill", "latent_attention_decode_full",
          "latent_q_proj", "latent_kv_proj", "latent_absorb_q",
          "latent_absorb_o", "moe_shared", *serve_moe.MOE_SCOPES)


def program_config(config: Mapping[str, Any]):
    """→ (the program's configuration for this file, the published sizes as
    run, for the reference and the readers); refused if anything the file
    states differs from what the program's preset computes."""
    from deepspeed_tpu.models import kimi_linear
    from deepspeed_tpu.models import transformer as tfm

    def refuse(what, said, gives):
        raise ValueError(f"configuration {config['name']}: the file says "
                         f"{what} = {said}, the program's preset gives "
                         f"{gives}")

    program, as_run = config["program"], config["as_run"]
    cfg = tfm.get_config(config["preset"], **config.get("overrides", {}))
    for key, attr in program["published"].items():
        said = as_run[key] if key in config["reduced"] else config[key]
        if getattr(cfg, attr) != said:
            refuse(key, said, getattr(cfg, attr))
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
    lin = config["linear_attn_config"]
    mixers = reference.mixers(config)
    if tuple(mixers) != cfg.kda_pattern:
        refuse("linear_attn_config (kda_layers / full_attn_layers)",
               "".join(mixers), "".join(cfg.kda_pattern))
    for key, attr in (("num_heads", "kda_num_heads"),
                      ("head_dim", "kda_head_dim"),
                      ("short_conv_kernel_size", "kda_conv_kernel")):
        if lin[key] != getattr(cfg, attr):
            refuse(f"linear_attn_config.{key}", lin[key], getattr(cfg, attr))
    dense = config["first_k_dense_replace"]
    want = ("dense",) * dense + ("sparse",) * (cfg.num_layers - dense)
    if want != cfg.mlp_layer_types or config["moe_layer_freq"] != 1:
        refuse("first_k_dense_replace / moe_layer_freq", dense,
               cfg.mlp_layer_types)
    if cfg.moe_shared_size != config["num_shared_experts"] \
            * config["moe_intermediate_size"]:
        refuse("num_shared_experts x moe_intermediate_size",
               config["num_shared_experts"], cfg.moe_shared_size)
    if cfg.moe_first_expert != as_run["first_expert"]:
        refuse("first_expert", as_run["first_expert"], cfg.moe_first_expert)
    model = {k: config[k] for k in program["model_keys"]}
    model.update(published_names(cfg), vocab_size=cfg.vocab_size,
                 num_hidden_layers=cfg.num_layers,
                 dense_intermediate_size=config["intermediate_size"],
                 kda_layers=kimi_linear.layers_of(cfg, "K"),
                 latent_layers=kimi_linear.layers_of(cfg, "A"))
    return cfg, model


def published_names(cfg) -> Dict[str, Any]:
    """What the shared readers' and ``serve_latent_moe``'s arithmetic asks
    under ITS names: the experts THIS CHIP holds and one's width, the
    router's rule."""
    return dict(experts_held=cfg.experts_held,
                first_expert=cfg.moe_first_expert,
                num_experts_per_tok=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk,
                intermediate_size=cfg.expert_width,
                num_experts=cfg.experts_held)


def published_model(cfg) -> Dict[str, Any]:
    """The other way: the published keys the reference reads, from a program
    configuration (the tier-1 tests and ``chip_smoke.py``, which start from a
    preset and have no file)."""
    from deepspeed_tpu.models import kimi_linear

    where = {k: [i + 1 for i, m in enumerate(cfg.kda_pattern) if m == k]
             for k in "KA"}
    return dict(
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        vocab_size=cfg.vocab_size, num_attention_heads=cfg.num_heads,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        linear_attn_config=dict(
            kda_layers=where["K"], full_attn_layers=where["A"],
            num_heads=cfg.kda_num_heads, head_dim=cfg.kda_head_dim,
            short_conv_kernel_size=cfg.kda_conv_kernel),
        first_k_dense_replace=cfg.mlp_layer_types.count("dense"),
        num_experts_per_token=cfg.moe_top_k,
        moe_renormalize=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_routed_scaling,
        moe_intermediate_size=cfg.expert_width,
        dense_intermediate_size=cfg.intermediate_size,
        kda_layers=kimi_linear.layers_of(cfg, "K"),
        latent_layers=kimi_linear.layers_of(cfg, "A"),
        **published_names(cfg))


def draw_small_tensors(params, seed):
    """The tensors ``init_params`` leaves at a constant, drawn from ``seed``
    (an int or a PRNG key): every norm's scale uniform in [0.5, 1.5) (at 1 a
    norm read from the wrong layer, or left out, would hardly show), and the
    router's correction bias normal ``ROUTER_BIAS_STD``
    (``serve_latent_moe``'s reason)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed

    def draw(leaf, salt):
        return jax.random.uniform(
            jax.random.fold_in(key, salt), leaf.shape,
            jnp.float32, 0.5, 1.5).astype(leaf.dtype)

    lay = params["layers"]
    for i, at in enumerate((lay["K"]["ln1"], lay["K"]["kda"]["o_norm"],
                            lay["A"]["ln1"], lay["A"]["attn"]["kv_a_norm"],
                            lay["D"]["ln2"], lay["S"]["ln2"],
                            params["final_norm"])):
        at["scale"] = draw(at["scale"], 0x81 + i)
    rb = lay["S"]["moe"]["router_bias"]
    lay["S"]["moe"]["router_bias"] = (ROUTER_BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 0x8B), rb.shape, jnp.float32)
    ).astype(rb.dtype)
    return params


def make_params(cfg, seed: int, bits: int, group: int):
    """The whole parameter tree on the device, in the types it is served in,
    from one jitted call: each stack a layer at a time (``lax.map``), so the
    bf16 form of more than one layer never exists."""
    import jax

    from deepspeed_tpu.inference.quantization import quantize_model_params
    from deepspeed_tpu.models import kimi_linear
    from deepspeed_tpu.models import transformer as tfm

    # a model of two layers that has one layer in every stack
    two = dataclasses.replace(cfg, num_layers=2, kda_pattern=("K", "A"),
                              mlp_layer_types=("dense", "sparse"))

    def whole(key):
        k_rest, *k_kinds = jax.random.split(key, 5)
        params = tfm.init_params(k_rest, two)  # embedding, head, norm
        layers = {}
        for kind, k in zip(kimi_linear.KINDS, k_kinds):
            def layer(key, kind=kind):
                lay = jax.tree.map(
                    lambda a: a[0],
                    tfm.init_params(key, two)["layers"][kind])
                if bits:
                    lay = quantize_model_params(
                        {"layers": {kind: lay}}, bits=bits,
                        group=group)["layers"][kind]
                return lay

            layers[kind] = jax.lax.map(
                layer, jax.random.split(k, kimi_linear.layers_of(cfg, kind)))
        params["layers"] = layers
        return draw_small_tensors(params, key)  # no constant of the seed

    return jax.jit(whole)(jax.random.PRNGKey(seed))


def serve_decode_sample(engine, seed: int, check: Mapping[str, Any]
                        ) -> Dict[str, Any]:
    """ONE request through the step programs AS SERVED (no tap: the compiled
    mixed step for its prompt, then THE DECODE-ONLY PROGRAM, stepped as the
    broker's turn steps it, two steps in flight) → the tokens its slot has
    read (the last one sampled was never fed), what the FIRST KDA layer's
    slot then holds (the state ``(H, d_k, d_v)``, the conv's kept inputs
    ``(taps - 1, 3 H d_k)``; a slot given back is written by nobody until it
    is taken again), the decode-only steps run and how many of them were
    dispatched ahead.  ``decode_sample_errors`` compares it."""
    rng = np.random.default_rng([seed, 0xDEC0DE])
    prompt = rng.integers(1, engine.model_cfg.vocab_size,
                          size=check["decode_prompt"]).tolist()
    fast, ahead = engine.fast_steps, engine.ahead_steps
    engine.put(prompt, max_new_tokens=check["decode_tokens"])
    seq = engine.waiting[-1]
    slot = -1
    while not seq.done:
        engine.step()
        slot = max(slot, seq.state_slot)  # -1 again once it is given back
    return {"tokens": seq.tokens[:-1], "prompt": len(prompt),
            "state": np.asarray(engine.caches["kda"][0, slot]),
            "conv": np.asarray(engine.caches["conv"][0, slot], np.float32),
            "steps": engine.fast_steps - fast,
            "ahead": engine.ahead_steps - ahead}


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]) -> List[tuple]:
    """``serve_latent_moe.tap_logits`` through ``HeldChoiceTap``: → [(prompt,
    tokens, [(position, logits)], experts used ``(routed layers, positions,
    k)``, the KDA state its slot held after the sample ``(KDA layers, H,
    d_k, d_v)`` float32)].  The first prompt is longer than several steps'
    budgets and no multiple of the chunk, so its state and conv inputs are
    carried across mixed steps and the pieces end off the steps' edges.
    ``check["logit_filler"]`` (optional): ``serve_latent_moe``'s, one more
    prompt still prefilling while the compared sequences decode."""
    from benchmark.held_choice_tap import HeldChoiceTap

    before = engine.drained()
    _CHECK.update(check, cfg=cfg)
    _NOTES["decode_sample"] = serve_decode_sample(engine, seed, check)
    before = before and engine.drained()
    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    filler = rng.integers(1, cfg.vocab_size,
                          size=check.get("logit_filler", 0)).tolist()
    tap = HeldChoiceTap(engine)
    try:
        uids = [engine.put(p, max_new_tokens=check["logit_tokens"])
                for p in prompts]
        if filler:
            engine.put(filler, max_new_tokens=1)
        out = engine.generate_all(burst=1)  # step by step: the tapped path
    finally:
        tap.remove()
    engine.kv.check_consistency()
    _NOTES.update(ok=before and engine.drained(), steps=dict(tap.steps),
                  dtypes={k: str(v.dtype) for k, v in engine.caches.items()},
                  shapes={k: list(v.shape) for k, v in engine.caches.items()})
    # a slot is given back when its sequence ends and written by nobody
    # since (every sequence of the sample has a slot of its own)
    slots = [tap.slots[u] for u in uids]
    if len(set(slots)) != len(slots):
        raise RuntimeError(f"the sample's sequences shared a slot: {slots}")
    return [(p, out[u][len(p):], tap.logits[u], tap.forced(u, len(out[u])),
             np.asarray(engine.caches["kda"][:, s]))
            for p, u, s in zip(prompts, uids, slots)]


def row_errors(params, model, tapped, pad: int, faults=(), force=True):
    """Every tapped sequence against ONE pass of the reference held to the
    program's expert choices (``faults``: a named wrong program of it;
    ``force`` False: its own) → (largest |engine - reference| over the
    vocabulary a tapped row; a sequence at a time the largest |slot -
    reference| over each KDA layer's state as a share of the reference
    state's largest entry; a sequence at a time ``router_inputs``)."""
    import jax.numpy as jnp

    errs, states, passes = [], [], []
    for prompt, tokens, rows, forced, held_state in tapped:
        seq, n = _padded(prompt, tokens, pad)
        held = np.full(forced.shape[:1] + (len(seq),) + forced.shape[2:], -1,
                       np.int32)
        held[:, :forced.shape[1]] = forced
        first = len(prompt) - 1  # the first tapped row reads this position
        out = reference.whole_pass(
            params, model, jnp.asarray(seq), last=len(seq) - first,
            faults=frozenset(faults),
            forced=jnp.asarray(held) if force else None,
            # the last token sampled was never read: n - 1 tokens in the state
            length=n - 1)
        want = np.asarray(out["logits"])
        errs += [float(np.abs(row - want[pos - first]).max())
                 for pos, row in rows]
        states.append([float(np.abs(got - ref).max() / np.abs(ref).max())
                       for got, ref in zip(held_state, out["states"])])
        passes.append(out["router_inputs"])
    return np.asarray(errs), states, passes


def first_kda_layer(params, model, tokens):
    """→ (what the reference's first KDA layer reads of ``tokens``: the
    embedding through its norm, float32 ``(S, hidden)``; the layer's
    weights; its heads)."""
    import jax.numpy as jnp

    from benchmark.reference.dense_decoder import rms_norm

    w = reference.layer_weights(params, "K", 0)
    seq = jnp.asarray(tokens, jnp.int32)
    a = rms_norm(params["embed"]["tokens"][seq].astype(jnp.float32),
                 w["ln1"], float(model["rms_norm_eps"]))
    return a, w, model["linear_attn_config"]["num_heads"]


def decode_sample_errors(params, model, sample, faults=()
                         ) -> Dict[str, float]:
    """What ``serve_decode_sample``'s slot holds against the reference's
    first KDA layer after the same tokens (``faults``: a named wrong program
    of it; what ``state_lost`` / ``conv_lost`` lose, they lose here between
    DECODE steps, a token each: ``lost_every`` 1) → ``state``: the largest
    |slot - reference| as a share of the state's largest entry; ``conv``: the
    same of the conv's kept inputs (the last ``taps - 1`` tokens' ``h W_qkv``
    before the conv, oldest first); ``conv_late``: what a path that kept
    them one token late would read."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference.dense_decoder import dense_weight

    a, w, heads = first_kda_layer(params, model, sample["tokens"])
    _, want = reference.kda_layer(
        a, w, heads=heads, eps=float(model["rms_norm_eps"]),
        faults=frozenset(faults), lost_every=1)
    kept = sample["conv"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = np.asarray(a[-(kept + 1):] @ dense_weight(w["w_qkv"]))

    def share(got, ref):
        return float(np.abs(got - ref).max() / np.abs(ref).max())

    return {"state": share(sample["state"], np.asarray(want)),
            "conv": share(sample["conv"], x[1:]),
            "conv_late": share(sample["conv"], x[:-1])}


def kda_direct(params, model, cfg, tokens, faults=(), decoded: int = 3
               ) -> float:
    """THE RECURRENCE, compared as a function (what ``check_router`` is to
    the router): the program's two KDA paths (``kda_chunk_scan`` over all but
    the last ``decoded`` tokens, then ``kda_decode_update`` a token at a
    time, jitted here on the device as the step programs call them) against
    the reference's scan a token at a time (``faults``: a named wrong program
    of it), BOTH on the float32 inputs the reference's first KDA layer makes
    of ``tokens`` → the largest difference of the final state as a share of
    its largest entry.  No bfloat16 activation lies between the two, so a
    recurrence that decays after the correction, or keeps its state in
    bfloat16, stands clear of float32's order of sums."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import kda

    a, w, heads = first_kda_layer(params, model, tokens)
    inputs = reference.kda_inputs(a, w, heads=heads)
    n, body = len(tokens), len(tokens) - decoded
    _, want = reference.kda_scan(*inputs, n, faults=frozenset(faults))
    state = jnp.zeros((1, 2) + want.shape, jnp.float32)
    one = jnp.ones((1,), bool)
    _, state = jax.jit(kda.kda_chunk_scan, static_argnames="chunk")(
        state, jnp.int32(0), *(x[:body] for x in inputs), jnp.array([0]),
        jnp.array([body]), jnp.array([0]), one, one,
        chunk=cfg.kda_chunk_size)
    step = jax.jit(kda.kda_decode_update)
    for t in range(body, n):
        tok = [jnp.stack([x[t], jnp.zeros_like(x[t])]) for x in inputs]
        _, state = step(state, jnp.int32(0), *tok, jnp.array([True, False]),
                        jnp.array([False, False]))
    return float(jnp.abs(state[0, 0] - want).max() / jnp.abs(want).max())


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The engine's step-program logits against the reference's full forward
    over the same codes, the reference HELD TO THE PROGRAM'S EXPERT CHOICES
    (with seeded random weights the 8th and 9th of 256 scores lie close,
    bfloat16 lands on the other side at some, and every later layer reads
    each flip; the router is compared directly, ``check_router``).
    ``logit_tol_median`` bounds the median row (a systematic fault),
    ``logit_tol`` the worst (a local one: a stale slot, a lost state);
    ``agree_min`` the share of (layer, position) pairs at which the
    reference's own router picks the program's experts.  THE STATE,
    directly: every KDA layer's state of each tapped sequence's slot against
    the reference's after the same tokens, the largest difference as a share
    of the state's largest entry: at most ``state_tol`` in the FIRST KDA
    layer (which reads the embedding through one norm and one projection:
    what differs there is the state's own arithmetic), at most
    ``state_tol_deep`` in any (the deeper layers read what bfloat16
    activations made of the layers before them; a state lost, stale or
    decayed the wrong way is wrong by its own size); and the low mantissa
    bits of what the slots hold (``serve_ssm_moe.low_bits_share``: a state
    kept or rounded in bfloat16 reads 0).  The logits of two dozen positions
    cannot tell a float32 state from a bfloat16 one; these can.  THE
    RECURRENCE as a function (``kda_direct``, at most ``kda_tol``): what
    bfloat16 activations hide of the mathematics' order.  WHAT THE SERVED
    DECODE PROGRAM WROTE (``serve_decode_sample``: the tapped sample's decode
    rows ride in mixed steps): the first KDA layer's state of its slot, at
    most ``state_tol`` as above, and the conv's kept inputs, at most
    ``conv_tol`` of their largest entry, after every decode-only step the
    request needed (``decode_tokens - 1``), some of them dispatched ahead.
    And the arrays: the types and the widths the file states, every block
    and slot free after the drain and after the sample."""
    errs, states, passes = row_errors(params, model, tapped,
                                      check["logit_pad"])
    _NOTES["router_pass"] = (
        _padded(*tapped[-1][:2], check["logit_pad"])[0], passes[-1])
    median, worst = float(np.median(errs)), float(errs.max())
    state_first = max(s[0] for s in states)
    state_worst = max(max(s) for s in states)
    low_bits = serve_ssm_moe.low_bits_share(np.stack([t[4] for t in tapped]))
    prompt, tokens = tapped[-1][:2]
    direct = kda_direct(params, model, _CHECK["cfg"], (prompt + tokens)[:-1])
    sample = _NOTES["decode_sample"]
    decoded = decode_sample_errors(params, model, sample)
    # (one step more where the window's last cancellation left a program
    # under way: the engine fetches it first and drops its rows)
    decoded_ok = (sample["steps"] >= check["decode_tokens"] - 1
                  and sample["ahead"] > 0
                  and decoded["state"] <= check["state_tol"]
                  and decoded["conv"] <= check["conv_tol"])
    agree = []
    for (prompt, tokens, _, forced, _), inputs in zip(tapped, passes):
        n = len(prompt) + len(tokens) - 1
        own = np.sort(np.asarray(reference.own_choices(
            params, model, inputs))[:, :n], -1)
        agree.append(float((own == np.sort(forced[:, :n], -1)).all(-1).mean()))
    stated = _CHECK.get("pools")
    pools_ok = stated is None or all(
        _NOTES["dtypes"].get(k) == v["dtype"]
        and _NOTES["shapes"].get(k, [0])[-1] == v["width"]
        for k, v in stated.items())
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"]
          and np.isfinite(state_worst) and state_first <= check["state_tol"]
          and state_worst <= check["state_tol_deep"]
          and low_bits is not None
          and low_bits >= check["state_low_bits_min"]
          and np.isfinite(direct) and direct <= check["kda_tol"]
          and decoded_ok and min(agree) >= check["agree_min"]
          and pools_ok and bool(_NOTES.get("ok")))
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(t[0]) for t in tapped]}), the reference held to the "
        f"program's experts (its own router picks the same at "
        f"{(100 * np.asarray(agree)).round(1).tolist()} % of the (layer, "
        f"position) pairs, at least {100 * check['agree_min']:.0f} asked); "
        f"|engine - reference| median {median:.4f} (allowed "
        f"{check['logit_tol_median']}), worst {worst:.4f} (allowed "
        f"{check['logit_tol']}); quartiles "
        f"{np.percentile(errs, [25, 50, 75, 90]).round(4).tolist()}; KDA "
        f"state of the slots against the reference's, as a share of the "
        f"state's largest entry: the first KDA layer a sequence "
        f"{[round(s[0], 6) for s in states]} (allowed "
        f"{check['state_tol']}), the worst layer "
        f"{[round(max(s), 6) for s in states]} (allowed "
        f"{check['state_tol_deep']}; layer by layer "
        f"{[[round(x, 4) for x in s] for s in states]}); {low_bits} of the "
        f"slots' non-zero "
        f"entries hold low mantissa bits (at least "
        f"{check['state_low_bits_min']} asked); the program's two KDA paths "
        f"against the reference's recurrence on the same float32 inputs: "
        f"{direct:.2e} of the state's largest entry (allowed "
        f"{check['kda_tol']:.0e}); the served decode-only program, "
        f"{sample['steps']} steps ({sample['ahead']} dispatched ahead) "
        f"behind a prompt of {sample['prompt']}: the first KDA layer's state "
        f"of its slot against the reference's {decoded['state']:.6f} "
        f"(allowed {check['state_tol']}), the conv's kept inputs "
        f"{decoded['conv']:.6f} (allowed {check['conv_tol']}; kept one token "
        f"late they would read {decoded['conv_late']:.3f}); arrays "
        f"{_NOTES.get('dtypes')} {_NOTES.get('shapes')} as stated: "
        f"{pools_ok}; all blocks and slots free after the drain and the "
        f"sample: {_NOTES.get('ok')}; tapped steps by program: "
        f"{_NOTES.get('steps')}")
    return {"rows": len(errs), "median": median, "worst": worst,
            "state_first": state_first, "state": state_worst,
            "low_bits": low_bits, "kda_direct": direct,
            "decode_state": decoded["state"], "decode_conv": decoded["conv"],
            "agree": agree, "ok": bool(ok)}


class TimedTraceSession(serve_moe.TraceSession):
    """``serve_moe.TraceSession`` that notes, on the clock of the program's
    spans, when the traced interval began and ended: where the trace's
    window annotation is opened and closed."""

    def start(self) -> None:
        super().start()
        _NOTES["traced_interval"] = [time.monotonic()]

    def stop(self) -> None:
        _NOTES["traced_interval"].append(time.monotonic())
        super().stop()


def latent_decode_note(obs) -> str:
    """For the log of a traced run, what ``latent_full_decode_roofline_pct``
    divides, a program: the steps the trace holds, the keys a step their
    spans count (of the interval; of the whole window beside it), the device
    seconds a call under the scope and the HBM seconds of those keys at the
    mathematics' 576 values and at the pool's width, which the kernel
    fetches (640)."""
    from benchmark import kda_flops, stats

    got = kda_flops.traced(obs)
    if got is None:
        return "latent_attention_decode_full: nothing traced"
    t, model, peaks = got
    layers, out = kda_flops.latent_layers(model), []
    values = kda_flops.entry_values(model)
    width = obs["engine"]["pools"]["latent"]["width"]
    for kind, program in (("mixed", "jit_mixed_step"),
                          ("decode", "jit_decode_step")):
        steps = [a for a in kda_flops.kda_steps(obs, kind)
                 if "latent_keys_single" in a]
        window = [s["attrs"].get("latent_keys_single", 0)
                  for s in stats.spans_named(obs, "engine/step", kind=kind)]
        n = kda_flops.steps_traced(t, model, program)
        taken = kda_flops.scope_seconds(
            t, ("latent_attention_decode_full",), program)
        if not steps or not n or not taken:
            continue
        keys = sum(a["latent_keys_single"] for a in steps) / len(steps)
        hbm = kda_flops.attention_bytes(model, keys / layers) \
            / peaks["hbm_bytes_per_s"]
        calls = {k: t["kernel_calls"].get(f"{program}/{k}") for k in (
            "latent_attention_decode_full", "kda_decode_update")}
        out.append(
            f"{program}: {n:.1f} steps traced (kernel calls {calls}), "
            f"{len(steps)} spans inside "
            f"{obs.get('traced_interval')}; keys a step a layer "
            f"{keys / layers:.0f} (the whole window's spans: "
            f"{np.mean(window) / layers:.0f}); {1e3 * taken / n / layers:.4f}"
            f" ms a call; HBM time of the keys {1e3 * hbm:.4f} ms at "
            f"{values} values, {1e3 * hbm * width / values:.4f} at {width}")
    return "latent_attention_decode_full, " + "; ".join(out)


def run(**kwargs) -> Dict[str, Any]:
    _CHECK.clear()
    _NOTES.clear()
    _CHECK["pools"] = kwargs["config"]["engine"].get("pools")
    clock = serve_ssm_moe.SetupClock()
    with mock.patch.multiple(
            serve_moe, program_config=program_config, reference=reference,
            make_params=make_params, tap_logits=tap_logits,
            check_logits=check_logits,
            check_router=serve_latent_moe.check_router,
            check_served=serve_latent_moe.check_served, MOE_SCOPES=SCOPES,
            TraceSession=TimedTraceSession, HERE=ORDERED_START), \
            mock.patch.object(serve_latent_moe, "reference", reference), \
            mock.patch.object(serve_moe.serve, "pick_sequences",
                              serve_latent_moe.pick_spread):
        obs = serve_moe.run(**kwargs)
    if len(_NOTES.get("traced_interval", ())) == 2:
        obs["traced_interval"] = _NOTES["traced_interval"]
    kwargs["log"](f"set-up {obs['setup_s']:.1f}s; JAX's own events before "
                  f"the window opened, summed (how many): "
                  f"{clock.before(obs['window']['t_open'])}")
    by_name = (obs.get("trace") or {}).get("by_name")
    if by_name:  # the traced run: where the device's time went, for the log
        rows = sorted({**by_name["scope_s"], **{
            f"{k} (kernel)": v for k, v in by_name["kernel_s"].items()}
        }.items(), key=lambda kv: -kv[1])
        kwargs["log"]("device seconds by scope and kernel, of "
                      f"{by_name['busy_s']:.3f} busy: " + ", ".join(
                          f"{k} {v:.4f}" for k, v in rows[:40]))
        kwargs["log"](latent_decode_note(obs))
    return obs
