"""Driver ``serve_ssm_moe``: driver ``serve_moe`` for a model of ONE mixer a
layer of three kinds (Mamba-2, routed MoE with a shared expert, attention
without positions: NVIDIA-Nemotron-3-Nano-30B-A3B), whose per-sequence state
lives beside the paged K/V.

Everything ``serve_moe.run`` does is done by it, imported: the server, the
load generator, the spans, the trace reduced by kernel and scope name, the
served tokens' margins, the count of kernel fallbacks.  What this
configuration changes is handed to it for the run, as ``serve_swa_moe`` does:

* ``program_config``: the file's published keys (``program.published``: key →
  attribute of ``TransformerConfig``), what ``model_type`` implies and the
  pattern as run (a contiguous run of the published
  ``hybrid_override_pattern``) checked against the program's preset.
* ``reference``: ``benchmark/reference/ssm_moe_decoder.py``.
* ``make_params``: one jitted call that makes each kind's stack a layer at a
  time (``serve.make_params`` knows one stack); the conv bias, ``D``, the
  group norm's weight and the norms are drawn from the seed, so that a
  program that drops one cannot pass for the right one.
* ``tap_logits``: through ``benchmark/routing_tap.py`` (the tap that donates
  pools AND state and reads the step programs' routing choices), which also
  notes whether every block and state slot came back after the drain, and
  reads THE ENGINE'S OWN STATE ARRAYS: the slots the window's served programs
  left (slots are never cleared) and, after the sample, the tapped
  sequences' slots.
* ``check_logits``: against this reference HELD TO THE PROGRAM'S ROUTING
  CHOICES (with seeded random weights the model's function jumps where its
  router ties: the reference's module text); the engine's state arrays
  against the same pass's final states and against the types the file states
  (``engine.state``: logits cannot see the state's precision); false too if a
  slot was left taken.  ``check_served``: by a share, not a worst token, for
  the same reason.
* ``check_router``: sigmoid scores, the correction bias in the choice only,
  the weights renormalised and scaled, compared directly, at every MoE layer.
* ``MOE_SCOPES``: the routed FFN's scopes and the state-space layer's.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, List, Mapping, Tuple
from unittest import mock

import numpy as np

from benchmark.drivers import serve_moe
from benchmark.reference import ssm_moe_decoder as reference

#: the scopes the traced run reduces by; the kernels' own first, so that an
#: operation inside ``ssm_scan/ssd_chunk_scan`` counts under the inner name
SCOPES = ("ssd_chunk_scan", "ssm_decode_update", "ssm_in_proj", "ssm_conv",
          "ssm_scan", "ssm_gate_norm", "ssm_out_proj", "moe_shared",
          *serve_moe.MOE_SCOPES)

#: what ``tap_logits`` (which holds the engine after the drain and after the
#: sample) notes of the engine for ``check_logits``: slots that came back,
#: the state arrays' types, the low bits of the states the window left
_SLOTS: Dict[str, Any] = {}
#: standard deviation of the router's correction bias (``draw_small_tensors``)
ROUTER_BIAS_STD = 0.02
#: the configuration's ``check`` of the run at hand, for ``check_served``
#: (``serve_moe.run`` hands it the margin alone), and ``engine.state``
_CHECK: Dict[str, Any] = {}


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
    program, as_run = config["program"], config["as_run"]
    for key, attr in program["published"].items():
        said = as_run.get(key, config[key]) if key in config["reduced"] \
            else config[key]
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
    first, n = as_run["first_layer"], as_run["num_hidden_layers"]
    pattern = config["hybrid_override_pattern"][first:first + n]
    if pattern != as_run["hybrid_override_pattern"] \
            or tuple(pattern) != cfg.mixer_pattern:
        refuse("hybrid_override_pattern (as run)", pattern,
               "".join(cfg.mixer_pattern))
    model = {k: config[k] for k in program["model_keys"]}
    model.update(num_hidden_layers=n, hybrid_override_pattern=pattern,
                 # under the names the shared readers' arithmetic knows
                 intermediate_size=config["moe_intermediate_size"],
                 num_experts=config["n_routed_experts"])
    return cfg, model


def published_model(cfg) -> Dict[str, Any]:
    """The other way: the published keys the reference reads, from a program
    configuration (the tier-1 tests and ``chip_smoke.py``, which start from a
    preset and have no file)."""
    return dict(num_hidden_layers=cfg.num_layers,
                hybrid_override_pattern="".join(cfg.mixer_pattern),
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.kv_heads, head_dim=cfg.head_dim,
                norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                mamba_num_heads=cfg.mamba_num_heads,
                mamba_head_dim=cfg.mamba_head_dim,
                n_groups=cfg.mamba_n_groups,
                ssm_state_size=cfg.mamba_state_size,
                conv_kernel=cfg.mamba_conv_kernel,
                n_routed_experts=cfg.num_experts,
                num_experts_per_tok=cfg.moe_top_k,
                norm_topk_prob=cfg.moe_norm_topk,
                routed_scaling_factor=cfg.moe_routed_scaling,
                hidden_size=cfg.hidden_size, vocab_size=cfg.vocab_size)


def draw_small_tensors(params, seed):
    """The tensors ``init_params`` leaves at a constant, drawn from ``seed``
    (an int, or a PRNG key: inside a jitted program a key that is an ARGUMENT
    keeps the program one for every seed, where an int is a constant of it
    and every new seed a new program to compile; uniform in [0.5, 1.5)): every norm's scale, the group norm's weight and
    ``D``.  At 1 a missing ``D`` or a norm applied at the wrong place would
    still move the logits; a norm's weight at 1 everywhere would not show
    one read from the wrong layer.

    And the router's correction bias, drawn again at a standard deviation of
    ``ROUTER_BIAS_STD``: a checkpoint's bias BALANCES the experts' load, and
    beside a seeded random router (which is balanced already) the program's
    own draw of 0.05 unbalances it: a decode step of 64 rows then hits 75 %
    of 128 experts, between 79 and 84 % from one seed to the next, and the
    step's time follows the seed (PERF.md section 6).  At 0.02 it still
    changes the choice at two positions in three."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed

    def draw(leaf, salt):
        return jax.random.uniform(
            jax.random.fold_in(key, salt), leaf.shape,
            jnp.float32, 0.5, 1.5).astype(leaf.dtype)

    lay = params["layers"]
    for i, kind in enumerate(("M", "E", "*")):
        lay[kind]["norm"]["scale"] = draw(lay[kind]["norm"]["scale"], 0x51 + i)
    lay["M"]["mamba"]["norm_w"] = draw(lay["M"]["mamba"]["norm_w"], 0x61)
    lay["M"]["mamba"]["D"] = draw(lay["M"]["mamba"]["D"], 0x62)
    params["final_norm"]["scale"] = draw(params["final_norm"]["scale"], 0x63)
    bias = lay["E"]["moe"]["router_bias"]
    lay["E"]["moe"]["router_bias"] = (ROUTER_BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 0x64), bias.shape,
        jnp.float32)).astype(bias.dtype)
    return params


def make_params(cfg, seed: int, bits: int, group: int):
    """The whole parameter tree on the device, in the types it is served in,
    from one jitted call: each kind's stack a layer at a time (``lax.map``),
    so the bf16 form of more than one layer never exists."""
    import jax

    from deepspeed_tpu.inference.quantization import quantize_model_params
    from deepspeed_tpu.models import transformer as tfm

    def one_of(kind):
        return dataclasses.replace(cfg, num_layers=1, mixer_pattern=(kind,))

    def whole(key):
        k_rest, *k_kinds = jax.random.split(key, 4)
        params = tfm.init_params(k_rest, one_of("*"))  # embedding, head, norm
        layers = {}
        for kind, k in zip(("M", "E", "*"), k_kinds):
            def layer(key, kind=kind):
                lay = jax.tree.map(
                    lambda a: a[0],
                    tfm.init_params(key, one_of(kind))["layers"][kind])
                if bits:
                    lay = quantize_model_params(
                        {"layers": {kind: lay}}, bits=bits,
                        group=group)["layers"][kind]
                return lay

            layers[kind] = jax.lax.map(
                layer, jax.random.split(k, cfg.layers_of(kind)))
        params["layers"] = layers
        return draw_small_tensors(params, key)  # no constant of the seed

    return jax.jit(whole)(jax.random.PRNGKey(seed))


def low_bits_share(state) -> Any:
    """Of the non-zero float32 elements of ``state``, the share whose low 16
    mantissa bits are not all zero: what bfloat16 cannot hold.  A state kept
    in float32 reads 1 - 2^-16; one kept in bfloat16 (the array's type, or a
    rounding inside the update) reads 0.  None where nothing is non-zero."""
    bits = np.ascontiguousarray(np.asarray(state, np.float32)).view(np.uint32)
    held = bits[(bits & 0x7FFFFFFF) != 0]
    return float(((held & 0xFFFF) != 0).mean()) if held.size else None


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]
               ) -> List[Tuple[List[int], List[int], list, np.ndarray,
                               np.ndarray]]:
    """``serve_moe.tap_logits`` through the tap that donates the pools and
    the state arrays and reads the step programs' routing choices
    (``benchmark/routing_tap.py``): → [(prompt, tokens, [(position, logits)],
    the experts used ``(MoE layers, positions, k)``, the sequence's slot of
    the engine's ``caches["ssm"]`` after its last step ``(Mamba layers, H,
    P, N)``)].  The engine's blocks and slots are counted before the sample
    (after the drain) and after it; before it the first slots of the state
    array are read as the window's served programs left them."""
    from benchmark.routing_tap import RoutedLogitTap

    before = engine.drained()
    _CHECK.update(check, cfg=cfg)
    ssm = engine.caches["ssm"]
    served = low_bits_share(ssm[:, :len(check["logit_prompts"])]) \
        if ssm.dtype == np.float32 else 0.0
    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    tap = RoutedLogitTap(engine)
    try:
        uids = [engine.put(p, max_new_tokens=check["logit_tokens"])
                for p in prompts]
        out = engine.generate_all(burst=1)  # step by step: the tapped path
    finally:
        tap.remove()
    engine.kv.check_consistency()
    # slots are never cleared and nothing was admitted after these three:
    # each slot holds its sequence's state after the last token it read
    states = [np.asarray(engine.caches["ssm"][:, tap.slots[u]], np.float32)
              for u in uids]
    _SLOTS.update(ok=before and engine.drained(),
                  free=engine.free_state_slots,
                  total=engine.total_state_slots,
                  dtypes={k: str(engine.caches[k].dtype)
                          for k in ("ssm", "conv")},
                  low_bits_served=served)
    return [(p, out[u][len(p):], tap.logits[u], tap.forced(u, len(out[u])), s)
            for p, u, s in zip(prompts, uids, states)]


def row_errors(params, model, tapped, pad: int, faults=(), force=True,
               router_faults=()):
    """Every tapped sequence against ONE pass of the reference held to the
    program's routing choices (``faults``: a named wrong program of it;
    ``force`` False: its own choices; ``router_faults``: a wrong router for
    ``agree`` alone): → (largest |engine - reference| over the vocabulary a
    tapped row; a sequence's share of (MoE layer, position) pairs at which the
    reference's router, reading what it read along that pass, picks the
    program's experts; ``(sequences, Mamba layers)``: the largest difference
    between the engine's slot and the reference's state after the last token
    the engine read, as a share of that state's largest element)."""
    import jax.numpy as jnp

    errs, agree, state = [], [], []
    for prompt, tokens, rows, forced, slot in tapped:
        n = len(prompt) + len(tokens)
        seq = np.zeros(-(-n // pad) * pad, np.int32)
        seq[:n] = prompt + tokens
        held = np.full(forced.shape[:1] + (len(seq),) + forced.shape[2:], -1,
                       np.int32)
        held[:, :forced.shape[1]] = forced
        first = len(prompt) - 1  # the first tapped row reads this position
        # the last token sampled was never read: the slot holds n - 1 tokens
        out = reference.whole_pass(
            params, model, jnp.asarray(seq), last=len(seq) - first,
            faults=frozenset(faults),
            forced=jnp.asarray(held) if force else None, length=n - 1)
        want = np.asarray(out["logits"])
        errs += [float(np.abs(row - want[pos - first]).max())
                 for pos, row in rows]
        final = np.asarray(out["states"])
        state.append(np.abs(slot - final).max((1, 2, 3))
                     / np.abs(final).max((1, 2, 3)))
        own = np.sort(np.asarray(reference.own_choices(
            params, model, out["router_inputs"], router_faults))[:, :n], -1)
        same = (own == np.sort(forced[:, :n], -1)).all(-1)  # (layers, n)
        agree.append(float(same.mean()))
    return np.asarray(errs), np.asarray(agree), np.asarray(state)


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The engine's step-program logits against the reference's full forward
    over the same codes, the reference HELD TO THE PROGRAM'S ROUTING CHOICES
    (the reference's module text: with seeded random weights this model's
    function jumps where its router ties, and the router is compared
    directly).  Two bounds on every row: ``logit_tol_median`` on the median
    row for a systematic fault, ``logit_tol`` on the worst row for a local
    one (a state read from the wrong slot or left from the sequence before, a
    stale K/V block).  ``agree_min`` bounds from below the share of (layer,
    position) pairs at which the reference's own router, along the same pass,
    picks the program's experts: a program whose router is wrong would pass
    the logits by forcing its own choices on the reference, and fails here.

    Then what logits cannot see, the state's precision, ON THE ENGINE'S OWN
    ARRAYS: ``caches["ssm"]`` and ``["conv"]`` have the types the file states
    (``engine.state``); each tapped sequence's slot lies within ``state_tol``
    of the reference pass's final state, Mamba layer by Mamba layer (what the
    step programs wrote through three chunks and 33 decode steps: a wrong
    decay, a state from the wrong slot, a lost write-back); and the states
    the window's served programs left hold what bfloat16 cannot
    (``state_low_bits_min``, ``low_bits_share``).  And the state slots: all
    free after the drain and after the sample."""
    errs, agree, state = row_errors(params, model, tapped, check["logit_pad"])
    median, worst = float(np.median(errs)), float(errs.max())
    stated = _CHECK.get("state")
    low = {"served": _SLOTS.get("low_bits_served"),
           "sample": low_bits_share(np.stack([t[4] for t in tapped]))}
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"]
          and float(agree.min()) >= check["agree_min"]
          and np.isfinite(state).all()
          and float(state.max()) <= check["state_tol"]
          and _SLOTS.get("dtypes") == stated
          and all(v is None or v >= check["state_low_bits_min"]
                  for v in low.values())
          and low["sample"] is not None and bool(_SLOTS.get("ok")))
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(t[0]) for t in tapped]}), the reference held to the "
        f"program's experts (its own router picks the same at "
        f"{(100 * agree).round(1).tolist()} % of the (layer, position) pairs "
        f"of each sequence, at least {100 * check['agree_min']:.0f} asked); "
        f"|engine - reference| median {median:.4f} (allowed "
        f"{check['logit_tol_median']}), worst {worst:.4f} (allowed "
        f"{check['logit_tol']}); quartiles "
        f"{np.percentile(errs, [25, 50, 75, 90]).round(4).tolist()}")
    log(f"state: the engine's arrays are {_SLOTS.get('dtypes')} (the file "
        f"states {stated}); the tapped sequences' slots differ from the "
        f"reference's final states by "
        f"{[[float(f'{v:.2e}') for v in row] for row in state]} of the "
        f"largest element, sequence by sequence and Mamba layer by Mamba "
        f"layer (allowed {check['state_tol']}); share of state elements "
        f"with low mantissa bits: {low} (at least "
        f"{check['state_low_bits_min']} asked)")
    log(f"state slots: {_SLOTS.get('free')} of {_SLOTS.get('total')} free "
        f"after the drain and after the sample")
    return {"rows": len(errs), "median": median, "worst": worst,
            "agree": agree.tolist(), "state": state.tolist(),
            "low_bits": low, "ok": bool(ok)}


def check_served(params, model, sequences, pad_to: int, margin: float,
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The window's served tokens under the reference's OWN routing (the
    served programs do not say which experts they used, so the reference
    cannot be held to them): wherever the two routed alike the served token
    is the reference's argmax or within ``margin`` of it, elsewhere it is
    whatever the other experts say.  Held: the share of served tokens within
    ``margin`` is at least ``served_min`` (sized on the chip: the program as
    it is against its wrong programs, the configuration's ``check.why``)."""
    import jax.numpy as jnp

    within, exact, checked = 0, 0, 0
    for prompt, served in sequences:
        seq = np.zeros(pad_to, np.int32)  # causal: the padding changes nothing
        seq[:len(prompt) + len(served)] = prompt + served
        m, rank = reference.served_margins(params, model, jnp.asarray(seq),
                                           len(prompt))
        m, rank = np.asarray(m)[:len(served)], np.asarray(rank)[:len(served)]
        within += int((m <= margin).sum())
        exact += int((rank == 0).sum())
        checked += len(served)
    share = within / max(checked, 1)
    log(f"reference (its own routing): {checked} served tokens of "
        f"{len(sequences)} sequences, {exact} are the reference's argmax, "
        f"{within} within {margin} of it ({100 * share:.1f} %, at least "
        f"{100 * _CHECK['served_min']:.0f} asked)")
    return {"tokens_checked": checked, "argmax_equal": exact,
            "within_margin": within,
            "ok": checked > 0 and share >= _CHECK["served_min"]}


def check_router(params, model, cfg, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """``serve_moe.check_router`` for this router, at EVERY MoE layer: the
    program's ``route`` (jitted here on the device) against the reference's,
    both on what the layer's router reads in the reference's pass over the
    shortest tapped sequence, rounded to the engine's activation type.
    ``router_tol`` bounds the largest relative difference of a score and of a
    weight; the experts chosen must be the reference's wherever its margin
    (of the BIASED scores) exceeds that tolerance."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe.dropless import route

    prompt, tokens = min(tapped, key=lambda t: len(t[0]))[:2]
    pad = check["logit_pad"]
    n = len(prompt) + len(tokens)
    seq = np.zeros(-(-n // pad) * pad, np.int32)
    seq[:n] = prompt + tokens
    moe = params["layers"]["E"]["moe"]
    program = jax.jit(lambda x, w, b: route(x, w, cfg, b))
    rel = gate = 0.0
    same, clear_share = True, 1.0
    inputs = reference.router_inputs(params, model, jnp.asarray(seq))
    for index, m in enumerate(inputs):
        m = m[:n].astype(jnp.dtype(cfg.dtype))
        w_router, bias = moe["router"][index], moe["router_bias"][index]
        got = program(m, w_router, bias)
        p, top, idx, margin = (np.asarray(a) for a in reference.router(
            m, w_router, bias, top_k=model["num_experts_per_tok"],
            norm_topk=bool(model["norm_topk_prob"]),
            scaling=float(model["routed_scaling_factor"])))
        rel = max(rel, float((np.abs(np.asarray(got.probs) - p) / p).max()))
        clear = margin > check["router_tol"]
        # as sets: the order may tie
        same &= bool((np.sort(np.asarray(got.experts)[clear], -1)
                      == np.sort(idx[clear], -1)).all())
        order = np.argsort(np.asarray(got.experts), -1)
        mine = np.take_along_axis(np.asarray(got.weights), order, -1)
        theirs = np.take_along_axis(top, np.argsort(idx, -1), -1)
        gate = max(gate, float((np.abs(mine[clear] - theirs[clear])
                                / theirs[clear]).max()))
        clear_share = min(clear_share, float(clear.mean()))
    ok = (np.isfinite(rel) and rel <= check["router_tol"] and same
          and gate <= check["router_tol"] and clear_share > 0.9)
    log(f"router: {len(inputs)} MoE layers, {n} positions each; scores "
        f"differ from the reference's by {rel:.2e} of their size at most, "
        f"the top-{model['num_experts_per_tok']} weights by {gate:.2e} "
        f"(allowed {check['router_tol']:.0e}); experts "
        f"{'equal' if same else 'DIFFER'} wherever the margin is clear (at "
        f"least {100 * clear_share:.0f} % of a layer's positions)")
    return {"layers": len(inputs), "positions": n, "prob_rel": rel,
            "gate_rel": gate, "experts_equal": same, "ok": bool(ok)}


class SetupClock:
    """JAX's own duration events (tracing, lowering, the backend's compile
    or the persistent cache's retrieval) as they come, so that the log can
    say what the set-up's seconds went to."""

    def __init__(self):
        import jax.monitoring

        self.events: List[Tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        self.events.append((time.monotonic(), event, duration))

    def before(self, t: float) -> str:
        sums, counts = collections.Counter(), collections.Counter()
        for at, event, duration in self.events:
            if at < t:
                sums[event] += duration
                counts[event] += 1
        return ", ".join(f"{e.rsplit('/', 1)[-1]} {v:.1f}s ({counts[e]})"
                         for e, v in sums.most_common(8))


def run(**kwargs) -> Dict[str, Any]:
    _CHECK.clear()
    _SLOTS.clear()
    _CHECK["state"] = dict(kwargs["config"]["engine"]["state"])
    clock = SetupClock()
    # serve_moe's module-level names rebound for this run, then put back
    with mock.patch.multiple(
            serve_moe, program_config=program_config, reference=reference,
            make_params=make_params, tap_logits=tap_logits,
            check_logits=check_logits, check_router=check_router,
            check_served=check_served, MOE_SCOPES=SCOPES):
        obs = serve_moe.run(**kwargs)
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
    return obs
