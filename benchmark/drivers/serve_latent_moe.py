"""Driver ``serve_latent_moe``: driver ``serve_moe`` for a model with LATENT
attention (MLA), a learned indexer that picks the keys a query attends over
and shares its pick with the layers behind it (DSA), leading dense layers and
A SHARE of its routed experts (GLM-5.2, ``model_type: glm_moe_dsa``).

Everything ``serve_moe.run`` does is done by it, imported: the server, the
load generator, the spans, the trace reduced by kernel and scope name, the
count of kernel fallbacks.  What this configuration changes is handed to it
for the run, as ``serve_ssm_moe`` does:

* ``program_config``: the file's published keys (``program.published``), what
  ``model_type`` implies, the two per-layer lists as run (a contiguous run of
  the published ones) and the indexer's period checked against the program's
  preset.
* ``reference``: ``benchmark/reference/latent_sparse_moe_decoder.py``.
* ``make_params``: one jitted call that makes each stack (attention, indexer,
  dense FFN, routed FFN) a layer at a time; every norm's scale, the indexer's
  LayerNorm bias and the router's correction bias are drawn from the seed.
* ``tap_logits``: through ``benchmark/selection_tap.py`` (the tap that
  donates the pools and reads, out of the step programs, the experts every
  position was routed to AND the keys every query of a "full" layer picked).
* ``check_logits``: against this reference HELD TO THE PROGRAM'S SELECTIONS
  AND EXPERT CHOICES; then ``check_indexer`` on the same pass: the program's
  scoring function against the reference's float32 scores on the same
  inputs, every pick against the reference's own ranking along the pass, the
  count of picks.
* ``check_router``: sigmoid scores over all ``n_routed_experts`` as published,
  at every routed layer (``serve_ssm_moe``'s, with this model's stack).
* ``check_served``: by a share (``serve_ssm_moe``'s rule) of EACH sequence,
  on the warm-up request and on requests that finished inside the window,
  picked over the traffic's whole range of lengths (``pick_spread`` in place
  of ``serve.pick_sequences``) and read whole by the reference.
* ``SCOPES``: the routed FFN's scopes, the latent attention's and the
  indexer's.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Dict, List, Mapping
from unittest import mock

import numpy as np

from benchmark.drivers import serve_moe, serve_ssm_moe
from benchmark.reference import latent_sparse_moe_decoder as reference

#: the scopes the traced run reduces by (``ops/pallas/latent_attention.py``,
#: ``models/latent_sparse.py``, ``moe/dropless.py``)
SCOPES = ("latent_attention_prefill", "latent_attention_decode",
          "dsa_index_scores", "dsa_topk", "dsa_index_proj", "latent_q_proj",
          "latent_kv_proj", "latent_absorb_q", "latent_absorb_o",
          "moe_shared", *serve_moe.MOE_SCOPES)

#: the directory of the generator ``serve_moe.run`` starts for this driver:
#: a closed loop's clients start in client order (``serve_swa_moe``'s reason)
ORDERED_START = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ordered_start")
ROUTER_BIAS_STD = 0.02
_CHECK: Dict[str, Any] = {}
_NOTES: Dict[str, Any] = {}


def program_config(config: Mapping[str, Any]):
    """→ (the program's configuration for this file, the published sizes as
    run, for the reference and the readers); refused if anything the file
    states differs from what the program's preset computes."""
    from deepspeed_tpu.models import transformer as tfm

    def refuse(what, said, gives):
        raise ValueError(f"configuration {config['name']}: the file says "
                         f"{what} = {said}, the program's preset gives "
                         f"{gives}")

    overrides = dict(config.get("overrides", {}))
    program, as_run = config["program"], config["as_run"]
    cfg = tfm.get_config(config["preset"], **overrides)
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
    # the published lists agree with the indexer's published period
    freq, skip = config["index_topk_freq"], config["index_skip_topk_offset"]
    want = ["full" if i < skip or (i - skip) % freq == freq - 1 else "shared"
            for i in range(len(config["indexer_types"]))]
    if want != list(config["indexer_types"]):
        refuse("indexer_types", "another pattern than index_topk_freq "
               f"{freq} and index_skip_topk_offset {skip} give", want[:12])
    first, n = as_run["first_layer"], as_run["num_hidden_layers"]
    for key, attr in (("indexer_types", "indexer_types"),
                      ("mlp_layer_types", "mlp_layer_types")):
        run = list(config[key][first:first + n])
        if run != list(as_run[key]) or tuple(run) != getattr(cfg, attr):
            refuse(f"{key} (as run)", run, list(getattr(cfg, attr)))
    if config["rope_parameters"]["rope_type"] != "default" \
            or config["rope_parameters"]["rope_theta"] != cfg.rope_theta:
        refuse("rope_parameters", config["rope_parameters"], cfg.rope_theta)
    if cfg.moe_first_expert != as_run["first_expert"]:
        refuse("first_expert", as_run["first_expert"], cfg.moe_first_expert)
    model = {k: config[k] for k in program["model_keys"]}
    model.update(num_hidden_layers=n, indexer_types=list(cfg.indexer_types),
                 mlp_layer_types=list(cfg.mlp_layer_types),
                 vocab_size=cfg.vocab_size, rope_theta=cfg.rope_theta,
                 experts_held=cfg.experts_held,
                 first_expert=cfg.moe_first_expert,
                 # under the names the shared readers' arithmetic knows: the
                 # experts THIS CHIP holds and one's width
                 dense_intermediate_size=config["intermediate_size"],
                 intermediate_size=config["moe_intermediate_size"],
                 num_experts=cfg.experts_held)
    return cfg, model


def published_model(cfg) -> Dict[str, Any]:
    """The other way: the published keys the reference reads, from a program
    configuration (the tier-1 tests and ``chip_smoke.py``, which start from a
    preset and have no file)."""
    return dict(
        num_hidden_layers=cfg.num_layers, hidden_size=cfg.hidden_size,
        vocab_size=cfg.vocab_size, num_attention_heads=cfg.num_heads,
        rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        index_topk=cfg.index_topk, index_n_heads=cfg.index_n_heads,
        index_head_dim=cfg.index_head_dim,
        indexer_types=list(cfg.indexer_types),
        mlp_layer_types=list(cfg.mlp_layer_types),
        n_routed_experts=cfg.num_experts, experts_held=cfg.experts_held,
        first_expert=cfg.moe_first_expert,
        num_experts_per_tok=cfg.moe_top_k, norm_topk_prob=cfg.moe_norm_topk,
        routed_scaling_factor=cfg.moe_routed_scaling,
        moe_intermediate_size=cfg.expert_width,
        dense_intermediate_size=cfg.intermediate_size,
        intermediate_size=cfg.expert_width, num_experts=cfg.experts_held)


def draw_small_tensors(params, seed):
    """The tensors ``init_params`` leaves at a constant, drawn from ``seed``
    (an int or a PRNG key): every norm's scale uniform in [0.5, 1.5) (at 1 a
    norm read from the wrong layer, or ``kv_a``'s left out, would hardly
    show), the indexer's LayerNorm bias normal 0.1, and the router's
    correction bias normal ``ROUTER_BIAS_STD`` (``serve_ssm_moe``'s reason:
    beside a seeded random router a larger one unbalances the experts)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed

    def draw(leaf, salt):
        return jax.random.uniform(
            jax.random.fold_in(key, salt), leaf.shape,
            jnp.float32, 0.5, 1.5).astype(leaf.dtype)

    lay = params["layers"]
    a, ix = lay["A"], lay["I"]["index"]
    for i, at in enumerate((a["ln1"], a["ln2"], a["attn"]["q_a_norm"],
                            a["attn"]["kv_a_norm"], ix["ik_norm"],
                            params["final_norm"])):
        at["scale"] = draw(at["scale"], 0x71 + i)
    bias = ix["ik_norm"]["bias"]
    ix["ik_norm"]["bias"] = (0.1 * jax.random.normal(
        jax.random.fold_in(key, 0x7A), bias.shape, jnp.float32)
    ).astype(bias.dtype)
    rb = lay["S"]["moe"]["router_bias"]
    lay["S"]["moe"]["router_bias"] = (ROUTER_BIAS_STD * jax.random.normal(
        jax.random.fold_in(key, 0x7B), rb.shape, jnp.float32)
    ).astype(rb.dtype)
    return params


def make_params(cfg, seed: int, bits: int, group: int):
    """The whole parameter tree on the device, in the types it is served in,
    from one jitted call: each stack a layer at a time (``lax.map``), so the
    bf16 form of more than one layer (1.6 GB for a routed one) never
    exists."""
    import jax

    from deepspeed_tpu.inference.quantization import quantize_model_params
    from deepspeed_tpu.models import latent_sparse
    from deepspeed_tpu.models import transformer as tfm

    def one_of(kind):  # a model of one layer that has a layer in ``kind``
        return dataclasses.replace(
            cfg, num_layers=1, indexer_types=("full",),
            mlp_layer_types=("dense" if kind == "D" else "sparse",))

    def whole(key):
        k_rest, *k_kinds = jax.random.split(key, 5)
        params = tfm.init_params(k_rest, one_of("D"))  # embedding, head, norm
        layers = {}
        for kind, k in zip(latent_sparse.KINDS, k_kinds):
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
                layer, jax.random.split(k, latent_sparse.layers_of(cfg, kind)))
        params["layers"] = layers
        return draw_small_tensors(params, key)  # no constant of the seed

    return jax.jit(whole)(jax.random.PRNGKey(seed))


def tap_logits(engine, cfg, seed: int, check: Mapping[str, Any]) -> List[tuple]:
    """``serve_moe.tap_logits`` through the tap that donates the pools and
    reads the step programs' expert choices and key selections
    (``benchmark/selection_tap.py``): → [(prompt, tokens, [(position,
    logits)], experts used ``(routed layers, positions, k)``, keys picked
    ``(full layers, positions, positions)`` bool)].  One prompt is longer
    than two ``index_topk`` (selection inside a chunk, across chunks and in
    decode), one shorter than one (every key is picked).

    ``check["logit_filler"]`` (optional): the length of one more prompt, put
    behind the others and compared with nothing, that is STILL PREFILLING
    WHILE THE COMPARED SEQUENCES DECODE, as a prompt is in the cell's window
    (24 clients over 16 rows): their decode rows then ride in mixed steps,
    through the mixed program's own decode path, and the tapped decode-only
    program is never called, so it is never built (it alone was 40 s of a
    run at the published widths, most of it the tracing of its GEMM kernels:
    PERF.md section 6, PR 40).  A filler too short for that costs the time
    and nothing else: the decode-only steps are tapped and compared as
    before.  ``_NOTES["steps"]`` says how many steps of each kind ran."""
    from benchmark.selection_tap import SelectionTap

    before = engine.drained()
    _CHECK.update(check, cfg=cfg)
    rng = np.random.default_rng([seed, 0x10617])
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in check["logit_prompts"]]
    filler = rng.integers(1, cfg.vocab_size,
                          size=check.get("logit_filler", 0)).tolist()
    tap = SelectionTap(engine)
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
    return [(p, out[u][len(p):], tap.logits[u], tap.forced(u, len(out[u])),
             tap.picked(u, len(out[u])))
            for p, u in zip(prompts, uids)]


def _padded(prompt, tokens, pad: int):
    n = len(prompt) + len(tokens)
    seq = np.zeros(-(-n // pad) * pad, np.int32)
    seq[:n] = prompt + tokens
    return seq, n


def row_errors(params, model, tapped, pad: int, faults=(), force=True):
    """Every tapped sequence against ONE pass of the reference held to the
    program's selections and expert choices (``faults``: a named wrong
    program of it; ``force`` False: its own) → (largest |engine - reference|
    over the vocabulary a tapped row, and a sequence at a time what
    ``reference.whole_pass`` says of the indexer and the router along that
    pass: ``out["indexer"]``, ``out["router_inputs"]``)."""
    import jax.numpy as jnp

    errs, passes = [], []
    for prompt, tokens, rows, forced, picked in tapped:
        seq, n = _padded(prompt, tokens, pad)
        held = np.full(forced.shape[:1] + (len(seq),) + forced.shape[2:], -1,
                       np.int32)
        held[:, :forced.shape[1]] = forced
        sel = np.zeros(picked.shape[:1] + (len(seq), len(seq)), bool)
        sel[:, :picked.shape[1], :picked.shape[2]] = picked
        first = len(prompt) - 1  # the first tapped row reads this position
        out = reference.whole_pass(
            params, model, jnp.asarray(seq), last=len(seq) - first,
            faults=frozenset(faults),
            forced=jnp.asarray(held) if force else None,
            # the last token sampled was never read: n - 1 queries picked
            selected=sel if force else None, length=n - 1)
        want = np.asarray(out["logits"])
        errs += [float(np.abs(row - want[pos - first]).max())
                 for pos, row in rows]
        passes.append({k: out[k] for k in ("router_inputs", "indexer")})
    return np.asarray(errs), passes


def check_indexer(params, model, cfg, tapped, passes,
                  check: Mapping[str, Any], log) -> Dict[str, Any]:
    """The indexer, three ways, at every "full" layer.

    (1) THE PROGRAM'S SCORING FUNCTION (``index_scores``, what both step
    programs call, jitted here on the device) against the reference's float32
    scores, both on the queries, weights and keys the reference's pass made,
    rounded to the engine's activation type: ``index_tol`` bounds the largest
    difference as a share of the layer's largest score.  The logits cannot
    see the precision the scores were summed in; this can.
    (2) THE PICKS the step programs made (the tap's), against the REFERENCE'S
    OWN float32 scores along the pass held to those picks: every picked key's
    score no further than ``select_band`` (a share of the spread of the
    query's visible scores) under the reference's k-th largest, every
    left-out key's no further above it; the share of picks that are the
    reference's own at least ``select_agree_min``.
    (3) Every query picks exactly ``min(index_topk, keys it sees)`` keys and
    none after its own position."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.latent_attention import index_scores

    k = model["index_topk"]
    dt = jnp.dtype(cfg.dtype)
    program = jax.jit(index_scores)
    rel, band, agree, counts_ok, causal_ok = 0.0, 0.0, 1.0, True, True
    for (prompt, tokens, _, _, picked), out in zip(tapped, passes):
        n = len(prompt) + len(tokens) - 1  # the last token was never read
        for layer, (qi, w, ki, scores) in enumerate(out["indexer"]):
            qi, w, ki = qi[:n].astype(dt), w[:n], ki[:n].astype(dt)
            got = np.asarray(program(qi, w, ki))
            want = np.asarray(reference.index_scores(
                qi.astype(jnp.float32), w, ki.astype(jnp.float32)))
            rel = max(rel, float(np.abs(got - want).max()
                                 / np.abs(want).max()))
            # the picks against the pass's own scores
            sc = np.asarray(scores)[:n, :n]
            sel = picked[layer, :n, :n]
            seen = np.tril(np.ones((n, n), bool))
            causal_ok &= not bool((sel & ~seen).any())
            want_n = np.minimum(np.arange(1, n + 1), k)
            counts_ok &= bool((sel.sum(1) == want_n).all())
            busy = np.arange(n) >= k  # the queries that leave keys out
            if not busy.any():
                continue
            s_vis = np.where(seen, sc, -np.inf)[busy]
            kth = -np.partition(-s_vis, k - 1, axis=1)[:, k - 1]
            spread = np.where(seen[busy], sc[busy], np.nan)
            spread = np.nanmax(spread, 1) - np.nanmin(spread, 1)
            under = np.where(sel[busy], kth[:, None] - s_vis, 0).max(1)
            over = np.where(seen[busy] & ~sel[busy], s_vis - kth[:, None],
                            0).max(1)
            band = max(band, float((np.maximum(under, over) / spread).max()))
            own = s_vis >= kth[:, None]
            agree = min(agree, float((own & sel[busy]).sum()
                                     / sel[busy].sum()))
    ok = (rel <= check["index_tol"] and band <= check["select_band"]
          and agree >= check["select_agree_min"] and counts_ok and causal_ok)
    log(f"indexer: scores differ from the reference's by {rel:.2e} of the "
        f"largest (allowed {check['index_tol']:.0e}); a pick lies at most "
        f"{band:.4f} of the spread on the wrong side of the reference's "
        f"{k}-th score (allowed {check['select_band']}); at least "
        f"{100 * agree:.1f} % of a layer's picks are the reference's own (at "
        f"least {100 * check['select_agree_min']:.0f} asked); every query "
        f"picks min({k}, keys seen): {counts_ok}; none after itself: "
        f"{causal_ok}")
    return {"score_rel": rel, "band": band, "agree": agree,
            "counts": counts_ok, "causal": causal_ok, "ok": bool(ok)}


def check_logits(params, model, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """The engine's step-program logits against the reference's full forward
    over the same codes, the reference HELD TO THE PROGRAM'S SELECTIONS AND
    EXPERT CHOICES (with seeded random weights the 2,048th and 2,049th score
    and the 8th and 9th expert lie close, bfloat16 lands on the other side at
    some, and every later layer reads each flip); both are compared directly
    (``check_indexer``, ``check_router``).  ``logit_tol_median`` bounds the
    median row (a systematic fault), ``logit_tol`` the worst (a local one: a
    stale block, a wrong position, another row's selection); ``agree_min``
    the share of (layer, position) pairs at which the reference's own router
    picks the program's experts.  And both pools: the types and the widths
    the file states, every block free after the drain and after the
    sample."""
    errs, passes = row_errors(params, model, tapped, check["logit_pad"])
    # what ``check_router`` reads: the routed layers' inputs along the last
    # sequence's pass, which is made here (a pass of its own was 3.5 s)
    _NOTES["router_pass"] = (
        _padded(*tapped[-1][:2], check["logit_pad"])[0],
        passes[-1]["router_inputs"])
    median, worst = float(np.median(errs)), float(errs.max())
    agree = []
    for (prompt, tokens, _, forced, _), out in zip(tapped, passes):
        n = len(prompt) + len(tokens) - 1
        own = np.sort(np.asarray(reference.own_choices(
            params, model, out["router_inputs"]))[:, :n], -1)
        agree.append(float((own == np.sort(forced[:, :n], -1)).all(-1).mean()))
    indexer = check_indexer(params, model, _CHECK["cfg"], tapped, passes,
                            check, log)
    stated = _CHECK.get("pools")
    pools_ok = stated is None or all(
        _NOTES["dtypes"].get(k) == v["dtype"]
        and _NOTES["shapes"].get(k, [0])[-1] == v["width"]
        for k, v in stated.items())
    ok = (np.isfinite(errs).all() and median <= check["logit_tol_median"]
          and worst <= check["logit_tol"]
          and min(agree) >= check["agree_min"] and indexer["ok"]
          and pools_ok and bool(_NOTES.get("ok")))
    log(f"logits: {len(errs)} rows of {len(tapped)} sequences (prompts "
        f"{[len(t[0]) for t in tapped]}), the reference held to the "
        f"program's selections and experts (its own router picks the same "
        f"at {(100 * np.asarray(agree)).round(1).tolist()} % of the (layer, "
        f"position) pairs, at least {100 * check['agree_min']:.0f} asked); "
        f"|engine - reference| median {median:.4f} (allowed "
        f"{check['logit_tol_median']}), worst {worst:.4f} (allowed "
        f"{check['logit_tol']}); quartiles "
        f"{np.percentile(errs, [25, 50, 75, 90]).round(4).tolist()}; pools "
        f"{_NOTES.get('dtypes')} {_NOTES.get('shapes')} as stated: "
        f"{pools_ok}; all blocks free after the drain and the sample: "
        f"{_NOTES.get('ok')}; tapped steps by program: "
        f"{_NOTES.get('steps')}")
    return {"rows": len(errs), "median": median, "worst": worst,
            "agree": agree, "indexer": indexer, "ok": bool(ok)}


def pick_spread(finished: List[dict], check: Mapping[str, Any],
                seed: int) -> List[dict]:
    """The window's finished requests the reference reads, in place of
    ``serve.pick_sequences`` (which takes the longest that fits and draws the
    rest): ``window_sequences`` of them SPREAD OVER THE TRAFFIC'S LENGTHS,
    the shortest, the longest and those evenly between by rank of prompt +
    answer, so that every run checks the contexts the window serves, 4k to
    16k, and not the ones a short reference has room for.  ``schedule_seed``
    fixes every client's lengths, so the picks have the same lengths run
    after run (the reference's shapes compile once)."""
    fits = sorted((r for r in finished if r["n_prompt"] + len(r["tokens"])
                   <= check["reference_len"]),
                  key=lambda r: (r["n_prompt"] + len(r["tokens"]),
                                 r["stream"], r["index"]))
    n = min(check["window_sequences"], len(fits))
    if n < 2:
        return fits[:n]
    return [fits[(2 * i * (len(fits) - 1) + n - 1) // (2 * (n - 1))]
            for i in range(n)]


def served_readings(params, model, sequences, pad: int, margin: float,
                    faults=()) -> List[Dict[str, Any]]:
    """A served sequence at a time, read WHOLE at its own length (padded to
    the next multiple of ``pad``; causal, so the padding changes nothing)
    under the reference's OWN selections and routing (``faults``: a named
    wrong program's): how many of its served tokens lie within ``margin`` of
    the reference's maximum, how many are its argmax."""
    import jax.numpy as jnp

    out = []
    for prompt, served in sequences:
        seq, n = _padded(prompt, served, pad)
        m, rank = reference.served_margins(params, model, jnp.asarray(seq),
                                           len(prompt), frozenset(faults))
        m, rank = np.asarray(m)[:len(served)], np.asarray(rank)[:len(served)]
        out.append({"context": n, "tokens": len(served),
                    "within": int((m <= margin).sum()),
                    "argmax": int((rank == 0).sum()),
                    "margins": np.where(np.isfinite(m), m, np.inf)})
    return out


def check_served(params, model, sequences, pad_to: int, margin: float,
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """WHAT THE TIMED PATH PRODUCED, AT THE LENGTHS IT PRODUCED IT: the
    warm-up request and the window's picks (``pick_spread``: finished inside
    the window, under load, 16 rows live, contexts from the traffic's
    shortest to its longest), each read whole by the reference.  The served
    programs do not say which keys and experts they used, so the reference
    runs under its OWN (``serve_ssm_moe.check_served``'s reason): wherever
    the two picked and routed alike the served token is the reference's
    argmax or within ``margin`` of it, elsewhere it is what the other keys
    and experts say.  Held, for EVERY sequence on its own (a fault that shows
    only past some context must not hide in the pooled count): the share of
    its served tokens within ``margin`` is at least ``served_min``.  Both are
    sized on the chip against named wrong programs at these lengths (the
    configuration's ``check.why``).  No window sequence to read is a failure:
    the check then says nothing of the timed path."""
    if len(sequences) < 2:  # ``serve_moe.run`` puts the warm-up first
        log("reference: no request finished inside the window fits the "
            "reference's length: nothing of the timed path was compared")
        return {"tokens_checked": 0, "ok": False}
    got = served_readings(params, model, sequences, _CHECK["logit_pad"],
                          margin)
    shares = [g["within"] / g["tokens"] for g in got]
    ok = all(s >= _CHECK["served_min"] for s in shares)
    log("reference (its own selections and routing), sequences read whole, "
        "the warm-up's first, the rest finished inside the window: "
        f"{served_summary(got)}; within {margin} at least "
        f"{100 * _CHECK['served_min']:.0f} % asked of each")
    return {"tokens_checked": sum(g["tokens"] for g in got),
            "window_tokens": sum(g["tokens"] for g in got[1:]),
            "shares": shares, "ok": bool(ok)}


def served_summary(got: List[Dict[str, Any]]) -> str:
    """A line a sequence: context, served tokens, how many are the
    reference's argmax and how many lie within 0.1 / 0.25 / 0.5 / 1 of it
    (what ``margin`` and ``served_min`` are sized from)."""
    return "; ".join(
        f"context {g['context']}: {g['tokens']} tokens, {g['argmax']} the "
        f"argmax, within 0.1/0.25/0.5/1.0 "
        f"{[int((g['margins'] <= m).sum()) for m in (0.1, 0.25, 0.5, 1.0)]}"
        f" ({100 * g['within'] / g['tokens']:.1f} % within the margin)"
        for g in got)


def check_router(params, model, cfg, tapped, check: Mapping[str, Any],
                 log: Callable[[str], None]) -> Dict[str, Any]:
    """``serve_ssm_moe.check_router`` on this model's routed stack: the
    program's ``route`` against the reference's router at EVERY routed layer,
    over all ``n_routed_experts`` scores, both on what that layer's router
    read along the reference's pass over the last tapped sequence (the one
    ``check_logits`` made, held to the program's picks: the router is
    compared as a function, whatever pass its inputs come from)."""
    shaped = {"layers": {"E": {"moe": params["layers"]["S"]["moe"]}}}
    routed = dict(model, hybrid_override_pattern=None)
    with mock.patch.multiple(serve_ssm_moe, reference=_RouterFace(params)):
        return serve_ssm_moe.check_router(shaped, routed, cfg, tapped[-1:],
                                          check, log)


class _RouterFace:
    """What ``serve_ssm_moe.check_router`` asks of a reference module, from
    this one: the routed layers' inputs along a pass, and the router."""

    def __init__(self, params):
        self.params = params

    def router_inputs(self, _shaped, model, seq):
        import jax.numpy as jnp

        read, kept = _NOTES.get("router_pass", (None, None))
        if kept and np.array_equal(read, np.asarray(seq)):
            return [jnp.asarray(m) for m in kept]
        return reference.whole_pass(self.params, model, seq, last=1
                                    )["router_inputs"]

    router = staticmethod(reference.router)


def run(**kwargs) -> Dict[str, Any]:
    _CHECK.clear()
    _NOTES.clear()
    _CHECK["pools"] = kwargs["config"]["engine"].get("pools")
    clock = serve_ssm_moe.SetupClock()
    # the sample of sequences holds (prompt, tokens, rows, forced, picked);
    # ``serve_moe.run`` hands ``check_router`` and ``check_logits`` the list
    with mock.patch.multiple(
            serve_moe, program_config=program_config, reference=reference,
            make_params=make_params, tap_logits=tap_logits,
            check_logits=check_logits, check_router=check_router,
            check_served=check_served, MOE_SCOPES=SCOPES,
            HERE=ORDERED_START), \
            mock.patch.object(serve_moe.serve, "pick_sequences", pick_spread):
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
