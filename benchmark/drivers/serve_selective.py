"""Driver ``serve_selective``: the HTTP front over one ``InferenceEngineV2``
for a model whose layers are a Mamba-1 mixer (or attention without
positions) AND a dense FFN, served whole in bfloat16 (AI21-Jamba2-3B), under
load from the benchmark's generator.

The server, the load generator (``benchmark/ordered_start/loadgen.py``), the
span collection and the trace reduced by kernel and scope name are
``drivers/serve.py``'s and ``drivers/serve_moe.py``'s, imported.  What this
driver does itself is decide ``correct`` FROM WHAT THE TIMED PATH PRODUCED IN
THE WINDOW:

* ``WindowTap`` wraps the engine's mixed step (this cell has a prompt waiting
  at all times, so every step is a mixed step, and the mixed step returns its
  logits whether anyone reads them): once the window is open it takes the
  first sequence whose prompt is at or above the traffic's median (and at
  most ``check.tap_longest``) and the first ``tap_sequences - 1`` others, and
  for each step in which such a sequence gets a token keeps that row of the
  step's logits ON THE DEVICE (a 256 KB slice; nothing is fetched inside the
  window), and at its last step the sequence's slot of the state array.
  The programs, their arguments and their results are the served ones.
* after the drain the kept rows come to the host, the engine is freed, and
  ``benchmark/reference/selective_ssm_decoder.py`` reads each tapped
  sequence whole (prompt, then what the server sent) in ONE uncached float32
  pass at the published widths, its attention in blocks of queries: the
  logits of the prompt's last position and of every decoded position, and
  every Mamba layer's final state.  Chunked prefill (4 to 32 mixed steps a
  prompt, the state carried between them), then decoding through the K/V
  pool and the state slots in mixed steps beside other rows' chunks, against
  a full forward of the same tokens.
* what logits cannot see, the state's precision, is held on the engine's own
  arrays as ``serve_ssm_moe`` holds it: the types the file states
  (``engine.state``) and the share of state elements with low mantissa bits.

``checks`` carries each number compared beside its limit.
"""

from __future__ import annotations

import gc
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Mapping, Optional
from unittest import mock

import numpy as np

from benchmark import common, loadgen
from benchmark.drivers import serve, serve_moe
from benchmark.drivers.serve_ssm_moe import SetupClock, low_bits_share
from benchmark.reference import selective_ssm_decoder as reference

ORDERED_START = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "ordered_start")

#: the scopes the traced run reduces by; the kernels' own first, so that an
#: operation inside ``sel_scan/selective_scan`` counts under the inner name
SCOPES = ("selective_scan", "selective_decode_update", "sel_in_proj",
          "sel_conv", "sel_x_proj", "sel_scan", "sel_gate", "sel_out_proj",
          "dense_ffn", "prefill_attention", "cache_write", "lm_head")

#: published key -> attribute of the program's ``TransformerConfig``
PUBLISHED = {
    "hidden_size": "hidden_size", "vocab_size": "vocab_size",
    "intermediate_size": "intermediate_size",
    "num_attention_heads": "num_heads", "num_key_value_heads": "kv_heads",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq_len",
    "mamba_d_conv": "mamba_conv_kernel", "mamba_d_state": "mamba_state_size",
    "mamba_dt_rank": "mamba_dt_rank", "mamba_expand": "mamba_expand",
    "hidden_act": "activation"}
#: what ``model_type: jamba`` implies without a key
IMPLIED = {"position": "none", "norm": "rmsnorm", "is_gated_mlp": True,
           "num_experts": 0, "sliding_window": 0, "qk_norm": False}
#: the published keys the reference and the readers take
MODEL_KEYS = ("num_hidden_layers", "attn_layer_period", "attn_layer_offset",
              "hidden_size", "intermediate_size", "vocab_size",
              "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
              "mamba_d_conv", "mamba_d_state", "mamba_dt_rank",
              "mamba_expand")


def pattern_of(layers: int, period: int, offset: int) -> str:
    """The family's rule as the program's pattern of sub-layers: a mixer
    (attention at ``i % period == offset``, Mamba elsewhere) and an FFN."""
    return "".join(("*" if i % period == offset else "S") + "F"
                   for i in range(layers))


def program_config(config: Mapping[str, Any]):
    """→ (the program's configuration for this file, the published sizes for
    the reference and the readers); refused if anything the file states
    differs from what the program's preset computes."""
    from deepspeed_tpu.models import transformer as tfm

    cfg = tfm.get_config(config["preset"], **config.get("overrides", {}))

    def refuse(what, said, gives):
        raise ValueError(f"configuration {config['name']}: the file says "
                         f"{what} = {said}, the program's preset gives "
                         f"{gives}")

    for key, attr in PUBLISHED.items():
        if getattr(cfg, attr) != config[key]:
            refuse(key, config[key], getattr(cfg, attr))
    for attr, value in IMPLIED.items():
        if getattr(cfg, attr) != value:
            raise ValueError(
                f"configuration {config['name']}: model_type "
                f"{config['model_type']} needs {attr} = {value}, the "
                f"program's preset gives {getattr(cfg, attr)}")
    for key in ("mamba_proj_bias", "sliding_window"):
        if config.get(key):
            raise ValueError(f"configuration {config['name']}: {key} = "
                             f"{config[key]} is not something the program "
                             f"computes")
    if not config["mamba_conv_bias"] or config["num_experts"] != 1 \
            or config["num_experts_per_tok"] != 1:
        raise ValueError(f"configuration {config['name']}: the program's "
                         f"conv has a bias and its FFN is dense")
    pattern = pattern_of(config["num_hidden_layers"],
                         config["attn_layer_period"],
                         config["attn_layer_offset"])
    if tuple(pattern) != cfg.mixer_pattern:
        refuse("the layer order (attn_layer_period, attn_layer_offset)",
               pattern, "".join(cfg.mixer_pattern))
    if cfg.head_dim * cfg.num_heads != config["hidden_size"]:
        refuse("head size", config["hidden_size"] // cfg.num_heads,
               cfg.head_dim)
    return cfg, {k: config[k] for k in MODEL_KEYS}


def published_model(cfg) -> Dict[str, Any]:
    """The other way: the published keys the reference reads, from a program
    configuration (the tier-1 tests and ``chip_smoke.py``, which start from
    a preset and have no file)."""
    mixers = [k for k in cfg.mixer_pattern if k != "F"]
    attn = [i for i, k in enumerate(mixers) if k == "*"]
    period = attn[1] - attn[0] if len(attn) > 1 else len(mixers)
    return dict(num_hidden_layers=len(mixers), attn_layer_period=period,
                attn_layer_offset=attn[0], hidden_size=cfg.hidden_size,
                intermediate_size=cfg.intermediate_size,
                vocab_size=cfg.vocab_size, num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.kv_heads, rms_norm_eps=cfg.norm_eps,
                mamba_d_conv=cfg.mamba_conv_kernel,
                mamba_d_state=cfg.mamba_state_size,
                mamba_dt_rank=cfg.mamba_dt_rank,
                mamba_expand=cfg.mamba_expand)


def draw_small_tensors(params, seed):
    """The tensors ``init_params`` leaves at a constant, drawn from ``seed``
    (an int or a PRNG key), uniform in [0.5, 1.5): every norm's scale (the
    sub-layers', the final one, and the three inside a Mamba mixer: dt, B,
    C) and ``D``.  At 1 a norm's scale read from the wrong layer, or left
    out, would not show."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed) if isinstance(seed, int) else seed

    def draw(leaf, salt):
        return jax.random.uniform(
            jax.random.fold_in(key, salt), leaf.shape,
            jnp.float32, 0.5, 1.5).astype(leaf.dtype)

    lay = params["layers"]
    for i, kind in enumerate(("S", "F", "*")):
        lay[kind]["norm"]["scale"] = draw(lay[kind]["norm"]["scale"], 0x71 + i)
    for i, name in enumerate(("dt_norm", "b_norm", "c_norm", "D")):
        lay["S"]["mamba"][name] = draw(lay["S"]["mamba"][name], 0x81 + i)
    params["final_norm"]["scale"] = draw(params["final_norm"]["scale"], 0x91)
    return params


def make_params(cfg, seed: int, bits: int = 0, group: int = 0):
    """The whole parameter tree on the device in the type it is served in
    (bfloat16: ``weight_bits`` 0), from one jitted call: each kind's stack a
    layer at a time (``lax.map``)."""
    import dataclasses

    import jax

    from deepspeed_tpu.models import transformer as tfm

    if bits:
        raise ValueError("driver serve_selective serves bfloat16 weights: "
                         "engine.weight_bits must be 0")
    kinds = sorted(set(cfg.mixer_pattern))

    def one_of(kind):
        # an "F" or a "*" alone is no served model, but it initialises; an
        # "S" needs no partner to initialise either
        return dataclasses.replace(cfg, num_layers=1, mixer_pattern=(kind,))

    def whole(key):
        k_rest, *k_kinds = jax.random.split(key, 1 + len(kinds))
        params = tfm.init_params(k_rest, one_of("*"))  # embedding, norm
        layers = {}
        for kind, k in zip(kinds, k_kinds):
            def layer(key, kind=kind):
                return jax.tree.map(
                    lambda a: a[0],
                    tfm.init_params(key, one_of(kind))["layers"][kind])

            layers[kind] = jax.lax.map(
                layer, jax.random.split(k, cfg.layers_of(kind)))
        params["layers"] = layers
        return draw_small_tensors(params, key)  # no constant of the seed

    return jax.jit(whole)(jax.random.PRNGKey(seed))


class WindowTap:
    """While installed and armed, keeps on the device the logits row of
    every token a tapped sequence gets from a MIXED step, and the sequence's
    state slot after its last step.  A sequence is taken at its first chunk:
    the first whose prompt holds ``long_min`` to ``long_max`` tokens, and the
    first ``others`` with at most ``long_max``.  The step programs, their
    arguments and what the engine does with their results are untouched: a
    kept row is a slice of the step's own output."""

    def __init__(self, engine, long_min: int, long_max: int, others: int):
        self.engine = engine
        self.long_min, self.long_max, self.others = long_min, long_max, others
        self.armed = False
        self.seqs: Dict[int, Dict[str, Any]] = {}
        self.decode_steps = 0
        self._saved = (engine._fwd, engine._decode_fwd, engine.builder.build)
        self._picks: list = []
        fwd, decode_fwd, build = self._saved
        # the two slices the tap makes, compiled HERE for a traced index:
        # an index that is a constant would be a program a row, compiled
        # inside the window
        import jax

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        ssm = engine.caches["ssm"]
        i32 = sds((), np.int32)
        self._row = jax.jit(lambda a, i: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False)).lower(
                sds((engine.cfg.max_seqs, engine.model_cfg.vocab_size),
                    np.float32), i32).compile()
        self._slot = jax.jit(lambda a, i: jax.lax.dynamic_index_in_dim(
            a, i, 1, keepdims=False)).lower(
                sds(ssm.shape, ssm.dtype), i32).compile()

        def tapped_build(picks):
            self._picks = list(picks)
            return build(picks)

        def tapped_fwd(params, caches, *args):
            out = fwd(params, caches, *args)
            if self.armed:
                self._keep(out)
            return out

        def tapped_decode(params, caches, *args):
            out = decode_fwd(params, caches, *args)
            if self.armed and self.live():
                # a token no mixed step's row holds; a sequence that ends
                # here still leaves its slot to be read
                self.decode_steps += 1
                for uid in self.live():
                    self._last_step(self.seqs[uid], out[1])
            return out

        engine._fwd, engine._decode_fwd = tapped_fwd, tapped_decode
        engine.builder.build = tapped_build

    def live(self) -> List[int]:
        return [u for u, s in self.seqs.items() if s["state"] is None]

    def _take(self, seq) -> bool:
        n = len(seq.tokens)
        if seq.seen_tokens or seq.uid in self.seqs or n > self.long_max:
            return False
        # a tapped sequence that was cancelled (a client's first, cut
        # request) gives its place to the next
        held = [s for s in self.seqs.values()
                if s["state"] is not None or not s["seq"].done]
        is_long = n >= self.long_min and not any(s["long"] for s in held)
        if not is_long and sum(not s["long"] for s in held) >= self.others:
            return False
        self.seqs[seq.uid] = {"prompt": n, "long": is_long, "rows": [],
                              "seq": seq, "tokens": None, "state": None,
                              "t_first": time.monotonic(), "t_last": None}
        return True

    def _keep(self, out) -> None:
        logits, caches = out[0], out[2]
        for row, (seq, n) in enumerate(self._picks):
            if seq.uid not in self.seqs and not self._take(seq):
                continue
            kept = self.seqs[seq.uid]
            if kept["state"] is not None \
                    or seq.seen_tokens + n < seq.cur_len:
                continue  # done, or a chunk that ends inside the prompt
            kept["rows"].append((seq.cur_len - 1,
                                 self._row(logits, np.int32(row))))
            self._last_step(kept, caches)

    def _last_step(self, kept, caches) -> None:
        """``kept``'s sequence gets a token from the step under way; if it
        is its last: the slot it leaves, and how many tokens it has read
        (the tokens themselves are read after the window: a step called
        behind the one under way finds the token before still a placeholder
        in the descriptor)."""
        seq = kept["seq"]
        if seq.generated + 1 >= seq.max_new_tokens and seq.state_slot >= 0:
            kept["tokens"] = (seq, seq.cur_len)
            kept["state"] = self._slot(caches["ssm"],
                                       np.int32(seq.state_slot))
            kept["t_last"] = time.monotonic()

    def remove(self) -> None:
        e = self.engine
        e._fwd, e._decode_fwd, e.builder.build = self._saved

    def finished(self, t_open: float, t_close: float, want: int
                 ) -> List[Dict[str, Any]]:
        """The tapped sequences that began and ended inside the window, the
        long one first, ``want`` at most, their rows and states fetched."""
        done = [s for s in self.seqs.values() if s["state"] is not None
                and t_open <= s["t_first"] and s["t_last"] < t_close]
        done.sort(key=lambda s: (not s["long"], s["t_first"]))
        out = []
        for s in done[:want]:
            seq, n = s["tokens"]
            out.append({"prompt": s["prompt"], "long": s["long"],
                        "tokens": list(seq.tokens[:n]),
                        "rows": [(pos, np.asarray(row, np.float32))
                                 for pos, row in s["rows"]],
                        "state": np.asarray(s["state"], np.float32)})
        return out


def sequence_errors(params, model, tapped: Mapping[str, Any], pad: int,
                    faults=()):
    """One tapped sequence against ONE pass of the reference (``faults``: a
    named wrong program of it): → (largest |engine - reference| over the
    vocabulary of every kept row; ``(Mamba layers,)``: the largest difference
    between the engine's slot and the reference's state after the last token
    the engine read, as a share of that state's largest element)."""
    import jax.numpy as jnp

    tokens, rows = tapped["tokens"], tapped["rows"]
    n = len(tokens)
    seq = np.zeros(-(-n // pad) * pad, np.int32)
    seq[:n] = tokens
    first = tapped["prompt"] - 1  # the first kept row reads this position
    out = reference.whole_pass(params, model, jnp.asarray(seq),
                               last=len(seq) - first,
                               faults=frozenset(faults), length=n)
    want = np.asarray(out["logits"])
    errs = np.asarray([float(np.abs(row - want[pos - first]).max())
                       for pos, row in rows])
    # the engine keeps (N, d_inner), the reference (d_inner, N)
    final = np.swapaxes(np.asarray(out["states"]), 1, 2)
    state = np.abs(tapped["state"] - final).max((1, 2)) \
        / np.abs(final).max((1, 2))
    return errs, state


def check_window(params, model, tapped: List[Mapping[str, Any]],
                 check: Mapping[str, Any], stated: Mapping[str, str],
                 dtypes: Mapping[str, str], log: Callable[[str], None]
                 ) -> Dict[str, list]:
    """The window's tapped sequences against the reference → name ->
    [number, limit] for ``checks``.  Two bounds on the rows:
    ``logit_tol_median`` on the median row for a systematic fault,
    ``logit_tol`` on the worst row for a local one (a state read from the
    wrong slot or left from the sequence before, a stale K/V block, a chunk
    that lost the state between two steps).  ``state_tol``: each sequence's
    slot against the reference's final state, Mamba layer by Mamba layer.
    ``state_low_bits_min``: the slots hold what bfloat16 cannot."""
    errs, states = [], []
    for t in tapped:
        e, s = sequence_errors(params, model, t, check["logit_pad"])
        errs.append(e)
        states.append(s)
        log(f"tapped sequence: prompt {t['prompt']}, {len(t['rows'])} rows "
            f"kept of {len(t['tokens']) - t['prompt'] + 1} tokens given; "
            f"|engine - reference| median {np.median(e):.4f}, worst "
            f"{e.max():.4f}; state, worst Mamba layer {s.max():.4f} (first "
            f"{s[0]:.4f}, last {s[-1]:.4f})")
    longest = max((t["prompt"] for t in tapped if t["long"]), default=0)
    rows = np.concatenate(errs) if errs else np.asarray([np.inf])
    state = np.concatenate(states) if states else np.asarray([np.inf])
    low = low_bits_share(np.stack([t["state"] for t in tapped])) \
        if tapped else 0.0
    finite = bool(np.isfinite(rows).all() and np.isfinite(state).all())
    return {
        "window_sequences": [len(tapped), check["window_sequences"]],
        "window_long_prompt": [longest, check["tap_long_min"]],
        "logit_rows_not_finite": [0.0 if finite else 1.0, 0],
        "logit_median": [float(np.median(rows)), check["logit_tol_median"]],
        "logit_worst": [float(rows.max()), check["logit_tol"]],
        "state_worst": [float(state.max()), check["state_tol"]],
        "state_low_bits": [float(low or 0.0), check["state_low_bits_min"]],
        "state_types_as_stated": [float(dict(dtypes) == dict(stated)), 1],
    }


#: tooling (``benchmark/tests/jamba2_wrong_programs.py``): a dict put here
#: before ``run`` receives the run's parameters, model and tapped sequences
KEEP: Optional[Dict[str, Any]] = None

#: checks held from below (the others from above)
AT_LEAST = ("window_sequences", "window_long_prompt", "state_low_bits",
            "state_types_as_stated", "kv_blocks_free", "state_slots_free",
            "mixed_step_share")


def failed_checks(checks: Mapping[str, list]) -> List[str]:
    return sorted(
        k for k, (v, lim) in checks.items()
        if not np.isfinite(v) or (v < lim if k in AT_LEAST else v > lim))


def run(*, cell: Mapping[str, Any], config: Mapping[str, Any],
        traffic: Mapping[str, Any], seed: int, seconds: float, trace: bool,
        device: Mapping[str, Any], t_ready: float,
        log: Callable[[str], None]) -> Dict[str, Any]:
    if traffic["loop"] != "closed":
        raise ValueError("driver serve_selective runs a closed loop, not "
                         f"{traffic['loop']!r}")
    import jax

    compiles = common.start_jax(log)
    clock = SetupClock()
    cfg, model = program_config(config)
    eng, check = config["engine"], config["check"]
    t0 = time.monotonic()
    params = make_params(cfg, seed, eng["weight_bits"])
    jax.block_until_ready(params)
    log(f"{config['name']}: {model['num_hidden_layers']} layers "
        f"({cfg.layers_of('S')} Mamba-1, {cfg.layers_of('*')} attention, "
        f"{cfg.layers_of('F')} FFN), {cfg.num_params() / 1e9:.3f} B "
        f"parameters in bfloat16, made on the device in "
        f"{time.monotonic() - t0:.1f}s")
    pool, engine, server, scfg = serve.build_server(cfg, params, config)
    port = server.server_port
    total_blocks, total_slots = engine.total_blocks, engine.total_state_slots

    # warm-up: one request whose prompt is longer than a step's token budget
    # compiles the mixed step (twice run: chunked prefill), the sampler and
    # the decode step; shapes are static, so these are all there are
    warm = {"prompt": np.random.default_rng([seed, 0xBEEF]).integers(
                1, cfg.vocab_size, size=check["warmup_prompt"]).tolist(),
            "max_tokens": check["warmup_tokens"]}
    rec = loadgen.Record(0xBEEF, 0, len(warm["prompt"]), warm["max_tokens"],
                         due=time.monotonic())
    loadgen.stream_completion(port, warm, rec, None, timeout_s=1100.0)
    if rec.status != "ok":
        raise RuntimeError(f"warm-up request failed: {rec.status}")
    log(f"warm-up request done ({rec.done - rec.due:.1f}s)")
    fallbacks = serve_moe.kernel_fallbacks(since=t_ready)

    session = None
    if trace:  # spans round the calls into the program, from outside
        engine.step = common.annotated(engine.step, "bench/engine.step")
        engine._fwd = serve_moe.StepProgram(engine._fwd, "bench/_fwd")
        engine._decode_fwd = serve_moe.StepProgram(engine._decode_fwd,
                                                   "bench/_decode_fwd")
        session = serve_moe.TraceSession(log, [engine._fwd,
                                               engine._decode_fwd])
    tap = WindowTap(engine, check["tap_long_min"], check["tap_longest"],
                    check["tap_sequences"] - 1)

    # the window, on the clock every process of this machine shares
    t_open = time.monotonic() + traffic["lead_s"] + traffic["ramp_s"]
    t_close = t_open + seconds
    spec = {"traffic": dict(traffic), "seed": seed, "vocab": cfg.vocab_size,
            "port": port, "t_open": t_open, "t_close": t_close,
            "timeout_s": traffic["request_timeout_s"]}
    with tempfile.TemporaryDirectory(prefix="bench-load-") as tmp:
        spec_path = os.path.join(tmp, "spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(ORDERED_START, "loadgen.py"),
             spec_path], stdout=subprocess.PIPE)
        try:
            collector = serve.SpanCollector().start()
            tracer_thread = session.run_beside(
                t_open + traffic["trace_after_s"],
                traffic["trace_seconds"]) if session else None
            time.sleep(max(0.0, t_open - time.monotonic()))
            tap.armed = True
            setup_s = t_open - t_ready
            log(f"window opens; set-up {setup_s:.1f}s")
            time.sleep(max(0.0, t_close - time.monotonic()))
            peak = common.memory_peak_bytes()
            log("window closed; waiting for the generator")
            out, _ = child.communicate(
                timeout=traffic["request_timeout_s"] + 60.0)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
    if child.returncode != 0:
        raise RuntimeError(f"load generator exited {child.returncode}")
    records = json.loads(out)["records"]
    if tracer_thread:
        tracer_thread.join()
    spans = collector.finish(t_open, t_close)

    # drain and shut down as the server's own main does, then the cache and
    # the slots must be whole again
    pool.drain(scfg.drain_timeout_s)
    server.shutdown()
    server.server_close()
    tap.remove()
    free, free_slots = engine.free_blocks, engine.free_state_slots
    log(f"drained: {free} of {total_blocks} KV blocks and {free_slots} of "
        f"{total_slots} state slots free")
    programs_in_window = compiles.between(t_open, t_close)
    with mock.patch.object(serve_moe, "MOE_SCOPES", SCOPES):
        reduced = session.reduce() if session else None  # reads the programs
    tapped = tap.finished(t_open, t_close, check["window_sequences"])
    dtypes = {k: str(engine.caches[k].dtype) for k in ("ssm", "conv")}
    log(f"tapped in the window: {len(tap.seqs)} sequences taken (prompts "
        f"{[s['prompt'] for s in tap.seqs.values()]}), {len(tapped)} began "
        f"and ended inside it; {tap.decode_steps} decode-only steps gave a "
        f"tapped sequence a token no mixed step's row holds")
    del engine, pool, server, tap
    gc.collect()

    # what the window holds: a closed loop's request is attempted when it
    # ended in the window (or was still running at its close: no failure)
    in_window = [r for r in records if r["status"] != "pending"
                 and t_open <= r["done"] and r["due"] < t_close]
    attempted = len(in_window)
    failed = sum(r["status"] not in ("ok", "cut") for r in in_window)
    short = sum(1 for r in in_window if r["status"] == "ok"
                and len(r["tokens"]) != r["asked"])
    steps = [s for s in spans if s["name"] == "engine/step"
             and s["attrs"].get("kind") in ("mixed", "decode")]
    mixed = sum(s["attrs"]["kind"] == "mixed" for s in steps)

    t0 = time.monotonic()
    checks = check_window(params, model, tapped, check, eng["state"], dtypes,
                          log)
    checks.update(
        kv_blocks_free=[free, total_blocks],
        state_slots_free=[free_slots, total_slots],
        failed_requests=[failed, 0],
        answers_not_max_tokens=[short, 0],
        kernel_fallbacks=[fallbacks, 0],
        mixed_step_share=[mixed / max(len(steps), 1),
                          check["mixed_step_share_min"]])
    wrong = failed_checks(checks)
    if KEEP is not None:
        KEEP.update(params=params, model=model, tapped=tapped, check=check)
    log(f"reference read {len(tapped)} sequences in "
        f"{time.monotonic() - t0:.1f}s; "
        + ("every check holds" if not wrong else f"FAILED: {wrong}"))
    log(f"set-up {setup_s:.1f}s; JAX's own events before the window "
        f"opened, summed (how many): {clock.before(t_open)}")
    by_name = (reduced or {}).get("by_name")
    if by_name:  # the traced run: where the device's time went, for the log
        rows = sorted({**by_name["scope_s"], **{
            f"{k} (kernel)": v for k, v in by_name["kernel_s"].items()}
        }.items(), key=lambda kv: -kv[1])
        log("device seconds by scope and kernel, of "
            f"{by_name['busy_s']:.3f} busy: " + ", ".join(
                f"{k} {v:.4f}" for k, v in rows[:40]))
    for r in records:  # prompts were for the check only
        r.pop("prompt", None)
    return {
        "correct": not wrong and attempted > 0,
        "checks": checks,
        "attempted": attempted,
        "failed": failed,
        "setup_s": setup_s,
        "window": {"t_open": t_open, "t_close": t_close, "seconds": seconds},
        "requests": records,
        "spans": spans,
        "compiles_in_window": programs_in_window,
        "memory_peak_bytes": peak,
        "device": dict(device),
        "chips": cell["chips"],
        "model": model,
        "engine": dict(eng),
        "trace": reduced,
    }
