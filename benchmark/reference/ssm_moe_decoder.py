"""Plain reference for a decoder of ONE mixer a layer, of three kinds
(NVIDIA-Nemotron-3-Nano-30B-A3B, ``model_type: nemotron_h``).

Written from the model's ``config.json`` and the published description of the
family (Mamba-2: Dao & Gu 2024, "Transformers are SSMs"; the router:
DeepSeek-V3's sigmoid scores with a correction bias), not from the program's
model file.  On one sequence ``x (S, hidden)``, layer ``i`` of kind
``hybrid_override_pattern[i]``:

    x <- x + mixer_i(RMSNorm(x; w_i, eps))

    M  [z | xBC | dt] = a W_in        (z d_inner, xBC d_inner + 2 G N, dt H)
       xBC_t = silu(sum_j cw[j] xBC_{t-3+j} + cb)     zeros before t = 0
       [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (outer) B_t[g(h)]
       y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]           S_{-1} = 0
       out = GroupRMSNorm(y * silu(z); groups of d_inner / G) W_out
    *  q, k, v = a Wq, a Wk, a Wv;  causal softmax(q k^T / sqrt(d)) v;  Wo
       grouped-query, no bias, NO positional embedding
    E  s = sigmoid(a Wr);  S = top-k of (s + correction bias);  w = s[S]
       w <- w / (sum w + 1e-20) (norm_topk_prob);  w <- routed_scaling w
       out = sum_{e in S} w_e relu(a Wup,e)^2 Wdown,e + relu(a Wup,sh)^2 Wdown,sh
    logits = RMSNorm(x_L; norm_f) Whead

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the recurrence is a ``lax.scan``
over single tokens (no chunks), the conv four shifted adds, every expert is
computed for every position (one at a time, summed under the top-k mask), an
``(S, S)`` causal mask; no kernel, no cache, no batching.  It shares no code
with the program.

Departures from the publication, all of them:

* The three projections of ``W_in`` are three matrices (``w_z``, ``w_xbc``,
  ``w_dt``): the same mathematics as one matrix of their columns side by side.
* Attention applies no rotary embedding (the configuration file's ``assumed``
  (a)); ``faults={"rope"}`` applies it, to show what that would change.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights``; int8 codes are dequantized here by
  ``dense_decoder.dense_weight``'s arithmetic.  The program stores the
  experts' width 1856 zero-padded to 1920 when it quantizes; the padded
  columns are zeros and read as such.
* A top-k tie goes to the lower expert index, as ``jax.lax.top_k`` breaks it.
* ``forced`` (None for the model): the experts each position is to use, layer
  by layer, in place of the reference's own top-k choice; the weights are
  still the reference's own scores at those experts.  With seeded random
  weights the sixth and the seventh of 128 scores lie thousandths apart, a
  chosen expert weighs 2.5 / 6 of the routed output, and a program in
  bfloat16 lands on the other side of such a tie at one position in eight a
  layer; every later position reads that through the states.  A comparison of
  logits therefore holds the reference to the choices the program made
  (``benchmark/routing_tap.py`` reads them out of its step programs) and
  compares the router itself directly (``serve_ssm_moe.check_router``).
* ``faults`` (a frozenset of names, empty for the model) turns the reference
  into a named WRONG program, one fault each: what the comparison that
  decides ``correct`` is sized against (``tests/test_nemotron3.py``,
  ``benchmark/tests/nemotron3_wrong_programs.py``).  ``ROUTER_FAULTS`` are
  wrong CHOICES alone (a pass held to ``forced`` does not read them): what
  ``own_choices`` is sized against.
* ``length`` (None for the model): the Mamba layers' states stand still from
  that position on (``dt`` 0: no decay, no input), so that a pass over a
  padded sequence also gives every layer's state after ``length`` tokens:
  what a server holds in a sequence's slot.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, FrozenSet, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (F32, _margins, dense_weight,
                                               head_logits, rms_norm, rope)

FAULTS = ("conv_bias", "D", "dt_bias", "norm_before_gate", "one_norm",
          "softmax_router", "bias_in_weights", "no_scaling", "no_shared",
          "relu", "rope", "state_bf16")
ROUTER_FAULTS = ("choice_without_bias", "router_bf16", "softmax_router")
NONE: FrozenSet[str] = frozenset()


def recurrence(x, dt, A, B, C, D, state, state_bf16: bool = False):
    """The state-space recurrence, one token at a time: ``x (S, H, P)``, ``dt
    (S, H)`` after its softplus, ``A, D (H,)``, ``B, C (S, H, N)`` (by head),
    ``state (H, P, N)`` → ``(y (S, H, P), the last state)``, float32.
    ``state_bf16`` keeps the state in bfloat16 between tokens: the wrong
    program of that name."""
    # (``reduce_precision``, not a cast there and back: XLA on the TPU keeps
    # excess precision through a pair of converts, and the fault vanished)
    keep = (lambda S: jax.lax.reduce_precision(S, 8, 7)) if state_bf16 \
        else (lambda S: S)

    def step(S, inp):
        x_t, dt_t, B_t, C_t = inp
        S = keep(jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    with jax.default_matmul_precision("highest"):
        S, y = jax.lax.scan(step, state.astype(F32), (x, dt, B, C))
    return y, S


@partial(jax.jit, static_argnames=("heads", "groups", "eps", "faults"))
def mamba(a: jax.Array, w: Dict[str, Any], length: Optional[jax.Array] = None,
          *, heads: int, groups: int, eps: float,
          faults: FrozenSet[str] = NONE) -> Tuple[jax.Array, jax.Array]:
    """The Mamba-2 mixer on one sequence ``a (S, hidden)`` from an empty
    state → (its output, the state ``(H, P, N)`` after the last position, or
    after ``length`` positions)."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        z = a @ dense_weight(w["w_z"])
        xbc = a @ dense_weight(w["w_xbc"])
        dt = a @ w["w_dt"].astype(F32)
        cw, cb = w["conv_w"].astype(F32), w["conv_b"].astype(F32)
        taps = cw.shape[0]
        padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), F32),
                                  xbc])
        conv = sum(cw[j] * padded[j:j + s] for j in range(taps))
        xbc = jax.nn.silu(conv if "conv_bias" in faults else conv + cb)
        d_inner = z.shape[1]
        n = (xbc.shape[1] - d_inner) // (2 * groups)
        x = xbc[:, :d_inner].reshape(s, heads, -1)
        B = xbc[:, d_inner:d_inner + groups * n].reshape(s, groups, n)
        C = xbc[:, d_inner + groups * n:].reshape(s, groups, n)
        B, C = (jnp.repeat(m, heads // groups, 1) for m in (B, C))
        dt = jax.nn.softplus(dt if "dt_bias" in faults
                             else dt + w["dt_bias"].astype(F32))
        if length is not None:
            dt = jnp.where(jnp.arange(s)[:, None] < length, dt, 0.0)
        A = -jnp.exp(w["A_log"].astype(F32))
        D = jnp.zeros_like(A) if "D" in faults else w["D"].astype(F32)
        y, state = recurrence(x, dt, A, B, C, D, jnp.zeros(x.shape[1:] + (n,), F32),
                          state_bf16="state_bf16" in faults)
        y = y.reshape(s, d_inner)
        gate, nw = jax.nn.silu(z), w["norm_w"].astype(F32)
        width = d_inner if "one_norm" in faults else d_inner // groups

        def group_norm(v):
            g = v.reshape(s, -1, width)
            g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
            return g.reshape(s, d_inner) * nw

        y = group_norm(y) * gate if "norm_before_gate" in faults \
            else group_norm(y * gate)
        return y @ dense_weight(w["w_out"]), state


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "faults"))
def attention(a: jax.Array, w: Dict[str, Any], *, heads: int, kv_heads: int,
              theta: float, faults: FrozenSet[str] = NONE) -> jax.Array:
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        q = (a @ dense_weight(w["wq"])).reshape(s, heads, -1)
        k = (a @ dense_weight(w["wk"])).reshape(s, kv_heads, -1)
        v = (a @ dense_weight(w["wv"])).reshape(s, kv_heads, -1)
        if "rope" in faults:
            q, k = rope(q, theta), rope(k, theta)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
        logits = jnp.einsum("shd,thd->hst", q, k) / jnp.sqrt(F32(q.shape[-1]))
        seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf), -1)
        o = jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)
        return o @ dense_weight(w["wo"])


@partial(jax.jit, static_argnames=("top_k", "norm_topk", "scaling", "faults"))
def router(m: jax.Array, w_router: jax.Array, bias: jax.Array, *, top_k: int,
           norm_topk: bool, scaling: float, faults: FrozenSet[str] = NONE,
           forced: Optional[jax.Array] = None
           ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """``m (S, hidden)`` → (the scores over all experts ``(S, E)``, the top-k
    weights ``(S, k)``, their experts ``(S, k)``, each position's margin: the
    k-th largest biased score less the (k+1)-th).  Float32.  ``forced (S,
    k)``: the experts to use where its row is not negative (module text)."""
    with jax.default_matmul_precision("highest"):
        if "router_bf16" in faults:  # scores in the activation type
            low = jnp.bfloat16
            s = jax.nn.sigmoid(m.astype(low) @ w_router.astype(low)
                               ).astype(F32)
        else:
            logits = m.astype(F32) @ w_router.astype(F32)
            s = jax.nn.softmax(logits, -1) if "softmax_router" in faults \
                else jax.nn.sigmoid(logits)
        biased = s if "choice_without_bias" in faults else s + bias.astype(F32)
        top, idx = jax.lax.top_k(biased, top_k + 1)
        margin = top[:, top_k - 1] - top[:, top_k]
        idx = idx[:, :top_k]
        if forced is not None:
            idx = jnp.where(forced[:, :1] >= 0, forced, idx)
        w = jnp.take_along_axis(biased if "bias_in_weights" in faults else s,
                                idx, -1)
        if norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        if "no_scaling" not in faults:
            w = w * scaling
        return s, w, idx, margin


@partial(jax.jit, static_argnames=("top_k", "norm_topk", "scaling", "faults"))
def moe(a: jax.Array, w: Dict[str, Any], forced: Optional[jax.Array] = None,
        *, top_k: int, norm_topk: bool, scaling: float,
        faults: FrozenSet[str] = NONE) -> Tuple[jax.Array, jax.Array]:
    """The MoE mixer on ``a (S, hidden)`` → (its output, the router margin
    of each position)."""
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        p, top, idx, margin = router(a, w["router"], w["router_bias"],
                                     top_k=top_k, norm_topk=norm_topk,
                                     scaling=scaling, faults=faults,
                                     forced=forced)
        gates = jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(top)

        def act(v):
            r = jax.nn.relu(v)
            return r if "relu" in faults else r * r

        def one(y, e):  # every expert on every position, one at a time
            up, down = (dense_weight(jax.tree.map(lambda t: t[e], w[k]))
                        for k in ("w_in", "w_out"))
            return y + gates[:, e, None] * (act(a @ up) @ down), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(a),
                            jnp.arange(w["router"].shape[-1]))
        if "sh_w_in" in w and "no_shared" not in faults:
            y = y + act(a @ dense_weight(w["sh_w_in"])) @ dense_weight(
                w["sh_w_out"])
        return y, margin


def layer_weights(params: Mapping[str, Any], kind: str, i: int
                  ) -> Dict[str, Any]:
    """Layer ``i`` of kind ``kind``'s stack in the program's parameter tree
    (leaves stacked on a leading layer axis BY KIND; a quantized leaf keeps
    its node type) under this file's names.  The only place that knows the
    program's layout."""
    lay = params["layers"][kind]
    take = partial(jax.tree.map, lambda t: t[i])
    inner = {"M": "mamba", "E": "moe", "*": "attn"}[kind]
    return {"norm": lay["norm"]["scale"][i],
            **{k: take(v) for k, v in lay[inner].items()}}


def _blocks(params: Mapping[str, Any], model: Mapping[str, Any],
            tokens: jax.Array, layers: int, faults: FrozenSet[str],
            forced: Optional[jax.Array] = None,
            length: Optional[jax.Array] = None):
    """The first ``layers`` layers on ``tokens (S,)`` → (the last one's
    output, the smallest router margin of each position over them, every MoE
    layer's router input, every Mamba layer's state at the end or after
    ``length`` positions)."""
    x = params["embed"]["tokens"][tokens].astype(F32)
    eps = float(model["norm_eps"])
    margin, inputs, states = jnp.full(tokens.shape, jnp.inf, F32), [], []
    seen = {"M": 0, "E": 0, "*": 0}
    for kind in model["hybrid_override_pattern"][:layers]:
        w = layer_weights(params, kind, seen[kind])
        seen[kind] += 1
        a = rms_norm(x, w["norm"], eps)
        if kind == "M":
            out, state = mamba(a, w, length, heads=model["mamba_num_heads"],
                               groups=model["n_groups"], eps=eps,
                               faults=faults)
            states.append(state)
        elif kind == "*":
            out = attention(a, w, heads=model["num_attention_heads"],
                            kv_heads=model["num_key_value_heads"],
                            theta=float(model["rope_theta"]), faults=faults)
        else:
            out, mg = moe(a, w,
                          None if forced is None else forced[seen["E"] - 1],
                          **_router_keys(model), faults=faults)
            margin = jnp.minimum(margin, mg)
            inputs.append(a)
        x = x + out
    return x, margin, inputs, states


def _router_keys(model: Mapping[str, Any]) -> Dict[str, Any]:
    return dict(top_k=model["num_experts_per_tok"],
                norm_topk=bool(model["norm_topk_prob"]),
                scaling=float(model["routed_scaling_factor"]))


def router_inputs(params: Mapping[str, Any], model: Mapping[str, Any],
                  tokens: jax.Array, forced: Optional[jax.Array] = None
                  ) -> list:
    """``tokens (S,)`` → what every MoE layer's router reads, in layer order:
    ``RMSNorm(x; w)`` of that layer, float32, along the pass that ``forced``
    steers (None: the reference's own)."""
    return _blocks(params, model, tokens,
                   len(model["hybrid_override_pattern"]), NONE, forced)[2]


def logits_and_margin(params: Mapping[str, Any], model: Mapping[str, Any],
                      tokens: jax.Array, last: Optional[int] = None,
                      faults: FrozenSet[str] = NONE,
                      forced: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """``tokens (S,)`` → (next-token logits in float32, each position's
    smallest router margin); with ``last`` only the final ``last`` positions
    go through the head.  ``forced (MoE layers, S, k)``: the experts each
    position uses (a row of -1: the reference's own choice)."""
    out = whole_pass(params, model, tokens, last, faults, forced)
    return out["logits"], out["margin"]


def whole_pass(params: Mapping[str, Any], model: Mapping[str, Any],
               tokens: jax.Array, last: Optional[int] = None,
               faults: FrozenSet[str] = NONE,
               forced: Optional[jax.Array] = None,
               length: Optional[int] = None) -> Dict[str, Any]:
    """One pass over ``tokens (S,)`` and everything a comparison reads from
    it: ``logits`` and ``margin`` (``logits_and_margin``), ``router_inputs``
    (a list, MoE layer by MoE layer) and ``states (Mamba layers, H, P, N)``:
    every Mamba layer's state after ``length`` positions (None: after all)."""
    x, margin, inputs, states = _blocks(
        params, model, tokens, len(model["hybrid_override_pattern"]),
        frozenset(faults), forced,
        None if length is None else jnp.int32(length))
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    return {"logits": head_logits(x, params["final_norm"]["scale"],
                                  params["lm_head"]["w"],
                                  eps=float(model["norm_eps"])),
            "margin": margin, "router_inputs": inputs,
            "states": jnp.stack(states) if states else None}


def logits(params: Mapping[str, Any], model: Mapping[str, Any],
           tokens: jax.Array, last: Optional[int] = None,
           faults: FrozenSet[str] = NONE,
           forced: Optional[jax.Array] = None) -> jax.Array:
    return logits_and_margin(params, model, tokens, last, faults, forced)[0]


def own_choices(params: Mapping[str, Any], model: Mapping[str, Any],
                inputs: list, faults: FrozenSet[str] = NONE) -> jax.Array:
    """The experts the reference's router (``faults``: a wrong router of
    ``ROUTER_FAULTS``) picks at every position of every MoE layer ``(MoE
    layers, S, k)``, on ``inputs``: what each layer's router read along some
    pass (``whole_pass``'s ``router_inputs``)."""
    picked = []
    for i, m in enumerate(inputs):
        w = layer_weights(params, "E", i)
        picked.append(router(m, w["router"], w["router_bias"],
                             **_router_keys(model),
                             faults=frozenset(faults))[2])
    return jnp.stack(picked)


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference, which reads
    the whole sequence in one uncached pass (``dense_decoder``'s rule)."""
    lg = logits(params, model, sequence, last=len(sequence) - n_prompt + 1)
    return _margins(lg[:-1], sequence[n_prompt:])
