"""Plain reference for a decoder with latent attention (MLA), a learned
indexer that picks the keys a query attends over and shares its pick with the
layers behind it (DSA with IndexShare), leading dense layers and sigmoid-routed
experts of which one chip holds a share (GLM-5.2, ``model_type:
glm_moe_dsa``).

Written from the model's ``config.json`` and the published descriptions of the
family (MLA: DeepSeek-V2; the indexer: DeepSeek-V3.2's sparse attention; the
router: DeepSeek-V3's sigmoid scores with a correction bias), not from the
program's model file.  On one sequence ``x (S, hidden)``, layer ``i``, ``h =
RMSNorm(x; ln1)``, position ``t``, keys ``s <= t``:

    c_q = RMSNorm(h W_qa);  [q_nope | q_rope]_j = (c_q W_qb)_j     64 heads
    [c_kv | k_rope] = h [W_kva | W_kr];  c_kv <- RMSNorm(c_kv)
    RoPE (adjacent pairs) on q_rope and on k_rope (one, shared by the heads)
    [k_nope | v]_j = (c_kv W_kvb)_j
    a_{t,s,j} = (q_nope_j . k_nope_j + q_rope_j . k_rope) / sqrt(nope + rope)
    p = softmax over s in S_t;  o_j = sum_s p v_j;  x <- x + concat(o) W_o

    indexer_types[i] == "full":
    qI_j = (c_q W_Iq)_j  (J heads of D);  kI = LayerNorm(h W_Ik) (one head)
    RoPE (adjacent pairs) on the first qk_rope_head_dim dims of both
    w = h W_Iw / sqrt(J D);  I_{t,s} = sum_j w_j relu(qI_j . kI_s)
    S_t = the index_topk keys s <= t of largest I_{t,s} (every key while
    t < index_topk; a tie goes to the lower position)
    "shared": S_t of the nearest "full" layer before

    m = RMSNorm(x; ln2)
    "dense":  x <- x + (silu(m W_gate) * (m W_in)) W_out
    "sparse": s = sigmoid(m W_r) over ALL n_routed_experts;  the top k of (s +
              correction bias);  w = s at those, / their sum, x
              routed_scaling_factor;  x <- x + sum over the chosen experts
              THIS CHIP HOLDS of w_e SwiGLU_e(m) + SwiGLU_shared(m)
    logits = RMSNorm(x_L; norm_f) W_head

The EXPANDED form: every key's ``k_nope`` and ``v`` are made from its latent
and an ``(S, S)`` mask carries the selection; queries in blocks of
``_QUERY_BLOCK`` (``attend``: ``_ATTEND_BLOCK``) so that a pass over the
longest context the cell serves, 17,408 positions at the published widths,
fits beside the weights (``attend`` 3.0 GB of temp there, by the TPU
compiler's account).  Float32 under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching.  It shares no code with the program.

Departures and choices, all of them:

* THE SHARE.  ``model["experts_held"]`` experts from ``model["first_expert"]``
  on are this chip's (the weights' expert axis holds just them).  The router
  scores all ``n_routed_experts`` and picks its top k among all; what the
  absent experts would have added is left out, in the program and here alike
  (the guide's rule for one chip of an expert-parallel group), and that
  partial sum goes on to the next layer.  ``faults={"absent_expert"}``
  computes one absent expert with a held one's weights;
  ``{"held_left_out"}`` drops one held expert.
* ``W_kva`` and ``W_kr`` are two matrices, the same mathematics as one of
  their columns side by side.
* Weights are whatever tree the caller hands in, read through
  ``layer_weights``; int8 codes are dequantized by ``dense_decoder
  .dense_weight``'s arithmetic.
* ``forced`` (None for the model): the experts each position is to use, and
  ``selected`` (None for the model): the keys each query of each "full" layer
  is to attend over, in place of the reference's own top-k's; the weights
  and the scores are still the reference's own.  With seeded random weights
  the k-th and the next score lie close; a program in bfloat16 lands on the
  other side at some, and every later layer reads each flip.  A comparison of
  logits holds the reference to what the program chose
  (``benchmark/selection_tap.py``) and compares the indexer and the router
  directly.
* ``faults``: named WRONG programs, one fault each, which the comparison
  that decides ``correct`` is sized against
  (``benchmark/tests/glm52_wrong_programs.py``).
* ``assumed`` in the configuration file: the softmax scale from nope + rope
  (256), "shared" reuses the last "full" layer's pick, LayerNorm with bias on
  ``kI`` (eps 1e-6) and RMSNorm elsewhere, no Hadamard rotation, no YaRN.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, FrozenSet, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_decoder import (F32, _margins, dense_weight,
                                               head_logits, rms_norm)
from benchmark.reference.ssm_moe_decoder import router  # the family's rule

FAULTS = ("no_relu", "no_head_weights", "shared_scores_itself",
          "selection_before_last", "one_key_short", "future_key",
          "rope_halves", "no_kv_norm", "scale_from_nope", "scores_bf16",
          "absent_expert", "held_left_out", "no_shared", "no_scaling",
          "rms_index_norm", "far_keys_lost")
#: ``far_keys_lost``: a query picks among its nearest ``FAR x index_topk``
#: keys only (4,608 at the published 2,048): a program that is right on every
#: context up to there and wrong past it, which a check that reads no longer
#: context cannot tell from the right one
FAR = 2.25
NONE: FrozenSet[str] = frozenset()
_QUERY_BLOCK = 256
#: ``attend``'s block of queries: its (heads, block, S) scores are 0.57 GB
#: at 17k keys, twice (the probabilities), beside the pass's other arrays
_ATTEND_BLOCK = 128


def rope_pairs(x: jax.Array, theta: float, dims: int, halves: bool = False
               ) -> jax.Array:
    """``x (S, heads, D)``: position p rotates the pair ``(2i, 2i + 1)`` of
    the first ``dims`` by ``p theta^(-2i / dims)``; the rest pass.
    ``halves``: the pairs ``(i, i + dims / 2)``, the wrong program."""
    s = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, dims, 2, dtype=F32) / dims)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    r = x[..., :dims]
    if halves:
        a, b = r[..., :dims // 2], r[..., dims // 2:]
        out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    else:
        a, b = r[..., 0::2], r[..., 1::2]
        out = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1
                        ).reshape(r.shape)
    return jnp.concatenate([out, x[..., dims:]], -1)


def layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


def index_scores(q: jax.Array, w: jax.Array, k: jax.Array,
                 faults: FrozenSet[str] = NONE) -> jax.Array:
    """``q (S, J, D)``, ``w (S, J)``, ``k (S', D)`` float32 → ``I (S, S')``,
    the queries in blocks of ``_QUERY_BLOCK``."""
    def block(qw):
        qb, wb = qw
        if "scores_bf16" in faults:  # summed and kept in the activation type
            dots = jnp.einsum("tjd,sd->tjs", qb.astype(jnp.bfloat16),
                              k.astype(jnp.bfloat16))
            act = dots if "no_relu" in faults else jax.nn.relu(dots)
            return jnp.einsum("tjs,tj->ts", act, wb.astype(jnp.bfloat16)
                              ).astype(F32)
        dots = jnp.einsum("tjd,sd->tjs", qb, k)
        act = dots if "no_relu" in faults else jax.nn.relu(dots)
        if "no_head_weights" in faults:
            return act.sum(1)
        return jnp.einsum("tjs,tj->ts", act, wb)

    with jax.default_matmul_precision("highest"):
        s = q.shape[0]
        pad = -s % _QUERY_BLOCK
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
        out = jax.lax.map(block, (
            q.reshape((-1, _QUERY_BLOCK) + q.shape[1:]),
            w.reshape((-1, _QUERY_BLOCK) + w.shape[1:])))
        return out.reshape(s + pad, -1)[:s]


@partial(jax.jit, static_argnames=("k", "faults"))
def own_selection(scores: jax.Array, k: int, faults: FrozenSet[str] = NONE
                  ) -> jax.Array:
    """``I (S, S)`` → bool ``(S, S)``: each query's ``k`` visible keys of
    largest score (all of them while it sees no more; a tie to the lower
    position, as ``lax.top_k``); the queries in blocks of ``_QUERY_BLOCK``."""
    s = scores.shape[0]
    k = min(k - (1 if "one_key_short" in faults else 0), s)
    ahead = 1 if "future_key" in faults else 0
    reach = int(FAR * k) if "far_keys_lost" in faults else s
    pad = -s % _QUERY_BLOCK
    scores = jnp.pad(scores, ((0, pad), (0, 0)))

    def block(start):
        i = start + jnp.arange(_QUERY_BLOCK)[:, None]
        j = jnp.arange(s)[None, :]
        seen = (j <= i + ahead) & (j > i - reach)
        sc = jax.lax.dynamic_slice_in_dim(scores, start, _QUERY_BLOCK)
        vals, idx = jax.lax.top_k(jnp.where(seen, sc, -jnp.inf), k)
        return jnp.zeros((_QUERY_BLOCK, s), bool).at[
            jnp.arange(_QUERY_BLOCK)[:, None], idx].max(vals > -jnp.inf)

    picked = jax.lax.map(block, jnp.arange(0, s + pad, _QUERY_BLOCK))
    return picked.reshape(s + pad, s)[:s]


@partial(jax.jit, static_argnames=("heads", "dims", "theta", "eps", "faults",
                                   "index"))
def attention_inputs(a, w, iw, *, heads, dims, theta, eps, index,
                     faults: FrozenSet[str] = NONE):
    """→ (q_nope (S, H, nope), q_rope, c_kv (S, rank), k_rope (S, rope), and
    the indexer's (qI, w, kI, I) when ``index``)."""
    nope, rp = dims
    halves = "rope_halves" in faults
    with jax.default_matmul_precision("highest"):
        s = a.shape[0]
        c_q = rms_norm(a @ dense_weight(w["w_qa"]), w["q_a_norm"], eps)
        q = (c_q @ dense_weight(w["w_qb"])).reshape(s, heads, nope + rp)
        q_rope = rope_pairs(q[..., nope:], theta, rp, halves)
        c_kv = a @ dense_weight(w["w_kva"])
        if "no_kv_norm" not in faults:
            c_kv = rms_norm(c_kv, w["kv_a_norm"], eps)
        k_rope = rope_pairs((a @ w["w_kr"].astype(F32))[:, None, :], theta,
                            rp, halves)[:, 0]
        ix = None
        if index:
            J = iw["w_iw"].shape[-1]
            qi = (c_q @ dense_weight(iw["w_iq"])).reshape(s, J, -1)
            D = qi.shape[-1]
            ki = a @ dense_weight(iw["w_ik"])
            if "rms_index_norm" in faults:
                ki = rms_norm(ki, iw["ik_scale"], 1e-6)
            else:
                ki = layer_norm(ki, iw["ik_scale"], iw["ik_bias"])
            qi = rope_pairs(qi, theta, rp, halves)
            ki = rope_pairs(ki[:, None, :], theta, rp, halves)[:, 0]
            wt = (a @ iw["w_iw"].astype(F32)) * (J * D) ** -0.5
            ix = (qi, wt, ki, index_scores(qi, wt, ki, faults))
        return q[..., :nope], q_rope, c_kv, k_rope, ix


@partial(jax.jit, static_argnames=("nope", "faults"))
def attend(q_nope, q_rope, c_kv, k_rope, picked, w, *, nope,
           faults: FrozenSet[str] = NONE):
    """The expanded attention under the selection's mask → ``(S, hidden)``."""
    s, heads, rp = q_rope.shape
    width = nope if "scale_from_nope" in faults else nope + rp
    with jax.default_matmul_precision("highest"):
        # k_nope and v each from its own columns of W_kvb: the same sums,
        # and no (S, heads, 448) array beside its two parts (2 GB at 17k)
        w_kvb = w["w_kvb"].astype(F32)
        k_nope = jnp.einsum("sc,chn->shn", c_kv, w_kvb[..., :nope])
        v = jnp.einsum("sc,chn->shn", c_kv, w_kvb[..., nope:])
        wo = dense_weight(w["wo"])

        def block(start):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, start, _ATTEND_BLOCK)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, start, _ATTEND_BLOCK)
            on = jax.lax.dynamic_slice_in_dim(picked, start, _ATTEND_BLOCK)
            sc = (jnp.einsum("thn,shn->hts", qn, k_nope)
                  + jnp.einsum("thr,sr->hts", qr, k_rope)) / jnp.sqrt(
                      F32(width))
            p = jax.nn.softmax(jnp.where(on[None], sc, -jnp.inf), -1)
            return jnp.einsum("hts,shv->thv", p, v).reshape(
                _ATTEND_BLOCK, -1) @ wo

        pad = -s % _ATTEND_BLOCK
        if pad:  # whole blocks; a padded query sees key 0 alone
            q_nope = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0)))
            q_rope = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0)))
            picked = jnp.pad(picked, ((0, pad), (0, 0))).at[s:, 0].set(True)
        o = jax.lax.map(block, jnp.arange(0, s + pad, _ATTEND_BLOCK))
        return o.reshape(s + pad, -1)[:s]


@partial(jax.jit, static_argnames=())
def dense_ffn(m, w):
    with jax.default_matmul_precision("highest"):
        gate = jax.nn.silu(m @ dense_weight(w["w_gate"]))
        return (gate * (m @ dense_weight(w["w_in"]))) @ dense_weight(
            w["w_out"])


@partial(jax.jit, static_argnames=("top_k", "norm_topk", "scaling", "first",
                                   "faults"))
def moe(m, w, forced=None, *, top_k: int, norm_topk: bool, scaling: float,
        first: int, faults: FrozenSet[str] = NONE):
    """The routed FFN on ``m (S, hidden)``: the router over ALL experts, the
    experts held here (``w["w_in"]``'s leading axis, from ``first`` on)
    computed one at a time on every position under the gates, the shared
    expert → (output, each position's router margin)."""
    with jax.default_matmul_precision("highest"):
        s = m.shape[0]
        held = w["w_in"].codes.shape[0] if hasattr(w["w_in"], "codes") \
            else w["w_in"].shape[0]
        p, top, idx, margin = router(
            m, w["router"], w["router_bias"], top_k=top_k,
            norm_topk=norm_topk, scaling=scaling,
            faults=frozenset(faults & {"no_scaling"}), forced=forced)
        gates = jnp.zeros_like(p).at[jnp.arange(s)[:, None], idx].set(top)
        if "absent_expert" in faults:  # an expert that lives elsewhere,
            # computed here with a held one's weights
            ghost = (first + held) % p.shape[1]
            gates = gates.at[:, first].add(gates[:, ghost])
        if "held_left_out" in faults:
            gates = gates.at[:, first + held - 1].set(0.0)

        def swiglu(x, gate, up, down):
            return (jax.nn.silu(x @ gate) * (x @ up)) @ down

        def one(y, e):
            gate, up, down = (dense_weight(jax.tree.map(lambda t: t[e], w[k]))
                              for k in ("w_gate", "w_in", "w_out"))
            return y + gates[:, first + e, None] * swiglu(m, gate, up,
                                                          down), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(held))
        if "no_shared" not in faults:
            y = y + swiglu(m, *(dense_weight(w[k]) for k in
                                ("sh_w_gate", "sh_w_in", "sh_w_out")))
        return y, margin


def layer_weights(params: Mapping[str, Any], kind: str, i: int
                  ) -> Dict[str, Any]:
    """Layer ``i`` of stack ``kind`` ("A": norms and attention, "I": indexer,
    "D": dense FFN, "S": routed FFN) of the program's parameter tree under
    this file's names.  The only place that knows the program's layout."""
    lay = params["layers"][kind]
    take = partial(jax.tree.map, lambda t: t[i])
    if kind == "A":
        at = lay["attn"]
        return {"ln1": lay["ln1"]["scale"][i], "ln2": lay["ln2"]["scale"][i],
                "q_a_norm": at["q_a_norm"]["scale"][i],
                "kv_a_norm": at["kv_a_norm"]["scale"][i],
                **{k: take(at[k]) for k in ("w_qa", "w_qb", "w_kva", "w_kr",
                                            "w_kvb", "wo")}}
    if kind == "I":
        ix = lay["index"]
        return {"ik_scale": ix["ik_norm"]["scale"][i],
                "ik_bias": ix["ik_norm"]["bias"][i],
                **{k: take(ix[k]) for k in ("w_iq", "w_ik", "w_iw")}}
    inner = lay["mlp" if kind == "D" else "moe"]
    return {k: take(v) for k, v in inner.items()}


def _router_keys(model: Mapping[str, Any]) -> Dict[str, Any]:
    return dict(top_k=model["num_experts_per_tok"],
                norm_topk=bool(model["norm_topk_prob"]),
                scaling=float(model["routed_scaling_factor"]))


def whole_pass(params: Mapping[str, Any], model: Mapping[str, Any],
               tokens: jax.Array, last: Optional[int] = None,
               faults: FrozenSet[str] = NONE,
               forced: Optional[jax.Array] = None,
               selected: Optional[np.ndarray] = None,
               length: Optional[int] = None, keep: bool = True
               ) -> Dict[str, Any]:
    """One pass over ``tokens (S,)`` and everything a comparison reads from
    it: ``logits`` (of the final ``last`` positions), ``margin`` (each
    position's smallest router margin), ``router_inputs`` (a list, routed
    layer by routed layer), ``indexer`` (a list, "full" layer by "full"
    layer, of ``(qI, w, kI, I)``) and ``picked`` (the selections used).
    ``forced (routed layers, S, k)``: the experts each position uses (a row
    of -1: the reference's own); ``selected (full layers, S, S)`` bool: the
    keys each query attends over, for the first ``length`` queries (the rest:
    the reference's own).  ``router_inputs``, ``indexer`` and ``picked`` are
    NumPy arrays on the host, so that nothing of a layer outlives it on the
    device (a pass over 17k positions at the published widths would keep
    9 GB of them); ``keep`` False: they come back empty."""
    faults = frozenset(faults)
    x = params["embed"]["tokens"][tokens].astype(F32)
    s = tokens.shape[0]
    eps = float(model["rms_norm_eps"])
    dims = (model["qk_nope_head_dim"], model["qk_rope_head_dim"])
    margin = jnp.full((s,), jnp.inf, F32)
    inputs, indexer, picks, used = [], [], [], []
    seen = {"I": 0, "D": 0, "S": 0}
    for i, (it, mt) in enumerate(zip(model["indexer_types"],
                                     model["mlp_layer_types"])):
        w = layer_weights(params, "A", i)
        full = it == "full" or "shared_scores_itself" in faults
        iw = None
        if full:  # (a shared layer that scores for itself has no indexer of
            # its own: the wrong program borrows the last full layer's)
            iw = layer_weights(params, "I", seen["I"] - (it != "full"))
        a = rms_norm(x, w["ln1"], eps)
        q_nope, q_rope, c_kv, k_rope, ix = attention_inputs(
            a, w, iw, heads=model["num_attention_heads"], dims=dims,
            theta=float(model["rope_theta"]), eps=eps, index=full,
            faults=faults)
        del a
        if full:
            own = own_selection(ix[3], model["index_topk"], faults)
            if it == "full":
                if selected is not None:
                    n = s if length is None else length
                    own = jnp.where((jnp.arange(s) < n)[:, None],
                                    jnp.asarray(selected[seen["I"]]), own)
                if keep:  # on the host: a pass over 9k positions keeps 3.5 GB
                    indexer.append(jax.tree.map(np.asarray, ix))
                    used.append(np.asarray(own))
                seen["I"] += 1
            picks.append(own)
            del picks[:-2]  # a layer reads the last pick, a wrong one the
            # one before
        del ix
        use = picks[-1]
        if "selection_before_last" in faults and it != "full" \
                and len(picks) > 1:
            use = picks[-2]
        if it != "full" and "shared_scores_itself" in faults:
            picks.pop()  # its own scores serve itself alone
        x = x + attend(q_nope, q_rope, c_kv, k_rope, use, w, nope=dims[0],
                       faults=faults)
        # 2 GB at 17k positions, which the next layer's would lie beside
        del q_nope, q_rope, c_kv, k_rope, use
        m = rms_norm(x, w["ln2"], eps)
        if mt == "dense":
            x = x + dense_ffn(m, layer_weights(params, "D", seen["D"]))
            seen["D"] += 1
        else:
            out, mg = moe(m, layer_weights(params, "S", seen["S"]),
                          None if forced is None else forced[seen["S"]],
                          **_router_keys(model),
                          first=int(model["first_expert"]), faults=faults)
            x = x + out
            margin = jnp.minimum(margin, mg)
            if keep:
                inputs.append(np.asarray(m))
            seen["S"] += 1
            del out
        del m
    if last is not None:
        x, margin = x[-last:], margin[-last:]
    return {"logits": head_logits(x, params["final_norm"]["scale"],
                                  params["lm_head"]["w"], eps=eps),
            "margin": margin, "router_inputs": inputs, "indexer": indexer,
            "picked": used}


def logits(params, model, tokens, last=None, faults=NONE, forced=None,
           selected=None) -> jax.Array:
    return whole_pass(params, model, tokens, last, faults, forced,
                      selected, keep=False)["logits"]


def own_choices(params: Mapping[str, Any], model: Mapping[str, Any],
                inputs: list, faults: FrozenSet[str] = NONE) -> jax.Array:
    """The experts the reference's router picks at every position of every
    routed layer ``(routed layers, S, k)``, on what each layer's router read
    along some pass (``whole_pass``'s ``router_inputs``)."""
    picked = []
    for i, m in enumerate(inputs):
        w = layer_weights(params, "S", i)
        picked.append(router(m, w["router"], w["router_bias"],
                             **_router_keys(model),
                             faults=frozenset(faults))[2])
    return jnp.stack(picked)


def served_margins(params: Mapping[str, Any], model: Mapping[str, Any],
                   sequence: jax.Array, n_prompt: int,
                   faults: FrozenSet[str] = NONE):
    """For one served sequence (prompt then the tokens the server sent): the
    margin and rank of each served token under the reference's OWN selections
    and routing, which reads the whole sequence in one uncached pass
    (``faults``: under a named wrong program's)."""
    lg = logits(params, model, sequence, last=len(sequence) - n_prompt + 1,
                faults=faults)
    return _margins(lg[:-1], sequence[n_prompt:])
