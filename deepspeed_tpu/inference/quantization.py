"""Weight-only quantization for inference params.

Reference: ``deepspeed/inference/quantization`` (``_init_group_wise_weight_
quantization``, matmul_4bit/8bit paths) — weights live in HBM as int8/int4
and dequantize inside the GEMM. Here the projection weights of every
transformer layer become ``QuantizedWeight`` pytree nodes that
``models/transformer._lin`` routes through the Pallas mixed GEMM.  A layer
scan over the stacked (L, K, N) nodes hands its body one layer's node, at the
price of a copy of that layer's codes before each GEMM; the v2 engine's
layer loop keeps the stacks whole and the kernels read them by layer
(``inference/v2/programs.py:hoist_quantized``).  The routed
experts of an MoE layer (``moe.w_in`` / ``w_gate`` / ``w_out``, stacked
(L, E, K, N)) are quantized per expert in the same format and served by the
grouped mixed GEMM (``ops/pallas/grouped_mixed_gemm``).

Embeddings / lm_head / norms / the MoE router stay high-precision (gather and tiny tensors
gain nothing from int codes), matching the reference's exclude list.  So do
an EVA layer's ``eva_phi`` and ``eva_mu`` (two vectors a head, read by the
summariser and no GEMM) and the eight output heads of a byte model
(``lm_head``, 4096 x 2560, multiplied in float32): codes are for ``wq``,
``wk``, ``wv``, ``wo`` and the MLP's three, as a Mistral layer's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.pallas.mixed_gemm import QuantizedWeight, quantize_gemm_weight
from ..utils.logging import logger

# projection weights inside each layer's attn/mlp/moe dicts (the router, the
# PR-MoE shared expert and its coefficient are not among the keys)
_QUANT_KEYS = frozenset({"wq", "wk", "wv", "wo", "w_in", "w_out", "w_gate",
                         # a routed MoE's shared expert; a Mamba-2 layer's
                         # z and xBC projections (models/ssm_hybrid.py; its
                         # 64-wide dt projection stays bf16)
                         "sh_w_in", "sh_w_out", "w_z", "w_xbc",
                         # a gated shared expert; latent attention's and its
                         # indexer's projections (models/latent_sparse.py).
                         # NOT among them, and so bf16: ``w_kvb`` (the
                         # absorbed paths use it a head at a time, as two
                         # einsums no GEMM kernel computes), ``w_kr`` (64
                         # columns) and ``w_iw`` (32 columns)
                         "sh_w_gate", "w_qa", "w_qb", "w_kva", "w_iq",
                         "w_ik",
                         # latent attention without query compression; a KDA
                         # layer's q, k and v in one (models/kimi_linear.py;
                         # its low-rank gate maps, ``w_beta`` and the conv
                         # stay bf16)
                         "w_q", "w_qkv"})
_QUANT_PARENTS = frozenset({"attn", "mlp", "moe", "mamba", "index", "kda"})


def pad_expert_width(w: jax.Array, key: str) -> jax.Array:
    """A routed expert's matrix ``(..., E, K, N)`` with the experts' inner
    width zero-padded to the next multiple of 128 where it is wider than 128
    and no multiple (nemotron_h: 1856 = 29 x 64 → 1920): the grouped kernel
    tiles N in lane-aligned divisors and K in groups of 128, and 1856 has
    neither.  The result is exact: a zero column of ``w_in`` / ``w_gate``
    gives an activation of 0 for silu, gelu and relu² alike, and the zero
    rows of ``w_out`` it meets add nothing.  The padded bytes are fetched."""
    axis = -2 if key == "w_out" else -1
    width = w.shape[axis]
    if w.ndim < 3 or width <= 128 or width % 128 == 0:
        return w
    pad = [(0, 0)] * w.ndim
    pad[axis] = (0, -width % 128)
    return jnp.pad(w, pad)


def pad_mlp_width(w: jax.Array, key: str) -> jax.Array:
    """A dense MLP's matrix ``(..., K, N)`` with the inner width zero-padded
    to the next multiple of 1024 where ``pick_gemm_tiles`` would find it no
    lane-aligned divisor of 512 columns or more (EvaByte: 11008 = 2^8 x 43
    → 11264 = 2^10 x 11; its widest is 256, rows of 256 B in HBM where
    "tiles of equal bytes measured faster wide than deep", and along K one
    group a step or all 43).  Exact, as ``pad_expert_width`` is: a zero
    column of ``w_in`` / ``w_gate`` gives 0 for every gated activation, and
    meets a zero row of ``w_out``.  The padded bytes (2.3 % of the MLP's)
    are fetched; every width a served configuration had before this one has
    a wide divisor (Mistral 14336: 3584) and is left as it is."""
    axis = -2 if key == "w_out" else -1
    width = w.shape[axis]
    wide = max((d for d in range(128, min(width, 4096) + 1, 128)
                if width % d == 0), default=width)
    if w.ndim < 2 or width <= 4096 or wide >= 512:
        return w
    pad = [(0, 0)] * w.ndim
    pad[axis] = (0, -width % 1024)
    return jnp.pad(w, pad)


def quantize_model_params(params: Dict[str, Any], bits: int = 8,
                          group: int = 256,
                          quantize=quantize_gemm_weight) -> Dict[str, Any]:
    """Replace layer projection weights with QuantizedWeight nodes."""
    def walk(tree, parent=None):
        if isinstance(tree, dict):
            return {k: (quantize({"moe": pad_expert_width,
                                  "mlp": pad_mlp_width}[parent](v, k)
                                 if parent in ("moe", "mlp")
                                 and k in ("w_in", "w_gate", "w_out") else v,
                                 bits=bits, group=group)
                        if (parent in _QUANT_PARENTS and k in _QUANT_KEYS
                            and getattr(v, "ndim", 0) >= 2)
                        else walk(v, k))
                    for k, v in tree.items()}
        return tree

    return walk(params)


def shardings_for_quantized(params: Dict[str, Any],
                            shardings: Dict[str, Any]) -> Dict[str, Any]:
    """Mirror a sharding tree onto a quantized param tree.

    Quantized leaves are placed REPLICATED: GSPMD cannot partition the
    opaque ``mixed_gemm`` pallas_call, so tensor-sharded codes would be
    all-gathered before every projection — strictly worse than storing them
    replicated (they are already 2–4× smaller than the weights they
    replace). Partitioning the kernel itself (shard_map / custom
    partitioning over the N axis) is the follow-up that restores per-device
    memory scaling; until then, warn when TP > 1 so the user knows the
    quantized bytes are per-device, not per-mesh.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    warned = False

    def walk(p, s):
        nonlocal warned
        if isinstance(p, QuantizedWeight):
            ns = s
            if not warned and any(ns.mesh.shape[a] > 1 for e in ns.spec
                                  if e is not None
                                  for a in ((e,) if isinstance(e, str) else e)):
                logger.warning(
                    "quantized weights are stored replicated across the "
                    "tensor-parallel mesh (the mixed GEMM kernel is not yet "
                    "partitioned); per-device weight memory is the full "
                    "quantized model")
                warned = True
            rep = NamedSharding(ns.mesh, PartitionSpec())
            return QuantizedWeight(rep, rep, p.bits, p.group, p.k)
        if isinstance(p, dict):
            return {k: walk(v, s[k]) for k, v in p.items()}
        return s

    return walk(params, shardings)


def quantize_on_host(params: Dict[str, Any], bits: int,
                     group: int) -> Dict[str, Any]:
    """Quantize on the host CPU backend so the accelerator never holds the
    full-precision weights (the whole point of weight-only quantization)."""
    cpu = jax.local_devices(backend="cpu")[0]
    # device_put (not default_device + asarray): already-committed accelerator
    # arrays are actually MOVED to host, keeping the no-fp-weights-on-chip
    # guarantee even when params arrive as device arrays
    host = jax.device_put(params, cpu)
    return quantize_model_params(host, bits=bits, group=group,
                                 quantize=_quantize_layerwise)


@functools.partial(jax.jit, static_argnames=("bits", "group"))
def _quantize_layerwise(w: jax.Array, bits: int, group: int
                        ) -> QuantizedWeight:
    """One leaf, jitted (it runs where its committed input is, on the
    host), and a stacked (L, K, N) or (L, E, K, N) leaf a layer at a time:
    the f32 intermediates are then one layer's (one layer's experts).  Op by
    op, a 32-layer stack of a 7B model's MLP weight holds three 7.5 GB f32
    copies of itself."""
    if w.ndim < 3:
        return quantize_gemm_weight(w, bits=bits, group=group)
    return jax.lax.map(
        functools.partial(quantize_gemm_weight, bits=bits, group=group), w)


def quantized_bytes(params: Dict[str, Any]) -> Dict[str, int]:
    """{quantized, total} parameter bytes — the memory-saving accounting."""
    q = t = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, QuantizedWeight)):
        if isinstance(leaf, QuantizedWeight):
            b = leaf.codes.nbytes + leaf.scales.nbytes
            q += b
            t += b
        else:
            t += getattr(leaf, "nbytes", 0)
    return {"quantized": q, "total": t}
