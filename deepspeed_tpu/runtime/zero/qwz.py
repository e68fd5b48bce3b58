"""ZeRO++ qwZ — quantized weight all-gather for stage-3 params.

Reference: ``partition_parameters.py:829`` (``CUDAQuantizer``) +
``engine.py:1325-1337`` (all_gather_coalesced with ``quantization`` handle):
stage-3 forward/backward gathers ship int8 codes + block scales instead of
full-precision weights, halving (bf16) or quartering (fp32) the gather
traffic, and dequantize on arrival.

TPU-native form: the implicit GSPMD all-gather of an fsdp-sharded parameter
is made explicit with a ``shard_map`` over the ``fsdp`` axis — quantize the
local shard, ``lax.all_gather`` the int8 codes and f32 block scales (this is
the wire traffic), dequantize and concatenate on-device.  A ``custom_vjp``
passes gradients through unchanged (straight-through: grads stay full
precision and follow the usual reduce-scatter, exactly like the reference,
which only quantizes the weight direction).

Because the whole step is jitted and the params feed a scanned layer stack,
XLA schedules these gathers per-layer inside the scan the same way it
schedules the implicit ones; with a recompute remat policy the dequantized
weights are not kept alive between forward and backward.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...ops.quantizer import dequantize_blockwise, quantize_blockwise
from ...parallel.topology import MeshTopology


def _fsdp_dim(spec: P) -> int:
    """Index of the dim sharded (exactly) by 'fsdp', or -1."""
    for i, entry in enumerate(spec):
        if entry == "fsdp" or entry == ("fsdp",):
            return i
    return -1


def qwz_gather_leaf(x: jax.Array, sharding: NamedSharding,
                    topo: MeshTopology, bits: int = 8,
                    block_size: int = 256) -> jax.Array:
    """Quantized-gather one fsdp-sharded param to fsdp-replicated."""
    spec = sharding.spec
    dim = _fsdp_dim(spec)
    n = topo.size("fsdp")
    if dim < 0 or n <= 1:
        return x

    out_entries = list(spec)
    out_entries[dim] = None
    out_spec = P(*out_entries)

    def local(xs):
        codes, scales = quantize_blockwise(xs, bits=bits,
                                           block_size=block_size)
        cg = lax.all_gather(codes, "fsdp")   # (n, blocks, block) int8 wire
        sg = lax.all_gather(scales, "fsdp")  # (n, blocks) f32 wire
        parts = [
            dequantize_blockwise(cg[i], sg[i], bits=bits,
                                 block_size=block_size, shape=xs.shape,
                                 dtype=x.dtype)
            for i in range(n)
        ]
        return jnp.concatenate(parts, axis=dim)

    @jax.custom_vjp
    def f(x_):
        return shard_map(local, mesh=topo.mesh, in_specs=spec,
                         out_specs=out_spec, check_vma=False)(x_)

    def f_fwd(x_):
        return f(x_), None

    def f_bwd(_, g):
        # straight-through: the weight grad is exact; constraining it back to
        # the fsdp-sharded layout restores the usual reduce-scatter schedule
        return (lax.with_sharding_constraint(
            g, NamedSharding(topo.mesh, spec)),)

    f.defvjp(f_fwd, f_bwd)
    return f(x)


def qwz_gather_tree(params: Any, shardings: Any, topo: MeshTopology,
                    bits: int = 8, block_size: int = 256) -> Any:
    """Apply :func:`qwz_gather_leaf` across a param pytree."""
    return jax.tree.map(
        lambda x, s: qwz_gather_leaf(x, s, topo, bits, block_size),
        params, shardings)
