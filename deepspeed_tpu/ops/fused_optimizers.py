"""Fused optimizer update kernels.

Capability analogue of the reference's fused device optimizers
(``csrc/adam/multi_tensor_adam.cu``, ``fused_adam_frontend.cpp``,
``csrc/lamb``, ``csrc/lion`` + the multi-tensor-apply machinery): one fused
pass over the flattened parameter state instead of per-tensor kernel
launches.

On TPU, XLA already fuses optax's elementwise update chains into a single
loop per tensor, so the multi-tensor-apply machinery is unnecessary; the
Pallas kernel here exists for the HBM-bound sharded update where manual
blocking + f32-in-VMEM accumulation measurably beats the default lowering,
and as the programmable base for quantized/stochastic-rounding updates.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas import backend


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, bc_ref,
                 p_out, m_out, v_out,
                 *, lr, b1, b2, eps, wd):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    v = v_ref[:]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    m_hat = m_new / bc_ref[0]
    v_hat = v_new / bc_ref[1]
    update = m_hat / (jnp.sqrt(v_hat) + eps) + wd * p
    p_out[:] = (p - lr * update).astype(p_out.dtype)
    m_out[:] = m_new
    v_out[:] = v_new


def fused_adamw_flat(params: jax.Array, grads: jax.Array, m: jax.Array,
                     v: jax.Array, step: jax.Array, lr: float,
                     b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                     weight_decay: float = 0.0, block: int = 1 << 16
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """AdamW update over a flat (N,) parameter vector.  m/v are f32.
    Returns (new_params, new_m, new_v)."""
    n = params.size
    padded = (n + block - 1) // block * block
    if padded != n:
        pad = padded - n

        def padf(x):
            return jnp.pad(x.reshape(-1), (0, pad))

        params, grads, m, v = map(padf, (params, grads, m, v))
    # one grid step covers ``block`` elements as an (8, block/8) tile: the
    # chip's compiler wants the last two block dims divisible by (8, 128)
    rows, cols = 8, block // 8
    shape2d = (padded // cols, cols)
    args = [params.reshape(shape2d), grads.reshape(shape2d),
            m.reshape(shape2d), v.reshape(shape2d)]

    # the bias corrections are two scalars: computed here, since the chip's
    # compiler has no scalar ``pow`` inside a kernel
    t = jnp.asarray(step, jnp.float32)
    bias_correction = jnp.stack([1.0 - b1 ** t, 1.0 - b2 ** t])

    grid = (padded // block,)
    out = pl.pallas_call(
        functools.partial(_adam_kernel, lr=lr, b1=b1, b2=b2, eps=eps,
                          wd=weight_decay),
        grid=grid,
        in_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))] * 4 +
                 [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec((rows, cols), lambda i: (i, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(shape2d, params.dtype),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
            jax.ShapeDtypeStruct(shape2d, jnp.float32),
        ],
        interpret=backend.interpret(),
        name="fused_adamw",
    )(*args, bias_correction)
    p_new, m_new, v_new = (o.reshape(-1)[:n] for o in out)
    return p_new, m_new, v_new


class FusedAdamState(NamedTuple):
    step: jax.Array
    m: jax.Array
    v: jax.Array


def fused_adamw_tree(params, grads, state: FusedAdamState, lr: float,
                     b1=0.9, b2=0.999, eps=1e-8, weight_decay=0.0):
    """Pytree wrapper: flattens all leaves into one fused update (the
    multi-tensor-apply role)."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    gleaves = jax.tree.leaves(grads)
    sizes = [l.size for l in leaves]
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    flat_p = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])
    flat_g = jnp.concatenate([g.reshape(-1).astype(jnp.float32) for g in gleaves])
    step = state.step + 1
    p_new, m_new, v_new = fused_adamw_flat(
        flat_p, flat_g, state.m, state.v, step, lr, b1, b2, eps, weight_decay)
    outs = []
    off = 0
    for size, shape, dt in zip(sizes, shapes, dtypes):
        outs.append(p_new[off:off + size].reshape(shape).astype(dt))
        off += size
    new_params = jax.tree_util.tree_unflatten(treedef, outs)
    return new_params, FusedAdamState(step, m_new, v_new)


def init_fused_adam_state(params) -> FusedAdamState:
    n = sum(l.size for l in jax.tree.leaves(params))
    return FusedAdamState(step=jnp.zeros((), jnp.int32),
                          m=jnp.zeros((n,), jnp.float32),
                          v=jnp.zeros((n,), jnp.float32))
