"""The two sub-layers a jamba layer is made of, for ``models/ssm_hybrid.py``'s
pattern of ``x <- x + f(RMSNorm(x))`` sub-layers: ``"S"`` a Mamba-1 mixer and
``"F"`` a dense gated FFN (AI21-Jamba2: 28 layers of a mixer AND an FFN are
56 letters, the attention mixers ``"*"`` as nemotron_h's).  The Mamba-1
mixer's mathematics is written once, here; the whole-sequence forward and the
v2 engine's step programs (``inference/v2/programs.py``) call the same pieces:

    [x | z] = a W_in                    (one matrix, x first)    ``sel_in_proj``
    x = silu(causal depthwise conv(x) + conv bias)               ``sel_conv``
    [dt | B | C] = x W_x; each through its RMSNorm (learned scale);
    delta = softplus(dt W_dt + dt_bias), A = -exp(A_log)         ``sel_x_proj``
    the recurrence of ``ops/pallas/selective_scan.py``           ``sel_scan``
    g = y * silu(z)                     (no group norm)          ``sel_gate``
    out = g W_out                                                ``sel_out_proj``

``delta``, ``A``, ``D``, the three inner norms and the recurrence are float32
whatever the activation dtype.  The conv's pieces are ``ssm_hybrid``'s
(``conv_ragged``, ``conv_taps``), over ``x`` alone.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..ops.pallas.selective_scan import selective_scan
from . import transformer as tfm

_F32 = jnp.float32


def has_sublayers(cfg) -> bool:
    """Whether the pattern holds this file's sub-layers."""
    return "S" in cfg.mixer_pattern or "F" in cfg.mixer_pattern


def check_config(cfg) -> None:
    if "M" in cfg.mixer_pattern:
        raise ValueError("mixer_pattern holds 'S' (Mamba-1) and 'M' (Mamba-2) "
                         "layers: a model holds one recurrence, not both")
    if not (cfg.mamba_expand > 0 and cfg.mamba_dt_rank > 0
            and cfg.mamba_state_size > 0):
        raise ValueError("an 'S' layer needs mamba_expand, mamba_dt_rank and "
                         "mamba_state_size")
    if cfg.mamba_num_heads or cfg.mamba_head_dim:
        raise ValueError("an 'S' layer has no heads: mamba_num_heads and "
                         "mamba_head_dim are Mamba-2's")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_layers(rng: jax.Array, cfg) -> Dict[str, Any]:
    """The ``"S"`` and ``"F"`` stacks of ``params["layers"]``.  ``A_log`` is
    ``log(1..N)`` along the state and ``dt_bias`` the inverse softplus of a
    step drawn log-uniformly in [1e-3, 1e-1] (the family's initialisation);
    both and ``D`` stay float32 whatever ``param_dtype`` is."""
    pd = jnp.dtype(cfg.param_dtype)
    h, f = cfg.hidden_size, cfg.intermediate_size
    di, N, R = cfg.mamba_d_inner, cfg.mamba_state_size, cfg.mamba_dt_rank
    kc = cfg.mamba_conv_kernel
    Ls, Lf = cfg.layers_of("S"), cfg.layers_of("F")
    keys = iter(jax.random.split(rng, 12))
    dense = tfm._dense_init
    step = jnp.exp(jax.random.uniform(next(keys), (Ls, di), _F32,
                                      math.log(1e-3), math.log(1e-1)))
    return {
        "S": {"norm": {"scale": jnp.ones((Ls, h), pd)}, "mamba": {
            "w_in": dense(next(keys), (Ls, h, 2 * di), h, pd),
            "conv_w": dense(next(keys), (Ls, kc, di), kc, pd),
            "conv_b": (0.1 * jax.random.normal(next(keys), (Ls, di))
                       ).astype(pd),
            "w_x": dense(next(keys), (Ls, di, R + 2 * N), di, pd),
            "dt_norm": jnp.ones((Ls, R), pd),
            "b_norm": jnp.ones((Ls, N), pd),
            "c_norm": jnp.ones((Ls, N), pd),
            "w_dt": dense(next(keys), (Ls, R, di), R, pd),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=_F32)), (Ls, di, N)),
            "D": jnp.ones((Ls, di), _F32),
            "w_out": dense(next(keys), (Ls, di, h), di, pd)}},
        "F": {"norm": {"scale": jnp.ones((Lf, h), pd)}, "mlp": {
            "w_gate": dense(next(keys), (Lf, h, f), h, pd),
            "w_in": dense(next(keys), (Lf, h, f), h, pd),
            "w_out": dense(next(keys), (Lf, f, h), f, pd)}},
    }


def layer_axes() -> Dict[str, Any]:
    ln = {"scale": ("layers", "embed")}
    return {
        "S": {"norm": dict(ln), "mamba": {
            "w_in": ("layers", "embed", "mlp"),
            "conv_w": ("layers", None, "mlp"), "conv_b": ("layers", "mlp"),
            "w_x": ("layers", "mlp", None), "dt_norm": ("layers", None),
            "b_norm": ("layers", None), "c_norm": ("layers", None),
            "w_dt": ("layers", None, "mlp"), "dt_bias": ("layers", "mlp"),
            "A_log": ("layers", "mlp", None), "D": ("layers", "mlp"),
            "w_out": ("layers", "mlp", "embed")}},
        "F": {"norm": dict(ln), "mlp": {
            "w_gate": ("layers", "embed", "mlp"),
            "w_in": ("layers", "embed", "mlp"),
            "w_out": ("layers", "mlp", "embed")}},
    }


def params_per_layer(cfg) -> Dict[str, int]:
    h, f = cfg.hidden_size, cfg.intermediate_size
    di, N, R = cfg.mamba_d_inner, cfg.mamba_state_size, cfg.mamba_dt_rank
    return {
        "S": h + h * 2 * di + cfg.mamba_conv_kernel * di + di
        + di * (R + 2 * N) + R + 2 * N + R * di + di + di * N + di + di * h,
        "F": h + 3 * h * f,
    }


# ---------------------------------------------------------------------------
# the Mamba-1 mixer's pieces (the step programs call them too)
# ---------------------------------------------------------------------------


def in_proj(a, p, cfg):
    """``a (..., h)`` → ``x, z (..., d_inner)``: the conv's input first, the
    gate second."""
    with jax.named_scope("sel_in_proj"):
        xz = tfm._lin(a, p, "w_in", "b_in")
        di = cfg.mamba_d_inner
        return xz[..., :di], xz[..., di:]


def _rms(v, scale, eps):
    return v * jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                             + eps) * scale.astype(_F32)


def scan_inputs(x, p, cfg):
    """The conv's output ``x (..., d_inner)`` → ``delta (..., d_inner)``
    float32 after bias and softplus, ``A (N, d_inner)`` negative, ``B, C
    (..., N)`` float32 after their norms, ``D (d_inner,)``."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_state_size
    with jax.named_scope("sel_x_proj"):
        dbc = jnp.matmul(x, p["w_x"].astype(x.dtype),
                         preferred_element_type=_F32)
        dt = _rms(dbc[..., :R], p["dt_norm"], cfg.norm_eps)
        B = _rms(dbc[..., R:R + N], p["b_norm"], cfg.norm_eps)
        C = _rms(dbc[..., R + N:], p["c_norm"], cfg.norm_eps)
        delta = jax.nn.softplus(
            jnp.matmul(dt.astype(x.dtype), p["w_dt"].astype(x.dtype),
                       preferred_element_type=_F32)
            + p["dt_bias"].astype(_F32))
        A = -jnp.exp(p["A_log"].astype(_F32)).T
        return delta, A, B, C, p["D"].astype(_F32)


def gate_out(y, z, p):
    """``y (..., d_inner)`` float32 from the recurrence (``D``'s skip in it)
    and the gate ``z`` → the mixer's output ``(..., h)``."""
    with jax.named_scope("sel_gate"):
        g = (y * jax.nn.silu(z.astype(_F32))).astype(z.dtype)
    with jax.named_scope("sel_out_proj"):
        return tfm._lin(g, p, "w_out", "b_out")


def selective_rows(a, p, cfg, ssm, conv, layer, row, offset, row_start,
                   row_len, slots, fresh, scanned):
    """A Mamba-1 mixer over a flat batch ``a (T, h)`` of rows lying end to
    end, each from the state of its slot, up to the recurrence: → ``((z, x,
    delta, A, B, C, D): the gate and the recurrence's inputs, y (T, d_inner),
    ssm, the rows' kept conv columns (R, K - 1, d_inner))`` with the rows
    ``scanned`` marks walked by ``selective_scan`` (their states written to
    ``ssm``); ``y`` of the other rows is zero, for the caller to fill."""
    from .ssm_hybrid import conv_ragged

    x, z = in_proj(a, p, cfg)
    with jax.named_scope("sel_conv"):
        kept = jnp.where(fresh[:, None, None], 0, conv[layer, slots])
        x, kept = conv_ragged(x, kept, p, row, offset, row_start, row_len)
    delta, A, B, C, D = scan_inputs(x, p, cfg)
    with jax.named_scope("sel_scan"):
        y, ssm = selective_scan(ssm, layer, x, delta, A, B, C, D, row_start,
                                row_len, slots, fresh, scanned)
    return (z, x, delta, A, B, C, D), y, ssm, kept


def ffn(a, p, cfg):
    """The dense gated FFN sub-layer: ``(silu(a W_gate) * (a W_up))
    W_down``."""
    with jax.named_scope("dense_ffn"):
        return tfm._mlp_block(a, p, cfg)
