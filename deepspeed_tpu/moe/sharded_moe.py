"""Expert-parallel MoE with explicit all-to-all dispatch.

Capability analogue of the reference's ``MOELayer`` + ``_AllToAll``
(``sharded_moe.py:536,:97``): unlike the GSPMD einsum path in
``moe/layer.py`` (where XLA infers the all-to-all from shardings), this path
makes the token shuffle an explicit ``lax.all_to_all`` over the ``ep`` mesh
axis inside ``shard_map`` — useful when manual comm/compute overlap or
payload inspection (AutoEP-style digests) is wanted.

Flow per device (E experts, P = ep size, local experts = E/P):
  gate → capacity-bucket locally → all_to_all tokens so each device holds the
  buckets of ITS experts from every peer → local expert FFN → all_to_all back
  → combine.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.topology import get_topology
from .layer import top_k_gating


def sharded_moe_block(x: jax.Array, p: Dict[str, Any], cfg) -> jax.Array:
    """Drop-in MoE FFN with explicit ep all-to-all. x: (B, S, H) with batch
    sharded over (dp, fsdp); expert weights sharded over 'ep' on the expert
    axis.  Requires num_experts % ep == 0.  Capacity (top-k) routing only —
    refusing other modes beats silently training with the wrong router."""
    routing = getattr(cfg, "moe_routing", "capacity")
    if routing != "capacity":
        raise ValueError(
            f"sharded_moe_block implements capacity (top-k) routing only; "
            f"moe_routing={routing!r} would be silently ignored — use the "
            f"GSPMD path (dense_moe_block / moe_block_with_losses) for it")
    topo = get_topology()
    ep = topo.size("ep")
    if ep == 1:
        from .layer import dense_moe_block

        return dense_moe_block(x, p, cfg)

    E = cfg.num_experts
    if E % ep != 0:
        raise ValueError(f"num_experts({E}) % ep({ep}) != 0")

    def local(x, router, w_in, w_gate, w_out):
        # local shapes: x (B_l, S, H); router (H, E); w_* (E/P, H, F)/(E/P, F, H)
        dt = x.dtype
        B_l, S, H = x.shape
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        gate = top_k_gating(logits, E, cfg.moe_top_k, cfg.moe_capacity_factor)
        disp = gate.dispatch_mask.astype(dt)  # (B_l, S, E, C)
        comb = gate.combine_weights.astype(dt)
        C = disp.shape[-1]

        # bucket tokens per expert: (E, B_l*C, H)
        xe = jnp.einsum("bsec,bsh->ebch", disp, x).reshape(E, B_l * C, H)
        # explicit token shuffle: split expert axis across peers, gather each
        # device's experts' buckets from everyone (reference _AllToAll.forward)
        xe = jax.lax.all_to_all(xe, "ep", split_axis=0, concat_axis=1,
                                tiled=True)  # (E/P, P*B_l*C, H)

        if w_gate is not None:
            hmid = jax.nn.silu(jnp.einsum("eth,ehf->etf", xe, w_gate.astype(dt))) * \
                jnp.einsum("eth,ehf->etf", xe, w_in.astype(dt))
        else:
            hmid = jax.nn.gelu(jnp.einsum("eth,ehf->etf", xe, w_in.astype(dt)),
                               approximate=True)
        ye = jnp.einsum("etf,efh->eth", hmid, w_out.astype(dt))

        # shuffle results back (reference _AllToAll.backward direction)
        ye = jax.lax.all_to_all(ye, "ep", split_axis=1, concat_axis=0,
                                tiled=True)  # (E, B_l*C, H)
        ye = ye.reshape(E, B_l, C, H)
        return jnp.einsum("bsec,ebch->bsh", comb, ye)

    # EP peers partition the DP batch (reference: EP ranks split the batch);
    # replicating it over ep would make every peer redo all dispatch work
    batch_spec = ("dp", "fsdp", "ep")
    if x.shape[0] % (topo.size("dp") * topo.size("fsdp") * ep) != 0:
        raise ValueError(
            f"batch {x.shape[0]} must divide dp*fsdp*ep "
            f"({topo.size('dp') * topo.size('fsdp') * ep}) for the explicit "
            "all-to-all MoE path")
    x_spec = P(batch_spec, None, None)
    has_gate = "w_gate" in p
    if has_gate:
        fn = local
        args = (x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
        specs = (x_spec, P(None, None), P("ep"), P("ep"), P("ep"))
    else:
        fn = lambda x, r, wi, wo: local(x, r, wi, None, wo)
        args = (x, p["router"], p["w_in"], p["w_out"])
        specs = (x_spec, P(None, None), P("ep"), P("ep"))
    y = shard_map(fn, mesh=topo.mesh, in_specs=specs,
                  out_specs=x_spec, check_vma=False)(*args)
    if getattr(cfg, "moe_use_residual", False):
        # PR-MoE shared expert + mixing coefficient is a dense per-token
        # computation — applied OUTSIDE the ep shard_map, same math as the
        # GSPMD path (training here then serving there must agree)
        from .layer import _prmoe_combine

        y = _prmoe_combine(x, y, p, cfg)
    return y
