"""Worker bodies for the multi-process distributed test tier.

Each function runs inside one real process of an N-process
``jax.distributed`` world (see ``runner.run_distributed``) and returns a
JSON-serializable result the parent compares rank-wise.  Only
fully-replicated outputs are read back (every process can address them);
sharded state is reduced via jitted collectives or
``multihost_utils.process_allgather`` first.
"""

from __future__ import annotations

from typing import Any, Dict

SEED = 1234


def _tiny_spec(seed: int = 0):
    import jax

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.runtime.engine import ModelSpec

    cfg = tfm.get_config("tiny", num_layers=2, max_seq_len=64)
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)

    def loss_fn(p, batch, rng):
        return tfm.loss_fn(p, batch, cfg)

    return ModelSpec(loss_fn=loss_fn, params=params,
                     param_axes=tfm.param_axes(cfg)), cfg


def _global_l2(tree) -> float:
    """L2 norm of a (possibly cross-process-sharded) pytree, computed by a
    jitted reduction whose scalar result is replicated → addressable."""
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree.leaves(tree) if isinstance(l, jax.Array)]

    @jax.jit
    def norm(ls):
        return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                            for l in ls))

    return float(norm(leaves))


def _train_engine(config_overrides: Dict[str, Any] | None = None):
    import deepspeed_tpu

    spec, cfg = _tiny_spec()
    config = {
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-3, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 10_000,
    }
    config.update(config_overrides or {})
    engine, _, _, _ = deepspeed_tpu.initialize(model=spec, config=config)
    return engine, cfg


def _batches(engine, cfg, steps: int):
    """Deterministic global batches — identical on every process (the
    single-controller data contract: each process places the same global
    batch; jax extracts its local shards)."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    tb = engine.batch_config.train_batch_size
    for _ in range(steps):
        yield {"input_ids": rng.integers(
            1, cfg.vocab_size, size=(tb, 32)).astype(np.int32)}


# ---------------------------------------------------------------------------
# workers
# ---------------------------------------------------------------------------


def comm_facade(args: Dict[str, Any]) -> Dict[str, Any]:
    """Process-tier (rank/world/barrier/broadcast) + device-tier collectives
    across REAL process boundaries."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax import shard_map

    from deepspeed_tpu import comm

    rank, world = comm.get_rank(), comm.get_world_size()
    comm.barrier("dist_test")
    bcast = comm.broadcast_host_value(
        np.asarray([rank * 10 + 7], np.int32), is_source=(rank == 0))

    n = jax.device_count()
    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    x_host = np.arange(n * 2, dtype=np.float32).reshape(n, 2) + 1.0
    x = jax.device_put(x_host, NamedSharding(mesh, P("dp")))
    sq_host = np.arange(n * n, dtype=np.float32).reshape(n, n)
    sq = jax.device_put(sq_host, NamedSharding(mesh, P("dp")))

    @jax.jit
    @__import__("functools").partial(
        shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=(P(), P(), P(), P(), P()),
        # all_gather outputs ARE replicated, but the static varying-axes
        # analysis cannot prove it — the asserts below check the values
        check_vma=False)
    def collectives(a, b):
        red = comm.all_reduce(a, "dp")                       # (1, 2) replicated
        gat = comm.all_gather(a, "dp")                       # (n, 2) replicated
        rs = comm.reduce_scatter(gat, "dp")                  # (1, 2) per shard
        rs_full = comm.all_gather(rs, "dp")                  # (n, 2) replicated
        a2a = comm.all_to_all(b, "dp", split_axis=1, concat_axis=0)
        # shard i's block is column i of the global matrix → transposing and
        # gathering on axis 0 yields the full distributed transpose
        a2a_full = comm.all_gather(jnp.transpose(a2a), "dp", axis=0)
        perm = comm.ppermute(a, "dp",
                             [(i, (i + 1) % comm.axis_size("dp"))
                              for i in range(comm.axis_size("dp"))])
        perm_full = comm.all_gather(perm, "dp")
        return red, rs_full, a2a_full, perm_full, gat

    red, rs_full, a2a_full, perm_full, gat = collectives(x, sq)
    return {
        "rank": rank, "world": world, "ndev": n,
        "bcast": np.asarray(bcast).tolist(),
        "all_reduce": np.asarray(red).tolist(),
        "reduce_scatter_gathered": np.asarray(rs_full).tolist(),
        "all_to_all_gathered": np.asarray(a2a_full).tolist(),
        "ppermute_gathered": np.asarray(perm_full).tolist(),
        "all_gather": np.asarray(gat).tolist(),
    }


def zero3_train(args: Dict[str, Any]) -> Dict[str, Any]:
    """ZeRO-3 training across process boundaries: param/opt shards live on
    different PROCESSES; the losses must match a single-process run of the
    same global mesh bit-for-bit (same HLO, same reduction order)."""
    import jax

    engine, cfg = _train_engine()
    losses = []
    for batch in _batches(engine, cfg, int(args.get("steps", 3))):
        m = engine.train_batch(batch)
        losses.append(float(m["loss"]))
    return {"losses": losses, "ndev": jax.device_count(),
            "param_l2": _global_l2(engine.state.params)}


def checkpoint_roundtrip(args: Dict[str, Any]) -> Dict[str, Any]:
    """Native-engine checkpointing in a multi-process world: the host
    snapshot is a process_allgather collective, process 0 writes, every
    process reloads (resharding onto its mesh) and training continues with
    losses identical to an uninterrupted run."""
    ckpt_engine = args.get("ckpt_engine", "native")
    save_dir = args["save_dir"]

    engine, cfg = _train_engine({"checkpoint": {"engine": ckpt_engine}})
    batches = list(_batches(engine, cfg, 4))
    losses = [float(engine.train_batch(b)["loss"]) for b in batches[:2]]
    engine.save_checkpoint(save_dir)
    norm_at_save = _global_l2(engine.state.params)

    # fresh engine (fresh params), load, continue
    engine2, _ = _train_engine({"checkpoint": {"engine": ckpt_engine}})
    engine2.load_checkpoint(save_dir)
    step_loaded = int(engine2.state.step)
    norm_loaded = _global_l2(engine2.state.params)
    resumed = [float(engine2.train_batch(b)["loss"]) for b in batches[2:]]
    cont = [float(engine.train_batch(b)["loss"]) for b in batches[2:]]
    return {"losses": losses, "resumed": resumed, "continued": cont,
            "norm_at_save": norm_at_save, "norm_loaded": norm_loaded,
            "step_loaded": step_loaded}
