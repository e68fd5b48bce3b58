"""Collective-facade tests over the virtual 8-device mesh
(reference model: tests/unit/comm/test_dist.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from deepspeed_tpu import comm
from deepspeed_tpu.parallel.topology import MeshTopology
from deepspeed_tpu.runtime.config import MeshConfig


@pytest.fixture
def mesh8(devices):
    return MeshTopology.from_config(MeshConfig()).mesh


def test_init_distributed_single_process():
    comm.init_distributed(verbose=False)
    assert comm.is_initialized()
    assert comm.get_world_size() == 1  # process-level
    assert comm.get_global_device_count() == 8  # device-level
    assert comm.get_rank() == 0


def test_all_reduce(mesh8):
    x = jnp.arange(8.0)

    def f(x):
        return comm.all_reduce(x, "dp")

    out = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(out, np.full(8, np.arange(8.0).sum()))


def test_all_reduce_avg(mesh8):
    x = jnp.arange(8.0)

    def f(x):
        return comm.all_reduce(x, "dp", op=comm.ReduceOp.AVG)

    out = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(out, np.full(8, np.arange(8.0).mean()))


def test_all_gather(mesh8):
    x = jnp.arange(8.0)

    def f(x):
        return comm.all_gather(x, "dp")

    # tiled gather: local (1,) -> (8,), replicated across the axis
    out = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P(None),
                    check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(8.0))


def test_reduce_scatter(mesh8):
    x = jnp.ones((8, 8))

    def f(x):
        return comm.reduce_scatter(x.reshape(-1), "dp")

    out = shard_map(f, mesh=mesh8, in_specs=P("dp", None), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, 8.0))


def test_all_to_all(mesh8):
    # Ulysses building block: swap shard axis seq<->heads
    x = jnp.arange(8 * 8 * 4.0).reshape(8, 8, 4)  # (seq, heads, dim)

    def f(x):  # local (1, 8, 4) -> (8, 1, 4)
        return comm.all_to_all(x, "dp", split_axis=1, concat_axis=0)

    out = shard_map(f, mesh=mesh8, in_specs=P("dp", None, None),
                    out_specs=P(None, "dp", None))(x)
    assert out.shape == (8, 8, 4)
    # the *global* tensor is unchanged — only the sharded axis moved seq→heads
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_ppermute_ring(mesh8):
    x = jnp.arange(8.0)
    n = 8
    perm = [(i, (i + 1) % n) for i in range(n)]

    def f(x):
        return comm.ppermute(x, "dp", perm)

    out = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(8.0), 1))


def test_comms_logger_records(mesh8):
    lg = comm.get_comms_logger()
    comm.configure(enabled=True)
    lg.reset()
    x = jnp.ones((64,), jnp.float32)

    def f(x):
        return comm.all_reduce(x, "dp")

    jax.jit(shard_map(f, mesh=mesh8, in_specs=P(None), out_specs=P(None)))(x)
    summary = comm.log_summary()
    assert "all_reduce@dp" in summary
    comm.configure(enabled=False)


def test_all_reduce_prod(mesh8):
    x = jnp.array([1., 2., 3., 4., -1., 1., 2., 1.])

    def f(x):
        return comm.all_reduce(x, "dp", op=comm.ReduceOp.PROD)

    out = shard_map(f, mesh=mesh8, in_specs=P("dp"), out_specs=P("dp"),
                    check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.full(8, -48.0))
