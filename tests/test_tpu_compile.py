"""The main path's Pallas kernels, compiled by the TPU compiler for a chip
that is described and not attached (v5e), at Mistral-7B widths.

Interpret mode, which every other kernel test uses, cannot see what Mosaic
refuses: shifts on int8 vectors, strided value slices that lower to a
gather, a scalar ``pow``, a block shape off the (8, 128) tiling, a kernel
under GSPMD on a mesh.  Each of those passed interpret mode and was refused
here.  Nothing runs: these are compiles, about two seconds each.

The topology is described inside a module-scoped fixture of this file, and
only this file may do so: the TPU library belongs to one process at a time,
so a module that loads it while it is imported gives the workers of a
parallel run different tests to collect.  Shapes and shardings are built in
the tests, the compile runs in the test's own process, and the persistent
compilation cache is off around them (a described chip cannot read it back).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from deepspeed_tpu.ops.pallas import backend

# mistral-7b: 32 query / 8 KV heads, head_dim 128, hidden 4096, MLP 14336
H, KV, D, HIDDEN, MLP = 32, 8, 128, 4096, 14336


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    cc.reset_cache()
    mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def mosaic(monkeypatch):
    """Steer the kernels off the interpreter, as they run on the chip."""
    monkeypatch.setattr(backend, "interpret", lambda: False)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args) -> str:
    """Compile ``fn`` for the described chip; the kernel must be in it."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel gave way to a reference"
    return text


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_paged_attention_compiles(one_chip, mosaic, kernel):
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_prefill_attention)

    seqs, blocks, bs, max_blocks = 16, 256, 64, 16
    sds = functools.partial(_sds, sharding=one_chip)
    cache = sds((blocks, bs, KV, D), jnp.bfloat16)
    tables, lens = sds((seqs, max_blocks), jnp.int32), sds((seqs,), jnp.int32)
    if kernel == "decode":
        _compile(paged_decode_attention, sds((seqs, H, D), jnp.bfloat16),
                 cache, cache, tables, lens)
    else:
        _compile(paged_prefill_attention,
                 sds((seqs, 512, H, D), jnp.bfloat16), cache, cache, tables,
                 lens, lens)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_window_compiles(one_chip, mosaic, grad):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((2, 2048, H, D), jnp.bfloat16, one_chip)
    kv = _sds((2, 2048, KV, D), jnp.bfloat16, one_chip)
    fn = functools.partial(flash_attention, causal=True, window=4096)
    if grad:
        fn = jax.grad(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, window=4096).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    _compile(fn, q, kv, kv)


def test_flash_attention_on_a_mesh_compiles(topo, mosaic):
    """GSPMD cannot partition a Mosaic kernel; on the engine's mesh the call
    becomes a shard_map over the batch, and each chip runs one row."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.parallel import topology

    mesh_topo = topology.MeshTopology({"fsdp": 4}, devices=topo.devices)
    topology.set_topology(mesh_topo)  # conftest resets it after the test
    rows = NamedSharding(mesh_topo.mesh, P(("dp", "fsdp")))
    q = _sds((4, 2048, H, D), jnp.bfloat16, rows)
    kv = _sds((4, 2048, KV, D), jnp.bfloat16, rows)
    text = _compile(functools.partial(flash_attention, causal=True,
                                      window=4096), q, kv, kv)
    assert f"bf16[1,{H},2048,{D}]" in text  # one batch row a chip


@pytest.mark.parametrize("bits", [8, 4, 6])
@pytest.mark.parametrize("m", [16, 256], ids=["decode", "prefill"])
def test_mixed_gemm_compiles(one_chip, mosaic, bits, m):
    from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                     mixed_gemm,
                                                     quantize_gemm_weight)

    for k, n in ((HIDDEN, MLP), (MLP, HIDDEN), (HIDDEN, (H + 2 * KV) * D)):
        qw = jax.eval_shape(
            functools.partial(quantize_gemm_weight, bits=bits, group=256),
            jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
        _compile(
            lambda x, c, s: mixed_gemm(x, QuantizedWeight(c, s, bits, 256, k)),
            _sds((m, k), jnp.bfloat16, one_chip),
            _sds(qw.codes.shape, qw.codes.dtype, one_chip),
            _sds(qw.scales.shape, qw.scales.dtype, one_chip))


@pytest.mark.parametrize("n", [HIDDEN * MLP, 1_000_003],
                         ids=["mlp-weight", "ragged"])
def test_fused_adamw_compiles(one_chip, mosaic, n):
    from deepspeed_tpu.ops.fused_optimizers import fused_adamw_flat

    p = _sds((n,), jnp.bfloat16, one_chip)
    m = _sds((n,), jnp.float32, one_chip)
    step = _sds((), jnp.int32, one_chip)
    _compile(lambda p_, g_, m_, v_, s_: fused_adamw_flat(
        p_, g_, m_, v_, s_, lr=1e-3, weight_decay=0.1), p, p, m, m, step)


def test_mesh_follows_the_torus(topo):
    """``MeshTopology`` hands a TPU's devices to ``create_device_mesh`` and
    no longer swallows what it raises: the 2x2 slice must arrange."""
    from deepspeed_tpu.parallel import topology

    t = topology.MeshTopology({"fsdp": 2, "tp": 2}, devices=topo.devices)
    assert t.mesh.devices.shape == (1, 1, 2, 1, 1, 2)
    assert {d.id for d in np.ravel(t.mesh.devices)} == \
        {d.id for d in topo.devices}
