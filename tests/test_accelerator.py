"""No fallback hides the device: the accelerator is what was asked for or
what JAX found, a device without a recorded peak raises, a kernel that gives
way on a TPU says so once, and the compile cache goes where it is told."""

import logging
import os

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.accelerator import real_accelerator
from deepspeed_tpu.accelerator.tpu_accelerator import (CPUAccelerator,
                                                       TPUAccelerator)
from deepspeed_tpu.ops.pallas import backend
from deepspeed_tpu.utils import compile_cache


class _Kind(TPUAccelerator):
    def __init__(self, kind):
        self._kind = kind

    def device_kind(self):
        return self._kind


@pytest.mark.parametrize("kind,dtype,peak", [
    ("TPU v5 lite", "bfloat16", 197.0), ("TPU v5 lite", "int8", 394.0),
    ("TPU v6 lite", "bfloat16", 918.0)])
def test_peak_tflops_by_device_kind(kind, dtype, peak):
    assert _Kind(kind).peak_tflops(dtype) == peak


def test_unknown_device_kind_has_no_default_peak():
    with pytest.raises(KeyError, match="TPU v9"):
        _Kind("TPU v9").peak_tflops()
    with pytest.raises(NotImplementedError):
        CPUAccelerator().peak_tflops()


@pytest.fixture()
def fresh_accelerator(monkeypatch):
    monkeypatch.setattr(real_accelerator, "_accelerator", None)
    monkeypatch.delenv("DS_ACCELERATOR", raising=False)
    return monkeypatch


def test_accelerator_is_what_was_asked_for(fresh_accelerator):
    fresh_accelerator.setenv("DSTPU_ACCELERATOR", "cpu")
    assert isinstance(real_accelerator.get_accelerator(), CPUAccelerator)
    assert real_accelerator.get_accelerator().memory_stats() == {}


def test_accelerator_follows_the_backend_when_not_asked(fresh_accelerator):
    fresh_accelerator.delenv("DSTPU_ACCELERATOR")
    assert real_accelerator.get_accelerator().platform() == \
        jax.default_backend()


def test_unknown_accelerator_override_raises(fresh_accelerator):
    fresh_accelerator.setenv("DSTPU_ACCELERATOR", "gpu")
    with pytest.raises(ValueError, match="DSTPU_ACCELERATOR"):
        real_accelerator.get_accelerator()


def test_kernel_that_gives_way_on_a_tpu_warns_once(monkeypatch, caplog):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention

    from deepspeed_tpu.utils.logging import _warning_once_impl

    monkeypatch.setattr(backend, "interpret", lambda: False)
    _warning_once_impl.cache_clear()
    monkeypatch.setattr(logging.getLogger("deepspeed_tpu"), "propagate", True)
    q = jax.ShapeDtypeStruct((4, 8, 64), jnp.bfloat16)  # head_dim 64
    cache = jax.ShapeDtypeStruct((2, 16, 16, 2, 64), jnp.bfloat16)
    layer = jax.ShapeDtypeStruct((), jnp.int32)
    tables = jax.ShapeDtypeStruct((4, 4), jnp.int32)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32)
    with caplog.at_level(logging.WARNING, logger="deepspeed_tpu"):
        for _ in range(2):
            out = jax.eval_shape(paged_decode_attention, q, cache, cache,
                                 layer, tables, lens)
    assert out.shape == q.shape
    warned = [r for r in caplog.records if "paged_decode_attention" in
              r.getMessage()]
    assert len(warned) == 1 and "head_dim=64" in warned[0].getMessage()


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
