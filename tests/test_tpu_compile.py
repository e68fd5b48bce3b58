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
import re

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


def _compile(fn, *args, kernels) -> str:
    """Compile ``fn`` for the described chip; each of ``kernels`` must be in
    it as a ``tpu_custom_call`` whose instruction carries the kernel's own
    name (``name=`` of its ``pallas_call``): that name is what the trace of
    a chip run and the benchmark's breakdown show, so it must not be the
    name of whatever scope happens to enclose the call.  Under ``jax.grad``
    JAX puts the transform round it (``jvp_<kernel>_``,
    ``transpose_jvp_<kernel>__``); the kernel's name stays whole."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "the kernel gave way to a reference"
    for kernel in kernels:
        assert re.search(
            rf'%(\w+_)?{kernel}_*(\.\d+)? = [^\n]*'
            r'custom_call_target="tpu_custom_call"',
            text), f"no tpu_custom_call instruction is named {kernel}"
    return text


def _pallas_calls(jaxpr) -> list:
    """The ``pallas_call`` equations of a jaxpr, those inside its calls too."""
    found = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            found.append(e)
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


def _pool_passes(compiled_text: str, pool) -> list:
    """The instructions of a compiled program that copy, slice or update a
    slice with a result the shape of a K/V pool ``(L, blocks, block, KV, D)``
    or of one layer of it, by (name, opcode): ``copy``, ``copy-start`` to the
    fast memory, ``slice-start``, ``dynamic-slice`` / ``dynamic-update-slice``
    and the fusions XLA names after them.  Each is a pass over 0.1-1.7 GB at
    the cells' sizes; a step program is to hold none (the in-place ``scatter``
    of the step's rows is not one)."""
    shapes = [f"bf16[{','.join(map(str, s))}]" for s in (pool, pool[1:])]
    return [(name, opcode) for name, result, opcode in re.findall(
        r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(", compiled_text,
        re.M) if any(s in result for s in shapes)
        and re.search("copy|slice", f"{name} {opcode}")]


def _quantized(params) -> list:
    from deepspeed_tpu.ops.pallas.mixed_gemm import QuantizedWeight

    def is_q(node):
        return isinstance(node, QuantizedWeight)

    return list(filter(is_q, jax.tree.leaves(params["layers"], is_leaf=is_q)))


def _instructions(compiled_text: str) -> list:
    """→ [(name, result, opcode)] of a compiled program's text."""
    return re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(",
                      compiled_text, re.M)


def _weight_passes(compiled_text: str, params,
                   fast_memory_share: float = 0.0) -> list:
    """The instructions of a compiled program that copy or slice with a
    result the shape of one layer of a quantized projection: its ``s8`` codes
    or its ``f32`` scales, with or without unit dimensions (an expert stack
    counts whole and an expert at a time), by (name, opcode, result).  Each
    was a pass over up to 58.7 MB before the GEMM that reads it, where W8A16
    exists to read the codes once; a step program is to hold none.  (XLA's own
    prefetch of a whole scale stack, or of a few layers of one, to the fast
    memory is not a layer's shape and is not counted.)

    ``fast_memory_share``: only for the program it was seen in (Nemotron's,
    whose stacks of four layers XLA prefetches a layer at a time): the bytes
    that ``slice-start`` / ``slice-done`` pairs into the fast memory
    (``S(1)``) may move in a layer's shape, as a share of all the quantized
    bytes the program holds.  HBM is still read once, by the prefetch in
    place of the kernel; past that share the pairs count like any pass."""
    def squeeze(dims):
        return tuple(d for d in dims if d != 1)

    shapes, held = set(), 0
    for qw in _quantized(params):
        held += qw.codes.size + 4 * qw.scales.size
        for dtype, a in (("s8", qw.codes), ("f32", qw.scales)):
            shapes |= {(dtype, squeeze(a.shape[1:])),
                       (dtype, squeeze(a.shape[-2:]))}
    found, prefetches, moved = [], [], 0
    for name, result, opcode in _instructions(compiled_text):
        hit = [(dtype, [int(n) for n in dims.split(",")])
               for dtype, dims in re.findall(r"\b(s8|f32)\[([\d,]+)\]", result)]
        hit = [(dtype, dims) for dtype, dims in hit
               if (dtype, squeeze(dims)) in shapes]
        if not hit or not re.search("copy|slice", f"{name} {opcode}"):
            continue
        if opcode in ("slice-start", "slice-done") and "S(1)" in result:
            prefetches.append((name, opcode, result))
            if opcode == "slice-done":
                moved += sum(int(np.prod(dims)) * (1 if dtype == "s8" else 4)
                             for dtype, dims in hit)
            continue
        found.append((name, opcode, result))
    if moved > fast_memory_share * held:
        found += prefetches + [("fast memory", "share", f"{moved / held:.4f}")]
    return found


def _whole_scale_stack_copies(compiled_text: str, params) -> list:
    """The ``copy`` instructions of a compiled program whose result is a WHOLE
    stack of ``f32`` scales ``(layers, ..., rows, N)``, by the stack's shape:
    a pass over every layer's scales at every step, before the kernel that
    reads them in place.  (The device keeps a stack whose rows are no
    multiple of the sublane tile with another dimension on the sublanes, and
    the kernel's operand wants it row-major: PERF.md section 7.)"""
    stacks = {",".join(map(str, qw.scales.shape)) for qw in _quantized(params)}
    return sorted(dims for name, result, opcode in _instructions(compiled_text)
                  if opcode == "copy"
                  for dims in re.findall(r"\bf32\[([\d,]+)\]", result)
                  if dims in stacks)


def _scope_gathers(compiled_text: str, scope: str) -> list:
    """The result shapes of the ``gather`` instructions of a compiled
    program (inside its fusions too) that ``scope`` holds, as ``[dims]``.  A
    mixer's ragged conv (``ssm_hybrid.conv_ragged``) gathers its rows' first
    and last ``K - 1`` tokens and the kept columns, ``R (K - 1)`` rows each;
    before PR 59 it gathered all ``T`` tokens twice a tap, six passes of
    ``(512, 5120)`` a layer that took longer than the scan kernel."""
    return [re.search(r"\[[\d,]*\]", result).group(0)
            for result, rest in re.findall(
                r"^\s*(?:ROOT )?%[\w.\-]+ = (\S+) gather\((.*)$",
                compiled_text, re.M)
            if re.search(rf'op_name="[^"]*/{scope}/', rest)]


def _lower_step_program(program: str, cfg, sds, group: int = 256, **sizes):
    """``decode_step``, ``mixed_step`` or the self-draft ``spec_step`` (four
    proposals a row) of a W8A16 model of ``cfg``, lowered on shapes alone at
    the serving cells' sizes (32 rows, 512 tokens a step, block 64, 416
    blocks: ``benchmark/configs/*-w8.json``; ``sizes`` are another
    configuration's) → (lowered, the pool's shape, or the two pools' of a
    model with window and global layers, the quantized parameters'
    shapes)."""
    from deepspeed_tpu.inference.quantization import quantize_model_params
    from deepspeed_tpu.inference.v2 import engine as v2e
    from deepspeed_tpu.inference.v2.programs import kind_of
    from deepspeed_tpu.models import transformer as tfm

    v2 = v2e.V2Config(**{**dict(
        max_tokens_per_step=512, max_seqs=32, block_size=64, num_blocks=416,
        max_blocks_per_seq=64,
        spec_mode="self_draft" if program == "spec_step" else "off"),
        **sizes})
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda key: quantize_model_params(
            tfm.init_params(key, cfg), bits=8, group=group),
            jax.random.PRNGKey(0)))
    # the caches as the engine allocates them: what the model's kind says
    arrays = kind_of(cfg).arrays(cfg, v2)
    caches = {name: sds(shape, dtype)
              for name, (shape, dtype) in arrays.items()}
    rows = lambda dtype: sds((v2.max_seqs,), dtype)  # noqa: E731
    tables = sds((v2.max_seqs, v2.max_blocks_per_seq), jnp.int32)
    if "latent" in arrays:  # a latent pool and, where there is one, the
        # indexer's
        pool = tuple(arrays[name][0] for name in ("latent", "index")
                     if name in arrays)
    elif "k_win" in arrays:  # the window layers' pool and table beside them
        # (an EVA model: its summaries' pool, then its window's)
        main = "k_sum" if "k_sum" in arrays else "k"
        pool, tables = (arrays[main][0], arrays["k_win"][0]), (tables, tables)
    else:
        pool = arrays["k"][0]
    if program == "decode_step":
        lowered = v2e.build_decode_forward(cfg, v2).lower(
            params, caches, rows(jnp.int32), rows(jnp.int32), tables,
            rows(jnp.int32), rows(jnp.float32),
            sds((2,), jnp.uint32), rows(jnp.int32))
    elif program == "spec_step":
        from deepspeed_tpu.inference.v2.spec import build_self_draft_step
        from deepspeed_tpu.linear.spec_heads import init_spec_heads

        heads = jax.tree.map(
            lambda a: sds(a.shape, a.dtype),
            jax.eval_shape(lambda key: init_spec_heads(
                key, cfg, v2.spec_k, base_params=tfm.init_params(key, cfg)),
                jax.random.PRNGKey(1)))
        lowered = build_self_draft_step(cfg, v2).lower(
            params, heads, caches, rows(jnp.int32), rows(jnp.int32), tables,
            rows(jnp.int32), sds((v2.max_seqs, cfg.hidden_size), jnp.float32),
            sds((2,), jnp.uint32), rows(jnp.float32), rows(jnp.int32))
    else:
        tokens = lambda: sds((v2.max_tokens_per_step,), jnp.int32)  # noqa: E731
        lowered = v2e.build_ragged_forward(cfg, v2).lower(
            params, caches, tokens(), tokens(), tokens(), tables,
            rows(jnp.int32), rows(jnp.int32), rows(jnp.int32),
            rows(jnp.int32),
            # a model with state layers: each row's state slot, behind the
            # two adapter arguments it never has
            *([None, None, rows(jnp.int32)] if kind_of(cfg).state else []))
    return lowered, pool, params


#: ``temp_size_in_bytes`` of the parent's step programs (afc6556, whose layer
#: scan sliced every quantized projection) at the cells' sizes.  Mistral's
#: decode and verify steps held a layer's MLP codes there; its mixed step and
#: OLMoE's programs held their slices in the fast memory, outside this count.
_PARENT_TEMP = {("mistral-7b", "decode_step"): 156_327_424,
                ("mistral-7b", "mixed_step"): 404_226_048,
                ("mistral-7b", "spec_step"): 156_511_232,
                ("olmoe-1b-7b", "decode_step"): 1_032_704,
                ("olmoe-1b-7b", "mixed_step"): 68_173_312}


def _assert_weights_stay_in_place(compiled, params, model, program):
    """No pass over a layer's codes or scales, the dense kernel still there by
    name, and no more temp than the parent's.  OLMoE is allowed 1.1 MB over
    it: XLA now prefetches the attention projections' scale stacks (1 MB
    each) a few layers at a time, and every such ``slice-start`` holds 16 KB
    of temp for its 512 bytes of bookkeeping (0.48 / 1.03 MB in all)."""
    text = compiled.as_text()
    assert _weight_passes(text, params) == []
    assert _whole_scale_stack_copies(text, params) == []
    assert re.search(r"%mixed_gemm[.\d]* = [^\n]*custom-call\(", text)
    slack = 1_100_000 if model == "olmoe-1b-7b" else 0
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= _PARENT_TEMP[model, program] + slack, temp


def _assert_pools_stay_in_place(compiled, pool):
    """The compiled step program holds a K/V pool in no form but the donated
    buffer: no pass over a pool or a layer of one, a temp smaller than one
    pool (the parent's held a second cache: 3.9-4.2 GB), and the output
    aliasing both donated pools.  At the cells' 416 blocks, where neither a
    pool nor a layer fits the fast memory XLA would otherwise prefetch it
    to."""
    pool_bytes = 2 * int(np.prod(pool))
    assert _pool_passes(compiled.as_text(), pool) == []
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes, (
        f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB holds a pool "
        f"({pool_bytes / 1e9:.2f} GB)")
    assert mem.alias_size_in_bytes >= 2 * pool_bytes


#: the served shapes of the paged kernels: query heads, KV heads, window
_SERVED_ATTENTION = {"mistral-7b": (H, KV, 0), "olmoe-1b-7b": (16, 16, 0),
                     "mellum2-window-layer": (32, 4, 1024),
                     "nemotron3-attention-layer": (32, 2, 0)}


@pytest.mark.parametrize("kernel,model", [
    (kernel, model) for kernel in ("decode", "prefill")
    for model in _SERVED_ATTENTION])
def test_paged_attention_compiles(one_chip, mosaic, kernel, model):
    """Each kernel alone on the whole pool (layers, blocks, ...) with the
    layer a traced scalar: Mosaic takes ``k_hbm.at[layer, blk]``.  Both at
    every served share of query heads a KV head (4, 1, 8, 16) and Mellum2's
    window, in bfloat16: the decode kernel on a step's rows (64 for
    Nemotron-3: its cell's), the prefill kernel on the flat queries of a step
    of 512 tokens; each one's ring event names the picker's tiles and no
    fallback."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_prefill_attention)

    heads, kv, window = _SERVED_ATTENTION[model]
    seqs, layers, blocks, bs, max_blocks = (64 if kv == 2 else 32, 4, 256,
                                            64, 16)
    sds = functools.partial(_sds, sharding=one_chip)
    pool = sds((layers, blocks, bs, kv, D), jnp.bfloat16)
    layer = sds((), jnp.int32)
    tables, lens = sds((seqs, max_blocks), jnp.int32), sds((seqs,), jnp.int32)
    tracer.clear()
    if kernel == "decode":
        text = _compile(functools.partial(paged_decode_attention,
                                          window=window),
                        sds((seqs, heads, D), jnp.bfloat16), pool, pool,
                        layer, tables, lens,
                        kernels=["paged_attention_decode"])
        assert f"bf16[{seqs},{heads},{D}]" in text
        event, = [s.attrs for s in tracer.spans()
                  if s.name == "kernel/paged_attention_decode_tiles"]
        assert event == {"rows": seqs, "heads": heads, "kv": kv, "d": D,
                         "block": bs, "window": window,
                         "kb": 32 // kv, "slots": 3,
                         "operand_dtype": "bfloat16"}
    else:
        text = _compile(functools.partial(paged_prefill_attention,
                                          window=window),
                        sds((512, heads, D), jnp.bfloat16), pool, pool,
                        layer, tables, lens, lens, lens,
                        kernels=["paged_attention_prefill"])
        assert f"bf16[512,{heads},{D}]" in text
        event, = [s.attrs for s in tracer.spans()
                  if s.name == "kernel/paged_attention_prefill_tiles"]
        assert event == {"t": 512, "heads": heads, "kv": kv, "d": D,
                         "block": bs, "window": window, "tq": "8/128",
                         "kb": 4, "grid_steps": 1}
    assert _pool_passes(text, (layers, blocks, bs, kv, D)) == []


@pytest.mark.parametrize("rows,heads,kv,d,dtype,span", [
    (200, 64, 8, 256, jnp.float32, 128), (32, 16, 4, 256, jnp.bfloat16, 32),
    (32, H, KV, D, jnp.float32, 32)], ids=["spans", "d256-kv4", "f32"])
def test_decode_attention_compiles_off_the_served_shapes(one_chip, mosaic,
                                                         rows, heads, kv, d,
                                                         dtype, span):
    """What no cell serves and the interpreter cannot judge: rows past what a
    grid step holds (two spans of 128 rows of 64 float32 heads of 256, the
    last padded), a ``head_dim`` of two lane tiles (the pools read as they
    lie, a block landing as ``(block, KV, D)``), and float32 pools."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, pick_decode_tiles)

    assert pick_decode_tiles(rows, heads, kv, d, 64, dtype).span == span
    sds = functools.partial(_sds, sharding=one_chip)
    pool = sds((2, 64, 64, kv, d), dtype)
    tracer.clear()
    _compile(paged_decode_attention, sds((rows, heads, d), dtype), pool, pool,
             sds((), jnp.int32), sds((rows, 16), jnp.int32),
             sds((rows,), jnp.int32), kernels=["paged_attention_decode"])
    event, = [s.attrs for s in tracer.spans()
              if s.name == "kernel/paged_attention_decode_tiles"]
    assert "fallback" not in event
    assert event["operand_dtype"] == jnp.dtype(dtype).name


@pytest.mark.parametrize("tokens,grid_steps", [(2048, 2), (4096, 4),
                                               (8192, 8), (1000, 1)])
def test_prefill_attention_compiles_at_any_budget(one_chip, mosaic, tokens,
                                                  grid_steps):
    """``max_tokens_per_step`` is the server's to set: past the 8 MiB of
    queries a grid step holds (1,024 tokens of Mistral's 32 heads) the kernel
    walks the budget in spans, so no budget is refused for its VMEM (whole in
    VMEM, 8,192 tokens asked for 142 MB of the chip's 128) and none gives
    way to the XLA path."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_prefill_attention)

    seqs, layers, blocks, bs, max_blocks = 32, 4, 256, 64, 16
    sds = functools.partial(_sds, sharding=one_chip)
    pool = sds((layers, blocks, bs, KV, D), jnp.bfloat16)
    tables, lens = sds((seqs, max_blocks), jnp.int32), sds((seqs,), jnp.int32)
    tracer.clear()
    text = _compile(paged_prefill_attention,
                    sds((tokens, H, D), jnp.bfloat16), pool, pool,
                    sds((), jnp.int32), tables, lens, lens, lens,
                    kernels=["paged_attention_prefill"])
    event, = [s.attrs for s in tracer.spans()
              if s.name == "kernel/paged_attention_prefill_tiles"]
    assert (event["tq"], event["grid_steps"]) == ("8/128", grid_steps)
    assert "fallback" not in event
    assert _pool_passes(text, (layers, blocks, bs, KV, D)) == []


def _flash_events(passes, dtype) -> list:
    """The ``kernel/flash_attention_tiles`` events of the compile just made:
    one a pass, none a fallback, each naming the dtype its dots multiply
    (the caller's: float32 sums, the operands as they were handed in)."""
    from deepspeed_tpu.observability.trace import tracer

    events = [s.attrs for s in tracer.spans()
              if s.name == "kernel/flash_attention_tiles"]
    assert {e["pass"] for e in events} == passes
    assert all(e["operand_dtype"] == jnp.dtype(dtype).name
               and "fallback" not in e for e in events)
    return events


_FLASH_KERNELS = ["flash_attention_fwd", "flash_attention_bwd_dkv",
                  "flash_attention_bwd_dq"]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_window_compiles(one_chip, mosaic, grad, dtype):
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((2, 2048, H, D), dtype, one_chip)
    kv = _sds((2, 2048, KV, D), dtype, one_chip)
    fn = functools.partial(flash_attention, causal=True, window=4096)
    if grad:
        fn = jax.grad(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, window=4096).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))
    tracer.clear()
    _compile(fn, q, kv, kv,
             kernels=_FLASH_KERNELS if grad else _FLASH_KERNELS[:1])
    _flash_events({"fwd", "dkdv", "dq"} if grad else {"fwd"}, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_at_unequal_widths_compiles(one_chip, mosaic, grad,
                                                    dtype):
    """Latent attention's expanded form at DeepSeek-V2-Lite's sizes: 16
    heads, a query-key width of 192 (one and a half lane tiles) beside a
    value width of 128, two rows of 8,192, and ``v`` is not padded.  bfloat16
    is what the cell trains in: its blocks stay bfloat16 in VMEM and 1,024
    fit; a float32 caller's are held to 512 (at 1,024 the dK/dV kernel passes
    the scoped VMEM)."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    qk = _sds((2, 8192, 16, 192), dtype, one_chip)
    v = _sds((2, 8192, 16, 128), dtype, one_chip)
    fn = functools.partial(flash_attention, causal=True, sm_scale=0.1147)
    if grad:
        fn = jax.grad(lambda q_, k_, v_: flash_attention(
            q_, k_, v_, causal=True, sm_scale=0.1147
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    tracer.clear()
    text = _compile(fn, qk, qk, v,
                    kernels=_FLASH_KERNELS if grad else _FLASH_KERNELS[:1])
    # the output is 128 wide
    assert f"{'bf16' if dtype == jnp.bfloat16 else 'f32'}[2,16,8192,128]" \
        in text
    events = _flash_events({"fwd", "dkdv", "dq"} if grad else {"fwd"}, dtype)
    block = 1024 if dtype == jnp.bfloat16 else 512
    assert all((e["d_qk"], e["d_v"], e["block_q"], e["block_k"])
               == (192, 128, block, block) for e in events)


@pytest.mark.parametrize("window", [2048, 0], ids=["band", "full"])
def test_flash_attention_band_at_16k_compiles(one_chip, mosaic, window):
    """Trinity-Mini's two kinds of layer at the trained length: one row of
    16,384, 32 query heads over 4 K/V heads of 128, a band of 2,048 keys or
    the whole triangle, forward and backward.  The banded calls carry
    ``_band`` behind their names (a trace tells them from the full layer's),
    the full layer's keep the plain ones; blocks stay 1,024 (the window is
    past the cap, so it is not shrunk).  The banded kernels' inner grid
    dimension is the band's width, 3 steps a row of tiles, the full layer's
    all 16; 45 / 136 tiles a head are live and 30 / 16 of them build the
    band's mask (ISSUE 61)."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((1, 16384, 32, 128), jnp.bfloat16, one_chip)
    kv = _sds((1, 16384, 4, 128), jnp.bfloat16, one_chip)
    fn = jax.grad(lambda q_, k_, v_: flash_attention(
        q_, k_, v_, causal=True, window=window).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    tracer.clear()
    suffix = "_band" if window else ""
    text = _compile(fn, q, kv, kv,
                    kernels=[k + suffix for k in _FLASH_KERNELS])
    assert ("flash_attention_fwd_band" in text) == bool(window)
    events = _flash_events({"fwd", "dkdv", "dq"}, jnp.bfloat16)
    assert all((e["block_q"], e["block_k"]) == (1024, 1024) for e in events)
    walk = (3, 45, 30) if window else (16, 136, 16)
    assert all((e["steps"], e["live_tiles"], e["edge_tiles"]) == walk
               for e in events)


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate-up", "down"])
def test_grouped_matmul_backward_compiles(one_chip, mosaic, k, n):
    """The held experts' grouped GEMM with its backward at DeepSeek-V2-Lite's
    widths on a round's 22,528 rows: forward, dlhs over the experts as they
    are stored, drhs with an expert's tiles summed in VMEM; 1,408 = 11 x 128
    is taken whole (steps of 128 columns would leave the MXU waiting)."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    rows, tile_m, experts = 22528, 512, 8

    def fn(lhs, rhs, tg, sizes, used):
        return jax.value_and_grad(lambda a, b: jnp.square(grouped_matmul(
            a, b, tg, sizes, tile_m=tile_m, used_tiles=used
        ).astype(jnp.float32)).sum(), argnums=(0, 1))(lhs, rhs)

    tracer.clear()
    _compile(fn, _sds((rows, k), jnp.bfloat16, one_chip),
             _sds((experts, k, n), jnp.bfloat16, one_chip),
             _sds((rows // tile_m,), jnp.int32, one_chip),
             _sds((experts,), jnp.int32, one_chip),
             _sds((), jnp.int32, one_chip),
             kernels=["grouped_matmul", "grouped_matmul_dlhs",
                      "grouped_matmul_drhs"])
    event = next(s.attrs for s in tracer.spans()
                 if s.name == "kernel/grouped_matmul_tiles")
    assert (event["e"], event["k"], event["n"], event["rows"],
            event["tile_m"]) == (experts, k, n, rows, tile_m)
    assert "fallback" not in event and 1408 in (event["tile_k"],
                                                event["tile_n"])


def test_latent_moe_train_step_carries_its_scopes(one_chip, mosaic):
    """The trained forward and backward of DeepSeek-V2-Lite's dense layer
    and one routed layer (8 of 64 experts, an eighth of the vocabulary, 2 x
    8,192 tokens) lower for the chip with every kernel in place (the two
    tests above compile them), and every scope the benchmark's readers sum
    is on an operation.  Lowered, not compiled: the compile is 40 s."""
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.sequence.tiled_compute import tiled_loss_fn

    cfg = tfm.get_config(
        "deepseek-v2-lite", num_layers=2, vocab_size=12800,
        moe_experts_held=8, mlp_layer_types=("dense", "sparse"),
        dtype="bfloat16", param_dtype="bfloat16")
    shapes = jax.eval_shape(lambda key: tfm.init_params(key, cfg),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), shapes)
    ids = _sds((2, 8192), jnp.int32, one_chip)
    text = jax.jit(lambda p, b: jax.value_and_grad(lambda p_: tiled_loss_fn(
        p_, {"input_ids": b}, cfg, tile_size=512), has_aux=True)(p)
    ).lower(params, ids).as_text(debug_info=True)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq", "grouped_matmul",
                   "grouped_matmul_dlhs", "grouped_matmul_drhs"):
        assert re.search(rf'[/"]{kernel}["/]', text), kernel
    assert text.count("tpu_custom_call") >= 12
    for scope in ("mla_qkv", "mla_attn", "mla_out", "moe_route",
                  "moe_dispatch", "moe_experts", "moe_combine", "moe_shared",
                  "moe_aux"):
        assert re.search(rf'[/"]{scope}/', text), scope
    # the share's layout is walked in rounds: no array of all 98,304
    # assignments x hidden exists
    assert "98304x2048x" not in text and "102912x" not in text


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
                                      window=4096), q, kv, kv,
                    kernels=["flash_attention_fwd"])
    assert f"bf16[1,{H},2048,{D}]" in text  # one batch row a chip


_WIDTHS = {"wq-wo": (HIDDEN, H * D), "wk-wv": (HIDDEN, KV * D),
           "w_in-w_gate": (HIDDEN, MLP), "w_out": (MLP, HIDDEN)}


@pytest.mark.parametrize("m,bits,width,layers", [
    *((m, bits, width, 0) for m in (32, 512) for width in _WIDTHS
      for bits in (8, 4, 6)),
    # what the served layer loop calls: the stack of 32 layers, read in place
    *((m, 8, width, 32) for m in (32, 512) for width in _WIDTHS),
    (32, 4, "w_in-w_gate", 32), (32, 6, "w_out", 32)])
def test_mixed_gemm_compiles(one_chip, mosaic, bits, width, m, layers):
    """The seven projections of a layer at the rows the serving cells run
    (32 decode rows, a chunk of 512 tokens), at the tile the picker gives
    them: the only place a machine without the chip sees a tile that
    overflows VMEM or a block Mosaic refuses.  An int8 call reads its
    weights once (one M tile) in steps of at least 1 MB of codes.  On the
    layer stack (``layers`` > 0, the layer a traced scalar) the compiled call
    holds no copy or slice of a layer's codes or scales."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                     mixed_gemm,
                                                     pick_gemm_tiles,
                                                     quantize_gemm_weight)

    k, n = _WIDTHS[width]
    tiles = pick_gemm_tiles(m, k, n, bits, 256)
    assert tiles.tm == m and tiles.tk % 256 == 0 and k % tiles.tk == 0
    assert tiles.grid_steps == (n // tiles.tn) * (k // tiles.tk)
    if bits == 8:
        assert tiles.code_bytes_per_step == tiles.tk * tiles.tn >= 1 << 20
    qw = jax.eval_shape(
        functools.partial(quantize_gemm_weight, bits=bits, group=256),
        jax.ShapeDtypeStruct(((layers,) if layers else ()) + (k, n),
                             jnp.bfloat16))
    args = [_sds((m, k), jnp.bfloat16, one_chip),
            _sds(qw.codes.shape, qw.codes.dtype, one_chip),
            _sds(qw.scales.shape, qw.scales.dtype, one_chip)]
    if layers:
        args.append(_sds((), jnp.int32, one_chip))
    text = _compile(
        lambda x, c, s, *layer: mixed_gemm(
            x, QuantizedWeight(c, s, bits, 256, k), *layer),
        *args, kernels=["mixed_gemm"])
    if layers:
        assert _weight_passes(text, {"layers": {"w": qw}}) == []


@pytest.mark.parametrize("m", [32, 512], ids=["decode", "prefill"])
def test_int8_gemm_compiles(one_chip, mosaic, m):
    """W8A8: activations quantized a row and group, int8 x int8 on the MXU."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import (QuantizedWeight,
                                                     int8_gemm,
                                                     quantize_gemm_weight)

    k, n = HIDDEN, MLP
    qw = jax.eval_shape(
        functools.partial(quantize_gemm_weight, bits=8, group=256),
        jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
    _compile(
        lambda x, c, s: int8_gemm(x, QuantizedWeight(c, s, 8, 256, k)),
        _sds((m, k), jnp.bfloat16, one_chip),
        _sds(qw.codes.shape, qw.codes.dtype, one_chip),
        _sds(qw.scales.shape, qw.scales.dtype, one_chip),
        kernels=["int8_gemm"])


@pytest.mark.parametrize("rows,experts,tile_m,k,n", [
    (4096, 8, 512, HIDDEN, MLP), (1280, 64, 16, 2048, 1024)],
    ids=["mixtral-train", "olmoe-decode"])
def test_grouped_matmul_compiles(one_chip, mosaic, rows, experts, tile_m, k,
                                 n):
    """The dropless MoE's bf16 grouped GEMM: 8 experts at Mistral's MLP
    widths (Mixtral-8x7B's) on 4096 tile-aligned rows, and OLMoE's 64
    experts on a decode step's 16-row tiles."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    _compile(
        lambda lhs, rhs, tg, sizes: grouped_matmul(lhs, rhs, tg, sizes,
                                                   tile_m=tile_m),
        _sds((rows, k), jnp.bfloat16, one_chip),
        _sds((experts, k, n), jnp.bfloat16, one_chip),
        _sds((rows // tile_m,), jnp.int32, one_chip),
        _sds((experts,), jnp.int32, one_chip),
        kernels=["grouped_matmul"])


@pytest.mark.parametrize("rows,tile_m,h,tokens,top_k", [
    (12288, 128, 2304, 512, 8), (1280, 16, 2048, 32, 8),
    (2432, 16, 2688, 64, 6), (6272, 128, 6144, 512, 8),
    (912, 16, 2304, 48, 8)],
    ids=["mellum2-mixed", "olmoe-decode", "nemotron3-decode",
         "glm52-mixed-share", "kimilinear-decode-share"])
def test_moe_rows_compiles(one_chip, mosaic, rows, tile_m, h, tokens, top_k):
    """The routed experts' gather kernel at the five served layouts (a
    one-hot of the block's sources, transposed into the MXU against the
    step's tokens).  ``gather_rows`` announces a mixed step's as ``pallas``
    and scatters a decode step's few assignments as the parent did."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import moe_rows

    block = moe_rows.block_rows(rows, tile_m, tokens, h, jnp.bfloat16)
    assert block
    # ``src``: a token a row for the kernel, an assignment a row for
    # ``gather_rows`` (only the types matter to a compile)
    args = (_sds((tokens, h), jnp.bfloat16, one_chip),
            _sds((rows,), jnp.int32, one_chip), _sds((), jnp.int32, one_chip))
    _compile(lambda x, src, used: moe_rows._rows_pallas(
        x, src, used, tile_m=tile_m, rows=block, interpret=False),
        *args, kernels=["moe_rows"])
    tracer.clear()
    text = jax.jit(lambda x, src, used, inverse: moe_rows.gather_rows(
        x, inverse, used, rows=rows, tile_m=tile_m, sources=lambda: src)
    ).lower(*args, _sds((tokens, top_k), jnp.int32, one_chip)
            ).compile().as_text()
    (event,) = [s.attrs for s in tracer.spans() if s.name == "kernel/moe_rows"]
    mixed = tokens * top_k >= moe_rows._MIN_LIVE
    assert event == {"rows": rows, "h": h, "tile_m": tile_m, "tokens": tokens,
                     "live": tokens * top_k,
                     "pallas" if mixed else "scatter": 1}
    assert ("tpu_custom_call" in text) == mixed


@pytest.mark.parametrize("n", [HIDDEN * MLP, 1_000_003],
                         ids=["mlp-weight", "ragged"])
def test_fused_adamw_compiles(one_chip, mosaic, n):
    from deepspeed_tpu.ops.fused_optimizers import fused_adamw_flat

    p = _sds((n,), jnp.bfloat16, one_chip)
    m = _sds((n,), jnp.float32, one_chip)
    step = _sds((), jnp.int32, one_chip)
    _compile(lambda p_, g_, m_, v_, s_: fused_adamw_flat(
        p_, g_, m_, v_, s_, lr=1e-3, weight_decay=0.1), p, p, m, m, step,
        kernels=["fused_adamw"])


@pytest.mark.parametrize("program", ["decode_step", "mixed_step",
                                     "spec_step"])
def test_step_programs_carry_their_names(one_chip, mosaic, program):
    """The server's two step programs and the self-draft speculation step
    lower, for the described chip at Mistral-7B's widths and depth (W8A16,
    the serving cells' sizes), to modules ``jit_decode_step``,
    ``jit_mixed_step`` and ``jit_spec_step``: the trace's ``XLA Modules``
    line and every operation name of the benchmark's breakdown start with
    them.  Their kernels and the forward's scopes are in the lowered text by
    name (the verify body has ``cache_write`` from the layer body it shares
    with the other two), and the compiled program leaves the K/V pools and
    the quantized projections where they lie: the kernels read both by
    (layer, ...), and no layer of either is copied or sliced out first."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm

    cfg = dataclasses.replace(tfm.get_config("mistral-7b"), dtype="bfloat16",
                              param_dtype="bfloat16")
    lowered, pool, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip))
    assert pool == (32, 416, 64, KV, D)
    chunked = ["paged_attention_prefill", "mixed_gemm", "prefill_attention",
               "cache_write"]
    inside = {"decode_step": ["paged_attention_decode", "mixed_gemm",
                              "decode_attention", "cache_write", "sampler"],
              "mixed_step": chunked, "spec_step": chunked}[program]
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{program} " in text
    assert "tpu_custom_call" in text
    for name in inside:
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    _assert_pools_stay_in_place(compiled, pool)
    _assert_weights_stay_in_place(compiled, params, "mistral-7b", program)
    if program == "mixed_step":
        _assert_attention_walks_the_tokens(compiled)


@pytest.mark.parametrize("step", ["decode", "mixed"])
def test_unpack_program_compiles_with_its_row_map(one_chip, step):
    """The program that takes a step's one buffer apart, at Mellum2's sizes
    (32 rows, two tables of 132 blocks, 512 tokens a step), handed the
    predecessor's output beside the buffer (ISSUE 54): where a token id is
    negative it is read out of that output by the row map, one small gather,
    and nothing of the buffer is copied but its fields; the shape without an
    output (an engine's first step) compiles to slices alone."""
    from deepspeed_tpu.inference.v2 import programs, ragged

    layout = (ragged.decode_layout(32, 132, two_pools=True)
              if step == "decode"
              else ragged.mixed_layout(512, 32, 132, two_pools=True))
    # (the memo's program, traced for the described chip)
    unpack = programs.build_unpack(layout)
    buf = _sds((layout.size,), jnp.int32, one_chip)
    out = _sds((32 + 2,), jnp.int32, one_chip)  # an MoE model's two stats
    alone = unpack.lower(buf).compile().as_text()
    mapped = unpack.lower(buf, out).compile().as_text()
    assert "gather" not in alone and "select" not in alone
    assert len(re.findall(r" gather\(", mapped)) <= 1
    fields = jax.eval_shape(unpack, buf, out)
    assert sorted(fields) == sorted(f[0] for f in layout.fields)
    assert fields["token_ids"].shape == ((32,) if step == "decode"
                                         else (512,))
    assert fields["token_ids"].dtype == jnp.int32


def _assert_attention_walks_the_tokens(compiled):
    """The mixed step's prefill attention reads the step's queries as the
    layer made them (ISSUE 32): the kernel's name once in the layer body,
    nothing laid out by (row, budget) = ``[32,512,32,128]`` (the parent
    zero-filled one such array, scattered into it, and gathered out of
    another), and a temp below the parent's 404,007,936 B, which held
    both."""
    text = compiled.as_text()
    calls = re.findall(r"%(paged_attention_prefill[.\d]*) = [^\n]*custom-call\(",
                       text)
    assert len(calls) == 1, calls
    assert f"[32,512,{H},{D}]" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 404_007_936 - 2 * 32 * 512 * H * D, temp


# olmoe-1b-7b: 64 experts of width 1024 on hidden 2048, top 8; the decode
# program routes 32 rows (256 assignments), the mixed step 512 tokens (4,096)
OLMOE_E, OLMOE_H, OLMOE_F, OLMOE_K = 64, 2048, 1024, 8


@pytest.mark.parametrize("tokens", [32, 512], ids=["decode", "mixed"])
@pytest.mark.parametrize("k,n", [(OLMOE_H, OLMOE_F), (OLMOE_H, OLMOE_F),
                                 (OLMOE_F, OLMOE_H)],
                         ids=["w_gate", "w_in", "w_out"])
def test_grouped_mixed_gemm_compiles(one_chip, mosaic, k, n, tokens):
    """The grouped W8A16 GEMM of the routed experts at OLMoE's three expert
    shapes, on the layer-stacked codes (16 x 64 experts, the layer an index),
    at the ``tile_m`` the decode and the mixed step program get: the
    picker's tile holds one expert's whole matrix (2 MB of codes a grid
    step), and nothing falls back."""
    from deepspeed_tpu.moe.dropless import moe_tile_m, padded_rows
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.grouped_mixed_gemm import grouped_mixed_gemm
    from deepspeed_tpu.ops.pallas.mixed_gemm import QuantizedWeight

    layers, group = 16, 256
    assignments = tokens * OLMOE_K
    tile_m = moe_tile_m(assignments, OLMOE_E)
    rows = padded_rows(assignments, OLMOE_E)
    assert (tile_m, rows) == {32: (16, 1280), 512: (128, 12288)}[tokens]
    tracer.clear()
    _compile(
        lambda x, c, s, tg, sizes, used, layer: grouped_mixed_gemm(
            x, QuantizedWeight(c, s, 8, group, k), tg, sizes, used,
            tile_m=tile_m, layer=layer),
        _sds((rows, k), jnp.bfloat16, one_chip),
        _sds((layers, OLMOE_E, k, n), jnp.int8, one_chip),
        _sds((layers, OLMOE_E, k // group, n), jnp.float32, one_chip),
        _sds((rows // tile_m,), jnp.int32, one_chip),
        _sds((OLMOE_E,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
        kernels=["grouped_mixed_gemm"])
    (event,) = [s.attrs for s in tracer.spans()
                if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert "fallback" not in event
    assert (event["tile_m"], event["tn"], event["tk"]) == (tile_m, n, k)
    assert event["code_bytes_per_step"] == k * n == 2 << 20
    assert event["grid_steps"] == rows // tile_m


@pytest.mark.parametrize("experts, tokens, top_k, h, f, tiles", [
    # Nemotron-3: 128 experts top 6, 64 rows and 512 tokens a step
    (128, 64, 6, 2688, 1920, ((1920, 896), (2688, 640))),
    (128, 512, 6, 2688, 1920, ((1920, 896), (2688, 640))),
    # GLM-5.2's matrices (a share's layout has other rows, the same tiles)
    (16, 16, 8, 6144, 2048, ((2048, 1024), (3072, 512))),
    (16, 512, 8, 6144, 2048, ((2048, 1024), (3072, 512))),
    # Mixtral's expert: no tile holds all of K
    (8, 32, 2, 4096, 14336, ((3584, 512), (4096, 512))),
], ids=["nemotron3-decode", "nemotron3-mixed", "glm52-decode", "glm52-mixed",
        "mixtral-decode"])
def test_grouped_mixed_gemm_compiles_with_k_in_tiles(one_chip, mosaic,
                                                     experts, tokens, top_k,
                                                     h, f, tiles):
    """The grouped W8A16 GEMM at expert shapes larger than a grid step's 2 MB
    of codes (group 128): the up and the down matrix compile under the dense
    rule's tile, K walked in the grid with the accumulator in VMEM, the
    scale block the tile's whole K column (21, 15, 48, 16, 32 and 112 rows:
    no multiple of the sublane tile has to divide it), and nothing falls
    back."""
    from deepspeed_tpu.moe.dropless import moe_tile_m, padded_rows
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas.grouped_mixed_gemm import grouped_mixed_gemm
    from deepspeed_tpu.ops.pallas.mixed_gemm import QuantizedWeight

    layers, group = 2, 128
    tile_m = moe_tile_m(tokens * top_k, experts)
    rows = padded_rows(tokens * top_k, experts)
    for (k, n), (tn, tk) in zip(((h, f), (f, h)), tiles):
        tracer.clear()
        _compile(
            lambda x, c, s, tg, sizes, used, layer: grouped_mixed_gemm(
                x, QuantizedWeight(c, s, 8, group, k), tg, sizes, used,
                tile_m=tile_m, layer=layer),
            _sds((rows, k), jnp.bfloat16, one_chip),
            _sds((layers, experts, k, n), jnp.int8, one_chip),
            _sds((layers, experts, k // group, n), jnp.float32, one_chip),
            _sds((rows // tile_m,), jnp.int32, one_chip),
            _sds((experts,), jnp.int32, one_chip),
            _sds((), jnp.int32, one_chip), _sds((), jnp.int32, one_chip),
            kernels=["grouped_mixed_gemm"])
        (event,) = [s.attrs for s in tracer.spans()
                    if s.name == "kernel/grouped_mixed_gemm_tiles"]
        assert "fallback" not in event
        assert (event["tn"], event["tk"], event["k_tiles"]) == (tn, tk,
                                                                k // tk)


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_olmoe_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of OLMoE-1B-7B (all 16 layers, W8A16 experts and
    all, the serving cell's sizes) compile for the described chip; the
    grouped kernel is in them by name once a projection (the layer loop holds
    three calls, the expert codes go in whole, and so do the four attention
    projections': no slice of either), the routed FFN's four scopes and the q/k norm are in the lowered operation names,
    and the K/V pools stay where they lie (at MHA widths a layer of one is
    109 MB: every pass the parent made over it cost 4.6 ms)."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm

    cfg = dataclasses.replace(tfm.get_config("olmoe-1b-7b"),
                              dtype="bfloat16", param_dtype="bfloat16")
    lowered, pool, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip))
    assert pool == (16, 416, 64, 16, 128)
    assert params["layers"]["moe"]["w_in"].codes.shape == (16, 64, 2048, 1024)
    assert params["layers"]["moe"]["router"].dtype == jnp.bfloat16
    text = lowered.as_text(debug_info=True)
    assert f"module @jit_{program} " in text
    for name in ("grouped_mixed_gemm", "mixed_gemm", "moe_route",
                 "moe_dispatch", "moe_experts", "moe_combine", "qk_norm"):
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    calls = re.findall(r"%(grouped_mixed_gemm[.\d]*) = [^\n]*custom-call\(",
                       compiled_text)
    assert len(calls) == 3, calls
    assert not re.search(r"dynamic-slice[^\n]*s8\[\d+,64,", compiled_text)
    assert not re.search(r"s8\[64,\d+,\d+\][^\n]* dynamic-slice\(",
                         compiled_text)
    _assert_pools_stay_in_place(compiled, pool)
    _assert_weights_stay_in_place(compiled, params, "olmoe-1b-7b", program)


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_mellum2_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of Mellum2-12B-A2.5B (every width as published,
    W8A16 at group 128, two periods of S S S F, the serving cell's engine
    sizes: a global pool of 3,000 blocks, a window pool of 801, tables of
    132) compile for the described chip.  Both kinds of attention layer run
    the paged kernel (for the mixed program the ring holds a
    ``kernel/paged_attention_window`` event of window 1024, for the decode
    program ``kernel/paged_attention_decode_tiles`` events of windows 1024
    and 0, none with ``fallback``),
    every GEMM its kernel (no ``kernel/*_tiles`` event with ``fallback``:
    group 128 tiles the experts' 2304 x 896 and 896 x 2304), the routed
    FFN's scopes are in the lowered names, and neither pool is copied."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(
        tfm.get_config("mellum2-12b-a2.5b", num_layers=8),
        dtype="bfloat16", param_dtype="bfloat16")
    tracer.clear()
    lowered, pools, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip), group=128,
        num_blocks=3000, num_window_blocks=801, max_blocks_per_seq=132)
    assert pools == ((2, 3000, 64, 4, 128), (6, 801, 64, 4, 128))
    assert params["layers"]["moe"]["w_out"].codes.shape == (8, 64, 896, 2304)
    assert params["layers"]["moe"]["w_out"].group == 128
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    kind = "decode" if program == "decode_step" else "prefill"
    if kind == "decode":  # the decode kernel's tiles name its window
        windows = {a["window"] for name, a in events
                   if name == "kernel/paged_attention_decode_tiles"}
        assert windows == {0, 1024}
    else:
        windows = [a for name, a in events
                   if name == "kernel/paged_attention_window"]
        assert windows and all(a == {"kind": kind, "window": 1024,
                                     "first_block_static": 0}
                               for a in windows)
    text = lowered.as_text(debug_info=True)
    for name in ("grouped_mixed_gemm", "mixed_gemm", "moe_route",
                 "moe_dispatch", "moe_experts", "moe_combine",
                 f"{kind}_attention"):
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    # a period's four layers are unrolled in the loop: four attention calls
    # (three banded, one full: two kernels of one name), twelve expert GEMMs
    calls = re.findall(rf"%(paged_attention_{kind}[.\d]*) = [^\n]*custom-call\(",
                       compiled_text)
    assert len(calls) == 4, calls
    calls = re.findall(r"%(grouped_mixed_gemm[.\d]*) = [^\n]*custom-call\(",
                       compiled_text)
    assert len(calls) == 12, calls
    # the rows reach the grouped layout through the gather kernel, which the
    # compiled text keeps under ``moe_dispatch`` (what
    # ``moe_dispatch_busy_pct`` reads), and no scatter of the layout's rows
    # (the parent's 0.42 ms a call) is left
    from benchmark import kernel_time

    rows_events = {(a["rows"], "pallas" in a, "scatter" in a)
                   for name, a in events if name == "kernel/moe_rows"}
    calls = re.findall(r"%(moe_rows[.\d]*) = [^\n]*custom-call\(",
                       compiled_text)
    if program == "decode_step":  # 256 assignments: the parent's scatter
        assert rows_events == {(1280, False, True)} and not calls
    else:
        assert rows_events == {(12288, True, False)}
        scope_of = kernel_time.scopes_of_text(
            compiled_text, ("moe_route", "moe_dispatch", "moe_experts",
                            "moe_combine"))
        assert len(calls) == 4 and {scope_of.get(c) for c in calls} == {
            "moe_dispatch"}, [(c, scope_of.get(c)) for c in calls]
        assert not re.search(r"= bf16\[12288,2304\]\S* scatter\(",
                             compiled_text)
    for pool in pools:
        assert _pool_passes(compiled_text, pool) == []
    assert _weight_passes(compiled_text, params) == []
    # known and not yet cured (PERF.md section 7, S9): the device keeps a
    # scale stack whose rows (18, 7) are no multiple of the sublane tile with
    # another dimension on the sublanes, and the kernels' operand wants it
    # row-major: all six stacks are copied whole at every step
    assert _whole_scale_stack_copies(compiled_text, params) == [
        "8,18,4096", "8,18,512", "8,18,512", "8,64,18,896", "8,64,18,896",
        "8,64,7,2304"]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        2 * 2 * int(np.prod(pool)) for pool in pools)
    assert mem.temp_size_in_bytes < 0.8e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_nemotron3_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of NVIDIA-Nemotron-3-Nano-30B-A3B (every width
    as published, W8A16 at group 128, the cut ``EMEM*EMEM*``: two scanned runs
    of ``E M`` pairs, an attention layer after each, so the cell's four traced
    bodies and no stack of one layer; the serving cell's engine sizes: 64 rows,
    2,048 blocks, tables of 24) compile for the described chip.  Every GEMM
    runs its kernel (no ``kernel/*_tiles`` event with ``fallback``: the
    experts' width 1856 is stored as 1920, all of N a tile and K walked in
    three, (896, 1920) and (640, 2688)), both state updates leave their ring
    events and their names in the lowered program beside the ``ssm_*``, ``moe_*`` and
    ``moe_shared`` scopes, and the K/V pool and both state arrays are
    updated in place."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(
        tfm.get_config("nemotron3-nano-30b-a3b", num_layers=10,
                       mixer_pattern="EMEM*EMEM*"),
        dtype="bfloat16", param_dtype="bfloat16")
    tracer.clear()
    lowered, pool, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip), group=128,
        max_seqs=64, num_blocks=2048, max_blocks_per_seq=24)
    assert pool == (2, 2048, 64, 2, 128)  # the attention layers' K/V alone
    moe = params["layers"]["E"]["moe"]
    assert moe["w_in"].codes.shape == (4, 128, 2688, 1920)
    assert moe["w_out"].codes.shape == (4, 128, 1920, 2688)
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    grouped = {(a["k"], a["n"], a["tn"], a["tk"]) for name, a in events
               if name == "kernel/grouped_mixed_gemm_tiles"}
    assert grouped == {(2688, 1920, 1920, 896), (1920, 2688, 2688, 640)}
    names = {name for name, _ in events}
    assert "kernel/ssm_decode_update" in names
    assert ("kernel/ssd_chunk_scan_tiles" in names) == (
        program == "mixed_step")
    text = lowered.as_text(debug_info=True)
    kind = "decode" if program == "decode_step" else "prefill"
    scopes = ["grouped_mixed_gemm", "mixed_gemm", "moe_route", "moe_dispatch",
              "moe_experts", "moe_combine", "moe_shared", "ssm_in_proj",
              "ssm_conv", "ssm_scan", "ssm_decode_update", "ssm_gate_norm",
              "ssm_out_proj", f"{kind}_attention"]
    if program == "mixed_step":
        scopes.append("ssd_chunk_scan")
    for name in scopes:
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    assert _pool_passes(compiled_text, pool) == []
    # stacks of four layers are prefetched to the fast memory a layer at a
    # time: 1.5 % of the program's quantized bytes in the decode step (the
    # experts' first scale stack), 3.1 % in the mixed step (the shared
    # expert's codes and the Mamba projections' scales too)
    assert _weight_passes(compiled_text, params, fast_memory_share=0.04) == []
    assert _weight_passes(compiled_text, params, fast_memory_share=0.01)
    # known and not yet cured (PERF.md section 7, S9): scale blocks of 21, 15
    # and 29 rows are no multiple of the sublane tile, and every scale stack
    # is copied whole at every step: 0.25 GB here, 0.29 at the cell's depth
    assert _whole_scale_stack_copies(compiled_text, params) == [
        "2,21,256", "2,21,256", "2,21,4096", "4,128,15,2688", "4,128,21,1920",
        "4,21,3712", "4,21,4096", "4,21,6144", "4,29,2688"]
    mem = compiled.memory_analysis()
    state = 4 * 65 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)
    assert mem.alias_size_in_bytes >= 2 * 2 * int(np.prod(pool)) + state
    assert mem.temp_size_in_bytes < 0.7e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_glm52_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of GLM-5.2 (every width as published, W8A16 at
    group 128, the chip's 16 of 256 experts, an eighth of the vocabulary; the
    cut ``D sssS``: the dense layer that picks and ONE period, so the cell's
    two traced bodies; the serving cell's engine sizes: 16 rows, 4,353
    blocks, tables of 272) compile for the described chip.  Every GEMM runs
    its kernel (no ``kernel/*_tiles`` event with ``fallback``: the grouped
    GEMM walks K = 6144 in six tiles of 1024, all 2048 columns a tile), the
    prefill path leaves its ring event, the rows of one token read their
    picks through the paged decode kernel under the pick's mask (PR 56: its
    ring event, its custom call, and no gather of 16 x 2,048 rows of 640
    left), the lowered program names the latent attention's and the
    indexer's scopes beside the ``moe_*`` ones, and both pools are updated in
    place."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(
        tfm.get_config(
            "glm-5.2", num_layers=5, vocab_size=19360, moe_experts_held=16,
            indexer_types=("full", "shared", "shared", "shared", "full"),
            mlp_layer_types=("dense",) + ("sparse",) * 4),
        dtype="bfloat16", param_dtype="bfloat16")
    tracer.clear()
    lowered, pools, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip), group=128,
        max_seqs=16, num_blocks=4353, max_blocks_per_seq=272)
    assert pools == ((5, 4353, 64, 640), (2, 4353, 64, 128))
    moe = params["layers"]["S"]["moe"]
    assert moe["w_in"].codes.shape == (4, 16, 6144, 2048)
    assert moe["router"].shape == (4, 6144, 256)  # all experts are scored
    assert params["layers"]["A"]["attn"]["w_kvb"].dtype == jnp.bfloat16
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    grouped = {(a["k"], a["n"], a["tn"], a["tk"], a["tile_m"])
               for name, a in events
               if name == "kernel/grouped_mixed_gemm_tiles"}
    tile_m = 16 if program == "decode_step" else 128
    assert grouped == {(6144, 2048, 2048, 1024, tile_m),
                       (2048, 6144, 3072, 512, tile_m)}
    tiled = [a for name, a in events
             if name == "kernel/latent_attention_prefill_tiles"]
    assert bool(tiled) == (program == "mixed_step")
    for a in tiled:  # the kernel engaged: items of 64 queries, 1,024 keys
        assert (a["form"], a["sq"], a["qb"], a["kb"], a["key_chunk"]) == (
            "absorbed, masked, pallas", 64, 8, 16, 1024), a
    rows = [a for name, a in events
            if name == "kernel/latent_attention_decode_tiles"]
    assert rows, "no kernel/latent_attention_decode_tiles event"
    for a in rows:  # 16 blocks a fetch of the tables' 272, three fetches held
        assert a == {"rows": 16, "heads": 64, "w": 640, "block": 64,
                     "s_max": 272 * 64, "k": 2048, "form": "masked, pallas",
                     "kb": 16, "slots": 3}, a
    text = lowered.as_text(debug_info=True)
    scopes = ["grouped_mixed_gemm", "mixed_gemm", "moe_route", "moe_dispatch",
              "moe_experts", "moe_combine", "moe_shared", "dsa_index_scores",
              "dsa_topk", "dsa_index_proj", "latent_attention_decode",
              "latent_q_proj", "latent_kv_proj", "latent_absorb_q",
              "latent_absorb_o"]
    if program == "mixed_step":
        scopes.append("latent_attention_prefill")
    for name in scopes:
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    for pool in pools:
        assert _pool_passes(compiled_text, pool) == []
    # the prefill path is a kernel of the program, under its own name: a
    # tile's scores (32 MB) and accumulator (16 MB) are no buffers of it
    assert bool(re.search(
        r"%latent_attention_prefill[.\d]* = .*custom_call_target="
        r'"tpu_custom_call"', compiled_text)) == (program == "mixed_step")
    # so is the decode path, and the gather it replaced is gone
    assert re.search(r"%latent_attention_decode[.\d]* = .*custom_call_target="
                     r'"tpu_custom_call"', compiled_text)
    assert "[16,2048,640]" not in compiled_text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(2 * int(np.prod(p)) for p in pools)
    assert mem.temp_size_in_bytes < 0.4e9, mem.temp_size_in_bytes


# -- EvaByte: EVA attention over a window pool and a summary pool ------------

#: (m, k, n, bits, group) -> (tm, tn, tk): what ``pick_gemm_tiles`` gave, on
#: the parent of the PR that brought EvaByte (9859eca), at every shape the
#: step programs of the six serving cells hand ``mixed_gemm`` (their
#: ``kernel/mixed_gemm_tiles`` events, traced at the cells' sizes: Mistral-7B
#: twice, OLMoE, Mellum2, Nemotron-3, GLM-5.2).  EvaByte's MLP width is
#: stored padded (``quantization.pad_mlp_width``) so that the picker stays
#: as it is for all of them
_SERVED_GEMM_TILES = [
    (16, 2048, 4096, 8, 128, 16, 4096, 512),
    (16, 2048, 6144, 8, 128, 16, 3072, 512),
    (16, 2048, 16384, 8, 128, 16, 4096, 512),
    (16, 6144, 128, 8, 128, 16, 128, 1536),
    (16, 6144, 512, 8, 128, 16, 512, 1536),
    (16, 6144, 2048, 8, 128, 16, 2048, 1024),
    (16, 6144, 12288, 8, 128, 16, 4096, 512),
    (16, 12288, 6144, 8, 128, 16, 3072, 512),
    (16, 16384, 6144, 8, 128, 16, 3072, 512),
    (32, 2048, 2048, 8, 256, 32, 2048, 512),
    (32, 2304, 512, 8, 128, 32, 512, 384),
    (32, 2304, 4096, 8, 128, 32, 4096, 384),
    (32, 4096, 1024, 8, 256, 32, 1024, 1024),
    (32, 4096, 2304, 8, 128, 32, 2304, 512),
    (32, 4096, 4096, 8, 256, 32, 4096, 512),
    (32, 4096, 14336, 8, 256, 32, 3584, 512),
    (32, 14336, 4096, 8, 256, 32, 4096, 512),
    (64, 2688, 256, 8, 128, 64, 256, 384),
    (64, 2688, 3712, 8, 128, 64, 3712, 384),
    (64, 2688, 4096, 8, 128, 64, 4096, 384),
    (64, 2688, 6144, 8, 128, 64, 3072, 384),
    (64, 3712, 2688, 8, 128, 64, 2688, 128),
    (64, 4096, 2688, 8, 128, 64, 2688, 512),
    (512, 2048, 2048, 8, 256, 512, 2048, 512),
    (512, 2048, 4096, 8, 128, 512, 4096, 512),
    (512, 2048, 6144, 8, 128, 512, 3072, 512),
    (512, 2048, 16384, 8, 128, 512, 4096, 512),
    (512, 2304, 512, 8, 128, 512, 512, 384),
    (512, 2304, 4096, 8, 128, 512, 4096, 384),
    (512, 2688, 256, 8, 128, 512, 256, 384),
    (512, 2688, 3712, 8, 128, 512, 3712, 384),
    (512, 2688, 4096, 8, 128, 512, 4096, 384),
    (512, 2688, 6144, 8, 128, 512, 3072, 384),
    (512, 3712, 2688, 8, 128, 512, 2688, 128),
    (512, 4096, 1024, 8, 256, 512, 1024, 1024),
    (512, 4096, 2304, 8, 128, 512, 2304, 512),
    (512, 4096, 2688, 8, 128, 512, 2688, 512),
    (512, 4096, 4096, 8, 256, 512, 4096, 512),
    (512, 4096, 14336, 8, 256, 512, 3584, 512),
    (512, 6144, 128, 8, 128, 512, 128, 1536),
    (512, 6144, 512, 8, 128, 512, 512, 1536),
    (512, 6144, 2048, 8, 128, 512, 2048, 1024),
    (512, 6144, 12288, 8, 128, 512, 4096, 512),
    (512, 12288, 6144, 8, 128, 512, 3072, 512),
    (512, 14336, 4096, 8, 256, 512, 4096, 512),
    (512, 16384, 6144, 8, 128, 512, 3072, 512),
]


@pytest.mark.parametrize("m,k,n,bits,group,tm,tn,tk", _SERVED_GEMM_TILES)
def test_gemm_tiles_at_the_served_shapes_are_the_parents(m, k, n, bits, group,
                                                          tm, tn, tk):
    from deepspeed_tpu.ops.pallas.mixed_gemm import pick_gemm_tiles

    got = pick_gemm_tiles(m, k, n, bits, group)
    assert (got.tm, got.tn, got.tk) == (tm, tn, tk)


def test_gemm_tiles_at_evabyte_s_shapes():
    """The published 11008 = 2^8 x 43 would tile 256 columns wide (and K in
    one group or all 43); stored as 11264 = 2^10 x 11 it tiles as Mistral's
    14336 does."""
    from deepspeed_tpu.ops.pallas.mixed_gemm import pick_gemm_tiles

    assert pick_gemm_tiles(8, 4096, 11008, 8, 256).tn == 256
    assert pick_gemm_tiles(8, 11008, 4096, 8, 256).tk == 256
    for m in (8, 512):
        up = pick_gemm_tiles(m, 4096, 11264, 8, 256)
        down = pick_gemm_tiles(m, 11264, 4096, 8, 256)
        assert (up.tn, up.tk, up.grid_steps) == (2816, 512, 32)
        assert (down.tn, down.tk, down.grid_steps) == (4096, 512, 22)


@pytest.mark.parametrize("kernel", ["decode", "prefill", "summarize"])
def test_eva_kernels_compile(one_chip, mosaic, kernel):
    """Each EVA kernel alone on the whole pools (layers, blocks, ...) at the
    cell's shapes (32 heads of 128, a window of 2,048 in chunks of 16, blocks
    of 64, four rows), the layer a traced scalar; the ring event names the
    tiles and no fallback, and no pool is copied."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import eva_attention as ea

    sds = functools.partial(_sds, sharding=one_chip)
    layers, rows, bs = 4, 4, 64
    win = sds((layers, 129, bs, H, D), jnp.bfloat16)
    summ = sds((layers, 65, bs, H, D), jnp.bfloat16)
    tab, lens = sds((rows, 256), jnp.int32), sds((rows,), jnp.int32)
    layer, size = sds((), jnp.int32), dict(window=2048, chunk=16)
    tracer.clear()
    if kernel == "summarize":
        text = _compile(
            functools.partial(ea.eva_summarize, **size), win, win, summ,
            summ, layer, tab, tab, lens, sds((H, D), jnp.bfloat16),
            sds((H, D), jnp.bfloat16), kernels=["eva_summarize"])
        event, = [s.attrs for s in tracer.spans()
                  if s.name == "kernel/eva_summarize_tiles"]
        assert event == {"heads": H, "d": D, "block": bs, "window": 2048,
                         "chunk": 16, "blocks_read": 32, "blocks_written": 2}
    else:
        q, more = ((sds((rows, H, D), jnp.bfloat16),
                    (lens, sds((rows,), jnp.bool_))) if kernel == "decode"
                   else (sds((512, H, D), jnp.bfloat16), (lens, lens, lens)))
        text = _compile(
            functools.partial(getattr(ea, f"eva_{kernel}_attention"), **size),
            q, win, win, summ, summ, layer, tab, tab, *more,
            kernels=[f"eva_attention_{kernel}"])
        event, = [s.attrs for s in tracer.spans()
                  if s.name == "kernel/eva_attention_tiles"]
        assert event == {"kind": kernel, "t": 32 if kernel == "decode"
                         else 512, "heads": H, "d": D, "block": bs,
                         "window": 2048, "chunk": 16, "kb": 4,
                         "tq": "8" if kernel == "decode" else "8/128"}
    # (the summariser writes the summary pool in place, and alone, with
    # nothing donated, that is a copy: the step programs' test holds it)
    for blocks in (129,) if kernel == "summarize" else (129, 65):
        assert _pool_passes(text, (layers, blocks, bs, H, D)) == []


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_evabyte_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of EvaByte-6.5B, WHOLE (32 layers, every width
    as published, W8A16 at group 256, the serving cell's engine sizes: four
    rows, a window pool of 129 blocks, a summary pool of 65, tables of 256),
    compile for the described chip.  Attention and the summariser run their
    kernels and every GEMM its own (no ``kernel/*_tiles`` event with
    ``fallback``), one call each a layer of the scan, their scopes are in
    the lowered names, all four pools are updated in place, and arguments and
    temp together stay under the 14.5 GB line (13.2 GB: the configuration
    file's ``sizing``)."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(tfm.get_config("evabyte-6.5b"),
                              dtype="bfloat16", param_dtype="bfloat16")
    tracer.clear()
    lowered, pools, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip),
        max_seqs=4, num_blocks=65, num_window_blocks=129,
        max_blocks_per_seq=256)
    assert pools == ((32, 65, 64, 32, 128), (32, 129, 64, 32, 128))
    assert params["layers"]["mlp"]["w_in"].codes.shape == (32, 4096, 11264)
    assert params["layers"]["attn"]["eva_phi"].shape == (32, 32, 128)
    assert params["lm_head"]["w"].shape == (4096, 8 * 320)
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    kind = "decode" if program == "decode_step" else "prefill"
    assert [a["kind"] for name, a in events
            if name == "kernel/eva_attention_tiles"] == [kind]
    text = lowered.as_text(debug_info=True)
    for name in ("mixed_gemm", f"eva_attention_{kind}", "eva_summarize",
                 "cache_write"):
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    for kernel, calls in ((f"eva_attention_{kind}", 1), ("eva_summarize", 1),
                          ("mixed_gemm", 7)):
        found = re.findall(rf"%({kernel}[.\d]*) = [^\n]*custom-call\(",
                           compiled_text)
        assert len(found) == calls, (kernel, found)
    for pool in pools:
        assert _pool_passes(compiled_text, pool) == []
    assert _weight_passes(compiled_text, params) == []
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        2 * 2 * int(np.prod(pool)) for pool in pools)
    assert mem.temp_size_in_bytes < 0.1e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9


# -- Kimi-Linear: KDA state slots beside a latent pool read whole ------------


@pytest.mark.parametrize("kernel", ["kda_decode", "latent_decode_full"])
def test_kimilinear_kernels_compile(one_chip, mosaic, kernel):
    """The two kernels the fifth kind brought, at the published widths and
    the serving cell's sizes: the KDA decode update (49 slots of 32 heads of
    128 x 128, 20 layers, the state in place) and the paged latent decode
    over a row's whole context (48 rows, 32 absorbed heads at the pool's 640,
    tables of 128 blocks of 64: 16 blocks a fetch, three fetches held; its
    operands and scratch as they were before the body learned to take a
    selection, PR 56: GLM-5.2's operand rides in for GLM-5.2 alone)."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import kda, latent_attention

    f32, H, D = jnp.float32, 32, 128
    sds = functools.partial(_sds, sharding=one_chip)
    tracer.clear()
    if kernel == "kda_decode":
        S1 = 49
        a_slot = sds((S1, H, D), f32)
        _compile(
            kda.kda_decode_update, sds((20, S1, H, D, D), f32),
            sds((), jnp.int32), a_slot, a_slot, a_slot, a_slot,
            sds((S1, H), f32), sds((S1,), bool), sds((S1,), bool),
            kernels=["kda_decode_update"])
        event = "kernel/kda_decode_update"
    else:
        full = functools.partial(latent_attention.latent_decode_attention_full,
                                 scale=192 ** -0.5, latent=512)
        args = (sds((48, H, 640), jnp.bfloat16),
                sds((7, 6145, 64, 640), jnp.bfloat16), sds((), jnp.int32),
                sds((48, 128), jnp.int32), sds((48,), jnp.int32))
        _compile(full, *args, kernels=["latent_attention_decode_full"])
        event = "kernel/latent_attention_decode_full_tiles"
    (attrs,) = [s.attrs for s in tracer.spans() if s.name == event]
    assert "fallback" not in attrs and "xla" not in attrs, attrs
    if kernel == "latent_decode_full":
        assert (attrs["kb"], attrs["slots"]) == (16, 3)
        (call,) = _pallas_calls(jax.make_jaxpr(full)(*args).jaxpr)
        assert call.params["name"] == "latent_attention_decode_full"
        assert [str(v.aval) for v in call.params["jaxpr"].invars] == [
            "Ref<smem>{int32[1]}", "Ref<smem>{int32[48,128]}",
            "Ref<smem>{int32[48]}", "Ref{bfloat16[48,32,640]}",
            "Ref<any>{bfloat16[7,6145,64,640]}", "Ref{float32[48,32,512]}",
            "Ref<smem>{int32[2,49]}", "Ref<vmem>{bfloat16[3,16,64,640]}",
            "Ref<semaphore_mem>{dma_sem[3,16]}"]


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_kimilinear_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of Kimi-Linear-48B-A3B at FULL DEPTH (27 layers,
    every width as published, W8A16 at group 128, the chip's 32 of 256
    experts, an eighth of the vocabulary; the serving cell's engine sizes: 48
    rows, 6,145 blocks, tables of 128) compile for the described chip.  Every
    GEMM runs its kernel and so do the KDA decode update and both latent
    paths (no ``kernel/*`` event with ``fallback``), the scopes of both
    mixers are in the lowered names beside the ``moe_*`` ones, the latent
    pool and both state arrays are updated in place, and arguments and temp
    together stay under the 14.5 GB line (the configuration file's
    ``as_run``)."""
    import dataclasses

    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(
        tfm.get_config("kimi-linear-48b", vocab_size=20480,
                       moe_experts_held=32),
        dtype="bfloat16", param_dtype="bfloat16")
    tracer.clear()
    lowered, pools, params = _lower_step_program(
        program, cfg, functools.partial(_sds, sharding=one_chip), group=128,
        max_seqs=48, num_blocks=6145, max_blocks_per_seq=128)
    assert pools == ((7, 6145, 64, 640),)
    moe = params["layers"]["S"]["moe"]
    assert moe["w_in"].codes.shape == (26, 32, 2304, 1024)
    assert moe["router"].shape == (26, 2304, 256)  # all experts are scored
    kda = params["layers"]["K"]["kda"]
    assert kda["w_qkv"].codes.shape == (20, 2304, 12288)
    assert kda["w_f_up"].dtype == jnp.bfloat16 and \
        kda["A_log"].dtype == jnp.float32
    assert params["layers"]["A"]["attn"]["w_q"].codes.shape == (7, 2304, 6144)
    assert params["layers"]["A"]["attn"]["w_kvb"].dtype == jnp.bfloat16
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    names = {name for name, _ in events}
    assert {"kernel/kda_decode_update",
            "kernel/latent_attention_decode_full_tiles"} <= names
    mixed = program == "mixed_step"
    assert ("kernel/kda_chunk_scan_tiles" in names) == mixed
    assert ("kernel/latent_attention_prefill_tiles" in names) == mixed
    text = lowered.as_text(debug_info=True)
    scopes = ["grouped_mixed_gemm", "mixed_gemm", "moe_route", "moe_dispatch",
              "moe_experts", "moe_combine", "moe_shared", "kda_in_proj",
              "kda_conv", "kda_gate_in", "kda_decode_update", "kda_gate_out",
              "kda_out_proj", "latent_attention_decode_full", "latent_q_proj",
              "latent_kv_proj", "latent_absorb_q", "latent_absorb_o"]
    if mixed:
        scopes += ["latent_attention_prefill", "kda_chunk_scan"]
    for name in scopes:
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    for pool in pools:
        assert _pool_passes(compiled_text, pool) == []
    for kernel in ("kda_decode_update", "latent_attention_decode_full"):
        assert re.search(rf"%{kernel}[.\d]* = .*custom_call_target="
                         r'"tpu_custom_call"', compiled_text), kernel
    if mixed:  # the conv reads the step's tokens through shifted slices
        gathers = _scope_gathers(compiled_text, "kda_conv")
        assert gathers and "[512,12288]" not in gathers, gathers
    mem = compiled.memory_analysis()
    state = 20 * 49 * 32 * 128 * 128 * 4
    assert mem.alias_size_in_bytes >= 2 * int(np.prod(pools[0])) + state
    print(f"kimi-linear-48b-ep8-w8 {program}: arguments "
          f"{mem.argument_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
          f"alias {mem.alias_size_in_bytes}")
    assert mem.temp_size_in_bytes < 0.7e9, mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9


def test_mesh_follows_the_torus(topo):
    """``MeshTopology`` hands a TPU's devices to ``create_device_mesh`` and
    no longer swallows what it raises: the 2x2 slice must arrange."""
    from deepspeed_tpu.parallel import topology

    t = topology.MeshTopology({"fsdp": 2, "tp": 2}, devices=topo.devices)
    assert t.mesh.devices.shape == (1, 1, 2, 1, 1, 2)
    assert {d.id for d in np.ravel(t.mesh.devices)} == \
        {d.id for d in topo.devices}


@pytest.mark.parametrize("kernel", ["selective_scan",
                                    "selective_decode_update"])
def test_jamba2_kernels_compile(one_chip, mosaic, kernel):
    """The two Mamba-1 state updates at AI21-Jamba2-3B's published widths
    and the serving cell's sizes (d_inner 5120 on the lanes, 16 states a
    channel, 33 slots, 26 layers' states in one array aliased in place; a
    step of 512 tokens over 32 rows): both are Mosaic kernels under their own
    names, and the scan's temp holds no ``(T, d_inner, N)`` array (168 MB)."""
    from deepspeed_tpu.observability.trace import tracer
    from deepspeed_tpu.ops.pallas import selective_scan as ss

    f32, i32 = jnp.float32, jnp.int32
    N, di, S1, L, T, R = 16, 5120, 33, 26, 512, 32
    sds = functools.partial(_sds, sharding=one_chip)
    state = sds((L, S1, N, di), f32)
    consts = (sds((N, di), f32),)
    tracer.clear()
    if kernel == "selective_scan":
        rows = (sds((R,), i32),) * 3 + (sds((R,), bool),) * 2
        args = (state, sds((), i32), sds((T, di), jnp.bfloat16),
                sds((T, di), f32), *consts, sds((T, N), f32),
                sds((T, N), f32), sds((di,), f32), *rows)
        fn = ss.selective_scan
    else:
        args = (state, sds((), i32), sds((S1, di), jnp.bfloat16),
                sds((S1, di), f32), *consts, sds((S1, N), f32),
                sds((S1, N), f32), sds((di,), f32), sds((S1,), bool),
                sds((S1,), bool))
        fn = ss.selective_decode_update
    _compile(fn, *args, kernels=[kernel])
    (attrs,) = [s.attrs for s in tracer.spans()
                if s.name == f"kernel/{kernel}"]
    assert "fallback" not in attrs and "xla" not in attrs, attrs
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= L * S1 * N * di * 4
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode_step", "mixed_step"])
def test_jamba2_step_programs_compile(one_chip, mosaic, program):
    """The two step programs of AI21-Jamba2-3B WHOLE (28 layers as 56
    sub-layers, every width as published, bfloat16 weights, the tied head
    over all 65,536 rows; the serving cell's engine sizes: 32 rows, 16,640
    blocks, tables of 520) compile for the described chip: both Mamba-1
    kernels and both paged attention kernels (20 query heads on ONE K/V
    head) run as kernels (no ``kernel/*`` event with ``fallback``), the
    ``sel_*`` and ``dense_ffn`` scopes are in the lowered names, the K/V pool
    and both state arrays are updated in place, and arguments and temp stay
    under the 14.5 GB line (the configuration file's ``as_run``)."""
    import dataclasses

    from deepspeed_tpu.inference.v2 import engine as v2e
    from deepspeed_tpu.inference.v2.programs import kind_of
    from deepspeed_tpu.models import transformer as tfm
    from deepspeed_tpu.observability.trace import tracer

    cfg = dataclasses.replace(tfm.get_config("jamba2-3b"), dtype="bfloat16",
                              param_dtype="bfloat16")
    v2 = v2e.V2Config(max_tokens_per_step=512, max_seqs=32, block_size=64,
                      num_blocks=16640, max_blocks_per_seq=520,
                      dtype="bfloat16")
    sds = functools.partial(_sds, sharding=one_chip)
    params = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda key: tfm.init_params(key, cfg),
                       jax.random.PRNGKey(0)))
    assert "lm_head" not in params  # tied
    arrays = kind_of(cfg).arrays(cfg, v2)
    assert arrays["k"][0] == (2, 16640, 64, 1, 128)
    assert arrays["ssm"] == ((26, 33, 16, 5120), jnp.float32)
    assert arrays["conv"][0] == (26, 33, 3, 5120)
    caches = {name: sds(shape, dtype)
              for name, (shape, dtype) in arrays.items()}
    rows = lambda dtype: sds((v2.max_seqs,), dtype)  # noqa: E731
    tables = sds((v2.max_seqs, v2.max_blocks_per_seq), jnp.int32)
    tracer.clear()
    if program == "decode_step":
        lowered = v2e.build_decode_forward(cfg, v2).lower(
            params, caches, rows(jnp.int32), rows(jnp.int32), tables,
            rows(jnp.int32), rows(jnp.float32), sds((2,), jnp.uint32),
            rows(jnp.int32))
    else:
        tokens = lambda: sds((v2.max_tokens_per_step,), jnp.int32)  # noqa: E731
        lowered = v2e.build_ragged_forward(cfg, v2).lower(
            params, caches, tokens(), tokens(), tokens(), tables,
            rows(jnp.int32), rows(jnp.int32), rows(jnp.int32),
            rows(jnp.int32), None, None, rows(jnp.int32))
    events = [(s.name, s.attrs) for s in tracer.spans()
              if s.name.startswith("kernel/")]
    assert not [e for e in events if "fallback" in e[1]], events
    names = {name for name, _ in events}
    mixed = program == "mixed_step"
    assert "kernel/selective_decode_update" in names
    assert ("kernel/selective_scan" in names) == mixed
    assert ("kernel/paged_attention_prefill_tiles" in names) == mixed
    text = lowered.as_text(debug_info=True)
    scopes = ["sel_in_proj", "sel_conv", "sel_x_proj", "sel_scan",
              "sel_gate", "sel_out_proj", "dense_ffn",
              "selective_decode_update"]
    if mixed:
        scopes += ["selective_scan", "prefill_attention"]
    for name in scopes:
        assert re.search(rf'[/"]{name}/', text), \
            f"{name} is not in the lowered program's operation names"
    compiled = lowered.compile()
    compiled_text = compiled.as_text()
    assert _pool_passes(compiled_text, arrays["k"][0]) == []
    kernels = ["selective_decode_update"] + (
        ["selective_scan", "paged_attention_prefill"] if mixed
        else ["paged_attention_decode"])
    for kernel in kernels:
        assert re.search(rf"%{kernel}[.\d]* = .*custom_call_target="
                         r'"tpu_custom_call"', compiled_text), kernel
    if mixed:  # the conv reads the step's tokens through shifted slices
        gathers = _scope_gathers(compiled_text, "sel_conv")
        assert gathers and "[512,5120]" not in gathers, gathers
    mem = compiled.memory_analysis()
    state = 26 * 33 * 16 * 5120 * 4
    assert mem.alias_size_in_bytes >= \
        2 * 2 * int(np.prod(arrays["k"][0])) + state
    print(f"jamba2-3b-bf16 {program}: arguments "
          f"{mem.argument_size_in_bytes}, temp {mem.temp_size_in_bytes}, "
          f"alias {mem.alias_size_in_bytes}")
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9
