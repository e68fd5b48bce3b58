"""The routed experts' W8A16 kernel walks K in the grid under the dense
GEMM's tile rule.

(i) the kernel in interpret mode against dequantize-then-``ragged_dot`` at
the four MoE cells' expert shapes (fewer experts), with K in one to six
tiles, tiles past the rows, an expert over several M tiles and the layer an
index; (ii) ``pick_gemm_tiles`` answers for the serving cells' dense
projections what is pinned here; (iii) an expert matrix too large for a step to hold all of K
(4096 x 14336) tiles.  What Mosaic makes of the same tiles is
``tests/test_tpu_compile.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.observability.trace import tracer
from deepspeed_tpu.ops.pallas import grouped_mixed_gemm as gmm
from deepspeed_tpu.ops.pallas.grouped_matmul import tile_aligned_layout
from deepspeed_tpu.ops.pallas.mixed_gemm import (GemmTiles,
                                                 dequantize_gemm_weight,
                                                 pick_gemm_tiles,
                                                 quantize_gemm_weight)

LAYERS, LAYER = 2, 1


def _case(rows_per_expert, k, n, group, tile_m, seed=0):
    """→ (rows in the grouped layout, the stacked weight, tile_group, sizes,
    used tiles, rows that are real, dequantize-then-``ragged_dot`` on layer
    ``LAYER``)."""
    E = len(rows_per_expert)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    qw = quantize_gemm_weight(
        jax.random.normal(keys[0], (LAYERS, E, k, n), jnp.bfloat16) * 0.05,
        bits=8, group=group)
    expert = jnp.asarray(np.random.default_rng(seed).permutation(
        np.repeat(np.arange(E), rows_per_expert)), jnp.int32)
    T = int(expert.shape[0])
    pos, tile_group, sizes, m_pad = tile_aligned_layout(expert, E, T, tile_m)
    used = jnp.sum(-(-jnp.bincount(expert, length=E) // tile_m))
    xs = jnp.zeros((m_pad, k), jnp.bfloat16).at[pos].set(
        jax.random.normal(keys[1], (T, k)).astype(jnp.bfloat16))
    w = dequantize_gemm_weight(jax.tree.map(lambda a: a[LAYER], qw))
    want = jax.lax.ragged_dot(xs.astype(jnp.float32),
                              w.astype(jnp.bfloat16).astype(jnp.float32),
                              sizes)
    return xs, qw, tile_group, sizes, used.astype(jnp.int32), pos, want


def _close(got, want, pos):
    """Both accumulate in float32 and round once to bfloat16: one rounding
    of an output of magnitude up to 8 apart."""
    got = got[pos].astype(jnp.float32)
    assert float(jnp.abs(want[pos]).max()) > 0.5  # rows that were computed
    assert float(jnp.abs(got - want[pos]).max()) < 0.04


# (K, N, group, K tiles of the up and of the down matrix) of an expert: the
# cells' configurations, the expert's width as the quantizer stores it
CELLS = {
    "olmoe": (2048, 1024, 256, 1, 1),
    "mellum2": (2304, 896, 128, 1, 1),
    "nemotron3": (2688, 1920, 128, 3, 3),
    "glm52": (6144, 2048, 128, 6, 4),
}


@pytest.mark.parametrize("matrix", ["up", "down"])
@pytest.mark.parametrize("cell", list(CELLS))
def test_kernel_at_the_cells_expert_shapes(cell, matrix):
    """(i) three experts of a cell's shape at a decode step's tile of 16
    rows: the first fills two M tiles, the second gets no row, so three of
    five tiles hold rows and two are skipped; the layer is an index into a
    stack of two; K is walked in as many tiles as the rule gives the
    shape."""
    h, f, group, up_tiles, down_tiles = CELLS[cell]
    k, n, k_tiles = (h, f, up_tiles) if matrix == "up" else (f, h, down_tiles)
    xs, qw, tile_group, sizes, used, pos, want = _case(
        [20, 0, 7], k, n, group, tile_m=16)
    assert (xs.shape[0] // 16, int(used)) == (5, 3)
    tracer.clear()
    got = jax.jit(lambda *a: gmm.grouped_mixed_gemm(
        *a, tile_m=16, layer=jnp.int32(LAYER)))(
            xs, qw, tile_group, sizes, used)
    (event,) = [s.attrs for s in tracer.spans()
                if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert "fallback" not in event
    assert event["k_tiles"] == k_tiles == k // event["tk"]
    assert event["grid_steps"] == 5 * (n // event["tn"]) * k_tiles
    assert event["m_tiles"] * event["steps_per_expert"] == event["grid_steps"]
    _close(got, want, pos)


@pytest.mark.parametrize("n_tiles", [1, 2])
@pytest.mark.parametrize("k_tiles", [1, 2, 3])
@pytest.mark.parametrize("tile_m", [16, 64])
def test_kernel_walks_k_in_tiles(tile_m, k_tiles, n_tiles):
    """(i) the same rows under tiles that cut K in one, two and three and N
    in one and two: the first expert fills three M tiles, the LAST experts
    get no row (a skipped tile then names another expert than its
    ``tile_group`` entry), one expert fills its tile to the last row."""
    k, n, group = 768, 512, 128
    xs, qw, tile_group, sizes, used, pos, want = _case(
        [2 * tile_m + 1, 0, 5, tile_m, 0, 0], k, n, group, tile_m, seed=1)
    m_tiles = xs.shape[0] // tile_m
    assert int(used) == 5 and m_tiles > 5
    tiles = GemmTiles(tile_m, n // n_tiles, k // k_tiles,
                      m_tiles * n_tiles * k_tiles, 0)
    got = jax.jit(lambda x, c, s, tg, u: gmm._grouped_pallas(
        x, c, s, tg, u.reshape(1), jnp.full((1,), LAYER, jnp.int32), tiles,
        group))(xs, qw.codes, qw.scales, tile_group, used)
    _close(got, want, pos)


def test_a_skipped_step_names_the_last_live_steps_blocks():
    """A tile past ``used`` moves no block index: M tile, N tile and K tile
    are the last step's that had rows, so its codes, scales and rows are in
    VMEM already; with no row at all, the first step's."""
    nj, nk = 3, 4
    live = jax.jit(lambda i, j, kk, used: gmm.live_step(i, j, kk, used, nj,
                                                        nk))
    for i, j, kk in ((0, 0, 0), (4, 2, 3), (4, 1, 2)):
        assert tuple(map(int, live(i, j, kk, 5))) == (i, j, kk)
    for i, j, kk in ((5, 0, 0), (5, 1, 2), (40, 2, 3)):
        assert tuple(map(int, live(i, j, kk, 5))) == (4, 2, 3)
    assert tuple(map(int, live(0, 0, 0, 0))) == (0, 2, 3)


# (ii) the dense projections of the serving cells' models: the group, the
# rows of a decode and of a mixed step (one M tile each), and (k, n) → (tn,
# tk, grid steps, code bytes a step) as the parent's picker answered (8e47bcc:
# gathered from the ``kernel/mixed_gemm_tiles`` events of the models' lowered
# decode and mixed step programs)
DENSE = {
    "mistral-7b": (256, (32, 512), [
        (4096, 1024, 1024, 1024, 4, 1048576),
        (4096, 4096, 4096, 512, 8, 2097152),
        (4096, 14336, 3584, 512, 32, 1835008),
        (14336, 4096, 4096, 512, 28, 2097152)]),
    "olmoe-1b-7b": (256, (32, 512), [
        (2048, 2048, 2048, 512, 4, 1048576)]),
    "mellum2-12b-a2.5b": (128, (32, 512), [
        (2304, 512, 512, 384, 6, 196608), (2304, 4096, 4096, 384, 6, 1572864),
        (4096, 2304, 2304, 512, 8, 1179648)]),
    "nemotron3-nano-30b-a3b": (128, (64, 512), [
        (2688, 256, 256, 384, 7, 98304), (2688, 3712, 3712, 384, 7, 1425408),
        (2688, 4096, 4096, 384, 7, 1572864),
        (2688, 6144, 3072, 384, 14, 1179648),
        (3712, 2688, 2688, 128, 29, 344064),
        (4096, 2688, 2688, 512, 8, 1376256)]),
    "glm-5.2": (128, (16, 512), [
        (2048, 4096, 4096, 512, 4, 2097152),
        (2048, 6144, 3072, 512, 8, 1572864),
        (2048, 16384, 4096, 512, 16, 2097152),
        (6144, 128, 128, 1536, 4, 196608), (6144, 512, 512, 1536, 4, 786432),
        (6144, 2048, 2048, 1024, 6, 2097152),
        (6144, 12288, 4096, 512, 36, 2097152),
        (12288, 6144, 3072, 512, 48, 1572864),
        (16384, 6144, 3072, 512, 64, 1572864)]),
}


@pytest.mark.parametrize("model", list(DENSE))
def test_dense_picker_answers_what_the_parent_answered(model):
    group, rows, shapes = DENSE[model]
    for m in rows:
        for k, n, *want in shapes:
            t = pick_gemm_tiles(m, k, n, 8, group)
            assert (t.tm, t.tn, t.tk, t.grid_steps,
                    t.code_bytes_per_step) == (m, *want), (m, k, n)


@pytest.mark.parametrize("args, want", [
    # GLM-5.2 (12.6 MB an expert): all of N or half of it, 2 MB a step
    ((400, 16, 6144, 2048, 8, 128), (2048, 1024)),
    ((6272, 128, 2048, 6144, 8, 128), (3072, 512)),
    # (iii) Mixtral's expert, which no tile holding all of K fitted
    ((1280, 16, 4096, 14336, 8, 128), (3584, 512)),
    ((1280, 16, 14336, 4096, 8, 128), (4096, 512)),
])
def test_grouped_picker_at_experts_of_many_steps(args, want):
    """OLMoE's, Mellum2's and Nemotron-3's experts are pinned beside their
    models (``test_nemotron3.py``, ``test_mellum2.py``)."""
    t = gmm.pick_grouped_tiles(*args)
    assert (t.tm, t.tn, t.tk) == (args[1],) + want


def test_an_expert_too_large_for_one_step_runs_the_kernel():
    """(iii) 4096 x 14336 at group 128 (58.7 MB of codes an expert, more
    than a step can hold all of K of): K is walked in tiles of 512."""
    xs, qw, tile_group, sizes, used, pos, want = _case(
        [9, 3], 4096, 14336, 128, tile_m=16)
    tracer.clear()
    got = jax.jit(lambda *a: gmm.grouped_mixed_gemm(
        *a, tile_m=16, layer=jnp.int32(LAYER)))(
            xs, qw, tile_group, sizes, used)
    (event,) = [s.attrs for s in tracer.spans()
                if s.name == "kernel/grouped_mixed_gemm_tiles"]
    assert "fallback" not in event
    assert (event["tn"], event["tk"], event["k_tiles"]) == (3584, 512, 8)
    _close(got, want, pos)
