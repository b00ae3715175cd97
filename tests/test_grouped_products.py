"""The held experts' grouped products as the repo's own kernel (ISSUE 37,
``ops/pallas_kernels.py`` ``grouped_matmul_pallas`` / ``grouped_outer_pallas``,
called from ``models/cores/experts.py``).

What these tests hold, on the CPU with the kernels in Pallas' interpret mode
(as ``add_rows``' are held in ``test_moe_way_back.py``): the three forms
(rows times the group's matrix, the same against the weights contracted over
their last axis, the rows-contracted product that is the weights' gradient)
against ``jax.lax.ragged_dot`` / ``ragged_dot_general``, however the groups
fall on the row tiles; the walk of the tiles (``group_visits``) and the
counter that is made of it; the tiles the shapes give and the shapes that
give none; and the layer and its gradients through the kernels against the
plain references of both cores, with a chunk over.

What they cannot hold: that Mosaic compiles the kernels (``tools/
chip_checks.py``) and what they cost (PERF.md, Findings, PR 37).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.models.cores import experts
from r2d2_tpu.ops import pallas_kernels
from r2d2_tpu.ops.pallas_kernels import (grouped_matmul_pallas,
                                         grouped_matmul_reference,
                                         grouped_outer_pallas,
                                         grouped_outer_reference,
                                         grouped_tiles, group_visits,
                                         tile_rows_visited)

from tests.test_moe_way_back import check_walk

TILE = 16
# name: (rows, k, n, the groups' sizes, (row tile, column tile))
CASES = {
    "even_groups": (64, 32, 48, (16, 16, 16, 16), (TILE, 16)),
    "groups_that_end_inside_tiles": (64, 32, 48, (10, 23, 14, 17), (TILE, 16)),
    "an_empty_group": (64, 32, 48, (20, 0, 30, 14), (TILE, 16)),
    "empty_groups_first_and_last": (64, 32, 48, (0, 40, 24, 0), (TILE, 48)),
    "groups_smaller_than_a_tile": (64, 32, 48, (3, 2, 1, 5), (TILE, 16)),
    "eight_groups_in_one_tile": (32, 32, 48, (1, 2, 1, 3, 1, 2, 1, 4),
                                 (TILE, 24)),
    "a_group_of_every_row": (64, 32, 48, (0, 0, 64, 0), (TILE, 16)),
    "rows_past_the_groups_total": (64, 32, 48, (9, 8, 7, 6), (TILE, 16)),
    "no_row_in_any_group": (64, 32, 48, (0, 0, 0, 0), (TILE, 16)),
    "one_row_tile": (32, 32, 48, (10, 12, 4, 6), (32, 16)),
    # a block wider than the columns one product in the kernel takes (512):
    # two chunks in the kernel's loop and 128 left over
    "columns_in_chunks_and_a_rest": (32, 16, 1152, (10, 12, 4, 6),
                                     (16, 1152)),
    # acting at 64 lanes: 384 pairs on 8 held experts, one tile of 128 each
    "acting_384_rows": (384, 16, 128, (40, 61, 35, 52, 48, 50, 47, 51),
                        (128, 128)),
    # the cells' widths cut to a tile or two: hidden 2,048 -> 256,
    # [gate | up] 2,816 | 3,584 -> 2 x 128 + ... kept in their ratio to 128
    "moonlight_core_widths_cut": (256, 256, 352, (30, 41, 28, 35, 33, 29, 31,
                                                  29), (64, 176)),
    "lfm2_core_widths_cut": (256, 256, 448, (31, 35, 29, 33, 30, 34, 32, 32),
                             (64, 224)),
}


def _operands(case, rng, dtype):
    rows, k, n, sizes, tiles = CASES[case]
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    w = jnp.asarray(rng.standard_normal((len(sizes), k, n)), dtype)
    cots = jnp.asarray(rng.standard_normal((rows, n)), dtype)
    return x, w, cots, jnp.asarray(sizes, jnp.int32), sum(sizes), tiles


def _close(got, want, dtype):
    # float32: sums of k products in another order; bf16: one rounding of
    # the float32 sum on either side
    tolerance = dict(rtol=1e-5, atol=1e-4) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tolerance)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_times_their_groups_matrix(case, dtype, rng):
    """Form 1: rows (M, k) x weights (G, k, n) -> (M, n); the rows past the
    groups' total are zeros."""
    x, w, _, sizes, total, tiles = _operands(case, rng, dtype)
    got = grouped_matmul_pallas(x, w, sizes, tiles=tiles, interpret=True)
    want = grouped_matmul_reference(x, w, sizes)
    assert got.dtype == x.dtype and got.shape == want.shape
    _close(got[:total], want[:total], dtype)
    assert not np.asarray(got[total:], np.float32).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_times_the_weights_contracted_over_their_last_axis(
        case, dtype, rng):
    """Form 2: rows (M, k) x weights (G, n, k) as they are kept -> (M, n),
    float32 out as the backward's ``t`` is: the same numbers as form 1 on
    a transposed copy, which is not made."""
    x, w, _, sizes, total, tiles = _operands(case, rng, dtype)
    kept = jnp.swapaxes(w, 1, 2)                       # (G, n, k)
    got = grouped_matmul_pallas(x, kept, sizes, transposed=True,
                                out_dtype=jnp.float32, tiles=tiles,
                                interpret=True)
    want = grouped_matmul_reference(x, w, sizes, out_dtype=jnp.float32)
    twin = grouped_matmul_reference(x, kept, sizes, transposed=True,
                                    out_dtype=jnp.float32)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    _close(got[:total], want[:total], "float32")
    _close(twin[:total], want[:total], "float32")
    assert not np.asarray(got[total:]).any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_weights_gradient_is_each_groups_rows_contracted(case, dtype,
                                                             rng):
    """Form 3: rows (M, k) transposed x cots (M, n) by group -> (G, k, n)
    float32; a group without rows gives zeros, rows in no group nothing."""
    x, _, cots, sizes, _, tiles = _operands(case, rng, dtype)
    got = grouped_outer_pallas(x, cots, sizes, tiles=tiles, interpret=True)
    want = grouped_outer_reference(x, cots, sizes)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    for group, size in enumerate(CASES[case][3]):
        if not size:
            assert not np.asarray(got[group]).any()


@pytest.mark.parametrize("form", ["weights", "weights_last_axis",
                                  "rows_contracted"])
def test_rows_in_no_group_reach_no_result(form, rng):
    """What the rows past the groups' total hold (here: not-a-numbers, as an
    undefined row of an earlier product may) reaches no row of a group and
    no group's block of the weights' gradient."""
    x, w, cots, sizes, total, tiles = _operands(
        "rows_past_the_groups_total", rng, "float32")
    x, cots = x.at[total:].set(jnp.nan), cots.at[total:].set(jnp.nan)
    if form == "rows_contracted":
        got = grouped_outer_pallas(x, cots, sizes, tiles=tiles,
                                   interpret=True)
        want = grouped_outer_reference(x.at[total:].set(0),
                                       cots.at[total:].set(0), sizes)
    else:
        last = form == "weights_last_axis"
        got = grouped_matmul_pallas(
            x, jnp.swapaxes(w, 1, 2) if last else w, sizes, transposed=last,
            tiles=tiles, interpret=True)[:total]
        want = grouped_matmul_reference(x, w, sizes)[:total]
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("outer", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_walk_visits_every_groups_rows_once(case, outer):
    """``group_visits``: a visit's rows are its group's rows (first, end)
    inside its tile; all visits' rows are the groups' rows, each once and in
    order; no more visits than tiles + groups - 1; the tiles past the
    groups' total are visited for no rows where rows are written, a group
    without rows once where a block a group is; the visits past the last
    repeat it."""
    rows, _, _, sizes, (tile, _) = CASES[case]
    at, group, lo, hi, total = (np.asarray(a) for a in group_visits(
        jnp.asarray(sizes, jnp.int32), rows, tile, outer))
    visits, total = len(at), int(total[0])
    assert visits == rows // tile + len(sizes) - 1 and 1 <= total <= visits
    ends = np.cumsum(sizes)
    covered, empty = [], []
    for v in range(total):
        assert 0 <= at[v] < rows // tile and 0 <= group[v] < len(sizes)
        if hi[v] > lo[v]:
            assert (lo[v], hi[v]) == (ends[group[v]] - sizes[group[v]],
                                      ends[group[v]])
        took = range(max(lo[v], at[v] * tile), min(hi[v], (at[v] + 1) * tile))
        covered.extend(took)
        if not took:
            empty.append(v)
    assert covered == list(range(sum(sizes)))
    # the order a block index may move in: tiles and groups never go back
    assert (np.diff(at[:total]) >= 0).all()
    assert (np.diff(group[:total]) >= 0).all() or not outer
    for name in (at, group, lo, hi):
        assert (name[total:] == name[total - 1]).all()
    live_tiles = -(-sum(sizes) // tile)
    if outer:
        assert sorted(group[empty]) == [g for g, s in enumerate(sizes)
                                        if not s]
    else:
        assert sorted(at[empty]) == list(range(live_tiles, rows // tile))
    # the counter: the visits that carry rows, in rows of their tiles
    assert int(tile_rows_visited(jnp.asarray(sizes, jnp.int32), tile)) == (
        total - len(empty)) * tile


@pytest.mark.parametrize("sizes, tile, rows", [
    ((750,) * 8, 128, 47 * 128 + 7 * 128),     # 6,000 rows: 47 tiles + 7
    ((768,) * 8, 128, 8 * 768),                 # the groups end on tiles
    ((1000,) * 8, 256, 32 * 256 + 7 * 256),
    ((0, 5, 0, 0), 8, 8), ((0, 0, 0, 0), 8, 0), ((3, 9, 4, 0), 8, 32),
])
def test_tile_rows_visited_counts_a_shared_tile_for_each_group(sizes, tile,
                                                               rows):
    assert int(tile_rows_visited(jnp.asarray(sizes, jnp.int32), tile)) == rows


# the grouped products a layer of either cell makes (rows, k, n) at its first
# chunk, an overflow chunk and acting's 64 lanes
CELL_PRODUCTS = [
    (rows, k, n)
    for rows_of, hidden, width in (((6656, 1024, 384), 2048, 1408),
                                   ((8704, 1024, 256), 2048, 1792))
    for rows in rows_of
    for k, n in ((hidden, 2 * width), (width, hidden), (hidden, width),
                 (2 * width, hidden))]


@pytest.mark.parametrize("outer", [False, True])
@pytest.mark.parametrize("rows, k, n", CELL_PRODUCTS)
def test_the_cells_shapes_give_tiles_that_fit(rows, k, n, outer):
    """``grouped_tiles`` from the shapes alone: every grouped product of
    either cell gets a row tile of 128 or 256 rows that divides its rows, a
    column tile of whole lanes that divides its columns, and blocks that
    fit the VMEM the kernel asks for."""
    tm, tn = grouped_tiles(rows, k, n, 2, 4, outer)
    assert tm in (128, 256) and rows % tm == 0
    assert tn % 128 == 0 and n % tn == 0
    # the rows' tile, the block that stays (the weights, or the float32
    # result), the tile that passes with the rows (the result's, or the
    # cots'): two buffers of each
    blocks = tm * k * 2 + (k * tn * 4 + tm * tn * 2 if outer
                           else k * tn * 2 + tm * tn * 4)
    assert 2 * blocks <= pallas_kernels._GROUPED_VMEM_BYTES


@pytest.mark.parametrize("rows, k, n", [
    (78, 2048, 2816),        # acting at 13 lanes: no whole row tile
    (6656, 2048, 200),       # columns that fill no lane tile
    (6656, 100, 2816),       # a contraction of no whole lanes
    (6656, 2**20, 2816),     # a contraction whose blocks no VMEM holds
])
def test_shapes_the_kernel_does_not_tile_take_the_reference(rows, k, n):
    """... and the dispatchers then lower ``jax.lax.ragged_dot`` for any
    platform (the tiny twins of the CPU tests are such shapes)."""
    assert grouped_tiles(rows, k, n, 2, 2) is None
    assert grouped_tiles(rows, k, n, 2, 4, outer=True) is None


@pytest.mark.parametrize("rows, kernel", [
    (6656, True), (8704, True), (2048, True), (1024, False), (384, False),
    (256, False)])
def test_calls_of_few_rows_keep_xlas_product(monkeypatch, rows, kernel):
    """The walk's first chunks take the kernel; its overflow chunks and
    acting's chunk, whose call sites cost every start and which no cell's
    step enters, do not reach the choice by platform at all."""
    asked = []
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, **k: asked.append(k) or "by platform")
    for reference in ("grouped_matmul_reference", "grouped_outer_reference"):
        monkeypatch.setattr(pallas_kernels, reference,
                            lambda *a, **k: "reference")
    x = jax.ShapeDtypeStruct((rows, 2048), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((8, 2048, 2816), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((rows, 2816), jnp.bfloat16)
    want = "by platform" if kernel else "reference"
    assert pallas_kernels.grouped_matmul(x, w, None) == want
    assert pallas_kernels.grouped_outer(x, c, None) == want
    assert len(asked) == (2 if kernel else 0)


def test_off_the_tpu_the_program_lowers_the_reference(rng):
    """What the program calls (``platform_dependent``): on this CPU the
    ``jax.lax`` twin, at shapes the kernel would tile on a TPU."""
    x = jnp.asarray(rng.standard_normal((2048, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((4, 128, 256)), jnp.bfloat16)
    sizes = jnp.asarray((50, 70, 0, 90), jnp.int32)
    assert grouped_tiles(2048, 128, 256, 2, 2) is not None
    got = experts.grouped_matmul(x, w, sizes, jnp.bfloat16)
    want = grouped_matmul_reference(x, w, sizes)
    np.testing.assert_array_equal(np.asarray(got[:210], np.float32),
                                  np.asarray(want[:210], np.float32))
    text = jax.jit(lambda x, w, s: experts.grouped_matmul(
        x, w, s, jnp.bfloat16)).lower(x, w, sizes).as_text()
    assert "tpu_custom_call" not in text


# -- the layer through the kernels --------------------------------------------


def _through_the_kernels(monkeypatch):
    """``experts.py``'s grouped products as the TPU's program makes them,
    in interpret mode at tiles of 8 rows (fewer where a chunk's rows are no
    multiple of 8: the case of one pair over)."""
    def tiles(m, n):
        return math.gcd(m, 8), next(t for t in (128, 64, 32, 16, 8)
                                    if n % t == 0)

    def matmul(rows, weights, group_sizes, transposed=False, out_dtype=None):
        n = weights.shape[1 if transposed else 2]
        return grouped_matmul_pallas(
            rows, weights, group_sizes, transposed=transposed,
            out_dtype=out_dtype, tiles=tiles(rows.shape[0], n),
            interpret=True)

    def outer(rows, cots, group_sizes):
        return grouped_outer_pallas(
            rows, cots, group_sizes, tiles=tiles(*cots.shape),
            interpret=True)

    monkeypatch.setattr(pallas_kernels, "grouped_matmul", matmul)
    monkeypatch.setattr(pallas_kernels, "grouped_outer", outer)


@pytest.mark.parametrize("kind", ["mla_moe", "conv_attn_moe"])
@pytest.mark.parametrize("walk", [
    "under_the_first_chunk", "one_pair_over_the_first_chunk",
    "skewed_onto_one_held_expert_many_chunks", "none_on_a_held_expert",
    "every_expert_held"])
def test_layer_and_gradients_through_the_kernels_are_the_references(
        monkeypatch, kind, walk):
    """``held_experts_ffn``'s value and its gradients (the stream's, the
    router's, both weights') through the three kernels against the plain
    reference of either core, at the tolerance ``test_moe_way_back.py``
    holds the ``jax.lax`` path to: under the first chunk, with one chunk
    over, skewed onto one held expert across five overflow chunks, with no
    pair here and with every expert held."""
    _through_the_kernels(monkeypatch)
    calls = []
    kernel = pallas_kernels.grouped_matmul
    monkeypatch.setattr(
        pallas_kernels, "grouped_matmul",
        lambda *a, **k: calls.append(a[0].shape) or kernel(*a, **k))
    check_walk(kind, monkeypatch, walk)
    assert calls, "the layer did not go through the kernels"
