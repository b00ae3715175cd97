"""The held experts' walk and its way back (ISSUE 30 and 34,
``models/cores/experts.py`` ``held_experts_ffn``): a first chunk that holds
the pairs a layer expects, overflow chunks for what runs over; a chunk's
rows are summed to their positions inside the walk
(``ops/pallas_kernels.py`` ``add_rows``), forward and backward, and no array
of the step has a row for every pair.

What these tests hold: the primitive, its jnp twin and the Pallas kernel in
interpret mode (one call and slices), against ``jax.ops.segment_sum``; the
layer and its gradients against the plain references of both cores, with the
held pairs under the first chunk, one pair over it, skewed onto one held
expert across many overflow chunks, none on a held expert, every expert
held (this core's cases here, ``conv_attn_moe``'s in
``test_core_conv_attn_moe.py``); the structure of the step's jaxpr; the
``rows_walked``, ``overflow_chunks`` and ``tile_rows`` counters of the record;
the first chunk's rows at the cells' shapes. (The grouped products' kernel:
``test_grouped_products.py``.)

What they cannot hold: that Mosaic compiles the kernel
(``tools/chip_checks.py``, ``tests/benchmarks/test_bm_compile_v5e.py``) and
what it costs (PERF.md, Findings, PR 30 and 34).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import r2d2_lfm2, r2d2_mla_moe
from r2d2_tpu.config import CoreConfig
from r2d2_tpu.learner.train_step import (create_train_state,
                                         make_learner_step)
from r2d2_tpu.models.cores import conv_attn_moe, experts, mla_moe
from r2d2_tpu.ops import pallas_kernels
from r2d2_tpu.ops.pallas_kernels import (add_rows, add_rows_pallas,
                                         add_rows_reference, sum_rows,
                                         sum_rows_pallas, sum_rows_reference)
from r2d2_tpu.replay.structs import ReplaySpec
from r2d2_tpu.telemetry.learning import MoeAggregator

from tests.test_cores import CORES, tiny_config, tiny_net
from tests.test_learning_diag import filled_replay
from tests.test_step_param_traffic import _walk

POSITIONS, WIDTH, ROWS = 40, 128, 48
# the rows of the walk's first chunk at the cells' 8,000 positions
FIRST_CHUNK = {"moonlight-core": 6656, "lfm2-core": 8704}


# -- (1) the primitive --------------------------------------------------------


def _positions(case, rng):
    """(pos (ROWS,), the rows that count) of a chunk: ``POSITIONS`` marks a
    row that stands for no pair."""
    pos = rng.integers(0, POSITIONS, ROWS)
    if case == "random":                 # a third of the rows dead, anywhere
        pos = np.where(rng.random(ROWS) < 0.33, POSITIONS, pos)
    elif case == "one_position":
        pos = np.full(ROWS, 17)
    elif case == "no_live_row":
        pos = np.full(ROWS, POSITIONS)
    elif case == "every_row_live":
        pass
    elif case == "ends_inside_a_group":
        # sorted pairs as the layer cuts them: a group's positions ascend
        # and none repeats, the next group starts over, and the chunk ends
        # partway through the third; then rows of no held expert
        groups = [np.sort(rng.choice(POSITIONS, size, replace=False))
                  for size in (19, 14, 9)]
        pos = np.concatenate(groups + [np.full(ROWS - 42, POSITIONS)])
    return jnp.asarray(pos, jnp.int32)


IMPLEMENTATIONS = {
    "reference": add_rows_reference,
    "pallas_interpret": lambda acc, rows, pos: add_rows_pallas(
        acc, rows, pos, 16, True),
    # more rows than a call holds: three slices
    "pallas_interpret_sliced": lambda acc, rows, pos: add_rows_pallas(
        acc, rows, pos, 16, True, 16),
    # what the program calls: off the TPU, the jnp twin
    "as_lowered_here": add_rows,
}


@pytest.mark.parametrize("case", ["random", "one_position", "no_live_row",
                                  "every_row_live", "ends_inside_a_group"])
@pytest.mark.parametrize("implementation", sorted(IMPLEMENTATIONS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rows_are_summed_to_their_positions(case, implementation, dtype, rng):
    pos = _positions(case, rng)
    rows = jnp.asarray(rng.standard_normal((ROWS, WIDTH)), dtype)
    acc = jnp.asarray(rng.standard_normal((POSITIONS, WIDTH)), jnp.float32)
    got = IMPLEMENTATIONS[implementation](acc, rows, pos)
    # segment POSITIONS takes the rows that stand for no pair, and is cut
    want = acc + jax.ops.segment_sum(rows.astype(jnp.float32), pos,
                                     POSITIONS + 1)[:POSITIONS]
    assert got.dtype == jnp.float32 and got.shape == acc.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    if case == "no_live_row":
        np.testing.assert_array_equal(got, acc)


FROM_ZERO = {
    "reference": sum_rows_reference,
    "pallas_interpret": lambda rows, pos, n: sum_rows_pallas(
        rows, pos, n, 16, True),
    "pallas_interpret_sliced": lambda rows, pos, n: sum_rows_pallas(
        rows, pos, n, 16, True, 16),
    "as_lowered_here": sum_rows,
}


@pytest.mark.parametrize("case", ["random", "no_live_row",
                                  "ends_inside_a_group"])
@pytest.mark.parametrize("implementation", sorted(FROM_ZERO))
def test_the_first_chunks_sums_start_at_zero(case, implementation, rng):
    """``sum_rows``: the same sums with no array of zeros to read."""
    pos = _positions(case, rng)
    rows = jnp.asarray(rng.standard_normal((ROWS, WIDTH)), jnp.bfloat16)
    got = FROM_ZERO[implementation](rows, pos, POSITIONS)
    want = jax.ops.segment_sum(rows.astype(jnp.float32), pos,
                               POSITIONS + 1)[:POSITIONS]
    assert got.dtype == jnp.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_the_kernel_takes_rows_and_positions_that_fill_no_tile(rng):
    """Acting at an odd number of lanes: rows no multiple of 8, positions
    no multiple of the block."""
    pos = jnp.asarray(rng.integers(0, 13, 21), jnp.int32)
    rows = jnp.asarray(rng.standard_normal((21, WIDTH)), jnp.float32)
    got = add_rows_pallas(jnp.zeros((13, WIDTH)), rows, pos, 8, True)
    np.testing.assert_allclose(
        got, jax.ops.segment_sum(rows, pos, 13), rtol=1e-6, atol=1e-6)


# -- (2) the layer and its gradients, however the pairs fill the walk ---------

CORE_MODULES = {"mla_moe": (mla_moe, r2d2_mla_moe),
                "conv_attn_moe": (conv_attn_moe, r2d2_lfm2)}
ONTO_EXPERT_1 = tuple(50.0 if e == 1 else 0.0 for e in range(8))
OFF_THE_HELD = (-50.0,) * 4 + (0.0,) * 4
ONE_PAIR_OVER = "the held pairs less one"
# name: (the first chunk's rows; None: the shapes' own, which at 40 positions
# is every pair; CHUNK_ROWS; the router's correction bias by expert; experts
# held of 8; the overflow chunks the case is there for)
WALKS = {
    "the_shapes_own_first_chunk": (None, 8, (0.0,) * 8, 4, 0),
    "under_the_first_chunk": (64, 8, (0.0,) * 8, 4, 0),
    "one_pair_over_the_first_chunk": (ONE_PAIR_OVER, 8, (0.0,) * 8, 4, 1),
    "skewed_onto_one_held_expert_many_chunks": (16, 8, ONTO_EXPERT_1, 4, 5),
    "skewed_onto_one_held_expert_one_chunk": (16, 2048, ONTO_EXPERT_1, 4, 1),
    "none_on_a_held_expert": (16, 8, OFF_THE_HELD, 4, 0),
    "every_expert_held": (None, 8, (0.0,) * 8, 8, 0),
}


def expert_layer(kind, monkeypatch, first, chunk_rows, bias, held):
    """An expert layer of either core that holds experts 0..held-1 of 8,
    walked with a first chunk of ``first`` rows and overflow chunks of
    ``chunk_rows``, and the same layer as the core's plain reference spells
    it: (core, parameters, stream, program, reference), the last two from
    (parameters, stream) to the layer's output (N, hidden)."""
    module, plain = CORE_MODULES[kind]
    monkeypatch.setattr(module, "CHUNK_ROWS", chunk_rows)
    core = CoreConfig(**{**CORES[kind], "routed_scaling_factor": 2.446,
                         "n_shared_experts": 1, "experts_held": held})
    ones = jnp.ones((core.hidden_size,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, core.hidden_size))
    layer = module.MoE(core, jnp.float32, True)
    params = layer.init(jax.random.PRNGKey(1), x, ones)["params"]
    params["e_score_correction_bias"] = jnp.asarray(bias)

    def program(params, x):
        out, stats = layer.apply({"params": params}, x, ones)
        return out.reshape(-1, core.hidden_size), stats

    def reference(params, x):
        flat = experts.rms_norm(x, ones, core.rms_norm_eps)
        return plain._experts(flat.reshape(-1, core.hidden_size), params,
                              dataclasses.asdict(core))

    if first == ONE_PAIR_OVER:
        first = int(program(params, x)[1]["chosen"][:held].sum()) - 1
    if first is not None:
        monkeypatch.setattr(experts, "first_chunk_rows",
                            lambda positions, core: first)
    return core, params, x, program, reference


def check_walk(kind, monkeypatch, walk):
    first, chunk_rows, bias, held, overflow = WALKS[walk]
    core, params, x, program, reference = expert_layer(
        kind, monkeypatch, first, chunk_rows, bias, held)
    positions = x.shape[0] * x.shape[1]
    pairs = positions * core.num_experts_per_tok
    cot = jax.random.normal(jax.random.PRNGKey(4),
                            (positions, core.hidden_size))
    out, stats = program(params, x)
    np.testing.assert_allclose(out, reference(params, x), atol=2e-6)
    # the counters: the walk is the first chunk and whole overflow chunks
    on_held = int(stats["chosen"][:held].sum())
    first = experts.first_chunk_rows(positions, core)    # patched or not
    chunk = min(chunk_rows, pairs - first)
    assert int(stats["dropped"]) == 0
    assert int(stats["overflow_chunks"]) == (
        chunk and -(-max(on_held - first, 0) // chunk))
    assert int(stats["rows_walked"]) == first + int(
        stats["overflow_chunks"]) * chunk
    assert on_held <= int(stats["rows_walked"])
    # the case is the one its name says
    assert (int(stats["overflow_chunks"]) >= overflow if overflow > 1
            else int(stats["overflow_chunks"]) == overflow)
    if "skewed" in walk:
        assert int(stats["chosen"][1]) == positions
    assert (on_held == 0) == (walk == "none_on_a_held_expert")
    assert (first == pairs) == (walk in ("the_shapes_own_first_chunk",
                                         "every_expert_held"))

    got = jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * cot),
                   argnums=(0, 1))(params, x)
    want = jax.grad(lambda p, x: jnp.sum(reference(p, x) * cot),
                    argnums=(0, 1))(params, x)
    for name, a, b in [
            ("stream", got[1], want[1]),
            ("router", got[0]["gate"], want[0]["gate"]),
            ("gate_up_proj", got[0]["experts"]["gate_up_proj"],
             want[0]["experts"]["gate_up_proj"]),
            ("down_proj", got[0]["experts"]["down_proj"],
             want[0]["experts"]["down_proj"])]:
        # with no pair on a held expert only the shared expert (mla_moe's)
        # hands the stream a gradient
        assert (float(jnp.abs(b).max()) > 0) == (
            on_held > 0 or (name == "stream" and kind == "mla_moe")), name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
    assert float(jnp.abs(got[0]["e_score_correction_bias"]).max()) == 0


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_layer_and_gradients_are_the_references_however_the_walk_fills(
        monkeypatch, walk):
    """The layer's output and its gradients (the stream's, the router's
    through the routing weights, the grouped products' weights') against
    the plain reference, with the held pairs under the first chunk, one
    pair over it, skewed onto one held expert across many overflow chunks
    (the benchmark's tests hold that case forward) or one, none on a held
    expert, and every expert held."""
    check_walk("mla_moe", monkeypatch, walk)


# -- (3) no array with a row for every pair -----------------------------------


def _shapes(jaxpr):
    """The shape of every value of a jaxpr and of the jaxprs inside it."""
    found = set()
    for eqn in _walk(jaxpr):
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval") and hasattr(v.aval, "shape"):
                found.add(tuple(v.aval.shape))
    return found


def test_no_array_of_the_step_has_a_row_for_every_pair(monkeypatch, rng):
    """The tiny step's 192 pairs a layer, of which it expects 96 here:
    with tiles of 8 rows a first chunk of 104 and overflow chunks of 40
    (the order padded to 224): the step and its gradient hold (first chunk,
    hidden), (overflow chunk, hidden) and (positions, hidden) arrays and
    none of (pairs, hidden), padded or not, flat or as slabs of choices."""
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", 40)
    monkeypatch.setattr(experts, "ROW_TILE", 8)
    cfg = tiny_config(**{"network.use_double": True})
    spec = ReplaySpec.from_config(cfg)
    net = tiny_net(cfg)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    rs = filled_replay(spec, rng)
    core = cfg.network.core
    positions = cfg.replay.batch_size * cfg.sequence.seq_len
    top_k, hidden = core.num_experts_per_tok, core.hidden_size
    pairs = positions * top_k
    assert pairs == 192 and experts.first_chunk_rows(positions, core) == 104
    step = make_learner_step(net, spec, cfg.optim, True, jit=False)
    shapes = _shapes(jax.make_jaxpr(step)(ts, rs).jaxpr)
    # the walk sees what it is meant to see
    assert {(104, hidden), (40, hidden), (positions, hidden)} <= shapes
    assert (pairs,) in shapes and (224,) in shapes
    for rows in (pairs, 224):
        assert not [s for s in shapes
                    if len(s) >= 2 and s[-1] == hidden
                    and int(np.prod(s[:-1])) == rows], rows


# -- (4) the counters ---------------------------------------------------------


@pytest.mark.parametrize("row_tile, chunk_rows", [
    (8, 16), (8, 40), (8, 2560), (128, 16), (512, 1024)])
def test_rows_walked_is_the_first_chunk_and_the_overflow_chunks(
        monkeypatch, rng, row_tile, chunk_rows):
    """Three steps of the tiny learner: with tiles of 8 rows the first
    chunk is the 96 expected pairs and a margin (104 rows), so a step's
    layer runs over it or not as its router falls; with tiles of 128 it
    is 128 rows; with the program's own every one of the 192 pairs."""
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", chunk_rows)
    monkeypatch.setattr(experts, "ROW_TILE", row_tile)
    cfg = tiny_config()
    spec = ReplaySpec.from_config(cfg)
    net = tiny_net(cfg)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    rs = filled_replay(spec, rng)
    step = make_learner_step(net, spec, cfg.optim, False)
    core = cfg.network.core
    positions = cfg.replay.batch_size * cfg.sequence.seq_len
    pairs = positions * core.num_experts_per_tok
    first = experts.first_chunk_rows(positions, core)
    assert first == {8: 104, 128: 128, 512: pairs}[row_tile]
    chunk = min(chunk_rows, pairs - first)
    aggregator = MoeAggregator(core)
    rows = overflow = 0
    for _ in range(3):
        ts, rs, metrics = step(ts, rs)
        aggregator.on_dispatch(metrics)
        held = int(np.asarray(metrics["moe/chosen"])[0, :core.experts_held]
                   .sum())
        overflow += chunk and -(-max(held - first, 0) // chunk)
        rows += first
    block = aggregator.flush()
    assert block["steps"] == 3
    (layer,) = block["layers"]
    assert layer["overflow_chunks"] == overflow
    assert layer["rows_walked"] == rows + overflow * chunk
    assert 0 < layer["pairs_held"] <= layer["rows_walked"] <= 3 * (
        first + (chunk and -(-(pairs - first) // chunk) * chunk))
    assert layer["dropped"] == 0
    # the rows of the row tiles the grouped products visit: whole tiles, the
    # pairs and at most a tile more for every held expert and step
    assert layer["tile_rows"] % row_tile == 0
    assert layer["pairs_held"] <= layer["tile_rows"] <= (
        layer["pairs_held"] + 3 * core.experts_held * row_tile)


def _cell_core(kind, routed, top_k, held=8):
    return CoreConfig(**{**CORES[kind], "n_routed_experts": routed,
                         "num_experts_per_tok": top_k, "experts_held": held})


@pytest.mark.parametrize("positions, core, rows", [
    # the cells' learner steps (64 x 125 positions) and acting (64 lanes)
    (8000, _cell_core("mla_moe", 64, 6), FIRST_CHUNK["moonlight-core"]),
    (8000, _cell_core("conv_attn_moe", 32, 4), FIRST_CHUNK["lfm2-core"]),
    # acting: 48 | 32 pairs expected here, one tile of 256 rows (of 384 | 256
    # pairs: the former keeps an overflow chunk of the other 128)
    (64, _cell_core("mla_moe", 64, 6), 256),
    (64, _cell_core("conv_attn_moe", 32, 4), 256),
    # every expert held: every pair is expected
    (8000, _cell_core("conv_attn_moe", 32, 4, 32), 32000),
    (13, _cell_core("mla_moe", 64, 6), 78),
])
def test_the_first_chunk_follows_from_the_shapes(positions, core, rows):
    assert experts.first_chunk_rows(positions, core) == rows
    expected = (positions * core.num_experts_per_tok * core.experts_held
                / core.n_routed_experts)
    assert expected <= rows <= positions * core.num_experts_per_tok
    # what ``add_rows`` keeps in VMEM whole, in float32, at 2,048 wide
    if rows < 10000:
        assert rows * 2048 * 4 <= pallas_kernels._ADD_ROWS_VMEM_BYTES
