"""The held experts' way back (ISSUE 30, ``models/cores/mla_moe.py``
``held_experts_ffn``): a chunk's rows are summed to their positions inside
the chunk walk (``ops/pallas_kernels.py`` ``add_rows``), forward and
backward, and no array of the step has a row for every pair.

What these tests hold: the primitive, its jnp twin and the Pallas kernel in
interpret mode, against ``jax.ops.segment_sum``; the layer's gradients
against the plain reference's with the router skewed onto one held expert,
so that a group spans chunks; the structure of the step's jaxpr; the
``rows_walked`` counter of the record.

What they cannot hold: that Mosaic compiles the kernel
(``tools/chip_checks.py``, ``tests/benchmarks/test_bm_compile_v5e.py``) and
what it costs (PERF.md, Findings, PR 30).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import r2d2_mla_moe
from r2d2_tpu.config import CoreConfig
from r2d2_tpu.learner.train_step import (create_train_state,
                                         make_learner_step)
from r2d2_tpu.models.cores import mla_moe
from r2d2_tpu.ops.pallas_kernels import (add_rows, add_rows_pallas,
                                         add_rows_reference)
from r2d2_tpu.replay.structs import ReplaySpec
from r2d2_tpu.telemetry.learning import MoeAggregator

from tests.test_cores import TINY_CORE, tiny_config, tiny_net
from tests.test_learning_diag import filled_replay
from tests.test_step_param_traffic import _walk

POSITIONS, WIDTH, ROWS = 40, 128, 48


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


def test_the_kernel_takes_rows_and_positions_that_fill_no_tile(rng):
    """Acting at an odd number of lanes: rows no multiple of 8, positions
    no multiple of the block."""
    pos = jnp.asarray(rng.integers(0, 13, 21), jnp.int32)
    rows = jnp.asarray(rng.standard_normal((21, WIDTH)), jnp.float32)
    got = add_rows_pallas(jnp.zeros((13, WIDTH)), rows, pos, 8, True)
    np.testing.assert_allclose(
        got, jax.ops.segment_sum(rows, pos, 13), rtol=1e-6, atol=1e-6)


# -- (2) the layer's gradients, a group spanning chunks -----------------------


def _skewed_layer(chunk_rows, monkeypatch):
    """An expert layer that holds experts 0..3 of 8, every position sent to
    held expert 1 (and one more), and the same layer as the reference
    spells it: (program, reference), each from (parameters, stream) to the
    layer's output (N, hidden)."""
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", chunk_rows)
    core = CoreConfig(**{**TINY_CORE, "routed_scaling_factor": 2.446,
                         "n_shared_experts": 1})
    ones = jnp.ones((core.hidden_size,))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, core.hidden_size))
    layer = mla_moe.MoE(core, jnp.float32, True)
    params = layer.init(jax.random.PRNGKey(1), x, ones)["params"]
    params["e_score_correction_bias"] = jnp.zeros(8).at[1].set(50.0)

    def program(params, x):
        out, stats = layer.apply({"params": params}, x, ones)
        return out.reshape(-1, core.hidden_size), stats

    def reference(params, x):
        flat = mla_moe.rms_norm(x, ones, core.rms_norm_eps)
        return r2d2_mla_moe._experts(flat.reshape(-1, core.hidden_size),
                                     params, dataclasses.asdict(core))

    return core, params, x, program, reference


@pytest.mark.parametrize("chunk_rows", [8, 2048])
def test_gradients_are_the_references_when_one_group_spans_the_chunks(
        monkeypatch, chunk_rows):
    """The benchmark's tests hold this case forward; here its gradients:
    the stream's, the router's (through the routing weights), the grouped
    products' weights'."""
    core, params, x, program, reference = _skewed_layer(chunk_rows,
                                                        monkeypatch)
    cot = jax.random.normal(jax.random.PRNGKey(4),
                            (x.shape[0] * x.shape[1], core.hidden_size))
    out, stats = program(params, x)
    assert int(stats["chosen"][1]) == out.shape[0]
    assert int(stats["dropped"]) == 0
    pairs = out.shape[0] * core.num_experts_per_tok
    chunk = min(chunk_rows, pairs)
    assert int(stats["rows_walked"]) == -(-int(
        stats["chosen"][:4].sum()) // chunk) * chunk
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
        assert float(jnp.abs(b).max()) > 0, name
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
    assert float(jnp.abs(got[0]["e_score_correction_bias"]).max()) == 0


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
    """The tiny step's 192 pairs a layer walked in chunks of 40 (padded to
    200): the step and its gradient hold (chunk, hidden) and (positions,
    hidden) arrays and none of (pairs, hidden), padded or not, flat or as
    slabs of choices."""
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", 40)
    cfg = tiny_config(**{"network.use_double": True})
    spec = ReplaySpec.from_config(cfg)
    net = tiny_net(cfg)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    rs = filled_replay(spec, rng)
    core = cfg.network.core
    positions = cfg.replay.batch_size * cfg.sequence.seq_len
    top_k, hidden = core.num_experts_per_tok, core.hidden_size
    pairs = positions * top_k
    assert pairs == 192
    step = make_learner_step(net, spec, cfg.optim, True, jit=False)
    shapes = _shapes(jax.make_jaxpr(step)(ts, rs).jaxpr)
    # the walk sees what it is meant to see
    assert (40, hidden) in shapes and (positions, hidden) in shapes
    assert (pairs,) in shapes and (200,) in shapes
    for rows in (pairs, 200):
        assert not [s for s in shapes
                    if len(s) >= 2 and s[-1] == hidden
                    and int(np.prod(s[:-1])) == rows], rows


# -- (4) the counter ----------------------------------------------------------


@pytest.mark.parametrize("chunk_rows", [16, 40, 2560])
def test_rows_walked_is_the_live_chunks_rows(monkeypatch, rng, chunk_rows):
    monkeypatch.setattr(mla_moe, "CHUNK_ROWS", chunk_rows)
    cfg = tiny_config()
    spec = ReplaySpec.from_config(cfg)
    net = tiny_net(cfg)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    rs = filled_replay(spec, rng)
    step = make_learner_step(net, spec, cfg.optim, False)
    core = cfg.network.core
    pairs = (cfg.replay.batch_size * cfg.sequence.seq_len
             * core.num_experts_per_tok)
    chunk = min(chunk_rows, pairs)
    aggregator = MoeAggregator(core)
    expected = 0
    for _ in range(3):
        ts, rs, metrics = step(ts, rs)
        aggregator.on_dispatch(metrics)
        held = int(np.asarray(metrics["moe/chosen"])[0, :core.experts_held]
                   .sum())
        expected += -(-held // chunk) * chunk
    block = aggregator.flush()
    assert block["steps"] == 3
    (layer,) = block["layers"]
    assert layer["rows_walked"] == expected
    assert 0 < layer["pairs_held"] <= layer["rows_walked"] <= 3 * (
        -(-pairs // chunk) * chunk)
    assert layer["dropped"] == 0
