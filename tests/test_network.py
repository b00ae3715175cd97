"""Network semantics tests (SURVEY.md §4: golden-value + invariant checks).

Verifies the static-shape unroll reproduces the reference's sequence
semantics (/root/reference/model.py:48-157) without pack/pad:
  * step-by-step unroll == whole-sequence unroll (causality);
  * dueling identity q = v + a - mean(a) ⇒ mean-advantage invariance;
  * gather-index math matches a naive ragged python reference;
  * padded suffix steps never affect gathered valid outputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import NetworkConfig
from r2d2_tpu.models import init_network, initial_hidden
from r2d2_tpu.ops.indexing import (
    frame_stack_indices,
    learning_step_mask,
    online_q_positions,
    target_q_positions,
)

A = 6


@pytest.fixture(scope="module")
def small_net():
    cfg = NetworkConfig(hidden_dim=32, cnn_out_dim=64)
    spec, params = init_network(
        jax.random.PRNGKey(0), A, cfg, frame_stack=2, frame_height=36, frame_width=36
    )
    return spec, params


def _rand_inputs(key, batch, seq, hw=36, stack=2):
    k1, k2 = jax.random.split(key)
    obs = jax.random.uniform(k1, (batch, seq, hw, hw, stack))
    la = jax.nn.one_hot(
        jax.random.randint(k2, (batch, seq), 0, A), A, dtype=jnp.float32
    )
    return obs, la


def test_unroll_matches_stepwise(small_net):
    """T-step unroll == T single steps threading hidden state: the actor's
    `step` and the learner's sequence pass are the same program."""
    spec, params = small_net
    obs, la = _rand_inputs(jax.random.PRNGKey(1), 2, 5)
    hidden = initial_hidden(2, spec.config.hidden_dim)

    q_full, h_full = spec.apply(params, obs, la, hidden)

    h = hidden
    qs = []
    for t in range(5):
        q_t, h = spec.apply(params, obs[:, t : t + 1], la[:, t : t + 1], h)
        qs.append(q_t[:, 0])
    q_step = jnp.stack(qs, axis=1)

    np.testing.assert_allclose(np.asarray(q_full), np.asarray(q_step), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h), atol=1e-5)


def test_padding_suffix_does_not_affect_prefix(small_net):
    """Causality: garbage past a sequence's true end leaves the valid prefix
    bit-identical — this is what licenses fixed-window unrolls over ragged
    sequences (replacing ref model.py:103-108 pack_padded_sequence)."""
    spec, params = small_net
    obs, la = _rand_inputs(jax.random.PRNGKey(2), 1, 6)
    hidden = initial_hidden(1, spec.config.hidden_dim)

    q_a, _ = spec.apply(params, obs, la, hidden)

    obs_b = obs.at[:, 4:].set(0.12345)
    la_b = la.at[:, 4:].set(0.0)
    q_b, _ = spec.apply(params, obs_b, la_b, hidden)

    np.testing.assert_allclose(np.asarray(q_a[:, :4]), np.asarray(q_b[:, :4]), atol=1e-6)


def test_dueling_mean_advantage_invariance(small_net):
    """Adding a constant to all advantages must not change Q (the mean
    baseline subtracts it) — the dueling identity of ref model.py:61."""
    spec, params = small_net
    obs, la = _rand_inputs(jax.random.PRNGKey(3), 2, 1)
    hidden = initial_hidden(2, spec.config.hidden_dim)

    q, _ = spec.apply(params, obs, la, hidden)

    shifted = jax.tree_util.tree_map(lambda x: x, params)
    bias_path = shifted["params"]["head"]["adv_out"]["bias"]
    shifted["params"]["head"]["adv_out"]["bias"] = bias_path + 3.7
    q_shift, _ = spec.apply(shifted, obs, la, hidden)

    np.testing.assert_allclose(np.asarray(q), np.asarray(q_shift), atol=1e-4)


@pytest.mark.slow
def test_hoisted_lstm_matches_flax_optimized_cell():
    """HoistedLSTM (input projection outside the scan) must reproduce
    nn.OptimizedLSTMCell exactly given the same weights: map flax's
    per-gate i{comp}/h{comp} params onto the concatenated [i,f,g,o] layout
    and compare the full unrolled outputs and final carry."""
    import flax.linen as nn

    from r2d2_tpu.models.network import HoistedLSTM

    B, T, D, H = 3, 11, 10, 8
    key = jax.random.PRNGKey(42)
    xs = jax.random.normal(key, (B, T, D))
    c0 = jax.random.normal(jax.random.fold_in(key, 1), (B, H))
    h0 = jax.random.normal(jax.random.fold_in(key, 2), (B, H))

    cell = nn.OptimizedLSTMCell(features=H)
    cell_params = cell.init(jax.random.PRNGKey(0), (c0, h0), xs[:, 0])

    scan_cell = nn.scan(
        nn.OptimizedLSTMCell, variable_broadcast="params",
        split_rngs={"params": False}, in_axes=1, out_axes=1)(features=H)
    (c_ref, h_ref), out_ref = scan_cell.apply(cell_params, (c0, h0), xs)

    p = cell_params["params"]
    gates = ["i", "f", "g", "o"]
    hoisted_params = {"params": {
        "input_proj": {"kernel": jnp.concatenate(
            [p[f"i{g}"]["kernel"] for g in gates], axis=1)},
        "recurrent_kernel": jnp.concatenate(
            [p[f"h{g}"]["kernel"] for g in gates], axis=1),
        "bias": jnp.concatenate([p[f"h{g}"]["bias"] for g in gates]),
    }}
    lstm = HoistedLSTM(features=H)
    (c_got, h_got), out_got = lstm.apply(hoisted_params, (c0, h0), xs)

    np.testing.assert_allclose(np.asarray(out_got), np.asarray(out_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c_got), np.asarray(c_ref),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_got), np.asarray(h_ref),
                               rtol=1e-5, atol=1e-6)

    # scan_unroll is a schedule knob, not a math change — bitwise-identical
    # outputs for any unroll factor (incl. one that doesn't divide T=11)
    for unroll in (4, 11):
        (c_u, h_u), out_u = HoistedLSTM(features=H, unroll=unroll).apply(
            hoisted_params, (c0, h0), xs)
        np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_got))
        np.testing.assert_array_equal(np.asarray(c_u), np.asarray(c_got))
        np.testing.assert_array_equal(np.asarray(h_u), np.asarray(h_got))


def test_non_dueling_head():
    cfg = NetworkConfig(hidden_dim=16, cnn_out_dim=32, use_dueling=False)
    spec, params = init_network(
        jax.random.PRNGKey(0), A, cfg, frame_stack=2, frame_height=36, frame_width=36
    )
    obs, la = _rand_inputs(jax.random.PRNGKey(4), 1, 2)
    q, h = spec.apply(params, obs, la, initial_hidden(1, 16))
    assert q.shape == (1, 2, A)
    assert h.shape == (1, 2, 16)


@pytest.mark.slow
def test_bf16_policy_runs_f32_outputs():
    cfg = NetworkConfig(hidden_dim=16, cnn_out_dim=32, bf16=True)
    spec, params = init_network(
        jax.random.PRNGKey(0), A, cfg, frame_stack=2, frame_height=36, frame_width=36
    )
    obs, la = _rand_inputs(jax.random.PRNGKey(5), 1, 3)
    q, h = spec.apply(params, obs, la, initial_hidden(1, 16))
    assert q.dtype == jnp.float32 and h.dtype == jnp.float32
    # params stay f32 (mixed-precision policy, not a cast-down of weights)
    assert params["params"]["torso"]["Conv_0"]["kernel"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# Gather-index semantics vs naive ragged reference
# ---------------------------------------------------------------------------


def _naive_target_positions(burn_in, learning, forward, fwd_max):
    """Literal transcription of the reference's slice-then-edge-pad loop
    (ref model.py:110-118), producing explicit output positions."""
    seq_len = burn_in + learning + forward
    start = burn_in + fwd_max
    positions = list(range(start, seq_len))
    pad = min(fwd_max - forward, learning)
    positions += [seq_len - 1] * pad
    return positions  # length == learning


@pytest.mark.parametrize(
    "burn_in,learning,forward",
    [
        (4, 10, 5),   # full window mid-episode
        (0, 10, 5),   # episode start, no burn-in yet
        (4, 10, 1),   # near episode end: forward shortened
        (4, 3, 1),    # final ragged tail: slice is empty, all edge-pad
        (2, 1, 1),    # single learning step
    ],
)
def test_target_positions_match_reference_semantics(burn_in, learning, forward):
    fwd_max, learn_max = 5, 10
    pos = target_q_positions(
        jnp.array([burn_in]), jnp.array([learning]), jnp.array([forward]),
        learn_max, fwd_max,
    )[0]
    naive = _naive_target_positions(burn_in, learning, forward, fwd_max)
    assert len(naive) == learning
    np.testing.assert_array_equal(np.asarray(pos[:learning]), np.asarray(naive))


def test_online_positions_and_mask():
    pos = online_q_positions(jnp.array([4, 0]), 10)
    np.testing.assert_array_equal(np.asarray(pos[0]), np.arange(4, 14))
    np.testing.assert_array_equal(np.asarray(pos[1]), np.arange(0, 10))
    mask = learning_step_mask(jnp.array([3, 10]), 10)
    assert mask[0].sum() == 3 and mask[1].sum() == 10
    assert mask[0, 2] == 1.0 and mask[0, 3] == 0.0


def test_actor_policy_forces_f32_under_bf16(rng):
    """Actors infer on host CPUs where bf16 is emulated: given a learner
    net with the bf16 policy forced on, ActorPolicy must rebuild itself
    f32 — and the learner's params (f32 storage under either policy) must
    drive it unchanged."""
    from r2d2_tpu.actor.policy import ActorPolicy
    from r2d2_tpu.models.network import NetworkApply

    cfg = NetworkConfig(hidden_dim=16, cnn_out_dim=32, bf16="on",
                        conv_layers=((8, 4, 2), (16, 3, 1)))
    net = NetworkApply(4, cfg, 2, 20, 20)
    assert net.config.bf16 is True          # forced on, resolved concrete
    params = net.init(jax.random.PRNGKey(0))
    # params are f32 storage even under the bf16 compute policy
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.float32

    policy = ActorPolicy(net, params, epsilon=0.0, seed=0)
    assert policy.net.config.bf16 is False  # rebuilt f32 for CPU inference
    policy.observe_reset(np.asarray(rng.integers(0, 255, (20, 20)), np.uint8))
    action, q, hidden = policy.act()
    assert 0 <= int(action) < 4
    assert np.asarray(q).dtype == np.float32
    assert np.isfinite(np.asarray(q)).all()


def test_frame_stack_indices():
    idx = frame_stack_indices(5, 4)
    assert idx.shape == (5, 4)
    np.testing.assert_array_equal(np.asarray(idx[0]), [0, 1, 2, 3])
    np.testing.assert_array_equal(np.asarray(idx[4]), [4, 5, 6, 7])
