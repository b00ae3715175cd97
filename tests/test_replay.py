"""Replay pipeline tests: LocalBuffer block assembly → device/host replay
add/sample/update, checked against the reference's ragged semantics
(/root/reference/worker.py:395-492, 85-209) via hand-computed expectations
and brute-force oracles (SURVEY.md §4).
"""

import jax
import numpy as np
import pytest

from r2d2_tpu.actor.local_buffer import LocalBuffer
from r2d2_tpu.replay import (
    HostReplay,
    ReplaySpec,
    replay_add,
    replay_init,
    replay_sample,
)
from r2d2_tpu.replay.device_replay import replay_size

A = 4  # action dim
HD = 8  # hidden dim


def make_spec(**kw) -> ReplaySpec:
    base = dict(
        num_blocks=8, seqs_per_block=4, block_length=20, burn_in=4,
        learning=5, forward=3, frame_stack=2, frame_height=12, frame_width=12,
        hidden_dim=HD, batch_size=16, prio_exponent=0.9, is_exponent=0.6,
    )
    base.update(kw)
    return ReplaySpec(**base)


def drive(buf: LocalBuffer, rng, n_steps: int, start_t: int = 0):
    """Push n_steps synthetic transitions; returns the per-step records."""
    recs = []
    for i in range(n_steps):
        t = start_t + i
        obs = np.full((12, 12), t % 250, np.uint8)
        q = rng.normal(size=A).astype(np.float32)
        hidden = rng.normal(size=(2, HD)).astype(np.float32)
        action = t % A
        reward = float(t % 3)
        buf.add(action, reward, obs, q, hidden)
        recs.append((action, reward, obs, q, hidden))
    return recs


def test_local_buffer_full_block_metadata(rng):
    """Full 20-step block with bootstrap: the reference's per-sequence
    burn-in/learning/forward formulas (ref worker.py:468-471)."""
    spec = make_spec()
    buf = LocalBuffer(spec, A, gamma=0.9)
    buf.reset(np.zeros((12, 12), np.uint8))
    drive(buf, rng, 20)
    blk = buf.finish(last_qval=np.ones(A, np.float32))

    assert int(blk.num_sequences) == 4
    np.testing.assert_array_equal(blk.burn_in_steps, [0, 4, 4, 4])
    np.testing.assert_array_equal(blk.learning_steps, [5, 5, 5, 5])
    np.testing.assert_array_equal(blk.forward_steps, [3, 3, 3, 1])
    np.testing.assert_array_equal(blk.seq_start, [0, 5, 10, 15])
    assert np.isnan(float(blk.sum_reward))  # not an episode end
    assert buf.curr_burn_in == 4  # burn-in carried to next block

    # n-step gamma: full window gamma^3 until the bootstrap-shortened tail
    g = blk.gamma.reshape(-1)[:20]
    np.testing.assert_allclose(g[:17], 0.9**3, rtol=1e-6)
    np.testing.assert_allclose(g[17:20], [0.9**3, 0.9**2, 0.9**1], rtol=1e-6)

    # n-step reward vs brute force (ref worker.py:463-466)
    rewards = np.array([t % 3 for t in range(20)], float)
    want = [sum(0.9**i * (rewards[t + i] if t + i < 20 else 0.0) for i in range(3))
            for t in range(20)]
    np.testing.assert_allclose(blk.reward.reshape(-1)[:20], want, rtol=1e-5)


def test_first_block_hidden_at_window_start(rng):
    """Episode-start blocks: the stored hidden must be the state at the
    sequence's WINDOW start (seq_start - burn_in), not s*learning steps in.
    The reference stores the latter (worker.py:459), handing the learner a
    state that already consumed the burn-in it is about to replay — a
    deliberate divergence fixed here."""
    spec = make_spec()
    buf = LocalBuffer(spec, A, gamma=0.9)
    buf.reset(np.zeros((12, 12), np.uint8))
    recs = drive(buf, rng, 20)
    blk = buf.finish(last_qval=np.ones(A, np.float32))

    # s=0: window start 0 -> initial zero state
    np.testing.assert_array_equal(blk.hidden[0], 0.0)
    # s=1: burn_in=min(5,4)=4, seq_start=5 -> window start 1 -> state after
    # step 1 = recs[0]'s hidden
    np.testing.assert_allclose(blk.hidden[1], recs[0][4], rtol=1e-6)
    # s=2: burn_in=4, seq_start=10 -> window start 6 -> recs[5]'s hidden
    np.testing.assert_allclose(blk.hidden[2], recs[5][4], rtol=1e-6)


def test_local_buffer_episode_end_and_carry(rng):
    """Partial block at episode end: zeroed gamma tail, episode return
    reported, next episode restarts burn-in at 0 (ref worker.py:445-456)."""
    spec = make_spec()
    buf = LocalBuffer(spec, A, gamma=0.9)
    buf.reset(np.zeros((12, 12), np.uint8))
    drive(buf, rng, 13)
    blk = buf.finish(last_qval=None)

    assert int(blk.num_sequences) == 3
    np.testing.assert_array_equal(blk.learning_steps[:3], [5, 5, 3])
    np.testing.assert_array_equal(blk.forward_steps[:3], [3, 3, 1])
    # terminal: last min(size, forward)=3 effective gammas are zero
    flat_gamma = blk.gamma.reshape(-1)
    np.testing.assert_allclose(flat_gamma[10:13], 0.0, atol=0)
    expected_return = sum(t % 3 for t in range(13))
    assert float(blk.sum_reward) == pytest.approx(expected_return)
    # empty 4th slot must be unsamplable
    assert blk.priority[3] == 0.0 and blk.learning_steps[3] == 0


def test_local_buffer_cross_block_hidden_alignment(rng):
    """Second block: hidden snapshot s=0 is the state before the *window*
    (burn-in start), i.e. the hidden captured burn_in steps before seq_start
    (the stored-state strategy, ref worker.py:459 + SURVEY §5.7)."""
    spec = make_spec()
    buf = LocalBuffer(spec, A, gamma=0.9)
    buf.reset(np.zeros((12, 12), np.uint8))
    recs1 = drive(buf, rng, 20)
    buf.finish(last_qval=np.ones(A, np.float32))
    recs2 = drive(buf, rng, 20, start_t=20)
    blk2 = buf.finish(last_qval=np.ones(A, np.float32))

    assert blk2.burn_in_steps[0] == 4
    # Window position 0 of block2/seq0 replays global step 17 (1-based):
    # its input hidden is the state after step 16 = recs1[15]'s hidden, and
    # its stacked obs is frames from steps 15,16 → obs_row[0] is step 15's
    # frame = recs1[14]'s obs (obs_row[0:stack] = steps 15,16).
    np.testing.assert_allclose(blk2.hidden[0], recs1[15][4], rtol=1e-6)
    np.testing.assert_array_equal(blk2.obs_row[0], recs1[14][2])
    np.testing.assert_array_equal(blk2.obs_row[1], recs1[15][2])
    # last_action at window position 0 is the action taken at step 16
    assert blk2.last_action_row[0] == recs1[15][0]


def _fill_blocks(spec, n, rng, gamma=0.9):
    buf = LocalBuffer(spec, A, gamma=gamma)
    buf.reset(np.zeros((12, 12), np.uint8))
    blocks = []
    t = 0
    for _ in range(n):
        drive(buf, rng, spec.block_length, start_t=t)
        t += spec.block_length
        blocks.append(buf.finish(last_qval=rng.normal(size=A).astype(np.float32)))
    return blocks


def test_exact_gather_padded_storage_is_transparent(rng):
    """spec.exact_gather pads the stored frame to the uint8 (32, 128)
    tile (12x12 -> 32x128 here; 84x84 -> 96x128 at reference scale; both
    minor dims must be tile-aligned for the async-copy DMA — builders, round 4);
    the padding must be invisible end-to-end: the same blocks + same
    sample keys yield batches whose unpadded rows and every other field
    are IDENTICAL to the unpadded spec's, and the decoded observation
    (out_height/out_width strip the pad) matches exactly."""
    from r2d2_tpu.ops.pallas_kernels import stack_frames_reference

    spec = make_spec()
    spec_pad = make_spec(exact_gather=True)
    assert spec_pad.stored_frame_height == 32 and spec.frame_height == 12
    assert spec_pad.stored_frame_width == 128 and spec.frame_width == 12

    blocks = _fill_blocks(spec, 3, rng)
    state, state_pad = replay_init(spec), replay_init(spec_pad)
    assert state_pad.obs.shape[2:] == (32, 128)
    for blk in blocks:
        state = replay_add(spec, state, blk)
        state_pad = replay_add(spec_pad, state_pad, blk)

    key = jax.random.PRNGKey(0)
    batch = replay_sample(spec, state, key)
    batch_pad = replay_sample(spec_pad, state_pad, key)

    np.testing.assert_array_equal(np.asarray(batch.idxes),
                                  np.asarray(batch_pad.idxes))
    np.testing.assert_array_equal(np.asarray(batch.obs),
                                  np.asarray(batch_pad.obs)[:, :, :12, :12])
    assert (np.asarray(batch_pad.obs)[:, :, 12:, :] == 0).all()
    assert (np.asarray(batch_pad.obs)[:, :, :, 12:] == 0).all()
    np.testing.assert_array_equal(np.asarray(batch.last_action),
                                  np.asarray(batch_pad.last_action))

    dec = stack_frames_reference(batch.obs, spec.seq_window,
                                 spec.frame_stack, out_height=12)
    dec_pad = stack_frames_reference(batch_pad.obs, spec.seq_window,
                                     spec.frame_stack, out_height=12,
                                     out_width=12)
    assert dec_pad.shape == dec.shape
    np.testing.assert_array_equal(np.asarray(dec), np.asarray(dec_pad))


def test_device_replay_add_sample_consistency(rng):
    """Jitted sample must return exactly the stored windows: cross-check every
    sampled field against direct numpy indexing of the ring state."""
    spec = make_spec()
    state = replay_init(spec)
    for blk in _fill_blocks(spec, 3, rng):
        state = replay_add(spec, state, blk)

    assert int(state.block_ptr) == 3
    assert int(replay_size(state)) == 3 * spec.block_length

    batch = replay_sample(spec, state, jax.random.PRNGKey(0))
    obs_np = np.asarray(state.obs)
    la_np = np.asarray(state.last_action)

    idxes = np.asarray(batch.idxes)
    assert (idxes < 3 * spec.seqs_per_block).all()
    assert (np.asarray(batch.learning_steps) > 0).all()
    w = np.asarray(batch.is_weights)
    assert np.isfinite(w).all() and (w > 0).all() and w.max() == pytest.approx(1.0)

    for i in range(spec.batch_size):
        b, s = idxes[i] // spec.seqs_per_block, idxes[i] % spec.seqs_per_block
        burn = int(np.asarray(state.burn_in_steps)[b, s])
        start = int(np.asarray(state.seq_start)[b, s]) - burn
        assert start >= 0
        win = spec.seq_window
        np.testing.assert_array_equal(
            np.asarray(batch.obs)[i], obs_np[b, start : start + win + spec.frame_stack - 1])
        np.testing.assert_array_equal(
            np.asarray(batch.last_action)[i], la_np[b, start : start + win])
        np.testing.assert_allclose(
            np.asarray(batch.hidden)[i], np.asarray(state.hidden)[b, s])


def test_device_replay_ring_overwrite(rng):
    """Wrapping the ring replaces old priorities — slots from the overwritten
    block must reflect the new block's data (ref worker.py:96-102)."""
    spec = make_spec(num_blocks=2)
    state = replay_init(spec)
    blocks = _fill_blocks(spec, 3, rng)
    state = replay_add(spec, state, blocks[0])
    tree_after_b0 = np.asarray(state.tree).copy()
    state = replay_add(spec, state, blocks[1])
    state = replay_add(spec, state, blocks[2])  # overwrites ring slot 0
    assert int(state.block_ptr) == 1
    leaves = np.asarray(state.tree)[2**spec.tree_layers // 2 - 1 :]
    want = np.asarray(blocks[2].priority) ** spec.prio_exponent
    np.testing.assert_allclose(leaves[: spec.seqs_per_block], want, rtol=1e-5)
    assert not np.allclose(leaves[: spec.seqs_per_block],
                           tree_after_b0[2**spec.tree_layers // 2 - 1 :][: spec.seqs_per_block])


def test_ring_accountant_mirrors_device_pointer(rng):
    """RingAccountant (the single host-side ring authority) must advance
    with the identical wrap rule as the compiled pointer in
    ReplayState.block_ptr — the invariant that makes the Learner's host
    mirror safe (it never reads the device pointer)."""
    from r2d2_tpu.replay.structs import RingAccountant

    spec = make_spec(num_blocks=3)
    state = replay_init(spec)
    ring = RingAccountant(spec.num_blocks)
    for blk in _fill_blocks(spec, 7, rng):   # wraps the 3-slot ring twice
        state = replay_add(spec, state, blk)
        ring.advance(int(np.asarray(blk.learning_steps).sum()))
        assert ring.ptr == int(state.block_ptr)
        assert ring.buffer_steps == int(replay_size(state))
    assert ring.total_adds == 7


def test_sample_distribution_follows_priorities(rng):
    """Stratified sampling must draw high-priority sequences more often."""
    spec = make_spec(batch_size=64)
    state = replay_init(spec)
    blocks = _fill_blocks(spec, 2, rng)
    # block 0: tiny priorities; block 1: large
    b0 = blocks[0].replace(priority=np.full(spec.seqs_per_block, 0.01, np.float32))
    b1 = blocks[1].replace(priority=np.full(spec.seqs_per_block, 1.0, np.float32))
    state = replay_add(spec, state, b0)
    state = replay_add(spec, state, b1)
    batch = replay_sample(spec, state, jax.random.PRNGKey(1))
    frac_b1 = (np.asarray(batch.idxes) >= spec.seqs_per_block).mean()
    assert frac_b1 > 0.9


def test_host_replay_matches_contract_and_staleness_guard(rng):
    spec = make_spec()
    host = HostReplay(spec, seed=0, use_native=False)
    blocks = _fill_blocks(spec, 3, rng)
    for blk in blocks:
        host.add(blk)
    assert len(host) == 3 * spec.block_length

    batch, snapshot = host.sample()
    assert snapshot == 3
    assert batch.obs.shape == (
        spec.batch_size, spec.seq_window + spec.frame_stack - 1, 12, 12)

    # advance the ring over block 0, then write back stale priorities:
    # leaves of block 0 must keep the *new* block's priorities
    for blk in _fill_blocks(spec, 6, rng):
        host.add(blk)  # ptr: 3..8 -> wraps, overwrites block 0
    leaf0 = 2**host.tree_layers // 2 - 1
    before = host.tree[leaf0 : leaf0 + spec.seqs_per_block].copy()
    host.update_priorities(batch.idxes, np.full(spec.batch_size, 99.0), snapshot)
    after = host.tree[leaf0 : leaf0 + spec.seqs_per_block]
    np.testing.assert_array_equal(before, after)


def test_host_replay_guard_survives_full_ring_lap(rng):
    """Exactly num_blocks adds between sample and write-back returns the ring
    pointer to its snapshot value — the reference's pointer-equality guard
    (worker.py:196-206) would apply every stale update; the monotonic
    add-counter guard must drop them all."""
    spec = make_spec()
    host = HostReplay(spec, seed=0, use_native=False)
    for blk in _fill_blocks(spec, 3, rng):
        host.add(blk)
    batch, snapshot = host.sample()
    for blk in _fill_blocks(spec, spec.num_blocks, rng):  # full lap
        host.add(blk)
    assert host.ring.ptr == 3  # pointer is back where it was
    tree_before = host.tree.copy()
    host.update_priorities(batch.idxes, np.full(spec.batch_size, 99.0), snapshot)
    np.testing.assert_array_equal(host.tree, tree_before)


def test_device_host_same_layout(rng):
    """Device and host replay must store identical bytes for the same blocks
    (the feeder can switch placement without re-encoding)."""
    spec = make_spec()
    blocks = _fill_blocks(spec, 2, rng)
    state = replay_init(spec)
    host = HostReplay(spec, use_native=False)
    for blk in blocks:
        state = replay_add(spec, state, blk)
        host.add(blk)
    np.testing.assert_array_equal(np.asarray(state.obs), host.obs)
    np.testing.assert_array_equal(np.asarray(state.last_action), host.last_action)
    np.testing.assert_allclose(np.asarray(state.reward), host.reward, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(state.seq_start), host.seq_start)


def test_device_ring_bytes_matches_allocation():
    """The capacity guard's estimate must be exact for what replay_init
    actually allocates (VERDICT r4 #3: refuse with numbers, don't OOM)."""
    for kw in ({}, {"exact_gather": True}):
        spec = make_spec(**kw)
        state = replay_init(spec)
        allocated = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
        # block_ptr (one i32 scalar) is the only array outside the estimate
        assert allocated - spec.device_ring_bytes == 4, kw


def test_replay_init_refuses_oversized_ring(monkeypatch):
    """A ring larger than the device's reported HBM must fail fast with a
    clear message (before allocating anything), not OOM mid-init."""
    from r2d2_tpu.replay import device_replay

    class FakeTpu:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_limit": 1 << 30}

    monkeypatch.setattr(device_replay.jax, "devices", lambda: [FakeTpu()])
    big = make_spec(num_blocks=4000, frame_height=84, frame_width=84,
                    exact_gather=True)
    assert big.device_ring_bytes > (1 << 30)
    with pytest.raises(ValueError, match="OOM at replay_init"):
        replay_init(big)
    # the refusal names the exact_gather escape hatch with its real size
    with pytest.raises(ValueError, match="pallas_exact_gather"):
        replay_init(big)


def test_replay_init_warns_on_large_padded_ring(monkeypatch):
    """exact_gather's 1.74x storage pad on a multi-GiB ring warns once at
    replay_init (ADVICE r4) — without allocating here (guard called
    directly)."""
    from r2d2_tpu.replay.device_replay import _guard_device_capacity

    big = make_spec(num_blocks=8000, frame_height=84, frame_width=84,
                    exact_gather=True)
    assert big.device_ring_bytes > (2 << 30)
    with pytest.warns(UserWarning, match="pads stored frames 84x84"):
        _guard_device_capacity(big)
