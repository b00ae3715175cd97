"""Shared tiny-shapes validation step for the multichip / multihost dryruns.

Builds the FULL dp-sharded training pipeline — sharded replay ring, one
synthetic block ring-written into every shard, one fused sharded learner
step (sample → unroll → loss → pmean(grads) → Adam → priority write-back) —
at toy sizes, then asserts the loss is finite and the updated params are
bit-identical on every locally-addressable shard. Used by
``__graft_entry__.dryrun_multichip`` (single-process virtual mesh) and by
``r2d2_tpu.parallel.multihost_dryrun`` (two ``jax.distributed`` processes
over a loopback coordinator — the DCN bring-up path of SURVEY §5.8).
"""

import numpy as np

_TINY_BATCH = 4   # _tiny_setup's batch size; the TP dryrun shards it over dp


def tp_dryrun_fits(n_devices: int) -> bool:
    """True when a dp=(n/2) x mp=2 mesh can shard the tiny batch evenly —
    the guard dryrun_multichip uses before attempting the TP step."""
    return n_devices % 2 == 0 and _TINY_BATCH % (n_devices // 2) == 0


def _synthetic_block(spec, rng=None):
    """One full synthetic block at ``spec``'s shapes (deterministic for a
    given rng; rng=None seeds fresh — identical in every process)."""
    from r2d2_tpu.replay.structs import Block

    rng = rng or np.random.default_rng(0)
    S, L = spec.seqs_per_block, spec.learning
    H, W = spec.frame_height, spec.frame_width
    return Block(
        obs_row=rng.integers(0, 255, (spec.obs_row_len, H, W)).astype(np.uint8),
        last_action_row=rng.integers(0, 4, (spec.la_row_len,)).astype(np.int32),
        hidden=rng.normal(size=(S, 2, spec.hidden_dim)).astype(np.float32),
        action=rng.integers(0, 4, (S, L)).astype(np.int32),
        reward=rng.normal(size=(S, L)).astype(np.float32),
        gamma=np.full((S, L), 0.99, np.float32),
        priority=np.ones((S,), np.float32),
        burn_in_steps=np.full((S,), spec.burn_in, np.int32),
        learning_steps=np.full((S,), L, np.int32),
        forward_steps=np.concatenate(
            [np.full((S - 1,), spec.forward), [1]]).astype(np.int32),
        seq_start=(spec.burn_in + L * np.arange(S)).astype(np.int32),
        num_sequences=np.asarray(S, np.int32),
        sum_reward=np.asarray(np.nan, np.float32),
    )


def _tiny_setup():
    """Shared toy-scale (spec, opt, net) for the dryrun steps — one source
    of the shapes so the dp and tp dryruns cannot desynchronize."""
    import jax

    from r2d2_tpu.config import NetworkConfig, OptimConfig
    from r2d2_tpu.models import init_network
    from r2d2_tpu.replay.structs import ReplaySpec

    spec = ReplaySpec(
        num_blocks=4, seqs_per_block=2, block_length=10, burn_in=4,
        learning=5, forward=3, frame_stack=2, frame_height=20, frame_width=20,
        hidden_dim=16, batch_size=_TINY_BATCH, prio_exponent=0.9,
        is_exponent=0.6)
    ncfg = NetworkConfig(hidden_dim=16, cnn_out_dim=32,
                         conv_layers=((8, 4, 2), (16, 3, 1)), use_double=True)
    opt = OptimConfig(target_net_update_interval=2)
    net, _ = init_network(jax.random.PRNGKey(0), 4, ncfg, frame_stack=2,
                          frame_height=20, frame_width=20)
    return spec, opt, net


def run_tiny_sharded_step(mesh) -> float:
    """Run one sharded step over ``mesh`` (axis 'dp'); returns the loss."""
    import jax

    from r2d2_tpu.learner import create_train_state
    from r2d2_tpu.parallel import make_sharded_learner_step, sharded_replay_init
    from r2d2_tpu.parallel.sharded import make_sharded_replay_add

    n_shards = mesh.shape["dp"]
    spec, opt, net = _tiny_setup()

    ts = create_train_state(jax.random.PRNGKey(1), net, opt)
    rs = sharded_replay_init(spec, mesh)

    # one synthetic block per shard (full sequences, unit priorities);
    # seeded identically in every process so multi-controller SPMD holds
    rng = np.random.default_rng(0)
    add = make_sharded_replay_add(spec, mesh)
    for d in range(n_shards):
        rs = add(rs, _synthetic_block(spec, rng), d)

    step = make_sharded_learner_step(net, spec, opt, use_double=True, mesh=mesh)
    ts, rs, metrics = step(ts, rs)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"non-finite loss {loss}"
    # params replicated identically on every locally-addressable shard
    leaf = jax.tree_util.tree_leaves(ts.params)[0]
    shards = [np.asarray(s.data) for s in leaf.addressable_shards]
    for s in shards[1:]:
        np.testing.assert_array_equal(shards[0], s)
    return loss


def run_tiny_sp_step(n_devices: int) -> float:
    """One pipelined sequence-parallel LSTM unroll over an ('sp',) mesh
    spanning all devices (parallel/sequence_parallel.py), checked exact
    against the in-chip scan. Returns the |outputs| sum."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from r2d2_tpu.models.network import HoistedLSTM
    from r2d2_tpu.parallel.sequence_parallel import make_sp_lstm

    B, T, D, H = 8, 2 * n_devices, 10, 8
    key = jax.random.PRNGKey(0)
    xs = jax.random.normal(key, (B, T, D))
    c0 = jnp.zeros((B, H))
    lstm = HoistedLSTM(features=H)
    params = lstm.init(jax.random.PRNGKey(1), (c0, c0), xs)
    (c_ref, h_ref), out_ref = lstm.apply(params, (c0, c0), xs)

    p = params["params"]
    sp = make_sp_lstm(Mesh(np.array(jax.devices()[:n_devices]), ("sp",)),
                      microbatches=4)
    out, final = sp(p["recurrent_kernel"], p["bias"],
                    xs @ p["input_proj"]["kernel"], jnp.stack([c0, c0]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(final[1]), np.asarray(h_ref))
    return float(jnp.abs(out).sum())


def run_tiny_device_mp_step(mesh) -> float:
    """One fused DEVICE-replay training step over a ('dp','mp') mesh with
    mp > 1: replay dp-sharded, wide params feature-sharded over mp, GSPMD
    collectives inside the sample-in-HBM step (parallel/sharded.py's GSPMD
    formulation — VERDICT r3 #4). Returns the loss."""
    import jax

    from r2d2_tpu.learner import create_train_state
    from r2d2_tpu.parallel import make_sharded_learner_step, sharded_replay_init
    from r2d2_tpu.parallel.sharded import make_sharded_replay_add
    from r2d2_tpu.parallel.tensor_parallel import state_shardings

    spec, opt, net = _tiny_setup()
    ts = create_train_state(jax.random.PRNGKey(1), net, opt)
    ts = jax.device_put(ts, state_shardings(ts, mesh, min_shard_width=8))
    rs = sharded_replay_init(spec, mesh)
    add = make_sharded_replay_add(spec, mesh)
    rng = np.random.default_rng(0)
    for d in range(mesh.shape["dp"]):
        rs = add(rs, _synthetic_block(spec, rng), d)
    step = make_sharded_learner_step(net, spec, opt, use_double=True,
                                     mesh=mesh)
    ts, rs, metrics = step(ts, rs)
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"non-finite device-mp loss {loss}"
    # at least one wide param leaf genuinely sharded across mp
    sharded = [l for l in jax.tree_util.tree_leaves(ts.params)
               if l.ndim >= 1
               and l.addressable_shards[0].data.shape[-1] != l.shape[-1]]
    assert sharded, "no param leaf sharded over mp in the device-mp dryrun"
    return loss


def run_tiny_tp_step(mesh) -> float:
    """One tensor-parallel training step over a ('dp','mp') mesh: params
    feature-sharded over mp, batch over dp, GSPMD collectives
    (parallel/tensor_parallel.py). Returns the loss."""
    import jax

    from r2d2_tpu.learner import create_train_state
    from r2d2_tpu.parallel.tensor_parallel import make_tp_external_batch_step
    from r2d2_tpu.replay.device_replay import (
        replay_add, replay_init, replay_sample)

    spec, opt, net = _tiny_setup()

    rs = replay_init(spec)
    rs = replay_add(spec, rs, _synthetic_block(spec))
    batch = replay_sample(spec, rs, jax.random.PRNGKey(3))

    step, place_state, place_batch = make_tp_external_batch_step(
        net, spec, opt, use_double=True, mesh=mesh, min_shard_width=8)
    ts = place_state(create_train_state(jax.random.PRNGKey(1), net, opt))
    ts, metrics = step(ts, place_batch(batch))
    loss = float(jax.device_get(metrics["loss"]))
    assert np.isfinite(loss), f"non-finite tp loss {loss}"
    return loss
