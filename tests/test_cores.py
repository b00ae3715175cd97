"""The memory-core interface (``r2d2_tpu/models/cores/``): the LSTM behind it
is the parent's network bit for bit; the ``mla_moe`` core's state, acting
step, routing and balance rule; what holds of either core with experts
(``mla_moe``, ``conv_attn_moe``: the cases parametrised over ``kind``); and
the places that store a state row (ring, ``LocalBuffer``, anakin carry,
state cache, snapshot) take its width from the core. The ``conv_attn_moe``
core's own cases (two kinds of part in one row) are in
``test_core_conv_attn_moe.py``. All at tiny sizes on the CPU, seeded random
weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import Config, CoreConfig, parse_overrides
from r2d2_tpu.models.cores import make_core, mla_moe, state_half
from r2d2_tpu.models.network import (ConvTorso, DuelingHead, HoistedLSTM,
                                     NetworkApply, pack_hidden, unpack_hidden)

ACTIONS = 4
TINY_ENV = {"env.frame_stack": 2, "env.frame_height": 24,
            "env.frame_width": 24,
            "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
            "network.cnn_out_dim": 32, "network.hidden_dim": 16,
            "network.bf16": "off"}
TINY_CORE = {"kind": "mla_moe", "hidden_size": 32, "num_attention_heads": 2,
             "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
             "v_head_dim": 8, "intermediate_size": 64,
             "moe_intermediate_size": 16, "n_routed_experts": 8,
             "num_experts_per_tok": 2, "experts_held": 4,
             "num_hidden_layers": 2, "memory_len": 4}
# 3 layers: (2 x 32) + 4 positions x (2 key/value heads x 8 x 2) + (2 x 32)
# = 256 values = (2, 128)
TINY_CONV_CORE = {"kind": "conv_attn_moe", "hidden_size": 32,
                  "num_attention_heads": 4, "num_key_value_heads": 2,
                  "conv_L_cache": 3, "intermediate_size": 64,
                  "moe_intermediate_size": 16, "n_routed_experts": 8,
                  "num_experts_per_tok": 2, "experts_held": 4,
                  "num_hidden_layers": 3,
                  "layer_types": ("conv", "full_attention", "conv"),
                  "memory_len": 4, "rope_theta": 1e6,
                  "routed_scaling_factor": 1.0}
CORES = {"mla_moe": TINY_CORE, "conv_attn_moe": TINY_CONV_CORE}
HALF = {"mla_moe": 80, "conv_attn_moe": 128}      # state_half of each
both_cores = pytest.mark.parametrize("kind", list(CORES))
TINY_REPLAY = {"sequence.burn_in_steps": 4, "sequence.learning_steps": 5,
               "sequence.forward_steps": 3, "replay.block_length": 20,
               "replay.capacity": 160, "replay.batch_size": 8}


def tiny_config(kind="mla_moe", **extra) -> Config:
    core = {f"network.core.{k}": v for k, v in CORES[kind].items()}
    return Config().replace(**{**TINY_ENV, **TINY_REPLAY, **core, **extra})


def tiny_net(cfg: Config) -> NetworkApply:
    return NetworkApply(ACTIONS, cfg.network, cfg.env.frame_stack,
                        cfg.env.frame_height, cfg.env.frame_width)


def inputs(key, batch, steps, net):
    k = jax.random.split(key, 3)
    h, w, s = net.obs_hw
    obs = jax.random.uniform(k[0], (batch, steps, h, w, s))
    action = jax.nn.one_hot(
        jax.random.randint(k[1], (batch, steps), 0, ACTIONS), ACTIONS)
    state = jax.random.normal(k[2], (batch, 2, net.state_half))
    return obs, action, state


# -- configuration: the second level of a dotted key ------------------------


def test_replace_reaches_the_core_and_keeps_the_rest():
    cfg = Config().replace(**{"network.core.kind": "mla_moe",
                              "network.core.experts_held": 8,
                              "network.hidden_dim": 256})
    assert cfg.network.core.kind == "mla_moe"
    assert cfg.network.core.experts_held == 8
    assert cfg.network.core.n_routed_experts == 64    # the source's
    assert cfg.network.hidden_dim == 256
    assert Config().network.core.kind == "lstm"
    hash(cfg.network)        # flax hashes the section


@both_cores
def test_config_with_a_core_round_trips_through_json(kind):
    cfg = tiny_config(kind)
    again = Config.from_json(cfg.to_json())
    assert again == cfg and isinstance(again.network.core, CoreConfig)
    hash(again.network)      # a JSON list came back as a tuple


def test_command_line_reaches_the_core():
    cfg = parse_overrides(Config(), ["--network.core.kind=mla_moe",
                                     "--network.core.memory_len=8",
                                     "--network.core.rope_theta=1e4"])
    assert cfg.network.core.memory_len == 8
    assert cfg.network.core.rope_theta == 1e4
    with pytest.raises(SystemExit, match="unknown field"):
        parse_overrides(Config(), ["--network.core.no_such=1"])


@pytest.mark.parametrize("key", ["network.core.kind.more", "replay.core.kind",
                                 "nodot"])
def test_replace_refuses_what_it_cannot_place(key):
    with pytest.raises(KeyError):
        Config().replace(**{key: 1})


@pytest.mark.parametrize("field,value", [
    ("kind", "gru"), ("num_experts_per_tok", 65), ("qk_rope_head_dim", 63),
    ("memory_len", 0), ("experts_held", 65), ("expert_offset", 60)])
def test_core_config_refuses_what_the_core_does_not_compute(field, value):
    with pytest.raises(ValueError):
        CoreConfig(**{"kind": "mla_moe", "experts_held": 8, field: value})


# -- the LSTM behind the interface ------------------------------------------


def test_lstm_core_is_the_parents_network_bit_for_bit():
    """Q, state and parameter tree of the network with the LSTM behind the
    interface against the parent's composition written out here: torso,
    ``HoistedLSTM`` under the name ``lstm`` on the unpacked (h, c), head."""
    cfg = Config().replace(**TINY_ENV)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    assert set(params["params"]) == {"torso", "lstm", "head"}
    assert net.state_half == state_half(cfg.network) == 16
    obs, action, state = inputs(jax.random.PRNGKey(1), 3, 7, net)
    q, new_state = net.apply(params, obs, action, state)

    p, n = params["params"], cfg.network
    latent = ConvTorso(n.cnn_out_dim, n.conv_layers, jnp.float32).apply(
        {"params": p["torso"]}, obs.reshape((21,) + obs.shape[2:]))
    rnn_in = jnp.concatenate([latent.reshape(3, 7, -1), action], axis=-1)
    carry, out = HoistedLSTM(features=n.hidden_dim).apply(
        {"params": p["lstm"]}, unpack_hidden(state), rnn_in)
    want_q = DuelingHead(ACTIONS, n.hidden_dim, n.use_dueling,
                         jnp.float32).apply(
        {"params": p["head"]}, out.reshape(21, -1)).reshape(3, 7, ACTIONS)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(new_state),
                                  np.asarray(pack_hidden(carry)))
    np.testing.assert_array_equal(np.asarray(net.init_state(5)),
                                  np.zeros((5, 2, 16), np.float32))


def test_lstm_step_program_costs_what_the_parents_did():
    """The fused step's XLA cost table at the gate's shapes is BASELINE.json's,
    exactly: the interface added no operation to the LSTM's programs."""
    import json
    import os

    from r2d2_tpu.telemetry import costmodel
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BASELINE.json")) as f:
        want = json.load(f)["costs"]["programs"]["learner_step"]
    got = costmodel.collect_cost_table(
        costmodel.gate_config(), variants=("learner_step",)
    )["programs"]["learner_step"]
    assert got["flops"] == want["flops"]
    assert got["bytes_accessed"] == want["bytes_accessed"]


# -- the mla_moe core -------------------------------------------------------


def test_state_row_is_the_latent_cache():
    cfg = tiny_config()
    core = make_core(cfg.network, jnp.float32)
    # 2 layers x 4 positions x (16 latent + 4 rope) = 160 values = (2, 80)
    assert core.state_half == state_half(cfg.network) == 80
    assert core.out_dim == 32
    full = Config().replace(**{"network.core.kind": "mla_moe",
                               "network.core.num_hidden_layers": 5})
    assert state_half(full.network) == 57_600


@both_cores
def test_unroll_equals_acting_step_by_step(kind):
    """An episode's first memory_len + 1 steps: the learner's unroll of the
    block from the empty state gives the Q and the final state of the same
    block acted one step at a time through the rolling cache (and, in the
    ``conv_attn_moe`` core, the convolutions' shifting inputs)."""
    cfg = tiny_config(kind)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    steps = cfg.network.core.memory_len + 1
    obs, action, _ = inputs(jax.random.PRNGKey(1), 3, steps, net)
    q, final = net.apply(params, obs, action, net.init_state(3))
    state, acted = net.init_state(3), []
    for t in range(steps):
        q_t, state = net.apply(params, obs[:, t:t + 1], action[:, t:t + 1],
                               state)
        acted.append(q_t)
    np.testing.assert_allclose(np.concatenate(acted, axis=1), q, atol=2e-6)
    np.testing.assert_allclose(state, final, atol=2e-6)


def test_acting_shifts_the_cache_by_one():
    cfg = tiny_config()
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(2), 3, 1, net)
    _, new = net.apply(params, obs, action, state)
    c = cfg.network.core
    shape = (3, c.num_hidden_layers, c.memory_len, -1)
    old, new = np.reshape(state, shape), np.reshape(new, shape)
    np.testing.assert_array_equal(new[:, :, :-1], old[:, :, 1:])
    assert np.abs(new[:, :, -1]).min() > 0


def test_an_empty_slot_is_not_attended_to():
    """A cache with its oldest slots empty gives the Q of the same cache
    whatever stands where a shorter cache would end: zeros are masked, not
    attended to as keys of value zero."""
    cfg = tiny_config()
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(3), 2, 3, net)
    c = cfg.network.core
    slots = np.array(state).reshape(2, c.num_hidden_layers, c.memory_len, -1)
    slots[:, :, :2] = 0.0
    masked, _ = net.apply(params, obs, action, slots.reshape(2, 2, -1))
    full, _ = net.apply(params, obs, action, state)
    from_empty, _ = net.apply(params, obs, action, net.init_state(2))
    assert np.abs(masked - full).max() > 1e-4
    assert np.abs(masked - from_empty).max() > 1e-4
    assert np.isfinite(np.asarray(from_empty)).all()


@both_cores
def test_the_stored_cache_gets_no_gradient(kind):
    cfg = tiny_config(kind)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(4), 2, 6, net)

    def total_q(state, obs):
        return jnp.sum(net.apply(params, obs, action, state)[0] ** 2)

    to_state, to_obs = jax.grad(total_q, argnums=(0, 1))(state, obs)
    assert float(jnp.abs(to_state).max()) == 0.0
    assert float(jnp.abs(to_obs).max()) > 0.0


def test_the_bias_changes_which_experts_are_chosen_and_not_their_weights():
    core = CoreConfig(**{**TINY_CORE, "routed_scaling_factor": 2.446})
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(5), (64, 8)))
    bias = jnp.zeros(8).at[7].set(10.0).at[0].set(-10.0)
    plain, _ = mla_moe.route(scores, jnp.zeros(8), core)
    chosen, weights = mla_moe.route(scores, bias, core)
    assert (np.asarray(chosen) == 7).any(axis=1).all()
    assert not (np.asarray(chosen) == 0).any()
    assert (np.sort(plain, axis=1) != np.sort(chosen, axis=1)).any()
    # weights are the chosen experts' own scores, normalised and scaled
    picked = np.take_along_axis(np.asarray(scores), np.asarray(chosen), 1)
    np.testing.assert_allclose(
        weights, picked / picked.sum(1, keepdims=True) * 2.446, rtol=1e-6)


def _common_part_stream(core, positions=512):
    """A residual stream as an agent's is: a part common to all positions
    three times the size of what varies between them."""
    k = jax.random.split(jax.random.PRNGKey(11), 2)
    common = 3.0 * jax.random.normal(k[0], (core.hidden_size,))
    return common + jax.random.normal(k[1], (1, positions, core.hidden_size))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_router_reads_what_varies_between_positions(seed):
    """On a stream with a large common part the learner's router (input
    less its mean over the call's positions) spreads the pairs over the
    experts, the held ones near their expected share, whatever the seeded
    weights; a router left with the common part (acting with nothing stored
    yet) sends every position to the same few experts."""
    core = CoreConfig(**{**TINY_CORE, "hidden_size": 64,
                         "n_routed_experts": 16, "experts_held": 4})
    x = _common_part_stream(core)
    ones = jnp.ones((core.hidden_size,))
    pairs = x.shape[1] * core.num_experts_per_tok

    def chosen(window_stats):
        layer = mla_moe.MoE(core, jnp.float32, window_stats)
        params = layer.init(jax.random.PRNGKey(seed), x, ones)
        return np.asarray(layer.apply(params, x, ones)[1]["chosen"])

    centred, left = chosen(True), chosen(False)
    assert centred.sum() == left.sum() == pairs
    assert centred.max() < 2.5 * pairs / 16
    assert 0.7 < centred[:4].sum() / (pairs * 4 / 16) < 1.3
    assert left.max() > 0.8 * x.shape[1]          # one expert, most positions
    assert (left == 0).sum() >= 6                 # and many experts none


@both_cores
def test_acting_subtracts_the_mean_the_train_step_stored(kind):
    """``apply_learner`` hands back the mean it centred each router on;
    with that stored among the parameters, acting on the same positions
    routes and answers as the learner did. The expert layers are found by
    their routers, whichever core holds them."""
    cfg = tiny_config(kind)
    core = cfg.network.core
    routers = core.num_hidden_layers - core.first_k_dense_replace
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(8), 4, 6, net)
    q, final, counters = net.apply_learner(params, obs, action, state)
    means = counters["input_mean"]
    assert means.shape == (routers, core.hidden_size)
    assert float(jnp.abs(means).max()) > 0
    q_unstored, _ = net.apply(params, obs, action, state)
    assert float(jnp.abs(q_unstored - q).max()) > 0
    stored = mla_moe.store_router_means(params, means)
    for i in range(routers):
        leaf = stored["params"]["mem_core"][
            f"layers_{core.first_k_dense_replace + i}"]["mlp"][
                "router_input_mean"]
        np.testing.assert_array_equal(leaf, means[i])
    assert jax.tree_util.tree_structure(stored) == \
        jax.tree_util.tree_structure(params)
    q_acted, final_acted = net.apply(stored, obs, action, state)
    np.testing.assert_allclose(q_acted, q, atol=2e-6)
    np.testing.assert_allclose(final_acted, final, atol=2e-6)


@both_cores
def test_the_bias_gets_no_gradient_and_the_router_does(kind):
    cfg = tiny_config(kind)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(6), 2, 6, net)
    grads = jax.grad(lambda p: jnp.sum(
        net.apply(p, obs, action, state)[0] ** 2))(params)
    mlp = grads["params"]["mem_core"]["layers_1"]["mlp"]
    assert float(jnp.abs(mlp["e_score_correction_bias"]).max()) == 0.0
    assert float(jnp.abs(mlp["router_input_mean"]).max()) == 0.0
    assert float(jnp.abs(mlp["gate"]).max()) > 0.0
    assert float(jnp.abs(mlp["experts"]["gate_up_proj"]).max()) > 0.0


@both_cores
def test_paths_written_for_the_lstm_refuse_another_core(kind):
    """... and the message names the core that was refused."""
    from r2d2_tpu.learner.train_step import make_external_batch_step
    from r2d2_tpu.replay.structs import ReplaySpec
    cfg = tiny_config(kind)
    net = tiny_net(cfg)
    with pytest.raises(NotImplementedError,
                       match=f"LSTM core; network.core.kind='{kind}'"):
        make_external_batch_step(net, ReplaySpec.from_config(cfg), cfg.optim,
                                 False)
    with pytest.raises(ValueError, match=f"inference_dtype.*{kind}"):
        tiny_net(tiny_config(kind, **{"network.inference_dtype": "int8"}))


# -- whoever stores a state row takes its width from the core ---------------


def _spec(kind):
    from r2d2_tpu.replay.structs import ReplaySpec
    spec = ReplaySpec.from_config(tiny_config(kind))
    assert spec.hidden_dim == HALF[kind]
    return spec


def _blocks(spec, rng, n):
    """``n`` blocks through ``LocalBuffer``, each step's state row random."""
    from r2d2_tpu.actor.local_buffer import LocalBuffer
    buf = LocalBuffer(spec, ACTIONS, gamma=0.9)
    buf.reset(np.zeros((24, 24), np.uint8))
    blocks, rows = [], []
    for _ in range(n):
        for t in range(spec.block_length):
            row = rng.normal(size=(2, spec.hidden_dim)).astype(np.float32)
            rows.append(row)
            buf.add(t % ACTIONS, 1.0, np.full((24, 24), t, np.uint8),
                    rng.normal(size=ACTIONS).astype(np.float32), row)
        blocks.append(buf.finish(
            last_qval=rng.normal(size=ACTIONS).astype(np.float32)))
    return blocks, np.stack(rows)


@both_cores
def test_local_buffer_stores_the_cores_row(rng, kind):
    spec = _spec(kind)
    (first, second), rows = _blocks(spec, rng, 2)
    assert first.hidden.shape == (spec.seqs_per_block, 2, HALF[kind])
    # the second block's sequences start from rows the actor handed over
    stored = np.asarray(second.hidden).reshape(spec.seqs_per_block, -1)
    handed = rows.reshape(len(rows), -1)
    for row in stored:
        assert (np.abs(handed - row).max(axis=1) == 0).any()


@both_cores
def test_ring_stores_and_samples_the_cores_row(rng, kind):
    from r2d2_tpu.replay.device_replay import (replay_add, replay_init,
                                               replay_sample)
    spec, half = _spec(kind), HALF[kind]
    blocks, _ = _blocks(spec, rng, 3)
    state = replay_init(spec)
    assert state.hidden.shape == (spec.num_blocks, spec.seqs_per_block, 2,
                                  half)
    for block in blocks:
        state = replay_add(spec, state, block)
    batch = replay_sample(spec, state, jax.random.PRNGKey(0))
    assert batch.hidden.shape == (spec.batch_size, 2, half)
    stored = np.concatenate([np.asarray(b.hidden) for b in blocks]).reshape(
        -1, 2 * half)
    for row in np.asarray(batch.hidden).reshape(spec.batch_size, -1):
        assert (np.abs(stored - row).max(axis=1) == 0).any()


@both_cores
def test_snapshot_round_trips_the_cores_row(rng, tmp_path, kind):
    from r2d2_tpu.replay.device_replay import replay_add, replay_init
    from r2d2_tpu.replay.snapshot import (capture_plain, load_snapshot,
                                          restore_plain, write_snapshot)
    from r2d2_tpu.replay.structs import RingAccountant
    spec = _spec(kind)
    state, ring = replay_init(spec), RingAccountant(spec.num_blocks)
    for block in _blocks(spec, rng, 2)[0]:
        state = replay_add(spec, state, block)
        ring.advance(int(np.asarray(block.learning_steps).sum()))
    write_snapshot(capture_plain(spec, state, ring, step=3), str(tmp_path), 0)
    back = restore_plain(spec, replay_init(spec),
                         RingAccountant(spec.num_blocks),
                         load_snapshot(str(tmp_path), 0))
    np.testing.assert_array_equal(np.asarray(back.hidden),
                                  np.asarray(state.hidden))
    # a ring of another core's width is refused
    other = dataclasses.replace(spec, hidden_dim=16)
    with pytest.raises(Exception):
        restore_plain(other, replay_init(other),
                      RingAccountant(other.num_blocks),
                      load_snapshot(str(tmp_path), 0))


@both_cores
def test_state_cache_and_policies_carry_the_cores_row(rng, kind):
    from r2d2_tpu.actor.policy import ActorPolicy
    from r2d2_tpu.serve.state_cache import StateCache
    cfg, half = tiny_config(kind), HALF[kind]
    net = tiny_net(cfg)
    cache = StateCache(4, 1, (24, 24), 2, net.state_half, action_dim=ACTIONS)
    assert cache.hidden.shape == (4, 2, half)
    row = rng.normal(size=(2, half)).astype(np.float32)
    slot, fresh = cache.lease(7)
    assert fresh
    cache.write_hidden(slot, row)
    np.testing.assert_array_equal(cache.gather([slot])[2][0], row)
    kept = cache.export_shard(0)
    cache.hidden[:] = 0.0
    cache.restore_shard(kept)
    np.testing.assert_array_equal(cache.hidden[cache.lease(7)[0]], row)
    # the actor's policy starts from the core's empty row and hands back
    # one of the same shape
    policy = ActorPolicy(net, net.init(jax.random.PRNGKey(0)), epsilon=0.0)
    assert policy.hidden.shape == (1, 2, half)
    policy.observe_reset(np.zeros((24, 24), np.uint8))
    _, _, hidden = policy.act()
    assert np.asarray(hidden).shape[-2:] == (2, half)


@both_cores
def test_anakin_carry_holds_the_cores_row(kind):
    from r2d2_tpu.actor.anakin import init_act_carry
    from r2d2_tpu.envs.factory import create_jax_env
    cfg, half = tiny_config(kind), HALF[kind]
    env = create_jax_env(cfg.env)
    carry = init_act_carry(env, _spec(kind), 3, jax.random.PRNGKey(0))
    assert carry.hidden.shape == (3, 2, half)
    assert carry.tail_hidden.shape == (3, 4 + 1, 2, half)
