"""The ``conv_attn_moe`` core's own cases (``r2d2_tpu/models/cores/
conv_attn_moe.py``): two kinds of stored state in one row, packed and
unpacked by the one layout function; what each kind of part does at the
actor's step and in a window; its configuration keys; the record's ``core``
block of every core; and the core on the normal path (``cli.train`` trains
and acts with it). What it shares with ``mla_moe`` is in ``test_cores.py``
(the cases parametrised over ``kind``), the plain reference in
``tests/benchmarks/test_bm_lfm2.py``. Tiny sizes, CPU, seeded weights."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.config import Config, CoreConfig, parse_overrides
from r2d2_tpu.models.cores import make_core, state_block, state_half
from r2d2_tpu.models.cores.conv_attn_moe import Part, state_layout
from tests.test_cores import (TINY_CONV_CORE, TINY_ENV, inputs,
                              tiny_config, tiny_net)
from tests.test_moe_way_back import WALKS, check_walk

KIND = "conv_attn_moe"


def _parts(core, state):
    """The row ``state`` (B, 2, half) cut into its layers' parts."""
    flat = np.array(state).reshape(len(state), -1)
    return [flat[:, p.offset:p.offset + p.size].reshape((-1,) + p.shape)
            for p in state_layout(core)]


def _packed(core, parts):
    return np.concatenate([p.reshape(len(p), -1) for p in parts],
                          axis=1).reshape(len(parts[0]), 2, -1)


# -- the row's layout -------------------------------------------------------


def test_layout_packs_the_layers_parts_in_layer_order():
    core = tiny_config(KIND).network.core
    assert state_layout(core) == [
        Part(0, "conv", 0, (2, 32)),
        Part(1, "full_attention", 64, (4, 32)),     # 2 heads x 8 x (k | v)
        Part(2, "conv", 192, (2, 32))]
    assert make_core(tiny_config(KIND).network, jnp.float32).state_half \
        == state_half(tiny_config(KIND).network) == 128
    # the published widths, layers 1-5: 4 x (2 x 2048) + 128 x 1024 floats
    full = CoreConfig(kind=KIND, num_hidden_layers=5, num_attention_heads=32,
                      num_key_value_heads=8, memory_len=128, layer_types=(
                          "conv", "full_attention", "conv", "conv", "conv"))
    sizes = [p.size for p in state_layout(full)]
    assert sizes == [4096, 131072, 4096, 4096, 4096]
    assert sum(sizes) == 2 * 73_728


def test_a_row_round_trips_through_its_parts(rng):
    core = tiny_config(KIND).network.core
    row = rng.normal(size=(3, 2, 128)).astype(np.float32)
    parts = _parts(core, row)
    assert [p.shape for p in parts] == [(3, 2, 32), (3, 4, 32), (3, 2, 32)]
    np.testing.assert_array_equal(_packed(core, parts), row)


@pytest.mark.parametrize("kind, parts", [
    ("lstm", [("lstm_h_c", 1, 32)]),
    ("mla_moe", [("latent_cache", 2, 160)]),
    (KIND, [("conv_state", 2, 128), ("key_value_window", 1, 128)])])
def test_record_block_tells_the_kinds_of_part_apart(kind, parts):
    cfg = (Config().replace(**TINY_ENV) if kind == "lstm"
           else tiny_config(kind))
    block = state_block(cfg.network, cfg.replay.batch_size,
                        cfg.sequence.seq_len)
    assert block["kind"] == kind and "remat" not in block
    assert block["parts"] == [
        {"kind": k, "layers": n, "floats": f, "bytes": 4 * f}
        for k, n, f in parts]
    assert block["row_bytes"] == 8 * state_half(cfg.network) == sum(
        p["bytes"] for p in block["parts"])


# -- what each kind of part does --------------------------------------------


def test_acting_shifts_each_kind_of_part_by_one():
    cfg = tiny_config(KIND)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(2), 3, 1, net)
    _, new = net.apply(params, obs, action, state)
    for old, now in zip(_parts(cfg.network.core, state),
                        _parts(cfg.network.core, new)):
        np.testing.assert_array_equal(now[:, :-1], old[:, 1:])
        assert np.abs(now[:, -1]).min() > 0
        assert np.abs(now[:, -1] - old[:, -1]).max() > 0


def test_a_window_leaves_its_last_positions_in_each_part():
    """After a window of 3 steps a conv part (2 positions) holds the
    window's own last two gated inputs, the key/value part (4 positions) its
    newest stored slot and the window's three rows; and an episode's first 4
    steps as one window leave the row that 3 steps and then the actor's step
    leave."""
    cfg = tiny_config(KIND)
    core = cfg.network.core
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(7), 2, 4, net)
    _, after3 = net.apply(params, obs[:, :3], action[:, :3], state)
    before, now = _parts(core, state), _parts(core, after3)
    np.testing.assert_array_equal(now[1][:, :1], before[1][:, -1:])
    for i in (0, 2):
        assert np.abs(now[i] - before[i]).min() > 0
    empty = net.init_state(2)
    _, after4 = net.apply(params, obs, action, empty)
    _, after3 = net.apply(params, obs[:, :3], action[:, :3], empty)
    _, stepped = net.apply(params, obs[:, 3:], action[:, 3:], after3)
    np.testing.assert_allclose(stepped, after4, atol=2e-6)


def test_an_empty_key_value_slot_is_not_attended_to():
    """A key/value window with its oldest slots empty gives the Q of the
    same window whatever stands where a shorter one would end: zeros are
    masked, not attended to as keys of value zero."""
    cfg = tiny_config(KIND)
    core = cfg.network.core
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(3), 2, 3, net)
    parts = _parts(core, state)
    parts[1][:, :2] = 0.0
    masked, _ = net.apply(params, obs, action, _packed(core, parts))
    full, _ = net.apply(params, obs, action, state)
    parts[1][:] = 0.0
    no_window, _ = net.apply(params, obs, action, _packed(core, parts))
    assert np.abs(masked - full).max() > 1e-5
    assert np.abs(masked - no_window).max() > 1e-5
    from_empty, _ = net.apply(params, obs, action, net.init_state(2))
    assert np.isfinite(np.asarray(from_empty)).all()


def test_a_conv_part_is_the_convolutions_left_context_and_no_more():
    """In a stack of conv layers alone, the stored parts reach the window's
    first L - 1 = 2 positions a layer (2 layers: 4 positions) and none
    after; zeros there are the convolution's own left padding."""
    cfg = tiny_config(KIND, **{
        "network.core.layer_types": ("conv", "conv"),
        "network.core.num_hidden_layers": 2})
    net = tiny_net(cfg)
    assert net.state_half == 64
    params = net.init(jax.random.PRNGKey(0))
    obs, action, state = inputs(jax.random.PRNGKey(4), 2, 7, net)
    stored, _ = net.apply(params, obs, action, state)
    empty, _ = net.apply(params, obs, action, net.init_state(2))
    differs = np.abs(np.asarray(stored - empty)).max(axis=(0, 2))
    assert (differs[:2] > 1e-6).all()
    assert differs[4:].max() == 0.0
    # a window is the causal convolution of the whole sequence: the second
    # half of a window from what the first half left is the whole window's
    whole, final = net.apply(params, obs, action, state)
    _, middle = net.apply(params, obs[:, :3], action[:, :3], state)
    second, end = net.apply(params, obs[:, 3:], action[:, 3:], middle)
    np.testing.assert_allclose(second, whole[:, 3:], atol=2e-6)
    np.testing.assert_allclose(end, final, atol=2e-6)


def test_the_learners_window_sees_further_back_than_the_actor_past_the_window():
    """The departure the docstring states: up to memory_len + 1 steps a
    window is the actor's steps (``test_cores.py``); past it the window's
    late steps still see its first, which the actor's rolling window of
    ``memory_len`` positions has let go."""
    cfg = tiny_config(KIND)
    net = tiny_net(cfg)
    params = net.init(jax.random.PRNGKey(0))
    steps = cfg.network.core.memory_len + 3
    obs, action, _ = inputs(jax.random.PRNGKey(1), 2, steps, net)
    q, _ = net.apply(params, obs, action, net.init_state(2))
    state, acted = net.init_state(2), []
    for t in range(steps):
        q_t, state = net.apply(params, obs[:, t:t + 1], action[:, t:t + 1],
                               state)
        acted.append(q_t)
    acted = np.concatenate(acted, axis=1)
    m = cfg.network.core.memory_len
    np.testing.assert_allclose(acted[:, :m + 1], q[:, :m + 1], atol=2e-6)
    assert np.abs(acted[:, m + 1:] - q[:, m + 1:]).max() > 1e-6


# -- configuration ----------------------------------------------------------


def test_command_line_reaches_the_layers_kinds():
    cfg = parse_overrides(Config(), [
        f"--network.core.kind={KIND}", "--network.core.num_hidden_layers=3",
        "--network.core.layer_types=conv,full_attention,conv",
        "--network.core.num_key_value_heads=4", "--network.core.conv_L_cache=4"])
    core = cfg.network.core
    assert core.layer_types == ("conv", "full_attention", "conv")
    assert core.num_key_value_heads == 4 and core.conv_L_cache == 4
    hash(cfg.network)
    assert [p.shape for p in state_layout(core)] == [
        (3, 2048), (40, 2 * 4 * 128), (3, 2048)]


@pytest.mark.parametrize("field, value", [
    ("layer_types", ("conv", "full_attention")),            # not one a layer
    ("layer_types", ("conv", "sliding_attention", "conv")),
    ("num_key_value_heads", 3), ("num_attention_heads", 5),
    ("hidden_size", 36),                                    # heads of 9
    ("conv_L_cache", 1), ("memory_len", 0), ("experts_held", 9),
    ("num_experts_per_tok", 9)])
def test_core_config_refuses_what_the_core_does_not_compute(field, value):
    with pytest.raises(ValueError, match="network.core"):
        CoreConfig(**{**TINY_CONV_CORE, field: value})


# -- the normal path --------------------------------------------------------


def _argv(overrides):
    def spell(value):
        if isinstance(value, bool):
            return "true" if value else "false"
        if isinstance(value, (list, tuple)):
            if value and isinstance(value[0], (list, tuple)):
                return ";".join(",".join(str(x) for x in row)
                                for row in value)
            return ",".join(str(x) for x in value)
        return str(value)
    return [f"--{key}={spell(value)}" for key, value in overrides.items()]


def test_cli_train_trains_and_acts_with_the_benchmarks_overrides(tmp_path):
    """``cli.train`` with ``benchmarks/configs/lfm2-core.json``'s overrides
    at the rehearsal size, spelt on the command line: thread actors act
    through the plain forward (T = 1, their rows go to the ring), the fused
    learner step trains, the record carries the ``moe`` and ``core``
    blocks."""
    from r2d2_tpu.cli import train as cli
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "lfm2-core.json")) as f:
        doc = json.load(f)
    overrides = {**doc["overrides"], **doc["rehearsal"],
                 # the rehearsal's twin is all dense (``rehearsal_note``)
                 "network.core.first_k_dense_replace": 1,
                 "env.game_name": "Fake", "network.bf16": "off",
                 "replay.learning_starts": 64, "actor.num_actors": 2,
                 "runtime.save_dir": str(tmp_path),
                 "runtime.save_interval": 0, "runtime.log_interval": 0.2,
                 "runtime.steps_per_dispatch": 2}
    stacks = cli.main(_argv(overrides) + ["--actor-mode=thread",
                                          "--max-steps=6",
                                          "--max-seconds=300"])
    learner = stacks[0].learner
    assert learner.cfg.network.core.kind == KIND
    assert learner.cfg.network.core.layer_types == (
        "conv", "full_attention", "conv")
    assert int(learner.train_state.step) >= 6
    assert learner.env_steps >= 64
    # the actors' rows are the core's: both kinds of part were written
    stored = np.asarray(learner.replay_state.hidden)
    assert stored.shape[-2:] == (2, learner.net.state_half)
    flat = stored.reshape(-1, 2 * learner.net.state_half)
    flat = flat[np.abs(flat).max(axis=1) > 0]
    for part in state_layout(learner.cfg.network.core):
        assert np.abs(flat[:, part.offset:part.offset + part.size]).max() > 0
    records = [json.loads(line) for line in
               open(tmp_path / "metrics_player0.jsonl")]
    assert any("moe" in r for r in records)
    (core_block,) = [r["core"] for r in records if "core" in r]
    assert [p["kind"] for p in core_block["parts"]] == [
        "conv_state", "key_value_window"]


@pytest.mark.parametrize("walk", sorted(WALKS))
def test_expert_layer_and_gradients_are_the_references_however_the_walk_fills(
        monkeypatch, walk):
    """This core's expert layer (no shared expert, 1e-6 in the weights'
    normalisation) against ``benchmarks/reference/r2d2_lfm2.py``, through
    the cases of the held experts' walk (``tests/test_moe_way_back.py``)."""
    check_walk("conv_attn_moe", monkeypatch, walk)
