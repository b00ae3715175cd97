"""The target network held in the dtype its forward reads
(``r2d2_tpu/learner/target.py``): under the bf16 policy a target leaf that
the forward only ever converts to bf16 is held in bf16, cast once a sync.

What these tests hold, at tiny sizes on the CPU with the compute dtype forced
to bf16, one case per core kind: the target unroll's Q from the narrowed tree
is the float32 tree's to the last bit (on the CPU: a TPU's two programs round
their bf16 activations apart, PERF.md, Findings); through one program the
float32 tree with the narrowed leaves rounded first gives the same Q, and
with a router rounded does not; the leaves kept float32 are exactly the
norms, the routers, the expert bias, the router's stored mean, the short
convolutions' taps and the torso's convolution kernels; a sync writes
``target_of(params)`` and a float32 target handed in still runs the scanned
steps; the record's ``target`` block; ``ld/target_dist`` reads 0 right after
a sync; a checkpoint whose target was saved as float32 restores into the
narrowed tree. With a float32 compute dtype or double-Q off nothing changes
(``test_step_param_traffic.py`` holds the float32 program itself).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from r2d2_tpu.learner.target import (like_target, target_block, target_of,
                                     target_plan)
from r2d2_tpu.learner.train_step import (_unrolled_q, create_train_state,
                                         make_learner_step,
                                         make_multi_learner_step)
from r2d2_tpu.replay.device_replay import replay_sample
from r2d2_tpu.replay.structs import ReplaySpec
from r2d2_tpu.telemetry.learning import param_distance

from tests.test_cores import tiny_config as core_config
from tests.test_learning_diag import filled_replay, tiny_cfg, tiny_net

INTERVAL = 3
BF16_DOUBLE = {"network.bf16": "on", "network.use_double": True,
               "optim.target_net_update_interval": INTERVAL,
               "optim.lr": 1e-3}
KINDS = ("lstm", "mla_moe", "conv_attn_moe")
# the leaves a core keeps float32, by their own name or their module's
_KEPT_NAMES = {"input_layernorm", "post_attention_layernorm",
               "operator_norm", "ffn_norm", "gate", "e_score_correction_bias",
               "router_input_mean"}
_KEPT_MODULES = {"input_norm", "norm", "kv_a_layernorm", "q_layernorm",
                 "k_layernorm"}


def _config(kind, **extra):
    overrides = {**BF16_DOUBLE, **extra}
    if kind == "lstm":
        return tiny_cfg(**overrides)
    return core_config(kind, **overrides)


def _setup(kind, **extra):
    cfg = _config(kind, **extra)
    net = tiny_net(cfg)
    return cfg, ReplaySpec.from_config(cfg), net


def _names(path):
    return [str(getattr(k, "key", k)) for k in path]


def _expected_kept(path) -> bool:
    names = _names(path)
    if names[1] == "torso":
        return names[2].startswith("Conv_") and names[-1] == "kernel"
    return (names[-1] in _KEPT_NAMES
            or (names[-1] == "weight" and names[-2] in _KEPT_MODULES)
            # a short convolution's taps: ``conv/conv``
            or names[-2:] == ["conv", "conv"])


def _assert_trees_equal(a, b):
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("kind", KINDS)
def test_target_q_from_the_narrowed_tree_is_the_float32_trees(kind, rng):
    cfg, spec, net = _setup(kind)
    params = net.init(jax.random.PRNGKey(0))
    narrowed = target_of(net, params)
    held = [x.dtype for x in jax.tree_util.tree_leaves(narrowed)]
    assert jnp.bfloat16 in held and jnp.float32 in held
    batch = replay_sample(spec, filled_replay(spec, rng),
                          jax.random.PRNGKey(5))
    unroll = jax.jit(lambda p, b: _unrolled_q(net, spec, p, b)[0])
    q32, q16 = unroll(params, batch), unroll(narrowed, batch)
    assert q32.dtype == q16.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q16), np.asarray(q32))


@pytest.mark.parametrize("kind", KINDS)
def test_the_forward_itself_rounds_what_the_plan_narrows(kind, rng):
    """The control that holds the plan to the float32 forward through one
    program: the float32 tree against the same tree with the leaves the
    plan narrows rounded to bf16 first, Q and the routers' counters bit for
    bit. The routers' kernels, which the plan keeps, rounded as well move
    them: a leaf narrowed by mistake shows here, where a reference handed
    the program's own target would agree with it. ``tools/chip_checks.py``
    (``target_plan_*``) makes the same control at the widths of the
    benchmark's cells."""
    cfg, spec, net = _setup(kind)
    params = net.init(jax.random.PRNGKey(0))
    plan = target_plan(net, params)
    batch = replay_sample(spec, filled_replay(spec, rng),
                          jax.random.PRNGKey(5))
    unroll = jax.jit(lambda p, b: _unrolled_q(net, spec, p, b))
    want = jax.tree_util.tree_leaves(unroll(params, batch))

    def same(p):
        got = jax.tree_util.tree_leaves(unroll(p, batch))
        return all(np.array_equal(x, y) for x, y in zip(got, want))

    assert same(jax.tree_util.tree_map(
        lambda d, p: p.astype(d).astype(p.dtype), plan, params))
    if kind != "lstm":
        assert not same(jax.tree_util.tree_map_with_path(
            lambda path, p: (p.astype(jnp.bfloat16).astype(p.dtype)
                             if _names(path)[-1] == "gate" else p), params))


@pytest.mark.parametrize("kind", KINDS)
def test_kept_leaves_are_norms_routers_bias_mean_taps_and_conv_kernels(kind):
    _, _, net = _setup(kind)
    params = net.init(jax.random.PRNGKey(0))
    plan = target_plan(net, params)
    flat = jax.tree_util.tree_flatten_with_path(plan)[0]
    kept = {"/".join(_names(p)) for p, d in flat if d == jnp.float32}
    want = {"/".join(_names(p)) for p, _ in flat if _expected_kept(p)}
    assert kept == want
    narrowed = {d for _, d in flat if d != jnp.float32}
    assert narrowed == {np.dtype(jnp.bfloat16)}
    if kind != "lstm":
        # every kind of kept leaf is there
        last = {_names(p)[-1] for p, d in flat if d == jnp.float32}
        assert {"gate", "e_score_correction_bias", "router_input_mean",
                "weight"} <= last
        assert (kind == "conv_attn_moe") == ("conv" in last)


@pytest.mark.parametrize("kind", KINDS)
def test_a_sync_writes_target_of_params_and_a_float32_target_still_runs(
        kind, rng):
    """K = INTERVAL steps a dispatch, so each dispatch ends on a sync: the
    first is handed the target as float32 (the parameters of another
    seed), the second the narrowed one it left."""
    cfg, spec, net = _setup(kind)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    plan = target_plan(net, ts.params)
    assert ([x.dtype for x in jax.tree_util.tree_leaves(ts.target_params)]
            == jax.tree_util.tree_leaves(plan))
    ts = ts.replace(target_params=net.init(jax.random.PRNGKey(4)))
    rs = filled_replay(spec, rng)
    multi = make_multi_learner_step(net, spec, cfg.optim, True, INTERVAL)
    for dispatch in range(2):
        ts, rs, m = multi(ts, rs)
        assert np.asarray(m["target_sync"]).tolist() == [0, 0, 1]
        _assert_trees_equal(ts.target_params, target_of(net, ts.params))
    # and the single step: a sync writes the narrowed parameters, a step
    # without one passes them through
    single = make_learner_step(net, spec, cfg.optim, True)
    for step in range(2 * INTERVAL + 1, 3 * INTERVAL + 1):
        before = jax.device_get(ts.target_params)     # the step donates it
        ts, rs, m = single(ts, rs)
        if step % INTERVAL:
            _assert_trees_equal(ts.target_params, before)
        else:
            _assert_trees_equal(ts.target_params, target_of(net, ts.params))
        assert int(m["target_sync"]) == (step % INTERVAL == 0)


@pytest.mark.parametrize("kind", KINDS)
def test_the_target_block_counts_what_the_plan_narrowed(kind):
    cfg, _, net = _setup(kind)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    plan = jax.tree_util.tree_leaves(target_plan(net, ts.params))
    sizes = [x.size for x in jax.tree_util.tree_leaves(ts.params)]
    block = target_block(ts.target_params)
    narrowed = sum(d == jnp.bfloat16 for d in plan)
    assert block == {
        "leaves_narrowed": narrowed, "leaves_kept": len(plan) - narrowed,
        "bytes": sum(n * (2 if d == jnp.bfloat16 else 4)
                     for n, d in zip(sizes, plan)),
        "bytes_float32": 4 * sum(sizes)}
    assert block["bytes"] < 0.6 * block["bytes_float32"]
    # double-Q off, or a float32 compute dtype: nothing narrowed
    for extra in ({"network.use_double": False}, {"network.bf16": "off"}):
        cfg, _, net = _setup(kind, **extra)
        ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
        assert target_plan(net, ts.params) is None
        block = target_block(ts.target_params)
        assert block["leaves_narrowed"] == 0
        assert block["bytes"] == block["bytes_float32"]
        _assert_trees_equal(ts.target_params, ts.params)


def test_the_learners_first_record_carries_the_target_block(rng, tmp_path):
    from r2d2_tpu.runtime.learner_loop import Learner
    cfg = _config("lstm", **{"runtime.save_dir": str(tmp_path),
                             "runtime.save_interval": 0})
    learner = Learner(cfg, tiny_net(cfg))
    first = learner.metrics.log(1.0)
    assert first["target"] == target_block(learner.train_state.target_params)
    assert first["target"]["leaves_narrowed"] > 0
    assert "target" not in learner.metrics.log(1.0)
    learner.stop_background()


@pytest.mark.parametrize("kind", ["lstm", "mla_moe"])
def test_target_distance_reads_zero_right_after_a_sync(kind):
    _, _, net = _setup(kind)
    params = net.init(jax.random.PRNGKey(0))
    target = target_of(net, params)
    assert float(param_distance(params, target, net)) == 0.0
    other = net.init(jax.random.PRNGKey(1))
    assert float(param_distance(other, target, net)) > 0.0


def test_a_float32_target_restores_into_the_narrowed_tree(tmp_path):
    from types import SimpleNamespace

    from r2d2_tpu.runtime.checkpoint import (apply_restore,
                                             resume_training_state,
                                             save_checkpoint)
    cfg, _, net = _setup("mla_moe")
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    # as a float32 target was saved before the target was held narrowed
    path = save_checkpoint(str(tmp_path), "Fake", 1, 0, ts.params,
                           ts.opt_state, ts.params, 7, env_steps=11)
    fresh = create_train_state(jax.random.PRNGKey(99), net, cfg.optim)
    restored, env_steps = resume_training_state(path, fresh)
    assert (int(restored.step), env_steps) == (7, 11)
    _assert_trees_equal(restored.target_params, target_of(net, ts.params))
    # and a narrowed target round-trips as it is
    path = save_checkpoint(str(tmp_path), "Fake", 2, 0, ts.params,
                           ts.opt_state, ts.target_params, 8, env_steps=12)
    restored, _ = resume_training_state(path, fresh)
    _assert_trees_equal(restored.target_params, ts.target_params)
    # a warm start copies the parameters into the target as it is held
    warm, _ = apply_restore(SimpleNamespace(resume=None, pretrain=path),
                            fresh)
    _assert_trees_equal(warm.target_params, target_of(net, ts.params))
    _assert_trees_equal(like_target(ts.target_params, ts.params),
                        ts.target_params)
