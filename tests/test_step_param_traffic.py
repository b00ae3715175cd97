"""A train step that is not a sync step moves no parameter-sized buffer but
Adam's own (ISSUE 28): the hard target sync is one ``lax.cond`` over the
tree (``sync_target``) and the learning diagnostics' reads of the old
parameters are tied before the optimizer's write.

What these tests hold: values bit-equal to the parent's formulation, written
out below with the per-leaf ``jnp.where`` and the old order; the structure of
the step's jaxpr; the three step factories' schedules; the ``target_syncs``
counter of the record.

What they cannot hold: the copies themselves. XLA:CPU does not show this
PR's difference on the tiny twin. Its scan body holds 18 parameter-shaped
``copy`` (LSTM; 50 with the ``mla_moe`` core) with the diagnostics on, in the
parent's formulation and in this PR's alike, and none with them off: the
CPU's pipeline takes no order from the barrier, and it copies in both
branches of the sync's ``cond`` where the TPU's passes the target through in
one instruction. So the count (8.87 -> 1.68 GB a step in
``moonlight-core.learner-long``, compiled for the described v5e) lives in
PERF.md and is not pinned here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from r2d2_tpu.learner.train_step import (create_train_state,
                                         make_external_batch_step,
                                         make_learner_step, make_loss_fn,
                                         make_multi_learner_step,
                                         make_optimizer)
from r2d2_tpu.ops.sum_tree import tree_update
from r2d2_tpu.replay.device_replay import replay_sample
from r2d2_tpu.replay.structs import ReplaySpec
from r2d2_tpu.telemetry.learning import LearningDiag, fused_diagnostics

from tests.test_cores import tiny_config as moe_config
from tests.test_learning_diag import filled_replay, tiny_cfg, tiny_net

INTERVAL = 3          # steps between target syncs
DIAG = LearningDiag(interval=2, dq_batch=4)
DOUBLE = {"network.use_double": True,
          "optim.target_net_update_interval": INTERVAL, "optim.lr": 1e-3}
CORES = {"lstm": lambda: tiny_cfg(**DOUBLE),
         "mla_moe": lambda: moe_config(**DOUBLE)}


def _setup(core, rng):
    cfg = CORES[core]()
    spec = ReplaySpec.from_config(cfg)
    net = tiny_net(cfg)
    ts = create_train_state(jax.random.PRNGKey(3), net, cfg.optim)
    # a target apart from the parameters, so a wrong pick shows
    ts = ts.replace(target_params=net.init(jax.random.PRNGKey(4)))
    return cfg, spec, net, ts, filled_replay(spec, rng)


def parent_multi_step(net, spec, optim, diag, k):
    """The parent's fused step: the optimizer first, the sync a per-leaf
    ``jnp.where`` on every step, the diagnostics last and untied."""
    loss_fn = make_loss_fn(net, spec, optim, True)
    tx = make_optimizer(optim)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(ts, rs):
        key, base = jax.random.split(ts.key)
        batch = replay_sample(spec, rs, jax.random.fold_in(base, 0))
        (loss, aux), grads = grad_fn(ts.params, ts.target_params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, ts.opt_state, ts.params)
            params = optax.apply_updates(ts.params, updates)
        if "moe" in aux:
            from r2d2_tpu.models.cores.mla_moe import store_router_means
            params = store_router_means(params, aux["moe"]["input_mean"])
        rs = rs.replace(tree=tree_update(
            spec.tree_layers, rs.tree, spec.prio_exponent,
            aux["priorities"], batch.idxes))
        new_step = ts.step + 1
        sync = (new_step % optim.target_net_update_interval) == 0
        target = jax.tree_util.tree_map(
            lambda p, t: jnp.where(sync, p, t), params, ts.target_params)
        grad_norm = optax.global_norm(grads)
        m = {"loss": loss, "mean_abs_td": aux["mean_abs_td"],
             "mean_q": aux["mean_q"], "grad_norm": grad_norm}
        m.update(fused_diagnostics(
            net, spec, diag, new_step, ts.params, ts.target_params, batch,
            aux, grads, loss, grad_norm, replay_state=rs))
        ts = ts.replace(params=params, target_params=target,
                        opt_state=opt_state, step=new_step, key=key)
        return ts, rs, m

    def multi(ts, rs):
        def body(carry, _):
            ts, rs, m = step(*carry)
            return (ts, rs), m
        (ts, rs), m = jax.lax.scan(body, (ts, rs), None, length=k)
        return ts, rs, m

    return step, jax.jit(multi)


def _assert_trees_equal(a, b):
    assert (jax.tree_util.tree_structure(a)
            == jax.tree_util.tree_structure(b))
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# -- (a) the values are the parent's, bit for bit ----------------------------


@pytest.mark.parametrize("core", sorted(CORES))
def test_scanned_steps_equal_the_parents_formulation(core, rng):
    """Two dispatches of K = 4: syncs on steps 3 and 6, diagnostics on 2, 4,
    6 and 8, so a sync and a diagnostics step share a scan and, on step 6,
    a step."""
    cfg, spec, net, ts, rs = _setup(core, rng)
    copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
    _, old = parent_multi_step(net, spec, cfg.optim, DIAG, 4)
    new = make_multi_learner_step(net, spec, cfg.optim, True, 4, diag=DIAG)
    a, b = (copy(ts), copy(rs)), (ts, rs)
    for dispatch in range(2):
        ts_a, rs_a, m_a = old(*a)
        ts_b, rs_b, m_b = new(*b)
        _assert_trees_equal(ts_a.params, ts_b.params)
        _assert_trees_equal(ts_a.target_params, ts_b.target_params)
        _assert_trees_equal(ts_a.opt_state, ts_b.opt_state)
        # the priorities written back, and |td| through its histogram
        np.testing.assert_array_equal(np.asarray(rs_a.tree),
                                      np.asarray(rs_b.tree))
        assert set(m_a) <= set(m_b)
        for name in m_a:
            np.testing.assert_array_equal(np.asarray(m_a[name]),
                                          np.asarray(m_b[name]), err_msg=name)
        steps = 4 * dispatch + np.arange(1, 5)
        np.testing.assert_array_equal(np.asarray(m_b["target_sync"]),
                                      steps % INTERVAL == 0)
        # the interval's values are there, from the old parameters
        on = steps % DIAG.interval == 0
        for name in ("ld/target_dist", "ld/delta_q_stored",
                     "ld/delta_q_zero", "ld/delta_q_recomputed"):
            np.testing.assert_array_equal(
                np.isfinite(np.asarray(m_b[name])), on, err_msg=name)
        a, b = (ts_a, rs_a), (ts_b, rs_b)
    assert int(ts_b.step) == 8
    # after step 8 the target is step 6's parameters: neither the start's
    # nor the latest
    leaf = lambda t: np.asarray(jax.tree_util.tree_leaves(t)[0])  # noqa: E731
    assert not np.array_equal(leaf(ts_b.target_params), leaf(ts_b.params))


# -- (b) the structure of the step -------------------------------------------


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _avals(variables):
    return [(tuple(v.aval.shape), str(v.aval.dtype)) for v in variables]


def _structure(step_fn, ts, rs):
    """(parameter-shaped selects outside the optimizer, conds whose outputs
    are the target tree) of the step's jaxpr."""
    leaves = jax.tree_util.tree_leaves(ts.target_params)
    target = [(tuple(x.shape), str(x.dtype)) for x in leaves]
    wide = {shape for shape, _ in target if len(shape) >= 2}
    eqns = list(_walk(jax.make_jaxpr(step_fn)(ts, rs).jaxpr))
    selects = [
        e for e in eqns if e.primitive.name == "select_n"
        and tuple(e.outvars[0].aval.shape) in wide
        and "optimizer" not in str(e.source_info.name_stack)]
    conds = [e for e in eqns if e.primitive.name == "cond"
             and _avals(e.outvars) == target]
    return selects, conds


@pytest.mark.parametrize("core", sorted(CORES))
def test_no_parameter_sized_select_and_one_cond_over_the_target(core, rng):
    cfg, spec, net, ts, rs = _setup(core, rng)
    wide = sum(x.ndim >= 2
               for x in jax.tree_util.tree_leaves(ts.target_params))
    # the walk sees what it is meant to see: the parent selects every leaf
    old, _ = parent_multi_step(net, spec, cfg.optim, DIAG, 1)
    selects, conds = _structure(old, ts, rs)
    assert len(selects) == wide and not conds
    new = make_learner_step(net, spec, cfg.optim, True, jit=False, diag=DIAG)
    selects, conds = _structure(new, ts, rs)
    assert not selects, [str(e.source_info.name_stack) for e in selects]
    assert len(conds) == 1
    # with double-Q off there is no sync in the program at all
    single = make_learner_step(net, spec, cfg.optim, False, jit=False,
                               diag=DIAG)
    assert _structure(single, ts, rs) == ([], [])


def test_diagnostics_are_tied_before_the_optimizer(rng):
    """The tie is an ``optimization_barrier`` that the old parameters and
    target pass together with the diagnostics' outputs; without the
    diagnostics the program has none."""
    cfg, spec, net, ts, rs = _setup("lstm", rng)
    n_leaves = len(jax.tree_util.tree_leaves(ts.params))

    def barriers(diag):
        step = make_learner_step(net, spec, cfg.optim, True, jit=False,
                                 diag=diag)
        return [e for e in _walk(jax.make_jaxpr(step)(ts, rs).jaxpr)
                if e.primitive.name == "optimization_barrier"]

    (tie,) = barriers(DIAG)
    assert len(tie.invars) > 2 * n_leaves
    leaves = [(tuple(x.shape), str(x.dtype))
              for x in jax.tree_util.tree_leaves(ts.params)]
    tied = _avals(tie.invars)
    assert all(tied.count(leaf) >= 2 * leaves.count(leaf) for leaf in leaves)
    assert barriers(None) == []


# -- (c) the three factories keep one schedule --------------------------------


def _schedule_holds(history, synced):
    """``history``: (params, target) after each step, as numpy trees."""
    previous = None
    for (params, target), fired in zip(history, synced):
        if fired:
            _assert_trees_equal(target, params)
        elif previous is not None:
            _assert_trees_equal(target, previous)
        previous = target


def test_three_step_factories_give_the_same_target(rng):
    from r2d2_tpu.config import MeshConfig
    from r2d2_tpu.parallel import make_mesh
    from r2d2_tpu.parallel.sharded import (make_sharded_learner_step,
                                           make_sharded_replay_add,
                                           sharded_replay_init)
    from r2d2_tpu.replay.device_replay import replay_add, replay_init
    from tests.test_learning_diag import stamped_block

    cfg, spec, net, ts0, _ = _setup("lstm", rng)
    blocks = [stamped_block(spec, rng, v) for v in range(1, 5)]
    host = lambda t: jax.tree_util.tree_map(np.asarray, t)  # noqa: E731
    fresh = lambda: jax.tree_util.tree_map(jnp.copy, ts0)   # noqa: E731
    steps = np.arange(1, 8)
    synced = steps % INTERVAL == 0

    def ring():
        rs = replay_init(spec)
        for blk in blocks:
            rs = replay_add(spec, rs, blk)
        return rs

    # the fused step
    fused = make_learner_step(net, spec, cfg.optim, True, diag=DIAG)
    ts, rs, hist_fused, fired = fresh(), ring(), [], []
    for _ in steps:
        ts, rs, m = fused(ts, rs)
        hist_fused.append(host((ts.params, ts.target_params)))
        fired.append(int(m["target_sync"]))
    np.testing.assert_array_equal(fired, synced)
    _schedule_holds(hist_fused, synced)

    # the external-batch step on the batches the fused step drew
    external = make_external_batch_step(net, spec, cfg.optim, True)
    ts, rs, key, hist_ext, fired = fresh(), ring(), ts0.key, [], []
    for _ in steps:
        key, base = jax.random.split(key)
        batch = replay_sample(spec, rs, jax.random.fold_in(base, 0))
        ts, m = external(ts, batch)
        rs = rs.replace(tree=tree_update(
            spec.tree_layers, rs.tree, spec.prio_exponent, m["priorities"],
            batch.idxes))
        hist_ext.append(host((ts.params, ts.target_params)))
        fired.append(int(m["target_sync"]))
    np.testing.assert_array_equal(fired, synced)
    _schedule_holds(hist_ext, synced)

    # the dp-sharded step on a mesh of one shard (the same sample stream)
    mesh = make_mesh(MeshConfig(dp=1))
    sharded = make_sharded_learner_step(net, spec, cfg.optim, True, mesh,
                                        diag=DIAG)
    add = make_sharded_replay_add(spec, mesh)
    rs = sharded_replay_init(spec, mesh)
    for blk in blocks:
        rs = add(rs, blk, 0)
    ts, hist_dp, fired = fresh(), [], []
    for _ in steps:
        ts, rs, m = sharded(ts, rs)
        hist_dp.append(host((ts.params, ts.target_params)))
        fired.append(int(m["target_sync"]))
    np.testing.assert_array_equal(fired, synced)
    _schedule_holds(hist_dp, synced)

    # and one target: each factory is its own XLA program, so to rounding
    for other in (hist_ext, hist_dp):
        for (_, t_fused), (_, t_other) in zip(hist_fused, other):
            for x, y in zip(jax.tree_util.tree_leaves(t_fused),
                            jax.tree_util.tree_leaves(t_other)):
                np.testing.assert_allclose(x, y, atol=2e-6)


# -- (d) the counter reaches the record ---------------------------------------


@pytest.mark.parametrize("k,dispatches", [(1, 7), (4, 2)])
def test_record_counts_the_target_syncs(k, dispatches, rng, tmp_path):
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.learner_loop import Learner
    from tests.test_learning_diag import stamped_block

    cfg = tiny_cfg(**DOUBLE, **{
        "runtime.save_dir": str(tmp_path), "runtime.save_interval": 0,
        "runtime.steps_per_dispatch": k, "replay.learning_starts": 40})
    learner = Learner(cfg, tiny_net(cfg))
    q = BlockQueue(use_mp=False)
    for v in range(1, 5):
        q.put(stamped_block(learner.spec, rng, v))
    while learner.drain(q, max_items=1):
        pass
    assert learner.ready
    assert learner.metrics.log(1.0)["target_syncs"] == 0
    for _ in range(dispatches):
        learner.step()
    learner.flush_metrics()
    record = learner.metrics.log(1.0)
    steps = k * dispatches
    assert record["training_steps"] == steps
    assert record["target_syncs"] == steps // INTERVAL
    # cumulative, as ``training_steps`` is
    learner.step()
    learner.flush_metrics()
    assert (learner.metrics.log(1.0)["target_syncs"]
            == (steps + k) // INTERVAL)
