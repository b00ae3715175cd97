"""Sharded learner: the fused step over a device mesh via shard_map.

Design (SURVEY §5.8, scaling-book recipe — pick a mesh, annotate shardings,
let XLA insert collectives):

  * The replay ring gains a leading ``dp`` axis sharded across chips: each
    chip owns ``num_blocks`` blocks, its own priority sum tree, and its own
    ring pointer. Prioritized sampling is per-shard (stratified within the
    chip's tree) — with round-robin block feeding this factorizes global
    stratified sampling across chips, and priority write-back stays chip-local
    (zero cross-chip traffic on the replay path).
  * Params / optimizer state are replicated; each chip computes gradients on
    its local ``batch_size`` sequences and a single ``pmean`` over ICI makes
    the Adam update identical everywhere — the global batch is
    ``dp * batch_size`` (the reference's learner has no equivalent axis; its
    batch is bounded by half a GPU, worker.py:251).
  * The RNG key is replicated; each shard folds in its axis index for
    sampling, and the carried key stays replicated.

The inner computation is the SAME ``make_loss_fn``/tree code as the
single-chip path — the mesh is an orthogonal layer, exactly the property the
reference's Ray design lacks.
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from r2d2_tpu.config import OptimConfig
from r2d2_tpu.learner.train_step import (
    TrainState, make_loss_fn, make_optimizer, sync_target)
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.ops.sum_tree import tree_update
from r2d2_tpu.replay.device_replay import (
    replay_init, replay_sample, replay_add, replay_add_many)
from r2d2_tpu.replay.structs import Block, ReplaySpec, ReplayState


def _shard0(tree):
    """Per-shard view: drop the leading dp axis (local size 1)."""
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _unshard0(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def sharded_replay_init(spec: ReplaySpec, mesh: Mesh) -> ReplayState:
    """Global replay state with leading dp axis, placed shard-per-chip.

    Built under jit with the dp sharding as its OUTPUT, so each chip
    allocates its own ring and nothing else. Materializing the dp-wide state
    on one device and resharding it needs dp x the ring there: 25.6 GiB at
    dp=4 and the default capacity, which a 16 GB v5e refused (PR 21)."""
    from r2d2_tpu.parallel.mesh import dp_sharding
    dp = mesh.shape["dp"]

    def build():
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (dp,) + x.shape),
            replay_init(spec))

    return jax.jit(build, out_shardings=dp_sharding(mesh))()


def make_sharded_replay_add(spec: ReplaySpec, mesh: Mesh):
    """add(state, block, shard_idx): ring-write ``block`` into one chip's
    shard (host feeder round-robins shard_idx). The block is broadcast and
    non-owners no-op — a few MB over ICI per 400 env steps."""

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("dp"), P(), P()), out_specs=P("dp"), check_vma=False)
    def add(state: ReplayState, block: Block, shard_idx):
        my = jax.lax.axis_index("dp")
        local = _shard0(state)

        def write(s):
            return replay_add(spec, s, block)

        local = jax.lax.cond(my == shard_idx[0], write, lambda s: s, local)
        return _unshard0(local)

    def add_fn(state, block, shard_idx: int):
        return add(state, block, jnp.asarray([shard_idx], jnp.int32))

    return jax.jit(add_fn, donate_argnums=0)


def _lane_group_size(num_lanes: int, dp: int) -> int:
    """The per-shard lane count, with the ONE divisibility check both
    sharded-anakin entry points share (Config and the loop re-state it
    earlier for explicit/resolved mesh.dp — this is the library-level
    backstop for direct callers)."""
    if num_lanes % dp != 0:
        raise ValueError(
            f"anakin lanes ({num_lanes}) must divide evenly across the "
            f"mesh's dp={dp} shards (lanes % dp == 0)")
    return num_lanes // dp


def init_sharded_act_carry(env, spec: ReplaySpec, num_lanes: int,
                           mesh: Mesh, key):
    """The sharded twin of actor/anakin.py init_act_carry: one fresh
    per-shard carry of ``num_lanes / dp`` lanes per chip, stacked on a
    leading dp axis and placed shard-per-chip. Shard s's RNG chain is
    ``fold_in(key, s)`` — the SAME construction tests reproduce when
    they build the per-shard reference path — so every shard's env
    schedules, ε draws and exploration streams are independent."""
    from r2d2_tpu.actor.anakin import init_act_carry
    from r2d2_tpu.parallel.mesh import dp_sharding
    dp = mesh.shape["dp"]
    lps = _lane_group_size(num_lanes, dp)
    carries = [init_act_carry(env, spec, lps, jax.random.fold_in(key, s))
               for s in range(dp)]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *carries)
    return jax.device_put(stacked, dp_sharding(mesh))


def make_sharded_anakin_act(env, net, spec: ReplaySpec, *, mesh: Mesh,
                            num_lanes: int, epsilons, gamma: float,
                            priority, near_greedy_eps: float,
                            priority_eta: float = 0.9,
                            quant_probe: bool = True):
    """The dp-sharded fused acting segment (ISSUE 8 tentpole):

        act(params, carry, replay_state, weight_version)
            -> (carry, replay_state, shard_stats)

    ONE shard_map dispatch: each shard runs the SAME act core as the
    1x1-mesh path (actor/anakin.py make_act_core) over its own lane
    group of ``num_lanes / dp`` lanes — pure-JAX env steps, policy
    forward, ε-greedy, auto-reset, in-graph block assembly — then
    ring-writes its group's blocks STRAIGHT into its local replay shard
    via ``replay_add_many``. No host round-trip, no cross-shard block
    traffic: the only replicated inputs are the params and the publish
    clock, and nothing is reduced across shards (stats come back
    per-shard).

    Semantics vs dp=1:

      * the Ape-X ε ladder spans the GLOBAL lane count — shard s gets
        the contiguous slice [s*lps, (s+1)*lps) of the ``num_lanes``-
        wide ladder, exactly like a vector-actor fleet's lane split
        (config.vector_lane_epsilons), so dp changes WHERE lanes run,
        never the exploration schedule;
      * per-shard RNG chains come from the carry built by
        ``init_sharded_act_carry`` (fold_in(key, shard)) — shards
        explore and reset independently;
      * ``shard_stats`` carries (dp,)-shaped per-shard reductions
        (episodes, reported episodes/return sums, env steps) so the
        telemetry layer can surface per-shard balance without a
        cross-shard reduce inside the program.

    Carry and replay state are donated (the multi-GB obs buffers update
    in place, per shard)."""
    from r2d2_tpu.actor.anakin import make_act_core
    import numpy as np
    dp = mesh.shape["dp"]
    eps_list = [float(e) for e in epsilons]
    if len(eps_list) != num_lanes:
        raise ValueError(
            f"need one epsilon per GLOBAL lane: got {len(eps_list)} for "
            f"{num_lanes} lanes (the ladder spans all shards)")
    lps = _lane_group_size(num_lanes, dp)
    if lps > spec.num_blocks:
        raise ValueError(
            f"per-shard lane group ({lps} = {num_lanes} lanes / dp={dp}) "
            f"must be <= num_blocks ({spec.num_blocks}): each segment "
            "ring-writes one block per lane into the shard's local ring, "
            "whose scatter rows must not alias")
    eps_shards = jnp.asarray(eps_list, jnp.float32).reshape(dp, lps)
    report_shards = jnp.asarray(
        np.asarray([e <= near_greedy_eps for e in eps_list],
                   bool).reshape(dp, lps))
    core = make_act_core(env, net, spec, num_lanes=lps, gamma=gamma,
                         priority=priority, priority_eta=priority_eta,
                         quant_probe=quant_probe)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P("dp"), P("dp"), P(), P("dp"), P("dp")),
        out_specs=(P("dp"), P("dp"), P("dp")), check_vma=False)
    def step(params, carry, replay_global, weight_version, eps, report):
        local_carry = _shard0(carry)
        local_replay = _shard0(replay_global)
        # lane provenance (ISSUE 10): shard s owns the contiguous slice
        # [s*lps, (s+1)*lps) of the GLOBAL ladder — the same layout the
        # eps reshape above encodes — so the stamps are derivable from
        # the axis index, no extra input
        my_lanes = (jax.lax.axis_index("dp") * lps
                    + jnp.arange(lps, dtype=jnp.int32))
        new_carry, blocks, stats = core(params, local_carry,
                                        weight_version, eps[0], report[0],
                                        lanes=my_lanes)
        local_replay = replay_add_many(spec, local_replay, blocks)
        shard_stats = {k: v[None] for k, v in stats.items()}
        # measured from the blocks that actually entered this shard's
        # ring, NOT a trace-time constant: under today's lockstep
        # program every shard emits full blocks every segment (so the
        # downstream imbalance ratio reads exactly 1.0 — asserted in
        # tests), but the signal follows the DATA, so a composition
        # that emits ragged/partial blocks per shard skews it for real
        shard_stats["env_steps"] = jnp.sum(
            blocks.learning_steps).astype(jnp.int32)[None]
        return (_unshard0(new_carry), _unshard0(local_replay), shard_stats)

    def act(params, carry, replay_state, weight_version):
        return step(params, carry, replay_state, weight_version,
                    eps_shards, report_shards)

    return jax.jit(act, donate_argnums=(1, 2))


def make_sharded_replay_add_many(spec: ReplaySpec, mesh: Mesh):
    """add_many(state, blocks, start_shard): ring-write K stacked blocks in
    ONE dispatch, round-robin across the dp shards — parity-exact with K
    sequential ``make_sharded_replay_add`` calls starting at ``start_shard``.

    Block k goes to shard ``(start_shard + k) % dp``; inside the single
    shard_map dispatch each shard scans the broadcast K-block batch and
    ring-writes its own strided subset in feed order (owner-conditional
    writes), so every shard's local pointer advances exactly as under the
    per-block path. The host pays one dispatch + one K-block transfer
    instead of K of each. K is a static shape (one compile per drain size).
    """
    dp = mesh.shape["dp"]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("dp"), P(), P()), out_specs=P("dp"), check_vma=False)
    def add_many(state: ReplayState, blocks: Block, start_shard):
        my = jax.lax.axis_index("dp")
        local = _shard0(state)
        k = blocks.priority.shape[0]

        def body(s, xs):
            blk, i = xs
            owner = (start_shard[0] + i) % dp
            return jax.lax.cond(
                my == owner, lambda st: replay_add(spec, st, blk),
                lambda st: st, s), None

        local, _ = jax.lax.scan(
            body, local, (blocks, jnp.arange(k, dtype=jnp.int32)))
        return _unshard0(local)

    def add_fn(state, blocks, start_shard: int):
        return add_many(state, blocks,
                        jnp.asarray([start_shard], jnp.int32))

    return jax.jit(add_fn, donate_argnums=0)


def _post_gradient_update(net: NetworkApply, tx, optim: OptimConfig,
                          use_double: bool, train_state: TrainState, grads,
                          key, loss, mean_abs_td, mean_q):
    """Everything after the (already-reduced) gradients: Adam update,
    target-net sync schedule, metrics dict, TrainState advance. ONE
    implementation shared by the manual shard_map dp path and the GSPMD
    mp path so their step semantics cannot diverge."""
    updates, opt_state = tx.update(grads, train_state.opt_state,
                                   train_state.params)
    params = optax.apply_updates(train_state.params, updates)

    new_step = train_state.step + 1
    target_params, target_sync = sync_target(
        net, optim, use_double, new_step, params, train_state.target_params)

    metrics = {
        "loss": loss,
        "mean_abs_td": mean_abs_td,
        "mean_q": mean_q,
        "grad_norm": optax.global_norm(grads),
        "target_sync": target_sync,
    }
    train_state = train_state.replace(
        params=params, target_params=target_params,
        opt_state=opt_state, step=new_step, key=key)
    return train_state, metrics


def make_sharded_learner_step(net: NetworkApply, spec: ReplaySpec,
                              optim: OptimConfig, use_double: bool, mesh: Mesh,
                              steps_per_dispatch: int = 1, diag=None,
                              rdiag=None):
    """The dp-sharded fused step. Same contract as make_learner_step.

    ``steps_per_dispatch`` > 1 scans K per-shard steps inside the shard_map
    body (pmean in the scan body is legal under shard_map), so one host
    dispatch buys K sharded training steps — the same amortization
    make_multi_learner_step gives the single-chip path, with identical
    math (same RNG chain, same target-sync schedule; equivalence tested in
    tests/test_parallel.py). Metrics come back stacked (K,) per dispatch.

    ``mesh`` may carry an mp axis > 1 (dp x mp): the body then runs MANUAL
    over dp only and AUTO (GSPMD) over mp — pass the TrainState in with its
    wide feature dims sharded over mp (tensor_parallel.state_shardings) and
    the SPMD partitioner inserts the TP collectives inside the same fused
    sample-in-HBM step; replay stays dp-sharded (mp-replicated). This
    honors the "model sharding is a mesh-axis change" promise on the
    flagship device-replay path (VERDICT r3 #4).

    ``diag`` (telemetry.LearningDiag or None): the learning diagnostics,
    reduced to replicated outputs so they fit the step's P() metric specs —
    histograms psum across shards (one GLOBAL-batch histogram), scalars
    pmean, staleness via reduced pmin/pmax/pmean version stats (the raw
    per-sequence stamp vectors differ per shard and are omitted here).

    ``rdiag`` (telemetry.ReplayDiag or None): the replay-observability
    pillar (ISSUE 10) over the PER-SHARD rings — sample-count /
    eviction accounting stays shard-local, lane bincounts psum to one
    global composition, and the sum-tree snapshots all_gather to
    ``rd/shard_*`` arrays (leading dp axis) so the record carries BOTH
    per-shard and merged tree-health views (the prerequisite
    instrumentation for rebalancing a sharded replay, ROADMAP item 3).
    """
    from r2d2_tpu.models.cores import require_lstm
    require_lstm(net.config, "the dp-sharded learner step")
    loss_fn = make_loss_fn(net, spec, optim, use_double)
    tx = make_optimizer(optim)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    k = steps_per_dispatch

    def one_step(train_state: TrainState, replay_state: ReplayState, my):
        key, sample_base = jax.random.split(train_state.key)
        sample_key = jax.random.fold_in(sample_base, my)
        batch = replay_sample(spec, replay_state, sample_key)

        (loss, aux), grads = grad_fn(
            train_state.params, train_state.target_params, batch)
        # gradient allreduce over ICI — the only cross-chip traffic per step
        grads = jax.lax.pmean(grads, "dp")
        loss = jax.lax.pmean(loss, "dp")

        tree = tree_update(spec.tree_layers, replay_state.tree,
                           spec.prio_exponent, aux["priorities"], batch.idxes)
        replay_state = replay_state.replace(tree=tree)

        ld = {}
        if diag is not None:
            import optax as _optax
            from r2d2_tpu.telemetry.learning import fused_diagnostics
            ld = fused_diagnostics(
                net, spec, diag, train_state.step + 1, train_state.params,
                train_state.target_params, batch, aux, grads, loss,
                _optax.global_norm(grads), replay_state=replay_state,
                raw_arrays=False)
            # make every diagnostic replicated (out_specs P()): counts add,
            # scalars average, version extrema take the fleet min/max
            for kk in ("ld/td_hist", "ld/prio_hist", "ld/q_hist"):
                ld[kk] = jax.lax.psum(ld[kk], "dp")
            ld["ld/version_min"] = jax.lax.pmin(ld["ld/version_min"], "dp")
            ld["ld/version_max"] = jax.lax.pmax(ld["ld/version_max"], "dp")
            ld["ld/nonfinite"] = jax.lax.pmax(ld["ld/nonfinite"], "dp")
            for kk in ("ld/version_mean", "ld/unknown_frac",
                       "ld/delta_q_stored", "ld/delta_q_zero",
                       "ld/delta_q_recomputed", "ld/target_dist"):
                ld[kk] = jax.lax.pmean(ld[kk], "dp")
            # grad-group norms are computed from the pmean'd grads —
            # already replicated, no reduction needed

        if rdiag is not None:
            from r2d2_tpu.telemetry.replaydiag import (fused_replay_diag,
                                                       shard_replay_diag)
            replay_state, rd = fused_replay_diag(
                spec, rdiag, train_state.step + 1, replay_state, batch)
            # gather/psum OUTSIDE the lax.cond (off-interval NaNs reduce
            # to NaNs, which the host aggregator skips) so no collective
            # ever sits inside a branch
            ld.update(shard_replay_diag(rd, "dp"))

        train_state, metrics = _post_gradient_update(
            net, tx, optim, use_double, train_state, grads, key, loss,
            jax.lax.pmean(aux["mean_abs_td"], "dp"),
            jax.lax.pmean(aux["mean_q"], "dp"))
        metrics.update(ld)
        return train_state, replay_state, metrics

    # mp > 1 routes to the fully-GSPMD formulation: a shard_map body that is
    # manual over dp but auto over mp trips XLA's partitioner on the
    # cross-partition allreduce ("must be in (partial) manual partitioning
    # mode", measured round 4), so the composition is expressed without
    # manual collectives instead.
    if mesh.shape.get("mp", 1) > 1:
        return _make_gspmd_learner_step(net, spec, optim, use_double, mesh,
                                        steps_per_dispatch, diag=diag,
                                        rdiag=rdiag)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P("dp")), out_specs=(P(), P("dp"), P()),
        check_vma=False)
    def step(train_state: TrainState, replay_global: ReplayState):
        replay_state = _shard0(replay_global)
        my = jax.lax.axis_index("dp")
        if k == 1:
            ts, rs, metrics = one_step(train_state, replay_state, my)
        else:
            def body(carry, _):
                ts, rs = carry
                ts, rs, m = one_step(ts, rs, my)
                return (ts, rs), m

            (ts, rs), metrics = jax.lax.scan(
                body, (train_state, replay_state), None, length=k)
        return ts, _unshard0(rs), metrics

    return jax.jit(step, donate_argnums=(0, 1))


def _make_gspmd_learner_step(net: NetworkApply, spec: ReplaySpec,
                             optim: OptimConfig, use_double: bool, mesh: Mesh,
                             steps_per_dispatch: int = 1, diag=None,
                             rdiag=None):
    """The dp x mp fused step, expressed entirely in GSPMD terms.

    Identical math and RNG chain to the manual shard_map path (per-shard
    sample keys are ``fold_in(base, shard_index)``; gradients are the mean
    over shards; same target-sync schedule — parity-tested), but the dp
    axis is a vmapped leading dimension whose mean-reduction GSPMD lowers
    to the allreduce, and the mp axis shards the params' wide feature dims
    (tensor_parallel.state_shardings) with the partitioner inserting the TP
    collectives inside the same fused sample-in-HBM program. Used for
    mesh.mp > 1, where a manual-dp/auto-mp shard_map body fails to
    partition (see make_sharded_learner_step).
    """
    loss_fn = make_loss_fn(net, spec, optim, use_double)
    tx = make_optimizer(optim)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    k = steps_per_dispatch
    dp = mesh.shape["dp"]
    replay_sharding = NamedSharding(mesh, P("dp"))

    def one_step(train_state: TrainState, replay_global: ReplayState):
        key, sample_base = jax.random.split(train_state.key)
        keys = jax.vmap(lambda i: jax.random.fold_in(sample_base, i))(
            jnp.arange(dp))    # int32 indices, matching lax.axis_index
        batches = jax.vmap(lambda rs, sk: replay_sample(spec, rs, sk))(
            replay_global, keys)

        (loss_v, aux_v), grads_v = jax.vmap(
            grad_fn, in_axes=(None, None, 0))(
            train_state.params, train_state.target_params, batches)
        grads = jax.tree_util.tree_map(lambda g: g.mean(0), grads_v)

        trees = jax.vmap(
            lambda t, pr, idx: tree_update(spec.tree_layers, t,
                                           spec.prio_exponent, pr, idx))(
            replay_global.tree, aux_v["priorities"], batches.idxes)
        replay_global = replay_global.replace(
            tree=jax.lax.with_sharding_constraint(trees, replay_sharding))

        ld = {}
        if diag is not None:
            import optax as _optax
            from r2d2_tpu.telemetry.learning import fused_diagnostics
            # shard 0's local view: the per-shard idxes index per-shard
            # rings, so the ΔQ context (and with it the whole diagnostic
            # sub-batch) is taken from one shard — documented, and the
            # loss/grads fed in stay GLOBAL
            shard0 = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
            ld = fused_diagnostics(
                net, spec, diag, train_state.step + 1, train_state.params,
                train_state.target_params, shard0(batches), shard0(aux_v),
                grads, loss_v.mean(), _optax.global_norm(grads),
                replay_state=shard0(replay_global))

        if rdiag is not None:
            from r2d2_tpu.telemetry.replaydiag import fused_replay_diag
            # vmap over shards keeps sample-count/eviction accounting
            # shard-local; the (dp, …) outputs ARE the per-shard views
            # (the manual path reaches the same layout via all_gather)
            replay_global, rdm = jax.vmap(
                lambda rs, b: fused_replay_diag(
                    spec, rdiag, train_state.step + 1, rs, b)
            )(replay_global, batches)
            replay_global = jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, replay_sharding), replay_global)
            if "rd/lane_counts" in rdm:
                ld["rd/lane_counts"] = rdm.pop("rd/lane_counts").sum(0)
            ld.update({k.replace("rd/", "rd/shard_"): v
                       for k, v in rdm.items()})

        train_state, metrics = _post_gradient_update(
            net, tx, optim, use_double, train_state, grads, key,
            loss_v.mean(), aux_v["mean_abs_td"].mean(),
            aux_v["mean_q"].mean())
        metrics.update(ld)
        return train_state, replay_global, metrics

    def step(train_state: TrainState, replay_global: ReplayState):
        if k == 1:
            return one_step(train_state, replay_global)

        def body(carry, _):
            ts, rs = carry
            ts, rs, m = one_step(ts, rs)
            return (ts, rs), m

        (ts, rs), metrics = jax.lax.scan(
            body, (train_state, replay_global), None, length=k)
        return ts, rs, metrics

    return jax.jit(step, donate_argnums=(0, 1))


def sharded_buffer_steps(state: ReplayState) -> int:
    """Total stored learning steps across all shards."""
    return int(jnp.sum(state.learning_steps))
