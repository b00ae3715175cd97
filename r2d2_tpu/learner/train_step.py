"""The fused R2D2 learner step — sample → unroll → loss → Adam → priority
write-back as ONE XLA program.

Reference semantics being reproduced (/root/reference/worker.py:308-381):
frame-stack reassembly + /255 (330-331), double-DQN action selection
(335-339), invertible value-rescaled n-step target (341,383-390), IS-weighted
0.5·MSE over ragged learning steps (344-346), mixed max/mean priority
(348-350,240-249), grad-clip(40) + Adam (361-364), periodic hard target sync
(375-377).

TPU-native deltas:
  * the reference pays a Ray RPC + numba tree walk to sample, a D2H sync to
    compute priorities, and an async RPC to write them back; here all three
    are jnp ops inside the jitted step — the learner never leaves the device;
  * two LSTM unrolls per step instead of three: because an LSTM output at t
    depends only on inputs ≤ t, the grad-enabled online unroll over the full
    window also provides the (stop-gradient) action-selection Q at t+n — the
    reference's separate no-grad online pass (worker.py:336) is a gather;
  * ragged sequence handling is gather indices + masks (ops/indexing.py), not
    pack/pad;
  * sample→train→update is atomic, so the ring staleness guard
    (worker.py:196-206) is unnecessary by construction;
  * torch.cuda.amp → bf16 compute policy in the network (no loss scaling
    needed: bf16 keeps f32's exponent range).
"""

from typing import Any, Dict, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from r2d2_tpu.config import OptimConfig
from r2d2_tpu.learner.target import target_of
from r2d2_tpu.models.cores import require_lstm
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.ops.indexing import (
    frame_stack_indices,
    learning_step_mask,
    online_q_positions,
    target_q_positions,
)
from r2d2_tpu.ops.priority import mixed_td_errors_masked
from r2d2_tpu.ops.sum_tree import tree_update
from r2d2_tpu.ops.value import inverse_value_rescale, value_rescale
from r2d2_tpu.replay.device_replay import replay_sample
from r2d2_tpu.replay.structs import ReplaySpec, ReplayState, SampleBatch


class TrainState(flax.struct.PyTreeNode):
    params: Any
    target_params: Any          # == params when use_double is off (unused)
    opt_state: Any
    step: jnp.ndarray           # () int32
    key: jax.Array


def make_optimizer(optim: OptimConfig) -> optax.GradientTransformation:
    """clip_grad_norm + Adam, matching torch Adam semantics
    (ref worker.py:268,363: lr=1e-4, eps=1e-3 added outside the sqrt)."""
    return optax.chain(
        optax.clip_by_global_norm(optim.grad_norm),
        optax.adam(optim.lr, eps=optim.adam_eps),
    )


def create_train_state(key: jax.Array, net: NetworkApply, optim: OptimConfig
                       ) -> TrainState:
    """A fresh train state: the parameters, the target as ``target_of``
    holds them (a copy), Adam's state."""
    pkey, skey = jax.random.split(key)
    params = net.init(pkey)
    tx = make_optimizer(optim)

    def held_target(p):
        # one program for the whole tree, not one a leaf's shape
        return jax.tree_util.tree_map(jnp.copy, target_of(net, p))

    return TrainState(
        params=params,
        target_params=jax.jit(held_target)(params),
        opt_state=tx.init(params),
        step=jnp.zeros((), jnp.int32),
        key=skey,
    )


def sync_target(net: NetworkApply, optim: OptimConfig, use_double: bool,
                new_step, params, target_params):
    """The hard target sync (ref worker.py:375-377) as ONE branch over the
    whole tree: every ``optim.target_net_update_interval`` steps, counted
    1-based like the reference's post-increment check, the target becomes
    ``target_of(params)``, the parameters in the dtypes the target is held
    in (learner/target.py); on every other step it is passed through, which
    XLA aliases in place (a per-leaf ``jnp.where`` read and rewrote every
    target leaf on every step). Both branches go through ``target_of``, so
    a target handed in as float32 leaves narrowed and the branches agree.
    Returns (target_params, fired) with ``fired`` the step's 0/1
    ``target_sync`` counter; with double-Q off nothing reads the target and
    nothing fires. Shared by every step factory so their schedules cannot
    diverge."""
    if not use_double:
        return target_params, jnp.zeros((), jnp.int32)
    sync = (new_step % optim.target_net_update_interval) == 0
    target_params = jax.lax.cond(
        sync, lambda p, t: target_of(net, p), lambda p, t: target_of(net, t),
        params, target_params)
    return target_params, sync.astype(jnp.int32)


def _decode_inputs(net: NetworkApply, spec: ReplaySpec, batch: SampleBatch,
                   use_pallas: bool) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """THE storage→network decode (one place for every unroll path): uint8
    frame rows → stacked normalized obs of logical shape (B,T,H,W,K), action
    indices → one-hot (-1 encodes the null action as zeros). Which decode
    runs follows from the input's shapes (ops/pallas_kernels.py
    decode_route): on TPU the Pallas kernel that writes the first
    convolution's own layout and hands over ``LaneFrames`` (the torso's
    batch, frame index in lanes, nothing for XLA to copy in between) where
    the batch tiles the lanes and storage is tile-padded, the planar Pallas
    kernel for other shapes, the jnp gather elsewhere; out_height/out_width
    strip any exact-gather storage tile padding. Decodes directly into the
    network's compute dtype: under the bf16 policy this skips materializing
    the 4x-larger f32 obs intermediate that XLA would cast at the conv
    boundary anyway."""
    from r2d2_tpu.ops.pallas_kernels import stack_frames
    with jax.named_scope("obs_decode"):
        stacked = stack_frames(batch.obs, spec.seq_window, spec.frame_stack,
                               use_pallas=use_pallas,
                               out_dtype=net.module.compute_dtype,
                               out_height=spec.frame_height,
                               out_width=spec.frame_width)
        last_action = jax.nn.one_hot(batch.last_action, net.action_dim,
                                     dtype=jnp.float32)
    return stacked, last_action


def _unrolled_q(net: NetworkApply, spec: ReplaySpec, params,
                batch: SampleBatch, use_pallas: bool = False
                ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Decode (see _decode_inputs) and unroll the full window from the
    stored hidden state. Returns (B, T, A) f32 Q-values and the counters
    the core sowed ({} for the LSTM)."""
    stacked, last_action = _decode_inputs(net, spec, batch, use_pallas)
    q, _, counters = net.apply_learner(params, stacked, last_action,
                                       batch.hidden)
    return q, counters


def make_loss_fn(net: NetworkApply, spec: ReplaySpec, optim: OptimConfig,
                 use_double: bool):
    """Returns loss(params, target_params, batch) -> (loss, aux). Pure —
    shared by the single-chip jit, the shard_map path, and the tests."""

    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_obs_decode
    use_pallas = resolve_pallas_obs_decode(optim.pallas_obs_decode)

    def loss_fn(params, target_params, batch: SampleBatch):
        q_online, counters = _unrolled_q(net, spec, params, batch, use_pallas)
        own = counters.pop("core", None)

        # the target unroll is computed BEFORE entering the loss scope —
        # its ops keep their torso/lstm/head component scopes un-nested
        if use_double:
            q_target_all, _ = _unrolled_q(net, spec, target_params, batch,
                                          use_pallas)

        # "loss" component scope (ISSUE 9): everything below is gathers
        # + masked reductions over the unrolled Q — cheap, but
        # attributable (telemetry/traceparse.py) rather than landing in
        # the trace's unattributed bucket
        with jax.named_scope("loss"):
            tpos = target_q_positions(batch.burn_in_steps,
                                      batch.learning_steps,
                                      batch.forward_steps, spec.learning,
                                      spec.forward)
            opos = online_q_positions(batch.burn_in_steps, spec.learning)
            mask = learning_step_mask(batch.learning_steps, spec.learning)

            # --- bootstrap value at t+n (no grad; ref worker.py:335-339) ---
            q_online_tn = jax.lax.stop_gradient(
                jnp.take_along_axis(q_online, tpos[:, :, None], axis=1))
            if use_double:
                a_star = jnp.argmax(q_online_tn, axis=-1)           # (B,L)
                q_target_all = jax.lax.stop_gradient(q_target_all)
                q_target_tn = jnp.take_along_axis(
                    q_target_all, tpos[:, :, None], axis=1)
                q_next = jnp.take_along_axis(
                    q_target_tn, a_star[:, :, None], axis=2)[:, :, 0]
            else:
                q_next = jnp.max(q_online_tn, axis=-1)              # (B,L)
            q_next = jax.lax.stop_gradient(q_next)

            target = value_rescale(
                batch.reward + batch.gamma * inverse_value_rescale(
                    q_next, optim.value_rescale_eps),
                optim.value_rescale_eps)                            # (B,L)

            # --- online Q(s_t, a_t) over learning steps (worker.py:344) ---
            q_learn = jnp.take_along_axis(q_online, opos[:, :, None], axis=1)
            q_chosen = jnp.take_along_axis(
                q_learn, batch.action[:, :, None], axis=2)[:, :, 0]  # (B,L)

            td = (target - q_chosen) * mask
            num_valid = jnp.maximum(jnp.sum(mask), 1.0)
            # IS-weighted 0.5*MSE over valid steps (ref worker.py:168,346)
            loss = 0.5 * jnp.sum(batch.is_weights[:, None] * td**2) / num_valid

            priorities = mixed_td_errors_masked(jnp.abs(td), mask,
                                                optim.priority_eta)
            if own is not None:
                # a core's own counters (models/cores/__init__.py): those
                # per position (layers, B, T), its loss among them, per
                # layer their mean over the valid learning positions, as
                # the TD loss is taken. The loss's gradient joins the TD
                # loss's under the one clip; the value returned stays the
                # TD loss, which the priorities and the reference check are
                # made of, and the core's is reported beside it
                own = {name: jnp.sum(jnp.take_along_axis(
                    value, opos[None], axis=2) * mask[None],
                    axis=(1, 2)) / num_valid if value.ndim == 3 else value
                    for name, value in own.items()}
                if "loss" in own:
                    core_loss = jnp.sum(own["loss"])
                    loss = loss + (core_loss
                                   - jax.lax.stop_gradient(core_loss))
        aux = {
            "priorities": priorities,
            "mean_abs_td": jnp.sum(jnp.abs(td)) / num_valid,
            "mean_q": jnp.sum(q_chosen * mask) / num_valid,
            # raw per-element views for the learning-diagnostics histograms
            # (telemetry/learning.py); DCE'd when no LearningDiag consumes
            # them, so the plain step's program is unchanged
            "abs_td": jnp.abs(td),
            "mask": mask,
            "q_chosen": q_chosen,
        }
        if counters:
            # the routing counters of the online forward (a core with experts)
            aux["moe"] = counters
        if own is not None:
            aux["core"] = own
        return loss, aux

    return loss_fn


def make_learner_step(net: NetworkApply, spec: ReplaySpec, optim: OptimConfig,
                      use_double: bool, jit: bool = True, diag=None,
                      rdiag=None):
    """Build the fused step:

        step(train_state, replay_state) -> (train_state, replay_state, metrics)

    Both states are donated: the optimizer state, params, replay rings and
    priority tree update in place in HBM.

    ``diag`` (telemetry.LearningDiag or None): fuse the learning-dynamics
    diagnostics into the same program — device-side |TD|/priority/Q
    histograms, per-group grad norms, the non-finite guard, sample
    staleness stamps, and (every ``diag.interval`` steps, under lax.cond
    so the steady-state path is untouched) target-parameter distance and
    the stored-state ΔQ check. None compiles the pre-diagnostics program
    byte-for-byte — the telemetry.learning_enabled kill switch. The
    diagnostics read the PRE-update params and target, so they are tied
    before the optimizer by data dependency (an ``optimization_barrier``
    that the old params and target pass together with the diagnostics'
    outputs; Python order means nothing to XLA): untied, XLA:TPU could not
    update the loop state in place and copied it on every step for a branch
    that runs once in ``diag.interval`` (8.87 GB of ``copy`` a step in the
    scan body of ``moonlight-core.learner-long``, 1.68 GB tied: PERF.md §5).

    ``rdiag`` (telemetry.ReplayDiag or None): the replay-observability
    pillar (ISSUE 10) fused the same way — the per-slot sample-count
    increment + lane-composition bincount every step, and the sum-tree
    health snapshot / eviction-accumulator read under lax.cond every
    ``rdiag.interval`` steps. Same kill-switch contract
    (telemetry.replay_diag_enabled).
    """
    loss_fn = make_loss_fn(net, spec, optim, use_double)
    tx = make_optimizer(optim)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(train_state: TrainState, replay_state: ReplayState):
        key, sample_base = jax.random.split(train_state.key)
        # fold_in(0) matches the dp-sharded step's per-shard key derivation,
        # so a dp=1 mesh reproduces the single-chip sample stream exactly
        # (tested in tests/test_parallel.py)
        sample_key = jax.random.fold_in(sample_base, 0)
        # nested-jit calls trace inline into this one program; the
        # component scope covers the window gather + stratified descent
        # (tree_sample carries its own nested sum_tree scope)
        with jax.named_scope("replay_sample"):
            batch = replay_sample(spec, replay_state, sample_key)

        (loss, aux), grads = grad_fn(
            train_state.params, train_state.target_params, batch)

        # priority write-back, atomic with the sample (no staleness window)
        tree = tree_update(
            spec.tree_layers, replay_state.tree, spec.prio_exponent,
            aux["priorities"], batch.idxes)
        replay_state = replay_state.replace(tree=tree)

        new_step = train_state.step + 1
        with jax.named_scope("optimizer"):
            # the norm the clip waits for (XLA keeps one of the two)
            grad_norm = optax.global_norm(grads)
        old_params, old_target = train_state.params, train_state.target_params
        ld = {}
        if diag is not None:
            from r2d2_tpu.telemetry.learning import fused_diagnostics
            # pre-update params: consistent with the batch just trained on
            ld = fused_diagnostics(
                net, spec, diag, new_step, old_params, old_target, batch,
                aux, grads, loss, grad_norm, replay_state=replay_state)
            # the tie (docstring): what the optimizer and the sync consume
            # exists only once the diagnostics' reads are done
            (old_params, old_target), ld = jax.lax.optimization_barrier(
                ((old_params, old_target), ld))

        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, train_state.opt_state,
                                           old_params)
            params = optax.apply_updates(old_params, updates)
        if "moe" in aux:
            # the mean the core centred its routers' inputs on goes among
            # the parameters, where acting reads it
            from r2d2_tpu.models.cores.experts import store_router_means
            params = store_router_means(params, aux["moe"]["input_mean"])

        target_params, target_sync = sync_target(
            net, optim, use_double, new_step, params, old_target)

        metrics = {
            "loss": loss,
            "mean_abs_td": aux["mean_abs_td"],
            "mean_q": aux["mean_q"],
            "grad_norm": grad_norm,
            "target_sync": target_sync,
        }
        if "moe" in aux:
            # the experts' routing counters of this step
            metrics.update({f"moe/{k}": v for k, v in aux["moe"].items()
                            if k != "input_mean"})
        if "core" in aux:
            # the core's own counters and its loss, per layer
            metrics.update({f"core/{k}": v for k, v in aux["core"].items()})
        metrics.update(ld)
        if rdiag is not None:
            # replay-pathology pillar (ISSUE 10): sample-count ring +
            # lane bincount every step, tree-health snapshot on the
            # rdiag.interval cadence — after the priority write-back so
            # the snapshot reflects this step's tree
            from r2d2_tpu.telemetry.replaydiag import fused_replay_diag
            replay_state, rd = fused_replay_diag(
                spec, rdiag, new_step, replay_state, batch)
            metrics.update(rd)
        train_state = train_state.replace(
            params=params, target_params=target_params,
            opt_state=opt_state, step=new_step, key=key)
        return train_state, replay_state, metrics

    if jit:
        return jax.jit(step, donate_argnums=(0, 1))
    return step


def make_external_batch_step(net: NetworkApply, spec: ReplaySpec,
                             optim: OptimConfig, use_double: bool,
                             diag=None, rdiag=None):
    """Train step for host-placement replay (config replay.placement="host"):
    the batch is sampled by HostReplay on the CPU (native C++ sum tree) and
    fed across the host boundary, mirroring the reference's architecture
    (/root/reference/worker.py:299-306) minus Ray. Returns
    (train_state, metrics) — priorities in metrics["priorities"] go back to
    the host tree asynchronously, guarded by HostReplay's staleness check.

    Sharding-agnostic by design: under committed (device_put) inputs the
    compiled program follows THEIR shardings, which is how the tensor-
    parallel path reuses this exact step (parallel/tensor_parallel.py).
    """
    require_lstm(net.config, "the external-batch train step")
    loss_fn = make_loss_fn(net, spec, optim, use_double)
    tx = make_optimizer(optim)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(train_state: TrainState, batch: SampleBatch):
        (loss, aux), grads = grad_fn(
            train_state.params, train_state.target_params, batch)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, train_state.opt_state,
                                           train_state.params)
            params = optax.apply_updates(train_state.params, updates)

        new_step = train_state.step + 1
        target_params, target_sync = sync_target(
            net, optim, use_double, new_step, params,
            train_state.target_params)

        grad_norm = optax.global_norm(grads)
        metrics = {
            "loss": loss,
            "priorities": aux["priorities"],
            "mean_abs_td": aux["mean_abs_td"],
            "mean_q": aux["mean_q"],
            "grad_norm": grad_norm,
            "target_sync": target_sync,
        }
        if diag is not None and batch.weight_version is not None:
            # host placement: histograms / grad norms / staleness / the
            # non-finite guard; ΔQ needs the device-resident ring context
            # and reports NaN here (replay_state=None)
            from r2d2_tpu.telemetry.learning import fused_diagnostics
            metrics.update(fused_diagnostics(
                net, spec, diag, new_step, train_state.params,
                train_state.target_params, batch, aux, grads, loss,
                grad_norm, replay_state=None))
        if rdiag is not None and batch.lane is not None and rdiag.lanes > 0:
            # host placement carries only the lane-composition half of the
            # replay pillar in-graph; sum-tree health / eviction lifetimes
            # come from the HostReplay numpy twin at the metrics flush
            from r2d2_tpu.telemetry.replaydiag import lane_counts
            metrics["rd/lane_counts"] = lane_counts(batch.lane, rdiag.lanes)
        train_state = train_state.replace(
            params=params, target_params=target_params,
            opt_state=opt_state, step=new_step, key=train_state.key)
        return train_state, metrics

    # Donation audit (ISSUE 6 satellite): train_state donated like every
    # step factory; the BATCH deliberately is not — the host loop reads
    # batch.idxes AFTER the step for the async priority write-back
    # (learner_loop._host_step_once), so donating it would hand the
    # write-back a dead buffer. The batch is also the prefetch thread's
    # fresh device_put each step, so there is no ring to alias in place.
    return jax.jit(step, donate_argnums=0)


def make_multi_learner_step(net: NetworkApply, spec: ReplaySpec,
                            optim: OptimConfig, use_double: bool,
                            steps_per_dispatch: int, diag=None, rdiag=None):
    """K fused steps per dispatch via lax.scan — one host round-trip buys K
    training steps.

    The reference pays a Ray RPC and a GPU sync per step by construction
    (/root/reference/worker.py:303,348); on TPU the remaining per-step cost
    is the host dispatch itself, which this amortizes. Semantics are
    identical to K calls of the single step (same RNG chain, same per-step
    target-sync schedule via the carried step counter); only the host-side
    observation points (weight publish, checkpoint) coarsen to dispatch
    boundaries. Returns stacked (K,) metrics per dispatch (the learning
    diagnostics' histograms stack to (K, 64), ΔQ to (K,) with NaN on the
    non-interval steps — the scanned cond predicate rides the carried
    step counter, so interval steps fire inside the scan too).
    """
    inner = make_learner_step(net, spec, optim, use_double, jit=False,
                              diag=diag, rdiag=rdiag)

    def multi_step(train_state: TrainState, replay_state: ReplayState):
        if use_double:
            # the loop carries the target as the sync leaves it: a target
            # handed in as float32 enters narrowed (a held one as it is)
            train_state = train_state.replace(target_params=target_of(
                net, train_state.target_params))

        def body(carry, _):
            ts, rs = carry
            ts, rs, m = inner(ts, rs)
            return (ts, rs), m

        (train_state, replay_state), metrics = jax.lax.scan(
            body, (train_state, replay_state), None, length=steps_per_dispatch)
        return train_state, replay_state, metrics

    return jax.jit(multi_step, donate_argnums=(0, 1))
