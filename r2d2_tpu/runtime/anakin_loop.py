"""Colocated act+train loop for the fully on-device acting path.

The orchestrator's host loop (runtime/orchestrator.py) spawns an actor
FLEET: threads/processes stepping Python envs, blocks crossing a queue,
weights crossing a shm service. This loop replaces all of it with a
single-threaded alternation on the device mesh (Podracer "Anakin", arxiv
2104.06272):

    act segment  — one jitted lax.scan: block_length steps of
                   actor.anakin_lanes batched pure-JAX envs + the policy
                   forward + in-graph block assembly (actor/anakin.py);
    ring-write   — the segment's N stacked blocks enter device replay via
                   the existing donated ``replay_add_many`` dispatch;
    train        — the learner's fused step(s), exactly as the host loop
                   dispatches them (same Learner, same diagnostics).

Mesh composition (ISSUE 8): with ``mesh.dp > 1`` the act segment and the
ring-write fuse into ONE shard_map dispatch over the Learner's mesh
(parallel/sharded.py make_sharded_anakin_act) — the lanes partition into
dp per-shard groups, each acting with its own RNG chain and its slice of
the GLOBAL ε ladder, writing straight into its local replay shard; the
learner's dp-sharded step then trains on the same mesh. Aggregate acting
throughput scales with dp while the learner gains its sharded-batch
throughput (PERF.md round 12). Only ``mesh.mp > 1`` and multihost remain
out of scope for the fused loop.

Weights are published BY REFERENCE: each acting segment reads
``learner.train_state.params`` directly — no weight service, no copy, and
the actors are never more than one segment stale. Staleness accounting
(PR5) keeps working: blocks are stamped with a pseudo publish count that
advances every ``weight_publish_interval`` learner steps, the same clock a
WeightPublisher would have ticked, and ``Learner.flush_metrics`` reads the
same counter — so sample-age and replay-occupancy ages stay meaningful.

Everything host-side is bookkeeping at SEGMENT cadence (N blocks, N*L env
steps at a time): ring accounting, the replay rate limiter, TrainMetrics,
telemetry stage timers (the new 'actor/act_scan' stage + the existing
ingest/learner stages), checkpoints. Episode returns are summed on device
and fetched lazily at log time. The loop is single-threaded and therefore
DETERMINISTIC given seeds — the collect:learn interleave is pinned by
``actor.anakin_scans_per_train`` (plus the rate limiter), not by host
scheduling.
"""

import os
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from r2d2_tpu.config import Config, apex_epsilon
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.replay.device_replay import replay_add_many
from r2d2_tpu.runtime.learner_loop import Learner
from r2d2_tpu.runtime.metrics import TrainMetrics


class AnakinStack:
    """Duck-typed PlayerStack twin for the on-device path: the pieces the
    callers actually touch (learner/metrics/telemetry + close())."""

    def __init__(self, cfg: Config, learner: Learner, metrics: TrainMetrics,
                 telemetry, carry):
        self.cfg = cfg
        self.player_idx = 0
        self.learner = learner
        self.metrics = metrics
        self.telemetry = telemetry
        self.carry = carry       # final ActCarry (inspection/tests)

    def close(self) -> None:
        self.learner.stop_background()
        self.telemetry.close()


def run_anakin_train(cfg: Config, *, max_training_steps: Optional[int] = None,
                     max_seconds: Optional[float] = None,
                     log_fn: Optional[Callable[[dict], None]] = None
                     ) -> List[AnakinStack]:
    """Run the fused act+train loop; returns [stack] (the Learner holds
    final state) — the same contract as orchestrator.train, which
    delegates here when ``actor.on_device`` is set."""
    from r2d2_tpu.actor.anakin import init_act_carry, make_anakin_act
    from r2d2_tpu.envs.factory import create_jax_env
    from r2d2_tpu.telemetry import Telemetry

    if not cfg.actor.on_device:
        raise ValueError("run_anakin_train requires actor.on_device=True")
    n_dev = len(jax.devices())
    dp = cfg.mesh.resolved_dp(n_dev)
    num_lanes = cfg.actor.anakin_lanes
    if cfg.mesh.mp > 1:
        raise NotImplementedError(
            "actor.on_device composes with data-parallel meshes only: the "
            "fused acting scan runs per-shard lane groups over mesh.dp, "
            "but model parallelism (mesh.mp > 1) shards the network's "
            "feature dims through the GSPMD learner step, which the "
            "acting scan does not run under — set mesh.mp=1 (mesh.dp > 1 "
            "is fine) or actor.on_device=false")
    if cfg.mesh.multihost:
        raise NotImplementedError(
            "actor.on_device is single-controller only: the fused loop "
            "owns the whole mesh from one process, while "
            "mesh.multihost=True runs the lockstep per-host trainer "
            "(parallel/multihost.py) — unset mesh.multihost, or use the "
            "host actor fleet for multihost runs")
    # the lane/shard contracts again, against the RESOLVED dp — Config
    # enforces both at construction for explicit mesh.dp, but dp=-1
    # (all devices) only resolves here
    if num_lanes % dp != 0:
        raise ValueError(
            f"actor.anakin_lanes ({num_lanes}) must be divisible by the "
            f"resolved mesh.dp ({dp}): each shard owns an equal lane "
            "group (anakin_lanes % dp == 0) — adjust actor.anakin_lanes "
            "or mesh.dp")

    env = create_jax_env(cfg.env)
    net = NetworkApply(env.action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)

    metrics = TrainMetrics(0, cfg.runtime.save_dir,
                           resume=bool(cfg.runtime.resume))
    telemetry = Telemetry.from_config(cfg, name="anakin-p0")
    metrics.set_telemetry(telemetry)
    if cfg.telemetry.enabled:
        telemetry.start_drain(
            os.path.join(cfg.runtime.save_dir or ".", "spans_player0.jsonl"),
            append=bool(cfg.runtime.resume))

    from r2d2_tpu.utils.platform import announce_runtime
    announce_runtime(cfg, metrics.logger)
    learner = Learner(cfg, net, 0, metrics=metrics)
    spec = learner.spec
    seg_steps = spec.block_length          # learning steps per lane-block
    if num_lanes // dp > spec.num_blocks:
        raise ValueError(
            f"per-shard lane group ({num_lanes // dp} = {num_lanes} lanes "
            f"/ dp={dp}) must be <= num_blocks ({spec.num_blocks}): grow "
            "replay.capacity or lower actor.anakin_lanes")
    pub_interval = max(cfg.runtime.weight_publish_interval, 1)

    def publish_count() -> int:
        # by-reference publication clock: what a WeightPublisher would
        # have counted had the learner pushed params every
        # weight_publish_interval steps (1 = the initial params)
        return 1 + learner.training_steps // pub_interval

    learner.weight_version_fn = publish_count

    # quantized acting (ISSUE 14): the by-reference pseudo-clock also
    # drives publish-time quantization — the inference bundle is rebuilt
    # only when the pseudo publish count TICKS (every
    # weight_publish_interval learner steps), never per segment, so the
    # acting scan streams a publish-time twin exactly like the host
    # actors do (no hot-path requantization). At "f32" the segment keeps
    # reading learner.train_state.params by reference, byte-identical.
    from r2d2_tpu.runtime.weights import make_publish_preparer
    prep = make_publish_preparer(net)
    quant_stats = None
    if prep is not None:
        from r2d2_tpu.telemetry import QuantStats
        quant_stats = QuantStats(cfg.network.inference_dtype,
                                 cfg.telemetry.quant_probe_interval)
        metrics.set_quant(quant_stats.interval_block)
    _bundle = {"tree": None, "pub": -1}

    def acting_params():
        if prep is None:
            return learner.train_state.params
        pc = publish_count()
        if _bundle["tree"] is None or _bundle["pub"] != pc:
            _bundle["tree"] = prep(learner.train_state.params, pc)
            _bundle["pub"] = pc
            quant_stats.on_stamp(pc)
        return _bundle["tree"]

    # the ε ladder spans the GLOBAL lane count whatever the mesh: dp
    # changes where lanes run, never the Ape-X exploration schedule
    epsilons = [apex_epsilon(i, num_lanes, cfg.actor.base_eps,
                             cfg.actor.eps_alpha) for i in range(num_lanes)]
    act_key = jax.random.PRNGKey(cfg.runtime.seed + 17)
    if dp > 1:
        # sharded anakin (ISSUE 8): the act scan + per-shard ring-write
        # fused into ONE shard_map dispatch over the Learner's mesh —
        # each shard's lane group feeds its local replay shard directly,
        # alongside the same mesh's dp-sharded learner step
        from r2d2_tpu.parallel import (init_sharded_act_carry,
                                       make_sharded_anakin_act)
        act_fn = make_sharded_anakin_act(
            env, net, spec, mesh=learner.mesh, num_lanes=num_lanes,
            epsilons=epsilons, gamma=cfg.optim.gamma,
            priority=cfg.actor.anakin_priority,
            near_greedy_eps=cfg.actor.near_greedy_eps,
            priority_eta=cfg.optim.priority_eta,
            quant_probe=cfg.telemetry.quant_probe_interval > 0)
        carry = init_sharded_act_carry(env, spec, num_lanes, learner.mesh,
                                       act_key)
    else:
        act_fn = make_anakin_act(
            env, net, spec, num_lanes=num_lanes, epsilons=epsilons,
            gamma=cfg.optim.gamma, priority=cfg.actor.anakin_priority,
            near_greedy_eps=cfg.actor.near_greedy_eps,
            priority_eta=cfg.optim.priority_eta,
            quant_probe=cfg.telemetry.quant_probe_interval > 0)
        carry = init_act_carry(env, spec, num_lanes, act_key)

    # system-health pillar (ISSUE 7), the on-device twin of the
    # PlayerStack wiring: resource sampler (the Learner registered ring +
    # train-state footprints; the lane carry registers here) and the alert
    # engine. No actor fleet, so no board gauges — this process's RSS/CPU
    # is the whole host picture. The compile/retrace monitor is the one
    # the Learner installed, bound to this loop's Telemetry: a build's
    # phases are spans under the stage open on the compiling thread, its
    # iteration and its call.
    resources = None
    compile_mon = learner.compile_monitor
    if cfg.telemetry.enabled and cfg.telemetry.resources_enabled:
        from r2d2_tpu.telemetry import (AlertEngine, ResourceMonitor,
                                        default_rules)
        from r2d2_tpu.telemetry.resources import (pytree_nbytes,
                                                  register_buffer)
        register_buffer("p0/anakin_carry", pytree_nbytes(carry))
        resources = ResourceMonitor(
            0, cfg.runtime.save_dir or ".",
            interval_s=cfg.telemetry.resources_interval_s,
            headroom_warn_frac=cfg.telemetry.resources_headroom_warn_frac,
            compile_monitor=compile_mon,
            aot_coverage_fn=learner.aot_coverage)
        metrics.set_resources(resources.block)
        if cfg.telemetry.alerts_enabled:
            metrics.set_sentinel(AlertEngine(
                default_rules(cfg.telemetry),
                jsonl_path=os.path.join(cfg.runtime.save_dir or ".",
                                        "alerts_player0.jsonl"),
                resume=bool(cfg.runtime.resume)))

    pending_stats: list = []

    def act_segment():
        nonlocal carry
        with telemetry.stage("actor/act_scan", lanes=num_lanes,
                             steps=seg_steps, shards=dp):
            if dp > 1:
                # act + ring-write fused in one sharded dispatch: each
                # shard's blocks land in its local replay without ever
                # leaving the shard, so there is no separate commit stage
                carry, learner.replay_state, stats = act_fn(
                    acting_params(), carry, learner.replay_state,
                    np.int32(publish_count()))
            else:
                carry, blocks, stats = act_fn(
                    acting_params(), carry,
                    np.int32(publish_count()))
        commit_s = 0.0
        if dp == 1:
            # commit latency only: the acting dispatch is its own stage;
            # folding it in would make ingest_drain_latency_ms
            # incomparable with the host path's pop-to-commit reading.
            # The record carries it with telemetry off too, hence the
            # clock reads beside the stage's own.
            t1 = time.time()
            with telemetry.stage("ingest/commit", blocks=num_lanes):
                learner.replay_state = replay_add_many(
                    spec, learner.replay_state, blocks)
            commit_s = time.time() - t1
        with telemetry.stage("anakin/accounting"):
            wv = publish_count()
            for _ in range(num_lanes):
                learner.ring.advance(seg_steps, wv)
                metrics.on_block(seg_steps, None)
            learner.env_steps += num_lanes * seg_steps
            metrics.set_buffer_size(learner.ring.buffer_steps)
            metrics.on_ingest_drain(num_lanes, commit_s)
            pending_stats.append(stats)

    def flush_stats():
        if not pending_stats:
            return
        fetched = jax.device_get(pending_stats)
        pending_stats.clear()
        # per-shard interval reductions (dp=1 stats are scalars — one
        # "shard"): episode counts/returns feed the return average, the
        # per-shard rows + imbalance ratio feed the record's anakin
        # block (telemetry/alerts.py shard_imbalance, inspect.py panel)
        eps_counts = np.sum([np.atleast_1d(s["reported_episodes"])
                             for s in fetched], axis=0)
        ret_sums = np.sum([np.atleast_1d(s["reported_return_sum"])
                           for s in fetched], axis=0)
        episodes = np.sum([np.atleast_1d(s["episodes"])
                           for s in fetched], axis=0)
        metrics.on_episodes(int(eps_counts.sum()), float(ret_sums.sum()))
        if quant_stats is not None and "quant_dq" in fetched[0]:
            # one probe per segment (per shard under dp > 1): interval
            # max |ΔQ| and the lane-weighted mean agreement feed the
            # record's quant block like the host actors' probes
            for s in fetched:
                quant_stats.on_probe(
                    float(np.max(np.atleast_1d(s["quant_dq"]))),
                    float(np.mean(np.atleast_1d(s["quant_agree"]))),
                    lanes=num_lanes)
        if dp > 1:
            shard_env = np.sum([np.atleast_1d(s["env_steps"])
                                for s in fetched], axis=0)
        else:
            shard_env = np.asarray([len(fetched) * num_lanes * seg_steps])
        lo = float(shard_env.min())
        metrics.set_anakin({
            "dp": dp,
            "lanes_per_shard": num_lanes // dp,
            "shard_env_steps": [int(v) for v in shard_env],
            "shard_episodes": [int(v) for v in episodes],
            "shard_reported_episodes": [int(v) for v in eps_counts],
            "shard_return_sum": [round(float(v), 4) for v in ret_sums],
            "shard_imbalance": (round(float(shard_env.max()) / lo, 4)
                                if lo > 0 else None),
        })

    # mid-run profiler capture (ISSUE 9 satellite): the fused on-device
    # loop is the exact path the kernel campaign profiles, yet only the
    # host-actor orchestrator had the capture triggers — wire the SAME
    # three (first-interval profile_dir, one-shot profile_at_step,
    # SIGUSR2 on demand) via the shared CaptureTriggers helper, so the
    # subtle arming/pending/restore rules exist once. Captures land
    # where telemetry/traceparse.py expects them.
    from r2d2_tpu.telemetry.profiler import CaptureTriggers
    triggers = CaptureTriggers(cfg.runtime)

    start = time.time()
    deadline = start + max_seconds if max_seconds else None
    max_steps = max_training_steps or cfg.optim.training_steps
    last_log = start
    stack = AnakinStack(cfg, learner, metrics, telemetry, carry)
    try:
        triggers.install()
        triggers.start_first_interval()
        if cfg.runtime.save_interval:
            learner.save(0)
        iteration = 0
        while ((deadline is None or time.time() < deadline)
               and learner.training_steps < max_steps):
            # one root span an iteration; every statement group below is
            # a child, so the root's self time is what no child names
            step0, env0 = learner.training_steps, learner.env_steps
            with telemetry.stage("anakin/iteration", iter=iteration,
                                 step=step0, env_steps=env0) as root:
                paused = learner.ingestion_paused
                if paused:
                    # rate limiter: collection is ahead of the
                    # collect:learn budget; only train until it reopens
                    # (the gate cannot be closed here — paused implies it
                    # is open)
                    learner._note_pause(True)
                else:
                    learner._note_pause(False)
                    scans = (cfg.actor.anakin_scans_per_train
                             if learner.ready else 1)
                    for _ in range(scans):
                        act_segment()
                if learner.ready and learner.training_steps < max_steps:
                    learner.step()
                with telemetry.stage("anakin/poll"):
                    now = time.time()
                    triggers.poll(now, learner.training_steps)
                    if resources is not None:
                        # resource sampling rides the loop at the same
                        # cheap-time-check cadence the PlayerStack's
                        # supervise pass uses
                        resources.maybe_sample(now)
                    if compile_mon is not None and learner.training_steps:
                        # warm-up ends once training has started: act_fn
                        # and the train program have compiled; any further
                        # compile of a known fn with new avals is a
                        # retrace (idempotent latch)
                        compile_mon.mark_warm()
                # counts at the iteration's boundary, before a log_fn
                # that may end the loop by raising
                root.tag(env_steps_written=learner.env_steps - env0,
                         blocks_written=((learner.env_steps - env0)
                                         // seg_steps),
                         train_steps=learner.training_steps - step0,
                         paused=paused)
                if now - last_log >= cfg.runtime.log_interval:
                    # the device is drained by flush_metrics and nothing
                    # is dispatched until the next iteration
                    with telemetry.stage("anakin/log"):
                        learner.flush_metrics()
                        with telemetry.stage("anakin/stats_fetch"):
                            flush_stats()
                        with telemetry.stage("metrics/record"):
                            record = metrics.log(now - last_log)
                        if log_fn:
                            with telemetry.stage("anakin/log_fn"):
                                log_fn({"player": 0, **record})
                        last_log = now
            iteration += 1
        learner.flush_metrics()
        flush_stats()
    finally:
        triggers.uninstall()   # stop any live capture, restore SIGUSR2
        stack.carry = carry
        try:
            if cfg.runtime.save_interval:
                learner.save_final()
        except Exception:
            import logging
            logging.getLogger(__name__).exception("final checkpoint failed")
        stack.close()     # the Learner's stop releases the compile monitor
    return [stack]
