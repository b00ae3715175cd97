"""Learner host driver around the fused device step.

The reference Learner is a Ray GPU actor with a prefetch thread pulling
batches over RPC and a train thread running torch ops
(/root/reference/worker.py:251-390). Two placements here
(config replay.placement):

  * "device" (default): batches never cross the host boundary — the fused
    step samples in HBM — so the host loop is thin: drain the feeder queue
    (jitted ring-writes), gate on learning_starts, dispatch steps, publish
    weights, checkpoint, count metrics. Ingestion between steps is the only
    add/sample interleaving point, which is what makes the fused step's
    priority write-back race-free (see replay/device_replay.py).
  * "host": the reference's architecture minus Ray — numpy ring + native C++
    sum tree on the CPU, a prefetch thread keeping ``prefetch_batches``
    device-resident batches in flight (ref worker.py:292-306), and an async
    priority write-back thread guarded by the staleness check
    (ref worker.py:368,192-209).
"""

import os
import queue as queue_mod
import threading
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from r2d2_tpu.config import Config
from r2d2_tpu.learner.train_step import (
    TrainState, create_train_state, make_external_batch_step,
    make_learner_step, make_multi_learner_step)
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.replay.device_replay import (
    replay_add, replay_add_many, replay_init)
from r2d2_tpu.replay.host_replay import HostReplay
from r2d2_tpu.replay.structs import Block, ReplaySpec
from r2d2_tpu.runtime.checkpoint import apply_restore, save_checkpoint
from r2d2_tpu.runtime.metrics import TrainMetrics


class Learner:
    def __init__(self, cfg: Config, net: NetworkApply, player_idx: int = 0,
                 seed: Optional[int] = None, metrics: Optional[TrainMetrics] = None):
        """Set-up is a span tree of the process's Telemetry (PR 38): a root
        ``learner/build`` (``iter="setup"``) over construction, the build-
        time compiles under its children, then the first dispatch of the
        step program (``learner/train_dispatch``, ``first=1``) and
        ``learner/first_ready``, one block on its outputs. A Learner handed
        no Telemetry (through ``metrics``) builds its own, with no drain
        thread: its spans go to ``spans_player{p}.jsonl`` as set-up ends
        and at ``stop_background``. The first Learner of a process installs
        the compile monitor, bound to that Telemetry; the loop around it
        takes it over (``compile_monitor``)."""
        from r2d2_tpu.telemetry import (NULL_TELEMETRY, CompileMonitor,
                                        Telemetry, active_monitor)
        self.metrics = metrics or TrainMetrics(player_idx, cfg.runtime.save_dir,
                                               resume=bool(cfg.runtime.resume))
        self._own_telemetry = None
        if self.metrics.telemetry is NULL_TELEMETRY and cfg.telemetry.enabled:
            self._own_telemetry = Telemetry.from_config(
                cfg, name=f"learner-p{player_idx}")
            self._own_telemetry.write_spans_to(
                os.path.join(cfg.runtime.save_dir or ".",
                             f"spans_player{player_idx}.jsonl"),
                append=bool(cfg.runtime.resume))
            self.metrics.set_telemetry(self._own_telemetry)
        self.compile_monitor = None
        if (cfg.telemetry.enabled and cfg.telemetry.compile_enabled
                and active_monitor() is None):
            self.compile_monitor = CompileMonitor(self.tele).install()
        self._first_dispatch = True
        with self.tele.stage("learner/build", iter="setup"):
            self._build(cfg, net, player_idx, seed)

    def _build(self, cfg: Config, net: NetworkApply, player_idx: int,
               seed: Optional[int]) -> None:
        tele = self.tele
        self.cfg = cfg
        self.net = net
        self.player_idx = player_idx
        self.spec = ReplaySpec.from_config(cfg)
        seed = cfg.runtime.seed if seed is None else seed
        key = jax.random.PRNGKey(seed + 1000 * player_idx)

        with tele.stage("learner/create_train_state"):
            self.train_state = create_train_state(key, net, cfg.optim)
        with tele.stage("learner/apply_restore"):
            self.train_state, resumed_env_steps = apply_restore(
                cfg.runtime, self.train_state)
        self.host_mode = cfg.replay.placement == "host"
        self.mesh = None
        # learning-dynamics diagnostics (ISSUE 5): a LearningDiag fuses
        # the diagnostic outputs into the jitted step; None (the
        # telemetry.learning_enabled kill switch) compiles the
        # pre-diagnostics program byte-for-byte. The aggregator holds the
        # per-dispatch device outputs and builds the periodic record's
        # 'learning' block (and owns the NaN forensics) at flush.
        from r2d2_tpu.telemetry.learning import (LearningAggregator,
                                                 LearningDiag)
        self._diag = LearningDiag.from_config(cfg)
        self._learning_agg = (LearningAggregator(
            player_idx, cfg.runtime.save_dir, cfg.telemetry.nan_policy,
            cfg.optim.lr) if self._diag is not None else None)
        # the routing counters of a core with experts (record block 'moe')
        from r2d2_tpu.telemetry.learning import MoeAggregator
        self._moe_agg = (MoeAggregator(cfg.network.core)
                         if net.core.routes_experts else None)
        # the counters of a core that counts something of its own (its
        # loss among them), made into record blocks by the core
        from r2d2_tpu.telemetry.learning import CoreAggregator
        self._core_agg = (CoreAggregator(net.core.summarise)
                          if hasattr(net.core, "summarise") else None)
        # replay & data-pathology pillar (ISSUE 10): same spec/aggregator
        # pattern — a ReplayDiag fuses sum-tree health, sample-lifetime
        # accounting and lane composition into the step; None (the
        # telemetry.replay_diag_enabled kill switch, mirrored by
        # spec.replay_diag for the ring-state allocation) compiles the
        # pre-pillar program and the record carries no replay_diag block.
        from r2d2_tpu.telemetry.replaydiag import (ReplayDiag,
                                                   ReplayDiagAggregator)
        self._rdiag = ReplayDiag.from_config(cfg)
        self._replay_agg = (ReplayDiagAggregator(self._rdiag.lanes)
                            if self._rdiag is not None else None)
        # wired by the orchestrator alongside `publish`: () -> the weight
        # service's current publish count — the learner half of the
        # sample-age clock (None = ages reported as unknown)
        self.weight_version_fn: Optional[Callable[[], int]] = None
        # -- disaggregated replay service (ISSUE 15) --
        # fleet.replay_shards >= 1 routes ingestion through N
        # addressable device shards (fleet/replay_service.py: the
        # dp-sharded rings generalized, plus the host-RAM spill tier)
        # and trains through the EXTERNAL-BATCH step on service-sampled
        # prioritized batches — the consumer draws from the service
        # instead of fusing sample+train over one in-mesh ring, which is
        # what lets producers/consumers/storage stop sharing a program.
        self.service = None
        self._exp_trace = None
        if cfg.fleet.replay_shards >= 1 and not self.host_mode:
            import dataclasses

            from r2d2_tpu.fleet.replay_service import ReplayService
            # equal device-ring slices per shard; the fused-path replay
            # diagnostics state stays off (the service's own telemetry
            # block carries shard/spill health; the external-batch
            # step's batch-side rdiag — lane composition — still runs)
            shard_spec = dataclasses.replace(
                self.spec,
                num_blocks=self.spec.num_blocks // cfg.fleet.replay_shards,
                replay_diag=False)
            self.service = ReplayService(
                shard_spec, cfg.fleet.replay_shards,
                spill_blocks=cfg.fleet.spill_blocks,
                route=cfg.fleet.replay_route,
                promote_per_sample=cfg.fleet.spill_promote_per_sample,
                ingest_batch_blocks=cfg.fleet.ingest_batch_blocks,
                spill_prefetch=cfg.fleet.spill_prefetch,
                tier_stats=(cfg.telemetry.enabled
                            and cfg.telemetry.replay_tiers_enabled))
            # experience lineage (ISSUE 19): sampled-batch stamps looked
            # up from the service's ring mirrors feed the record's
            # 'trace' block (env-step->gradient latency)
            if cfg.telemetry.enabled and cfg.telemetry.tracing_enabled:
                from r2d2_tpu.telemetry.tracing import ExperienceTrace
                self._exp_trace = ExperienceTrace(
                    cfg.telemetry.trace_sample_every)
            # service-mode sample staging (ISSUE 16): the PR-2 stager
            # treatment for the consumer side — a prefetch thread draws
            # the next per-shard batch while the train dispatch runs,
            # and priority write-backs batch per sampled shard on a
            # writeback thread (off = the synchronous PR-15 step,
            # byte-identical)
            self._svc_staging = cfg.fleet.sample_staging
            if self._svc_staging:
                self._svc_error: Optional[BaseException] = None
                self._svc_prefetch_q: queue_mod.Queue = queue_mod.Queue(
                    maxsize=2)
                self._svc_writeback_q: queue_mod.Queue = queue_mod.Queue(
                    maxsize=64)
                self._svc_stop = threading.Event()
                self._svc_threads: list = []
            # one service-sampled batch per step — same degradation the
            # host branch warns about, made equally loud here
            if cfg.runtime.steps_per_dispatch > 1:
                import logging
                logging.getLogger(__name__).warning(
                    "fleet.replay_shards: ignoring "
                    "runtime.steps_per_dispatch=%d (the service-routed "
                    "learner trains one service-sampled batch per step)",
                    cfg.runtime.steps_per_dispatch)
            self._k = 1
            self.replay_state = None
            self._step_fn = make_external_batch_step(
                net, shard_spec, cfg.optim, cfg.network.use_double,
                diag=self._diag, rdiag=self._rdiag)
            self._service_key = jax.random.PRNGKey(seed + 777
                                                   + 1000 * player_idx)
        elif self.host_mode:
            # dispatch amortization needs the device-resident replay (each
            # host-mode step consumes one host-sampled batch); degrade
            # rather than reject. Warn only for an explicitly-set value > 1
            # (the -1 auto default resolves silently). (warning, not info:
            # nothing configures logging, so only the stdlib lastResort
            # handler [WARNING+] makes this visible)
            import logging
            if cfg.runtime.steps_per_dispatch > 1:
                logging.getLogger(__name__).warning(
                    "replay.placement='host': ignoring "
                    "runtime.steps_per_dispatch=%d (host mode trains one "
                    "host-sampled batch per step)",
                    cfg.runtime.steps_per_dispatch)
            self._k = 1
            self._bg_error: Optional[BaseException] = None
            self.replay_state = None
            self.host_replay = HostReplay(self.spec, seed=seed)
            if cfg.mesh.mp > 1:
                # tensor parallelism (parallel/tensor_parallel.py): the
                # SAME external-batch step with params feature-sharded
                # over 'mp' and the batch over 'dp' — GSPMD inserts the
                # collectives. place_batch runs in the prefetch thread.
                from r2d2_tpu.parallel import make_mesh
                from r2d2_tpu.parallel.tensor_parallel import (
                    make_tp_external_batch_step)
                tp_mesh = make_mesh(cfg.mesh)
                self._step_fn, place_state, self._place_batch = (
                    make_tp_external_batch_step(
                        net, self.spec, cfg.optim, cfg.network.use_double,
                        tp_mesh, diag=self._diag, rdiag=self._rdiag))
                self.train_state = place_state(self.train_state)
            else:
                self._step_fn = make_external_batch_step(
                    net, self.spec, cfg.optim, cfg.network.use_double,
                    diag=self._diag, rdiag=self._rdiag)
                self._place_batch = jax.device_put
            self._prefetch_q: queue_mod.Queue = queue_mod.Queue(
                maxsize=max(1, cfg.runtime.prefetch_batches))
            self._writeback_q: queue_mod.Queue = queue_mod.Queue(maxsize=64)
            self._bg_stop = threading.Event()
            self._bg_threads: list = []
        else:
            dp = cfg.mesh.resolved_dp(len(jax.devices()))
            self._k = cfg.runtime.resolved_steps_per_dispatch()
            if dp > 1 or cfg.mesh.mp > 1:
                # dp-sharded learner (SURVEY §5.8): replay sharded
                # chip-per-shard, per-shard prioritized sampling, gradient
                # pmean over ICI. Blocks round-robin across shards.
                # mp > 1 composes: the same fused step runs manual over dp
                # and GSPMD-auto over mp, with the TrainState's wide
                # feature dims sharded over mp (tensor_parallel) and replay
                # mp-replicated — model sharding stays a mesh-axis change
                # on the device-replay flagship path (VERDICT r3 #4).
                from r2d2_tpu.parallel import (
                    make_mesh, make_sharded_learner_step,
                    make_sharded_replay_add, sharded_replay_init)
                self.mesh = make_mesh(cfg.mesh)
                self._dp = self.mesh.shape["dp"]
                self._next_shard = 0
                if cfg.mesh.mp > 1:
                    from r2d2_tpu.parallel.tensor_parallel import (
                        state_shardings)
                    self.train_state = jax.device_put(
                        self.train_state,
                        state_shardings(self.train_state, self.mesh))
                with tele.stage("learner/replay_init"):
                    self.replay_state = sharded_replay_init(self.spec,
                                                            self.mesh)
                self._step_fn = make_sharded_learner_step(
                    net, self.spec, cfg.optim, cfg.network.use_double,
                    self.mesh, steps_per_dispatch=self._k, diag=self._diag,
                    rdiag=self._rdiag)
                self._sharded_add = make_sharded_replay_add(
                    self.spec, self.mesh)
            else:
                with tele.stage("learner/replay_init"):
                    self.replay_state = replay_init(self.spec)
                if self._k > 1:
                    self._step_fn = make_multi_learner_step(
                        net, self.spec, cfg.optim, cfg.network.use_double,
                        self._k, diag=self._diag, rdiag=self._rdiag)
                else:
                    self._step_fn = make_learner_step(
                        net, self.spec, cfg.optim, cfg.network.use_double,
                        diag=self._diag, rdiag=self._rdiag)

        # what a sequence's stored state row holds (record block 'core', on
        # the run's first record)
        from r2d2_tpu.models.cores import state_block
        self.metrics.set_core(state_block(
            net.config, cfg.replay.batch_size, cfg.sequence.seq_len))
        # the dtypes the target is held in (record block 'target', first
        # record): what the plan narrowed, and the bytes it holds
        from r2d2_tpu.learner.target import target_block
        self.metrics.set_target(target_block(self.train_state.target_params))
        if self._exp_trace is not None:
            # experience lineage (ISSUE 19): the record's 'trace' block
            self.metrics.set_tracing(self._exp_trace.interval_block)
        self.publish: Optional[Callable] = None   # wired by orchestrator

        # Ring accounting: ONE RingAccountant per replay (VERDICT r2 weak
        # #5). Host placement shares HostReplay's own instance; device
        # placement keeps a host mirror of the compiled pointer in
        # ReplayState.block_ptr — mirroring avoids a blocking device read
        # (a sync of the dispatch queue) per ingested
        # block, and replay_add advances the device pointer with the
        # identical wrap rule (asserted in tests/test_replay.py).
        from r2d2_tpu.replay.structs import RingAccountant
        if self.service is not None:
            # the service IS the accounting facade: per-shard
            # RingAccountants advance inside add_block, and the facade's
            # buffer_steps/total_adds/live_versions sum them — the same
            # duck-typed surface the gate/metrics/flush read
            self.ring = self.service
        elif self.host_mode:
            self.ring = self.host_replay.ring
        else:
            # round-robin feeding visits the dp shards' ring slots in a
            # single global order — one accountant over dp * num_blocks
            # slots mirrors every shard's compiled pointer exactly
            self.ring = RingAccountant(
                self.spec.num_blocks * (self._dp if self.mesh else 1))
        self.env_steps = resumed_env_steps
        self._host_step = int(self.train_state.step)
        # last step a checkpoint covered: save_final() is a no-op unless
        # training advanced past it (nothing new to save at construction,
        # resumed or fresh)
        self._last_saved_step = self._host_step
        # Rate-limiter baselines: the collect:learn budget is measured from
        # THIS process's starting point, not from step/env-step zero — a
        # resumed run restores large cumulative counters while its replay
        # ring restarts empty, and an absolute comparison would pause
        # ingestion forever (training could never start).
        self._ratio_env_base = self.env_steps
        self._ratio_step_base = self._host_step
        self._pending_losses: list = []   # device scalars, flushed lazily
        self._pending_syncs: list = []    # the steps' 0/1 target_sync

        # -- batched + pipelined ingestion (ISSUE 2) --
        # K > 1 (device placement only): a background stager thread drains
        # the feeder queue in stacked K-block batches and launches their
        # host→device transfer while the current train dispatch runs; the
        # main thread commits staged batches (ONE replay_add_many dispatch
        # per batch) between train dispatches, where ring/rate-limiter
        # accounting happens — the same interleaving point the per-block
        # path uses, so the fused step's priority write-back stays
        # race-free. Host placement keeps K = 1: its ingest is a numpy
        # copy, not a device dispatch.
        # service mode keeps the per-block drain (K = 1): spill
        # retention shadows each block's host page at add time, and the
        # service's routing is per-block by definition
        self._ingest_k = (1 if (self.host_mode or self.service is not None)
                          else
                          min(cfg.replay.resolved_ingest_batch_blocks(),
                              self.spec.num_blocks))
        self._sharded_add_many = None
        if self.mesh is not None and self._ingest_k > 1:
            from r2d2_tpu.parallel import make_sharded_replay_add_many
            self._sharded_add_many = make_sharded_replay_add_many(
                self.spec, self.mesh)
        self._stager: Optional[threading.Thread] = None
        # AOT add_many executables per batch size, compiled in the STAGER
        # thread before a batch is enqueued, so a new batch size never
        # stalls the commit path with an XLA compile — on the dp-sharded
        # path too (batched ingestion auto-engages on TPU, where a lazy
        # ~1.5 s mid-run compile measurably parks the actors). Replay
        # shape/sharding avals are captured now, before any donation
        # invalidates the live arrays.
        self._add_many_cache: dict = {}
        if self.replay_state is None:
            self._replay_shapes = None
        elif self.mesh is not None:
            # sharding-annotated avals: lowering a shard_map program from
            # plain ShapeDtypeStructs would let the compiler pick layouts
            # the committed per-shard arrays then fail to match
            self._replay_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding),
                self.replay_state)
        else:
            self._replay_shapes = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self.replay_state)
        self._ingest_stop = threading.Event()
        # buffer attribution (ISSUE 7): register this player's device
        # footprints with the process registry so the periodic record's
        # resources block names owners instead of one opaque HBM total.
        # Names are re-registered on a rebuilt Learner (same-name
        # overwrite), and registration is read-side-only — with
        # resources off nothing ever reads it, so the record schema
        # stays byte-identical.
        if cfg.telemetry.enabled and cfg.telemetry.resources_enabled:
            from r2d2_tpu.telemetry.resources import (clear_player_buffers,
                                                      pytree_nbytes,
                                                      register_buffer)
            # drop the previous incarnation's entries first: same-name
            # overwrite doesn't cover components the rebuilt stack
            # LACKS (e.g. an earlier run's stager staging window when
            # this run drains per-block)
            clear_player_buffers(player_idx)
            register_buffer(f"p{player_idx}/train_state",
                            pytree_nbytes(self.train_state))
            if self.replay_state is not None:
                register_buffer(f"p{player_idx}/replay_ring",
                                pytree_nbytes(self.replay_state))
            if self.service is not None:
                register_buffer(f"p{player_idx}/replay_service",
                                self.service.device_bytes)
        # depth 2: one batch committing + one transfer in flight bounds
        # staged memory at 2K blocks while keeping the pipeline full
        self._ingest_q: queue_mod.Queue = queue_mod.Queue(maxsize=2)
        # one-shot 'costs' record block (ISSUE 9), latched at first flush
        self._costs_attached = False
        self._ingest_error: Optional[BaseException] = None
        self._staged_env_steps = 0        # popped but not yet committed
        self._staged_blocks = 0
        self._staged_lock = threading.Lock()
        self._pause_started: Optional[float] = None

        # -- crash-recovery plane (ISSUE 18) --
        # runtime.snapshot_interval > 0: a background SnapshotWriter
        # persists a consistent cut of the replay plane (service shards
        # or the in-mesh state) at interval boundaries; on resume with
        # runtime.restore_replay the newest committed cut is loaded back
        # bit-exactly BEFORE training continues.
        self._snap_writer = None
        self._restores = 0
        self._restored_blocks = 0
        self._snap_capture_s = 0.0
        # adds committed at the last snapshot — lost_blocks_est is the
        # gauge of what a crash RIGHT NOW would cost (bounded by the
        # snapshot interval; the kill drill measures it for real)
        self._snap_adds = 0
        if cfg.runtime.snapshot_interval > 0 and not self.host_mode:
            from r2d2_tpu.replay.snapshot import SnapshotWriter
            self._snap_writer = SnapshotWriter(cfg.runtime.save_dir,
                                               player_idx)
        if (cfg.runtime.resume and cfg.runtime.restore_replay
                and not self.host_mode):
            self._restore_replay_snapshot()

    def _restore_replay_snapshot(self) -> None:
        """Resume plane c (ISSUE 18): reload the newest committed replay
        snapshot next to the checkpoint — every shard's ring/tree/stamps
        /spill pages plus the service sample key, so the restored
        learner's next sample (and next-step loss) equals the
        uninterrupted twin's. Silently a no-op when no snapshot exists
        (a pre-PR18 resume restores params/opt-state only)."""
        from r2d2_tpu.replay.snapshot import load_snapshot, restore_plain
        snap = load_snapshot(self.cfg.runtime.save_dir, self.player_idx)
        if snap is None:
            return
        if self.service is not None:
            self.service.restore_state(snap)
            key = snap["extra"].get("service_key")
            if key is not None:
                self._service_key = jax.device_put(
                    np.asarray(key, np.uint32))
        else:
            self.replay_state = restore_plain(
                self.spec, self.replay_state, self.ring, snap)
            if self.mesh is not None:
                self._next_shard = int(
                    snap["extra"].get("next_shard", 0))
            key = snap["extra"].get("train_key")
            if key is not None:
                cur = self.train_state.key
                self.train_state = self.train_state.replace(
                    key=jax.device_put(np.asarray(key, np.uint32),
                                       cur.sharding))
        self._restores = 1
        self._restored_blocks = sum(s["ring"]["total_adds"]
                                    for s in snap["shards"])
        self._snap_adds = self.ring.total_adds
        env_steps = snap["extra"].get("env_steps")
        if env_steps is not None:
            # the checkpoint's env_steps counter stopped at its save;
            # the snapshot's cut is newer (or equal) — adopt the later
            self.env_steps = max(self.env_steps, int(env_steps))
        self.metrics.set_buffer_size(self.ring.buffer_steps)

    @property
    def tele(self):
        """The process Telemetry, read through metrics DYNAMICALLY: the
        orchestrator attaches it to TrainMetrics (set_telemetry), possibly
        after this Learner was constructed; a stale binding here would
        silently observe into the NULL sink forever."""
        return self.metrics.telemetry

    # -- ingestion --

    def ingest(self, block: Block) -> None:
        """Ring-write of one actor block (ref worker.py:85-120) — jitted on
        device, or into the host replay. Accounting goes through the single
        RingAccountant so the device path never blocks on a pointer read."""
        learning = int(np.asarray(block.learning_steps).sum())
        if self.host_mode:
            self.host_replay.add(block)   # advances the shared accountant
        elif self.service is not None:
            # routed by shard key; the per-shard accountants (and the
            # spill-tier demotion of whatever the ring-write overwrote)
            # advance inside the service
            self.service.add_block(block)
        else:
            # strip the lineage leaf before the jitted add (the in-mesh
            # programs are compiled traceless — the service path's AOT
            # discipline); the stamp lands in the accountant mirror
            trace = block.trace_ms
            if trace is not None:
                trace = int(np.asarray(trace))
                block = block.replace(trace_ms=None)
            if self.mesh is not None:
                self.replay_state = self._sharded_add(
                    self.replay_state, block, self._next_shard)
                self._next_shard = (self._next_shard + 1) % self._dp
            else:
                self.replay_state = replay_add(
                    self.spec, self.replay_state, block)
            wv = int(np.asarray(block.weight_version))
            if trace is None:
                self.ring.advance(learning, wv)
            else:
                from r2d2_tpu.telemetry.tracing import now_ms
                self.ring.advance(learning, wv, trace_ms=trace,
                                  ingest_ms=(now_ms() if trace >= 0
                                             else -1))
        self.env_steps += learning
        ret = float(np.asarray(block.sum_reward))
        self.metrics.on_block(learning, None if np.isnan(ret) else ret)
        self.metrics.set_buffer_size(self.ring.buffer_steps)

    @property
    def ingestion_paused(self) -> bool:
        """Rate limiter (replay.max_env_steps_per_train_step): true when
        data collection is far enough ahead of learning that ingestion
        should wait. Leaving blocks in the bounded feeder queue
        back-pressures the actors (they park in put()), pinning the
        collect:learn ratio independently of host scheduling."""
        ratio = self.cfg.replay.max_env_steps_per_train_step
        if ratio <= 0:
            return False
        # Staged-but-uncommitted blocks count as collected EVERYWHERE in
        # this check: they were already popped from the feeder and WILL
        # commit at the next drain regardless of training, so (a) counting
        # them toward the training-gate fill cannot livelock, and (b) NOT
        # counting them would let the stager pull far past the budget
        # while commits lag behind pops (the gate would read open forever).
        with self._staged_lock:
            staged_steps = self._staged_env_steps
            staged_blocks = self._staged_blocks
        # Never pause while the training gate is closed: ingestion is the
        # only thing that can open it (learning_starts fill, and under a dp
        # mesh one block per shard), so pausing there would livelock —
        # drain() returns 0 forever while ready waits for a block that can
        # never arrive.
        if not self._gate_open(staged_blocks, staged_steps):
            return False
        budget = (self.cfg.replay.learning_starts
                  + ratio * max(self._host_step - self._ratio_step_base, 1))
        return (self.env_steps + staged_steps
                - self._ratio_env_base) >= budget

    def _note_pause(self, paused: bool) -> None:
        """Rate-limiter pause-time accounting (whichever thread owns the
        feeder-pop loop calls this: the main thread on the legacy path, the
        stager on the pipelined path)."""
        if paused:
            if self._pause_started is None:
                self._pause_started = time.time()
        elif self._pause_started is not None:
            self.metrics.on_ingest_pause(time.time() - self._pause_started)
            self._pause_started = None

    def drain(self, queue, max_items: Optional[int] = None) -> int:
        """Move actor blocks from the feeder queue into the replay. Legacy
        path (ingest_batch_blocks = 1): pop + ingest synchronously, up to
        ``max_items`` (default replay.drain_max_blocks — one knob for this
        loop and the orchestrator's warm-up loop). Pipelined path (K > 1):
        commit whatever stacked batches the stager has staged; the stager
        drains the feeder in K-block bursts on its own thread."""
        if self._ingest_k > 1:
            return self._drain_pipelined(queue)
        if max_items is None:
            max_items = self.cfg.replay.drain_max_blocks
        paused = self.ingestion_paused
        self._note_pause(paused)
        if paused:
            return 0
        t0 = time.time()
        blocks = queue.drain(max_items)
        t_get = time.time()
        if (self.service is not None and self.service.ingest_k > 1
                and len(blocks) > 1):
            # grouped service ingest (ISSUE 16): one routed add_blocks
            # call commits the whole drain through per-shard
            # replay_add_many chunks — bit-identical contents, one
            # dispatch per chunk instead of per block. The
            # orchestrator's warm-up loop reaches this through the same
            # drain(), so bring-up bursts get the grouped plane too.
            self._ingest_group(blocks)
        else:
            for blk in blocks:
                self.ingest(blk)
        if self.service is not None and self.service.ingest_k > 1:
            # producer-side depth left behind this drain — the
            # ingest_backlog alert's gauge (qsize -1 = unknown -> 0)
            self.service.note_backlog(queue.qsize())
        if blocks:
            t1 = time.time()
            self.metrics.on_ingest_drain(len(blocks), t1 - t0)
            tele = self.tele
            tele.observe("ingest/ring_get", t_get - t0)
            tele.observe("ingest/commit", t1 - t_get)
            tele.record_span("ingest/commit", t0, t1,
                             {"blocks": len(blocks)})
        return len(blocks)

    def _ingest_group(self, blocks: List[Block]) -> None:
        """Grouped service commit with the same per-block accounting the
        sequential :meth:`ingest` loop performs (env steps, episode
        returns, buffer gauge) — the ring facade's totals advance inside
        the service exactly as K sequential adds would."""
        self.service.add_blocks(blocks)
        for block in blocks:
            learning = int(np.asarray(block.learning_steps).sum())
            self.env_steps += learning
            ret = float(np.asarray(block.sum_reward))
            self.metrics.on_block(learning, None if np.isnan(ret) else ret)
        self.metrics.set_buffer_size(self.ring.buffer_steps)

    # -- pipelined ingestion (stager thread + commit) --

    def _drain_pipelined(self, queue) -> int:
        if self._ingest_error is not None:
            raise RuntimeError(
                "ingest stager thread died") from self._ingest_error
        if self._stager is None or not self._stager.is_alive():
            self._start_stager(queue)
        committed = 0
        # same per-drain block cap as the legacy path: a producer that
        # outpaces the learner must not starve the train loop by keeping
        # this commit loop spinning
        while committed < self.cfg.replay.drain_max_blocks:
            try:
                staged, metas, t_pop = self._ingest_q.get_nowait()
            except queue_mod.Empty:
                break
            committed += self._commit_staged(staged, metas, t_pop)
        self.metrics.set_ingest_queue_depth(self._ingest_q.qsize())
        return committed

    def _commit_staged(self, staged: Block, metas, t_pop: float) -> int:
        """ONE device dispatch ring-writes the whole stacked batch; ring
        pointer, rate-limiter env-step base, and metrics account here — at
        commit time, on the main thread — so back-pressure and the
        device/host pointer mirror keep the per-block path's semantics."""
        k = len(metas)
        t_commit = time.time()
        # the stager AOT-compiled this batch size before enqueueing
        exe = self._add_many_cache.get(k)
        if self.mesh is not None:
            if exe is not None:
                self.replay_state = exe(self.replay_state, staged,
                                        np.int32(self._next_shard))
            else:   # defensive fallback: jit-call path (compiles here)
                self.replay_state = self._sharded_add_many(
                    self.replay_state, staged, self._next_shard)
            self._next_shard = (self._next_shard + k) % self._dp
        else:
            if exe is not None:
                self.replay_state = exe(self.replay_state, staged)
            else:
                self.replay_state = replay_add_many(
                    self.spec, self.replay_state, staged)
        total = 0
        for learning, ret, wv, trace in metas:
            if trace is None:
                self.ring.advance(learning, wv)
            else:
                from r2d2_tpu.telemetry.tracing import now_ms
                self.ring.advance(learning, wv, trace_ms=trace,
                                  ingest_ms=(now_ms() if trace >= 0
                                             else -1))
            self.metrics.on_block(learning, ret)
            total += learning
        self.env_steps += total
        with self._staged_lock:
            self._staged_env_steps -= total
            self._staged_blocks -= k
        self.metrics.set_buffer_size(self.ring.buffer_steps)
        now = time.time()
        self.metrics.on_ingest_drain(k, now - t_pop)
        self.tele.observe("ingest/commit", now - t_commit)
        self.tele.record_span("ingest/commit", t_commit, now, {"blocks": k})
        return k

    def _compile_add_many(self, kb: int):
        """Lower + AOT-compile the add_many executable for batch size
        ``kb`` — the ONE lowering recipe (stager thread only), shared by
        the startup precompile and the odd-size fallback, deriving block
        avals from the authoritative record layout (empty_block_np)."""
        from r2d2_tpu.replay.structs import empty_block_np
        proto = empty_block_np(self.spec)
        blocks = Block(**{
            name: jax.ShapeDtypeStruct((kb,) + arr.shape, arr.dtype)
            for name, arr in proto.items()})
        if self.mesh is not None:
            shard = jax.ShapeDtypeStruct((), np.int32)
            return self._sharded_add_many.lower(
                self._replay_shapes, blocks, shard).compile()
        return replay_add_many.lower(
            self.spec, self._replay_shapes, blocks).compile()

    def _aot_bucket_sizes(self) -> list:
        """The add_many batch sizes the stager drains — every power-of-two
        bucket up to K PLUS K itself: a non-pow2 ingest_batch_blocks is
        the steady-state drain size under load and would otherwise hit
        the lazy mid-run compile exactly when load first reaches K. One
        recipe shared by the startup precompile and the coverage report
        (telemetry/compile.py), so the report can never drift from what
        the precompile actually targets."""
        sizes = []
        kb = 1
        while kb < self._ingest_k:
            sizes.append(kb)
            kb *= 2
        sizes.append(self._ingest_k)
        return sizes

    def aot_coverage(self) -> Optional[dict]:
        """AOT-precompile coverage of the stager's add_many buckets
        (ISSUE 7): expected bucket sizes vs actually-compiled executables
        — a non-empty ``missing`` list means a mid-run lazy compile is
        still possible, the exact hazard the precompile exists to
        prevent. None on the legacy per-block path (no stager)."""
        if self._ingest_k <= 1:
            return None
        from r2d2_tpu.telemetry.compile import aot_coverage
        return aot_coverage(self._aot_bucket_sizes(),
                            list(self._add_many_cache))

    def _precompile_add_many(self) -> None:
        """AOT-compile add_many for every stager bucket size — runs once
        in the stager thread at startup, i.e. during the warm-up fill, so
        a ~1.5 s XLA compile never stalls mid-run ingestion (measured: a
        lazy mid-run compile backs the feeder up enough to park the
        actors)."""
        for kb in self._aot_bucket_sizes():
            if self._ingest_stop.is_set():
                break
            if kb not in self._add_many_cache:
                self._add_many_cache[kb] = self._compile_add_many(kb)

    def _start_stager(self, queue) -> None:
        cfg = self.cfg
        if cfg.telemetry.enabled and cfg.telemetry.resources_enabled:
            # staging-window attribution (ISSUE 7): the pipeline holds at
            # most 2 staged batches of K blocks (queue depth 2) — the
            # bound, not a live gauge; registered once at stager start
            from r2d2_tpu.replay.structs import empty_block_np
            from r2d2_tpu.telemetry.resources import register_buffer
            block_bytes = sum(a.nbytes
                              for a in empty_block_np(self.spec).values())
            register_buffer(f"p{self.player_idx}/ingest_staging",
                            2 * self._ingest_k * block_bytes)

        def stage_loop():
            try:
                self._precompile_add_many()
                while not self._ingest_stop.is_set():
                    paused = self.ingestion_paused
                    self._note_pause(paused)
                    if paused:
                        time.sleep(0.002)
                        continue
                    t_pop = time.time()
                    # Drain what is queued NOW, rounded down to a power-of-
                    # two bucket (bounds the distinct compiled add_many
                    # batch sizes at log2(K)+1) — never wait for a full
                    # batch: an explicit accumulation window throttles
                    # ingestion below the offered load and back-pressures
                    # the actors for nothing. Batching emerges under load
                    # on its own — while the bounded staging queue is full,
                    # the feeder accumulates and the next drain sees a
                    # bigger bucket.
                    want = self._ingest_k
                    avail = queue.qsize()
                    if avail == 0:
                        time.sleep(0.001)
                        continue
                    if 0 < avail < want:
                        want = 1 << (avail.bit_length() - 1)
                    stacked, k = queue.drain_stacked(want)
                    if k == 0:
                        time.sleep(0.001)
                        continue
                    self.tele.observe("ingest/ring_get",
                                      time.time() - t_pop)
                    if k not in self._add_many_cache:
                        # odd size (qsize-less backend): compile HERE
                        # (stager thread), never at commit
                        self._add_many_cache[k] = self._compile_add_many(k)
                    trace = stacked.trace_ms
                    if trace is not None:
                        # strip before staging — the AOT add_many avals
                        # are traceless (the per-block path's discipline);
                        # stamps mirror into the accountant at commit
                        trace = np.asarray(trace, np.int64)
                        stacked = stacked.replace(trace_ms=None)
                    learning = np.asarray(stacked.learning_steps)\
                        .sum(axis=1).astype(np.int64)
                    rets = np.asarray(stacked.sum_reward, np.float32)
                    wvs = np.asarray(stacked.weight_version, np.int64)
                    metas = [
                        (int(learning[i]),
                         None if np.isnan(rets[i]) else float(rets[i]),
                         int(wvs[i]),
                         int(trace[i]) if trace is not None else None)
                        for i in range(k)]
                    with self._staged_lock:
                        self._staged_env_steps += int(learning.sum())
                        self._staged_blocks += k
                    # starts the host→device transfer; it proceeds while
                    # the main thread's train dispatch runs (replicated
                    # across the mesh on the dp-sharded path, matching the
                    # AOT executable's P() block avals)
                    if self.mesh is not None:
                        from jax.sharding import (
                            NamedSharding, PartitionSpec)
                        staged = jax.device_put(
                            stacked, NamedSharding(self.mesh,
                                                   PartitionSpec()))
                    else:
                        staged = jax.device_put(stacked)
                    now = time.time()
                    # stage = pop + stack + host->device launch; the wait
                    # for a staging-queue slot below is back-pressure, not
                    # staging work, and stays out of the histogram
                    self.tele.observe("ingest/stage", now - t_pop)
                    self.tele.record_span("ingest/stage", t_pop, now,
                                          {"blocks": k})
                    while not self._ingest_stop.is_set():
                        try:
                            self._ingest_q.put((staged, metas, t_pop),
                                               timeout=0.2)
                            break
                        except queue_mod.Full:
                            continue
            except BaseException as e:   # surfaced by _drain_pipelined
                self._ingest_error = e
                raise

        self._stager = threading.Thread(
            target=stage_loop, daemon=True,
            name=f"learner-ingest-stager-p{self.player_idx}")
        self._stager.start()

    def _gate_open(self, extra_blocks: int = 0, extra_steps: int = 0) -> bool:
        """The training-gate conditions — ONE implementation shared by
        ``ready`` (committed blocks only) and the rate limiter's pause
        check (committed + staged), so the two cannot drift apart and
        re-open the pause-before-ready livelock."""
        if (self.mesh is not None
                and self.ring.total_adds + extra_blocks < self._dp):
            return False
        if self.service is not None and not self.service.all_shards_nonempty:
            # every service shard must hold a block before sampling (an
            # empty tree yields NaN importance weights — the dp mesh's
            # same precondition, enforced per addressable shard)
            return False
        return (self.ring.buffer_steps + extra_steps
                >= self.cfg.replay.learning_starts)

    @property
    def ready(self) -> bool:
        """Training gate (ref worker.py:214-218, config.learning_starts).
        Under a dp mesh every shard must also hold at least one block —
        per-shard prioritized sampling over an empty tree yields NaN
        importance weights."""
        return self._gate_open()

    @property
    def training_steps(self) -> int:
        """Host-mirrored step counter (no device sync)."""
        return self._host_step

    # -- host-placement pipeline (ref worker.py:292-306,368) --

    def _start_background(self) -> None:
        def prefetch():
            try:
                while not self._bg_stop.is_set():
                    t0 = time.time()
                    batch, snapshot = self.host_replay.sample()
                    dev = self._place_batch(batch)
                    self.tele.observe("learner/sample", time.time() - t0)
                    while not self._bg_stop.is_set():
                        try:
                            self._prefetch_q.put((dev, snapshot), timeout=0.5)
                            break
                        except queue_mod.Full:
                            continue
            except BaseException as e:  # surfaced by _host_step_once
                self._bg_error = e
                raise

        def writeback():
            try:
                while not self._bg_stop.is_set():
                    try:
                        idxes, prios, snapshot = self._writeback_q.get(timeout=0.5)
                    except queue_mod.Empty:
                        continue
                    t0 = time.time()
                    self.host_replay.update_priorities(
                        np.asarray(idxes), np.asarray(jax.device_get(prios)),
                        snapshot)
                    self.tele.observe("learner/priority_writeback",
                                      time.time() - t0)
            except BaseException as e:
                self._bg_error = e
                raise

        for fn, name in ((prefetch, "prefetch"), (writeback, "prio-writeback")):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"learner-{name}-p{self.player_idx}")
            t.start()
            self._bg_threads.append(t)

    def stop_background(self, join_timeout: float = 10.0) -> None:
        try:
            self._stop_threads(join_timeout)
        finally:
            if self._own_telemetry is not None:
                self._own_telemetry.flush()
            if self.compile_monitor is not None:
                self.compile_monitor.uninstall()

    def _stop_threads(self, join_timeout: float) -> None:
        stuck = []
        if self._snap_writer is not None:
            # drain + stop the snapshot writer first: a queued cut still
            # writing must land (it is newer than anything on disk)
            self._snap_writer.stop(join_timeout)
        if self._stager is not None:
            # drain the staging queue so a stager parked in a full-queue
            # put can observe the stop event; staged-but-uncommitted
            # blocks are dropped (shutdown only)
            self._ingest_stop.set()
            deadline = time.time() + join_timeout
            while self._stager.is_alive() and time.time() < deadline:
                try:
                    self._ingest_q.get_nowait()
                except queue_mod.Empty:
                    pass
                self._stager.join(timeout=0.1)
            if self._stager.is_alive():
                stuck.append(self._stager.name)
            else:
                self._stager = None
        if self.service is not None:
            # service stager threads (ISSUE 16 sample staging) + the
            # service's own prefetch thread; both no-ops when off
            if self._svc_staging and self._svc_threads:
                self._svc_stop.set()
                for t in self._svc_threads:
                    deadline = time.time() + join_timeout
                    while t.is_alive() and time.time() < deadline:
                        try:
                            self._svc_prefetch_q.get_nowait()
                        except queue_mod.Empty:
                            pass
                        t.join(timeout=0.1)
                    if t.is_alive():
                        stuck.append(t.name)
                self._svc_threads = [t for t in self._svc_threads
                                     if t.is_alive()]
            self.service.close()
        if not self.host_mode:
            if stuck:
                import logging
                logging.getLogger(__name__).warning(
                    "learner background threads did not exit within %.1fs: "
                    "%s", join_timeout, stuck)
            return
        self._bg_stop.set()
        # Unblock a prefetch thread parked in a full-queue put by draining
        # the prefetch queue, then join; surface anything still stuck (a
        # thread blocked inside a device transfer would otherwise outlive
        # the orchestrator's close() silently).
        for t in self._bg_threads:
            deadline = time.time() + join_timeout
            while t.is_alive() and time.time() < deadline:
                try:
                    self._prefetch_q.get_nowait()
                except queue_mod.Empty:
                    pass
                t.join(timeout=0.1)
            if t.is_alive():
                stuck.append(t.name)
        self._bg_threads = [t for t in self._bg_threads if t.is_alive()]
        if stuck:
            import logging
            logging.getLogger(__name__).warning(
                "learner background threads did not exit within %.1fs: %s",
                join_timeout, stuck)

    def _host_step_once(self) -> dict:
        if not self._bg_threads:
            self._start_background()
        while True:
            try:
                batch, snapshot = self._prefetch_q.get(timeout=2.0)
                break
            except queue_mod.Empty:
                # fail loudly instead of hanging if a pipeline thread died
                if self._bg_error is not None:
                    raise RuntimeError(
                        "host-replay pipeline thread died"
                    ) from self._bg_error
                if not any(t.is_alive() for t in self._bg_threads):
                    raise RuntimeError(
                        "host-replay pipeline threads exited without error")
        self.train_state, m = self._step_fn(self.train_state, batch)
        # async priority write-back (ref worker.py:368); staleness-guarded
        try:
            self._writeback_q.put_nowait(
                (batch.idxes, m.pop("priorities"), snapshot))
        except queue_mod.Full:
            m.pop("priorities", None)   # drop under backpressure — counted
            self.metrics.on_dropped_priority_update()
        return m

    # -- service-mode step (ISSUE 15; ISSUE 16 sample staging) --

    def _start_service_stager(self) -> None:
        """fleet.sample_staging: the host-placement pipeline's shape on
        the service path — a prefetch thread draws the next prioritized
        batch (service.sample is already device-resident, so staging
        hides the sample/promotion latency, not a transfer) and a
        writeback thread applies priority updates grouped per sampled
        shard (one lock acquisition per group via
        service.update_priorities_group; each entry keeps its own
        adds-snapshot staleness guard)."""
        def prefetch():
            try:
                while not self._svc_stop.is_set():
                    self._service_key, key = jax.random.split(
                        self._service_key)
                    t0 = time.time()
                    batch, shard, snapshot = self.service.sample(key)
                    self.tele.observe("learner/sample", time.time() - t0)
                    token = None
                    if self._exp_trace is not None:
                        token = self._exp_trace.on_sample(
                            self.service.trace_lookup(
                                shard, np.asarray(batch.idxes)))
                    staged = (batch, shard, snapshot, token)
                    while not self._svc_stop.is_set():
                        try:
                            self._svc_prefetch_q.put(staged, timeout=0.5)
                            break
                        except queue_mod.Full:
                            continue
            except BaseException as e:  # surfaced by _service_step_staged
                self._svc_error = e
                raise

        def writeback():
            try:
                while not self._svc_stop.is_set():
                    try:
                        first = self._svc_writeback_q.get(timeout=0.5)
                    except queue_mod.Empty:
                        continue
                    entries = [first]
                    while True:     # batch whatever is immediately ready
                        try:
                            entries.append(self._svc_writeback_q.get_nowait())
                        except queue_mod.Empty:
                            break
                    groups: dict = {}
                    for shard, idxes, prios, snapshot in entries:
                        groups.setdefault(shard, []).append(
                            (np.asarray(idxes),
                             np.asarray(jax.device_get(prios)), snapshot))
                    t0 = time.time()
                    for shard, group in groups.items():
                        self.service.update_priorities_group(shard, group)
                    self.tele.observe("learner/priority_writeback",
                                      time.time() - t0)
            except BaseException as e:
                self._svc_error = e
                raise

        for fn, name in ((prefetch, "svc-prefetch"),
                         (writeback, "svc-writeback")):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"learner-{name}-p{self.player_idx}")
            t.start()
            self._svc_threads.append(t)

    def _service_step_staged(self) -> dict:
        if not self._svc_threads:
            self._start_service_stager()
        while True:
            try:
                batch, shard, snapshot, token = self._svc_prefetch_q.get(
                    timeout=2.0)
                break
            except queue_mod.Empty:
                # fail loudly instead of hanging if a stager thread died
                if self._svc_error is not None:
                    raise RuntimeError(
                        "service stager thread died") from self._svc_error
                if not any(t.is_alive() for t in self._svc_threads):
                    raise RuntimeError(
                        "service stager threads exited without error")
        self.train_state, m = self._step_fn(self.train_state, batch)
        if self._exp_trace is not None:
            self._exp_trace.on_train(token)
        try:
            self._svc_writeback_q.put_nowait(
                (shard, batch.idxes, m.pop("priorities"), snapshot))
        except queue_mod.Full:
            m.pop("priorities", None)   # drop under backpressure — counted
            self.metrics.on_dropped_priority_update()
        return m

    def _service_step_once(self) -> dict:
        """Disaggregated consumer loop: draw one prioritized batch from
        the service's next shard, train through the external-batch step,
        write the new priorities straight back to that shard. In-proc
        producers never interleave an add here (the single-threaded
        drain/step cadence is the same interleaving point the fused
        path relies on); SOCKET producers can, so the write-back rides
        the sample's adds-snapshot through the service's staleness
        guard (a raced batch's update is dropped and counted, never
        written onto the overwriting block). Spill promotion happens
        inside service.sample BEFORE the tree descent, keeping the
        returned idxes valid for this write-back."""
        if self._svc_staging:
            return self._service_step_staged()
        self._service_key, key = jax.random.split(self._service_key)
        t0 = time.time()
        batch, shard, snapshot = self.service.sample(key)
        self.tele.observe("learner/sample", time.time() - t0)
        token = None
        if self._exp_trace is not None:
            token = self._exp_trace.on_sample(
                self.service.trace_lookup(shard, np.asarray(batch.idxes)))
        self.train_state, m = self._step_fn(self.train_state, batch)
        if self._exp_trace is not None:
            self._exp_trace.on_train(token)
        t0 = time.time()
        # the snapshot arms the staleness guard: with socket producers
        # feeding the service concurrently, an add landing mid-step must
        # not have its fresh block's priorities clobbered by this batch
        self.service.update_priorities(shard, batch.idxes,
                                       m.pop("priorities"),
                                       adds_snapshot=snapshot)
        self.tele.observe("learner/priority_writeback", time.time() - t0)
        return m

    # -- training --

    def step(self) -> dict:
        """One device dispatch = ``steps_per_dispatch`` fused steps. Never
        blocks on the device: metrics stay device arrays until
        flush_metrics() (called at log time); the step counter is
        host-mirrored. Publish/checkpoint fire when their interval boundary
        falls inside the dispatched step range."""
        tele = self.tele
        with tele.stage("learner/step"):
            prev = self._host_step
            # host-side dispatch cost (the device executes
            # asynchronously; device occupancy is what xprof captures
            # measure)
            first = {"first": 1} if self._first_dispatch else {}
            with tele.stage("learner/train_dispatch", k=self._k, step=prev,
                            **first):
                if self.host_mode:
                    m = self._host_step_once()
                elif self.service is not None:
                    m = self._service_step_once()
                else:
                    self.train_state, self.replay_state, m = self._step_fn(
                        self.train_state, self.replay_state)
            if first:
                # the one sync of a run's start: the first execution
                # (the program's load and first run) ends set-up's spans
                self._first_dispatch = False
                with tele.stage("learner/first_ready"):
                    jax.block_until_ready(m)
                tele.flush_soon()
            self._host_step += self._k
            step = self._host_step
            # scalar (k=1) or (k,) array
            self._pending_losses.append(m["loss"])
            self._pending_syncs.append(m["target_sync"])
            if self._learning_agg is not None:
                # hold the dispatch's ld/ outputs (device values, no
                # sync); aggregated into the 'learning' record block at
                # flush time
                self._learning_agg.on_dispatch(m)
            if self._replay_agg is not None:
                # same contract for the rd/ outputs (replay pillar,
                # ISSUE 10)
                self._replay_agg.on_dispatch(m)
            if self._moe_agg is not None:
                self._moe_agg.on_dispatch(m)
            if self._core_agg is not None:
                self._core_agg.on_dispatch(m)

            rt = self.cfg.runtime
            if (self.publish is not None
                    and step // rt.weight_publish_interval
                        > prev // rt.weight_publish_interval):
                with tele.stage("weights/publish"):
                    self.publish(self.train_state.params)
            if (rt.save_interval
                    and step // rt.save_interval > prev // rt.save_interval):
                self.save(step // rt.save_interval)
            if (self._snap_writer is not None and rt.snapshot_interval
                    and step // rt.snapshot_interval
                        > prev // rt.snapshot_interval):
                self.snapshot_replay()
        return m

    def _capture_replay(self) -> dict:
        """Consistent cut at the commit boundary between dispatches (the
        caller's position in the step loop IS the quiescent point; the
        service capture additionally holds the service lock against
        socket producers and stager threads)."""
        step = self._host_step
        if self.service is not None:
            extra = {
                "service_key": np.asarray(
                    jax.device_get(self._service_key)).tolist(),
                "env_steps": int(self.env_steps),
            }
            return self.service.snapshot_state(step, extra)
        from r2d2_tpu.replay.snapshot import capture_plain
        # the fused step folds its sample key off train_state.key, which
        # the checkpoint does NOT carry (resume_training_state keeps the
        # reference's no-RNG contract) — the snapshot carries it instead,
        # so a restored learner replays the exact sample stream its
        # uninterrupted twin draws (same contract as service_key above)
        extra = {
            "env_steps": int(self.env_steps),
            "train_key": np.asarray(
                jax.device_get(self.train_state.key)).tolist(),
        }
        if self.mesh is not None:
            extra["next_shard"] = int(self._next_shard)
        return capture_plain(self.spec, self.replay_state, self.ring,
                             step, extra)

    def snapshot_replay(self) -> None:
        """Capture + hand off one durable replay snapshot (ISSUE 18).
        The train path pays only the host capture (device_get of the
        ring state); serialization and the atomic tmp+rename write run
        on the writer thread."""
        if self._snap_writer is None:
            return
        t0 = time.time()
        snap = self._capture_replay()
        self._snap_capture_s = time.time() - t0
        self.tele.observe("recovery/snapshot_capture",
                          self._snap_capture_s)
        self._snap_writer.submit(snap)
        self._snap_adds = self.ring.total_adds

    def recovery_block(self) -> Optional[dict]:
        """The periodic record's ``recovery`` block (attached by the
        orchestrator only when the plane is on, so recovery-off runs
        keep a byte-identical schema). ``lost_blocks_est`` is the adds
        committed since the last snapshot — exactly the experience a
        crash at this instant would cost."""
        if self._snap_writer is None:
            return None
        import os as _os
        w = self._snap_writer
        meta = w.last_meta
        snap = {
            "count": w.count,
            "dropped": w.dropped,
            "age_s": (round(time.time() - meta["written_at"], 3)
                      if meta else None),
            "bytes": meta["payload_bytes"] if meta else None,
            "write_s": meta["write_s"] if meta else None,
            "capture_s": round(self._snap_capture_s, 6),
            "step": meta["step"] if meta else None,
        }
        return {
            "snapshot": snap,
            "restores": self._restores,
            "restored_blocks": self._restored_blocks,
            "lost_blocks_est": max(
                0, self.ring.total_adds - self._snap_adds),
            "supervisor": {"restarts": int(_os.environ.get(
                "R2D2_SUPERVISOR_RESTARTS", "0"))},
        }

    def flush_metrics(self) -> None:
        """Convert accumulated device losses to host floats (ONE sync for the
        whole interval) and feed the training counters. With learning
        diagnostics on, also aggregate the interval's ld/ outputs into the
        record's 'learning' block — and run the NaN forensics there (a
        nan_policy=halt raises out of this flush, stopping the run at the
        log boundary that first observed the poisoned step)."""
        if (not self._costs_attached and self.cfg.telemetry.enabled
                and self.cfg.telemetry.costmodel_enabled
                # the analytic table counts the LSTM network only
                and self.cfg.network.core.kind == "lstm"):
            # one-shot cost-model block (ISSUE 9): analytic per-component
            # flops/bytes for THIS config — pure host math, no compile —
            # attached at the first flush so the run's very first record
            # carries the compute anatomy the roofline tool elaborates
            self._costs_attached = True
            from r2d2_tpu.telemetry.costmodel import analytic_component_costs
            # self.net holds the RESOLVED bf16 tri-state, so the byte
            # estimates match what this run actually moves
            costs = analytic_component_costs(
                self.cfg, self.net.action_dim,
                act_bytes=2 if self.net.config.bf16 else 4)
            self.metrics.set_costs({
                "model_flops_per_step": costs["model_flops_per_step"],
                "tokens_per_step": costs["tokens_per_step"],
                "components": {
                    name: {"flops": c["flops"], "bytes": c["bytes"]}
                    for name, c in costs["components"].items()},
                "serial_chain": costs["serial_chain"],
            })
        if self._pending_losses:
            with self.tele.stage("learner/device_sync",
                                 losses=len(self._pending_losses)):
                arrays, syncs = jax.device_get(
                    (self._pending_losses, self._pending_syncs))
            self._pending_losses.clear()
            self._pending_syncs.clear()
            for loss in np.concatenate([np.atleast_1d(a) for a in arrays]):
                self.metrics.on_train_step(float(loss))
            self.metrics.on_target_syncs(sum(int(np.sum(a)) for a in syncs))
        # the aggregators fetch their own device values: a second and a
        # third transfer at every log boundary, after the sync above
        with self.tele.stage("learner/diag_flush") as flush:
            if self._learning_agg is not None:
                pub = (int(self.weight_version_fn())
                       if self.weight_version_fn is not None else None)
                self.metrics.set_learning(self._learning_agg.flush(
                    self._host_step, publish_count=pub,
                    occupancy_versions=self.ring.live_versions()))
            if self._moe_agg is not None:
                self.metrics.set_moe(self._moe_agg.flush())
            if self._core_agg is not None:
                blocks = self._core_agg.flush()
                self.metrics.set_core_blocks(blocks)
                # the span tree carries the blocks' counts of the steps this
                # flush closed, as <block>_<count> (k_sparse_attn_roofline
                # reads dsa_pairs)
                flush.tag(**{f"{name}_{k}": v
                             for name, block in (blocks or {}).items()
                             for k, v in block.items() if isinstance(v, int)})
            if self._replay_agg is not None:
                # host placement: the HostReplay numpy twin supplies the
                # sum-tree health + eviction snapshot the external-batch step
                # cannot form in-graph (ISSUE 10)
                host_stats = (self.host_replay.diag_raw()
                              if self.host_mode else None)
                self.metrics.set_replay_diag(
                    self._replay_agg.flush(host_stats=host_stats))

    def save(self, index: int) -> str:
        ts = self.train_state
        self._last_saved_step = self._host_step
        path = save_checkpoint(
            self.cfg.runtime.save_dir, self.cfg.env.game_name, index,
            self.player_idx, ts.params, ts.opt_state, ts.target_params,
            int(ts.step), self.env_steps, config_json=self.cfg.to_json())
        if self.cfg.runtime.keep_checkpoints > 0:
            # retention GC (ISSUE 18 satellite): prune after every save
            # so disk growth is bounded at keep_checkpoints orbax dirs
            from r2d2_tpu.runtime.checkpoint import prune_checkpoints
            prune_checkpoints(self.cfg.runtime.save_dir,
                              self.cfg.env.game_name, self.player_idx,
                              self.cfg.runtime.keep_checkpoints)
        return path

    def save_final(self) -> Optional[str]:
        """Preemption-safe final checkpoint: write one last save on a clean
        stop so a preempted run resumes from the stop point, not the last
        periodic interval boundary. No-op when save_interval is unset or
        the current step is already covered by a save (stopping exactly on
        a boundary must not write the same state twice). The index lands
        one past the current periodic slot so it sorts as the newest
        checkpoint for resume. With the recovery plane on, a final replay
        snapshot is written SYNCHRONOUSLY alongside (the process is about
        to exit — a SIGTERM-preempted run resumes with zero replay
        loss)."""
        rt = self.cfg.runtime
        if not rt.save_interval or self._host_step <= self._last_saved_step:
            return None
        path = self.save(self._host_step // rt.save_interval + 1)
        if self._snap_writer is not None:
            self._snap_writer.write_now(self._capture_replay())
            self._snap_adds = self.ring.total_adds
        return path

    def run(self, queue, should_stop: Callable[[], bool],
            max_steps: Optional[int] = None) -> int:
        """Drain + train until should_stop() or max_steps training steps
        (the reference trains for config.training_steps, worker.py:312)."""
        max_steps = max_steps or self.cfg.optim.training_steps
        # initial checkpoint at step 0 (ref worker.py:311)
        if self.cfg.runtime.save_interval:
            self.save(0)
        while not should_stop() and self._host_step < max_steps:
            self.drain(queue)
            if self.ready:
                self.step()
            else:
                time.sleep(0.05)
        self.flush_metrics()
        return self._host_step
