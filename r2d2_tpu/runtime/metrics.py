"""Training metrics and logging, key-compatible with the reference.

The reference centralizes counters in the ReplayBuffer actor and writes
``train_player{p}.log`` lines that plot.py regex-matches
(/root/reference/worker.py:35-37,220-234; plot.py:33-48). This class keeps the
exact key strings so the reference's offline plots work unchanged, and adds a
structured JSONL stream for programmatic consumers.
"""

import json
import logging
import os
import time
from typing import Optional

import numpy as np


class TrainMetrics:
    def __init__(self, player_idx: int = 0, log_dir: str = ".",
                 jsonl: bool = True, resume: bool = False):
        self.player_idx = player_idx
        if log_dir:
            os.makedirs(log_dir, exist_ok=True)
        self.logger = logging.getLogger(f"r2d2_tpu.player_{player_idx}")
        self.logger.setLevel(logging.INFO)
        self.logger.propagate = False
        path = os.path.join(log_dir or ".", f"train_player{player_idx}.log")
        # resume=True (runtime.resume set): APPEND — a preempted run
        # resuming from its final checkpoint must not wipe the log/JSONL
        # history the plots and the inspector are built from; a fresh run
        # truncates both (the JSONL is opened "a" per record, so it needs
        # the explicit truncation here).
        handler = logging.FileHandler(path, "a" if resume else "w")
        handler.setFormatter(logging.Formatter("%(message)s"))
        self.logger.handlers = [handler]
        self._jsonl_path = (os.path.join(log_dir or ".", f"metrics_player{player_idx}.jsonl")
                            if jsonl else None)
        if self._jsonl_path and not resume:
            open(self._jsonl_path, "w").close()
        self._start = time.time()
        # telemetry aggregator (set_telemetry): owns the stage timers this
        # record's 'stages' block summarizes. NULL keeps learner-only
        # constructions working with zero branching at the call sites.
        from r2d2_tpu.telemetry import NULL_TELEMETRY
        self.telemetry = NULL_TELEMETRY

        self.buffer_size = 0
        self.env_steps = 0
        self.last_env_steps = 0
        self.num_episodes = 0
        self.episode_reward = 0.0
        self.training_steps = 0
        self.last_training_steps = 0
        self.target_syncs = 0
        self.sum_loss = 0.0
        self.dropped_priority_updates = 0
        self._next_drop_warn = 1

        # ingestion observability (ISSUE 2): per-interval accumulators,
        # reset at each log(), plus a cumulative block counter the e2e
        # bench reads for whole-run blocks/s. Locked: the pipelined
        # stager thread feeds on_ingest_pause while the main thread's
        # log() resets — an unguarded read-modify-write would double-count
        # or drop an interval's pause time.
        import threading
        self._ingest_lock = threading.Lock()
        self.ingest_blocks_total = 0
        self._ingest_drains = 0
        self._ingest_blocks = 0
        self._ingest_latency_sum = 0.0
        self._ingest_pause_time = 0.0
        self.ingest_queue_depth = 0

        # worker-health counters (ISSUE 3): last supervision snapshot
        # (PlayerStack.supervise / the multihost fleet push WorkerHealth's
        # cumulative counters here); defaults keep the record schema
        # stable for learner-only runs that never supervise
        self._actor_health = {}

        # learning-dynamics block (ISSUE 5): set per flush by the
        # LearningAggregator; emitted once per record then cleared, and
        # OMITTED entirely when learning diagnostics are off (consumers
        # key on its presence, like the 'stages' block)
        self._learning = None
        self._moe = None

        # sharded-anakin composition block (ISSUE 8): per-shard rows +
        # the env-step imbalance ratio, set at each stats flush by the
        # fused loop; emitted once per record then cleared, OMITTED on
        # every non-anakin run (consumers key on its presence)
        self._anakin = None

        # fleet observability block (ISSUE 12): set per flush by the
        # rank-0 FleetAggregator (per-rank step-time table, straggler
        # rank, lockstep-wait fraction, env-step divergence, host-row
        # ages, merged fleet stage histograms); emitted once per record
        # then cleared, OMITTED on every non-multihost run and under the
        # telemetry.fleet_enabled kill switch (schema byte-identical to
        # PR10, stability-tested)
        self._fleet = None

        # replay & data-pathology block (ISSUE 10): set per flush by the
        # ReplayDiagAggregator (sum-tree health, eviction lifetimes, lane
        # composition); emitted once per record then cleared, OMITTED
        # entirely under the telemetry.replay_diag_enabled kill switch
        # (schema byte-identical to PR9, stability-tested)
        self._replay_diag = None

        # cost-model block (ISSUE 9): the analytic per-component
        # flops/bytes summary of the configured step, set ONCE by the
        # Learner's first flush and emitted on the next record only (it
        # is static per config — re-emitting every interval would bloat
        # the JSONL with constants); OMITTED entirely under the
        # telemetry.costmodel_enabled kill switch (schema byte-identical
        # to pre-PR9, stability-tested)
        self._costs = None
        self._core = None

        # serving plane (ISSUE 13): a serving-block provider
        # (ServingStats.interval_block, attached by the orchestrating
        # loop when actor.inference="server" or a standalone server
        # shares this metrics stream) — called once per log(); a None
        # return (no serving traffic this interval) omits the key, and
        # an unattached provider (every local-inference run) leaves the
        # record byte-identical to the pre-PR13 schema.
        self._serving_fn = None

        # quantized inference plane (ISSUE 14): a quant-block provider
        # (QuantStats.interval_block, attached by the orchestrating loop
        # when network.inference_dtype != "f32") — called once per
        # log(); unattached (every f32 run) the record is byte-identical
        # to the PR13 schema.
        self._quant_fn = None

        # policy-quality pillar (ISSUE 20): a quality-block provider
        # (QualityLedger.interval_block — Q-calibration join, continuous
        # per-scenario eval with checkpoint lineage, shadow divergence,
        # promotion state; the provider also appends the
        # quality_player{p}.jsonl ledger row) — called once per log();
        # unattached (telemetry.quality_enabled off, the default) the
        # record is byte-identical to the PR19 schema.
        self._quality_fn = None

        # elastic fleet plane (ISSUE 15): a replay_service-block
        # provider (per-shard fill, spill occupancy/hit-rate, fan-out
        # relay depth/lag, membership lease counts) attached by the
        # orchestrating loop when any fleet plane is configured on —
        # unattached (every legacy run) the record is byte-identical to
        # the PR14 schema.
        self._replay_service_fn = None

        # crash-recovery plane (ISSUE 18): a recovery-block provider
        # (Learner.recovery_block — snapshot age/bytes/durations, restore
        # counts, estimated lost blocks, supervisor restarts) attached by
        # the orchestrating loop when runtime.snapshot_interval > 0 —
        # unattached (every run with the plane off) the record is
        # byte-identical to the PR17 schema.
        self._recovery_fn = None

        # cross-plane tracing (ISSUE 19): a trace-block provider
        # (ExperienceTrace.interval_block — the end-to-end env-step ->
        # gradient latency histogram with its per-hop breakdown)
        # attached by the learner when telemetry.tracing_enabled —
        # unattached (the kill switch, every legacy run) the record is
        # byte-identical to the PR18 schema.
        self._tracing_fn = None

        # system-health pillar (ISSUE 7): a resources-block provider
        # (ResourceMonitor.block) and the alert engine, both attached by
        # the orchestrating loop. None = the blocks are OMITTED and the
        # record schema is byte-identical to pre-PR7 (the
        # telemetry.resources_enabled kill switch; stability-tested).
        self._resources_fn = None
        self._sentinel = None

    # -- feed points --

    def on_block(self, learning_steps: int, episode_return: Optional[float]) -> None:
        """Called per ingested block (ref worker.py:117-120)."""
        self.env_steps += learning_steps
        if episode_return is not None and not np.isnan(episode_return):
            self.episode_reward += float(episode_return)
            self.num_episodes += 1

    def on_episodes(self, count: int, return_sum: float) -> None:
        """Batched episode-return feed for the fused on-device acting path:
        episode ends are counted on device and fetched as per-interval
        (count, sum) aggregates, so the return average matches on_block's
        per-episode feed without a host transfer per episode."""
        if count > 0 and np.isfinite(return_sum):
            self.episode_reward += float(return_sum)
            self.num_episodes += int(count)

    def on_train_step(self, loss: float) -> None:
        """Called per learner step (ref worker.py:211-212)."""
        self.training_steps += 1
        self.sum_loss += float(loss)

    def on_target_syncs(self, fired: int) -> None:
        """Hard target syncs among the flushed steps (the steps' 0/1
        ``target_sync``, learner/train_step.py sync_target)."""
        self.target_syncs += int(fired)

    def set_buffer_size(self, size: int) -> None:
        self.buffer_size = int(size)

    def on_ingest_drain(self, blocks: int, latency: float) -> None:
        """Called once per non-empty ingestion drain: ``blocks`` blocks
        entered the replay in one batch, ``latency`` seconds from queue pop
        to replay commit (the pipelined path's stage→commit lag; the
        legacy path's synchronous drain+ingest wall time)."""
        with self._ingest_lock:
            self._ingest_drains += 1
            self._ingest_blocks += blocks
            self.ingest_blocks_total += blocks
            self._ingest_latency_sum += latency

    def on_ingest_pause(self, seconds: float) -> None:
        """Rate-limiter pause time: ingestion stood still for ``seconds``
        while collection was ahead of the collect:learn budget."""
        with self._ingest_lock:
            self._ingest_pause_time += seconds

    def set_ingest_queue_depth(self, depth: int) -> None:
        """Staged batches awaiting commit (pipelined ingestion gauge)."""
        self.ingest_queue_depth = int(depth)

    def set_telemetry(self, telemetry) -> None:
        """Attach the process's Telemetry: log() then emits the aggregated
        per-interval 'stages' block (P50/P95/P99 per pipeline stage,
        fleet-wide when an actor TelemetryBoard is attached to it)."""
        self.telemetry = telemetry

    def set_learning(self, block: Optional[dict]) -> None:
        """Attach the interval's learning-diagnostics block (|TD|/priority
        /Q histograms, grad norms, ΔQ, staleness — telemetry/learning.py);
        None = nothing this interval (no training steps, or diagnostics
        disabled) and the record carries no 'learning' key."""
        self._learning = block

    def set_moe(self, block: Optional[dict]) -> None:
        """Attach the interval's expert-routing block (per expert layer:
        histogram of chosen experts, pairs on held experts, their max/mean
        load, router entropy, dropped — telemetry/learning.py
        MoeAggregator); None = the core routes nothing and the record
        carries no 'moe' key."""
        self._moe = block

    def set_anakin(self, block: Optional[dict]) -> None:
        """Attach the interval's sharded-anakin block (per-shard env
        steps / episodes / return sums + the max/min env-step imbalance
        ratio — runtime/anakin_loop.py flush_stats); None = nothing this
        interval and the record carries no 'anakin' key."""
        self._anakin = block

    def set_fleet(self, block: Optional[dict]) -> None:
        """Attach the interval's fleet-observability block (per-rank
        step-time skew, straggler identity, lockstep-wait fraction,
        env-step divergence, host-row ages — telemetry/fleet.py); None =
        nothing this interval and the record carries no 'fleet' key."""
        self._fleet = block

    def set_replay_diag(self, block: Optional[dict]) -> None:
        """Attach the interval's replay-diagnostics block (sum-tree
        health + collapse indicators, per-slot eviction lifetimes with
        the never-sampled fraction, ε-lane composition of the sampled
        batches — telemetry/replaydiag.py); None = nothing this interval
        (no training, or the pillar disabled) and the record carries no
        'replay_diag' key."""
        self._replay_diag = block

    def set_costs(self, block: Optional[dict]) -> None:
        """Attach the one-shot cost-model block (ISSUE 9): analytic
        per-component flops/bytes + the serial-chain model for the
        configured step (telemetry/costmodel.analytic_component_costs).
        Emitted on exactly one record then cleared; None = no block."""
        self._costs = block

    def set_core(self, block: Optional[dict]) -> None:
        """Attach the one-shot memory-core block: the core's kind and its
        state row by kind of part (kind, layers, floats, bytes —
        models/cores/__init__.py state_block), so that a reader can tell an
        LSTM's (h, c) from a latent cache, and a convolution's state from a
        window of keys and values. Emitted on exactly one record."""
        self._core = block

    def set_serving(self, provider) -> None:
        """Attach the serving-block provider (ISSUE 13): a callable
        returning ``ServingStats.interval_block()`` — request/reply
        counts, latency percentiles, batch-fill histogram summary,
        client lease churn. Called once per log(); None returns omit
        the block (consumers key on its presence)."""
        self._serving_fn = provider

    def set_quant(self, provider) -> None:
        """Attach the quant-block provider (ISSUE 14): a callable
        returning ``QuantStats.interval_block()`` — the active inference
        dtype, probe count, max |Q_f32 − Q_quant|, and greedy-action
        agreement of the interval's in-graph accuracy probes. Called
        once per log(); None returns omit the block."""
        self._quant_fn = provider

    def set_quality(self, provider) -> None:
        """Attach the quality-block provider (ISSUE 20): a callable
        returning ``QualityLedger.interval_block()`` — the interval's
        Q-calibration gap stats, the latest per-scenario eval rows with
        checkpoint lineage, shadow-scoring divergence, and the promotion
        state machine's sub-block. Called once per log(); None returns
        omit the block (consumers key on its presence)."""
        self._quality_fn = provider

    def set_replay_service(self, provider) -> None:
        """Attach the replay_service-block provider (ISSUE 15): a
        callable returning the elastic-fleet telemetry dict — per-shard
        fill/adds, spill-tier occupancy + hit-rate + interval thrash,
        fan-out relay depth/lag, membership lease counts. ISSUE 16 adds
        key-gated sub-blocks the provider emits only when their feature
        is on (record-schema byte-identity at defaults): "ingest"
        (grouped-dispatch counters + backlog — the ingest_backlog alert
        rule reads replay_service.ingest.backlog from here), "socket"
        (windowed-frame server stats), and spill prefetch/write-back
        counters inside "spill". Called once per log(); None returns
        omit the block (consumers key on its presence)."""
        self._replay_service_fn = provider

    def set_recovery(self, provider) -> None:
        """Attach the recovery-block provider (ISSUE 18): a callable
        returning the crash-recovery telemetry dict — latest replay
        snapshot (age/bytes/capture+write durations/step), restore
        counts + restored blocks, the estimated at-risk block count
        (adds since the last snapshot), supervisor restart count.
        Called once per log(); None returns omit the block (consumers
        key on its presence)."""
        self._recovery_fn = provider

    def set_tracing(self, provider) -> None:
        """Attach the trace-block provider (ISSUE 19): a callable
        returning ``ExperienceTrace.interval_block()`` — the sampled
        row count, the e2e_experience_latency histogram summary
        (env-step emission -> gradient consumption), and its per-hop
        breakdown (emit_to_ingest / ingest_to_sample / sample_to_train).
        Called once per log(); None returns omit the block (consumers
        key on its presence)."""
        self._tracing_fn = provider

    def set_resources(self, provider) -> None:
        """Attach the resources-block provider (ISSUE 7): a callable
        returning the ResourceMonitor's ``block()`` dict — called once
        per log() so EVERY periodic record carries a ``resources``
        entry while the pillar is enabled."""
        self._resources_fn = provider

    def set_sentinel(self, engine) -> None:
        """Attach the alert engine (ISSUE 7): log() evaluates the rule
        set against the assembled record — alerts see the same interval
        they alert on — and the record carries the resulting ``alerts``
        block; firings append to alerts_player{p}.jsonl inside the
        engine."""
        self._sentinel = engine

    def set_actor_health(self, snapshot: dict) -> None:
        """Supervision counters (WorkerHealth.snapshot + stall-dump count)
        for the periodic record — restarts, hangs, breaker trips, parked
        slots, heartbeat staleness."""
        self._actor_health = dict(snapshot)

    def on_dropped_priority_update(self) -> None:
        """Called when a priority write-back batch is dropped because the
        async write-back queue is saturated (host placement). Dropping
        silently degrades PER toward uniform sampling, so make it loud:
        warn at the first drop and at each 10x milestone after (the stdlib
        lastResort handler shows WARNING+ even with logging unconfigured)."""
        self.dropped_priority_updates += 1
        if self.dropped_priority_updates >= self._next_drop_warn:
            logging.getLogger(__name__).warning(
                "player %d: %d priority write-back batch(es) dropped under "
                "write-back queue backpressure — PER is degrading toward "
                "uniform sampling; the write-back thread is not keeping up",
                self.player_idx, self.dropped_priority_updates)
            self._next_drop_warn *= 10

    # -- emission (exact reference key strings, ref worker.py:220-234) --

    def log(self, log_interval: float) -> dict:
        self.logger.info(f"buffer size: {self.buffer_size}")
        buffer_speed = (self.env_steps - self.last_env_steps) / log_interval
        self.logger.info(f"buffer update speed: {buffer_speed}/s")
        self.logger.info(f"number of environment steps: {self.env_steps}")
        avg_return = None
        if self.num_episodes != 0:
            avg_return = self.episode_reward / self.num_episodes
            self.logger.info(f"average episode return: {avg_return:.4f}")
            self.episode_reward = 0.0
            self.num_episodes = 0
        self.logger.info(f"number of training steps: {self.training_steps}")
        train_speed = (self.training_steps - self.last_training_steps) / log_interval
        self.logger.info(f"training speed: {train_speed}/s")
        mean_loss = None
        if self.training_steps != self.last_training_steps:
            mean_loss = self.sum_loss / (self.training_steps - self.last_training_steps)
            self.logger.info(f"loss: {mean_loss:.4f}")
            self.last_training_steps = self.training_steps
            self.sum_loss = 0.0
        self.last_env_steps = self.env_steps

        record = {
            "t": time.time() - self._start,
            "buffer_size": self.buffer_size,
            "buffer_speed": buffer_speed,
            "env_steps": self.env_steps,
            "avg_episode_return": avg_return,
            "training_steps": self.training_steps,
            "training_speed": train_speed,
            "target_syncs": self.target_syncs,
            "loss": mean_loss,
            "dropped_priority_updates": self.dropped_priority_updates,
            # worker-health counters: cumulative, overlaid by the latest
            # supervision snapshot when a supervisor is running
            "actor_restarts": 0,
            "actor_hangs_detected": 0,
            "actor_breaker_trips": 0,
            "actor_parked_slots": 0,
            "shm_slots_recovered": 0,
            "ingest_stall_dumps": 0,
            "heartbeat_age_max_s": None,
        }
        record.update(self._actor_health)
        with self._ingest_lock:
            # ingestion observability (per-interval; the e2e bench's
            # ingestion phase reads these)
            record.update({
                "ingest_blocks_total": self.ingest_blocks_total,
                "ingest_drains": self._ingest_drains,
                "ingest_blocks_per_drain": (
                    round(self._ingest_blocks / self._ingest_drains, 2)
                    if self._ingest_drains else None),
                "ingest_drain_latency_ms": (
                    round(1e3 * self._ingest_latency_sum
                          / self._ingest_drains, 3)
                    if self._ingest_drains else None),
                "ingest_queue_depth": self.ingest_queue_depth,
                "ingest_pause_time": round(self._ingest_pause_time, 3),
            })
            self._ingest_drains = 0
            self._ingest_blocks = 0
            self._ingest_latency_sum = 0.0
            self._ingest_pause_time = 0.0
        if self._learning is not None:
            # ONE learning block per interval (ISSUE 5) — consumed on
            # emission so a training pause doesn't replay stale numbers
            record["learning"] = self._learning
            self._learning = None
        if self._moe is not None:
            record["moe"] = self._moe
            self._moe = None
        if self._anakin is not None:
            # ONE anakin block per interval (ISSUE 8), consumed like the
            # learning block; emitted before the sentinel pass so the
            # shard_imbalance rule sees its own interval
            record["anakin"] = self._anakin
            self._anakin = None
        if self._fleet is not None:
            # ONE fleet block per interval (ISSUE 12), consumed on
            # emission; before the sentinel pass so the rank_straggler /
            # lockstep_wait_frac / fleet_desync / missing_rank rules see
            # their own interval
            record["fleet"] = self._fleet
            self._fleet = None
        if self._replay_diag is not None:
            # ONE replay_diag block per interval (ISSUE 10), consumed on
            # emission; before the sentinel pass so the priority-collapse
            # / never-sampled / lane-starvation rules see their own
            # interval
            record["replay_diag"] = self._replay_diag
            self._replay_diag = None
        if self._costs is not None:
            # ONE costs block per run (ISSUE 9), consumed on emission —
            # the numbers are pure config constants, so one record
            # carries them and the stream stays lean
            record["costs"] = self._costs
            self._costs = None
        if self._core is not None:
            record["core"] = self._core
            self._core = None
        if self.telemetry.enabled:
            # ONE aggregated block per interval covering the whole fleet:
            # learner-local stage timers merged with the actor board's
            # per-slot deltas (ISSUE 4). Omitted entirely when telemetry
            # is off — consumers key on its presence, and the PR-2/3 keys
            # above are unaffected either way (schema-stability-tested).
            record["stages"] = self.telemetry.interval_summary()
            record["telemetry_dropped_spans"] = self.telemetry.spans.dropped
        if self._serving_fn is not None:
            # serving block (ISSUE 13): request latency / batch fill /
            # client churn for the interval. Before the sentinel pass so
            # the serve_* rules see their own interval; a no-traffic
            # interval returns None and the key is omitted.
            serving = self._serving_fn()
            if serving is not None:
                record["serving"] = serving
        if self._quant_fn is not None:
            # quant block (ISSUE 14): the active inference dtype + the
            # interval's accuracy-probe aggregates. Before the sentinel
            # pass so the quant_divergence rule sees its own interval.
            quant = self._quant_fn()
            if quant is not None:
                record["quant"] = quant
        if self._replay_service_fn is not None:
            # elastic-fleet block (ISSUE 15): shard fill / spill health /
            # fan-out lag / membership leases. Before the sentinel pass
            # so the spill_thrash / fanout_lag / orphaned_slot rules see
            # their own interval.
            rs = self._replay_service_fn()
            if rs is not None:
                record["replay_service"] = rs
        if self._quality_fn is not None:
            # policy-quality block (ISSUE 20): eval return / Q-calibration /
            # shadow divergence / promotion state. Before the sentinel pass
            # so the quality_regression / canary_divergence / promotion_stall
            # rules see their own interval.
            quality = self._quality_fn()
            if quality is not None:
                record["quality"] = quality
        if self._recovery_fn is not None:
            # crash-recovery block (ISSUE 18): snapshot age / restore
            # counts / at-risk blocks / supervisor restarts. Before the
            # sentinel pass so the snapshot_stale / recovery_loop rules
            # see their own interval.
            recovery = self._recovery_fn()
            if recovery is not None:
                record["recovery"] = recovery
        if self._tracing_fn is not None:
            # cross-plane trace block (ISSUE 19): env-step -> gradient
            # latency with per-hop breakdown. Before the sentinel pass
            # so the e2e_latency_growth rule sees its own interval; an
            # interval that traced nothing returns None and the key is
            # omitted.
            trace = self._tracing_fn()
            if trace is not None:
                record["trace"] = trace
        if self._resources_fn is not None:
            # machine-side block (ISSUE 7): devices/host/buffer footprints
            # + the compile sub-block. Before the sentinel, which reads it.
            record["resources"] = self._resources_fn()
        if self._sentinel is not None:
            # the alert pass sees the COMPLETE record of its own interval
            # (throughput, health, learning, resources); firings also
            # append to alerts_player{p}.jsonl inside the engine
            record["alerts"] = self._sentinel.evaluate(record)
        if self._jsonl_path:
            with open(self._jsonl_path, "a") as f:
                f.write(json.dumps(record) + "\n")
        return record
