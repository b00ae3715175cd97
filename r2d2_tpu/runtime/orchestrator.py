"""System bring-up: the reference's ``train()`` (/root/reference/train.py:21-66)
without Ray.

Per player (1, or ``num_players`` complete stacks for multiplayer self-play):
one Learner on the TPU, a weight service, a block queue, and N actors on host
CPUs with the Ape-X ε ladder. Actors start first; training begins once the
buffer passes ``learning_starts`` (the reference polls buffer.ready,
train.py:49-54); the driver loop logs every ``log_interval`` seconds.

Actor modes:
  * "thread"  — actors are threads with CPU-pinned jitted policies; hermetic,
    used by tests and single-host quickstarts.
  * "process" — spawned OS processes (the reference's Ray-actor equivalent):
    CPU-pinned children, shared-memory weight reads, shm-ring blocks.

Multiplayer wiring mirrors train.py:28-45: actor i of player 0 hosts game i
on port base+i; actor i of every other player joins that game.
"""

import multiprocessing as mp
import os
import signal
import threading
import time
from typing import Callable, List, Optional

from r2d2_tpu.config import Config, apex_epsilon
from r2d2_tpu.envs.factory import create_env
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.runtime.actor_loop import make_actor_env, make_actor_policy
from r2d2_tpu.runtime.actor_main import actor_process_main
from r2d2_tpu.runtime.feeder import BlockQueue
from r2d2_tpu.runtime.learner_loop import Learner
from r2d2_tpu.runtime.metrics import TrainMetrics
from r2d2_tpu.runtime.weights import (InProcWeightStore, WeightPublisher,
                                      make_publish_preparer, wrap_publish)


class _VacantSlot:
    """Placeholder worker for a spare membership slot (ISSUE 15): keeps
    the worker lists index-aligned with the slot table so a joiner can
    land in ANY leased slot. Never alive; supervision skips it anyway
    (spare slots are health-detached until adopted)."""

    def is_alive(self) -> bool:
        return False


class PlayerStack:
    """One player's buffer+learner+actors (the reference creates these per
    player in train.py:28-45)."""

    def __init__(self, cfg: Config, player_idx: int, action_dim: int):
        self.cfg = cfg
        self.player_idx = player_idx
        self.net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                                cfg.env.frame_height, cfg.env.frame_width)
        self.metrics = TrainMetrics(player_idx, cfg.runtime.save_dir,
                                    resume=bool(cfg.runtime.resume))
        # unified telemetry (ISSUE 4): ONE Telemetry for this process
        # (learner threads + thread actors observe straight into it);
        # process actors publish through the shm board, which the
        # aggregator differences per log interval. Attached to metrics
        # BEFORE Learner construction so the learner's stage observes
        # never land in the NULL sink; the board's shm allocation happens
        # at the END of __init__ so nothing can raise past a live segment.
        from r2d2_tpu.telemetry import Telemetry
        self.telemetry = Telemetry.from_config(
            cfg, name=f"learner-p{player_idx}")
        self.tele_board = None
        self.metrics.set_telemetry(self.telemetry)
        self.learner = Learner(cfg, self.net, player_idx, metrics=self.metrics)
        self.threads: List[threading.Thread] = []
        self.processes: List[mp.Process] = []
        from r2d2_tpu.runtime.feeder import (
            HeartbeatBoard, IngestStallDetector, RingRecoveryScheduler,
            WorkerHealth)
        self._seen_dead: set = set()    # reaped dead process objects
        self._ring_recovery = RingRecoveryScheduler()
        # elastic membership (ISSUE 15): the slot table spans the
        # fleet's MAX width (fleet.max_slots spare slots lease-able by
        # joiners); the heartbeat board / health policy / telemetry
        # board size to it so an adopted spare publishes through the
        # same rows the startup fleet does. Default config: n_slots ==
        # num_actors and everything below is byte-identical to PR14.
        self.n_slots = cfg.fleet.resolved_max_slots(cfg.actor.num_actors)
        from r2d2_tpu.fleet.membership import FleetMembership
        self.membership = FleetMembership(
            self.n_slots, cfg.actor.envs_per_actor,
            initial_active=cfg.actor.num_actors,
            num_shards=max(cfg.fleet.replay_shards, 1))
        # worker-health subsystem: per-slot heartbeats + the shared
        # watchdog/backoff/breaker policy (feeder.py) + the learner-side
        # ingest stall detector
        self.heartbeats = HeartbeatBoard(self.n_slots)
        self.health = WorkerHealth.from_runtime(
            self.n_slots, self.heartbeats, cfg.runtime)
        for spare in range(cfg.actor.num_actors, self.n_slots):
            # spare slots carry no worker until a joiner leases them —
            # supervision must neither hang-check nor respawn them
            self.health.detach(spare)
        self._stall = IngestStallDetector(cfg.runtime.ingest_stall_timeout_s)
        # grammar-scheduled joins (tools/chaos.py join@t=S): admitted by
        # supervise() once the slot is parked/free and t has elapsed
        from r2d2_tpu.tools.chaos import parse_join_spec
        self._join_schedule = (parse_join_spec(cfg.actor.fault_spec)
                               if cfg.actor.fault_spec else {})
        self._joins_done: set = set()
        self._run_start = time.time()
        # weight fan-out tree (ISSUE 15): built by the actor spawners
        # when fleet.fanout_degree >= 2 (in-proc relays in thread mode,
        # shm relay segments in process mode)
        self._fanout = None
        self._shm_fanout = None
        self._actor_mode = None
        # replay-service socket rung: remote producers route blocks in
        self._service_server = None
        if (cfg.fleet.service_transport == "socket"
                and self.learner.service is not None):
            from r2d2_tpu.fleet.replay_service import ReplayServiceServer
            self._service_server = ReplayServiceServer(
                self.learner.service, cfg.fleet.service_host,
                cfg.fleet.service_port)
        # fleet telemetry: the record's replay_service block (per-shard
        # fill, spill health, fan-out lag, membership leases) — attached
        # only when a fleet plane is configured on, so legacy records
        # stay byte-identical to the PR14 schema
        if cfg.fleet.active and cfg.telemetry.enabled:
            self.metrics.set_replay_service(self._replay_service_block)
        # crash-recovery plane (ISSUE 18): the record's recovery block
        # (snapshot age/bytes/durations, restore counts, at-risk blocks,
        # supervisor restarts) — attached only when the snapshot plane
        # is on, so plane-off records stay byte-identical to PR17
        if cfg.telemetry.enabled and cfg.runtime.snapshot_interval > 0:
            self.metrics.set_recovery(self.learner.recovery_block)
        # last replay-service re-announcement (ISSUE 18): a restarted
        # standalone service posts its address here through the lease
        # board; 'info' callers (joining producers) dial the survivor
        self._replay_announce = None
        self.publisher = None
        self.store = None
        self.queue: Optional[BlockQueue] = None
        self.resources = None
        self.sentinel = None
        # central policy inference service (ISSUE 13): in server mode the
        # stack owns ONE PolicyServer + its endpoint/stats; the endpoint
        # and transports OUTLIVE server restarts (the chaos drill swaps
        # only the server object via restart_serve_server). The stats
        # aggregator is shared with in-proc clients so the periodic
        # record's 'serving' block carries CLIENT-visible latencies.
        self.serve_stats = None
        self.serve_endpoint = None
        self.serve_server = None
        # serving fleet (ISSUE 17): serve.servers > 1 swaps the ONE
        # PolicyServer for a ServerFleet (per-server cache slices behind
        # the shard→server router); the shared stats aggregator and the
        # construction entry points are unchanged, so the single-server
        # path stays byte-identical
        self.serve_fleet = None
        self._serve_transport = None
        self._serve_fleet_transports = []
        self._serve_weight_sub = None
        self._serve_weight_subs = []
        self._serve_weight_poll = None
        self._serve_weight_poll_factory = None
        self._serve_weight_version = None
        self._serve_weight_version_factory = None
        self._serve_copy_updates = True
        self._serve_client_timed = True
        self._serve_spec = None
        self._lease_server = None
        if cfg.actor.inference == "server":
            from r2d2_tpu.serve import InprocEndpoint, ServingStats
            self.serve_stats = ServingStats()
            if cfg.telemetry.enabled and cfg.telemetry.tracing_enabled:
                from r2d2_tpu.telemetry.tracing import ServeTrace
                self.serve_stats.trace = ServeTrace()
            self.serve_endpoint = InprocEndpoint()
            self.metrics.set_serving(self._serving_block)
        # quantized inference plane (ISSUE 14): the publish-time
        # quantizer (None at "f32" — the weight plumbing is then
        # byte-identical to PR13) and the accuracy-probe aggregator
        # feeding the record's 'quant' block. Thread actors and the
        # policy server share ONE QuantStats; process actors run the
        # quantized forward from the same published twin but probe-free
        # (their probe results have no channel back to this record —
        # served inference probes server-side instead).
        self._publish_prep = make_publish_preparer(self.net)
        self.quant_stats = None
        if cfg.network.inference_dtype != "f32":
            from r2d2_tpu.telemetry import QuantStats
            self.quant_stats = QuantStats(
                cfg.network.inference_dtype,
                cfg.telemetry.quant_probe_interval)
            self.metrics.set_quant(self.quant_stats.interval_block)
        # policy-quality plane (ISSUE 20): the quality aggregator + the
        # quality_player{p}.jsonl ledger feeding the record's 'quality'
        # block; the background evaluator and the promotion manager are
        # built by the actor spawners once the weight store exists.
        # Default-off: records stay byte-identical to the PR-19 schema.
        self.quality_stats = None
        self.quality_ledger = None
        self.quality_evaluator = None
        self.promotion = None
        self.shadow = None
        self._shadow_mirror = None
        self._routing_channels: List = []
        if cfg.telemetry.enabled and cfg.telemetry.quality_enabled:
            from r2d2_tpu.telemetry import QualityLedger, QualityStats
            self.quality_stats = QualityStats()
            try:
                self.quality_ledger = QualityLedger(
                    self.quality_stats, cfg.runtime.save_dir or ".",
                    player_idx, resume=bool(cfg.runtime.resume))
            except BaseException:
                self.heartbeats.close()
                raise
            self.metrics.set_quality(self.quality_ledger.interval_block)
        # LAST: telemetry board shm + the span-drain's file I/O. Anything
        # raising after an shm allocation would leak the segment (train()
        # only closes stacks that made it into its list), so the file I/O
        # is guarded to unwind BOTH boards created above.
        if cfg.telemetry.enabled:
            from r2d2_tpu.telemetry import TelemetryBoard
            self.tele_board = TelemetryBoard(self.n_slots)
            self.telemetry.attach_board(self.tele_board)
            try:
                resume = bool(cfg.runtime.resume)
                save_dir = cfg.runtime.save_dir or "."
                if not resume:
                    # fresh run: clear stale actor span files from a
                    # previous run of this save_dir (actor processes
                    # APPEND so respawns keep their predecessors' spans —
                    # this is the one place that truncates, once per run)
                    import glob
                    for stale in glob.glob(os.path.join(
                            save_dir, f"spans_p{player_idx}_a*.jsonl")):
                        try:
                            os.remove(stale)
                        except OSError:
                            pass
                self.telemetry.start_drain(
                    os.path.join(save_dir,
                                 f"spans_player{player_idx}.jsonl"),
                    append=resume)
            except BaseException:
                self.tele_board.close()
                self.heartbeats.close()
                raise
        # system-health pillar (ISSUE 7): resource sampler + the alert
        # engine behind the telemetry.resources_enabled kill switch — off,
        # neither exists and the periodic record stays byte-identical to
        # the pre-PR7 schema. The Learner registered its buffer
        # footprints during construction above; the sampler reads the
        # shared registry and the actor gauges off the telemetry board.
        # Compile events are process-global: the FIRST stack's Learner of
        # a multiplayer process installed the compile/retrace monitor,
        # bound to this stack's Telemetry, and this stack takes it over.
        # Wired LAST (the alert stream truncation is file I/O): a failure
        # here must unwind the shm segments allocated above.
        self.compile_monitor = self.learner.compile_monitor
        if cfg.telemetry.enabled and cfg.telemetry.resources_enabled:
            from r2d2_tpu.telemetry import (AlertEngine, ResourceMonitor,
                                            default_rules)
            try:
                self.resources = ResourceMonitor(
                    player_idx, cfg.runtime.save_dir or ".",
                    interval_s=cfg.telemetry.resources_interval_s,
                    headroom_warn_frac=(
                        cfg.telemetry.resources_headroom_warn_frac),
                    board=self.tele_board,
                    compile_monitor=self.compile_monitor,
                    aot_coverage_fn=self.learner.aot_coverage)
                self.metrics.set_resources(self.resources.block)
                if cfg.telemetry.alerts_enabled:
                    self.sentinel = AlertEngine(
                        default_rules(cfg.telemetry),
                        jsonl_path=os.path.join(
                            cfg.runtime.save_dir or ".",
                            f"alerts_player{player_idx}.jsonl"),
                        resume=bool(cfg.runtime.resume))
                    self.metrics.set_sentinel(self.sentinel)
            except BaseException:
                if self.compile_monitor is not None:
                    self.compile_monitor.uninstall()
                if self.tele_board is not None:
                    self.tele_board.close()
                self.heartbeats.close()
                raise

    def actor_env_args(self, actor_idx: int):
        """Multiplayer host/join wiring (ref train.py:33-38; shared with
        the per-player-job multihost path via MultiplayerConfig.env_args)."""
        return self.cfg.multiplayer.env_args(self.player_idx, actor_idx)

    def _serving_block(self):
        """Periodic-record 'serving' block provider: the fleet's
        aggregate (shared stats + per-server rows) when serving is
        sharded, the single server's stats otherwise — same schema for
        everything that existed before the fleet."""
        if self.serve_fleet is not None:
            return self.serve_fleet.interval_block(
                deadline_ms=self.cfg.serve.deadline_ms,
                max_batch=self.cfg.serve.max_batch)
        return self.serve_stats.interval_block(
            deadline_ms=self.cfg.serve.deadline_ms,
            max_batch=self.cfg.serve.max_batch)

    def _start_serve_server(self) -> None:
        """(Re)build the serving plane against persistent endpoints —
        the ONE construction path for cold start and the chaos drill's
        restart (the replacement adopts the learner's CURRENT params and
        the same weight-service reader). serve.servers > 1 builds the
        sharded ServerFleet (ISSUE 17) instead of one PolicyServer; the
        default leaves this path byte-identical to the single-server
        plane."""
        if self.cfg.serve.servers > 1:
            from r2d2_tpu.serve import ServerFleet
            self.serve_fleet = ServerFleet(
                self.cfg, self.net, self.learner.train_state.params,
                stats=self.serve_stats, telemetry=self.telemetry,
                client_timed=self._serve_client_timed,
                weight_poll_factory=self._serve_weight_poll_factory,
                weight_version=self._serve_weight_version,
                weight_version_factory=self._serve_weight_version_factory,
                copy_updates=self._serve_copy_updates,
                quant_stats=self.quant_stats)
            return
        from r2d2_tpu.serve import PolicyServer
        self.serve_server = PolicyServer(
            self.cfg, self.net, self.learner.train_state.params,
            endpoint=self.serve_endpoint,
            weight_poll=self._serve_weight_poll,
            weight_version=self._serve_weight_version,
            copy_updates=self._serve_copy_updates,
            stats=self.serve_stats, telemetry=self.telemetry,
            client_timed=self._serve_client_timed,
            quant_stats=self.quant_stats).start()

    def restart_serve_server(self) -> None:
        """Replace a (possibly dead) server with a fresh one on the same
        endpoint; connected clients reconnect transparently (their
        retries drain into the replacement; the lost state cache resets
        served episodes to the episode-initial state, the same grace as
        an eviction). In fleet mode the chaos drill targets individual
        servers through kill/supervise instead — a full restart rebuilds
        the whole fleet."""
        if self.serve_fleet is not None:
            self.serve_fleet.stop()
            self.serve_fleet = None
        if self.serve_server is not None:
            self.serve_server.stop()
        self._start_serve_server()

    def install_shadow(self, candidate_channel, *,
                       sample_rate: Optional[float] = None, seed: int = 0):
        """Shadow-score a candidate server (ISSUE 20): mirror a sampled
        fraction of every routed live request batch to
        ``candidate_channel`` and feed greedy-agreement divergence into
        the quality block — the evidence ``PromotionManager.decide``
        gates on. Installs on every existing router AND every router
        spawned later; candidate replies never reach clients."""
        if self.quality_stats is None:
            raise RuntimeError("shadow scoring needs telemetry."
                               "quality_enabled (the quality plane)")
        if self.shadow is not None:
            raise RuntimeError("a shadow scorer is already installed — "
                               "clear_shadow() first")
        from r2d2_tpu.fleet.promotion import ShadowScorer
        rate = (self.cfg.serve.shadow_sample_rate
                if sample_rate is None else float(sample_rate))
        self.shadow = ShadowScorer(candidate_channel, self.quality_stats,
                                   sample_rate=rate, seed=seed).start()
        self._shadow_mirror = self.shadow.mirror
        for ch in self._routing_channels:
            ch.set_mirror(self._shadow_mirror)
        return self.shadow

    def clear_shadow(self) -> None:
        """Uninstall the shadow tap (promotion decided either way)."""
        if self.shadow is None:
            return
        for ch in self._routing_channels:
            ch.set_mirror(None)
        self._shadow_mirror = None
        self.shadow.stop()
        self.shadow = None

    def start_actors_threads(self, stop: threading.Event) -> None:
        cfg = self.cfg
        prep = self._publish_prep
        params0 = self.learner.train_state.params
        # quant mode publishes the inference bundle (f32 + twin + stamp)
        # through the SAME store; construction counts as publication 1.
        # Thread policies take their initial tree from store.current()
        # (one shared prepared tree, fresh across respawns)
        self.store = InProcWeightStore(
            prep(params0, 1) if prep else params0)
        publish = wrap_publish(
            self.store.publish, prep, lambda: self.store.publish_count)
        # weight fan-out tree (ISSUE 15): the learner publishes ONCE to
        # the root store; in-proc relays re-publish and each actor slot
        # reads its leaf relay — the root sees <= degree readers no
        # matter the fleet width. The published tree (incl. the stamped
        # quant bundle) rides through relays unchanged.
        if cfg.fleet.fanout_degree >= 2:
            from r2d2_tpu.fleet.fanout import FanoutTree
            self._fanout = FanoutTree(
                self.store, self.n_slots, cfg.fleet.fanout_degree,
                pull_interval_s=cfg.fleet.fanout_pull_interval_s)

            def publish_and_pump(params, _pub=publish):
                _pub(params)
                self._fanout.on_publish()
            publish = publish_and_pump
        self.learner.publish = publish
        # staleness clock (ISSUE 5): the learner half of sample-age =
        # publish count at flush − the block's generation stamp
        self.learner.weight_version_fn = lambda: self.store.publish_count
        self.queue = BlockQueue(use_mp=False)
        self._stop = stop
        self._actor_mode = "thread"
        if self.quality_stats is not None:
            # deployment plane (ISSUE 20): the promotion state machine
            # over THIS store/fan-out tree (its block rides the quality
            # record via stats.set_promotion), and the continuous-eval
            # client polling save_dir for new checkpoints — publish
            # stamps at eval time give the ledger its lineage.
            from r2d2_tpu.fleet.promotion import PromotionManager
            from r2d2_tpu.telemetry import QualityEvaluator
            self.promotion = PromotionManager(
                cfg.fleet, self.store, fanout=self._fanout,
                stats=self.quality_stats, save_dir=cfg.runtime.save_dir)
            self.quality_evaluator = QualityEvaluator(
                cfg, self.player_idx, self.quality_stats,
                interval_s=cfg.telemetry.quality_eval_interval_s,
                rounds=cfg.telemetry.quality_eval_rounds,
                clients=cfg.telemetry.quality_eval_clients,
                serve=(cfg.actor.inference == "server"),
                stamp_fn=lambda: self.store.publish_count).start()
        if self.serve_endpoint is not None:
            # thread-mode serving: the server polls the in-proc store
            # under its own reader id; clients share the stats object so
            # the serving block's latency is the CLIENT-visible round
            # trip (the SLO the chaos drill fires on)
            self._serve_weight_poll = lambda: self.store.poll("serve")
            self._serve_weight_version = \
                lambda: self.store.reader_version("serve")
            # fleet mode: each server slot is its OWN store reader
            # ("serve0", "serve1", ...) so the slots' weight adoption
            # and staleness stamps stay independent
            self._serve_weight_poll_factory = (
                lambda slot: (lambda: self.store.poll(f"serve{slot}")))
            self._serve_weight_version_factory = (
                lambda slot: (
                    lambda: self.store.reader_version(f"serve{slot}")))
            self._serve_copy_updates = True
            self._serve_client_timed = True
            self._start_serve_server()
        for i in range(cfg.actor.num_actors):
            self._spawn_thread_actor(i)
        while len(self.threads) < self.n_slots:
            self.threads.append(_VacantSlot())
        self._start_lease_server()

    def _spawn_thread_actor(self, i: int) -> threading.Thread:
        cfg = self.cfg
        seed = cfg.runtime.seed + 10_000 * self.player_idx + 100 * i
        # scalar (run_actor) or vectorized (run_vector_actor) per
        # cfg.actor.envs_per_actor — one shared construction path with the
        # spawned actor process and the throughput bench (actor_loop.py)
        # env_factory=create_env: route lane construction through THIS
        # module's symbol so tests can monkeypatch it
        env = make_actor_env(cfg, self.player_idx, i, seed,
                             env_factory=create_env,
                             num_players=cfg.multiplayer.num_players,
                             **self.actor_env_args(i))

        # per-spawn cancel event: the hang watchdog cannot kill a thread,
        # so it sets this and abandons the incarnation — a thread that
        # ever unwedges sees should_stop and exits instead of double-
        # feeding its slot
        cancel = threading.Event()

        def should_stop(cancel=cancel):
            return self._stop.is_set() or cancel.is_set()

        if self.serve_fleet is not None:
            # sharded serving: a routing channel over ALL fleet
            # endpoints — requests aim by client-id hash and re-aim on
            # MISROUTED bounces as the fleet grows/shrinks
            serve_channel = self.serve_fleet.connect()
            self._routing_channels.append(serve_channel)
            if self._shadow_mirror is not None:
                serve_channel.set_mirror(self._shadow_mirror)
        elif self.serve_endpoint is not None:
            serve_channel = self.serve_endpoint.connect()
        else:
            serve_channel = None
        # weight distribution endpoints for this slot: its leaf relay of
        # the fan-out tree when configured (ISSUE 15), the root store
        # directly otherwise — identical (poll, version, current) shapes
        if self._fanout is not None:
            fo_poll, fo_version, fo_current = self._fanout.endpoints(i)
        elif self.store is not None:
            fo_poll = (lambda reader_id=i: self.store.poll(reader_id))
            fo_version = (
                lambda reader_id=i: self.store.reader_version(reader_id))
            fo_current = (
                lambda reader_id=i: self.store.current(reader_id=reader_id))
        else:
            fo_poll = fo_version = fo_current = None
        # initial params: the distribution plane's CURRENT published
        # tree — already prepared (the quant bundle; no per-policy
        # requantization) AND fresh on a mid-training respawn/adoption,
        # whose dead predecessor consumed the slot's reader version so
        # its first poll() would return None; adopting here also fixes
        # the staleness stamp
        init_params = (fo_current() if fo_current is not None
                       else self.learner.train_state.params)
        policy, run_loop = make_actor_policy(
            cfg, self.net, init_params, i, seed,
            total_actors=self.n_slots,
            serve_channel=serve_channel, serve_stats=self.serve_stats,
            should_stop=should_stop, quant_stats=self.quant_stats)

        from r2d2_tpu.runtime.actor_loop import instrument_block_sink
        self.heartbeats.reset_slot(i)
        if serve_channel is not None:
            # served inference: the SERVER owns weight sync; the block's
            # staleness stamp is the publish count riding each reply
            weight_version = lambda: policy.weight_version  # noqa: E731
            weight_poll = lambda: None                      # noqa: E731
        else:
            # generation stamp: the version this slot's distribution
            # endpoint last adopted (relay-aware: a lagging relay's
            # consumers stamp OLDER versions, which is the truth)
            weight_version = fo_version
            weight_poll = fo_poll
        quality_feed = None
        if self.quality_stats is not None:
            # Q-calibration tap (ISSUE 20): the slot's LocalBuffers feed
            # predicted-vs-realized gaps, stamped with the version this
            # slot is acting with (the PR-5 lineage join)
            from r2d2_tpu.replay.structs import ReplaySpec
            from r2d2_tpu.telemetry import make_calibration_feed
            quality_feed = make_calibration_feed(
                self.quality_stats, gamma=cfg.optim.gamma,
                n_steps=ReplaySpec.from_config(cfg).forward,
                sample_every=cfg.telemetry.quality_calib_sample_every,
                stamp_fn=weight_version)
        sink = instrument_block_sink(
            cfg, i,
            lambda b: self.queue.put_patient(
                b, should_stop,
                beat=lambda: self.heartbeats.touch(i),
                telemetry=self.telemetry),
            board=self.heartbeats, telemetry=self.telemetry,
            weight_version=weight_version,
            # lane provenance (ISSUE 10): worker i owns the contiguous
            # global-ladder slice [i*k, (i+1)*k) — the same layout
            # vector_lane_epsilons spreads ε over, and the identity a
            # joiner adopts with the slot (ISSUE 15)
            lane_base=i * cfg.actor.envs_per_actor,
            # injected 'leave' faults park the slot for re-adoption
            # BEFORE the worker unwinds (tools/chaos.py ChaosLeave);
            # the generation gates leave injection to the slot's
            # ORIGINAL worker — an adopted incarnation is a new worker
            on_leave=lambda: self._on_worker_leave(i),
            generation=self.membership.generation(i))

        def loop(env=env, policy=policy, run_loop=run_loop,
                 weight_poll=weight_poll, sink=sink,
                 should_stop=should_stop, quality_feed=quality_feed):
            from r2d2_tpu.tools.chaos import ChaosLeave

            # the run loop owns env and closes it on every exit
            try:
                run_loop(cfg, env, policy,
                         block_sink=sink,
                         weight_poll=weight_poll,
                         should_stop=should_stop,
                         telemetry=self.telemetry,
                         quality_feed=quality_feed)
            except ChaosLeave:
                # deliberate departure (ISSUE 15): the slot already
                # parked via on_leave — unwind quietly, not as a crash
                pass
            except Exception:
                # a served policy raising ServeUnavailable DURING
                # shutdown is the clean-stop path, not a failure
                if not should_stop():
                    raise

        t = threading.Thread(target=loop, daemon=True,
                             name=f"actor-p{self.player_idx}-{i}")
        t.health_cancel = cancel
        t.start()
        if i < len(self.threads):
            self.threads[i] = t
        else:
            self.threads.append(t)
        return t

    def start_actors_processes(self, stop_event) -> None:
        cfg = self.cfg
        self._ctx = mp.get_context("spawn")
        prep = self._publish_prep
        params0 = self.learner.train_state.params
        self.publisher = WeightPublisher(
            prep(params0, 1) if prep else params0)
        publish = wrap_publish(
            self.publisher.publish, prep,
            lambda: self.publisher.publish_count)
        # shm fan-out tree (ISSUE 15): relay nodes re-publish the root
        # segment into their own segments; each actor process attaches
        # to its leaf relay's segment name through the unchanged
        # actor_main plumbing. Pumped on every publish + the supervise
        # cadence.
        if cfg.fleet.fanout_degree >= 2:
            from r2d2_tpu.fleet.fanout import ShmFanout
            template = prep(params0, 0) if prep else params0
            self._shm_fanout = ShmFanout(
                self.publisher.name, template, self.n_slots,
                cfg.fleet.fanout_degree)
            self._shm_fanout.pump()   # relays adopt the initial publish

            def publish_and_pump(params, _pub=publish):
                _pub(params)
                self._shm_fanout.pump()
            publish = publish_and_pump
        self.learner.publish = publish
        self.learner.weight_version_fn = \
            lambda: self.publisher.publish_count
        self.queue = BlockQueue(
            use_mp=True, ctx=self._ctx,
            shm_spec=self.learner.spec if cfg.runtime.shm_transport else None,
            tracing=(cfg.telemetry.enabled
                     and cfg.telemetry.tracing_enabled))
        self._stop = stop_event
        self._actor_mode = "process"
        if self.serve_endpoint is not None:
            self._start_serve_transport()
        for i in range(cfg.actor.num_actors):
            self._spawn_process_actor(i)
        while len(self.processes) < self.n_slots:
            self.processes.append(_VacantSlot())
        self._start_lease_server()

    def _start_serve_transport(self) -> None:
        """Process-mode serving: the server lives in THIS (learner)
        process and actor processes reach it over the transport ladder —
        the shm request/reply rings by default (the shm_feeder
        discipline), TCP loopback when forced or when the native
        toolchain is unavailable. The server reads weights through a
        WeightSubscriber on the existing publisher segment (one more
        reader, zero new mechanisms)."""
        cfg = self.cfg
        from r2d2_tpu.runtime.weights import WeightSubscriber
        # the subscriber template must match the PUBLISHED tree — the
        # inference bundle in quant mode (stamp value irrelevant: the
        # template only provides structure)
        template = self.learner.train_state.params
        if self._publish_prep is not None:
            template = self._publish_prep(template, 0)
        if cfg.serve.servers > 1:
            # sharded serving over processes (ISSUE 17): sockets only
            # (config validation rejects shm + servers>1 — the shm rings
            # are single-consumer). Each fleet slot reads weights through
            # its OWN WeightSubscriber (independent adoption cursors) and
            # listens on its own TCP port; the spec ships the full
            # address map + the initial shard assignment so actor
            # processes build a RoutingChannel without a handshake.
            subs = {}

            def _sub_for(slot):
                if slot not in subs:
                    s = WeightSubscriber(self.publisher.name, template)
                    subs[slot] = s
                    self._serve_weight_subs.append(s)
                return subs[slot]

            self._serve_weight_poll_factory = \
                lambda slot: _sub_for(slot).poll
            self._serve_weight_version_factory = (
                lambda slot: (lambda: _sub_for(slot).publish_count))
            self._serve_copy_updates = False
            self._serve_client_timed = False
            self._start_serve_server()     # builds the ServerFleet
            from r2d2_tpu.serve import SocketServerTransport
            servers = {}
            for slot, ep in self.serve_fleet.serve_spec_servers().items():
                port = cfg.serve.port + slot if cfg.serve.port else 0
                t = SocketServerTransport(ep.submit, cfg.serve.host, port)
                self._serve_fleet_transports.append(t)
                servers[slot] = (t.host, t.port)
            self._serve_spec = {
                "transport": "socket_fleet",
                "servers": servers,
                "total_shards": self.serve_fleet.total_shards,
                "assign": self.serve_fleet.shard_map.to_wire(),
            }
            return
        sub = WeightSubscriber(self.publisher.name, template)
        self._serve_weight_sub = sub
        self._serve_weight_poll = sub.poll
        self._serve_weight_version = lambda: sub.publish_count
        # WeightSubscriber.poll materializes a fresh copy per poll — the
        # server may own those buffers directly (actor_main's reasoning)
        self._serve_copy_updates = False
        # clients are in other processes: the server times request
        # latency itself (receive→reply; client timeouts still reach the
        # histogram through the chaos drill's in-proc path)
        self._serve_client_timed = False
        reply_slots = max(cfg.serve.reply_ring_slots,
                          cfg.actor.envs_per_actor)
        if cfg.serve.transport in ("auto", "shm"):
            try:
                from r2d2_tpu.serve import ShmServeTransport
                self._serve_transport = ShmServeTransport(
                    self.serve_endpoint.submit,
                    (cfg.env.frame_height, cfg.env.frame_width),
                    self.net.action_dim, self.net.state_half,
                    request_slots=cfg.serve.request_ring_slots,
                    tracing=(cfg.telemetry.enabled
                             and cfg.telemetry.tracing_enabled))
                self._serve_spec = {
                    "transport": "shm",
                    "request_ring": self._serve_transport.request_ring,
                    "action_dim": self.net.action_dim,
                    "hidden_dim": self.net.state_half,
                    "reply_slots": reply_slots,
                }
            except Exception as e:
                if cfg.serve.transport == "shm":
                    raise
                import logging
                logging.getLogger(__name__).warning(
                    "native shm serve transport unavailable (%s); "
                    "falling back to TCP loopback", e)
        if self._serve_spec is None:
            from r2d2_tpu.serve import SocketServerTransport
            self._serve_transport = SocketServerTransport(
                self.serve_endpoint.submit, cfg.serve.host, cfg.serve.port)
            self._serve_spec = {
                "transport": "socket",
                "host": self._serve_transport.host,
                "port": self._serve_transport.port,
            }
        self._start_serve_server()

    def _spawn_process_actor(self, i: int) -> mp.Process:
        cfg = self.cfg
        # the ε ladder spans the fleet's MAX width (n_slots == num_actors
        # unless fleet.max_slots reserves spares), so the exploration
        # schedule is fixed as the fleet churns
        eps = apex_epsilon(i, self.n_slots, cfg.actor.base_eps,
                           cfg.actor.eps_alpha)
        self.heartbeats.reset_slot(i)
        if self.tele_board is not None:
            # fresh incarnation: cumulative telemetry counts restart at
            # zero (the aggregator's reset detection handles the edge)
            self.tele_board.reset_slot(i)
        # weight segment: the slot's leaf relay under the shm fan-out
        # tree, the root publisher otherwise (identical subscriber API)
        shm_name = (self._shm_fanout.segment_for(i)
                    if self._shm_fanout is not None
                    else self.publisher.name)
        p = self._ctx.Process(
            target=actor_process_main,
            args=(cfg.to_dict(), self.player_idx, i, eps,
                  shm_name, self.queue._q, self._stop),
            kwargs={**self.actor_env_args(i),
                    "total_actors": self.n_slots,
                    "health_board": self.heartbeats, "health_slot": i,
                    "telemetry_board": self.tele_board,
                    "serve_spec": self._serve_spec,
                    "generation": self.membership.generation(i)},
            daemon=True, name=f"actor-p{self.player_idx}-{i}")
        p.start()
        if i < len(self.processes):
            self.processes[i] = p
        else:
            self.processes.append(p)
        return p

    def supervise(self) -> int:
        """One health pass: restart dead actors (the reference has no
        failure handling at all — a crashed Ray actor silently reduces
        throughput forever, SURVEY §5.3), kill+respawn HUNG ones (alive
        but heartbeat-stale), apply per-slot restart backoff and the
        crash-loop breaker, run the ingest stall detector, and surface the
        counters in TrainMetrics. Returns the number of restarts performed.

        Shm-ring slot reclamation runs for every NEWLY-failed actor
        process regardless of runtime.restart_dead_actors (round-3 advisor):
        a producer that died between reserve and commit wedges the ring head
        slot whether or not it gets respawned, and with restarts off the
        learner would otherwise starve even with other actors alive."""
        from r2d2_tpu.runtime.feeder import supervise_workers
        if self._stop.is_set():
            return 0
        if self.resources is not None:
            # resource sampling rides the supervision cadence (a cheap
            # time check; the sample itself is a handful of dict reads
            # per telemetry.resources_interval_s)
            self.resources.maybe_sample()
        if self.compile_monitor is not None and self.learner.training_steps:
            # warm-up ends when training has started: the train program
            # and the actor policies have compiled by now, so any further
            # compile of a known fn with new avals is a retrace (mark_warm
            # is idempotent — called every pass, latches once)
            self.compile_monitor.mark_warm()
        if self._shm_fanout is not None:
            # relay propagation rides the supervise cadence too, so a
            # publish between supervision passes still reaches leaves
            # promptly even if the publish-time pump raced a subscriber
            self._shm_fanout.pump()
        restart = self.cfg.runtime.restart_dead_actors
        # elastic membership (ISSUE 15): a dead/left worker's slot PARKS
        # for re-adoption instead of respawning in place — joiners
        # (join_actor / the grammar's join@t schedule) re-admit it
        park = self._park_slot if self.cfg.fleet.elastic else None
        restarted = 0
        if self.serve_fleet is not None:
            # serving-fleet health rides the same cadence (ISSUE 17): a
            # dead server's slot parks, survivors adopt its orphaned
            # cache shards, and clients re-route off MISROUTED bounces
            restarted += self.serve_fleet.supervise()
        # threads are scanned even with restarts off (respawn=None), like
        # processes below: the hang watchdog must still flag a wedged
        # thread and feed the failure counters — restart_dead_actors
        # gates RESPAWNING, not detection
        restarted += supervise_workers(
            self.threads, self._seen_dead,
            respawn=(self._spawn_thread_actor
                     if restart and park is None else None),
            health=self.health, park=park)
        restarted += supervise_workers(
            self.processes, self._seen_dead,
            respawn=(self._spawn_process_actor
                     if restart and park is None else None),
            ring=self._ring_recovery,
            health=self.health, park=park)
        self.health.ring_slots_recovered += self._ring_recovery.tick(
            self.queue)
        # grammar-scheduled joins (join@t=S): admit once the slot is
        # parked/free and the schedule time elapsed
        if self._join_schedule:
            from r2d2_tpu.fleet.membership import SLOT_ACTIVE
            now_rel = time.time() - self._run_start
            for slot, fault in self._join_schedule.items():
                if slot in self._joins_done or now_rel < fault.t:
                    continue
                if self.membership.state(slot) == SLOT_ACTIVE:
                    continue       # still occupied; retry next pass
                self.join_actor(slot)
                self._joins_done.add(slot)
                restarted += 1
        workers = self.processes or self.threads
        self._stall.check(
            self.metrics.ingest_blocks_total,
            sum(1 for w in workers if w.is_alive()),
            self.learner.ingestion_paused,
            diagnostics=self._stall_diagnostics)
        self.metrics.set_actor_health(
            {**self.health.snapshot(),
             "ingest_stall_dumps": self._stall.dumps})
        return restarted

    # -- elastic membership (ISSUE 15) --

    def _on_worker_leave(self, slot: int) -> None:
        """The sink's on_leave hook (an injected ``leave`` fault): park
        the slot BEFORE the worker unwinds, so the supervisor sees a
        detached slot, never a crash."""
        self.membership.park(slot, reason="left")
        self.health.detach(slot)

    def _park_slot(self, slot: int, hung: bool) -> None:
        """Elastic supervision policy: a dead (or watchdog-killed hung)
        worker's slot parks for re-adoption — no in-place respawn, no
        backoff ladder; training continues on the remaining fleet."""
        import logging
        self.membership.park(slot, reason="hung" if hung else "died")
        self.health.detach(slot)
        logging.getLogger(__name__).warning(
            "elastic fleet: worker slot %d %s — slot PARKED for "
            "re-adoption (active fleet now %d/%d)", slot,
            "hung" if hung else "died",
            len(self.membership.active_slots()), self.n_slots)

    def leave_actor(self, slot: int) -> None:
        """Deliberate departure: park the slot's lease and stop its
        worker. The slot's lane range / ε slice / replay routing are
        preserved for the next joiner; the learner keeps training on
        the remaining fleet."""
        from r2d2_tpu.runtime.feeder import kill_worker
        self.membership.park(slot, reason="left")
        self.health.detach(slot)
        workers = self.processes if self.processes else self.threads
        if slot < len(workers):
            w = workers[slot]
            if not isinstance(w, _VacantSlot):
                kill_worker(w)
                self._seen_dead.add(w)

    def join_actor(self, slot: Optional[int] = None):
        """Admit a joiner into a RUNNING fleet: lease a parked (or
        spare) slot and spawn a worker that adopts its full identity —
        heartbeat row, lane range, ε-ladder slice, replay routing. The
        new worker reads weights through the slot's distribution
        endpoint (leaf relay under fan-out) and its blocks carry the
        adopted lane stamps, so provenance checks span the churn."""
        lease = self.membership.lease(slot)
        i = lease.slot
        self.health.attach(i)
        corpse = None
        workers = self.processes if self._actor_mode == "process" \
            else self.threads
        if i < len(workers):
            corpse = workers[i]
        if self._actor_mode == "process":
            self._spawn_process_actor(i)
        else:
            self._spawn_thread_actor(i)
        if corpse is not None:
            self._seen_dead.discard(corpse)
        return lease

    def _start_lease_server(self) -> None:
        """Socket face of the lease table (ROADMAP 2c; gated on
        ``fleet.lease_transport == "socket"``): ``cli/join.py`` dials
        this to admit an acting worker into the running fleet — the SAME
        ``join_actor`` slot-adoption path the in-process join schedule
        uses — or to grow/shrink the serving fleet (ISSUE 17)."""
        if self.cfg.fleet.lease_transport != "socket":
            return
        from r2d2_tpu.fleet.membership import MembershipServer

        def _join(slot=None):
            lease = self.join_actor(slot)
            return {"slot": lease.slot, "generation": lease.generation,
                    "lane_base": lease.lane_base, "lanes": lease.lanes,
                    "shard_key": lease.shard_key}

        def _leave(slot):
            self.leave_actor(int(slot))
            return {"slot": int(slot)}

        def _grow_serve():
            return {"slot": self.grow_serve_server(),
                    "servers": sorted(self.serve_fleet.servers)}

        def _shrink_serve(slot=None):
            return {"slot": self.shrink_serve_server(slot),
                    "servers": sorted(self.serve_fleet.servers)}

        def _announce_replay(host, port, shards=None, step=None,
                             anchor_wall=None):
            # ISSUE 18: a (re)started ReplayService re-registers its
            # address after restoring from snapshot — producers that
            # lost their socket rediscover the survivor via 'info'.
            # ISSUE 19: the announcement is also the clock-anchor
            # exchange — the board echoes ITS wall clock at receipt, so
            # the announcer can estimate its skew against the learner
            # plane (offset ≈ anchor_wall - board_wall, good to ±RTT/2)
            # without any shared monotonic clock.
            self._replay_announce = {"host": str(host), "port": int(port),
                                     "shards": shards, "step": step,
                                     "t": time.time()}
            if anchor_wall is not None:
                self._replay_announce["anchor_wall"] = float(anchor_wall)
            return {"ok": True, "board_wall": time.time()}

        def _info():
            info = {"membership": self.membership.snapshot(),
                    "actor_mode": self._actor_mode}
            if self._replay_announce is not None:
                info["replay_service"] = self._replay_announce
            if self.promotion is not None:
                # cli/promote.py --status dials this
                info["promotion"] = self.promotion.block()
            if self.serve_fleet is not None:
                info["serving"] = {
                    "servers": sorted(self.serve_fleet.servers),
                    "map_version": self.serve_fleet.shard_map.version,
                }
            if (self._serve_spec is not None
                    and self._serve_spec.get("transport") != "shm"):
                # socket specs travel (a joiner can dial the servers);
                # the shm spec's ring handle is same-host/spawn-only
                info["serve_spec"] = self._serve_spec
            return info

        self._lease_server = MembershipServer(
            {"join": _join, "leave": _leave, "grow_serve": _grow_serve,
             "shrink_serve": _shrink_serve, "info": _info,
             "announce_replay": _announce_replay},
            host=self.cfg.fleet.lease_host,
            port=self.cfg.fleet.lease_port)
        import logging
        logging.getLogger(__name__).info(
            "fleet lease API on %s:%d", self._lease_server.host,
            self._lease_server.port)

    def grow_serve_server(self) -> int:
        """Elastic serving fleet (ISSUE 17): lease a parked/free server
        slot, re-slice the shard map, and hand the boundary shard groups
        to the new server. Returns the grown slot."""
        if self.serve_fleet is None:
            raise RuntimeError("grow_serve_server requires serve.servers"
                               " > 1 (a running ServerFleet)")
        return self.serve_fleet.grow_server()

    def shrink_serve_server(self, slot: Optional[int] = None) -> int:
        """Retire a serving-fleet server: its shard groups rehome to the
        survivors (leases, op-dedup and hidden state ride along), then
        the slot parks. Returns the retired slot."""
        if self.serve_fleet is None:
            raise RuntimeError("shrink_serve_server requires serve.servers"
                               " > 1 (a running ServerFleet)")
        return self.serve_fleet.shrink_server(slot)

    def _replay_service_block(self):
        """The record's ``replay_service`` block: shard/spill health
        from the learner's service, fan-out relay stats, membership
        lease counts (orphan horizon = 2x the hang timeout — a leased
        slot silent that long has no supervision verdict coming)."""
        block = {}
        if self.learner.service is not None:
            block.update(self.learner.service.interval_block())
        if self._service_server is not None:
            # windowed socket rung (ISSUE 16): per-interval frame/block
            # counts, max in-flight window occupancy, injected ack drops
            block["socket"] = self._service_server.interval_stats()
        if self._fanout is not None:
            block["fanout"] = self._fanout.stats()
        elif self._shm_fanout is not None:
            block["fanout"] = self._shm_fanout.stats(
                self.publisher.publish_count)
        horizon = 2.0 * self.cfg.runtime.hang_timeout_s
        block["membership"] = self.membership.snapshot(
            self.heartbeats.ages() if self.heartbeats is not None else None,
            orphan_horizon_s=horizon)
        return block

    def _stall_diagnostics(self) -> dict:
        """Snapshot for the one-shot stall dump: who was alive, how stale
        each heartbeat was, and where the pipeline stood."""
        lr = self.learner
        workers = self.processes or self.threads
        return {
            "heartbeat_ages_s": [round(float(a), 1)
                                 for a in self.heartbeats.ages()],
            "heartbeat_counts": [int(c) for c in self.heartbeats.counts()],
            "workers_alive": [w.is_alive() for w in workers],
            "parked_slots": [i for i in range(self.cfg.actor.num_actors)
                             if self.health.is_parked(i)],
            "queue_depth": self.queue.qsize() if self.queue else -1,
            "buffer_steps": lr.ring.buffer_steps,
            "staged_blocks": lr._staged_blocks,
            "ingestion_paused": lr.ingestion_paused,
            "training_steps": lr.training_steps,
        }

    def close(self) -> None:
        self.learner.stop_background()
        if self.quality_evaluator is not None:
            self.quality_evaluator.stop()
        self.clear_shadow()
        if self._lease_server is not None:
            self._lease_server.close()
        if self._service_server is not None:
            self._service_server.close()
        if self.serve_server is not None:
            self.serve_server.stop()
        if self.serve_fleet is not None:
            self.serve_fleet.stop()
        if self._serve_transport is not None:
            self._serve_transport.close()
        for t in self._serve_fleet_transports:
            t.close()
        if self._serve_weight_sub is not None:
            self._serve_weight_sub.close()
        for s in self._serve_weight_subs:
            s.close()
        if self._shm_fanout is not None:
            # relays close BEFORE the root publisher: each holds a
            # subscriber on the root (or a parent relay's) segment
            self._shm_fanout.close()
        if self.publisher is not None:
            self.publisher.close()
        for p in self.processes:
            if isinstance(p, _VacantSlot):
                continue           # spare membership slot, never spawned
            p.join(timeout=5.0)
            if p.is_alive():
                p.terminate()
                p.join(timeout=2.0)
            if p.is_alive():
                # terminate ignored (wedged engine child): escalate so a
                # zombie never outlives the run
                p.kill()
                p.join(timeout=2.0)
        # join thread actors too: a daemon actor thread still inside an XLA
        # compile when the interpreter exits dies with a C++ abort
        # ("FATAL: exception not rethrown") — harmless but alarming noise
        for t in self.threads:
            if isinstance(t, _VacantSlot):
                continue
            t.join(timeout=5.0)
        if self.queue is not None:
            self.queue.close()   # releases/unlinks the shm ring (owner)
        self.heartbeats.close()  # releases/unlinks the heartbeat board
        self.telemetry.close()   # stops the drain thread, final flush
        if self.tele_board is not None:
            self.tele_board.close()


def train(cfg: Config, *, max_training_steps: Optional[int] = None,
          max_seconds: Optional[float] = None, actor_mode: str = "thread",
          log_fn: Callable[[dict], None] = None) -> List[PlayerStack]:
    """Run the full system; returns the player stacks (learners hold final
    state). Blocking — the reference's train.py never returns either
    (train.py:60-66); here max_training_steps / max_seconds bound the run."""
    assert actor_mode in ("thread", "process")
    if cfg.actor.on_device:
        # Anakin-style fully on-device acting (ISSUE 6): the fused
        # act+train loop replaces the whole actor fleet — no threads, no
        # processes, no block queue, no weight service (actor_mode is
        # moot). Everything below this guard is the legacy path,
        # byte-identical when the knob is off.
        from r2d2_tpu.runtime.anakin_loop import run_anakin_train
        return run_anakin_train(cfg, max_training_steps=max_training_steps,
                                max_seconds=max_seconds, log_fn=log_fn)
    if cfg.mesh.multihost:
        # DCN bring-up BEFORE any backend use, so jax.devices() sees the
        # whole slice (SURVEY §5.8; validated by the two-process loopback
        # dryrun in parallel/multihost_dryrun.py). Every host runs this
        # same train() as an SPMD controller. This single-controller loop
        # dispatches at its own cadence, which multi-controller JAX cannot
        # tolerate — multi-process jobs must use the rank-aware lockstep
        # loop instead (parallel/multihost.py; cli/train.py routes there
        # automatically).
        if cfg.mesh.num_processes > 1:
            raise NotImplementedError(
                "mesh.multihost training with num_processes > 1 must go "
                "through r2d2_tpu.parallel.multihost.train_multihost (the "
                "lockstep multi-controller loop; cli/train.py routes there "
                "automatically) — this single-controller train() would "
                "dispatch collective programs at diverging per-host "
                "cadences.")
        from r2d2_tpu.parallel import init_distributed
        init_distributed(cfg.mesh)
    num_players = cfg.multiplayer.num_players if cfg.multiplayer.enabled else 1

    # probe env for the action dim (ref worker.py:259 creates a throwaway env)
    probe = create_env(cfg.env, seed=cfg.runtime.seed)
    action_dim = probe.action_space.n
    probe.close()

    if actor_mode == "thread":
        stop = threading.Event()
    else:
        stop = mp.get_context("spawn").Event()

    # Map external SIGTERM/SIGINT onto the clean stop path, so a stopped run
    # still writes its final checkpoint and unlinks its shm segments. Only
    # the main thread may install handlers; restored below.
    prev_handlers = {}
    stacks: List[PlayerStack] = []
    # profiler capture triggers (telemetry/profiler.CaptureTriggers —
    # ONE shared implementation with the fused anakin loop, ISSUE 9):
    # legacy first-interval (profile_dir set), runtime.profile_at_step
    # (one-shot, fires when the learner step counter first reaches it),
    # and SIGUSR2 (on demand, any number of times). Start/stop are
    # idempotent, so the finally below can always uninstall without
    # tracking which trigger started a capture.
    from r2d2_tpu.telemetry.profiler import CaptureTriggers
    triggers = CaptureTriggers(cfg.runtime)
    try:
        # Everything after handler installation sits inside this try so the
        # finally always restores them — even when stack construction or
        # actor startup raises.
        if threading.current_thread() is threading.main_thread():
            def _on_signal(signum, frame):
                if stop.is_set():
                    # Second signal: the clean path is already requested but
                    # may be blocked inside a wedged device call — restore
                    # the previous handler so a repeated Ctrl+C/SIGTERM can
                    # still interrupt rather than being swallowed forever.
                    prev = prev_handlers.get(signum) or signal.SIG_DFL
                    signal.signal(signum, prev)
                    if signum == signal.SIGINT:
                        raise KeyboardInterrupt
                    return
                stop.set()
            for sig in (signal.SIGTERM, signal.SIGINT):
                try:
                    prev_handlers[sig] = signal.signal(sig, _on_signal)
                except (ValueError, OSError):
                    pass

        # SIGUSR2 flag handler (main-thread check inside; restore in
        # triggers.uninstall — the handler only flags, the loop starts
        # the capture outside signal context)
        triggers.install()

        # player_id >= 0: this job runs exactly ONE player of the
        # population (per-player-job composition — README "Multiplayer at
        # pod scale"); the player index still feeds the host/join wiring
        # and seed offsets, so N such jobs reproduce the in-process
        # population stack-for-stack.
        if cfg.multiplayer.enabled and cfg.multiplayer.player_id >= 0:
            player_indices = [cfg.multiplayer.player_id]
        else:
            player_indices = list(range(num_players))
        # appended one-by-one (not a comprehension): PlayerStack.__init__
        # allocates the heartbeat shm segment, and the finally below only
        # closes stacks that made it into the list — a mid-population
        # construction failure must not leak the earlier stacks' segments
        for p in player_indices:
            stacks.append(PlayerStack(cfg, p, action_dim))
        from r2d2_tpu.utils.platform import announce_runtime
        announce_runtime(cfg, stacks[0].metrics.logger)
        for st in stacks:
            if actor_mode == "thread":
                st.start_actors_threads(stop)
            else:
                st.start_actors_processes(stop)

        start = time.time()
        deadline = start + max_seconds if max_seconds else None
        max_steps = max_training_steps or cfg.optim.training_steps
        last_log = last_supervise = start

        def timed_out() -> bool:
            return deadline is not None and time.time() > deadline

        def supervise_due() -> bool:
            # supervision runs on its own cadence, decoupled from the log
            # interval, in BOTH loops — an actor that dies or hangs before
            # learning_starts used to go unsupervised and wedge warm-up
            # until the deadline
            nonlocal last_supervise
            if time.time() - last_supervise < cfg.runtime.supervise_interval_s:
                return False
            last_supervise = time.time()
            return True

        # warm-up: fill buffers to learning_starts (ref train.py:49-54).
        # drain() bursts at replay.drain_max_blocks here AND in the
        # training loop below — one knob, no silently different warm-up
        # rate — and routes to the pipelined stager when
        # replay.ingest_batch_blocks > 1.
        while (not all(st.learner.ready for st in stacks) and not timed_out()
               and not stop.is_set()):
            for st in stacks:
                st.learner.drain(st.queue)
            if supervise_due():
                for st in stacks:
                    st.supervise()
            time.sleep(0.02)

        # initial step-0 checkpoint (ref worker.py:311)
        for st in stacks:
            if cfg.runtime.save_interval:
                st.learner.save(0)

        # optional jax.profiler trace of the first training interval
        # (SURVEY §5.1 — the reference has no profiling at all); capture
        # lifecycle owned by ProfilerCapture so an exception anywhere can
        # neither leave a trace running nor stop a dead one
        triggers.start_first_interval()

        while (not timed_out() and not stop.is_set()
               and any(st.learner.training_steps < max_steps for st in stacks)):
            for st in stacks:
                st.learner.drain(st.queue)
                if st.learner.ready and st.learner.training_steps < max_steps:
                    st.learner.step()
            now = time.time()
            # mid-run capture triggers: end an elapsed window, fire the
            # one-shot profile_at_step when ANY player's step counter
            # first crosses it, service a pending SIGUSR2 request
            triggers.poll(now, max(
                (st.learner.training_steps for st in stacks), default=0))
            if supervise_due():
                for st in stacks:
                    st.supervise()
            if now - last_log >= cfg.runtime.log_interval:
                for st in stacks:
                    st.learner.flush_metrics()
                    record = st.metrics.log(now - last_log)
                    if log_fn:
                        log_fn({"player": st.player_idx, **record})
                last_log = now
        for st in stacks:
            st.learner.flush_metrics()
    finally:
        triggers.uninstall()  # stop any live capture, restore SIGUSR2
        stop.set()
        for st in stacks:
            # preemption-safe final checkpoint: a clean stop (SIGTERM/
            # SIGINT or deadline) between periodic saves would otherwise
            # resume from the last interval boundary, replaying work
            try:
                if cfg.runtime.save_interval:
                    st.learner.save_final()
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "final checkpoint for player %d failed", st.player_idx)
            st.close()
        for sig, handler in prev_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass
    return stacks
