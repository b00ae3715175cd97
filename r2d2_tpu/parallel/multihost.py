"""Rank-aware multi-controller training over a multi-host mesh.

This is the production DCN scaling path (SURVEY §5.8): every process
(host) is one JAX controller over its local chips; ``jax.distributed``
stitches them into one global mesh whose 'dp' axis spans all chips. The
reference has no analog — its scaling unit is one learner process on half
a GPU (/root/reference/worker.py:251), and scaling actors beyond one
machine would need a Ray cluster it never configures.

Design:

  * **Each host owns its own actors** (Ape-X ε ladder over the GLOBAL
    actor index), its own feeder queue, and its own weight store. Blocks
    feed only the host's local replay shards — zero cross-host experience
    traffic; the gradient ``pmean`` inside the sharded step is the only
    per-step DCN collective.
  * **Lockstep by construction.** Multi-controller JAX requires every
    process to enter the same compiled programs in the same order. Every
    loop iteration dispatches exactly one ``lockstep_ingest`` program
    (per-shard conditional ring-writes + psum'd global counters + stop
    consensus), reads back its REPLICATED outputs (identical on every
    host by construction), and — iff those say ready — dispatches exactly
    one sharded train step. Every control-flow decision derives from
    replicated values, so every host takes the same branch; host-local
    timing (queue depth, sleeps, signals) only changes iteration *data*,
    never dispatch *order*.
  * **Stop consensus**: each host contributes a local stop flag (signal,
    deadline) to the ingest program; the psum makes any host's stop
    everyone's stop on the same iteration — no host is left blocked in a
    collective whose peers exited.
  * **Rank 0 de-duplicates side effects**: checkpoints and metrics logs
    (params are replicated bit-identically everywhere, so this loses
    nothing).
  * **Fleet observability** (ISSUE 12, ``telemetry.fleet_enabled``):
    the lockstep row carries per-rank step-time gauges (straggler
    argmax in-graph, zero extra DCN dispatches), every rank measures
    compute vs blocked-in-collective time and runs a local AlertEngine
    (ranks > 0: firings -> alerts_host{r}.jsonl), and rank 0's
    FleetAggregator merges host rows into the record's ``fleet`` block
    — see telemetry/fleet.py and README "Fleet observability".

Scope: thread- OR process-mode actors (process mode gives each host a
spawned CPU-pinned actor fleet fed through the native shm ring, exactly
like the single-host orchestrator), device OR host replay placement
(host = one reference-style CPU HostReplay per process feeding the GSPMD
external-batch step per-step, with a tiny psum consensus program instead
of lockstep_ingest — make_lockstep_consensus), single
player, dp x mp meshes (mesh.mp > 1 feature-shards the wide params over
mp via the GSPMD learner step and GSPMD lockstep ingest; mp must divide
each host's device count so every dp row stays host-local). Resume/
warm-start work rank-consistently (every controller restores the same
checkpoint file from the shared filesystem). Unsupported combinations
raise immediately.

Multiplayer population training composes as ONE MULTIHOST JOB PER PLAYER:
set ``multiplayer.player_id`` on each job (player 0's actors host the
games, every other player's actor gidx joins game gidx). Each player's
stack is an independent mesh job; players interact only through the game
engine's host/join sockets, not through collectives — so there is no
cross-player lockstep, and any player job can restart independently.
See README "Multiplayer at pod scale"; the two-job loopback test
(tests/test_parallel.py) runs two concurrent player jobs end-to-end.

Demo / validation (two loopback controllers, virtual CPU devices):

    python -m r2d2_tpu.parallel.multihost            # launcher
"""

import functools
import os
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from r2d2_tpu.config import Config, apex_epsilon
from r2d2_tpu.replay.structs import Block, ReplaySpec, empty_block_np


class LocalActorFleet:
    """One host's actor workers (threads OR spawned processes) with
    PlayerStack-style supervision.

    Restarts are purely host-local (they touch no collective state, so
    lockstep is unaffected) and must NEVER propagate an exception into the
    lockstep learner loop — a host crashing mid-collective abandons every
    peer until the jax.distributed heartbeat timeout, exactly the failure
    the stop consensus exists to prevent. A failed respawn is logged and
    retried on the next supervision tick instead.

    ``queue``: pass the host's BlockQueue when workers are PROCESSES so a
    producer crash between reserve and commit gets its shm ring slot
    reclaimed (RingRecoveryScheduler semantics; no-op for thread fleets
    and non-shm transports).

    ``health``: pass a runtime.feeder.WorkerHealth to enable hang
    detection, restart backoff, and the crash-loop breaker — the SAME
    policy object PlayerStack uses, so single-host and multihost get
    identical supervision semantics. None (default) keeps the plain
    dead-worker scan."""

    def __init__(self, spawn_fn: Callable[[int], object], n: int,
                 restart_dead: bool, stop, queue=None, health=None):
        from r2d2_tpu.runtime.feeder import RingRecoveryScheduler
        self._spawn = spawn_fn
        self._restart = restart_dead
        self._stop = stop
        self._queue = queue
        self.health = health
        self._ring_recovery = RingRecoveryScheduler()
        self._seen_dead: set = set()
        self.threads: List[object] = [spawn_fn(i) for i in range(n)]

    def _respawn(self, i: int):
        """Respawn wrapper: a failure is logged and retried next tick
        (never propagated into the lockstep loop — see class docstring)."""
        import logging
        try:
            return self._spawn(i)
        except Exception:
            logging.getLogger(__name__).exception(
                "actor %d respawn failed; will retry next supervision "
                "tick", i)
            return None

    def supervise(self) -> int:
        """Respawn dead (and, with ``health``, hung) workers; returns the
        number restarted (logged). Ring reclamation runs for newly-failed
        workers regardless of the restart flag (the wedge exists either
        way)."""
        import logging

        from r2d2_tpu.runtime.feeder import supervise_workers
        if self._stop.is_set():
            return 0
        restarted = supervise_workers(
            self.threads, self._seen_dead,
            respawn=self._respawn if self._restart else None,
            ring=self._ring_recovery if self._queue is not None else None,
            health=self.health)
        if self._queue is not None:
            freed = self._ring_recovery.tick(self._queue)
            if self.health is not None:
                self.health.ring_slots_recovered += freed
        if restarted:
            logging.getLogger(__name__).warning(
                "restarted %d dead actor worker(s)", restarted)
        return restarted

    def join(self, timeout: float = 5.0) -> None:
        for t in self.threads:
            t.join(timeout=timeout)
            if t.is_alive() and hasattr(t, "terminate"):   # process worker
                t.terminate()


def make_lockstep_ingest(spec: ReplaySpec, mesh, fleet: bool = False):
    """One jitted program per loop iteration: conditional per-shard block
    writes, global counters, and stop consensus.

    Inputs (global shapes, 'dp'-sharded): replay state; cum_env (dp,) i32
    cumulative ingested learning-steps per shard; blocks stacked with a
    leading dp axis (each host fills only its local shards' rows — at most
    one valid row per host per iteration); valid (dp,) i32; stop (dp,) i32.
    Outputs: new state, new cum_env, and a dict of REPLICATED scalars:
    buffer_steps (live steps in the ring), filled_shards (shards holding
    data — the dp ready-gate), env_steps (cumulative), stop (>0 = any
    host requested stop).

    ``fleet=True`` (ISSUE 12) appends one (dp,) f32 operand — each host
    fills its owned rows with its previous iteration's wall step time —
    and widens the replicated info dict with the skew gauges: the
    all-gathered per-row step-time and cumulative-env-step tables,
    sum/max/min reductions, and a one-hot argmax so every rank learns
    the straggler's dp-row identity in-graph. Same single dispatch —
    zero extra collectives on the DCN critical path. ``fleet=False``
    compiles the exact PR-10 program (the kill-switch contract).

    mp > 1 routes to the GSPMD formulation (vmap over the dp-leading
    state, scalar sums lowering to the allreduces) for the same reason as
    the learner step: a manual-dp/auto-mp shard_map body fails to
    partition. Identical contract; the manual path stays for mp == 1.
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from r2d2_tpu.parallel.sharded import _shard0, _unshard0
    from r2d2_tpu.replay.device_replay import replay_add

    if mesh.shape.get("mp", 1) > 1:
        return _make_gspmd_lockstep_ingest(spec, mesh, fleet)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P("dp"),) * (6 if fleet else 5),
        out_specs=(P("dp"), P("dp"), P()),
        check_vma=False)
    def ingest(state, cum_env, blocks, valid, stop, *times):
        local = _shard0(state)
        blk = jax.tree_util.tree_map(lambda x: x[0], blocks)
        local = jax.lax.cond(
            valid[0] > 0, lambda s: replay_add(spec, s, blk),
            lambda s: s, local)
        added = jnp.where(valid[0] > 0, blk.learning_steps.sum(), 0)
        cum = cum_env[0] + added.astype(jnp.int32)
        my_steps = local.learning_steps.sum()
        info = {
            "buffer_steps": jax.lax.psum(my_steps, "dp"),
            "filled_shards": jax.lax.psum(
                (my_steps > 0).astype(jnp.int32), "dp"),
            "env_steps": jax.lax.psum(cum, "dp"),
            "stop": jax.lax.psum(stop[0], "dp"),
        }
        if fleet:
            t = times[0][0]
            tmax = jax.lax.pmax(t, "dp")
            onehot = (t >= tmax).astype(jnp.int32)   # 1 on the straggler
            idx = jax.lax.axis_index("dp")
            info.update({
                "step_times": jax.lax.all_gather(t, "dp"),
                "step_time_sum": jax.lax.psum(t, "dp"),
                "step_time_max": tmax,
                "step_time_min": jax.lax.pmin(t, "dp"),
                # one-hot argmax: pmax picks the highest tied row
                "straggler_shard": jax.lax.pmax(
                    jnp.where(onehot > 0, idx, -1), "dp"),
                "env_steps_shards": jax.lax.all_gather(cum, "dp"),
            })
        return _unshard0(local), cum[None], info

    return jax.jit(ingest, donate_argnums=(0, 1))


def _make_gspmd_lockstep_ingest(spec: ReplaySpec, mesh, fleet: bool = False):
    """The dp x mp lockstep ingest: same contract as make_lockstep_ingest
    (incl. the fleet gauge widening — the reductions/argmax lower to
    GSPMD allreduces, the tables to replicating constraints), expressed
    without manual collectives (the replay stays dp-sharded /
    mp-replicated; the scalar reductions become GSPMD allreduces).

    Known trade-off: the vmapped ``lax.cond`` lowers through select, so an
    invalid row still pays its block write's bandwidth before being
    discarded — including no-op spin iterations. This cannot be avoided
    with a second counters-only program: the lockstep invariant requires
    every host to dispatch the SAME program each iteration, and block
    presence is host-local state, so program selection may never depend on
    it. Bounded cost: a few MB per iteration during the fill phase,
    mp > 1 meshes only."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from r2d2_tpu.replay.device_replay import replay_add

    sharding = NamedSharding(mesh, P("dp"))
    replicated = NamedSharding(mesh, P())

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def ingest(state, cum_env, blocks, valid, stop, *times):
        def add_row(s, blk, v):
            return jax.lax.cond(v > 0, lambda ss: replay_add(spec, ss, blk),
                                lambda ss: ss, s)

        state = jax.vmap(add_row)(state, blocks, valid)
        state = jax.tree_util.tree_map(
            lambda x: jax.lax.with_sharding_constraint(x, sharding), state)
        added = jnp.where(
            valid > 0,
            jax.vmap(lambda b: b.learning_steps.sum())(blocks), 0)
        cum_env = cum_env + added.astype(jnp.int32)
        my_steps = jax.vmap(lambda s: s.learning_steps.sum())(state)
        info = {
            "buffer_steps": my_steps.sum(),
            "filled_shards": (my_steps > 0).astype(jnp.int32).sum(),
            "env_steps": cum_env.sum(),
            "stop": stop.sum(),
        }
        if fleet:
            t = times[0]
            # tables replicate (hosts cannot device_get non-addressable
            # dp shards of a multi-controller array — the PR5 lesson)
            info.update({
                "step_times": jax.lax.with_sharding_constraint(
                    t, replicated),
                "step_time_sum": t.sum(),
                "step_time_max": t.max(),
                "step_time_min": t.min(),
                "straggler_shard": jnp.argmax(t).astype(jnp.int32),
                "env_steps_shards": jax.lax.with_sharding_constraint(
                    cum_env, replicated),
            })
        return state, cum_env, info

    return ingest


def _write_host_telemetry_row(writer, rank: int, tele,
                              t_start: float, resources=None,
                              stages=None, fleet_block=None,
                              stage_counts=None, clock_anchor=None,
                              actors_per_rank=None, engine=None) -> None:
    """One per-host aggregated telemetry row per log interval. Rank 0's
    stage summary rides the main TrainMetrics record (it owns the
    player's metrics files); every other rank appends compact rows here so
    a pod-wide view exists without breaking the rank-0-deduplicates-side-
    effects rule — tools/inspect.py reads both. With the resource pillar
    on (ISSUE 7) the row also carries this host's ``resources`` block
    (its own devices + RSS/CPU — resource state is host-local).

    Under the fleet plane (ISSUE 12) the row widens: a ``wall`` clock
    stamp (rank 0 ages other ranks' rows off it — the missing_rank
    signal), this rank's ``fleet`` timing block, its CUMULATIVE
    ``stage_counts`` (mergeable by elementwise add into the rank-0 fleet
    view), the lockstep-iteration-1 ``clock_anchor`` the trace merge
    aligns ranks on, and ``actors_per_rank`` (maps actor span files to
    ranks). ``engine`` runs this rank's local AlertEngine over the row
    itself, so its ``alerts`` block sees the same interval it describes
    and firings land in alerts_host{r}.jsonl. ``stages`` overrides the
    default interval summary (rank 0's interval is consumed by the main
    record, so its own fleet-mode row carries the cumulative summary).
    ``writer`` is a RotatingJsonlWriter — host rows are size-capped."""
    row = {"t": round(time.time() - t_start, 3), "rank": rank,
           "stages": (tele.interval_summary() if stages is None
                      else stages),
           "telemetry_dropped_spans": tele.spans.dropped}
    if resources is not None:
        row["resources"] = resources.block()
    if fleet_block is not None:
        row["wall"] = round(time.time(), 3)
        row["fleet"] = fleet_block
        if stage_counts is not None:
            row["stage_counts"] = stage_counts
        if clock_anchor is not None:
            row["clock_anchor"] = clock_anchor
        if actors_per_rank is not None:
            row["actors_per_rank"] = actors_per_rank
    if engine is not None:
        row["alerts"] = engine.evaluate(row)
    writer.write(row)


def owned_dp_rows(mesh) -> List[int]:
    """dp rows whose devices (all mp columns) live on THIS process.
    Host-local data (experience blocks, host-replay batches) can only feed
    rows this process owns, so an mp-spanning row is a hard scope error."""
    import jax

    rows = mesh.devices.reshape(mesh.shape["dp"], -1)   # (dp, mp)
    me = jax.process_index()
    owners = []
    for r in range(rows.shape[0]):
        procs = {d.process_index for d in rows[r]}
        if len(procs) != 1:
            raise NotImplementedError(
                f"dp row {r} spans processes {sorted(procs)} — with "
                "mesh.mp > 1, mp must divide each host's device count "
                "so every dp row (and its mp replicas) stays on one "
                "host")
        owners.append(procs.pop())
    return [r for r, o in enumerate(owners) if o == me]


def _local_dp_values(arr) -> np.ndarray:
    """This process's rows of a dp-sharded 1-D array, in global-index order
    (= the order this process supplied them to
    ``make_array_from_process_local_data``). mp-replicated shards of the
    same dp row are deduplicated by index."""
    shards = {}
    for s in arr.addressable_shards:
        start = s.index[0].start or 0
        shards.setdefault(start, np.asarray(s.data))
    return np.concatenate([shards[k] for k in sorted(shards)])


def make_lockstep_consensus(mesh, fleet: bool = False):
    """The host-replay twin of lockstep_ingest's counter/stop outputs: a
    tiny psum program every iteration. Each process contributes
    [buffer_steps, env_steps, ready, stop] ONCE (on its first owned dp
    row; zero rows elsewhere); the psum over dp returns the same sums on
    every host, so every control-flow decision downstream is replicated —
    the lockstep invariant with no device replay involved.

    ``fleet=True`` (ISSUE 12) widens the row to 5 columns — col 4 is
    this host's previous-iteration step time in µs — and the program
    additionally all-gathers the raw (dp, 5) row table, so every rank
    reads the full per-rank step-time/env-step picture off the SAME
    dispatch; the sum/max/min/argmax gauges derive from the table over
    each rank's first owned row (the only row a host fills). fleet=False
    compiles the exact PR-10 (dp, 4) psum."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from r2d2_tpu.telemetry.fleet import mesh_row_ranks, rank_first_rows

    sharding = NamedSharding(mesh, P("dp"))
    local_rows = owned_dp_rows(mesh)
    ncols = 5 if fleet else 4
    if fleet:
        row_ranks = mesh_row_ranks(mesh)
        first_rows = rank_first_rows(row_ranks, len(set(row_ranks)))

        @jax.jit
        def psum_rows(x):                                   # (dp, 5) int32
            def body(v):
                return (jax.lax.psum(v, "dp"),
                        jax.lax.all_gather(v, "dp", axis=0, tiled=True))
            # check_vma off: the all-gathered table IS replicated, the
            # static check just cannot infer it (same waiver as the
            # lockstep ingest program)
            return shard_map(body, mesh=mesh, in_specs=P("dp"),
                             out_specs=(P(), P()), check_vma=False)(x)
    else:
        @jax.jit
        def psum_rows(x):                                   # (dp, 4) int32
            return shard_map(lambda v: jax.lax.psum(v, "dp"),
                             mesh=mesh, in_specs=P("dp"), out_specs=P())(x)

    def consense(buffer_steps: int, env_steps: int, ready: bool,
                 stop_flag: int, step_time_s: float = 0.0) -> dict:
        rows = np.zeros((len(local_rows), ncols), np.int32)
        vals = [buffer_steps, env_steps, int(bool(ready)), int(stop_flag)]
        if fleet:
            # µs in int32: cap at 2000 s so the cast can never overflow
            vals.append(int(min(max(step_time_s, 0.0), 2000.0) * 1e6))
        rows[0] = vals
        x = jax.make_array_from_process_local_data(sharding, rows)
        if fleet:
            summed, table = psum_rows(x)
            out = np.asarray(summed).reshape(-1, ncols)[0]
        else:
            out = np.asarray(psum_rows(x)).reshape(-1, ncols)[0]
        info = {"buffer_steps": int(out[0]), "env_steps": int(out[1]),
                "ready_procs": int(out[2]), "stop": int(out[3])}
        if fleet:
            table = np.asarray(table).reshape(-1, ncols)
            times = table[:, 4].astype(np.float64) / 1e6        # (dp,) s
            per_rank = times[first_rows]
            info.update({
                "step_times": times,
                "step_time_sum": float(per_rank.sum()),
                "step_time_max": float(per_rank.max()),
                "step_time_min": float(per_rank.min()),
                "straggler_shard": int(
                    first_rows[int(np.argmax(per_rank))]),
                "env_steps_shards": table[:, 1].astype(np.int64),
            })
        return info

    return consense


class HostFeed:
    """Builds each iteration's global ingest operands from process-local
    blocks: a (dp,)-leading stacked Block whose rows are zeros except this
    host's round-robin target shard, plus the valid/stop flag vectors.
    Every leaf goes through ``jax.make_array_from_process_local_data`` so
    no host ever needs another host's data."""

    def __init__(self, spec: ReplaySpec, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.spec = spec
        self.sharding = NamedSharding(mesh, P("dp"))
        # row ownership: every dp row's devices (its mp columns) must live
        # on ONE host — blocks are fed host-locally (owned_dp_rows raises
        # on an mp-spanning row)
        self.local_rows = owned_dp_rows(mesh)
        me = jax.process_index()
        if not self.local_rows:
            raise ValueError(
                f"process {me} owns no mesh shards — mesh.dp must cover "
                f"every participating host's devices")
        lo, hi = self.local_rows[0], self.local_rows[-1]
        if self.local_rows != list(range(lo, hi + 1)):
            raise NotImplementedError(
                "non-contiguous per-process mesh rows are not supported "
                f"(process {me} owns {self.local_rows})")
        self.local_dp = len(self.local_rows)
        self._zero = empty_block_np(spec)
        self._rr = 0
        # the all-zero (blocks, valid, stop) triple for block=None, stop=0
        # iterations, built once: ingest_fn does not donate these operands,
        # so reusing them avoids a full zero-block allocation + H2D
        # transfer per no-op iteration (the pre-ready fill phase spins on
        # exactly these)
        self._noop = self._build(None, 0)


    def build(self, block: Optional[Block], stop_flag: int):
        """Returns (blocks, valid, stop) global arrays for lockstep_ingest.
        ``block`` lands in the next local shard (round-robin); None = no-op
        iteration (all-invalid rows, reused from the prebuilt triple)."""
        if block is None and not stop_flag:
            return self._noop
        return self._build(block, stop_flag)

    def times(self, step_time_s: float):
        """The fleet-widened ingest's (dp,) f32 timing operand: every
        owned row carries this host's previous-iteration step time
        (seconds). Built fresh per iteration — it changes every time, so
        there is nothing to reuse (and it is 4 bytes per dp row)."""
        import jax
        arr = np.full((self.local_dp,), step_time_s, np.float32)
        return jax.make_array_from_process_local_data(self.sharding, arr)

    def _build(self, block: Optional[Block], stop_flag: int):
        import jax

        stacked = {}
        target = self._rr
        for name, zero in self._zero.items():
            rows = np.broadcast_to(
                zero[None], (self.local_dp,) + zero.shape).copy()
            if block is not None:
                rows[target] = np.asarray(getattr(block, name))
            stacked[name] = jax.make_array_from_process_local_data(
                self.sharding, rows)
        valid = np.zeros((self.local_dp,), np.int32)
        if block is not None:
            valid[target] = 1
            self._rr = (self._rr + 1) % self.local_dp
        stop = np.full((self.local_dp,), int(stop_flag), np.int32)
        return (Block(**stacked),
                jax.make_array_from_process_local_data(self.sharding, valid),
                jax.make_array_from_process_local_data(self.sharding, stop))


def train_multihost(cfg: Config, *, max_training_steps: Optional[int] = None,
                    max_seconds: Optional[float] = None,
                    actor_mode: str = "thread",
                    log_fn: Callable[[dict], None] = None) -> dict:
    """The rank-aware ``train()``: run this same function on every host of
    the pod (SPMD controllers). Blocks until done; returns a summary dict
    {step, env_steps, buffer_steps, params} for this process.
    """
    import jax

    if actor_mode not in ("thread", "process"):
        raise ValueError(f"actor_mode must be 'thread' or 'process', got "
                         f"{actor_mode!r}")
    if cfg.multiplayer.enabled and cfg.multiplayer.player_id < 0:
        raise NotImplementedError(
            "multihost training runs ONE player's stack per job: set "
            "multiplayer.player_id to this job's player index and launch "
            "one multihost job per player (players interact only through "
            "the game engine's host/join sockets, never through "
            "collectives — README \"Multiplayer at pod scale\"). "
            "multiplayer.player_id=-1 (whole population in-process) is the "
            "single-host orchestrator's mode.")
    if cfg.replay.placement not in ("device", "host"):
        raise ValueError(
            f"unknown replay.placement {cfg.replay.placement!r}")
    host_mode = cfg.replay.placement == "host"
    # fleet observability plane (ISSUE 12): widened lockstep gauges,
    # per-iteration compute-vs-wait timing, the rank-0 fleet block,
    # per-rank alert engines, clock-anchored host rows
    fleet_on = cfg.telemetry.enabled and cfg.telemetry.fleet_enabled
    from r2d2_tpu.telemetry.learning import LearningAggregator, LearningDiag
    # learning diagnostics (ISSUE 5): fused into the lockstep step like
    # the single-host path; only rank 0 aggregates (it owns TrainMetrics)
    learn_diag = LearningDiag.from_config(cfg)
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.learner.train_step import create_train_state
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.parallel.mesh import init_distributed, make_mesh
    from r2d2_tpu.parallel.sharded import (
        make_sharded_learner_step, sharded_replay_init)
    from r2d2_tpu.runtime.checkpoint import apply_restore, save_checkpoint
    from r2d2_tpu.runtime.feeder import BlockQueue
    from r2d2_tpu.runtime.metrics import TrainMetrics
    from r2d2_tpu.runtime.weights import InProcWeightStore

    init_distributed(cfg.mesh)
    rank, nprocs = jax.process_index(), jax.process_count()

    spec = ReplaySpec.from_config(cfg)
    probe = create_env(cfg.env, seed=cfg.runtime.seed)
    action_dim = probe.action_space.n
    probe.close()
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)

    # quantized inference (ISSUE 14): the accuracy-probe aggregator for
    # this host's THREAD actors (process children probe-free, the
    # single-host rule); rank 0 wires it into the record below so the
    # quant block + quant_divergence rule cover fleet mode too
    quant_stats = None
    if cfg.network.inference_dtype != "f32":
        from r2d2_tpu.telemetry import QuantStats
        quant_stats = QuantStats(cfg.network.inference_dtype,
                                 cfg.telemetry.quant_probe_interval)

    # identical seed on every host -> identical initial params; the pmean'd
    # updates keep them identical forever (tested single-host; the loopback
    # demo asserts it cross-process)
    ts = create_train_state(jax.random.PRNGKey(cfg.runtime.seed), net,
                            cfg.optim)
    # Resume/warm-start: every rank restores the SAME checkpoint file
    # (shared filesystem, the normal pod setup): identical host values on
    # every controller, so lockstep and cross-host param equality hold
    # from step one — the same property the fresh-init path gets from the
    # shared seed. The replay ring restarts empty, as in single-host
    # resume. apply_restore is the one shared restore policy (also the
    # single-host Learner's), so the two paths cannot diverge.
    ts, resumed_env = apply_restore(cfg.runtime, ts)
    mesh = make_mesh(cfg.mesh)
    if mesh.shape["mp"] > 1:
        # pod-scale tensor parallelism: wide params feature-sharded over
        # mp, the GSPMD learner step + GSPMD lockstep ingest (both routed
        # automatically by their factories), replay dp-sharded /
        # mp-replicated. HostFeed validates that every dp row stays on one
        # host. Identical init on every rank keeps the mp shards
        # rank-consistent the same way replication does for mp=1.
        from r2d2_tpu.parallel.tensor_parallel import state_shardings
        ts = jax.device_put(ts, state_shardings(ts, mesh))
    dp = mesh.shape["dp"]
    from jax.sharding import NamedSharding, PartitionSpec as P
    if host_mode:
        # Host-placement lockstep (the reference-style CPU replay under the
        # multi-controller loop): each process owns ONE HostReplay fed by
        # its own actors (dp = independent per-host data, like the device
        # path's per-shard rings); every iteration dispatches the tiny
        # consensus psum instead of lockstep_ingest, and — iff the
        # replicated outputs say ready — every process samples its share
        # of the global batch, assembles it dp-sharded, and dispatches the
        # SAME GSPMD external-batch step (gradients reduce over the global
        # batch automatically). Priority write-back stays host-local, with
        # HostReplay's monotonic staleness guard intact. Per-step dispatch
        # (k=1): sampling happens on the host between steps, so there is
        # no k-step scan to fuse — same as the single-host host path.
        from r2d2_tpu.learner.train_step import make_external_batch_step
        from r2d2_tpu.replay.host_replay import HostReplay
        if spec.batch_size % dp:
            raise ValueError(
                f"replay.batch_size={spec.batch_size} is not divisible by "
                f"mesh dp={dp} — the batch axis cannot shard evenly")
        local_rows_n = len(owned_dp_rows(mesh))
        local_batch = spec.batch_size * local_rows_n // dp
        # per-rank seed: each host's replay samples ITS OWN distribution
        host_replay = HostReplay(spec, seed=cfg.runtime.seed + 7919 * rank)
        consense = make_lockstep_consensus(mesh, fleet=fleet_on)
        ext_step = make_external_batch_step(net, spec, cfg.optim,
                                            cfg.network.use_double,
                                            diag=learn_diag)
        batch_sharding = NamedSharding(mesh, P("dp"))
        if mesh.shape["mp"] == 1:
            # replicate the state across the mesh (mp > 1 already placed
            # feature-sharded above); identical host values on every rank
            ts = jax.device_put(ts, NamedSharding(mesh, P()))
        env_local = 0
        if cfg.runtime.steps_per_dispatch > 1:
            # same warning the single-host host path emits: sampling
            # happens on the host between steps, so there is no k-step
            # scan to fuse
            import logging
            logging.getLogger(__name__).warning(
                "runtime.steps_per_dispatch=%d is ignored under "
                "replay.placement='host' (host sampling is per-step)",
                cfg.runtime.steps_per_dispatch)
        k = 1
    else:
        rs = sharded_replay_init(spec, mesh)
        cum_env = jax.device_put(np.zeros((dp,), np.int32),
                                 NamedSharding(mesh, P("dp")))

        k = cfg.runtime.resolved_steps_per_dispatch()
        step_fn = make_sharded_learner_step(
            net, spec, cfg.optim, cfg.network.use_double, mesh,
            steps_per_dispatch=k, diag=learn_diag)
        ingest_fn = make_lockstep_ingest(spec, mesh, fleet=fleet_on)
        feed = HostFeed(spec, mesh)

    # -- local actors (this host's share of the global fleet) --
    # The stop event must be shareable with spawned children in process
    # mode; both Event kinds serve the lockstep loop identically.
    n_local = cfg.actor.num_actors
    publisher = None
    if actor_mode == "process":
        import multiprocessing as mp
        from r2d2_tpu.runtime.actor_main import actor_process_main
        from r2d2_tpu.runtime.weights import WeightPublisher
        ctx = mp.get_context("spawn")
        stop = ctx.Event()
        # quantized inference (ISSUE 14): publish the inference bundle
        # (f32 + quantized twin + stamp) through the same segment — the
        # shared publish-time hook, so the lockstep fleet's actors
        # stream the same publish-time twin single-host actors do
        from r2d2_tpu.runtime.weights import (make_publish_preparer,
                                              wrap_publish)
        prep = make_publish_preparer(net)
        publisher = WeightPublisher(
            prep(ts.params, 1) if prep else ts.params)
        try:
            queue = BlockQueue(
                use_mp=True, ctx=ctx,
                shm_spec=spec if cfg.runtime.shm_transport else None)
        except BaseException:
            # the publisher's /dev/shm segment was already created; don't
            # leak it past a failed ring bring-up (round-4 review) — the
            # try/finally that normally owns both starts only at fleet
            # construction below
            publisher.close()
            raise
        publish = wrap_publish(publisher.publish, prep,
                               lambda: publisher.publish_count)
        # weight fan-out tree (ISSUE 15): this host's relay tier of the
        # fleet-wide tree — the rank's learner publishes ONCE to its
        # root segment, shm relays re-publish, and the host's local
        # actors subscribe to leaf relays (the root sees <= degree
        # readers per host no matter the local fan-out). Relays carry
        # the stamped quant bundle unchanged.
        shm_fanout = None
        if cfg.fleet.fanout_degree >= 2:
            from r2d2_tpu.fleet.fanout import ShmFanout
            try:
                shm_fanout = ShmFanout(
                    publisher.name,
                    prep(ts.params, 0) if prep else ts.params,
                    n_local, cfg.fleet.fanout_degree)
                shm_fanout.pump()   # adopt the construction publish
            except BaseException:
                queue.close()
                publisher.close()
                raise
            _root_publish = publish

            def publish(params, _pub=_root_publish, _f=shm_fanout):
                _pub(params)
                _f.pump()
    else:
        stop = threading.Event()
        shm_fanout = None

    # SIGTERM/SIGINT land on the stop event, which feeds the next
    # iteration's local_stop flag into the psum consensus — the signaled
    # host keeps dispatching until every controller agrees to stop on the
    # SAME iteration, instead of abandoning peers mid-collective (they
    # would wedge until the jax.distributed heartbeat timeout).
    import signal
    prev_handlers = {}
    if threading.current_thread() is threading.main_thread():
        def _on_signal(signum, frame):
            stop.set()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, _on_signal)
            except (ValueError, OSError):
                pass

    # Per-player-job multiplayer (README "Multiplayer at pod scale"): this
    # job's player index drives the host/join wiring and the seed offset.
    # pid=0 when multiplayer is off, so every single-player formula below
    # is unchanged. Game index = the actor's GLOBAL index (player 0's job
    # hosts games 0..total_actors-1; player p's actor gidx joins game
    # gidx), so all player jobs must configure the same actor fan-out.
    pid = cfg.multiplayer.player_id if cfg.multiplayer.enabled else 0
    # host/join args OBSERVED by this host's envs (thread mode; the fake
    # env records what the factory resolved) — returned in the summary so
    # per-player-job launches can assert the wiring end-to-end. Keyed by
    # actor slot (not appended): supervisor respawns re-record, never
    # duplicate.
    observed_wiring = [None] * n_local

    # Crash-recovery rank-0 twin (ISSUE 18): the single-host learner's
    # durable replay snapshot plane, mirrored into the lockstep loop.
    # Active on the shapes where rank 0 addresses the WHOLE ring (one
    # controller, dp=1, device placement — the single-controller pod and
    # the loop's test reality); wider pods log the gap once and rely on
    # checkpoint resume alone (ROADMAP 4b: dp-sharded snapshot cuts).
    # The ring twin is a host RingAccountant advanced per ingested block
    # — the same mirror discipline as the single-host Learner's.
    snap_writer = None
    snap_ring = None
    capture_plain = None
    if cfg.runtime.snapshot_interval > 0 and rank == 0 and not host_mode:
        import logging
        if nprocs > 1 or dp > 1:
            logging.getLogger(__name__).warning(
                "runtime.snapshot_interval=%d: the rank-0 replay "
                "snapshot twin needs a rank-0-addressable ring "
                "(nprocs=1, dp=1; got nprocs=%d dp=%d) — replay "
                "snapshots are skipped, checkpoint resume still works",
                cfg.runtime.snapshot_interval, nprocs, dp)
        else:
            from r2d2_tpu.replay.snapshot import (SnapshotWriter,
                                                  capture_plain,
                                                  load_snapshot,
                                                  restore_plain)
            from r2d2_tpu.replay.structs import RingAccountant
            snap_ring = RingAccountant(spec.num_blocks)
            snap_writer = SnapshotWriter(cfg.runtime.save_dir or ".", pid)
            if cfg.runtime.resume and cfg.runtime.restore_replay:
                snap = load_snapshot(cfg.runtime.save_dir or ".", pid)
                if snap is not None and snap.get("kind") == "plain":
                    rs0 = jax.tree_util.tree_map(lambda x: x[0], rs)
                    restored0 = restore_plain(spec, rs0, snap_ring, snap)
                    # re-pin the restored plain cut under the dp axis on
                    # the sharded state's own placement
                    rs = jax.tree_util.tree_map(
                        lambda r0, full: jax.device_put(
                            np.asarray(jax.device_get(r0))[None],
                            full.sharding),
                        restored0, rs)
                    logging.getLogger(__name__).warning(
                        "rank-0 twin restored %d replay block(s) from "
                        "the step-%s snapshot", snap_ring.total_adds,
                        snap.get("step"))

    if actor_mode == "process":
        def spawn_actor(i: int):
            # player_idx=pid / actor_idx=gidx reproduces the thread path's
            # seed formula (seed + 10_000*pid + 100*gidx) inside
            # actor_process_main; total_actors sizes the vector ε ladder
            # over the GLOBAL fleet (rank-local num_actors x nprocs)
            gidx = rank * n_local + i
            eps = apex_epsilon(gidx, nprocs * n_local, cfg.actor.base_eps,
                               cfg.actor.eps_alpha)
            heartbeats.reset_slot(i)
            if tele_board is not None:
                tele_board.reset_slot(i)
            seg = (shm_fanout.segment_for(i) if shm_fanout is not None
                   else publisher.name)
            p = ctx.Process(
                target=actor_process_main,
                args=(cfg.to_dict(), pid, gidx, eps, seg,
                      queue._q, stop),
                kwargs={**cfg.multiplayer.env_args(pid, gidx),
                        "total_actors": nprocs * n_local,
                        "health_board": heartbeats, "health_slot": i,
                        "telemetry_board": tele_board},
                daemon=True, name=f"actor-p{pid}h{rank}-{i}")
            p.start()
            return p
    else:
        # quantized inference (ISSUE 14): same publish-time bundle hook
        # as the process path / the single-host orchestrator
        from r2d2_tpu.runtime.weights import (make_publish_preparer,
                                              wrap_publish)
        prep = make_publish_preparer(net)
        store = InProcWeightStore(prep(ts.params, 1) if prep else ts.params)
        publish = wrap_publish(store.publish, prep,
                               lambda: store.publish_count)
        queue = BlockQueue(use_mp=False)

        def spawn_actor(i: int) -> threading.Thread:
            gidx = rank * n_local + i
            eps = apex_epsilon(gidx, nprocs * n_local, cfg.actor.base_eps,
                               cfg.actor.eps_alpha)
            seed = cfg.runtime.seed + 10_000 * pid + 100 * gidx
            # shared scalar/vector construction (runtime/actor_loop.py):
            # env_factory routes through THIS module's create_env symbol,
            # global gidx + fleet total size the vector ε ladder
            from r2d2_tpu.runtime.actor_loop import (make_actor_env,
                                                     make_actor_policy)
            env = make_actor_env(cfg, pid, gidx, seed,
                                 env_factory=create_env,
                                 name=f"p{pid}h{rank}a{i}",
                                 num_players=cfg.multiplayer.num_players,
                                 **cfg.multiplayer.env_args(pid, gidx))
            # vector envs expose lanes; wiring is identical across a
            # worker's lanes, so record lane 0's
            uw = getattr(env, "envs", [env])[0]
            uw = getattr(uw, "unwrapped", uw)
            observed_wiring[i] = getattr(uw, "multiplayer_wiring", None)
            # store.current: the prepared published tree (no per-policy
            # requantization) that is also FRESH on a mid-training
            # respawn — the predecessor consumed this reader's version,
            # so a first poll() would return None against stale params
            policy, run_loop = make_actor_policy(
                cfg, net, store.current(reader_id=i), gidx, seed,
                epsilon=eps, total_actors=nprocs * n_local,
                quant_stats=quant_stats)

            # per-spawn cancel event + instrumented sink: identical health
            # wiring to PlayerStack._spawn_thread_actor
            cancel = threading.Event()

            def should_stop(cancel=cancel):
                return stop.is_set() or cancel.is_set()

            from r2d2_tpu.runtime.actor_loop import instrument_block_sink
            heartbeats.reset_slot(i)
            sink = instrument_block_sink(
                cfg, i,
                lambda b, should_stop=should_stop, slot=i: queue.put_patient(
                    b, should_stop,
                    beat=lambda: heartbeats.touch(slot),
                    telemetry=tele),
                board=heartbeats, telemetry=tele,
                # generation stamp, same contract as the single-host
                # thread spawner (reader_id matches weight_poll below)
                weight_version=lambda reader_id=i:
                    store.reader_version(reader_id),
                # lane provenance (ISSUE 10): gidx is the GLOBAL worker
                # index across the multihost fleet — the ladder layout
                # the ε spread above uses
                lane_base=gidx * cfg.actor.envs_per_actor)

            def loop(env=env, policy=policy, run_loop=run_loop,
                     reader_id=i, sink=sink, should_stop=should_stop):
                # the run loop owns env and closes it on every exit
                run_loop(cfg, env, policy,
                         block_sink=sink,
                         weight_poll=lambda: store.poll(reader_id),
                         should_stop=should_stop,
                         telemetry=tele)

            t = threading.Thread(target=loop, daemon=True,
                                 name=f"actor-h{rank}-{i}")
            t.health_cancel = cancel
            t.start()
            return t

    # worker health is host-local by construction (heartbeats, backoff,
    # breaker touch no collective state) — the same board+policy objects
    # the single-host PlayerStack uses, so supervision semantics are
    # identical across the two paths. Created HERE, immediately before the
    # try that owns its shm segment (the spawn closures above bind late);
    # nothing between this allocation and the finally can raise past it.
    from r2d2_tpu.runtime.feeder import HeartbeatBoard, WorkerHealth
    heartbeats = HeartbeatBoard(n_local)
    health = WorkerHealth.from_runtime(n_local, heartbeats, cfg.runtime)

    # per-rank fleet telemetry (ISSUE 4) — host-local like the health
    # subsystem (no collective state): thread actors observe straight into
    # this rank's Telemetry; process actors publish through the shm board,
    # which interval_summary() differences. Rank 0's summary joins the
    # TrainMetrics record; other ranks append per-host rows.
    # shm allocation ONLY here (no file I/O — that sits inside the try
    # below, whose finally owns these segments' close())
    from r2d2_tpu.telemetry import Telemetry, TelemetryBoard
    tele = Telemetry.from_config(cfg, name=f"learner-h{rank}")
    tele_board = None
    if cfg.telemetry.enabled and actor_mode == "process":
        tele_board = TelemetryBoard(n_local)
        tele.attach_board(tele_board)

    # fleet construction onward sits inside the try: a spawn failure for
    # actor k must not orphan the k-1 already-running actor processes on a
    # live shm ring — the finally unwinds them (round-4 review)
    fleet = None
    resources = None
    compile_mon = None
    try:
        if cfg.telemetry.enabled:
            resume = bool(cfg.runtime.resume)
            if not resume:
                # fresh run: clear this rank's actors' stale span files
                # (the spawned processes APPEND so supervisor respawns
                # keep their predecessors' spans)
                for i in range(n_local):
                    try:
                        os.remove(os.path.join(
                            cfg.runtime.save_dir or ".",
                            f"spans_p{pid}_a{rank * n_local + i}.jsonl"))
                    except OSError:
                        pass
            tele.start_drain(os.path.join(
                cfg.runtime.save_dir or ".", f"spans_host{rank}.jsonl"),
                append=resume)
        fleet = LocalActorFleet(
            spawn_actor, n_local, cfg.runtime.restart_dead_actors, stop,
            queue=queue if actor_mode == "process" else None,
            health=health)

        # pid-keyed logs/checkpoints: per-player jobs sharing a filesystem
        # write train_player{pid}.log and player-pid checkpoint dirs, like
        # the in-process population path (ref worker.py:35-37)
        metrics = (TrainMetrics(pid, cfg.runtime.save_dir,
                                resume=bool(cfg.runtime.resume))
                   if rank == 0 else None)
        if metrics is not None:
            metrics.set_telemetry(tele)   # stages ride the rank-0 record
            if quant_stats is not None:
                # quant accuracy block (ISSUE 14) on the rank-0 record
                metrics.set_quant(quant_stats.interval_block)
        # rank-0 learning aggregation: the 'learning' block (+ NaN
        # forensics) rides the same rank-0 record as everything else
        learn_agg = (LearningAggregator(pid, cfg.runtime.save_dir,
                                        cfg.telemetry.nan_policy,
                                        cfg.optim.lr)
                     if metrics is not None and learn_diag is not None
                     else None)
        # system-health pillar (ISSUE 7), rank-aware: EVERY rank samples
        # its own devices/host/actor-slots (resource state is host-local,
        # like the health and stage telemetry above) and owns its own
        # compile monitor (compile events are process-global per rank
        # process). Rank 0's block + the alert engine ride the main
        # TrainMetrics record — the rank-0-deduplicates-side-effects rule
        # — while other ranks' compact blocks join their per-host
        # telemetry rows.
        if cfg.telemetry.enabled and cfg.telemetry.resources_enabled:
            from r2d2_tpu.telemetry import (AlertEngine, CompileMonitor,
                                            ResourceMonitor, active_monitor,
                                            default_rules)
            from r2d2_tpu.telemetry.resources import (clear_player_buffers,
                                                      pytree_nbytes,
                                                      register_buffer)
            clear_player_buffers(pid)   # previous same-process run's entries
            register_buffer(f"p{pid}/train_state", pytree_nbytes(ts))
            if not host_mode:
                register_buffer(f"p{pid}/replay_ring", pytree_nbytes(rs))
            if cfg.telemetry.compile_enabled and active_monitor() is None:
                compile_mon = CompileMonitor().install()
            resources = ResourceMonitor(
                pid, cfg.runtime.save_dir or ".",
                interval_s=cfg.telemetry.resources_interval_s,
                headroom_warn_frac=(
                    cfg.telemetry.resources_headroom_warn_frac),
                board=tele_board, compile_monitor=compile_mon)
            if metrics is not None:
                metrics.set_resources(resources.block)
                if cfg.telemetry.alerts_enabled:
                    metrics.set_sentinel(AlertEngine(
                        default_rules(cfg.telemetry),
                        jsonl_path=os.path.join(
                            cfg.runtime.save_dir or ".",
                            f"alerts_player{pid}.jsonl"),
                        resume=bool(cfg.runtime.resume)))
        pub_count = ((lambda: publisher.publish_count)
                     if publisher is not None
                     else (lambda: store.publish_count))
        # -- fleet observability plane (ISSUE 12) --
        # Host rows move to the size-capped rotating writer (rotation
        # applies with or without the fleet switch — the unbounded-growth
        # fix stands on its own); rank 0 writes a row too UNDER THE FLEET
        # PLANE ONLY (uniform per-rank inspector panels + the clock
        # anchor), keeping the pre-PR12 file set when it is off. Every
        # rank tracks its lockstep timing in a FleetAggregator; ranks > 0
        # additionally run a local AlertEngine over their own rows
        # (firings -> alerts_host{r}.jsonl) — until now they evaluated no
        # rules at all. Same append-on-resume contract as TrainMetrics.
        from r2d2_tpu.telemetry.fleet import (
            FLEET_INFO_KEYS, FleetAggregator, RotatingJsonlWriter,
            cumulative_stage_matrix, host_alerts_path, host_row_path,
            mesh_row_ranks, stage_counts_dict, summarize_stage_counts)
        host_writer = None
        if tele.enabled and (rank != 0 or fleet_on):
            host_writer = RotatingJsonlWriter(
                host_row_path(cfg.runtime.save_dir or ".", rank),
                max_bytes=cfg.telemetry.fleet_host_row_max_bytes,
                resume=bool(cfg.runtime.resume))
        elif rank == 0 and not cfg.runtime.resume:
            # fleet (or telemetry) off on a FRESH run: a previous
            # fleet-on run's rank-0 host row must not leak into this
            # run's inspector view / trace merge — the pre-PR12
            # file-set contract the kill switch promises
            for suffix in ("", ".1"):
                try:
                    os.remove(host_row_path(
                        cfg.runtime.save_dir or ".", rank) + suffix)
                except OSError:
                    pass
        fleet_mon = None
        host_engine = None
        if fleet_on:
            fleet_mon = FleetAggregator(
                rank, nprocs, mesh_row_ranks(mesh),
                save_dir=cfg.runtime.save_dir or ".",
                missing_age_s=cfg.telemetry.alerts_missing_rank_age_s)
            if (rank != 0 and cfg.telemetry.resources_enabled
                    and cfg.telemetry.alerts_enabled):
                from r2d2_tpu.telemetry import AlertEngine, default_rules
                host_engine = AlertEngine(
                    default_rules(cfg.telemetry),
                    jsonl_path=host_alerts_path(
                        cfg.runtime.save_dir or ".", rank),
                    resume=bool(cfg.runtime.resume))
        # chaos straggler hook (tests only, R2D2_MH_CHAOS_STRAGGLER=
        # "rank:slowxF"): the named rank stretches every iteration's
        # compute phase by ~F (sleep proportional to its own last step
        # time) — the injected straggler the fleet gauges must name
        straggler_factor = 0.0
        chaos_straggler = os.environ.get("R2D2_MH_CHAOS_STRAGGLER", "")
        if chaos_straggler:
            r_s, _, kind = chaos_straggler.partition(":")
            if int(r_s) == rank:
                from r2d2_tpu.tools.chaos import parse_fault_spec
                straggler_factor = parse_fault_spec(f"0:{kind}")[0].factor
        t_run_start = time.time()
        max_steps = max_training_steps or cfg.optim.training_steps
        deadline = time.time() + max_seconds if max_seconds else None
        rt = cfg.runtime
        ratio = cfg.replay.max_env_steps_per_train_step
        step_count = int(ts.step)  # nonzero after resume; max_steps cumulative
        step_base = step_count     # rate-limiter budget counts from THIS
        paused = False             # process's start
        last_ckpt_step = step_count   # last step a checkpoint covered
        pending_losses: list = []
        last_log = last_supervise = time.time()
        info = {"buffer_steps": 0, "env_steps": 0, "filled_shards": 0}

        halt_error: list = []

        def flush_losses():
            if pending_losses and metrics is not None:
                t0 = time.perf_counter()
                arrays = jax.device_get(pending_losses)
                tele.observe("learner/device_sync",
                             time.perf_counter() - t0)
                for arr in arrays:
                    for loss in np.atleast_1d(arr):
                        metrics.on_train_step(float(loss))
            pending_losses.clear()
            if learn_agg is not None:
                # occupancy ages: host placement has the ring mirror right
                # here (this rank's HostReplay accountant); under the
                # device-placement lockstep ingest the stamps live only
                # device-side, so occupancy stays a single-host/host-mode
                # feature — sample ages flow either way
                occ = (host_replay.ring.live_versions() if host_mode
                       else None)
                try:
                    metrics.set_learning(learn_agg.flush(
                        step_count, publish_count=pub_count(),
                        occupancy_versions=occ))
                except RuntimeError as e:
                    if "nan_policy=halt" not in str(e):
                        raise
                    # nan_policy=halt under lockstep: raising out of the
                    # loop on rank 0 alone would abandon the other ranks
                    # mid-collective (they would wedge until the
                    # jax.distributed heartbeat timeout — the same hazard
                    # the SIGTERM path routes around). Feed the shared
                    # stop consensus instead: every rank exits the loop on
                    # the SAME iteration, then rank 0 re-raises after the
                    # clean unwind.
                    halt_error.append(e)
                    stop.set()

        debug = bool(os.environ.get("R2D2_MH_DEBUG"))
        chaos_kill_at = int(os.environ.get("R2D2_MH_CHAOS_KILL_ACTOR", "0"))
        chaos_done = False
        it = 0
        while step_count < max_steps:
            it += 1
            if straggler_factor > 1.0 and fleet_mon is not None:
                # injected compute slowdown (chaos straggler hook):
                # genuinely stretches this rank's iteration by ~factor
                time.sleep(min((straggler_factor - 1.0)
                               * fleet_mon.last_step_s, 0.25))
            local_stop = int(stop.is_set()
                             or (deadline is not None
                                 and time.time() > deadline))
            block = None
            if not paused:
                drained = queue.drain(1)
                block = drained[0] if drained else None
            if host_mode:
                if block is not None:
                    host_replay.add(block)
                    # learning_steps.sum(), not block_length: partial
                    # blocks (episode boundaries) carry zero-step slots —
                    # same accounting as lockstep_ingest's device path
                    env_local += int(np.sum(np.asarray(
                        block.learning_steps)))
                t0 = time.perf_counter()
                info = consense(len(host_replay), env_local,
                                len(host_replay) > 0, local_stop,
                                step_time_s=(fleet_mon.last_step_s
                                             if fleet_mon else 0.0))
                if fleet_mon is not None:
                    t_coll = time.perf_counter() - t0
                    fleet_mon.on_collective(info, t_coll)
                    tele.observe("lockstep/dispatch", t_coll)
                    info = {kk: v for kk, v in info.items()
                            if kk not in FLEET_INFO_KEYS}
            else:
                t0 = time.perf_counter()
                args = feed.build(block, local_stop)
                if fleet_mon is not None:
                    args = args + (feed.times(fleet_mon.last_step_s),)
                rs, cum_env, dev_info = ingest_fn(rs, cum_env, *args)
                fetched = jax.device_get(dev_info)
                t_coll = time.perf_counter() - t0
                info = {kk: int(v) for kk, v in fetched.items()
                        if kk not in FLEET_INFO_KEYS}
                if fleet_mon is not None:
                    # the dispatch+readback is the pod's synchronization
                    # point: blocked time here IS the price of skew
                    fleet_mon.on_collective(fetched, t_coll)
                    tele.observe("lockstep/dispatch", t_coll)
                if block is not None:
                    # only real ingests count — the pre-ready no-op spin
                    # iterations would otherwise dominate the histogram
                    tele.observe("ingest/commit", t_coll)
                    if snap_ring is not None:
                        # ring twin: same accounting replay_add applied
                        # in-graph, kept host-side for the snapshot cut
                        snap_ring.advance(
                            int(np.sum(np.asarray(block.learning_steps))),
                            int(np.asarray(block.weight_version)))
            if debug:
                print(f"[mh rank={rank} it={it}] step={step_count} "
                      f"block={block is not None} {info}", flush=True)
            if metrics is not None and block is not None:
                ret = float(np.asarray(block.sum_reward))
                metrics.on_block(0, None if np.isnan(ret) else ret)
            if info["stop"] > 0:
                break

            # every decision below uses only replicated values -> every
            # host takes the same branch (the lockstep invariant)
            if host_mode:
                ready = (info["ready_procs"] == nprocs
                         and info["buffer_steps"]
                         >= cfg.replay.learning_starts)
            else:
                ready = (info["filled_shards"] == dp
                         and info["buffer_steps"]
                         >= cfg.replay.learning_starts)
            paused = bool(
                ready and ratio > 0
                and info["env_steps"] >= cfg.replay.learning_starts
                    + ratio * max(step_count - step_base, 1))
            if ready:
                prev = step_count
                if host_mode:
                    t0 = time.perf_counter()
                    batch_np, snapshot = host_replay.sample(local_batch)
                    gbatch = jax.tree_util.tree_map(
                        lambda a: jax.make_array_from_process_local_data(
                            batch_sharding, np.asarray(a)), batch_np)
                    t1 = time.perf_counter()
                    tele.observe("learner/sample", t1 - t0)
                    ts, m = ext_step(ts, gbatch)
                    tele.observe("learner/train_dispatch",
                                 time.perf_counter() - t1)
                    # Pin the layout before the per-host split: the step is
                    # sharding-agnostic by design (its compiled output
                    # layout follows GSPMD's choice), so a compiler change
                    # that replicated or resharded priorities would
                    # silently hand _local_dp_values wrong-length data.
                    # device_put is a no-op when the layout already matches
                    # and an explicit reshard when it does not.
                    prios_local = _local_dp_values(
                        jax.device_put(m["priorities"], batch_sharding))
                    if len(prios_local) != len(batch_np.idxes):
                        raise RuntimeError(
                            f"priority write-back shape drift: "
                            f"{len(prios_local)} local priorities for "
                            f"{len(batch_np.idxes)} sampled idxes "
                            "(dp-sharded step output no longer matches "
                            "this host's batch rows)")
                    t0 = time.perf_counter()
                    host_replay.update_priorities(
                        batch_np.idxes, prios_local, snapshot)
                    tele.observe("learner/priority_writeback",
                                 time.perf_counter() - t0)
                    if learn_agg is not None and "ld/weight_versions" in m:
                        # the (B,) stamp/idx passthroughs keep the batch's
                        # global dp sharding, which rank 0 cannot
                        # device_get across hosts — substitute this rank's
                        # LOCAL sampled values (already host numpy; the
                        # same distribution rank 0 trained on). The
                        # reduced histograms/scalars are GSPMD reduction
                        # outputs and fetch fine.
                        m["ld/weight_versions"] = np.asarray(
                            batch_np.weight_version)
                        m["ld/batch_idxes"] = np.asarray(batch_np.idxes)
                else:
                    t0 = time.perf_counter()
                    ts, rs, m = step_fn(ts, rs)
                    tele.observe("learner/train_dispatch",
                                 time.perf_counter() - t0)
                step_count += k
                if metrics is not None:   # only rank 0 flushes; don't
                    pending_losses.append(m["loss"])   # accumulate elsewhere
                if learn_agg is not None:
                    learn_agg.on_dispatch(m)
                boundary = lambda iv: iv and step_count // iv > prev // iv
                if boundary(rt.weight_publish_interval):
                    t0 = time.perf_counter()
                    publish(ts.params)
                    tele.observe("weights/publish",
                                 time.perf_counter() - t0)
                if rank == 0 and boundary(rt.save_interval):
                    save_checkpoint(
                        rt.save_dir, cfg.env.game_name,
                        step_count // rt.save_interval, pid, ts.params,
                        ts.opt_state, ts.target_params, step_count,
                        resumed_env + info["env_steps"],
                        config_json=cfg.to_json())
                    last_ckpt_step = step_count
                    if rt.keep_checkpoints > 0:
                        # retention GC twin (ISSUE 18): same rank-0
                        # dedup rule as the other side effects
                        from r2d2_tpu.runtime.checkpoint import \
                            prune_checkpoints
                        prune_checkpoints(rt.save_dir, cfg.env.game_name,
                                          pid, rt.keep_checkpoints)
                if snap_writer is not None and boundary(
                        rt.snapshot_interval):
                    # async durable replay snapshot off the train path —
                    # capture (device→host) here at the commit boundary,
                    # serialization rides the writer thread
                    rs0 = jax.tree_util.tree_map(lambda x: x[0], rs)
                    snap_writer.submit(capture_plain(
                        spec, rs0, snap_ring, step_count))
            else:
                time.sleep(0.01)

            if (chaos_kill_at and not chaos_done
                    and actor_mode == "process" and it >= chaos_kill_at):
                # chaos hook (tests only, R2D2_MH_CHAOS_KILL_ACTOR=<it>):
                # SIGKILL one actor child mid-run, then tick supervision
                # immediately — the fleet must detect the corpse, reclaim
                # any shm ring slot it held between reserve and commit,
                # and respawn, all without disturbing the lockstep loop
                # (restarts are host-local by design, see LocalActorFleet)
                victim = fleet.threads[0]
                victim.kill()
                victim.join(5.0)
                chaos_restarted = fleet.supervise()
                import json as _json
                with open(os.path.join(rt.save_dir,
                                       f"chaos_kill_r{rank}.json"),
                          "w") as f:
                    _json.dump({"iteration": it,
                                "restarted": chaos_restarted,
                                "victim_exitcode": victim.exitcode}, f)
                chaos_done = True

            now = time.time()
            if now - last_supervise >= rt.supervise_interval_s:
                fleet.supervise()   # every host tends its own actor fleet
                last_supervise = now
                if resources is not None:
                    # resource sampling rides the supervision cadence,
                    # exactly like the single-host PlayerStack
                    resources.maybe_sample(now)
                if compile_mon is not None and step_count > step_base:
                    # this process has trained: the lockstep program (and
                    # the actor policies it feeds) compiled during warm-up
                    compile_mon.mark_warm()
            if now - last_log >= rt.log_interval:
                if metrics is not None:
                    flush_losses()
                    metrics.env_steps = resumed_env + info["env_steps"]
                    metrics.set_buffer_size(info["buffer_steps"])
                    metrics.set_actor_health(health.snapshot())
                    if fleet_mon is not None:
                        # the rank-0 fleet block: local lockstep timing +
                        # the gauge tables + the cross-host merge (other
                        # ranks' host-row ages and stage histograms)
                        metrics.set_fleet(fleet_mon.flush(
                            now=now,
                            local_stage_counts=stage_counts_dict(
                                cumulative_stage_matrix(tele))))
                    record = metrics.log(now - last_log)
                    if fleet_mon is not None and host_writer is not None:
                        # rank 0's own host row (fleet plane only): the
                        # clock anchor + cumulative stage counts for the
                        # per-rank panels — its INTERVAL summary was just
                        # consumed by the record, so the row carries the
                        # cumulative one
                        cum = cumulative_stage_matrix(tele)
                        _write_host_telemetry_row(
                            host_writer, rank, tele, t_run_start,
                            stages=summarize_stage_counts(
                                stage_counts_dict(cum)),
                            fleet_block=record.get("fleet"),
                            stage_counts=stage_counts_dict(cum),
                            clock_anchor=fleet_mon.clock_anchor,
                            actors_per_rank=n_local)
                    if log_fn:
                        log_fn({"rank": rank, **record})
                elif tele.enabled:
                    # ranks > 0 have no TrainMetrics (rank 0 de-duplicates
                    # side effects) but their pipeline still needs
                    # observability: one aggregated per-host row per
                    # interval (plus, under the fleet plane, this rank's
                    # timing block, mergeable stage counts, clock anchor,
                    # and its local alert engine's verdict)
                    fb = sc = None
                    if fleet_mon is not None:
                        fb = fleet_mon.flush(now=now)
                        sc = stage_counts_dict(
                            cumulative_stage_matrix(tele))
                    _write_host_telemetry_row(
                        host_writer, rank, tele, t_run_start,
                        resources=resources, fleet_block=fb,
                        stage_counts=sc,
                        clock_anchor=(fleet_mon.clock_anchor
                                      if fleet_mon else None),
                        actors_per_rank=(n_local if fleet_mon else None),
                        engine=host_engine)
                last_log = now
            if fleet_mon is not None:
                # close the iteration: its duration feeds the NEXT
                # iteration's psum row (a one-iteration lag — irrelevant
                # at alerting cadence) and the lockstep/step histogram.
                # The first call only arms the clock (returns 0.0) and
                # must not count as a sub-µs sample.
                step_s = fleet_mon.on_step()
                if step_s > 0:
                    tele.observe("lockstep/step", step_s)
        flush_losses()
        # preemption-safe final checkpoint (same contract as the
        # single-host Learner.save_final): a clean stop — signal fed
        # through the stop consensus, deadline, or max_steps — between
        # periodic saves writes one last rank-0 checkpoint so the pod
        # resumes from the stop point, not the last interval boundary.
        # Reached only on the clean path (every rank broke out of the
        # loop together), so params are consistent across hosts.
        if (rank == 0 and rt.save_interval
                and step_count > last_ckpt_step):
            save_checkpoint(
                rt.save_dir, cfg.env.game_name,
                step_count // rt.save_interval + 1, pid, ts.params,
                ts.opt_state, ts.target_params, step_count,
                resumed_env + info["env_steps"],
                config_json=cfg.to_json())
            if rt.keep_checkpoints > 0:
                from r2d2_tpu.runtime.checkpoint import prune_checkpoints
                prune_checkpoints(rt.save_dir, cfg.env.game_name, pid,
                                  rt.keep_checkpoints)
        if snap_writer is not None:
            # final synchronous snapshot (Learner.save_final's contract):
            # the stop point's replay contents, not the last interval's
            rs0 = jax.tree_util.tree_map(lambda x: x[0], rs)
            snap_writer.write_now(capture_plain(
                spec, rs0, snap_ring, step_count))
        if halt_error:
            # deferred nan_policy=halt (see flush_losses): every rank left
            # the loop via the stop consensus; now fail loudly on rank 0
            raise halt_error[0]
    finally:
        stop.set()
        if snap_writer is not None:
            snap_writer.stop()
        for sig, handler in prev_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass
        if fleet is not None:
            fleet.join(timeout=5.0)
        if shm_fanout is not None:
            # relays close BEFORE the root publisher (each holds a
            # subscriber on the root/parent segment)
            shm_fanout.close()
        if publisher is not None:
            publisher.close()
        queue.close()    # releases/unlinks the shm ring (owner side)
        heartbeats.close()   # releases/unlinks the heartbeat board
        tele.close()         # stops the drain thread, final flush
        if tele_board is not None:
            tele_board.close()
        if compile_mon is not None:
            # restore the pxla logger exactly (level/propagation) and
            # release this rank process's active-monitor slot
            compile_mon.uninstall()

    return {"step": step_count, "env_steps": resumed_env + info["env_steps"],
            "buffer_steps": info["buffer_steps"], "params": ts.params,
            "player_id": pid, "actor_wiring": observed_wiring}


# ---------------------------------------------------------------------------
# Producer-only host (ISSUE 16): actors on a host with NO replay shards
# emit into the usual BlockQueue; this pump drains stacked groups and
# ships them over the replay service's socket rung.  Config validation
# rejects fleet.replay_shards x mesh.multihost (the sharded service is a
# single-controller plane), so a multihost fleet reaches a remote
# ReplayService exclusively through this producer-side wiring — the
# learner host runs the service + ReplayServiceServer, producer hosts
# run their actor loops plus run_replay_producer against it.


def run_replay_producer(queue, host: str, port: int, *,
                        window: int = 1, group: int = 8,
                        stop: Optional[threading.Event] = None,
                        seconds: Optional[float] = None) -> dict:
    """Drain ``queue`` (a runtime.feeder.BlockQueue fed by this host's
    actor fleet) into the remote ReplayService at ``host:port`` until
    ``stop`` is set or ``seconds`` elapse.

    ``group`` is the stacked-frame size (mirrors
    ``fleet.ingest_batch_blocks`` on the serving side: one frame becomes
    one grouped ingest dispatch there) and ``window`` the pipelined
    in-flight frame bound (``fleet.socket_window``).  Blocks ship in
    arrival order, so the server-side routing (round-robin or lane) sees
    the exact sequence a local fleet would have produced.  Returns
    {"blocks_sent", "frames_sent", "blocks_acked"} — acked==sent after
    the final flush unless the connection died."""
    from r2d2_tpu.fleet.replay_service import (RemoteReplayProducer,
                                               ReplayProducerPump)
    producer = RemoteReplayProducer(host, port, window=window)
    pump = ReplayProducerPump(queue, producer, group=group)
    try:
        pump.run(stop=stop, seconds=seconds)
    finally:
        stats = {"blocks_sent": pump.blocks_sent,
                 "frames_sent": producer.frames_sent,
                 "blocks_acked": producer.blocks_acked}
        producer.close()
    return stats


# ---------------------------------------------------------------------------
# Loopback demo/validation: N controller processes on one machine, virtual
# CPU devices, fake env — the full rank-aware loop end-to-end (the test in
# tests/test_parallel.py runs this).

def _demo_config(save_dir: str) -> "Config":
    return Config().replace(**{
        "env.game_name": "Fake",
        "env.frame_height": 24, "env.frame_width": 24, "env.frame_stack": 2,
        "network.hidden_dim": 16, "network.cnn_out_dim": 32,
        "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
        "sequence.burn_in_steps": 4, "sequence.learning_steps": 5,
        "sequence.forward_steps": 3,
        "replay.capacity": 800, "replay.block_length": 20,
        "replay.batch_size": 4, "replay.learning_starts": 60,
        "actor.num_actors": 1,
        "runtime.save_dir": save_dir, "runtime.save_interval": 4,
        "runtime.log_interval": 2.0, "runtime.weight_publish_interval": 2,
        "runtime.steps_per_dispatch": 2,
        "mesh.multihost": True,
    })


def _demo_worker(process_id: int, num_processes: int, coordinator: str,
                 devices_per_process: int, save_dir: str,
                 max_steps: int, resume: str = "",
                 actor_mode: str = "thread", mp: int = 1,
                 player_id: int = -1, num_players: int = 2,
                 num_actors: int = 1, placement: str = "device",
                 envs_per_actor: int = 1) -> None:
    from r2d2_tpu.utils.platform import pin_cpu_platform
    pin_cpu_platform(devices_per_process)
    import jax

    n_global = num_processes * devices_per_process
    cfg = _demo_config(save_dir).replace(**{
        "mesh.coordinator_address": coordinator,
        "mesh.num_processes": num_processes, "mesh.process_id": process_id,
        "mesh.dp": n_global // mp, "mesh.mp": mp,
        "actor.num_actors": num_actors,
        "actor.envs_per_actor": envs_per_actor,
        "replay.placement": placement,
        **({"runtime.resume": resume} if resume else {}),
        **({"multiplayer.enabled": True, "multiplayer.player_id": player_id,
            "multiplayer.num_players": num_players}
           if player_id >= 0 else {}),
    })
    out = train_multihost(cfg, max_training_steps=max_steps, max_seconds=240,
                          actor_mode=actor_mode)

    # Bit-exactness evidence, asserted in two layers: replicated leaves'
    # local shards identical within this process here (mp-SHARDED leaves
    # carry different slices per device by design, so they digest as the
    # gathered global array), and the full-tree digest identical ACROSS
    # processes by launch_demo (the cross-host invariant README
    # advertises).
    import hashlib
    import json
    os.makedirs(save_dir, exist_ok=True)   # no checkpoint may have created it
    if cfg.mesh.mp > 1:
        # the tp run must GENUINELY shard (a silently-replicated "tp" run
        # would pass every other check)
        assert any(not l.sharding.is_fully_replicated
                   for l in jax.tree_util.tree_leaves(out["params"])), \
            "mp > 1 but every param leaf is replicated"
    digest = hashlib.sha256()
    for path, leaf in sorted(
            jax.tree_util.tree_flatten_with_path(out["params"])[0],
            key=lambda kv: str(kv[0])):
        if leaf.sharding.is_fully_replicated:
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            for s in shards[1:]:
                np.testing.assert_array_equal(shards[0], s)
        digest.update(str(path).encode())
        digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
    with open(os.path.join(save_dir, f"params_digest_r{process_id}.json"),
              "w") as f:
        json.dump({"step": out["step"], "sha256": digest.hexdigest(),
                   "player_id": out["player_id"],
                   "actor_wiring": out["actor_wiring"]}, f)
    print(f"[proc {process_id}] multihost train ok: step={out['step']} "
          f"env_steps={out['env_steps']} sha256={digest.hexdigest()[:16]}",
          flush=True)


def launch_demo(num_processes: int = 2, devices_per_process: int = 2,
                save_dir: str = "/tmp/r2d2_multihost_demo",
                max_steps: int = 8, timeout: float = 300.0,
                resume: str = "", actor_mode: str = "thread",
                mp: int = 1, player_id: int = -1,
                num_players: int = 2, num_actors: int = 1,
                placement: str = "device", envs_per_actor: int = 1) -> list:
    """Spawn the loopback controllers and assert the final params came out
    BIT-IDENTICAL across hosts (each worker writes a digest file covering
    every param leaf; divergence anywhere fails the launch). Returns the
    per-rank digest records ({step, sha256, player_id, actor_wiring}).
    ``player_id >= 0`` runs the job as ONE player of a multiplayer
    population (README "Multiplayer at pod scale"); per-player jobs must
    all configure the same TOTAL actor fan-out (num_processes *
    num_actors), since the game index is the global actor index.
    ``actor_wiring`` is observed from the envs in thread actor mode only —
    process-mode actors build their envs in spawned children, so the
    records carry None there."""
    import glob
    import json
    import sys

    from r2d2_tpu.parallel.loopback import run_loopback_workers

    for stale in glob.glob(os.path.join(save_dir, "params_digest_r*.json")):
        os.remove(stale)
    run_loopback_workers(
        lambda pid, coordinator: [
            sys.executable, "-m", "r2d2_tpu.parallel.multihost",
            f"--process-id={pid}", f"--num-processes={num_processes}",
            f"--coordinator={coordinator}",
            f"--devices-per-process={devices_per_process}",
            f"--save-dir={save_dir}", f"--max-steps={max_steps}",
            f"--resume={resume}", f"--actor-mode={actor_mode}",
            f"--mp={mp}", f"--player-id={player_id}",
            f"--num-players={num_players}", f"--num-actors={num_actors}",
            f"--placement={placement}",
            f"--envs-per-actor={envs_per_actor}",
        ], num_processes, timeout, "multihost train demo")

    digests = []
    for pid in range(num_processes):
        with open(os.path.join(save_dir, f"params_digest_r{pid}.json")) as f:
            digests.append(json.load(f))
    # step + param digest must match on every rank; actor_wiring is
    # rank-local by design (each host's actors own different game ports)
    core = [{k: d[k] for k in ("step", "sha256")} for d in digests]
    if any(c != core[0] for c in core[1:]):
        raise SystemExit(
            f"multihost train demo: params DIVERGED across controllers: "
            f"{digests}")
    print(f"multihost train demo: {num_processes} controllers x "
          f"{devices_per_process} devices ok, params bit-identical "
          f"across hosts", flush=True)
    return digests


def main(argv=None) -> None:
    import argparse
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument("--num-processes", type=int, default=2)
    p.add_argument("--coordinator", default=None)
    p.add_argument("--devices-per-process", type=int, default=2)
    p.add_argument("--save-dir", default="/tmp/r2d2_multihost_demo")
    p.add_argument("--max-steps", type=int, default=8)
    p.add_argument("--resume", default="")
    p.add_argument("--actor-mode", choices=("thread", "process"),
                   default="thread")
    p.add_argument("--mp", type=int, default=1,
                   help="tensor-parallel axis width (params feature-sharded "
                        "over mp; must divide devices-per-process)")
    p.add_argument("--player-id", type=int, default=-1,
                   help=">= 0: run this job as ONE player of a multiplayer "
                        "population (one multihost job per player)")
    p.add_argument("--num-players", type=int, default=2)
    p.add_argument("--num-actors", type=int, default=1,
                   help="actors per controller; per-player jobs must all "
                        "match on num_processes * num_actors")
    p.add_argument("--envs-per-actor", type=int, default=1,
                   help="env lanes per actor worker (vectorized actor; the "
                        "ε ladder spans num_processes * num_actors * lanes)")
    p.add_argument("--placement", choices=("device", "host"),
                   default="device",
                   help="replay placement: device = HBM rings + lockstep "
                        "ingest; host = per-process CPU HostReplay + "
                        "consensus psum + external-batch step")
    args = p.parse_args(argv)
    if args.process_id is None:
        launch_demo(args.num_processes, args.devices_per_process,
                    args.save_dir, args.max_steps, resume=args.resume,
                    actor_mode=args.actor_mode, mp=args.mp,
                    player_id=args.player_id, num_players=args.num_players,
                    num_actors=args.num_actors, placement=args.placement,
                    envs_per_actor=args.envs_per_actor)
    else:
        _demo_worker(args.process_id, args.num_processes, args.coordinator,
                     args.devices_per_process, args.save_dir, args.max_steps,
                     resume=args.resume, actor_mode=args.actor_mode,
                     mp=args.mp, player_id=args.player_id,
                     num_players=args.num_players,
                     num_actors=args.num_actors, placement=args.placement,
                     envs_per_actor=args.envs_per_actor)


if __name__ == "__main__":
    main()
