"""End-to-end actors→learner throughput benchmark.

Everything measured before this tool was learner-only (fused-step
seq-updates/s on synthetic batches; tools/soak.py: device-side ring
behavior). This tool measures the SYSTEM: how fast experience is generated
and how fast it is consumed, simultaneously — the reference's two logged
speeds, 'buffer update speed' and 'training speed'
(/root/reference/worker.py:222,229) — plus an actor-only scalar-vs-vector
sweep that quantifies the ``actor.envs_per_actor`` batching win on this
host (VERDICT "Next round" #3: does the feeder side become the wall?).

Phases:

  1. **Actor sweep** (in-process, no learner): one actor worker on the fake
     env at each requested ``envs_per_actor`` (1 = the legacy scalar loop,
     N>1 = the vectorized loop's single jitted (N, 1) forward), timed after
     a compile warm-up. Reports env-steps/s per cell and the speedup over
     the scalar loop — the Podracer-style batching measurement (arxiv
     2104.06272, 1907.08467).
  2. **End-to-end run** (optional, ``--e2e-seconds > 0``): the REAL system —
     process-mode vector actors feeding the real learner through the shm
     block ring — via orchestrator.train, reporting steady-state env-steps/s
     and learner updates/s (and seq-updates/s = updates/s × batch) from the
     TrainMetrics records.
  3. **Ingestion A/B** (default when the e2e phase runs, ``--ingest-ab``):
     the e2e run twice — batched+pipelined replay ingestion
     (``replay.ingest_batch_blocks = K``: stacked feeder drains, one
     ``replay_add_many`` dispatch per K blocks, background stager) vs the
     legacy per-block path — with blocks/s ingested, drain latency, and
     rate-limiter pause time from the ingestion counters, in one artifact.
  4. **Sharded-anakin A/B** (``--sharded-anakin-ab``): the fused
     act+train loop on a 1x1 mesh vs the same total lane count
     partitioned across a dp-wide (CPU-emulated) mesh — per-shard lane
     groups acting into local replay shards alongside the dp-sharded
     learner step — with per-arm medians and the env/learner scaling
     ratios in one artifact (``E2E_r12.json``).
  5. **Telemetry / learning / resources / tracing A/Bs**
     (``--telemetry-ab`` / ``--learning-ab`` / ``--resources-ab`` /
     ``--tracing-ab``): the same e2e system with the respective kill
     switch on vs off — the < 2% overhead budgets for the PR-4 stage
     telemetry, the PR-5 fused learning diagnostics (histograms,
     staleness, ΔQ cadence), the PR-7 machine-side pillar (memory
     sampling, RSS/CPU gauges, compile/retrace capture, the per-record
     alert pass), and the PR-19 cross-plane experience lineage (sampled
     ``Block.trace_ms`` stamps, ring mirrors, the env-step→gradient
     latency block).
  6. **Fleet A/B** (``--fleet-ab``): the lockstep multihost trainer (one
     controller over an emulated dp mesh) with ``telemetry.fleet_enabled``
     on vs off — the widened psum gauges, per-iteration lockstep timing,
     and the rank-0 FleetAggregator under the same < 2% budget
     (``E2E_r14.json``).
  7. **Quant A/B** (``--quant-ab``): the quantized inference plane
     (ISSUE 14) — thread-mode acting arm at ``network.inference_dtype``
     f32 vs int8 (ABBA medians; int8 cells carry the ``quant`` accuracy
     block), a serving-probe arm at both dtypes, and the analytic
     weight-bytes table with the >= 3x int8 streaming cut
     (``E2E_r16.json``).

Output: ONE JSON line (the driver artifact), also written to ``--out``.
Hermetic on any backend — the fake env and (for the e2e phase) a
CPU-feasible reduced training shape, recorded in the artifact.
"""

import json
import os
import sys
import time
from typing import List, Optional, Tuple

import numpy as np

# CPU-feasible e2e shape: the full system topology (process actors, shm
# ring, real learner) at a reduced frame/network/batch shape so BOTH sides
# sustain measurable rates on a small CPU host (this container has 2 cores;
# a batch x window learner step at the reference shape takes ~25 s there,
# starving the measurement). The artifact records the exact config; TPU
# runs can override back to the reference training shape.
E2E_CPU_OVERRIDES = {
    "env.frame_height": 42, "env.frame_width": 42,
    "network.hidden_dim": 128, "network.cnn_out_dim": 256,
    "network.conv_layers": ((16, 8, 4), (32, 4, 2)),
    "sequence.burn_in_steps": 8, "sequence.learning_steps": 5,
    "sequence.forward_steps": 3,
    "replay.capacity": 40_000, "replay.block_length": 80,
    "replay.batch_size": 8, "replay.learning_starts": 800,
    "runtime.save_interval": 0, "runtime.log_interval": 2.0,
}


def _bench_config(overrides: Optional[dict] = None):
    from r2d2_tpu.config import Config
    base = {"env.game_name": "Fake"}
    base.update(overrides or {})
    return Config().replace(**base)


def measure_actor_throughput(cfg, envs_per_actor: int, seconds: float = 5.0,
                             seed: int = 0) -> dict:
    """env-steps/s of ONE actor worker on the fake env: the scalar loop at
    envs_per_actor=1, the vectorized loop otherwise. Blocks are dropped at
    the sink — this isolates the generation side (policy inference + env
    stepping + LocalBuffer assembly), the part envs_per_actor batches."""
    import jax

    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.actor_loop import make_actor_env, make_actor_policy

    cfg = cfg.replace(**{"actor.envs_per_actor": envs_per_actor})
    net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    params = net.init(jax.random.PRNGKey(0))
    sink = lambda _block: None
    no_poll = lambda: None

    # the same construction path the orchestrator and actor processes use
    env = make_actor_env(cfg, 0, 0, seed)
    policy, run_loop = make_actor_policy(cfg, net, params, 0, seed,
                                         epsilon=cfg.actor.base_eps)
    run = lambda stop, cap: run_loop(cfg, env, policy, sink, no_poll, stop,
                                     max_env_steps=cap)

    # compile warm-up outside the timed window (the jitted step + the
    # bootstrap share one program; one step() compiles it)
    policy.step()

    deadline = [0.0]
    stop = lambda: time.time() >= deadline[0]
    t0 = time.time()
    deadline[0] = t0 + seconds
    steps = run(stop, None)
    elapsed = time.time() - t0
    return {"envs_per_actor": envs_per_actor, "env_steps": int(steps),
            "seconds": round(elapsed, 3),
            "env_steps_per_sec": round(steps / elapsed, 1)}


def run_actor_sweep(sweep: List[int], seconds: float = 5.0,
                    overrides: Optional[dict] = None) -> dict:
    """The scalar-vs-vectorized table; speedups are against the sweep's
    envs_per_actor=1 cell (the legacy loop's aggregate env-steps/s — what
    those same envs achieve when stepped one-at-a-time)."""
    cfg = _bench_config(overrides)
    cells = [measure_actor_throughput(cfg, k, seconds=seconds) for k in sweep]
    out = {"cells": cells}
    base = next((c for c in cells if c["envs_per_actor"] == 1), None)
    if base is not None:
        # only a measured k=1 cell may serve as the scalar baseline — a
        # sweep without one gets no speedup fields rather than a mislabel
        for c in cells:
            c["speedup_vs_scalar"] = round(
                c["env_steps_per_sec"] / base["env_steps_per_sec"], 2)
        out["scalar_env_steps_per_sec"] = base["env_steps_per_sec"]
    return out


def run_e2e(seconds: float = 60.0, envs_per_actor: int = 16,
            num_actors: int = 1, overrides: Optional[dict] = None,
            actor_mode: str = "process") -> dict:
    """Process-mode (default) vector actors feeding the REAL learner;
    both speeds measured from the same run's TrainMetrics records
    (steady-state mean: records after the first, when training has
    started). The serve A/B runs the same system in thread mode so the
    in-proc serving rung carries client-observed latencies."""
    from r2d2_tpu.runtime.orchestrator import train

    ov = dict(E2E_CPU_OVERRIDES)
    ov.update({"actor.num_actors": num_actors,
               "actor.envs_per_actor": envs_per_actor})
    ov.update(overrides or {})
    # bench runs must not litter the default save_dir with telemetry
    # streams (save_interval is 0 here, but spans/metrics still write);
    # a scratch dir we created is removed again after the run
    scratch = None
    if "runtime.save_dir" not in ov:
        import tempfile
        scratch = tempfile.mkdtemp(prefix="r2d2_e2e_")
        ov["runtime.save_dir"] = scratch
    cfg = _bench_config(ov)
    records = []
    t0 = time.time()
    try:
        stacks = train(cfg, max_seconds=seconds, actor_mode=actor_mode,
                       log_fn=records.append)
    finally:
        if scratch is not None:
            import shutil
            shutil.rmtree(scratch, ignore_errors=True)
    elapsed = time.time() - t0
    learner = stacks[0].learner
    batch = cfg.replay.batch_size
    # steady state: drop the first record (warm-up/fill dominates it) and
    # records where training had not started; if NONE qualify (run too
    # short to train) the steady-state speeds report 0 — the *_overall
    # fields still carry the whole-run rates, never mislabeled warm-up
    steady = [r for r in records[1:] if r.get("training_speed")]
    env_speed = (float(np.mean([r["buffer_speed"] for r in steady]))
                 if steady else 0.0)
    train_speed = (float(np.mean([r["training_speed"] for r in steady]))
                   if steady else 0.0)
    # ingestion observability (TrainMetrics ingest counters, ISSUE 2)
    blocks_total = learner.metrics.ingest_blocks_total
    bpd = [r["ingest_blocks_per_drain"] for r in records
           if r.get("ingest_blocks_per_drain")]
    lat = [r["ingest_drain_latency_ms"] for r in records
           if r.get("ingest_drain_latency_ms") is not None]
    # telemetry evidence (ISSUE 4): per-stage the newest summary seen in
    # any record (union, not last-record-only: the board flush cadence can
    # exceed this shape's short log interval, so actor stages land in
    # SOME intervals — at the production log_interval every record has
    # them)
    stages = {}
    for r in records:
        stages.update(r.get("stages") or {})
    stages = stages or None
    # learning-diagnostics evidence (ISSUE 5): newest non-null value per
    # field across the records (ΔQ fires on its own step cadence, so most
    # short log intervals carry None for it — field-wise merge keeps the
    # last real sample); histogram bucket dumps stripped (the artifact
    # wants the summary, not 3x64 counts)
    learning = None
    for r in records:
        lb = r.get("learning")
        if not lb:
            continue
        clean = {k: v for k, v in lb.items() if not k.endswith("_counts")}
        if learning is None:
            learning = clean
        else:
            learning.update(
                {k: v for k, v in clean.items() if v is not None})
    # sharded-anakin evidence (ISSUE 8): the newest per-shard block (dp,
    # lanes/shard, per-shard env steps, imbalance); absent on non-anakin
    # runs
    anakin = next((r["anakin"] for r in reversed(records)
                   if r.get("anakin")), None)
    # replay-diagnostics evidence (ISSUE 10): field-wise merge, newest
    # non-null value per sub-block (tree snapshots fire on their own
    # cadence; evictions only appear once the ring wraps), histogram
    # count dumps stripped like the learning block's
    replay_diag = None
    for r in records:
        rd = r.get("replay_diag")
        if not rd:
            continue
        clean = {k: ({kk: vv for kk, vv in v.items()
                      if not kk.endswith("_counts")}
                     if isinstance(v, dict) else v)
                 for k, v in rd.items()}
        if replay_diag is None:
            replay_diag = clean
        else:
            replay_diag.update(
                {k: v for k, v in clean.items() if v is not None})
    # serving evidence (ISSUE 13): field-wise merge of the serving
    # blocks, newest non-null per sub-field (the latency histogram and
    # batch stats reset per interval, so one quiet interval must not
    # blank the evidence); present only on inference="server" runs
    serving = None
    for r in records:
        sb = r.get("serving")
        if not sb:
            continue
        if serving is None:
            serving = dict(sb)
        else:
            serving.update({k: v for k, v in sb.items() if v is not None})
    # quantized-inference evidence (ISSUE 14): probe COUNTS accumulate
    # across the run (per-interval ints read 0, not None, in a
    # probe-free interval — last-wins would erase the run's evidence);
    # the quality gauges take the newest non-null value. None on every
    # inference_dtype="f32" run (the sibling serving/anakin convention:
    # the key is always present, null when the plane is off).
    quant = None
    for r in records:
        qb = r.get("quant")
        if not qb:
            continue
        if quant is None:
            quant = dict(qb)
            continue
        for k, v in qb.items():
            if k in ("probes", "lanes_probed"):
                quant[k] = (quant.get(k) or 0) + (v or 0)
            elif v is not None:
                quant[k] = v
    # elastic-fleet evidence (ISSUE 15): field-wise merge of the
    # replay_service blocks, newest non-null per sub-block (membership
    # joins/leaves are cumulative so last-wins is exact; spill interval
    # counters take the newest populated snapshot); None on every run
    # with no fleet plane configured (the key-absence contract)
    replay_service = None
    for r in records:
        fb = r.get("replay_service")
        if not fb:
            continue
        if replay_service is None:
            replay_service = dict(fb)
        else:
            replay_service.update(
                {k: v for k, v in fb.items() if v is not None})
    # experience-lineage evidence (ISSUE 19): sampled COUNTS accumulate
    # across records (each interval_block consumes its interval, so
    # last-wins would erase the run's tally); the latency histograms
    # take the newest non-null summary. None on every run with
    # tracing_enabled off (the key-absence contract).
    trace = None
    for r in records:
        tb = r.get("trace")
        if not tb:
            continue
        if trace is None:
            trace = dict(tb)
            continue
        for k, v in tb.items():
            if k == "sampled":
                trace[k] = (trace.get(k) or 0) + (v or 0)
            elif v is not None:
                trace[k] = v
    # policy-quality evidence (ISSUE 20): sub-block-wise merge, newest
    # non-null (the eval snapshot persists across intervals and every
    # sub-block carries its own cumulative totals, so last-wins is
    # exact; interval-consumed calibration/shadow extrema take the
    # newest populated interval). None on every run with
    # quality_enabled off (the key-absence contract).
    quality = None
    for r in records:
        qy = r.get("quality")
        if not qy:
            continue
        if quality is None:
            quality = dict(qy)
        else:
            quality.update({k: v for k, v in qy.items() if v is not None})
    # crash-recovery evidence (ISSUE 18): the newest recovery block —
    # its snapshot counters are cumulative, so last-wins is exact; None
    # on every run with the snapshot plane off (the key-absence
    # contract, like serving/quant/replay_service)
    recovery = next((r["recovery"] for r in reversed(records)
                     if r.get("recovery")), None)
    # system-health evidence (ISSUE 7): the newest resources block plus
    # the run's alert tally — proof the pillar actually flowed (or, with
    # the kill switch off, that the records carried neither key)
    resources = next((r["resources"] for r in reversed(records)
                      if r.get("resources")), None)
    alerts_fired = sum(len((r.get("alerts") or {}).get("fired") or [])
                       for r in records)
    alerts_present = any("alerts" in r for r in records)
    return {
        "seconds": round(elapsed, 1),
        "num_actors": num_actors,
        "envs_per_actor": envs_per_actor,
        "ingest_batch_blocks": learner._ingest_k,
        "total_env_steps": int(learner.env_steps),
        "total_train_steps": int(learner.training_steps),
        "env_steps_per_sec": round(env_speed, 1),
        "learner_steps_per_sec": round(train_speed, 2),
        "learner_seq_updates_per_sec": round(train_speed * batch, 1),
        "env_steps_per_sec_overall": round(learner.env_steps / elapsed, 1),
        "learner_steps_per_sec_overall": round(
            learner.training_steps / elapsed, 2),
        "blocks_ingested": int(blocks_total),
        "blocks_ingested_per_sec": round(blocks_total / elapsed, 2),
        "ingest_blocks_per_drain": (round(float(np.mean(bpd)), 2)
                                    if bpd else None),
        "ingest_drain_latency_ms": (round(float(np.mean(lat)), 3)
                                    if lat else None),
        "ingest_pause_time": round(
            sum(r.get("ingest_pause_time") or 0.0 for r in records), 3),
        "batch_size": batch,
        "records": len(records),
        "stages": stages,
        "learning": learning,
        "replay_diag": replay_diag,
        "anakin": anakin,
        "serving": serving,
        "quant": quant,
        "trace": trace,
        "quality": quality,
        "replay_service": replay_service,
        "recovery": recovery,
        "resources": resources,
        "alerts_present": alerts_present,
        "alerts_fired": alerts_fired,
        "config": {k: ov[k] for k in sorted(ov)},
    }


def run_ingest_ab(seconds: float, envs_per_actor: int, num_actors: int,
                  ingest_blocks: int, overrides: Optional[dict] = None
                  ) -> dict:
    """Ingestion A/B (ISSUE 2 acceptance): the SAME e2e system run twice on
    this host — batched+pipelined ingestion (replay.ingest_batch_blocks =
    ``ingest_blocks``) vs the legacy per-block path (= 1) — in one
    artifact. The claim under test: higher learner updates/s at unchanged
    env-steps/s when per-block dispatch leaves the learner's critical
    path."""
    out = {}
    for label, k in (("ingest_off", 1), ("ingest_on", ingest_blocks)):
        ov = dict(overrides or {})
        ov["replay.ingest_batch_blocks"] = k
        out[label] = run_e2e(seconds, envs_per_actor, num_actors,
                             overrides=ov)
    off, on = out["ingest_off"], out["ingest_on"]
    if off["learner_steps_per_sec"] > 0:
        out["learner_speedup"] = round(
            on["learner_steps_per_sec"] / off["learner_steps_per_sec"], 3)
    if off["env_steps_per_sec"] > 0:
        out["env_steps_ratio"] = round(
            on["env_steps_per_sec"] / off["env_steps_per_sec"], 3)
    return out


def run_telemetry_ab(seconds: float, envs_per_actor: int, num_actors: int,
                     overrides: Optional[dict] = None) -> dict:
    """Telemetry overhead A/B (ISSUE 4 acceptance): the SAME e2e system
    run twice — ``telemetry.enabled`` on vs off — in one artifact. The
    budget under test: full telemetry (per-stage histograms on every
    pipeline hot path, span rings, board publication) costs < 2%
    env-steps/s. The ON cell also carries the aggregated stage
    percentiles as evidence the instrumentation actually flowed."""
    out = {}
    for label, on in (("telemetry_off", False), ("telemetry_on", True)):
        ov = dict(overrides or {})
        ov["telemetry.enabled"] = on
        out[label] = run_e2e(seconds, envs_per_actor, num_actors,
                             overrides=ov)
    off, on_ = out["telemetry_off"], out["telemetry_on"]
    if off["env_steps_per_sec"] > 0:
        ratio = on_["env_steps_per_sec"] / off["env_steps_per_sec"]
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if off["learner_steps_per_sec"] > 0:
        out["learner_steps_ratio"] = round(
            on_["learner_steps_per_sec"] / off["learner_steps_per_sec"], 3)
    out["stage_count_on"] = len(on_.get("stages") or {})
    return out


def run_learning_ab(seconds: float, envs_per_actor: int, num_actors: int,
                    overrides: Optional[dict] = None,
                    repeats: int = 2) -> dict:
    """Learning-diagnostics overhead A/B (ISSUE 5 acceptance): the SAME
    e2e system with ``telemetry.learning_enabled`` on vs off, in one
    artifact. Budget under test: fused histograms + staleness stamps +
    the interval-gated ΔQ unrolls cost < 2% on BOTH env-steps/s and
    learner updates/s. The ON cell carries the aggregated ``learning``
    block (ΔQ stored/zero/recomputed, sample ages, grad norms) as
    evidence the diagnostics actually flowed end-to-end.

    Cells run INTERLEAVED off/on ``repeats`` times and the headline
    ratios come from per-arm medians: on a small shared host the actor
    side swings ±10% run-to-run (2-core scheduling noise dwarfs the
    effect under test — the telemetry-AB round hit the same wall), and a
    single pair routinely reports whichever way the wind blew. Every
    cell's speeds stay in the artifact."""
    cells = {"learning_off": [], "learning_on": []}
    for _ in range(max(repeats, 1)):
        for label, on in (("learning_off", False), ("learning_on", True)):
            ov = dict(overrides or {})
            ov["telemetry.learning_enabled"] = on
            # dQ must FIRE inside the window for the evidence fields, but
            # its cadence is the measurement: one reference unroll costs
            # ~2 train steps (measured on the CPU e2e shape), so
            # interval=100 amortizes to ~1% of learner time — the
            # production default (200) halves that again. Forcing a tight
            # cadence here would measure a config nobody runs.
            ov.setdefault("telemetry.learning_interval", 100)
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"learning_off": cells["learning_off"][-1],
           "learning_on": cells["learning_on"][-1],
           "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("learning_off", "env_steps_per_sec") > 0:
        ratio = (med("learning_on", "env_steps_per_sec")
                 / med("learning_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("learning_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("learning_on", "learner_steps_per_sec")
            / med("learning_off", "learner_steps_per_sec"), 3)
    # evidence: newest ON cell carrying each field
    lb = {}
    for c in cells["learning_on"]:
        lb.update({k: v for k, v in (c.get("learning") or {}).items()
                   if v is not None})
    out["learning_block_on"] = bool(lb)
    out["delta_q_on"] = lb.get("delta_q")
    out["sample_age_on"] = lb.get("sample_age")
    out["learning_block_off"] = any(
        c.get("learning") for c in cells["learning_off"])
    return out


def run_resources_ab(seconds: float, envs_per_actor: int, num_actors: int,
                     overrides: Optional[dict] = None,
                     repeats: int = 2) -> dict:
    """Resource/compile/alerts overhead A/B (ISSUE 7 acceptance): the
    SAME e2e system with ``telemetry.resources_enabled`` on vs off, in
    one artifact. Budget under test: the machine-side pillar — periodic
    ``memory_stats`` sampling + buffer attribution, per-actor-slot
    RSS/CPU gauges through the shm board, the compile/retrace log
    listener, and the per-record alert-rule pass — costs < 2% on BOTH
    env-steps/s and learner updates/s (the PR4 budget). Cells run
    INTERLEAVED off/on ``repeats`` times with per-arm medians, exactly
    like the learning A/B (single cells swing ±10% on the 2-core host).
    The ON cells carry the ``resources`` block + the alert tally as
    evidence the pillar actually flowed; the OFF cells prove the records
    carried neither key (the kill-switch schema contract)."""
    cells = {"resources_off": [], "resources_on": []}
    for _ in range(max(repeats, 1)):
        for label, on in (("resources_off", False), ("resources_on", True)):
            ov = dict(overrides or {})
            ov["telemetry.resources_enabled"] = on
            # sample every interval at this short log cadence — the
            # PRODUCTION default (10 s) samples less often, so benching
            # the tighter cadence bounds the real overhead from above
            ov.setdefault("telemetry.resources_interval_s", 2.0)
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"resources_off": cells["resources_off"][-1],
           "resources_on": cells["resources_on"][-1],
           "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("resources_off", "env_steps_per_sec") > 0:
        ratio = (med("resources_on", "env_steps_per_sec")
                 / med("resources_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("resources_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("resources_on", "learner_steps_per_sec")
            / med("resources_off", "learner_steps_per_sec"), 3)
    on_cells = cells["resources_on"]
    out["resources_block_on"] = any(c.get("resources") for c in on_cells)
    out["alerts_block_on"] = any(c.get("alerts_present") for c in on_cells)
    out["alerts_fired_on"] = sum(c.get("alerts_fired") or 0
                                 for c in on_cells)
    rb = next((c["resources"] for c in reversed(on_cells)
               if c.get("resources")), None)
    out["compile_block_on"] = bool(rb and rb.get("compile"))
    out["resources_block_off"] = any(
        c.get("resources") for c in cells["resources_off"])
    out["alerts_block_off"] = any(
        c.get("alerts_present") for c in cells["resources_off"])
    return out


def run_recovery_ab(seconds: float, envs_per_actor: int, num_actors: int,
                    overrides: Optional[dict] = None,
                    repeats: int = 2,
                    snapshot_interval: int = 200) -> dict:
    """Crash-recovery plane overhead A/B (ISSUE 18 acceptance): the SAME
    e2e system with ``runtime.snapshot_interval`` on vs off, in one
    artifact. Budget under test: the durable replay snapshot path —
    per-interval device→host ring capture, the async SnapshotWriter's
    npz serialization + atomic tmp/rename commit, and the recovery
    telemetry block — costs < 2% on BOTH env-steps/s and learner
    updates/s (the capture is the only on-path piece; the write rides a
    background thread). Cells run INTERLEAVED off/on ``repeats`` times
    with per-arm medians, like the resources A/B. The ON cells carry
    the ``recovery`` block (snapshot count/bytes/write_s) as evidence
    snapshots actually flowed; the OFF cells prove the records carried
    no ``recovery`` key at all (the kill-switch schema contract)."""
    cells = {"recovery_off": [], "recovery_on": []}
    for _ in range(max(repeats, 1)):
        for label, interval in (("recovery_off", 0),
                                ("recovery_on", snapshot_interval)):
            ov = dict(overrides or {})
            ov["runtime.snapshot_interval"] = interval
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"recovery_off": cells["recovery_off"][-1],
           "recovery_on": cells["recovery_on"][-1],
           "repeats": max(repeats, 1),
           "snapshot_interval": snapshot_interval,
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("recovery_off", "env_steps_per_sec") > 0:
        ratio = (med("recovery_on", "env_steps_per_sec")
                 / med("recovery_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("recovery_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("recovery_on", "learner_steps_per_sec")
            / med("recovery_off", "learner_steps_per_sec"), 3)
    on_cells = cells["recovery_on"]
    out["recovery_block_on"] = any(c.get("recovery") for c in on_cells)
    rb = next((c["recovery"] for c in reversed(on_cells)
               if c.get("recovery")), None)
    if rb:
        out["snapshots_written"] = (rb.get("snapshot") or {}).get("count")
        out["snapshot_bytes"] = (rb.get("snapshot") or {}).get("bytes")
        out["snapshot_write_s"] = (rb.get("snapshot") or {}).get("write_s")
    out["recovery_block_off"] = any(
        c.get("recovery") for c in cells["recovery_off"])
    return out


def run_tracing_ab(seconds: float, envs_per_actor: int, num_actors: int,
                   overrides: Optional[dict] = None,
                   repeats: int = 2) -> dict:
    """Cross-plane tracing overhead A/B (ISSUE 19 acceptance): the SAME
    e2e system with ``telemetry.tracing_enabled`` on vs off, in one
    artifact. Budget under test: the lineage path — the per-emission
    sampled stamp on ``Block.trace_ms``, the strip-before-device-commit
    + ring-mirror bookkeeping inside the ingest path, the sample-time
    slot lookup, and the per-record ``trace`` block assembly — costs
    <= 2%% on BOTH env-steps/s and learner updates/s. Cells run
    ABBA-interleaved ``repeats`` times with per-arm medians (the
    serve/fleet-AB noise treatment; single cells swing ±10%% on the
    2-core host). The ON cells carry the ``trace`` block (sampled rows,
    the env-step->gradient e2e histogram, per-hop breakdown) as
    end-to-end evidence; the OFF cells prove the records carried no
    ``trace`` key at all (the kill-switch schema contract)."""
    cells = {"tracing_off": [], "tracing_on": []}
    for rep in range(max(repeats, 1)):
        order = (("tracing_off", False), ("tracing_on", True))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, on in order:
            ov = dict(overrides or {})
            ov["telemetry.tracing_enabled"] = on
            # trace a denser fraction than the production default so the
            # short window accumulates real histograms — stamping MORE
            # blocks bounds the per-emission overhead from above
            ov.setdefault("telemetry.trace_sample_every", 4)
            # lineage lives on the replay-service path (the ring-mirror
            # bookkeeping under test); BOTH arms run it so the A/B
            # isolates tracing, not the service plane itself
            ov.setdefault("fleet.replay_shards", 1)
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"tracing_off": cells["tracing_off"][-1],
           "tracing_on": cells["tracing_on"][-1],
           "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("tracing_off", "env_steps_per_sec") > 0:
        ratio = (med("tracing_on", "env_steps_per_sec")
                 / med("tracing_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("tracing_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("tracing_on", "learner_steps_per_sec")
            / med("tracing_off", "learner_steps_per_sec"), 3)
    # evidence: merge the ON cells' trace blocks (counts sum, hop
    # summaries newest-non-null — the run_e2e merge semantics again)
    tb = {}
    for c in cells["tracing_on"]:
        for k, v in (c.get("trace") or {}).items():
            if k == "sampled":
                tb[k] = (tb.get(k) or 0) + (v or 0)
            elif v is not None:
                tb[k] = v
    out["trace_block_on"] = bool(tb)
    out["traced_rows_on"] = tb.get("sampled")
    e2e = tb.get("e2e_experience_latency") or {}
    out["e2e_latency_p50_ms"] = e2e.get("p50_ms")
    out["e2e_latency_p95_ms"] = e2e.get("p95_ms")
    out["hops_on"] = sorted((tb.get("hops") or {}).keys())
    out["trace_block_off"] = any(
        c.get("trace") for c in cells["tracing_off"])
    return out


def run_promotion_ab(seconds: float, envs_per_actor: int, num_actors: int,
                     overrides: Optional[dict] = None,
                     repeats: int = 2) -> dict:
    """Policy-quality overhead A/B + promotion-drill evidence (ISSUE 20
    acceptance): the SAME e2e system with ``telemetry.quality_enabled``
    on vs off, in one artifact. Budget under test: the quality plane's
    in-band costs — the per-block calibration tap inside
    ``LocalBuffer.finish`` (run at sample_every=1, bounding the
    production cadence from above), the QualityStats aggregation, and
    the per-record ``quality`` block + ``quality_player{p}.jsonl``
    ledger row assembly — cost <= 2%% on BOTH env-steps/s and learner
    updates/s. Cells run ABBA-interleaved ``repeats`` times with
    per-arm medians (the tracing-AB noise treatment) in THREAD mode so
    the calibration tap actually rides the acting hot path. The ON
    cells carry the ``quality`` block as end-to-end evidence; the OFF
    cells prove the records carried no ``quality`` key at all (the
    kill-switch schema contract).

    A final evidence cell runs the full gated-canary promotion drill
    (tools/chaos.py ``--promotion``): corrupted candidate refused with
    ``canary_divergence`` fired exactly once, healthy candidate
    promoted fleet-wide via ONE root publish, bit-identical rollback."""
    cells = {"quality_off": [], "quality_on": []}
    for rep in range(max(repeats, 1)):
        order = (("quality_off", False), ("quality_on", True))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, on in order:
            ov = dict(overrides or {})
            ov["telemetry.quality_enabled"] = on
            # every finished block feeds the calibration join — denser
            # than any production cadence, so the measured overhead
            # bounds the per-emission cost from above
            ov.setdefault("telemetry.quality_calib_sample_every", 1)
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov,
                                        actor_mode="thread"))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"quality_off": cells["quality_off"][-1],
           "quality_on": cells["quality_on"][-1],
           "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("quality_off", "env_steps_per_sec") > 0:
        ratio = (med("quality_on", "env_steps_per_sec")
                 / med("quality_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("quality_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("quality_on", "learner_steps_per_sec")
            / med("quality_off", "learner_steps_per_sec"), 3)
    # evidence: merge the ON cells' quality blocks (sub-blocks carry
    # their own cumulative totals, newest-non-null — the run_e2e merge
    # semantics again)
    qb = {}
    for c in cells["quality_on"]:
        for k, v in (c.get("quality") or {}).items():
            if v is not None:
                qb[k] = v
    out["quality_block_on"] = bool(qb)
    out["calibration_samples_on"] = (
        (qb.get("calibration") or {}).get("samples_total"))
    out["promotion_state_on"] = (qb.get("promotion") or {}).get("state")
    out["quality_block_off"] = any(
        c.get("quality") for c in cells["quality_off"])
    # the promotion-drill evidence cell: real servers, real mirrors,
    # real fan-out — the acceptance's refuse/promote/rollback proof
    from r2d2_tpu.tools.chaos import run_promotion_drill
    drill = run_promotion_drill(max(seconds, 60.0))
    out["promotion_drill"] = {
        "passed": all(drill["verdict"].values()),
        "verdict": drill["verdict"],
        "corrupt_divergence": drill.get("corrupt_divergence"),
        "healthy_divergence": drill.get("healthy_divergence"),
        "promoted_stamp": drill.get("promoted_stamp"),
        "rolled_back_to_stamp": drill.get("rolled_back_to_stamp"),
        "alerts_fired": drill.get("alerts_fired"),
    }
    return out


def run_replay_diag_ab(seconds: float, envs_per_actor: int, num_actors: int,
                       overrides: Optional[dict] = None,
                       repeats: int = 2, sharded_dp: int = 2) -> dict:
    """Replay-diagnostics overhead A/B (ISSUE 10 acceptance): the SAME
    e2e host-actor system with ``telemetry.replay_diag_enabled`` on vs
    off, in one artifact. Budget under test: the fused pillar — the
    per-step sample-count scatter + lane bincount, the interval-gated
    sum-tree snapshot, and eviction accounting inside replay_add_many —
    costs < 2% on BOTH env-steps/s and learner updates/s. Cells run
    INTERLEAVED off/on ``repeats`` times with per-arm medians (the
    learning/resources-AB noise treatment; single cells swing ±10% on
    the 2-core host).

    A final evidence cell runs the SHARDED (emulated dp=``sharded_dp``)
    anakin loop with the pillar on — the acceptance's second path — and
    records its ``replay_diag`` block with per-shard + merged sum-tree
    views. Requires >= sharded_dp visible devices (main forces the CPU
    host-device count when it owns the process)."""
    cells = {"replay_diag_off": [], "replay_diag_on": []}
    for _ in range(max(repeats, 1)):
        for label, on in (("replay_diag_off", False),
                          ("replay_diag_on", True)):
            ov = dict(overrides or {})
            ov["telemetry.replay_diag_enabled"] = on
            # the snapshot must FIRE inside the short window for the
            # evidence fields; interval=20 is ~4x the production cadence
            # relative to step rate on this shape, bounding overhead
            # from above
            ov.setdefault("telemetry.replay_diag_interval", 20)
            cells[label].append(run_e2e(seconds, envs_per_actor,
                                        num_actors, overrides=ov))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"replay_diag_off": cells["replay_diag_off"][-1],
           "replay_diag_on": cells["replay_diag_on"][-1],
           "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("replay_diag_off", "env_steps_per_sec") > 0:
        ratio = (med("replay_diag_on", "env_steps_per_sec")
                 / med("replay_diag_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("replay_diag_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("replay_diag_on", "learner_steps_per_sec")
            / med("replay_diag_off", "learner_steps_per_sec"), 3)
    # evidence: newest ON cell carrying each sub-block (host-actor path)
    rd = {}
    for c in cells["replay_diag_on"]:
        rd.update({k: v for k, v in (c.get("replay_diag") or {}).items()
                   if v is not None})
    out["replay_diag_block_on"] = bool(rd)
    out["tree_on"] = rd.get("tree")
    out["evictions_on"] = rd.get("evictions")
    out["lanes_on"] = rd.get("lanes")
    out["replay_diag_block_off"] = any(
        c.get("replay_diag") for c in cells["replay_diag_off"])

    # the sharded-anakin evidence cell: per-shard + merged tree views on
    # the emulated dp mesh (the acceptance's second path)
    import jax
    if len(jax.devices()) >= sharded_dp:
        ov = dict(ANAKIN_AB_OVERRIDES)
        ov.update(overrides or {})
        ov.update({"actor.on_device": True, "actor.anakin_lanes": 64,
                   "mesh.dp": sharded_dp,
                   "telemetry.replay_diag_enabled": True,
                   "telemetry.replay_diag_interval": 5})
        cell = run_e2e(seconds, overrides=ov)
        out["sharded_anakin_on"] = cell
        srd = cell.get("replay_diag") or {}
        out["sharded_tree_on"] = srd.get("tree")
        out["sharded_shards_on"] = srd.get("shards")
    return out


def run_serve_ab(seconds: float, lanes: int = 16,
                 overrides: Optional[dict] = None,
                 repeats: int = 2, sweep: Tuple[int, ...] = (1, 4, 16)
                 ) -> dict:
    """Serving overhead + batching-under-load evidence (ISSUE 13
    acceptance): the SAME thread-mode e2e system — one vector actor
    worker whose lanes each hold a serve client — with
    ``actor.inference`` local vs server at equal lanes, ABBA-interleaved
    ``repeats`` times with per-arm medians (the fleet-AB noise
    treatment), PLUS a client-count sweep (server mode at 1/4/16 lanes)
    recording the batch-fill climb with load.

    The claims under test on this CPU container: server-mode aggregate
    env-steps/s stays within 0.8x of local at 16 clients (the mechanism
    is not pathological — the WIN is placement on real accelerators,
    where the batched forward leaves the actor host entirely), mean
    batch fill > 1 from 4 clients up, and P99 request latency bounded by
    the deadline + one forward. Thread mode keeps the in-proc rung under
    test (client-observed latency in the serving block); the process
    rungs (shm/socket) are round-trip-tested in tests/test_serve.py."""
    base = dict(overrides or {})
    cells = {"local": [], "server": []}
    for rep in range(max(repeats, 1)):
        order = (("local", "local"), ("server", "server"))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, mode in order:
            ov = dict(base)
            ov["actor.inference"] = mode
            cells[label].append(run_e2e(
                seconds, envs_per_actor=lanes, num_actors=1,
                overrides=ov, actor_mode="thread"))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"local": cells["local"][-1], "server": cells["server"][-1],
           "lanes": lanes, "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("local", "env_steps_per_sec") > 0:
        out["env_steps_ratio_serve"] = round(
            med("server", "env_steps_per_sec")
            / med("local", "env_steps_per_sec"), 3)
    if med("local", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio_serve"] = round(
            med("server", "learner_steps_per_sec")
            / med("local", "learner_steps_per_sec"), 3)
    sb = next((c["serving"] for c in reversed(cells["server"])
               if c.get("serving")), None)
    out["serving_block_on"] = bool(sb)
    if sb:
        out["serve_latency_p99_ms"] = (sb.get("latency") or {}).get(
            "p99_ms")
        out["serve_fill_mean"] = (sb.get("batch") or {}).get("fill_mean")
    out["serving_block_local"] = any(c.get("serving")
                                     for c in cells["local"])

    # client-count sweep: batch fill climbing with load is the
    # micro-batcher's central claim — each lane is one blocking client,
    # so fill tracks the number of concurrently-pending requests. The
    # probe isolates the SERVING plane (no colocated learner): on this
    # 2-core host the integrated arms' tail latency is GIL/scheduler
    # contention with the training loop, which would mis-measure the
    # batcher itself; the SLO leg (p99 <= deadline + one forward) is
    # checked per cell against the same run's forward percentiles.
    out["client_sweep"] = [
        serve_latency_probe(min(seconds, 15.0), n, overrides=base)
        for n in sweep]
    fills = [c["fill_mean"] for c in out["client_sweep"]
             if c["fill_mean"] is not None]
    if fills:
        out["serve_fill_mean_sweep_max"] = max(fills)
    out["serve_slo_ok_sweep"] = all(
        c.get("slo_ok") for c in out["client_sweep"])
    return out


def quant_weight_bytes_table(overrides: Optional[dict] = None) -> dict:
    """Analytic weight-streaming table (ISSUE 14 acceptance): bytes of
    the acting forward's weight tree per inference dtype at the
    REFERENCE network shape (hidden 512 / cnn 1024 / Nature convs —
    what the TPU projection is about), plus this bench's reduced shape
    for context. Pure eval_shape math, no compile; the int8 ratio is
    the >= 3x cut the costmodel gate also snapshots exactly."""
    import jax

    from r2d2_tpu.config import Config, NetworkConfig
    from r2d2_tpu.models.network import (NetworkApply, param_tree_bytes,
                                         quantize_params)

    def row(ncfg, stack, h, w):
        net = NetworkApply(6, ncfg, stack, h, w)
        params = jax.eval_shape(net.init, jax.random.PRNGKey(0))
        out = {}
        for mode in ("f32", "bf16", "int8"):
            tree = (params if mode == "f32" else jax.eval_shape(
                lambda p, _m=mode: quantize_params(p, _m), params))
            out[f"weight_bytes_{mode}"] = param_tree_bytes(tree)
        for mode in ("bf16", "int8"):
            out[f"weight_bytes_ratio_{mode}"] = round(
                out["weight_bytes_f32"] / out[f"weight_bytes_{mode}"], 3)
        return out

    ref = Config()
    bench = _bench_config(dict(E2E_CPU_OVERRIDES, **(overrides or {})))
    return {
        "reference_shape": row(
            NetworkConfig(), ref.env.frame_stack, ref.env.frame_height,
            ref.env.frame_width),
        "bench_shape": row(
            bench.network, bench.env.frame_stack, bench.env.frame_height,
            bench.env.frame_width),
    }


def run_quant_ab(seconds: float, lanes: int = 16,
                 overrides: Optional[dict] = None,
                 repeats: int = 2) -> dict:
    """Quantized-inference A/B (ISSUE 14 acceptance), three arms in one
    artifact:

      * **acting arm** — the SAME thread-mode e2e system (one vector
        actor worker + the real learner) at ``network.inference_dtype``
        f32 vs int8, ABBA-interleaved ``repeats`` times with per-arm
        medians (the serve/fleet-AB noise treatment); the int8 cells
        carry the ``quant`` block (probes, agreement, |ΔQ|) as
        end-to-end evidence and f32 cells prove the records carry no
        ``quant`` key (the kill-switch schema contract);
      * **serving-probe arm** — the pure serving-plane latency probe
        (no colocated learner) at f32 vs int8: requests/s, batch fill,
        forward percentiles, the SLO leg;
      * **weight-bytes table** — the analytic streaming cut per dtype
        at the reference shape (the >= 3x int8 acceptance line, also
        exact-match-gated through the costmodel table).

    CPU-gate framing (PERF.md round 17): the acting ratio measures the
    weight-streaming mechanism one memory tier down — the bench-shape
    f32 tree spills this host's per-core cache while the int8 twin
    stays resident (measured 1.19x) — and the weight-bytes table is
    what projects to TPU, where the acting forward is
    HBM-streaming-bound (the costmodel bytes tables)."""
    base = dict(overrides or {})
    base.setdefault("telemetry.quant_probe_interval", 64)
    cells = {"acting_f32": [], "acting_int8": []}
    for rep in range(max(repeats, 1)):
        order = (("acting_f32", "f32"), ("acting_int8", "int8"))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, mode in order:
            ov = dict(base)
            ov["network.inference_dtype"] = mode
            cells[label].append(run_e2e(
                seconds, envs_per_actor=lanes, num_actors=1,
                overrides=ov, actor_mode="thread"))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"acting_f32": cells["acting_f32"][-1],
           "acting_int8": cells["acting_int8"][-1],
           "lanes": lanes, "repeats": max(repeats, 1),
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("acting_f32", "env_steps_per_sec") > 0:
        out["env_steps_ratio_quant"] = round(
            med("acting_int8", "env_steps_per_sec")
            / med("acting_f32", "env_steps_per_sec"), 3)
    if med("acting_f32", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio_quant"] = round(
            med("acting_int8", "learner_steps_per_sec")
            / med("acting_f32", "learner_steps_per_sec"), 3)
    qb = {}
    for c in cells["acting_int8"]:
        qb.update({k: v for k, v in (c.get("quant") or {}).items()
                   if v is not None})
    out["quant_block_on"] = bool(qb)
    out["quant_agree_frac"] = qb.get("agree_frac")
    out["quant_dq_max"] = qb.get("dq_max")
    out["quant_probes"] = qb.get("probes")
    out["quant_block_f32"] = any(c.get("quant")
                                 for c in cells["acting_f32"])

    # serving-probe arm: the micro-batcher itself at each dtype — the
    # serving plane is the second consumer the ISSUE names, and the
    # probe isolates it from the training loop's core contention
    out["serve_probe"] = {}
    for mode in ("f32", "int8"):
        ov = dict(base)
        ov["network.inference_dtype"] = mode
        out["serve_probe"][mode] = serve_latency_probe(
            min(seconds, 15.0), lanes, overrides=ov)
    f32_rps = out["serve_probe"]["f32"].get("requests_per_sec") or 0
    if f32_rps > 0:
        out["serve_requests_ratio_quant"] = round(
            (out["serve_probe"]["int8"].get("requests_per_sec") or 0)
            / f32_rps, 3)
    out["serve_slo_ok_quant"] = bool(
        out["serve_probe"]["int8"].get("slo_ok"))

    out["weight_bytes"] = quant_weight_bytes_table(overrides)
    return out


def run_elastic_ab(seconds: float, overrides: Optional[dict] = None,
                   repeats: int = 2, num_actors: int = 4,
                   lanes_per_actor: int = 4) -> dict:
    """Elastic-fleet A/B (ISSUE 15 acceptance), two arm pairs in one
    artifact:

      * **churn arm** — the SAME thread-mode e2e system (num_actors
        vector workers + the real learner) fixed vs CHURNED at equal
        lanes: the churned cells run ``fleet.elastic`` with a
        grammar-injected ``leave@block`` on 25%% of the fleet and a
        ``join@t`` re-adoption mid-run (the supervisor admits the
        joiner; the slot's lane range/ε slice are adopted). ABBA-
        interleaved ``repeats`` times with per-arm medians; churned
        cells carry the ``replay_service`` membership block (joins/
        leaves) as end-to-end evidence. The claim: churn costs bounded
        throughput (the departed slot's share for the gap), and the
        learner NEVER stalls — training_speed stays nonzero in every
        churned record after warm-up.
      * **spill arm** — the service-routed learner
        (``fleet.replay_shards=2``) with the host-RAM spill tier off vs
        on (spill sized to 1x the device rings → 2x total capacity):
        learner updates/s ratio ON/OFF bounds the spill tier's cost on
        the training path, and the ON cell's spill occupancy/hit-rate
        prove pages actually demote and re-promote."""
    base = dict(overrides or {})
    lanes = num_actors * lanes_per_actor
    n_leave = max(1, int(num_actors * 0.25))
    join_at = max(seconds * 0.55, 10.0)
    spec_parts = []
    for s in range(n_leave):
        spec_parts.append(f"{s}:leave@block={30 + 5 * s}")
        spec_parts.append(f"{s}:join@t={join_at + 2.0 * s:.1f}")
    churn_ov = {
        "fleet.elastic": True,
        "actor.fault_spec": ";".join(spec_parts),
        "runtime.supervise_interval_s": 1.0,
    }
    cells = {"fixed": [], "churned": []}
    for rep in range(max(repeats, 1)):
        order = (("fixed", {}), ("churned", churn_ov))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, extra in order:
            ov = dict(base)
            ov.update(extra)
            cells[label].append(run_e2e(
                seconds, envs_per_actor=lanes_per_actor,
                num_actors=num_actors, overrides=ov, actor_mode="thread"))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"fixed": cells["fixed"][-1], "churned": cells["churned"][-1],
           "lanes": lanes, "repeats": max(repeats, 1),
           "left_and_rejoined": n_leave,
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("fixed", "env_steps_per_sec") > 0:
        out["env_steps_ratio_churn"] = round(
            med("churned", "env_steps_per_sec")
            / med("fixed", "env_steps_per_sec"), 3)
    if med("fixed", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio_churn"] = round(
            med("churned", "learner_steps_per_sec")
            / med("fixed", "learner_steps_per_sec"), 3)
    mb = {}
    for c in cells["churned"]:
        mb.update(((c.get("replay_service") or {}).get("membership")
                   or {}))
    out["membership_block_on"] = bool(mb)
    out["churn_joins"] = mb.get("joins")
    out["churn_leaves"] = mb.get("leaves")
    out["membership_block_fixed"] = any(c.get("replay_service")
                                        for c in cells["fixed"])

    # spill arm: the service-routed learner with the spill tier off/on.
    # Device rings shrink so the ring cycles within the bench window
    # (demotions need overwrites); spill ON sizes the tier to the whole
    # device budget — 2x effective capacity, the acceptance geometry.
    svc_base = dict(base)
    svc_base.update({
        "fleet.replay_shards": 2,
        "replay.capacity": 8_000,          # 100 blocks -> 50/shard
        "replay.learning_starts": 400,
    })
    spill_cells = {}
    for label, spill in (("spill_off", 0), ("spill_on", 50)):
        ov = dict(svc_base)
        ov["fleet.spill_blocks"] = spill
        spill_cells[label] = run_e2e(
            min(seconds, 30.0), envs_per_actor=lanes_per_actor,
            num_actors=num_actors, overrides=ov, actor_mode="thread")
    out["spill_off"] = spill_cells["spill_off"]
    out["spill_on"] = spill_cells["spill_on"]
    if spill_cells["spill_off"]["learner_steps_per_sec"] > 0:
        out["learner_steps_ratio_spill"] = round(
            spill_cells["spill_on"]["learner_steps_per_sec"]
            / spill_cells["spill_off"]["learner_steps_per_sec"], 3)
    sp = ((spill_cells["spill_on"].get("replay_service") or {})
          .get("spill") or {})
    out["spill_occupancy"] = sp.get("occupancy")
    out["spill_hit_rate"] = sp.get("hit_rate")
    out["spill_capacity"] = sp.get("capacity")
    return out


def _synth_service_blocks(spec, n: int, seed: int = 0) -> list:
    """Synthetic filled block records at ``spec``'s exact layout (the
    socket/spill cells need wire-shaped payloads, not real episodes):
    positive priorities so the sampled tree is well-formed, stamped
    learning steps so the accountant advances."""
    from r2d2_tpu.replay.structs import Block, empty_block_np
    rng = np.random.default_rng(seed)
    proto = empty_block_np(spec)
    blocks = []
    for i in range(n):
        fields = {k: v.copy() for k, v in proto.items()}
        fields["priority"] = np.abs(rng.normal(
            1.0, 0.5, spec.seqs_per_block)).astype(np.float32) + 1e-3
        fields["learning_steps"] = np.full(
            (spec.seqs_per_block,), spec.learning, np.int32)
        fields["num_sequences"] = np.asarray(spec.seqs_per_block, np.int32)
        fields["weight_version"] = np.asarray(i, np.int32)
        blocks.append(Block(**fields))
    return blocks


def run_service_ingest_ab(seconds: float, overrides: Optional[dict] = None,
                          repeats: int = 3, num_actors: int = 4,
                          lanes_per_actor: int = 4,
                          ingest_blocks: int = 8,
                          socket_window: int = 4) -> dict:
    """Batched/pipelined service data-plane A/B (ISSUE 16 acceptance),
    three cells in one artifact:

      * **socket rung** — an in-proc ReplayService behind its TCP
        server, one remote producer pushing a fixed synthetic block
        budget: per-block lockstep frames (PR 15's rung) vs stacked
        windowed frames (one ``addw`` frame per group of
        ``ingest_blocks``, ``socket_window`` unacked frames in flight)
        into a grouped-ingest service. ABBA-interleaved ``repeats``
        with per-arm medians; ``socket_speedup`` is the >= 1.3x
        headline (frame count and ack round-trips both drop ~Kx).
      * **e2e arms** — the SAME service-routed thread-mode system
        (``fleet.replay_shards=2``) at ``fleet.ingest_batch_blocks``
        1 vs ``ingest_blocks``: ``learner_steps_ratio_ingest`` bounds
        the grouped commit plane's cost on the training path (>= 0.98
        acceptance).
      * **spill prefetch** — a populated spill tier sampled under
        inline promotion vs the async priority-ordered prefetch
        (``fleet.spill_prefetch``): median sample-path latency per arm;
        ``prefetch_sample_speedup`` >= 1 means moving promotion off the
        sample path never cost latency."""
    from r2d2_tpu.fleet.replay_service import (RemoteReplayProducer,
                                               ReplayService,
                                               ReplayServiceServer)
    from r2d2_tpu.replay.structs import ReplaySpec

    base = dict(overrides or {})
    out: dict = {}

    # -- socket-rung producer cell ---------------------------------------
    spec = ReplaySpec(
        num_blocks=64, seqs_per_block=4, block_length=20, burn_in=4,
        learning=5, forward=3, frame_stack=2, frame_height=12,
        frame_width=12, hidden_dim=16, batch_size=16, prio_exponent=0.9,
        is_exponent=0.6, replay_diag=False)
    n_blocks = 192
    blocks = _synth_service_blocks(spec, n_blocks)
    cells = {"per_block": [], "batched": []}

    def socket_arm(batched: bool) -> float:
        svc = ReplayService(spec, 2, ingest_batch_blocks=(
            ingest_blocks if batched else 1))
        server = ReplayServiceServer(svc)
        producer = RemoteReplayProducer(
            server.host, server.port,
            window=(socket_window if batched else 1))
        try:
            t0 = time.perf_counter()
            if batched:
                for i in range(0, n_blocks, ingest_blocks):
                    producer.add_blocks(blocks[i:i + ingest_blocks])
                producer.flush()
            else:
                for blk in blocks:
                    producer.add_block(blk)
            dt = time.perf_counter() - t0
            assert server.blocks_received == n_blocks
            return n_blocks / dt
        finally:
            producer.close()
            server.close()

    # One untimed pass per arm first: the grouped arm's first run pays the
    # replay_add_many AOT chunk compiles and the per-block arm pays the
    # replay_add jit — neither belongs in a timed cell.
    socket_arm(False)
    socket_arm(True)
    for rep in range(max(repeats, 1)):
        order = (("per_block", False), ("batched", True))
        if rep % 2:
            order = order[::-1]    # ABBA: cancel monotonic host drift
        for label, batched in order:
            cells[label].append(socket_arm(batched))
    med_off = float(np.median(cells["per_block"]))
    med_on = float(np.median(cells["batched"]))
    out["socket_rung"] = {
        "blocks": n_blocks, "group": ingest_blocks,
        "window": socket_window, "repeats": max(repeats, 1),
        "per_block_blocks_per_sec_cells": [round(v, 1)
                                           for v in cells["per_block"]],
        "batched_blocks_per_sec_cells": [round(v, 1)
                                         for v in cells["batched"]],
        "per_block_blocks_per_sec": round(med_off, 1),
        "batched_blocks_per_sec": round(med_on, 1),
    }
    if med_off > 0:
        out["socket_speedup"] = round(med_on / med_off, 3)

    # -- e2e arms: grouped commit plane on the real learner path ---------
    svc_base = dict(base)
    svc_base.update({
        "fleet.replay_shards": 2,
        "replay.capacity": 8_000,          # 100 blocks -> 50/shard
        "replay.learning_starts": 400,
    })
    e2e_cells = {"ingest_off": [], "ingest_on": []}
    for rep in range(max(repeats - 1, 1)):
        order = (("ingest_off", 1), ("ingest_on", ingest_blocks))
        if rep % 2:
            order = order[::-1]
        for label, k in order:
            ov = dict(svc_base)
            ov["fleet.ingest_batch_blocks"] = k
            e2e_cells[label].append(run_e2e(
                min(seconds, 30.0), envs_per_actor=lanes_per_actor,
                num_actors=num_actors, overrides=ov, actor_mode="thread"))
    out["ingest_off"] = e2e_cells["ingest_off"][-1]
    out["ingest_on"] = e2e_cells["ingest_on"][-1]
    out["learner_steps_per_sec_cells"] = {
        k: [c["learner_steps_per_sec"] for c in v]
        for k, v in e2e_cells.items()}

    def med(label):
        return float(np.median(
            [c["learner_steps_per_sec"] for c in e2e_cells[label]]))

    if med("ingest_off") > 0:
        out["learner_steps_ratio_ingest"] = round(
            med("ingest_on") / med("ingest_off"), 3)
    ingest_tel = (out["ingest_on"].get("replay_service") or {}).get(
        "ingest") or {}
    out["ingest_blocks_per_dispatch"] = ingest_tel.get("blocks_per_dispatch")

    # -- spill prefetch: sample-path latency, inline vs async ------------
    def prefetch_arm(prefetch: bool) -> float:
        svc = ReplayService(spec, 1, spill_blocks=64, promote_per_sample=1,
                            spill_prefetch=prefetch)
        try:
            for blk in _synth_service_blocks(spec, 128, seed=7):
                svc.add_block(blk)       # 64 demoted into the tier
            import jax
            key = jax.random.PRNGKey(0)
            lat = []
            for _ in range(40):
                key, sub = jax.random.split(key)
                t0 = time.perf_counter()
                batch, shard, _snap = svc.sample(sub)
                jax.block_until_ready(batch.obs)
                lat.append(time.perf_counter() - t0)
                svc.update_priorities(
                    shard, batch.idxes,
                    np.ones(spec.batch_size, np.float32))
            svc.drain_prefetch()
            return float(np.median(lat))
        finally:
            svc.close()

    inline_s = prefetch_arm(False)
    prefetch_s = prefetch_arm(True)
    out["spill_prefetch"] = {
        "inline_sample_ms": round(inline_s * 1e3, 3),
        "prefetch_sample_ms": round(prefetch_s * 1e3, 3),
    }
    if prefetch_s > 0:
        out["prefetch_sample_speedup"] = round(inline_s / prefetch_s, 3)
    return out


def serve_latency_probe(seconds: float, clients: int,
                        overrides: Optional[dict] = None) -> dict:
    """Pure serving-plane cell: one in-proc PolicyServer, ``clients``
    pipelined lanes stepping synthetic frames as fast as replies come
    back. Measures the micro-batcher itself — batch fill, client-visible
    latency percentiles, forward time — without a training loop
    competing for the cores. ``slo_ok`` is the acceptance leg: latency
    p99 <= serve.deadline_ms + the same run's forward p99."""
    import jax

    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.serve import (InprocEndpoint, PolicyServer,
                                RemoteBatchedPolicy, ServingStats)
    from r2d2_tpu.telemetry import Telemetry
    ov = dict(E2E_CPU_OVERRIDES)
    ov.update(overrides or {})
    ov.pop("actor.inference", None)
    cfg = _bench_config(ov)
    net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    params = net.init(jax.random.PRNGKey(0))
    stats = ServingStats()
    telemetry = Telemetry(name="serve-probe")
    ep = InprocEndpoint()
    srv = PolicyServer(cfg, net, params, endpoint=ep, stats=stats,
                       telemetry=telemetry, client_timed=True).start()
    try:
        remote = RemoteBatchedPolicy(
            ep.connect(), net.action_dim, [0.05] * clients,
            list(range(clients)), stats=stats,
            timeout_s=cfg.serve.request_timeout_s)
        rng = np.random.default_rng(0)
        h, w = cfg.env.frame_height, cfg.env.frame_width
        frames = rng.integers(0, 255, (64, h, w), np.uint8)
        for i in range(clients):
            remote.observe_reset_lane(i, frames[i % 64])
        for _ in range(3):                       # warm the round trip
            remote.act()
        stats.interval_block()                   # drop warm-up samples
        telemetry.timers.take()
        ticks = 0
        t0 = time.time()
        while time.time() - t0 < seconds:
            actions, _, _ = remote.act()
            remote.observe(frames[(ticks + np.arange(clients)) % 64],
                           actions)
            ticks += 1
        elapsed = time.time() - t0
        block = stats.interval_block() or {}
        from r2d2_tpu.telemetry.core import summarize_matrix
        stages = summarize_matrix(telemetry.timers.take())
        fwd = stages.get("serve/forward") or {}
        lat = block.get("latency") or {}
        cell = {
            "clients": clients,
            "seconds": round(elapsed, 1),
            "ticks": ticks,
            "requests_per_sec": round(ticks * clients / elapsed, 1),
            "fill_mean": (block.get("batch") or {}).get("fill_mean"),
            "fill_p99": (block.get("batch") or {}).get("fill_p99"),
            "latency_p50_ms": lat.get("p50_ms"),
            "latency_p99_ms": lat.get("p99_ms"),
            "forward_p50_ms": fwd.get("p50_ms"),
            "forward_p99_ms": fwd.get("p99_ms"),
            "deadline_ms": cfg.serve.deadline_ms,
        }
        if lat.get("p99_ms") is not None and fwd.get("p99_ms") is not None:
            cell["slo_ok"] = bool(
                lat["p99_ms"] <= cfg.serve.deadline_ms + fwd["p99_ms"])
        return cell
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# Sharded serving fleet A/B (ISSUE 17): scaling curve + brownout anatomy.
#
# This container exposes ONE core, so N REAL server forwards cannot
# overlap — a real-compute scaling cell would measure GIL arbitration,
# not the fleet. The scaling cells therefore run TIMED-FORWARD
# EMULATION: the real jitted forward is calibrated once per dispatch
# bucket (median of repeated runs), then each emulated server's forward
# is a GIL-releasing sleep of the calibrated time returning zeros. What
# stays REAL: the whole serving plane around the forward — routing,
# micro-batching, cache leases, admission, reply paths. Parity/failover
# correctness runs with REAL forwards in tests/test_serve.py.


def _calibrate_forward_table(cfg, net, params, buckets,
                             repeats: int = 5) -> dict:
    """Median real single-forward latency per pow2 dispatch bucket —
    the timed-forward emulation's lookup table (seconds per bucket)."""
    from r2d2_tpu.actor.policy import make_forward_fn
    fwd = make_forward_fn(net)
    h, w, s = net.obs_hw
    hd = net.state_half
    table = {}
    for b in sorted(set(int(x) for x in buckets)):
        args = (params, np.zeros((b, h, w, s), np.float32),
                np.zeros(b, np.int32), np.zeros((b, 2, hd), np.float32))
        np.asarray(fwd(*args)[0])            # compile outside the timing
        ts = []
        for _ in range(max(repeats, 1)):
            t0 = time.perf_counter()
            np.asarray(fwd(*args)[0])
            ts.append(time.perf_counter() - t0)
        table[b] = float(np.median(ts))
    return table


def serve_fleet_probe(seconds: float, servers: int, clients: int,
                      overrides: Optional[dict] = None,
                      forward_table: Optional[dict] = None,
                      max_batch: Optional[int] = None,
                      queue_depth_bound: int = 0) -> dict:
    """One serving-fleet cell: ``servers`` in-proc server loops behind
    the client-side router, ``clients`` pipelined lanes stepping
    synthetic frames as fast as replies come back. ``state_shards`` is
    set to the client count so contiguous client ids spread EVENLY over
    the servers (each lane its own shard group); per-server
    ``max_batch`` defaults to the per-server lane share so a full
    micro-batch dispatches without waiting out the deadline. With
    ``forward_table`` the forward is the calibrated sleep stand-in (see
    the section comment); without it the real forward runs (parity-true
    but meaningless for N>1 scaling on one core).

    The scaling cells pass an EXPLICIT ``max_batch`` = clients /
    max-fleet-width so every arm forwards the same batch shape and the
    arms differ only in how many of those equal batches run at once:
    the single server drains the client tick as max-width sequential
    dispatches, four servers overlap them exactly as N accelerator
    hosts would. (Letting each arm batch its full per-server share
    instead would fold the CPU calibration's strong batch sublinearity
    — a host artifact; accelerators at serving batch sizes are
    latency-bound — into the fleet curve.)"""
    import jax

    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.serve import (RemoteBatchedPolicy, ServerFleet,
                                ServingStats)
    shards = max(clients, servers)
    mb = max_batch if max_batch is not None else max(
        1, clients // servers)
    ov = dict(overrides or {})
    ov.pop("actor.inference", None)
    ov.update({
        "serve.servers": servers, "serve.max_servers": servers,
        "serve.state_shards": shards, "serve.state_slots": 64 * shards,
        "serve.max_batch": mb,
        "serve.queue_depth_bound": queue_depth_bound,
    })
    cfg = _bench_config(ov)
    net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    params = net.init(jax.random.PRNGKey(0))
    hd = net.state_half
    fff = None
    if forward_table is not None:
        biggest = max(forward_table)

        def fff(slot, _t=forward_table, _big=biggest):
            def fwd(params, stacked, last_action, hidden):
                b = int(stacked.shape[0])
                time.sleep(_t.get(b, _t[_big]))
                return (np.zeros(b, np.int64),
                        np.zeros((b, 6), np.float32),
                        np.zeros((b, 2, hd), np.float32))
            return fwd
    stats = ServingStats()
    fleet = ServerFleet(cfg, net, params, stats=stats, client_timed=True,
                        forward_fn_factory=fff)
    try:
        remote = RemoteBatchedPolicy(
            fleet.connect(), net.action_dim, [0.05] * clients,
            list(range(clients)), stats=stats,
            timeout_s=cfg.serve.request_timeout_s)
        rng = np.random.default_rng(0)
        h, w = cfg.env.frame_height, cfg.env.frame_width
        frames = rng.integers(0, 255, (64, h, w), np.uint8)
        for i in range(clients):
            remote.observe_reset_lane(i, frames[i % 64])
        for _ in range(3):                       # warm the round trip
            remote.act()
        fleet.interval_block()                   # drop warm-up samples
        ticks = 0
        t0 = time.time()
        while time.time() - t0 < seconds:
            actions, _, _ = remote.act()
            remote.observe(frames[(ticks + np.arange(clients)) % 64],
                           actions)
            ticks += 1
        elapsed = time.time() - t0
        block = fleet.interval_block() or {}
        lat = block.get("latency") or {}
        adm = block.get("admission") or {}
        alat = adm.get("admitted_latency") or {}
        cell = {
            "servers": servers,
            "clients": clients,
            "max_batch": mb,
            "queue_depth_bound": queue_depth_bound,
            "emulated_forward": forward_table is not None,
            "seconds": round(elapsed, 1),
            "ticks": ticks,
            # logical client steps/s — shed retries do NOT count, so
            # this is goodput, the number the scaling gate reads
            "requests_per_sec": round(ticks * clients / elapsed, 1),
            "fill_mean": (block.get("batch") or {}).get("fill_mean"),
            "latency_p50_ms": lat.get("p50_ms"),
            "latency_p99_ms": lat.get("p99_ms"),
            "admitted_p50_ms": alat.get("p50_ms"),
            "admitted_p99_ms": alat.get("p99_ms"),
            "shed": adm.get("shed", 0),
            "shed_frac": adm.get("shed_frac", 0.0),
            "client_shed_retries": remote.shed_retries,
            "server_rows": len((block.get("servers") or {})
                               .get("rows") or {}),
        }
        return cell
    finally:
        fleet.stop()


def socket_rt_probe(seconds: float,
                    overrides: Optional[dict] = None) -> dict:
    """Socket-transport round-trip re-quote (TCP_NODELAY satellite):
    one real-forward server behind the TCP loopback transport, ONE
    blocking client — the per-request wire latency with Nagle disabled
    on both sides, comparable against PERF.md's earlier socket quotes."""
    import jax

    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.serve import (InprocEndpoint, PolicyServer, RemotePolicy,
                                ServingStats, SocketChannel,
                                SocketServerTransport)
    ov = dict(E2E_CPU_OVERRIDES)
    ov.update(overrides or {})
    ov.pop("actor.inference", None)
    cfg = _bench_config(ov)
    net = NetworkApply(6, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    params = net.init(jax.random.PRNGKey(0))
    stats = ServingStats()
    ep = InprocEndpoint()
    srv = PolicyServer(cfg, net, params, endpoint=ep, stats=stats,
                       client_timed=True).start()
    transport = SocketServerTransport(ep.submit, cfg.serve.host, 0)
    try:
        remote = RemotePolicy(
            SocketChannel(transport.host, transport.port),
            net.action_dim, 0.05, stats=stats,
            timeout_s=cfg.serve.request_timeout_s)
        rng = np.random.default_rng(0)
        h, w = cfg.env.frame_height, cfg.env.frame_width
        frame = rng.integers(0, 255, (h, w), np.uint8)
        remote.observe_reset(frame)
        for _ in range(5):
            remote.act()
        lats = []
        t0 = time.time()
        while time.time() - t0 < seconds:
            t1 = time.perf_counter()
            action, _, _ = remote.act()
            lats.append(time.perf_counter() - t1)
            remote.observe(frame, action)
        arr = np.asarray(lats) * 1e3
        return {
            "round_trips": len(lats),
            "rt_p50_ms": round(float(np.percentile(arr, 50)), 3),
            "rt_p95_ms": round(float(np.percentile(arr, 95)), 3),
            "rt_p99_ms": round(float(np.percentile(arr, 99)), 3),
            "tcp_nodelay": True,
        }
    finally:
        transport.close()
        srv.stop()


def run_serve_fleet_ab(seconds: float, overrides: Optional[dict] = None,
                       repeats: int = 2,
                       servers_sweep: Tuple[int, ...] = (1, 2, 4),
                       clients_sweep: Tuple[int, ...] = (8, 16)) -> dict:
    """Serving-fleet scaling A/B (ISSUE 17 acceptance), one artifact:

      * **scaling curve** — requests/s at 1/2/4 emulated server loops x
        client widths, ABBA-interleaved ``repeats`` times with per-arm
        medians; the gate is 4 servers >= 2.5x single-server goodput at
        the widest EQUAL client count. ``max_batch`` is pinned to
        clients / max-fleet-width in EVERY arm (equal batch shape;
        serve_fleet_probe's docstring argues why), so the arms differ
        only in how many of those batches forward concurrently. A
        ``single_server_full_batch`` cell (1 server batching its whole
        client share at once) rides along as the transparency baseline
        for the CPU table's batch sublinearity.
      * **brownout anatomy** — single server at 2x-overload (clients =
        2x max_batch), bound off vs on: with ``queue_depth_bound`` the
        overflow sheds (retry-after; clients back off and retry) while
        ADMITTED p99 stays within the SLO (deadline + 2 service times);
        unbounded, the same offered load queues and the client-visible
        p99 inflates past it.
      * **socket round trip** — the TCP_NODELAY re-quote cell.

    The forward calibration table (real jitted forward, median per pow2
    bucket, at the REFERENCE network shape so per-row compute dominates
    dispatch overhead) ships in the artifact."""
    base = dict(overrides or {})
    cmax = max(clients_sweep)
    # reference-shape network for calibration + scaling cells: on a tiny
    # net the fixed dispatch overhead flattens fwd(C)/fwd(C/4) and the
    # cell would measure overhead, not scaling headroom
    import jax

    from r2d2_tpu.models.network import NetworkApply
    cal_cfg = _bench_config(base)
    cal_net = NetworkApply(6, cal_cfg.network, cal_cfg.env.frame_stack,
                           cal_cfg.env.frame_height,
                           cal_cfg.env.frame_width)
    cal_params = cal_net.init(jax.random.PRNGKey(0))
    buckets = []
    b = 1
    while b <= cmax:
        buckets.append(b)
        b *= 2
    table = _calibrate_forward_table(cal_cfg, cal_net, cal_params, buckets)
    out = {
        "repeats": max(repeats, 1),
        "forward_table_ms": {str(k): round(v * 1e3, 3)
                             for k, v in sorted(table.items())},
        "emulation": "timed-forward (calibrated sleep; see PERF.md)",
    }

    width = max(servers_sweep)
    cells = {}
    for rep in range(max(repeats, 1)):
        arms = list(servers_sweep)
        if rep % 2:
            arms = arms[::-1]      # ABBA: cancel monotonic host drift
        for c in clients_sweep:
            for s in arms:
                if c < s or c % width:
                    continue
                cells.setdefault((s, c), []).append(serve_fleet_probe(
                    seconds, s, c, overrides=base, forward_table=table,
                    max_batch=max(1, c // width)))
    out["scaling"] = [
        {**runs[-1],
         "requests_per_sec": float(np.median(
             [r["requests_per_sec"] for r in runs])),
         "requests_per_sec_cells": [r["requests_per_sec"] for r in runs]}
        for (s, c), runs in sorted(cells.items())]

    def med_rps(s, c):
        runs = cells.get((s, c))
        return (float(np.median([r["requests_per_sec"] for r in runs]))
                if runs else None)

    hi, lo = max(servers_sweep), min(servers_sweep)
    if med_rps(lo, cmax):
        out["fleet_scaling_ratio"] = round(
            med_rps(hi, cmax) / med_rps(lo, cmax), 3)
        out["fleet_scaling_servers"] = [lo, hi]
        out["fleet_scaling_clients"] = cmax
    # transparency baseline: one server batching its FULL client share
    # (best single-server batch shape; folds the CPU table's batch
    # sublinearity back in — see serve_fleet_probe's docstring)
    out["single_server_full_batch"] = serve_fleet_probe(
        seconds, 1, cmax, overrides=base, forward_table=table,
        max_batch=cmax)

    # brownout anatomy: ONE server, offered load 2x its micro-batch
    # capacity; the bound is HALF a batch deep (the shed pass runs after
    # each batch fill and rejects only the overflow past the bound, so a
    # bound >= max_batch under exactly-2x load never triggers)
    mb = cmax // 2
    over = {k: v for k, v in base.items()}
    unbounded = serve_fleet_probe(seconds, 1, cmax, overrides=over,
                                  forward_table=table, max_batch=mb,
                                  queue_depth_bound=0)
    bounded = serve_fleet_probe(seconds, 1, cmax, overrides=over,
                                forward_table=table, max_batch=mb,
                                queue_depth_bound=max(1, mb // 2))
    svc_ms = table[mb] * 1e3
    slo_ms = _bench_config(base).serve.deadline_ms + 2.0 * svc_ms
    out["brownout"] = {
        "overload_factor": 2.0,
        "max_batch": mb,
        "service_ms": round(svc_ms, 3),
        "slo_ms": round(slo_ms, 3),
        "unbounded": unbounded,
        "bounded": bounded,
    }
    out["brownout_shed_frac"] = bounded["shed_frac"]
    if bounded.get("admitted_p99_ms") is not None:
        out["brownout_admitted_p99_ms"] = bounded["admitted_p99_ms"]
        out["brownout_ok"] = bool(
            bounded["shed_frac"] > 0.0
            and bounded["admitted_p99_ms"] <= slo_ms)
        # regress-gated form of the brownout acceptance: emitted ONLY
        # while the bounded arm actually sheds, so the metric VANISHES
        # (a gate failure) if brownout stops triggering, and its value
        # drops below 1.0 exactly when admitted p99 exceeds the SLO.
        if bounded["shed_frac"] > 0.0:
            out["brownout_slo_headroom_ratio"] = round(
                slo_ms / bounded["admitted_p99_ms"], 3)

    out["socket_rt"] = socket_rt_probe(min(seconds, 10.0), overrides=base)
    return out


def run_fleet_mh(seconds: float, envs_per_actor: int = 8,
                 dp: int = 2, fleet_on: bool = True,
                 overrides: Optional[dict] = None) -> dict:
    """One lockstep-trainer cell for the fleet A/B: the rank-aware
    ``train_multihost`` loop run as a SINGLE controller over an emulated
    dp-wide mesh (this container's CPU backend has no multiprocess
    collectives — known since PR 3 — so the in-artifact A/B measures the
    fleet plane's per-iteration cost where it lives: the widened psum
    row, the per-iteration timers, the gauge readback, and the rank-0
    aggregator; the loopback two-process twin is the slow-marked test).
    Thread actors feed the real lockstep ingest + dp-sharded learner
    step; speeds come from the rank-0 TrainMetrics records exactly like
    ``run_e2e``."""
    from r2d2_tpu.parallel.multihost import train_multihost

    ov = dict(E2E_CPU_OVERRIDES)
    ov.update({"actor.num_actors": 1,
               "actor.envs_per_actor": envs_per_actor,
               "mesh.dp": dp,
               "telemetry.fleet_enabled": bool(fleet_on)})
    ov.update(overrides or {})
    scratch = None
    if "runtime.save_dir" not in ov:
        import tempfile
        scratch = tempfile.mkdtemp(prefix="r2d2_fleet_")
        ov["runtime.save_dir"] = scratch
    cfg = _bench_config(ov)
    records = []
    t0 = time.time()
    try:
        out = train_multihost(cfg, max_training_steps=10**9,
                              max_seconds=seconds, actor_mode="thread",
                              log_fn=records.append)
    finally:
        if scratch is not None:
            import shutil
            shutil.rmtree(scratch, ignore_errors=True)
    elapsed = time.time() - t0
    steady = [r for r in records[1:] if r.get("training_speed")]
    env_speed = (float(np.mean([r["buffer_speed"] for r in steady]))
                 if steady else 0.0)
    train_speed = (float(np.mean([r["training_speed"] for r in steady]))
                   if steady else 0.0)
    fleet = next((r["fleet"] for r in reversed(records)
                  if r.get("fleet")), None)
    return {
        "seconds": round(elapsed, 1),
        "dp": dp,
        "fleet_enabled": bool(fleet_on),
        "total_env_steps": int(out["env_steps"]),
        "total_train_steps": int(out["step"]),
        "env_steps_per_sec": round(env_speed, 1),
        "learner_steps_per_sec": round(train_speed, 2),
        "env_steps_per_sec_overall": round(out["env_steps"] / elapsed, 1),
        "learner_steps_per_sec_overall": round(out["step"] / elapsed, 2),
        "records": len(records),
        "fleet": fleet,
        "config": {k: ov[k] for k in sorted(ov)},
    }


def run_fleet_ab(seconds: float, envs_per_actor: int = 8, dp: int = 2,
                 overrides: Optional[dict] = None,
                 repeats: int = 2) -> dict:
    """Fleet-observability overhead A/B (ISSUE 12 acceptance): the SAME
    lockstep trainer with ``telemetry.fleet_enabled`` on vs off, in one
    artifact. Budget under test: the fleet plane — the widened psum row
    (one f32 per dp row + the gauge reductions/all-gathers riding the
    existing dispatch), per-iteration perf_counter pairs, the gauge-table
    readback, the rank-0 FleetAggregator flush, and the rank-0 host row
    — costs < 2% on BOTH env-steps/s and learner updates/s (the
    established pillar budget). Cells run INTERLEAVED off/on ``repeats``
    times with per-arm medians (the learning/resources-AB noise
    treatment). The ON cells carry the ``fleet`` block (per-rank
    step-time table, wait fraction, straggler rank) as end-to-end
    evidence; the OFF cells prove the records carried no ``fleet`` key
    (the kill-switch schema contract)."""
    cells = {"fleet_off": [], "fleet_on": []}
    for rep in range(max(repeats, 1)):
        order = (("fleet_off", False), ("fleet_on", True))
        if rep % 2:
            # ABBA order: repeated in-process runs on a small shared
            # host drift slower over time (cache/alloc pressure), and a
            # fixed A,B order would hand the whole drift to one arm —
            # alternating cancels the linear component in the medians
            order = order[::-1]
        for label, on in order:
            cells[label].append(run_fleet_mh(
                seconds, envs_per_actor, dp=dp, fleet_on=on,
                overrides=overrides))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {"fleet_off": cells["fleet_off"][-1],
           "fleet_on": cells["fleet_on"][-1],
           "repeats": max(repeats, 1),
           "dp": dp,
           "env_steps_per_sec_cells": {
               k: [c["env_steps_per_sec"] for c in v]
               for k, v in cells.items()},
           "learner_steps_per_sec_cells": {
               k: [c["learner_steps_per_sec"] for c in v]
               for k, v in cells.items()}}
    if med("fleet_off", "env_steps_per_sec") > 0:
        ratio = (med("fleet_on", "env_steps_per_sec")
                 / med("fleet_off", "env_steps_per_sec"))
        out["env_steps_ratio"] = round(ratio, 3)
        out["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    if med("fleet_off", "learner_steps_per_sec") > 0:
        out["learner_steps_ratio"] = round(
            med("fleet_on", "learner_steps_per_sec")
            / med("fleet_off", "learner_steps_per_sec"), 3)
    fb = next((c["fleet"] for c in reversed(cells["fleet_on"])
               if c.get("fleet")), None)
    out["fleet_block_on"] = bool(fb)
    if fb:
        out["wait_frac_on"] = (fb.get("lockstep") or {}).get("wait_frac")
        out["step_time_on"] = fb.get("step_time")
    out["fleet_block_off"] = any(c.get("fleet")
                                 for c in cells["fleet_off"])
    return out


# Anakin A/B shape: the acting-path STRUCTURAL overhead measurement. The
# policy/env compute is shrunk until it is nearly free on this host (8px
# frames, hidden 16, one conv), because the quantity under test is the
# host-boundary cost per env step — interpreter round-trips, per-tick jit
# dispatch, numpy rolls, LocalBuffer appends, queue hops — which the fused
# on-device path removes. The host arm's floor is ~3 ms of that per-step
# host work per 16-lane tick REGARDLESS of shape, so shrinking compute
# isolates the structural term. Both arms run the IDENTICAL config except
# the routing knobs. On the shared-silicon CPU container the fused arm is
# still bounded by the same 2 cores that run the host arm's policy, which
# caps the measurable ratio (see PERF.md "On-device acting"); on a TPU the
# acting scan runs on accelerator silicon the host actor cannot use at
# all, which is where the Podracer-class orders-of-magnitude appear.
ANAKIN_AB_OVERRIDES = {
    "env.frame_height": 8, "env.frame_width": 8,
    "env.frame_stack": 2, "env.episode_len": 200,
    "network.hidden_dim": 16, "network.cnn_out_dim": 16,
    "network.conv_layers": ((4, 4, 4),),
    "sequence.burn_in_steps": 8, "sequence.learning_steps": 5,
    "sequence.forward_steps": 3,
    # capacity = anakin lanes x block_length: the ring must hold one full
    # segment (one block per lane); kept identical in BOTH arms — ring
    # size shapes the learner's compile/sample cost, so it is part of the
    # matched config, which also caps the lane count at 1024
    "replay.block_length": 200, "replay.capacity": 204_800,
    "replay.batch_size": 8, "replay.learning_starts": 1_000,
    "runtime.save_interval": 0, "runtime.log_interval": 2.0,
}


def run_anakin_ab(seconds: float, envs_per_actor: int = 16,
                  anakin_lanes: int = 512,
                  overrides: Optional[dict] = None,
                  repeats: int = 2) -> dict:
    """On-device acting A/B (ISSUE 6 acceptance): the host-vector actor
    system vs the fused Anakin loop, same config, one artifact.

    Three cells:
      * ``host_vector``   — the legacy system: one process actor with
        ``envs_per_actor`` lanes feeding the learner through the shm ring
        (the PR1-era architecture at this shape);
      * ``anakin``        — ``actor.on_device`` with ``anakin_lanes``
        lanes, unthrottled (acting-rate headline);
      * ``anakin_balanced`` — the fused loop rate-limited to a
        collect:learn ratio that matches the host arm's learner cadence,
        showing the SAME loop trains at full learner speed while still
        collecting several times faster than the host arm.

    Arms run INTERLEAVED ``repeats`` times and the headline ratios come
    from per-arm medians, for the same reason ``run_learning_ab`` does:
    single cells swing ±10% on the shared 2-core host, which is noise at
    the ~62x acting headline but material for the ~1.3x balanced learner
    ratio. Every cell's speeds stay in the artifact.

    The headline ``env_steps_ratio`` is anakin / host_vector."""
    base = dict(ANAKIN_AB_OVERRIDES)
    base.update(overrides or {})
    anakin_ov = dict(base)
    anakin_ov.update({"actor.on_device": True,
                      "actor.anakin_lanes": anakin_lanes})
    bal_ov = dict(base)
    bal_ov.update({"actor.on_device": True,
                   "actor.anakin_lanes": max(anakin_lanes // 2, 1),
                   "replay.max_env_steps_per_train_step": 1024.0})
    cells = {"host_vector": [], "anakin": [], "anakin_balanced": []}
    for _ in range(max(repeats, 1)):
        cells["host_vector"].append(
            run_e2e(seconds, envs_per_actor=envs_per_actor, num_actors=1,
                    overrides=dict(base)))
        cells["anakin"].append(run_e2e(seconds, overrides=dict(anakin_ov)))
        cells["anakin_balanced"].append(
            run_e2e(seconds, overrides=dict(bal_ov)))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {label: runs[-1] for label, runs in cells.items()}
    out["repeats"] = max(repeats, 1)
    out["env_steps_per_sec_cells"] = {
        k: [c["env_steps_per_sec"] for c in v] for k, v in cells.items()}
    out["learner_steps_per_sec_cells"] = {
        k: [c["learner_steps_per_sec"] for c in v] for k, v in cells.items()}
    host_env = med("host_vector", "env_steps_per_sec")
    if host_env > 0:
        out["env_steps_ratio"] = round(
            med("anakin", "env_steps_per_sec") / host_env, 2)
        out["env_steps_ratio_balanced"] = round(
            med("anakin_balanced", "env_steps_per_sec") / host_env, 2)
    host_lr = med("host_vector", "learner_steps_per_sec")
    if host_lr > 0:
        out["learner_steps_ratio_balanced"] = round(
            med("anakin_balanced", "learner_steps_per_sec") / host_lr, 3)
    return out


def run_sharded_anakin_ab(seconds: float, anakin_lanes: int = 1024,
                          dp: int = 2, overrides: Optional[dict] = None,
                          repeats: int = 3) -> dict:
    """Sharded-anakin scaling A/B (ISSUE 8 acceptance): the fused
    act+train loop on a 1x1 mesh vs the IDENTICAL config on a dp-wide
    mesh — same ``anakin_lanes`` total, partitioned into per-shard lane
    groups acting into their local replay shards while the learner runs
    its dp-sharded step on the same mesh. Three cells:

      * ``anakin_dp1``     — actor.on_device at ``anakin_lanes`` on
        mesh.dp=1 (the PR6 fused loop at the same total lane count);
      * ``anakin_sharded`` — the same lanes on mesh.dp=``dp``
        (``anakin_lanes/dp`` per shard);
      * ``anakin_dp1_half_lanes`` — mesh.dp=1 at ``anakin_lanes/dp``
        lanes, i.e. ONE shard's group on one device: the strongest
        single-mesh reference (a lone fused program tops out near this
        lane count — growing it past the cache-friendly width REGRESSES
        per-step cost, which is exactly why scaling continues through
        shards, not lanes), and the honest denominator for the
        weak-scaling reading.

    The headline ``env_steps_ratio_sharded`` compares the equal-lane
    arms; ``env_steps_ratio_sharded_vs_half`` quotes the sharded arm
    against the half-lane single-mesh reference so the scaling claim
    can never hide behind an oversized dp=1 denominator. On CPU the
    mesh is emulated
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which
    ``main`` sets automatically when it owns the process); the claim
    under test — aggregate env-steps/s scaling with dp at
    equal-or-better learner updates/s — carries to real chips, where
    each shard owns its own silicon. Arms run INTERLEAVED ``repeats``
    times with per-arm medians (the run_learning_ab noise treatment);
    every cell's speeds stay in the artifact."""
    import jax
    if len(jax.devices()) < dp:
        raise SystemExit(
            f"--sharded-anakin-ab needs >= {dp} devices but only "
            f"{len(jax.devices())} are visible; on CPU run with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={dp} "
            "(python -m r2d2_tpu.tools.e2e_bench sets this itself when "
            "launched as the main program)")
    base = dict(ANAKIN_AB_OVERRIDES)
    base.update({"actor.on_device": True,
                 "actor.anakin_lanes": anakin_lanes})
    base.update(overrides or {})
    dp1_ov = dict(base, **{"mesh.dp": 1})
    dpn_ov = dict(base, **{"mesh.dp": dp})
    half_ov = dict(base, **{"mesh.dp": 1,
                            "actor.anakin_lanes": anakin_lanes // dp})
    cells = {"anakin_dp1": [], "anakin_sharded": [],
             "anakin_dp1_half_lanes": []}
    for _ in range(max(repeats, 1)):
        cells["anakin_dp1"].append(run_e2e(seconds, overrides=dict(dp1_ov)))
        cells["anakin_sharded"].append(
            run_e2e(seconds, overrides=dict(dpn_ov)))
        cells["anakin_dp1_half_lanes"].append(
            run_e2e(seconds, overrides=dict(half_ov)))

    def med(label, key):
        return float(np.median([c[key] for c in cells[label]]))

    out = {label: runs[-1] for label, runs in cells.items()}
    out["dp"] = dp
    out["anakin_lanes"] = anakin_lanes
    out["repeats"] = max(repeats, 1)
    out["env_steps_per_sec_cells"] = {
        k: [c["env_steps_per_sec"] for c in v] for k, v in cells.items()}
    out["learner_steps_per_sec_cells"] = {
        k: [c["learner_steps_per_sec"] for c in v] for k, v in cells.items()}
    out["dp1_env_steps_per_sec"] = round(
        med("anakin_dp1", "env_steps_per_sec"), 1)
    out["sharded_env_steps_per_sec"] = round(
        med("anakin_sharded", "env_steps_per_sec"), 1)
    out["dp1_learner_steps_per_sec"] = round(
        med("anakin_dp1", "learner_steps_per_sec"), 2)
    out["sharded_learner_steps_per_sec"] = round(
        med("anakin_sharded", "learner_steps_per_sec"), 2)
    out["half_lanes_env_steps_per_sec"] = round(
        med("anakin_dp1_half_lanes", "env_steps_per_sec"), 1)
    if out["dp1_env_steps_per_sec"] > 0:
        out["env_steps_ratio_sharded"] = round(
            out["sharded_env_steps_per_sec"]
            / out["dp1_env_steps_per_sec"], 3)
    if out["dp1_learner_steps_per_sec"] > 0:
        out["learner_steps_ratio_sharded"] = round(
            out["sharded_learner_steps_per_sec"]
            / out["dp1_learner_steps_per_sec"], 3)
    if out["half_lanes_env_steps_per_sec"] > 0:
        out["env_steps_ratio_sharded_vs_half"] = round(
            out["sharded_env_steps_per_sec"]
            / out["half_lanes_env_steps_per_sec"], 3)
    return out


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--sweep", default="1,4,16",
                   help="comma-separated envs_per_actor cells (actor phase)")
    p.add_argument("--seconds", type=float, default=5.0,
                   help="measurement window per actor-sweep cell")
    p.add_argument("--e2e-seconds", type=float, default=60.0,
                   help="end-to-end actors+learner window (0 disables)")
    p.add_argument("--envs-per-actor", type=int, default=16,
                   help="lanes per actor in the e2e phase")
    p.add_argument("--num-actors", type=int, default=1)
    p.add_argument("--ingest-ab", type=int, default=1,
                   help="1 (default): run the e2e phase as an ingestion A/B"
                        " — batched+pipelined (replay.ingest_batch_blocks ="
                        " --ingest-batch-blocks) vs the per-block path, one"
                        " artifact; 0: single e2e run at the config default")
    p.add_argument("--ingest-batch-blocks", type=int, default=8,
                   help="K for the A/B's batched cell")
    p.add_argument("--anakin-ab", type=int, default=0,
                   help="1: run the e2e phase as the on-device acting A/B "
                        "instead — host-vector actor system vs the fused "
                        "Anakin act+train loop at the structural-overhead "
                        "shape (ANAKIN_AB_OVERRIDES), one artifact with "
                        "env-steps/s and learner updates/s per arm")
    p.add_argument("--anakin-lanes", type=int, default=512,
                   help="batched env lanes for the A/B's on-device cell "
                        "(512 is this host's steps/s sweet spot; raise "
                        "replay.capacity via --override when raising this "
                        "past capacity/block_length)")
    p.add_argument("--sharded-anakin-ab", type=int, default=0,
                   help="1: run the e2e phase as the sharded-anakin "
                        "scaling A/B instead — the fused act+train loop "
                        "at --sharded-lanes on mesh.dp=1 vs the SAME "
                        "lanes partitioned across a --sharded-dp mesh "
                        "(CPU: emulated devices, forced automatically), "
                        "plus a half-lane dp=1 reference arm, one "
                        "artifact with per-arm medians and the "
                        "env/learner scaling ratios")
    p.add_argument("--sharded-dp", type=int, default=2,
                   help="mesh width for the sharded-anakin A/B's dp arm")
    p.add_argument("--sharded-lanes", type=int, default=1024,
                   help="TOTAL lanes for the sharded-anakin A/B (both "
                        "main arms; the reference arm runs half) — "
                        "divisible by --sharded-dp, and the FULL count "
                        "must stay <= capacity/block_length (the "
                        "equal-lane dp=1 arm holds all of them on one "
                        "ring; raise replay.capacity via --override "
                        "when raising this)")
    p.add_argument("--telemetry-ab", type=int, default=0,
                   help="1: run the e2e phase as a telemetry on/off A/B "
                        "instead (overhead budget < 2%% env-steps/s; one "
                        "artifact with both cells + the ON cell's stage "
                        "percentiles)")
    p.add_argument("--learning-ab", type=int, default=0,
                   help="1: run the e2e phase as a learning-diagnostics "
                        "on/off A/B instead (telemetry.learning_enabled; "
                        "budget < 2%% on env-steps/s AND learner "
                        "updates/s; the ON cell carries the 'learning' "
                        "block as end-to-end evidence)")
    p.add_argument("--replay-diag-ab", type=int, default=0,
                   help="1: run the e2e phase as a replay-diagnostics "
                        "on/off A/B instead (telemetry.replay_diag_enabled;"
                        " budget < 2%% on env-steps/s AND learner "
                        "updates/s; interleaved repeats with per-arm "
                        "medians, the ON cells carry the 'replay_diag' "
                        "block, plus one sharded (emulated dp=2) anakin "
                        "evidence cell with per-shard + merged sum-tree "
                        "views)")
    p.add_argument("--fleet-ab", type=int, default=0,
                   help="1: run the e2e phase as the fleet-observability "
                        "on/off A/B instead (telemetry.fleet_enabled; the "
                        "lockstep multihost trainer as one controller "
                        "over an emulated --sharded-dp mesh; budget < 2%% "
                        "on env-steps/s AND learner updates/s; "
                        "interleaved repeats with per-arm medians, the "
                        "ON cells carry the 'fleet' block as evidence)")
    p.add_argument("--serve-ab", type=int, default=0,
                   help="1: run the e2e phase as the policy-serving A/B "
                        "instead (ISSUE 13) — thread-mode actors with "
                        "actor.inference local vs server at equal lanes "
                        "(ABBA-interleaved, per-arm medians) plus a "
                        "1/4/16 client-count sweep showing batch fill "
                        "climbing with load; one artifact with the "
                        "serving block (latency percentiles, fill) as "
                        "evidence")
    p.add_argument("--serve-lanes", type=int, default=16,
                   help="lanes (= serve clients) for the serve A/B's "
                        "equal-lane arms")
    p.add_argument("--quant-ab", type=int, default=0,
                   help="1: run the e2e phase as the quantized-inference "
                        "A/B instead (ISSUE 14) — thread-mode acting arm "
                        "at network.inference_dtype f32 vs int8 "
                        "(ABBA-interleaved, per-arm medians, the int8 "
                        "cells carry the 'quant' accuracy block) + a "
                        "serving-probe arm at both dtypes + the analytic "
                        "weight-bytes table (the >= 3x int8 cut); one "
                        "artifact (E2E_r16.json)")
    p.add_argument("--service-ingest-ab", type=int, default=0,
                   help="1: run the e2e phase as the batched service "
                        "data-plane A/B instead (ISSUE 16) — socket-rung "
                        "producer cell (per-block lockstep vs stacked "
                        "windowed frames, ABBA medians, the >= 1.3x "
                        "headline), service-routed learner at "
                        "fleet.ingest_batch_blocks 1 vs "
                        "--ingest-batch-blocks (updates/s ratio >= 0.98), "
                        "and the spill-prefetch sample-latency pair; one "
                        "artifact (E2E_r18.json)")
    p.add_argument("--socket-window", type=int, default=4,
                   help="in-flight frame bound for the service-ingest "
                        "A/B's windowed arm (fleet.socket_window)")
    p.add_argument("--elastic-ab", type=int, default=0,
                   help="1: run the e2e phase as the elastic-fleet A/B "
                        "instead (ISSUE 15) — fixed vs churned fleet at "
                        "equal lanes (grammar-injected leave@block + "
                        "join@t re-adoption under fleet.elastic; the "
                        "learner must never stall) plus a spill-tier "
                        "on/off pair on the service-routed learner "
                        "(fleet.replay_shards=2, 2x-capacity spill); "
                        "one artifact (E2E_r17.json)")
    p.add_argument("--serve-fleet-ab", type=int, default=0,
                   help="1: run the e2e phase as the serving-fleet "
                        "scaling A/B instead (ISSUE 17) — 1/2/4 emulated "
                        "server loops x client widths on the client-side "
                        "router (timed-forward emulation, calibrated per "
                        "dispatch bucket; ABBA-interleaved, per-arm "
                        "medians; 4-server >= 2.5x goodput gate), the "
                        "2x-overload brownout pair (queue_depth_bound "
                        "off/on; admitted p99 within SLO while shedding) "
                        "and the TCP_NODELAY socket round-trip re-quote; "
                        "one artifact (E2E_r19.json)")
    p.add_argument("--recovery-ab", type=int, default=0,
                   help="run the crash-recovery overhead A/B instead "
                        "(ISSUE 18): runtime.snapshot_interval on vs "
                        "off on the same e2e system — the durable "
                        "replay snapshot plane must cost < 2%% on both "
                        "env-steps/s and learner updates/s, the ON "
                        "cells must carry the recovery block, the OFF "
                        "cells must not")
    p.add_argument("--snapshot-interval", type=int, default=200,
                   help="--recovery-ab: the ON arm's snapshot cadence "
                        "in learner steps (default models the ~30s "
                        "loss window the kill drills assert; the write "
                        "duty cycle, not the on-path capture, is the "
                        "cost, so overhead scales ~1/interval)")
    p.add_argument("--tracing-ab", type=int, default=0,
                   help="1: run the e2e phase as the cross-plane tracing "
                        "on/off A/B instead (ISSUE 19: "
                        "telemetry.tracing_enabled; budget <= 2%% on "
                        "env-steps/s AND learner updates/s; ABBA-"
                        "interleaved repeats with per-arm medians; the "
                        "ON cells carry the 'trace' block — sampled "
                        "rows, the env-step->gradient e2e latency "
                        "histogram, per-hop breakdown — as end-to-end "
                        "evidence; one artifact, E2E_r21.json)")
    p.add_argument("--promotion-ab", type=int, default=0,
                   help="1: run the e2e phase as the policy-quality "
                        "on/off A/B instead (ISSUE 20: "
                        "telemetry.quality_enabled; budget <= 2%% on "
                        "env-steps/s AND learner updates/s; ABBA-"
                        "interleaved repeats with per-arm medians in "
                        "thread mode so the calibration tap rides the "
                        "acting hot path; the ON cells carry the "
                        "'quality' block, the OFF cells none; plus the "
                        "gated-canary promotion drill as the evidence "
                        "cell; one artifact, E2E_r22.json)")
    p.add_argument("--resources-ab", type=int, default=0,
                   help="1: run the e2e phase as a resource/compile/alerts "
                        "on/off A/B instead (telemetry.resources_enabled; "
                        "budget < 2%% on env-steps/s AND learner "
                        "updates/s; the ON cells carry the 'resources' "
                        "block + alert tally as end-to-end evidence)")
    p.add_argument("--ab-repeats", type=int, default=2,
                   help="interleaved off/on pairs for the learning A/B "
                        "(medians per arm; small-host noise control)")
    p.add_argument("--out", default=os.environ.get("R2D2_E2E_OUT", ""),
                   help="also write the JSON artifact to this path")
    p.add_argument("--override", action="append", default=[],
                   help="dotted config override key=value (repeatable)")
    args = p.parse_args(argv)

    if args.sharded_anakin_ab or args.replay_diag_ab or args.fleet_ab:
        # the emulated-mesh recipe (README "On-device acting"): the CPU
        # platform must present >= dp devices BEFORE the backend
        # initializes — harmless on real accelerators (the flag only
        # shapes the host platform). argparse runs first so this can
        # land before the jax import below. The replay-diag A/B needs it
        # for its sharded-anakin evidence cell; the fleet A/B for its
        # emulated dp-wide lockstep mesh.
        from r2d2_tpu.utils.platform import force_host_device_count
        force_host_device_count(max(args.sharded_dp, 2))
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    import jax

    overrides = {}
    for ov in args.override:
        k, _, v = ov.partition("=")
        try:
            overrides[k] = json.loads(v)
        except (json.JSONDecodeError, ValueError):
            overrides[k] = v

    dev = jax.devices()[0]
    out = {"metric": "e2e_throughput", "platform": dev.platform,
           "device_kind": dev.device_kind,
           "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    sweep = [int(x) for x in args.sweep.split(",") if x]
    if sweep:
        out["actor_sweep"] = run_actor_sweep(sweep, seconds=args.seconds,
                                             overrides=overrides)
    if args.e2e_seconds > 0:
        if args.sharded_anakin_ab:
            out["e2e_sharded_anakin_ab"] = run_sharded_anakin_ab(
                args.e2e_seconds, anakin_lanes=args.sharded_lanes,
                dp=args.sharded_dp, overrides=overrides,
                repeats=args.ab_repeats)
        elif args.anakin_ab:
            out["e2e_anakin_ab"] = run_anakin_ab(
                args.e2e_seconds, args.envs_per_actor,
                anakin_lanes=args.anakin_lanes, overrides=overrides,
                repeats=args.ab_repeats)
        elif args.fleet_ab:
            out["e2e_fleet_ab"] = run_fleet_ab(
                args.e2e_seconds, args.envs_per_actor,
                dp=args.sharded_dp, overrides=overrides,
                repeats=args.ab_repeats)
        elif args.service_ingest_ab:
            out["e2e_service_ingest_ab"] = run_service_ingest_ab(
                args.e2e_seconds, overrides=overrides,
                repeats=args.ab_repeats,
                ingest_blocks=args.ingest_batch_blocks,
                socket_window=args.socket_window)
        elif args.serve_fleet_ab:
            out["e2e_serve_fleet_ab"] = run_serve_fleet_ab(
                args.e2e_seconds, overrides=overrides,
                repeats=args.ab_repeats)
        elif args.elastic_ab:
            out["e2e_elastic_ab"] = run_elastic_ab(
                args.e2e_seconds, overrides=overrides,
                repeats=args.ab_repeats)
        elif args.quant_ab:
            out["e2e_quant_ab"] = run_quant_ab(
                args.e2e_seconds, lanes=args.serve_lanes,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.serve_ab:
            out["e2e_serve_ab"] = run_serve_ab(
                args.e2e_seconds, lanes=args.serve_lanes,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.replay_diag_ab:
            out["e2e_replay_diag_ab"] = run_replay_diag_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats,
                sharded_dp=args.sharded_dp)
        elif args.recovery_ab:
            out["recovery_ab"] = run_recovery_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats,
                snapshot_interval=args.snapshot_interval)
        elif args.promotion_ab:
            out["e2e_promotion_ab"] = run_promotion_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.tracing_ab:
            out["e2e_tracing_ab"] = run_tracing_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.resources_ab:
            out["e2e_resources_ab"] = run_resources_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.learning_ab:
            out["e2e_learning_ab"] = run_learning_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides, repeats=args.ab_repeats)
        elif args.telemetry_ab:
            out["e2e_telemetry_ab"] = run_telemetry_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                overrides=overrides)
        elif args.ingest_ab:
            out["e2e_ingest_ab"] = run_ingest_ab(
                args.e2e_seconds, args.envs_per_actor, args.num_actors,
                args.ingest_batch_blocks, overrides=overrides)
        else:
            out["e2e"] = run_e2e(args.e2e_seconds, args.envs_per_actor,
                                 args.num_actors, overrides=overrides)
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
