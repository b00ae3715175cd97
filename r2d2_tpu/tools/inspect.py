"""Live run inspector: terminal dashboard over the telemetry stream,
plus Chrome-trace export of the recorded spans.

Reads what a training run leaves in ``runtime.save_dir``:

  * ``metrics_player{p}.jsonl``  — the per-interval aggregated records
    (throughput counters, health counters, and the telemetry 'stages'
    block with fleet-wide P50/P95/P99 per pipeline stage);
  * ``telemetry_host{r}.jsonl``  — per-host stage rows under multihost
    (fleet mode widens them: lockstep timing, mergeable stage counts,
    clock anchors, per-rank alert state — rendered as the per-rank
    panel, and the anchors align the cross-host trace merge);
  * ``spans_*.jsonl``            — drained span events per process;
  * ``alerts_player{p}.jsonl``   — the sentinel's fired alerts (the
    record's ``alerts`` panel is the live view, this file the history).

On-device (anakin) runs render too: one metrics file, no heartbeat
board, the fused ``actor/act_scan`` stage — the fleet-health panel is
replaced by a mode tag instead of showing empty; a dp-sharded run adds
one row per shard (env steps / episodes / return sums) from the
record's ``anakin`` block.

Dashboard mode tails the records and redraws one screen per interval —
run it in a second terminal against a live soak. Export mode
(``--export-trace out.json``) merges every spans file into ONE
Chrome-trace JSON (each process a pid row, each thread a tid track) that
loads in Perfetto / chrome://tracing, viewable alongside the xprof
capture ``runtime.profile_at_step`` or SIGUSR2 triggered. The merge
spans every PLANE of a disaggregated run (ISSUE 19): learner + actor
spans, the policy server's ``spans_serve.jsonl``, and a standalone
ReplayService's ``spans_replay_service.jsonl`` land on one timeline,
aligned per the clock anchors their processes stamped at lease
announcement (``plane_clock_offsets``; cross-host rank spans keep the
PR-12 host-anchor shift).

    python -m r2d2_tpu.tools.inspect --dir models               # once
    python -m r2d2_tpu.tools.inspect --dir models --follow      # live
    python -m r2d2_tpu.tools.inspect --dir models --export-trace t.json
"""

import glob
import json
import os
import sys
import time
from typing import List, Optional

from r2d2_tpu.telemetry.fleet import read_last_jsonl_row
from r2d2_tpu.tools.logparse import parse_jsonl

# stages in display order; anything else in the record appends after
_STAGE_ORDER = [
    "actor/act_scan",
    "actor/forward", "actor/env_step", "actor/block_emit",
    "actor/queue_put", "actor/weight_sync",
    "ingest/ring_get", "ingest/stage", "ingest/commit",
    "learner/sample", "learner/train_dispatch", "learner/device_sync",
    "learner/priority_writeback", "weights/publish",
    "lockstep/dispatch", "lockstep/step",
    "serve/enqueue", "serve/batch_wait", "serve/forward", "serve/reply",
]


def _fmt(v, width: int = 10) -> str:
    if v is None:
        return "-".rjust(width)
    if isinstance(v, float):
        return f"{v:.3f}".rjust(width)
    return str(v).rjust(width)


def render_record(record: dict, host_rows: Optional[List[dict]] = None,
                  costs: Optional[dict] = None,
                  roofline: Optional[dict] = None) -> str:
    """One dashboard frame from the newest aggregated record. ``costs``
    is the run's one-shot cost-model block (it rides exactly one record,
    so the caller digs it out of the stream's history); ``roofline`` the
    newest roofline artifact found next to the metrics (ISSUE 9)."""
    lines = []
    lines.append(
        f"t={record.get('t', 0):8.1f}s  "
        f"env_steps={record.get('env_steps', 0):>10}  "
        f"train_steps={record.get('training_steps', 0):>8}  "
        f"buffer={record.get('buffer_size', 0):>8}")
    lines.append(
        f"env-steps/s={record.get('buffer_speed') or 0.0:9.1f}  "
        f"updates/s={record.get('training_speed') or 0.0:7.2f}  "
        f"loss={_fmt(record.get('loss'), 8)}  "
        f"return={_fmt(record.get('avg_episode_return'), 8)}")
    stages = record.get("stages") or {}
    # on-device (anakin) runs have no actor fleet: one metrics file, no
    # heartbeat board, the fused 'actor/act_scan' stage instead of the
    # per-worker actor stages — label the mode instead of rendering
    # fleet-health panels that can only ever show empty
    on_device = "actor/act_scan" in stages
    health = [] if on_device else [
        f"{k.split('actor_')[-1]}={record[k]}" for k in (
            "actor_restarts", "actor_hangs_detected", "actor_breaker_trips",
            "actor_parked_slots") if record.get(k)]
    ingest = (f"ingest: blocks={record.get('ingest_blocks_total', 0)} "
              f"blocks/drain={_fmt(record.get('ingest_blocks_per_drain'), 6)}"
              f" queue={record.get('ingest_queue_depth', 0)} "
              f"pause={record.get('ingest_pause_time', 0.0)}s")
    if on_device:
        ingest = "mode: on-device (anakin, fused act+train)   " + ingest
    lines.append(ingest + ("   health: " + " ".join(health) if health else ""))
    an = record.get("anakin")
    if an:
        lines.append(render_anakin(an, record.get("quant")))
    fb = record.get("fleet")
    if fb:
        lines.append("")
        lines.append(render_fleet(fb))
    lb = record.get("learning")
    if lb:
        lines.append("")
        lines.append(render_learning(lb))
    rd = record.get("replay_diag")
    if rd:
        lines.append("")
        lines.append(render_replay_diag(rd))
    sv = record.get("serving")
    if sv:
        lines.append("")
        lines.append(render_serving(sv, record.get("quant")))
    qb = record.get("quant")
    if qb and not sv:
        # quantized LOCAL/anakin inference (no serving panel to ride):
        # the dtype + live agreement gauge get their own line
        lines.append("")
        lines.append(render_quant(qb))
    qy = record.get("quality")
    if qy:
        lines.append("")
        lines.append(render_quality(qy))
    tb = record.get("trace")
    if tb:
        lines.append("")
        lines.append(render_trace(tb))
    rb = record.get("resources")
    if rb:
        lines.append("")
        lines.append(render_resources(rb))
    cb = costs or record.get("costs")
    if cb or roofline:
        lines.append("")
        lines.append(render_costs(cb, roofline))
    ab = record.get("alerts")
    if ab is not None:
        lines.append(render_alerts(ab))
    if stages:
        lines.append("")
        lines.append(f"{'stage':<28}{'count':>8}{'p50 ms':>10}"
                     f"{'p95 ms':>10}{'p99 ms':>10}")
        order = ([s for s in _STAGE_ORDER if s in stages]
                 + [s for s in sorted(stages) if s not in _STAGE_ORDER])
        for name in order:
            s = stages[name]
            lines.append(f"{name:<28}{s.get('count', 0):>8}"
                         f"{_fmt(s.get('p50_ms'))}{_fmt(s.get('p95_ms'))}"
                         f"{_fmt(s.get('p99_ms'))}")
        dropped = record.get("telemetry_dropped_spans")
        if dropped:
            lines.append(f"(spans dropped under ring pressure: {dropped})")
    else:
        lines.append("(no 'stages' block — telemetry.enabled=false, or a "
                     "pre-telemetry run)")
    if host_rows:
        lines.append("")
        lines.append(render_host_rows(host_rows))
    return "\n".join(lines)


def render_fleet(fb: dict) -> str:
    """The fleet panel (ISSUE 12): per-rank step-time table with the
    straggler called out, lockstep-wait fraction, env-step divergence,
    and host-row health — the record's ``fleet`` block."""
    lines = [f"fleet: {fb.get('ranks')} rank(s), "
             f"{fb.get('iters')} lockstep iters"]
    ls = fb.get("lockstep") or {}
    if ls.get("wait_frac") is not None:
        lines[0] += (f"  wait={100 * ls['wait_frac']:.0f}% of step "
                     f"(dispatch p~{_fmt(ls.get('wait_ms_mean'), 1).strip()}"
                     f"ms, step {_fmt(ls.get('step_ms_mean'), 1).strip()}ms)")
    st = fb.get("step_time") or {}
    per = st.get("per_rank_ms") or []
    if per:
        straggler = st.get("straggler_rank")
        cells = [f"r{i}={v:.1f}{'*' if i == straggler else ''}"
                 for i, v in enumerate(per)]
        line = "  step-time ms: " + " ".join(cells)
        if st.get("skew") is not None:
            line += f"   skew={st['skew']:.2f}"
        if straggler is not None:
            line += f"  straggler=rank {straggler}"
        lines.append(line)
    env = fb.get("env_steps") or {}
    if env.get("interval"):
        line = ("  env-steps this interval: "
                + " ".join(f"r{i}={v}"
                           for i, v in enumerate(env["interval"])))
        if env.get("divergence") is not None:
            line += f"   divergence={env['divergence']:.2f}"
        lines.append(line)
    hr = fb.get("host_rows") or {}
    if hr:
        bits = []
        if hr.get("max_age_s") is not None:
            bits.append(f"stalest row {hr['max_age_s']:.1f}s")
        if hr.get("absent_ranks"):
            bits.append(f"ABSENT ranks {hr['absent_ranks']}")
        if bits:
            lines.append("  host rows: " + " ".join(bits))
    return "\n".join(lines)


def render_host_rows(host_rows: List[dict]) -> str:
    """The per-rank panel (ISSUE 12): one line per host row — stage P99
    peaks, HBM headroom, step-time/wait view, and alert state — instead
    of the old one-line 'N stages' summary."""
    lines = ["per-rank (telemetry_host*.jsonl):"]
    for row in host_rows:
        stages = row.get("stages") or {}
        bits = [f"  rank {row.get('rank')}: t={row.get('t', 0):.1f}s"]
        # the three slowest stages by P99 — where this rank's time goes
        top = sorted(((s.get("p99_ms") or 0.0, name)
                      for name, s in stages.items()), reverse=True)[:3]
        if top:
            bits.append("p99 " + " ".join(
                f"{name.split('/')[-1]}={p99:.1f}ms"
                for p99, name in top))
        rb = row.get("resources") or {}
        if rb.get("hbm_headroom_frac_min") is not None:
            bits.append(f"hbm-free={100 * rb['hbm_headroom_frac_min']:.0f}%")
        fb = (row.get("fleet") or {})
        ls = fb.get("lockstep") or {}
        if ls.get("wait_frac") is not None:
            bits.append(f"wait={100 * ls['wait_frac']:.0f}%")
        st = fb.get("step_time") or {}
        if st.get("skew") is not None:
            bits.append(f"skew={st['skew']:.2f}")
        ab = row.get("alerts")
        if ab is not None:
            active = ab.get("active") or []
            bits.append("alerts: " + (" ".join(active) if active
                                      else "none"))
        lines.append(" ".join(bits))
    return "\n".join(lines)


def render_anakin(an: dict, quant: Optional[dict] = None) -> str:
    """The sharded-anakin composition panel (ISSUE 8): one row per
    shard (env steps, episodes, return sums this interval) plus the
    env-step imbalance ratio the shard_imbalance alert watches. A
    quantized acting scan (ISSUE 14) adds the active inference dtype to
    the head line (the agreement gauge renders as its own quant line)."""
    imb = an.get("shard_imbalance")
    head = (f"anakin mesh: dp={an.get('dp')} "
            f"lanes/shard={an.get('lanes_per_shard')}"
            + (f"  imbalance={imb:.2f}" if imb is not None else "")
            + (f"  inference={quant.get('dtype')}" if quant else ""))
    lines = [head]
    env = an.get("shard_env_steps") or []
    eps = an.get("shard_episodes") or []
    rep = an.get("shard_reported_episodes") or []
    ret = an.get("shard_return_sum") or []

    def at(seq, i):
        return seq[i] if i < len(seq) else None

    for i, steps in enumerate(env):
        bits = [f"  shard {i}: env-steps={steps}"]
        if at(eps, i) is not None:
            bits.append(f"episodes={eps[i]}")
        if at(rep, i) is not None:
            bits.append(f"reported={rep[i]}")
        if at(ret, i) is not None:
            bits.append(f"return-sum={ret[i]:.2f}")
        lines.append(" ".join(bits))
    return "\n".join(lines)


def render_quant(qb: dict) -> str:
    """The quantized-inference gauge (ISSUE 14): active inference dtype
    + the interval's live f32-twin agreement / max |ΔQ| probes — the
    record's ``quant`` block."""
    bits = [f"quant: dtype={qb.get('dtype')}"]
    if qb.get("probes"):
        bits.append(f"probes={qb['probes']}")
        if qb.get("agree_frac") is not None:
            bits.append(f"agree={100 * qb['agree_frac']:.1f}%")
        if qb.get("agree_min") is not None:
            bits.append(f"(min {100 * qb['agree_min']:.0f}%)")
        if qb.get("dq_max") is not None:
            bits.append(f"|dQ|max={qb['dq_max']:.4g}")
    else:
        bits.append("no probes this interval")
    if qb.get("publish_stamp"):
        bits.append(f"twin@pub={qb['publish_stamp']}")
    return " ".join(bits)


def render_serving(sv: dict, quant: Optional[dict] = None) -> str:
    """The serving panel (ISSUE 13): request latency percentiles, batch
    fill, dispatch causes, and client lease churn — the record's
    ``serving`` block from the central policy inference server. When the
    run serves a quantized forward (ISSUE 14), the active inference
    dtype + live agreement gauge render as the panel's last line."""
    lat = sv.get("latency") or {}
    batch = sv.get("batch") or {}
    clients = sv.get("clients") or {}
    lines = [f"serving: {sv.get('requests', 0)} req "
             f"{sv.get('replies', 0)} ok "
             f"{sv.get('expired', 0)} expired "
             f"{sv.get('timeouts', 0)} timeouts(cum)  "
             f"clients={clients.get('active', 0)}"]
    if lat:
        lines.append(
            f"  latency ms: p50={_fmt(lat.get('p50_ms'), 8).strip()} "
            f"p95={_fmt(lat.get('p95_ms'), 8).strip()} "
            f"p99={_fmt(lat.get('p99_ms'), 8).strip()}"
            + (f"   SLO deadline {sv['deadline_ms']}ms"
               if sv.get("deadline_ms") is not None else ""))
    if batch.get("count"):
        bits = [f"  batches={batch['count']} "
                f"fill={_fmt(batch.get('fill_mean'), 6).strip()}"
                f"/{sv.get('max_batch', '-')}"]
        for key, label in (("full_frac", "full"),
                           ("deadline_frac", "deadline"),
                           ("starved_frac", "starved")):
            if batch.get(key) is not None:
                bits.append(f"{label}={100 * batch[key]:.0f}%")
        lines.append(" ".join(bits))
    churn = [f"{k}={clients[k]}" for k in
             ("connects", "reconnects", "disconnects", "evictions")
             if clients.get(k)]
    if churn:
        lines.append("  leases: " + " ".join(churn))
    adm = sv.get("admission")
    if adm:
        alat = adm.get("admitted_latency") or {}
        bits = [f"  admission: shed={adm.get('shed', 0)} "
                f"({100 * adm.get('shed_frac', 0.0):.1f}%) "
                f"misrouted={adm.get('misrouted', 0)}"]
        if alat.get("p99_ms") is not None:
            bits.append(f"admitted p99={_fmt(alat['p99_ms'], 8).strip()}ms")
        lines.append(" ".join(bits))
    fleet = sv.get("servers")
    if fleet:
        lines.append(f"  fleet: {fleet.get('count', 0)} servers "
                     f"map v{fleet.get('map_version', 0)}")
        for slot, row in sorted((fleet.get("rows") or {}).items(),
                                key=lambda kv: int(kv[0])):
            lines.append(
                f"    server {slot}: {row.get('requests', 0)} req "
                f"fill={_fmt(row.get('fill_mean'), 6).strip()} "
                f"p50={_fmt(row.get('latency_p50_ms'), 8).strip()} "
                f"p99={_fmt(row.get('latency_p99_ms'), 8).strip()} "
                f"shed={row.get('shed', 0)} "
                f"shards={row.get('shards', 0)}")
    if quant:
        lines.append("  " + render_quant(quant))
    return "\n".join(lines)


def render_replay_diag(rd: dict) -> str:
    """The replay-pathology panel (ISSUE 10): sum-tree health + collapse
    indicators (merged and, on a dp mesh, per shard), eviction lifetimes
    with the never-sampled fraction, and the ε-lane composition of the
    interval's sampled batches."""
    lines = []
    tree = rd.get("tree") or {}
    if tree:
        bits = [f"replay: tree active={tree.get('active_leaves')}"]
        if tree.get("ess_frac") is not None:
            bits.append(f"ess={tree.get('ess')} "
                        f"({100 * tree['ess_frac']:.0f}% of active)")
        if tree.get("max_mean_ratio") is not None:
            bits.append(f"max/mean={tree['max_mean_ratio']:.2f}")
        if tree.get("frac_at_max") is not None:
            bits.append(f"at-max={100 * tree['frac_at_max']:.0f}%")
        pr = tree.get("priorities") or {}
        if pr:
            bits.append(f"prio p50={pr['p50']:.4g} p95={pr['p95']:.4g}")
        lines.append(" ".join(bits))
    else:
        lines.append("replay: (no tree snapshot this interval)")
    for i, sh in enumerate(rd.get("shards") or []):
        if not sh:
            continue
        lines.append(f"  shard {i}: active={sh.get('active_leaves')} "
                     f"ess-frac={sh.get('ess_frac')} "
                     f"at-max={sh.get('frac_at_max')}")
    ev = rd.get("evictions") or {}
    if ev.get("evicted"):
        bits = [f"  evictions: {ev['evicted']} total"]
        if ev.get("never_sampled_frac") is not None:
            bits.append(f"NEVER-SAMPLED {100 * ev['never_sampled_frac']:.1f}%")
        if ev.get("mean_lifetime") is not None:
            bits.append(f"mean-lifetime={ev['mean_lifetime']:.2f}x")
        if ev.get("mean_age_blocks") is not None:
            bits.append(f"mean-age={ev['mean_age_blocks']:.0f} adds")
        it = ev.get("interval") or {}
        if it.get("evicted"):
            bits.append(f"(+{it['evicted']} this interval)")
        lines.append(" ".join(bits))
    ln = rd.get("lanes") or {}
    if ln:
        bits = [f"  lanes: {ln.get('active_lanes')}/{ln.get('total_lanes')}"
                f" active"]
        if ln.get("starved_frac"):
            bits.append(f"starved={100 * ln['starved_frac']:.0f}%")
        if ln.get("max_share") is not None:
            bits.append(f"top-lane share={100 * ln['max_share']:.0f}%")
        if ln.get("unknown_frac"):
            bits.append(f"unknown={100 * ln['unknown_frac']:.0f}%")
        lines.append(" ".join(bits))
    return "\n".join(lines)


def render_costs(cb: Optional[dict], roofline: Optional[dict]) -> str:
    """The cost-model / roofline panel (ISSUE 9): per-component FLOP
    shares from the run's one-shot ``costs`` block, joined with
    %-of-peak from the newest roofline artifact when one sits next to
    the metrics stream (tools/roofline.py --out)."""
    lines = []
    rl_comps = {}
    # the artifact is discovered by mtime alone (run dir or cwd) — guard
    # against joining a DIFFERENT shape's roofline (e.g. the gate-preset
    # ROOFLINE.json from `make roofline` next to a reference-shape run):
    # the record's costs block and the artifact both carry the analytic
    # model FLOPs, which pin the shape
    if roofline and cb and cb.get("model_flops_per_step"):
        rl_mfps = (roofline.get("parity") or {}).get("model_flops_per_step")
        if rl_mfps and abs(rl_mfps - cb["model_flops_per_step"]) \
                > 0.05 * cb["model_flops_per_step"]:
            lines.append("costs: (roofline artifact is for a different "
                         "shape — ignored; rerun `make roofline` against "
                         "this config)")
            roofline = None
    if roofline:
        ls = (roofline.get("learner_step") or {})
        rl_comps = ls.get("components") or {}
        peak = roofline.get("peak") or {}
        # name the artifact's preset in the header, and say so when the
        # run carries no costs block to validate the shape against (the
        # costmodel kill switch off) — mtime discovery must never let a
        # different-shape artifact masquerade as the live run's stats
        bits = [f"roofline[{roofline.get('preset', '?')}]"
                f"@{peak.get('device_kind', '?')}"]
        if not (cb or {}).get("model_flops_per_step"):
            bits.append("(shape unverified vs this run)")
        if ls.get("measured_ms"):
            bits.append(f"step={ls['measured_ms']:.2f}ms")
        if ls.get("pct_of_peak_total") is not None:
            bits.append(f"{ls['pct_of_peak_total']:.1f}% of peak")
        if peak.get("nominal"):
            bits.append("[nominal peaks]")
        par = (roofline.get("parity") or {}).get("ratio")
        if par is not None:
            bits.append(f"parity={par:.3f}")
        lines.append("costs: " + " ".join(bits))
    comps = (cb or {}).get("components") or rl_comps
    if comps:
        total = sum(c.get("flops", 0.0) for c in comps.values()) or 1.0
        row = []
        for name, c in sorted(comps.items(),
                              key=lambda kv: -kv[1].get("flops", 0.0)):
            bit = f"{name}={100 * c.get('flops', 0.0) / total:.0f}%"
            rc = rl_comps.get(name) or {}
            if rc.get("pct_of_peak") is not None:
                bit += f"({rc['pct_of_peak']:.1f}%pk)"
            row.append(bit)
        prefix = "  flops: " if lines else "costs: "
        lines.append(prefix + " ".join(row))
    if cb and cb.get("model_flops_per_step"):
        sc = cb.get("serial_chain") or {}
        lines.append(
            f"  model {cb['model_flops_per_step'] / 1e9:.3f} GFLOP/step"
            + (f"  serial chain {sc.get('iterations')} iters "
               f"({100 * sc.get('share_of_total', 0):.1f}% of FLOPs)"
               if sc else ""))
    return "\n".join(lines) if lines else "costs: (none)"


def render_learning(lb: dict) -> str:
    """The learning-dynamics panel (ISSUE 5): ΔQ, value-histogram
    percentiles, grad norms, staleness — one compact block per record."""
    lines = []
    dq = lb.get("delta_q") or {}
    if any(v is not None for v in dq.values()):
        lines.append(
            "learning: dQ stored={} zero={} recomputed={}".format(
                *(_fmt(dq.get(k), 8).strip()
                  for k in ("stored", "zero", "recomputed"))))
    else:
        lines.append("learning: (no dQ sample this interval)")
    row = []
    for label, key in (("|TD|", "td_abs"), ("prio", "priority"),
                       ("|Q|", "q_abs")):
        h = lb.get(key)
        if h:
            row.append(f"{label} p50={h['p50']:.4g} p95={h['p95']:.4g}")
    if row:
        lines.append("  " + "   ".join(row))
    gn = lb.get("grad_norm") or {}
    if gn:
        lines.append("  grad-norm " + " ".join(
            f"{k}={v.get('mean'):.4g}" for k, v in sorted(gn.items())
            if v.get("mean") is not None))
    age = lb.get("sample_age") or {}
    rage = lb.get("replay_age") or {}
    bits = []
    if age.get("p50") is not None:
        bits.append(f"sample-age p50={age['p50']:.0f} p95={age['p95']:.0f} "
                    f"max={age['max']}")
    if age.get("unknown_frac"):
        bits.append(f"unknown={100 * age['unknown_frac']:.0f}%")
    if rage.get("p50") is not None:
        bits.append(f"replay-age p50={rage['p50']:.0f} p95={rage['p95']:.0f}")
    if lb.get("target_param_dist") is not None:
        bits.append(f"target-dist={lb['target_param_dist']:.4g}")
    if bits:
        lines.append("  " + "   ".join(bits))
    if lb.get("nonfinite_steps"):
        lines.append(f"  !! NON-FINITE steps this interval: "
                     f"{lb['nonfinite_steps']} (see nan_dump_player*.json)")
    return "\n".join(lines)


def render_quality(qy: dict) -> str:
    """The policy-quality panel (ISSUE 20): continuous-eval return per
    scenario, the in-stream Q-calibration gauge (greedy max-Q at
    decision time vs realized n-step return), shadow-scoring divergence
    against a canary candidate, and the promotion state machine — the
    record's ``quality`` block."""
    ev = qy.get("eval") or {}
    cal = qy.get("calibration") or {}
    sh = qy.get("shadow") or {}
    pr = qy.get("promotion") or {}
    head = "quality:"
    if ev.get("mean_return") is not None:
        head += (f" eval={ev['mean_return']:.2f}"
                 + (f" (ckpt step {ev['checkpoint_step']})"
                    if ev.get("checkpoint_step") is not None else "")
                 + (f" stamp={ev['publish_stamp']}"
                    if ev.get("publish_stamp") is not None else "")
                 + (f"<-{ev['parent_stamp']}"
                    if ev.get("parent_stamp") is not None else ""))
    else:
        head += " (no eval rollout yet)"
    if ev.get("evals_total"):
        head += f"  evals={ev['evals_total']}"
    lines = [head]
    for row in ev.get("scenarios") or []:
        lines.append(f"  scenario {row.get('scenario')}: "
                     f"mean={_fmt(row.get('mean_return'), 8).strip()} "
                     f"min={_fmt(row.get('min_return'), 8).strip()} "
                     f"max={_fmt(row.get('max_return'), 8).strip()} "
                     f"({row.get('episodes', 0)} ep)")
    if cal.get("samples"):
        lines.append(
            f"  calibration: {cal['samples']} joined sample(s) "
            f"gap={_fmt(cal.get('gap_mean'), 8).strip()}"
            + (f" |gap|max={_fmt(cal.get('gap_abs_max'), 8).strip()}"
               if cal.get("gap_abs_max") is not None else "")
            + (f" stamp={cal['stamp']}"
               if cal.get("stamp") is not None else "")
            + f" (total {cal.get('samples_total', 0)})")
    if sh.get("requests"):
        bits = [f"  shadow: {sh['requests']} scored"]
        if sh.get("divergence") is not None:
            bits.append(f"divergence={sh['divergence']:.3f}")
        if sh.get("agree_frac") is not None:
            bits.append(f"agree={100 * sh['agree_frac']:.1f}%")
        if sh.get("dq_max") is not None:
            bits.append(f"|dQ|max={sh['dq_max']:.4g}")
        if sh.get("dropped"):
            bits.append(f"dropped={sh['dropped']}")
        bits.append(f"(total {sh.get('mirrored_total', 0)})")
        lines.append(" ".join(bits))
    if pr.get("state") and pr["state"] != "idle":
        bits = [f"  promotion: {pr['state'].upper()}"]
        if pr.get("age_s") is not None:
            bits.append(f"age={pr['age_s']:.0f}s")
        if pr.get("candidate_stamp") is not None:
            bits.append(f"candidate={pr['candidate_stamp']}")
        if pr.get("previous_stamp") is not None:
            bits.append(f"previous={pr['previous_stamp']}")
        counts = [f"{k}={pr[k]}" for k in
                  ("promotions", "rollbacks", "refusals") if pr.get(k)]
        if counts:
            bits.append(" ".join(counts))
        lines.append(" ".join(bits))
    return "\n".join(lines)


def render_trace(tb: dict) -> str:
    """The cross-plane tracing panel (ISSUE 19): the end-to-end
    env-step -> gradient latency of the interval's lineage-stamped
    blocks, broken down per pipeline hop — the record's ``trace``
    block."""
    e2e = tb.get("e2e_experience_latency") or {}
    head = f"trace: {tb.get('sampled', 0)} sampled row(s)"
    if e2e.get("p50_ms") is not None:
        head += (f"  e2e env-step->gradient ms: p50={e2e['p50_ms']:.0f} "
                 f"p95={e2e['p95_ms']:.0f} p99={e2e['p99_ms']:.0f}")
    lines = [head]
    hops = tb.get("hops") or {}
    if hops:
        bits = []
        for name in ("emit_to_ingest", "ingest_to_sample",
                     "sample_to_train"):
            h = hops.get(name)
            if h and h.get("p50_ms") is not None:
                bits.append(f"{name}={h['p50_ms']:.0f}ms")
        if bits:
            lines.append("  hops p50: " + " ".join(bits))
    return "\n".join(lines)


def render_resources(rb: dict) -> str:
    """The machine-side panel (ISSUE 7): per-device HBM + headroom, host
    RSS/CPU, the buffer-attribution table, and the compile/retrace
    sub-block — one compact block per record."""
    lines = []
    devs = rb.get("devices") or []
    dev_bits = []
    for d in devs[:4]:
        if d.get("bytes_in_use") is None:
            continue
        bit = f"dev{d.get('id')}={d['bytes_in_use'] / 2**20:.0f}MiB"
        if d.get("headroom_frac") is not None:
            bit += f" ({100 * d['headroom_frac']:.0f}% free)"
        dev_bits.append(bit)
    host = rb.get("host") or {}
    host_bits = []
    if host.get("rss_bytes") is not None:
        host_bits.append(f"rss={host['rss_bytes'] / 2**20:.0f}MiB")
    if host.get("cpu_pct") is not None:
        host_bits.append(f"cpu={host['cpu_pct']:.0f}%")
    if host.get("threads") is not None:
        host_bits.append(f"threads={host['threads']}")
    lines.append("resources: "
                 + (" ".join(dev_bits) if dev_bits
                    else "(no device byte counters — CPU backend)")
                 + ("   host: " + " ".join(host_bits) if host_bits else ""))
    slots = rb.get("actor_slots") or {}
    if slots.get("rss_bytes"):
        rss = [f"{b / 2**20:.0f}" for b in slots["rss_bytes"]]
        cpu = ["-" if c is None else f"{c:.0f}"
               for c in slots.get("cpu_pct") or []]
        lines.append(f"  actor slots rss MiB: [{' '.join(rss)}]"
                     + (f"  cpu %: [{' '.join(cpu)}]" if cpu else ""))
    bufs = rb.get("buffers") or {}
    if bufs:
        top = sorted(bufs.items(), key=lambda kv: -kv[1])[:6]
        lines.append("  buffers: " + " ".join(
            f"{name}={b / 2**20:.0f}MiB" for name, b in top)
            + f"  total={rb.get('buffers_total', 0) / 2**20:.0f}MiB")
    comp = rb.get("compile")
    if comp:
        line = (f"  compile: total={comp.get('compiles_total', 0)} "
                f"({comp.get('compile_time_s_total', 0.0):.1f}s) "
                f"interval={comp.get('compiles', 0)} "
                f"retraces={comp.get('retraces_total', 0)}"
                + (" [warm]" if comp.get("warm") else " [warming up]"))
        if "trace_lower_s" in comp:
            line += (f"  trace+lower={comp['trace_lower_s']:.1f}s cache "
                     f"hits={comp['cache_hits']} "
                     f"misses={comp['cache_misses']}")
        aot = comp.get("aot") or {}
        if aot.get("missing"):
            line += f"  !! AOT buckets missing: {aot['missing']}"
        lines.append(line)
        last = comp.get("last_retrace")
        if comp.get("retraces_interval") and last:
            lines.append(f"  !! RETRACE {last.get('fn')} "
                         f"{(last.get('avals') or '')[:80]}")
    return "\n".join(lines)


def render_alerts(ab: dict) -> str:
    """The sentinel panel (ISSUE 7): rules active now + firings this
    interval; silent when everything is healthy."""
    active = ab.get("active") or []
    fired = ab.get("fired") or []
    if not active and not fired:
        return "alerts: none active"
    lines = [f"alerts ACTIVE: {' '.join(active)}"]
    for a in fired:
        bit = f"  -> FIRED {a.get('severity', '?').upper()} {a.get('rule')}"
        if a.get("value") is not None:
            bit += f" value={a['value']:.4g} bound={a.get('bound')}"
        if a.get("baseline") is not None:
            bit += f" baseline={a['baseline']:.4g}"
        lines.append(bit)
    return "\n".join(lines)


def newest_roofline(run_dir: str) -> Optional[dict]:
    """The newest roofline artifact next to the metrics stream (or in
    the working directory — where `make roofline` drops it)."""
    paths = [p for d in (run_dir, ".") for pat in
             ("ROOFLINE*.json", "roofline*.json")
             for p in glob.glob(os.path.join(d, pat))]
    if not paths:
        return None
    try:
        # getmtime inside the guard: a follow-mode dashboard can race a
        # `make roofline` rewrite (or a deletion) between glob and stat
        with open(max(set(paths), key=os.path.getmtime)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def costs_record(records: List[dict]) -> Optional[dict]:
    """The one-shot ``costs`` block from wherever in the stream it rode
    (the first record after the learner's first flush)."""
    for rec in reversed(records):
        if rec.get("costs"):
            return rec["costs"]
    return None


def newest_host_rows(run_dir: str) -> List[dict]:
    # O(tail) + rotation-aware: a near-cap host row file must not cost
    # a full parse per dashboard frame, and the instant between a
    # rotation's rename and its next write must not drop the rank
    rows = []
    for path in sorted(glob.glob(os.path.join(run_dir,
                                              "telemetry_host*.jsonl"))):
        row = read_last_jsonl_row(path)
        if row is not None:
            rows.append(row)
    return rows


def fleet_clock_offsets(run_dir: str):
    """Cross-host clock alignment from the fleet host rows (ISSUE 12):
    each rank's row carries a wall/monotonic anchor pair stamped when
    lockstep iteration 1's collective completed — a genuinely
    pod-synchronized instant — so ``offset[r] = anchor_r.wall -
    anchor_0.wall`` estimates rank r's wall-clock skew against rank 0.
    Returns ``({rank: offset_seconds}, actors_per_rank)``; empty when no
    anchored rows exist (pre-PR12 runs, fleet off, single-host)."""
    import re
    offsets = {}
    anchors = {}
    actors_per_rank = None
    for path in glob.glob(os.path.join(run_dir, "telemetry_host*.jsonl")):
        m = re.search(r"telemetry_host(\d+)\.jsonl$", path)
        if not m:
            continue
        row = read_last_jsonl_row(path)
        if row is None:
            continue
        a = row.get("clock_anchor")
        if a and a.get("wall") is not None:
            anchors[int(m.group(1))] = a
        if row.get("actors_per_rank"):
            actors_per_rank = int(row["actors_per_rank"])
    base = anchors.get(0)
    if base is not None:
        for r, a in anchors.items():
            offsets[r] = a["wall"] - base["wall"]
    return offsets, actors_per_rank


def plane_clock_offsets(run_dir: str) -> dict:
    """Per-PLANE clock offsets (ISSUE 19), generalizing the per-rank
    anchors: serve / replay-service processes stamp a ``proc`` header
    (plane, pid, wall/mono anchor) on their periodic rows, and a
    standalone ReplayService exchanges anchors with the lease board at
    announcement — its ``offset_est`` (seconds its wall clock runs
    AHEAD of the learner plane's, good to ±RTT/2) is what aligns its
    spans here. Planes without an exchange anchor at 0 (same-host wall
    clocks). Returns ``{spans-file basename: offset_seconds}``."""
    offsets = {}
    for name, pattern in (("spans_serve.jsonl", "serve_metrics.jsonl"),
                          ("spans_replay_service.jsonl",
                           "service_metrics_p*.jsonl")):
        for path in glob.glob(os.path.join(run_dir, pattern)):
            row = read_last_jsonl_row(path)
            anchor = ((row or {}).get("proc") or {}).get("clock_anchor")
            if anchor is not None:
                offsets[name] = float(anchor.get("offset_est") or 0.0)
    return offsets


def _span_file_rank(path: str, actors_per_rank) -> Optional[int]:
    """Which rank produced a spans file: host files carry it in the
    name; actor files carry the GLOBAL worker index, which maps back via
    the fleet rows' actors_per_rank (None = unknown, left unshifted)."""
    import re
    name = os.path.basename(path)
    m = re.match(r"spans_host(\d+)\.jsonl$", name)
    if m:
        return int(m.group(1))
    m = re.match(r"spans_p\d+_a(\d+)\.jsonl$", name)
    if m and actors_per_rank:
        return int(m.group(1)) // actors_per_rank
    return None


def export_chrome_trace(run_dir: str, out_path: str) -> int:
    """Merge every spans_*.jsonl under ``run_dir`` into one Chrome-trace
    JSON; returns the number of span events exported. When the run's
    fleet host rows carry clock anchors (ISSUE 12), every rank's spans
    are shifted onto rank 0's wall clock before the merge — one aligned
    Perfetto timeline with per-rank tracks instead of one skewed track
    per process."""
    from r2d2_tpu.telemetry import chrome_trace_events
    offsets, actors_per_rank = fleet_clock_offsets(run_dir)
    plane_offsets = plane_clock_offsets(run_dir)
    events = []
    n = 0
    for pid_index, path in enumerate(
            sorted(glob.glob(os.path.join(run_dir, "spans_*.jsonl")))):
        spans = parse_jsonl(path)
        n += len(spans)
        rank = _span_file_rank(path, actors_per_rank)
        shift = offsets.get(rank, 0.0) if rank is not None else 0.0
        # ISSUE 19: serve / replay-service plane spans align on the
        # anchor their process exchanged at lease announcement
        shift += plane_offsets.get(os.path.basename(path), 0.0)
        if shift:
            spans = [{**ev, "ts": ev["ts"] - shift} for ev in spans]
        pid = (spans[0].get("pid") if spans else None) or \
            os.path.basename(path)[len("spans_"):-len(".jsonl")]
        if rank is not None:
            pid = f"rank{rank}/{pid}"
        events.extend(chrome_trace_events(spans, pid, pid_index))
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)
    return n


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--dir", default="models",
                   help="the run's save_dir (metrics/spans live there)")
    p.add_argument("--player", type=int, default=0)
    p.add_argument("--follow", action="store_true",
                   help="keep tailing and redraw per new record")
    p.add_argument("--interval", type=float, default=2.0,
                   help="poll cadence in follow mode")
    p.add_argument("--export-trace", default="",
                   help="write Chrome-trace JSON here (Perfetto-loadable) "
                        "and exit")
    args = p.parse_args(argv)

    if args.export_trace:
        n = export_chrome_trace(args.dir, args.export_trace)
        print(f"exported {n} spans from {args.dir!r} to "
              f"{args.export_trace!r}")
        return 0

    path = os.path.join(args.dir, f"metrics_player{args.player}.jsonl")
    last_len = -1
    while True:
        try:
            records = parse_jsonl(path)
        except FileNotFoundError:
            print(f"waiting for {path} ..." if args.follow
                  else f"no metrics stream at {path}")
            if not args.follow:
                return 1
            time.sleep(args.interval)
            continue
        if records and len(records) != last_len:
            last_len = len(records)
            frame = render_record(records[-1], newest_host_rows(args.dir),
                                  costs=costs_record(records),
                                  roofline=newest_roofline(args.dir))
            if args.follow and sys.stdout.isatty():
                sys.stdout.write("\x1b[2J\x1b[H")   # clear + home
            print(f"== {path} (record {len(records)}) ==")
            print(frame, flush=True)
            # the alert stream's newest firings (machine-readable side of
            # the record's 'alerts' panel; absent pre-PR7 or with the
            # pillar off)
            apath = os.path.join(args.dir,
                                 f"alerts_player{args.player}.jsonl")
            if os.path.exists(apath):
                for row in parse_jsonl(apath, limit=3):
                    print(f"  alert@t={row.get('t', 0):.0f}s "
                          f"{row.get('severity', '?')}: {row.get('rule')} "
                          f"value={row.get('value')}", flush=True)
        if not args.follow:
            return 0
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
