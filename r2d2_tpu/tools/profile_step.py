"""One-command profiling of the fused learner step (SURVEY §5.1).

Captures a ``jax.profiler`` trace of N fused train steps on synthetic
replay at the configured scale, then aggregates the Chrome-trace events
per execution plane — the per-op device-time attribution that drove every
round-3/4 optimization decision (PERF.md), as a reproducible tool instead
of a by-hand analysis. The reference has no profiling hooks at all; its
GPU time is opaque outside nvprof runs it never scripts.

    python -m r2d2_tpu.cli.profile --steps 20 --out /tmp/r2d2_prof

On TPU the summary's interesting plane is ``/device:TPU:0`` (XLA op
spans); on CPU only the host plane exists (python dispatch) — the tool
reports whatever planes the backend emitted.
"""

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from r2d2_tpu.config import Config

PlaneSummary = List[Tuple[str, float, int]]   # (name, total_us, count)


def capture_step_trace(cfg: Config, steps: int, out_dir: str,
                       warmup: int = 3) -> str:
    """Run ``steps`` fused learner steps (resolved defaults: decode/gather
    kernels, bf16, steps_per_dispatch) under a profiler trace; returns
    ``out_dir``. Replay is filled with synthetic blocks at the configured
    shapes, so no actors/envs are involved — this profiles the learner
    alone, like the benchmark's learner cells."""
    import jax
    import numpy as np

    from r2d2_tpu.learner import create_train_state, make_learner_step
    from r2d2_tpu.learner.train_step import make_multi_learner_step
    from r2d2_tpu.models import NetworkApply
    from r2d2_tpu.parallel.dryrun import _synthetic_block
    from r2d2_tpu.replay import ReplaySpec, replay_add, replay_init

    spec = ReplaySpec.from_config(cfg)
    action_dim = 18
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    ts = create_train_state(jax.random.PRNGKey(1), net, cfg.optim)
    rs = replay_init(spec)
    rng = np.random.default_rng(0)
    # enough blocks that stratified sampling has real spread; bounded so
    # setup stays cheap at big configured capacities
    for _ in range(min(spec.num_blocks, 8)):
        rs = replay_add(spec, rs, _synthetic_block(spec, rng))

    k = cfg.runtime.resolved_steps_per_dispatch()
    if k > 1:
        step = make_multi_learner_step(net, spec, cfg.optim,
                                       cfg.network.use_double, k)
    else:
        step = make_learner_step(net, spec, cfg.optim, cfg.network.use_double)

    for _ in range(warmup):                      # compile outside the trace
        ts, rs, m = step(ts, rs)
    jax.block_until_ready(m["loss"])

    # whole dispatches only: the ACTUAL traced step count is
    # dispatches * k, which can exceed the request — recorded in the
    # metadata file so ms/step always divides by what really ran
    dispatches = -(-max(1, steps) // k)
    traced_steps = dispatches * k
    # shared capture lifecycle (telemetry/profiler.py): the trace stops
    # exactly once even when a step raises mid-capture — the same helper
    # the orchestrator's first-interval/profile_at_step/SIGUSR2 captures
    # run on
    from r2d2_tpu.telemetry.profiler import trace
    with trace(out_dir):
        for _ in range(dispatches):
            ts, rs, m = step(ts, rs)
        jax.block_until_ready(m["loss"])
    with open(os.path.join(out_dir, "profile_meta.json"), "w") as f:
        json.dump({"steps": traced_steps, "steps_per_dispatch": k,
                   "batch_size": spec.batch_size}, f)
    return out_dir


def traced_step_count(trace_dir: str) -> Optional[int]:
    """The step count recorded by capture_step_trace, or None for traces
    captured elsewhere."""
    try:
        with open(os.path.join(trace_dir, "profile_meta.json")) as f:
            return int(json.load(f)["steps"])
    except (OSError, KeyError, ValueError):
        return None


def summarize_trace(trace_dir: str, top: int = 25
                    ) -> Dict[str, PlaneSummary]:
    """Aggregate the newest Chrome trace under ``trace_dir``: per execution
    plane (pid), total duration and count of every complete ('X') event,
    sorted by total time. Spans can overlap (these are NOT exclusive
    occupancy numbers — same caveat as PERF.md's round-3 analysis)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(
            f"no *.trace.json.gz under {trace_dir!r} — did the capture run?")
    with gzip.open(paths[-1], "rt") as f:
        events = json.load(f)["traceEvents"]

    plane_names: Dict[int, str] = {}
    totals: Dict[int, Dict[str, List[float]]] = defaultdict(
        lambda: defaultdict(lambda: [0.0, 0]))
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            plane_names[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "X":
            t = totals[e["pid"]][e["name"]]
            t[0] += float(e.get("dur", 0.0))
            t[1] += 1
    out: Dict[str, PlaneSummary] = {}
    for pid, names in totals.items():
        plane = plane_names.get(pid, f"pid{pid}")
        rows = sorted(((n, d, int(c)) for n, (d, c) in names.items()),
                      key=lambda r: -r[1])
        out[plane] = rows[:top]
    return out


def device_plane(summary: Dict[str, PlaneSummary]
                 ) -> Optional[Tuple[str, PlaneSummary]]:
    """The accelerator plane of a summary, if one exists."""
    for plane, rows in summary.items():
        if "/device:" in plane and "CPU" not in plane:
            return plane, rows
    return None


def format_summary(summary: Dict[str, PlaneSummary], steps: int) -> str:
    lines = []
    for plane, rows in sorted(summary.items()):
        lines.append(f"== {plane} (top {len(rows)} by total span; spans "
                     "overlap — not exclusive occupancy) ==")
        for name, us, count in rows:
            lines.append(f"  {us/1e3:10.3f} ms  x{count:<6d} "
                         f"{us/1e3/max(steps,1):8.4f} ms/step  {name[:90]}")
    return "\n".join(lines)
