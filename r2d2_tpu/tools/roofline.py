"""Roofline report generator (ISSUE 9): analytic component costs + XLA
program costs + per-backend peak specs + measured step time, joined into
the PERF.md-style table — replacing the round-5 hand math.

What one run produces (JSON artifact + printed table):

  * per-component (torso / lstm / head / sum_tree / replay) FLOPs,
    bytes, arithmetic intensity, compute-vs-memory-bound classification
    against the backend's ridge point, and — when a step time is
    measured or given — %-of-peak per component;
  * the learner step's XLA totals from the fully-unrolled cost twin
    (telemetry/costmodel.py ``unroll_scans=True`` — XLA counts a
    while-loop body once, so only the unrolled program's FLOPs reflect
    executed work) with the parity check against
    ``bench.model_flops_per_step`` (the 5% acceptance bar);
  * the serial-chain critical-path model (iterations, FLOP share, the
    implied per-iteration latency at the measured step time);
  * the anakin acting program's totals + per-env-step compute.

Peaks come from telemetry/costmodel.PEAK_SPECS (keyed by device_kind; a
device missing from it is an error); the CPU backend gets a flagged
NOMINAL row so the report renders on the test backend without pretending
to know the host (override with --peak-flops / --hbm-gbps). Optionally join a
traceparse attribution summary (--trace-summary) to show measured
device-time shares next to the analytic ones.

    python -m r2d2_tpu.tools.roofline                       # auto preset
    python -m r2d2_tpu.tools.roofline --preset reference --out ROOFLINE.json
    make roofline
"""

import json
import sys
import time
from typing import Any, Dict, Optional

from r2d2_tpu.telemetry.costmodel import (analytic_component_costs,
                                          collect_cost_table, gate_config,
                                          model_flops_per_step, peak_spec)

ROOFLINE_VARIANTS = ("learner_step", "anakin_act", "replay_add_many",
                     "replay_sample")


def _preset_config(preset: str):
    from r2d2_tpu.config import Config
    if preset == "auto":
        import jax
        preset = "reference" if jax.default_backend() == "tpu" else "gate"
    if preset == "reference":
        # the real training shape; compiles take minutes on CPU — the
        # default there is the pinned gate fixture instead
        return Config().replace(**{"env.game_name": "Fake",
                                   "env.episode_len": 400}), "reference"
    if preset == "gate":
        return gate_config(), "gate"
    raise SystemExit(f"unknown preset {preset!r} (auto|gate|reference)")


def measure_step_time_ms(cfg, n_timed: int = 5) -> float:
    """Compile + time the production learner step on synthetic replay
    (the profile_step fill pattern) — median of ``n_timed`` dispatches."""
    import jax
    import numpy as np

    from r2d2_tpu.envs.factory import create_jax_env
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_learner_step)
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.replay.device_replay import replay_add, replay_init
    from r2d2_tpu.replay.structs import ReplaySpec
    from r2d2_tpu.replay.synthetic import make_synthetic_block

    spec = ReplaySpec.from_config(cfg)
    action_dim = create_jax_env(cfg.env).action_dim
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    ts = create_train_state(jax.random.PRNGKey(1), net, cfg.optim)
    rs = replay_init(spec)
    rng = np.random.default_rng(0)
    for _ in range(min(spec.num_blocks, 8)):
        rs = replay_add(spec, rs, make_synthetic_block(spec, rng))
    step = make_learner_step(net, spec, cfg.optim, cfg.network.use_double)
    for _ in range(2):                                 # compile + warm
        ts, rs, m = step(ts, rs)
    jax.block_until_ready(m["loss"])
    times = []
    for _ in range(n_timed):
        t0 = time.perf_counter()
        ts, rs, m = step(ts, rs)
        jax.block_until_ready(m["loss"])
        times.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(times))


def build_report(cfg, preset: str, step_time_ms: Optional[float],
                 peak: Dict[str, Any],
                 trace_summary: Optional[dict] = None) -> Dict[str, Any]:
    """The joined roofline report — pure given its inputs (the CLI
    measures/loads them), so tests can golden-file the analytic side."""
    from r2d2_tpu.envs.factory import create_jax_env
    action_dim = create_jax_env(cfg.env).action_dim
    xla = collect_cost_table(cfg, variants=ROOFLINE_VARIANTS,
                             unroll_scans=True)
    programs = xla["programs"]

    # the RESOLVED compute dtype picks both the peak FLOP/s row and the
    # analytic activation byte size — judging bf16 flops against a bf16
    # peak while counting f32 activation bytes would understate every
    # component's arithmetic intensity 2x on TPU
    from r2d2_tpu.models.network import NetworkApply
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    bf16 = bool(net.config.bf16)
    analytic = analytic_component_costs(cfg, action_dim,
                                        act_bytes=2 if bf16 else 4)
    peak_flops = float(peak["flops_bf16" if bf16 else "flops_f32"])
    bw_bytes = float(peak["hbm_gbps"]) * 1e9
    ridge = peak_flops / bw_bytes            # FLOPs/byte at the roofline knee

    step_s = step_time_ms / 1e3 if step_time_ms else None
    comp_rows: Dict[str, Any] = {}
    total_flops = analytic["total_flops"]
    trace_comps = (trace_summary or {}).get("components") or {}
    for name, c in analytic["components"].items():
        ai = c["flops"] / c["bytes"] if c["bytes"] else 0.0
        row = {
            "flops": c["flops"],
            "bytes": c["bytes"],
            "arithmetic_intensity": round(ai, 4),
            "bound": "compute" if ai >= ridge else "memory",
            "share_of_flops": round(c["flops"] / total_flops, 6)
            if total_flops else 0.0,
            # the component's floor at peak: whichever wall it hits
            "time_at_peak_ms": round(1e3 * max(
                c["flops"] / peak_flops, c["bytes"] / bw_bytes), 6),
        }
        if step_s:
            row["pct_of_peak"] = round(
                100.0 * c["flops"] / (step_s * peak_flops), 4)
        if name in trace_comps:
            row["device_time_share"] = trace_comps[name].get("share")
        comp_rows[name] = row

    lstep = programs.get("learner_step", {})
    xla_flops = lstep.get("flops")
    mfps = analytic["model_flops_per_step"]
    parity = {
        "xla_flops": xla_flops,
        "model_flops_per_step": mfps,
        "ratio": (round(xla_flops / mfps, 4)
                  if xla_flops and mfps else None),
    }

    serial = dict(analytic["serial_chain"])
    serial["floor_at_peak_ms"] = round(
        1e3 * serial["flops"] / peak_flops, 6)
    if step_s:
        # upper bound on the chain's per-iteration latency: the whole
        # measured step attributed to the chain (reality overlaps — the
        # PERF.md round-5 additive model brackets it from both sides)
        serial["implied_tau_us_upper"] = round(
            1e6 * step_s / serial["iterations"], 3)

    report = {
        "schema": 1,
        "preset": preset,
        "backend": xla["backend"],
        "peak": peak,
        "compute_dtype": "bf16" if bf16 else "f32",
        "ridge_flops_per_byte": round(ridge, 4),
        "shape": xla["shape"],
        "action_dim": action_dim,
        "learner_step": {
            "measured_ms": step_time_ms,
            "xla": lstep,
            "total_flops_analytic": total_flops,
            "pct_of_peak_total": (round(
                100.0 * total_flops / (step_s * peak_flops), 4)
                if step_s else None),
            "components": comp_rows,
            "serial_chain": serial,
        },
        "parity": parity,
        "anakin_act": None,
        "programs": programs,
    }
    act = programs.get("anakin_act")
    if act:
        seg_steps = cfg.actor.anakin_lanes * cfg.replay.block_length
        report["anakin_act"] = {
            "xla": act,
            "env_steps_per_segment": seg_steps,
            "flops_per_env_step": (round(act["flops"] / seg_steps, 1)
                                   if act.get("flops") else None),
        }
    if trace_summary is not None:
        report["trace_attribution"] = {
            "attributed_frac": trace_summary.get("attributed_frac"),
            "total_us": trace_summary.get("total_us"),
        }
    return report


def format_report(report: Dict[str, Any]) -> str:
    ls = report["learner_step"]
    peak = report["peak"]
    lines = []
    nominal = " [NOMINAL peaks — CPU fallback, do not quote]" \
        if peak.get("nominal") else ""
    lines.append(
        f"roofline @ {peak.get('device_kind')} "
        f"({report['compute_dtype']} peak "
        f"{peak['flops_bf16' if report['compute_dtype'] == 'bf16' else 'flops_f32'] / 1e12:.1f} "
        f"TFLOP/s, {peak['hbm_gbps']:.0f} GB/s, ridge "
        f"{report['ridge_flops_per_byte']:.1f} FLOP/B){nominal}")
    mm = ls["measured_ms"]
    lines.append(
        f"learner step: {ls['total_flops_analytic'] / 1e9:.3f} GFLOP "
        + (f"measured {mm:.3f} ms -> {ls['pct_of_peak_total']:.2f}% of peak"
           if mm else "(no measured step time)"))
    lines.append(f"{'component':<10}{'GFLOP':>10}{'MB':>10}{'AI':>9}"
                 f"{'bound':>9}{'%flops':>8}{'%peak':>8}")
    for name, r in ls["components"].items():
        pct = r.get("pct_of_peak")
        lines.append(
            f"{name:<10}{r['flops'] / 1e9:>10.4f}{r['bytes'] / 2**20:>10.2f}"
            f"{r['arithmetic_intensity']:>9.1f}{r['bound']:>9}"
            f"{100 * r['share_of_flops']:>7.1f}%"
            + (f"{pct:>7.2f}%" if pct is not None else f"{'-':>8}"))
    sc = ls["serial_chain"]
    lines.append(
        f"serial chain: {sc['iterations']} dependent iterations, "
        f"{100 * sc['share_of_total']:.1f}% of FLOPs, floor at peak "
        f"{sc['floor_at_peak_ms']:.4f} ms"
        + (f", implied tau <= {sc['implied_tau_us_upper']:.1f} us/iter"
           if "implied_tau_us_upper" in sc else ""))
    par = report["parity"]
    if par["ratio"] is not None:
        lines.append(
            f"parity: XLA unrolled {par['xla_flops'] / 1e9:.3f} GFLOP vs "
            f"model_flops_per_step {par['model_flops_per_step'] / 1e9:.3f} "
            f"GFLOP (ratio {par['ratio']:.4f})")
    act = report.get("anakin_act")
    if act:
        fpes = act["flops_per_env_step"]
        lines.append(
            f"anakin act: {act['xla'].get('flops', 0) / 1e9:.4f} GFLOP / "
            f"segment = "
            + (f"{fpes:.0f}" if fpes is not None else "-")
            + f" FLOP/env-step ({act['env_steps_per_segment']} "
              "steps/segment)")
    ta = report.get("trace_attribution")
    if ta:
        lines.append(f"trace attribution: "
                     f"{100 * (ta.get('attributed_frac') or 0):.1f}% of "
                     f"{(ta.get('total_us') or 0) / 1e3:.2f} ms device time "
                     "mapped to components")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--preset", default="auto",
                   help="auto (gate on CPU, reference on TPU) | gate | "
                        "reference")
    p.add_argument("--out", default="ROOFLINE.json")
    p.add_argument("--step-time-ms", type=float, default=None,
                   help="use this step time instead of measuring")
    p.add_argument("--no-measure", action="store_true",
                   help="skip the live step timing (%%-of-peak omitted)")
    p.add_argument("--peak-flops", type=float, default=None,
                   help="override the peak FLOP/s (both dtypes)")
    p.add_argument("--hbm-gbps", type=float, default=None,
                   help="override the memory bandwidth (GB/s)")
    p.add_argument("--trace-summary", default="",
                   help="traceparse attribution JSON to join "
                        "(per-component measured device-time shares)")
    args = p.parse_args(argv)

    cfg, preset = _preset_config(args.preset)
    peak = peak_spec()
    if args.peak_flops:
        peak = dict(peak, flops_bf16=args.peak_flops,
                    flops_f32=args.peak_flops, nominal=False)
    if args.hbm_gbps:
        peak = dict(peak, hbm_gbps=args.hbm_gbps)

    step_ms = args.step_time_ms
    if step_ms is None and not args.no_measure:
        print("measuring learner step time ...", file=sys.stderr)
        step_ms = measure_step_time_ms(cfg)

    trace_summary = None
    if args.trace_summary:
        with open(args.trace_summary) as f:
            trace_summary = json.load(f)

    report = build_report(cfg, preset, step_ms, peak,
                          trace_summary=trace_summary)
    print(format_report(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
