"""Benchmark: learner sequence-updates/sec/chip (BASELINE.md north star).

Measures the fused R2D2 learner step — prioritized sample from HBM replay +
full 55-step conv/LSTM unroll + value-rescaled double/dueling loss + Adam +
priority write-back, one XLA program — at the reference's training
configuration (batch 128 sequences, burn-in 40 / learning 10 / n-step 5,
84x84x4 frames, cnn_out 1024, LSTM 512, dueling on, double off;
/root/reference/config.py).

Measurements (VERDICT r2 #1/#3 + the rounds-3/4 kernels):
  1. obs-decode A/B at the base config: XLA gather vs the pallas VMEM kernel;
  1b. replay sample-gather A/B: the scalar-prefetch pallas row gather vs the
     XLA batched-dynamic-slice gather, inside the full fused step;
  2. the perf matrix {f32, bf16} x {steps_per_dispatch 1, 4, 16} on the
     default decode path — the reference's amp analog (config.py:35) and the
     host-dispatch amortization the reference cannot do (it pays a Ray RPC
     per step by construction, worker.py:303);
  2b. optional A/B cells, ordered by information value: the fused pallas
     LSTM scan (block_t sweep), the gather variant opposite the shipped
     default, space_to_depth, NHWC decode (default-skipped dead end), and
     the double-DQN unroll-fusion pair;
  3. an analytic model-FLOPs/s estimate against the chip's peak (MFU).

This file measures the LEARNER side only (synthetic replay, no actors).
The system-level number — process-mode vector actors feeding this learner,
env-steps/s and learner steps/s reported together — is
r2d2_tpu/tools/e2e_bench.py (also reachable as a soak phase:
``cli.soak --e2e-seconds=...``); artifact E2E_r06.json.

vs_baseline: the reference publishes NO numbers (BASELINE.json "published":
{}). Its learner logs 'training speed' in updates/s (worker.py:229); upstream
runs of this codebase on a desktop GPU train at ~5 updates/s = 640
sequence-updates/s (128-sequence batches). That figure is the documented
baseline estimate used here until a measured reference log is available.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

One process, one run: ``run_bench()`` measures on the TPU it finds and exits
non-zero when it finds none — a number is only ever printed by the run that
measured it. The output names ``platform`` and ``device_kind``.

Env knobs:
  R2D2_BENCH_SMOKE=1     tiny config, xla-decode spd=1 only; the one run
                         allowed on a CPU (the output says "platform": "cpu")
  R2D2_BENCH_SKIP=a,b    skip optional cells whose label contains a substring
  R2D2_BENCH_PLSTM_BT=   block_t values for the fused-LSTM cells (default 1,5)
  R2D2_BENCH_NHWC=1      re-measure the NHWC decode cell (a recorded dead end)
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

REFERENCE_SEQ_UPDATES_PER_SEC = 640.0  # ~5 train steps/s * batch 128 (see above)


def require_backend():
    """The devices this run measures on. A bench number is a chip number:
    without a TPU this exits non-zero, except for the explicit
    R2D2_BENCH_SMOKE=1 contract run, whose output is labelled
    ``"platform": "cpu"``."""
    import jax

    devs = jax.devices()
    print(f"backend: {devs[0].platform} x{len(devs)} "
          f"({devs[0].device_kind})", file=sys.stderr)
    if devs[0].platform != "tpu" and not os.environ.get("R2D2_BENCH_SMOKE"):
        print(f"bench: needs a TPU, found platform={devs[0].platform!r} "
              f"({devs[0].device_kind}). A CPU run measures nothing a user "
              "pays for; R2D2_BENCH_SMOKE=1 runs the tiny CPU contract check.",
              file=sys.stderr)
        sys.exit(1)
    return devs


def model_flops_per_step(cfg, action_dim: int, use_double: bool) -> float:
    """Analytic model FLOPs for one train step (fwd + bwd ~= 3x fwd MACs*2),
    counting the conv torso, FC, LSTM, and head matmuls over the full
    (batch x seq_window) unroll. Elementwise/decode/Adam FLOPs are noise
    against these and are not counted.

    The math lives in telemetry/costmodel.py (ONE source for this count,
    the roofline tool, and the cost-regression gate), reconciled against
    XLA ``cost_analysis()`` there: the first conv's input gradient is
    never computed (obs needs no grad — XLA DCEs it), which the pre-PR9
    count here overstated by 5-7% at the reference shape."""
    from r2d2_tpu.telemetry.costmodel import model_flops_per_step as _mfps
    return _mfps(cfg, action_dim, use_double)


def make_synthetic_block(spec, rng):
    # shared with tools/soak.py so bench and soak can never construct
    # divergent reference-shaped data
    from r2d2_tpu.replay.synthetic import make_synthetic_block as _mk
    return _mk(spec, rng)


def _last_loss(metrics):
    """Scalar loss from single-step ({} of scalars) or multi-step ((K,))."""
    loss = np.asarray(metrics["loss"])
    return float(loss.reshape(-1)[-1])


def measure_path(step, ts, rs, label: str, steps_per_dispatch: int = 1,
                 n_timed: int = 30):
    """Compile, warm up, and time one step function. Returns
    (train_steps_per_sec, ts, rs) — threading state through so all paths
    reuse the same filled replay ring."""
    import jax

    t0 = time.time()
    ts, rs, m = step(ts, rs)
    jax.block_until_ready(m["loss"])
    print(f"[{label}] compile + first step: {time.time()-t0:.1f}s "
          f"loss={_last_loss(m):.5f}", file=sys.stderr)

    for _ in range(3):  # warmup
        ts, rs, m = step(ts, rs)
    jax.block_until_ready(m["loss"])

    # TWO independent timing windows, not one: a transient stall inside a
    # single window silently corrupts the cell (one round-4 cell read 34x
    # under its real value). A stall can only make a window SLOWER, so when
    # the windows disagree the faster one is the measurement; agreement
    # combines both for the tighter estimate.
    rates = []
    for _ in range(2):
        t0 = time.time()
        for _ in range(n_timed // 2):
            ts, rs, m = step(ts, rs)
        jax.block_until_ready(m["loss"])
        rates.append((n_timed // 2) * steps_per_dispatch / (time.time() - t0))
    if max(rates) > 1.3 * min(rates):
        steps_per_sec = max(rates)
        print(f"[{label}] timing windows disagree "
              f"({rates[0]:.2f} vs {rates[1]:.2f} steps/s — transient "
              "stall?); taking the faster window", file=sys.stderr)
    else:
        steps_per_sec = sum(rates) / 2
    print(f"[{label}] {steps_per_sec:.2f} train steps/s; "
          f"loss={_last_loss(m):.5f}", file=sys.stderr)
    return steps_per_sec, ts, rs


def run_bench() -> None:
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    devs = require_backend()
    on_tpu = devs[0].platform == "tpu"
    smoke = bool(os.environ.get("R2D2_BENCH_SMOKE"))

    import jax

    from r2d2_tpu.config import Config
    from r2d2_tpu.learner import (
        create_train_state, make_learner_step, make_multi_learner_step)
    from r2d2_tpu.models import init_network
    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_obs_decode
    from r2d2_tpu.replay import ReplaySpec, replay_add, replay_init

    # reference-default training config; replay capacity trimmed to bound
    # bench setup time (25.6k steps of ring is plenty to sample 128 from)
    cfg = Config().replace(**{"replay.capacity": 25_600})
    if smoke:
        cfg = cfg.replace(**{
            "replay.capacity": 1_600, "replay.block_length": 400,
            "replay.batch_size": 8, "network.hidden_dim": 64,
            "network.cnn_out_dim": 64})
    spec = ReplaySpec.from_config(cfg)
    action_dim = 18  # full Atari action set

    net, _ = init_network(jax.random.PRNGKey(0), action_dim, cfg.network)
    ts = create_train_state(jax.random.PRNGKey(1), net, cfg.optim)
    rs = replay_init(spec)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for _ in range(spec.num_blocks):
        rs = replay_add(spec, rs, make_synthetic_block(spec, rng))
    jax.block_until_ready(rs.tree)
    print(f"filled {spec.num_blocks} blocks in {time.time()-t0:.1f}s",
          file=sys.stderr)

    use_double = cfg.network.use_double
    flops_per_step = model_flops_per_step(cfg, action_dim, use_double)
    # MFU denominator: the chip's dense bf16 peak from THE peak table (f32
    # cells are reported against it too — the MXU multiplies in bf16). A
    # TPU missing from the table raises there; no rate is made up.
    from r2d2_tpu.telemetry.costmodel import peak_spec
    peak = peak_spec(devs[0].device_kind)["flops_bf16"] if on_tpu else 0.0

    # static context for assemble_output
    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_setting
    bf16_resolved = resolve_pallas_setting(cfg.network.bf16, "network.bf16")
    s2d_default = resolve_pallas_setting(cfg.network.space_to_depth,
                                         "network.space_to_depth")
    ctx = {
        "default_label": (f"{'bf16' if bf16_resolved else 'f32'}"
                          f"_spd{cfg.runtime.resolved_steps_per_dispatch()}"
                          f"{'_s2d' if s2d_default else ''}"),
        "batch_size": spec.batch_size,
        "flops_per_step": flops_per_step,
        "peak": peak,
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        # what the default cell ACTUALLY measures on this backend: the
        # label string doesn't encode every knob (a flipped pallas_lstm
        # default still reads "bf16_spd16"), so the artifact spells the
        # resolved configuration out
        "defaults": {
            "bf16": bf16_resolved,
            "steps_per_dispatch": cfg.runtime.resolved_steps_per_dispatch(),
            "space_to_depth": s2d_default,
            "pallas_obs_decode": resolve_pallas_obs_decode(
                cfg.optim.pallas_obs_decode),
            "pallas_gather": spec.pallas_gather,
            "exact_gather": spec.exact_gather,
            "pallas_lstm": resolve_pallas_setting(
                cfg.network.pallas_lstm, "network.pallas_lstm"),
            "pallas_lstm_block": cfg.network.pallas_lstm_block,
        },
    }

    def build_step(use_pallas: bool, bf16: bool, spd: int, step_spec=None,
                   s2d: bool = False):
        opt = dataclasses.replace(
            cfg.optim, pallas_obs_decode="on" if use_pallas else "off")
        # s2d=True forces the rewrite on; otherwise the SHIPPED default
        # applies, so the matrix keeps describing the defaults if the
        # space_to_depth default ever flips
        netcfg = dataclasses.replace(
            cfg.network, bf16=bf16,
            space_to_depth="on" if s2d else cfg.network.space_to_depth)
        from r2d2_tpu.models import NetworkApply
        net_b = NetworkApply(action_dim, netcfg, cfg.env.frame_stack,
                             cfg.env.frame_height, cfg.env.frame_width)
        step_spec = step_spec or spec
        if spd == 1:
            return make_learner_step(net_b, step_spec, opt, use_double)
        return make_multi_learner_step(net_b, step_spec, opt, use_double, spd)

    results = {}
    matrix = {}
    # cell_status: a parallel per-cell map so the artifact is
    # self-describing — "not-run", "ok", "ok-reused", "anomaly" (value kept
    # but implausible — a stall or an early block_until_ready),
    # "mosaic-reject", "failed:<Type>", or "skipped:<reason>". Bare null
    # cells were indistinguishable across those cases (VERDICT r4).
    cell_status = {}
    # R2D2_BENCH_SKIP: comma-separated substrings of optional-cell labels to
    # skip on a rerun
    skip = [s for s in os.environ.get("R2D2_BENCH_SKIP", "").split(",") if s]

    def skipped(label):
        if any(s in label for s in skip):
            print(f"[{label}] skipped via R2D2_BENCH_SKIP", file=sys.stderr)
            cell_status[label] = "skipped:R2D2_BENCH_SKIP"
            return True
        return False

    def record(label, seq_per_sec):
        """Record a measured cell, classifying implausible values so they
        never read as clean measurements (round-4 f32_spd4=245 lesson)."""
        matrix[label] = seq_per_sec
        st = "ok"
        base = matrix.get("f32_spd1")
        if base and seq_per_sec < 0.3 * base:
            st = "anomaly"
            print(f"[{label}] ANOMALY: {seq_per_sec:.1f} seq/s < 0.3x the "
                  f"f32_spd1 base ({base:.1f}) — transient stall "
                  "suspected; disregard this cell", file=sys.stderr)
        if peak:
            mfu = seq_per_sec / spec.batch_size * flops_per_step / peak
            if mfu > 0.9:
                st = "anomaly"
                print(f"[{label}] ANOMALY: implied MFU {mfu:.2f} — early "
                      "block_until_ready suspected (round-3 hazard); "
                      "disregard this cell", file=sys.stderr)
        cell_status[label] = st

    def record_fail(label, e):
        matrix[label] = None
        msg = str(e)
        cell_status[label] = ("mosaic-reject"
                              if "osaic" in msg or "osaic" in type(e).__name__
                              else f"failed:{type(e).__name__}")
        print(f"[{label}] FAILED: {type(e).__name__}: {e}", file=sys.stderr)

    def mark_skip(label, reason):
        # don't clobber a more specific status (R2D2_BENCH_SKIP)
        if cell_status.get(label, "not-run") == "not-run":
            cell_status[label] = "skipped:" + reason

    def gate_reason():
        if smoke:
            return "smoke"
        if not on_tpu:
            return "needs-tpu"
        return "gated"

    # pre-seed every planned cell as None/"not-run" so the matrix always
    # lists every cell
    # the gather A/B cell measures whichever side is NOT the default spec
    spec_pad = dataclasses.replace(spec, exact_gather=not spec.exact_gather)
    ab_label = ("bf16_spd16_exactgather" if spec_pad.exact_gather
                else "bf16_spd16_rowgather")
    # R2D2_BENCH_PLSTM_BT: comma-separated block_t values to sweep in the
    # fused-LSTM section (timesteps per kernel grid iteration; must divide
    # seq_window=55). Parsed here so every swept cell is pre-seeded below.
    plstm_bts = [int(v) for v in os.environ.get(
        "R2D2_BENCH_PLSTM_BT", "1,5").split(",") if v]
    plstm_labels = ["bf16_spd16_plstm" if bt == 1
                    else f"bf16_spd16_plstm_bt{bt}" for bt in plstm_bts]
    if smoke:
        planned = ["f32_spd1"]
    else:
        planned = (["f32_spd1", "f32_spd4", "f32_spd16",
                    "bf16_spd1", "bf16_spd4", "bf16_spd16",
                    "bf16_spd16_s2d", ab_label, "bf16_spd16_nhwc"]
                   + plstm_labels
                   + ["bf16_spd16_double", "bf16_spd16_double_fused"])
    for label in planned:
        matrix[label] = None
        cell_status[label] = "not-run"

    # --- 1. decode A/B at the base config (f32, spd=1) ------------------
    for label, use_pallas in (("xla_decode", False), ("pallas_decode", True)):
        if use_pallas and (not on_tpu or smoke):
            results[label] = None
            reason = ("smoke mode measures the xla path only" if smoke else
                      f"pallas needs a TPU backend (have {devs[0].platform})")
            print(f"[{label}] skipped: {reason}", file=sys.stderr)
            continue
        step = build_step(use_pallas, bf16=False, spd=1)
        try:
            sps, ts, rs = measure_path(step, ts, rs, label)
            results[label] = sps * spec.batch_size
        except Exception as e:  # pallas lowering failure must not kill the bench
            if not use_pallas:
                raise
            results[label] = None
            print(f"[{label}] FAILED: {type(e).__name__}: {e}", file=sys.stderr)

    # default decode path for the matrix (auto: pallas on TPU)
    default_pallas = (resolve_pallas_obs_decode(cfg.optim.pallas_obs_decode)
                      and results.get("pallas_decode") is not None)

    # --- 1b. sample-gather A/B (gather_rows_pallas vs the XLA gather) ----
    # Part 1 ran with spec.pallas_gather auto-resolved (pallas on TPU); one
    # extra measurement with the gather forced off isolates its effect on
    # the full fused step.
    if on_tpu and not smoke and spec.pallas_gather:
        spec_xla_gather = dataclasses.replace(spec, pallas_gather=False)
        step = build_step(default_pallas, bf16=False, spd=1,
                          step_spec=spec_xla_gather)
        sps, ts, rs = measure_path(step, ts, rs, "xla_gather")
        results["xla_gather"] = sps * spec.batch_size
        results["pallas_gather"] = (results["pallas_decode"] if default_pallas
                                    else results["xla_decode"])
    else:
        results["xla_gather"] = results["pallas_gather"] = None

    # --- 2. perf matrix {f32, bf16} x {steps_per_dispatch 1, 4, 16} -----
    combos = [(False, 1)] if smoke else [
        (False, 1), (False, 4), (False, 16),
        (True, 1), (True, 4), (True, 16)]
    for bf16, spd in combos:
        label = f"{'bf16' if bf16 else 'f32'}_spd{spd}"
        if bf16 and not on_tpu:
            matrix[label] = None
            mark_skip(label, "needs-tpu")
            print(f"[{label}] skipped: bf16 matrix is a TPU measurement",
                  file=sys.stderr)
            continue
        if not bf16 and spd == 1:
            # identical configuration to the part-1 A/B winner — reuse the
            # measurement instead of paying another compile + timing window
            reused = (results["pallas_decode"] if default_pallas
                      else results["xla_decode"])
            matrix[label] = reused
            cell_status[label] = "ok-reused"
            print(f"[{label}] = {reused:.1f} seq/s (reused from part-1 A/B)",
                  file=sys.stderr)
            continue
        step = build_step(default_pallas, bf16, spd)
        sps, ts, rs = measure_path(step, ts, rs, label, steps_per_dispatch=spd)
        record(label, sps * spec.batch_size)
        if peak:
            mfu = sps * flops_per_step / peak
            print(f"[{label}] ~{sps * flops_per_step / 1e12:.1f} TFLOP/s "
                  f"model flops = {100*mfu:.1f}% of {peak/1e12:.0f} TFLOP/s "
                  "bf16 peak", file=sys.stderr)

    # --- 2b. fused-pallas-LSTM A/B at the bf16_spd16 policy -------------
    # network.pallas_lstm runs the 55-step recurrent chain as ONE pallas
    # kernel (Wh VMEM-resident, f32 scratch carries, custom-VJP backward —
    # ops/pallas_lstm.py) instead of a lax.scan while-loop, attacking the
    # profiled per-iteration overhead on the serial chain. Win -> flip the
    # default; Mosaic rejection -> documented dead end.
    # (plstm_bts / plstm_labels parsed up top so the sweep is pre-seeded)
    for bt, label in zip(plstm_bts, plstm_labels):
        if (on_tpu and not smoke and default_pallas
                and not skipped(label)):
            try:
                opt_default = dataclasses.replace(
                    cfg.optim, pallas_obs_decode="on")
                from r2d2_tpu.models import NetworkApply
                net_pl = NetworkApply(
                    action_dim, dataclasses.replace(
                        cfg.network, bf16=True, pallas_lstm="on",
                        pallas_lstm_block=bt),
                    cfg.env.frame_stack, cfg.env.frame_height,
                    cfg.env.frame_width)
                ts_pl = create_train_state(jax.random.PRNGKey(1), net_pl,
                                           cfg.optim)
                step = make_multi_learner_step(net_pl, spec, opt_default,
                                               use_double, 16)
                sps, _tspl, rs = measure_path(step, ts_pl, rs, label,
                                              steps_per_dispatch=16)
                record(label, sps * spec.batch_size)
            except Exception as e:   # never kill the bench for extra cells
                record_fail(label, e)
        else:
            matrix[label] = None
            mark_skip(label, gate_reason())

    # --- 2b2. exact-read pad-gather A/B at the bf16_spd16 policy ---------
    # replay.pallas_exact_gather pads stored frames (84x84 -> 96x128) and
    # DMAs only each sampled window (async copy) instead of the whole ring
    # row (~7.7x read amplification). It measured +4.2% and is now the TPU
    # default ("auto", builders, round 4) — so this cell measures the OTHER side
    # (exact_gather forced to the opposite of the default spec), keeping
    # the A/B in every artifact in case a chip generation shifts it.
    # Storage layout changes with the flag, so this cell builds its own
    # replay.
    if on_tpu and not smoke and not skipped(ab_label):
        try:
            rs_pad = replay_init(spec_pad)
            rng_pad = np.random.default_rng(0)
            for _ in range(spec_pad.num_blocks):
                rs_pad = replay_add(spec_pad, rs_pad,
                                    make_synthetic_block(spec_pad, rng_pad))
            jax.block_until_ready(rs_pad.tree)
            step = build_step(default_pallas, bf16=True, spd=16,
                              step_spec=spec_pad)
            ts_pg = create_train_state(jax.random.PRNGKey(1), net, cfg.optim)
            sps, _tspg, rs_pad = measure_path(step, ts_pg, rs_pad, ab_label,
                                              steps_per_dispatch=16)
            record(ab_label, sps * spec.batch_size)
            del rs_pad
        except Exception as e:   # never kill the bench for the extra cell
            record_fail(ab_label, e)
    else:
        matrix[ab_label] = None
        mark_skip(ab_label, gate_reason())

    # --- 2b3. space_to_depth A/B at the bf16_spd16 policy (the current
    # shipped TPU default; compare against that cell specifically) --------
    # The exact first-conv rewrite (network.space_to_depth) targets the
    # MXU's input-lane underutilization on the 4-channel frame stack. The
    # knob changes the param layout so its default stays explicit
    # ('off'/'on'); this cell measures what flipping it would buy so the
    # default can follow measurement (params differ, so this uses a fresh
    # train state — the throughput comparison is unaffected).
    if on_tpu and not smoke and not skipped("bf16_spd16_s2d"):
        try:
            from r2d2_tpu.models import NetworkApply
            opt_default = dataclasses.replace(
                cfg.optim,
                pallas_obs_decode="on" if default_pallas else "off")
            s2d_cfg = dataclasses.replace(cfg.network, bf16=True,
                                          space_to_depth="on")
            s2d_net = NetworkApply(action_dim, s2d_cfg, cfg.env.frame_stack,
                                   cfg.env.frame_height, cfg.env.frame_width)
            # ONE net builds both the train state and the step, so their
            # param trees cannot drift
            ts_s2d = create_train_state(jax.random.PRNGKey(1), s2d_net,
                                        cfg.optim)
            step = make_multi_learner_step(s2d_net, spec, opt_default,
                                           use_double, 16)
            sps, _ts2, rs = measure_path(step, ts_s2d, rs, "bf16_spd16_s2d",
                                         steps_per_dispatch=16)
            record("bf16_spd16_s2d", sps * spec.batch_size)
        except Exception as e:   # never kill the bench for the extra cell
            record_fail("bf16_spd16_s2d", e)
    else:
        matrix["bf16_spd16_s2d"] = None
        mark_skip("bf16_spd16_s2d", gate_reason())

    # --- 2b4. NHWC-decode A/B at the bf16_spd16 policy -------------------
    # optim.pallas_decode_layout="nhwc" folds the post-decode layout
    # transpose (the ~1.6 ms/step HBM copy in the round-3 profile) into
    # the kernel's in-register relayout. Win -> flip the default; Mosaic
    # rejection -> documented dead end.
    # default-SKIPPED: four distinct Mosaic rejections settled this as a
    # dead end on the round-4 stack (PERF.md). Re-enable with
    # R2D2_BENCH_NHWC=1 when the Mosaic version changes.
    if (on_tpu and not smoke and default_pallas
            and os.environ.get("R2D2_BENCH_NHWC")
            and not skipped("bf16_spd16_nhwc")):
        try:
            opt_nhwc = dataclasses.replace(
                cfg.optim, pallas_obs_decode="on",
                pallas_decode_layout="nhwc")
            from r2d2_tpu.models import NetworkApply
            net_n = NetworkApply(
                action_dim, dataclasses.replace(cfg.network, bf16=True),
                cfg.env.frame_stack, cfg.env.frame_height,
                cfg.env.frame_width)
            ts_n = create_train_state(jax.random.PRNGKey(1), net_n, cfg.optim)
            step = make_multi_learner_step(net_n, spec, opt_nhwc,
                                           use_double, 16)
            sps, _tsn, rs = measure_path(step, ts_n, rs, "bf16_spd16_nhwc",
                                         steps_per_dispatch=16)
            record("bf16_spd16_nhwc", sps * spec.batch_size)
        except Exception as e:   # never kill the bench for the extra cell
            record_fail("bf16_spd16_nhwc", e)
    else:
        matrix["bf16_spd16_nhwc"] = None
        mark_skip("bf16_spd16_nhwc",
                  gate_reason() if (not on_tpu or smoke)
                  else "dead-end; set R2D2_BENCH_NHWC=1 to re-measure")

    # --- 2c. double-DQN unroll-fusion A/B at the bf16_spd16 policy -------
    # use_double=True pays a SECOND 55-step recurrent unroll; sequential
    # (two XLA while-loops) vs interleaved-in-one-scan
    # (optim.fused_double_unroll, models/network.py dual_sequence_q). The
    # default config keeps use_double off (reference parity), so this pair
    # measures the double-DQN configuration's wall and what the fusion buys
    # — flip the fused_double_unroll default when the _fused cell wins.
    if on_tpu and not smoke:
        from r2d2_tpu.models import NetworkApply
        for label, fused in (("bf16_spd16_double", "off"),
                             ("bf16_spd16_double_fused", "on")):
            if skipped(label):
                matrix[label] = None
                continue
            try:
                opt_d = dataclasses.replace(
                    cfg.optim,
                    pallas_obs_decode="on" if default_pallas else "off",
                    fused_double_unroll=fused)
                net_d = NetworkApply(
                    action_dim,
                    dataclasses.replace(cfg.network, bf16=True,
                                        use_double=True),
                    cfg.env.frame_stack, cfg.env.frame_height,
                    cfg.env.frame_width)
                ts_d = create_train_state(jax.random.PRNGKey(1), net_d,
                                          cfg.optim)
                step = make_multi_learner_step(net_d, spec, opt_d,
                                               use_double=True,
                                               steps_per_dispatch=16)
                sps, _tsd, rs = measure_path(step, ts_d, rs, label,
                                             steps_per_dispatch=16)
                record(label, sps * spec.batch_size)
            except Exception as e:   # never kill the bench for extra cells
                record_fail(label, e)
    else:
        for label in ("bf16_spd16_double", "bf16_spd16_double_fused"):
            matrix[label] = None
            mark_skip(label, gate_reason())

    # --- report ----------------------------------------------------------
    # primary metric: what the SHIPPED defaults actually run — default
    # decode path, NetworkConfig.bf16, RuntimeConfig.steps_per_dispatch —
    # when that cell was measured; otherwise (smoke mode trims the matrix)
    # the best measured cell, reported under its own label so value and
    # measured_config always describe the same configuration. The full
    # matrix is attached so the defaults can be re-validated against the
    # measurements each round. matrix['f32_spd1'] is always populated (a
    # failed base measurement raises in part 1), so assemble_output never
    # returns None here.
    print(json.dumps(assemble_output(results, matrix, ctx, cell_status)))


def assemble_output(results: dict, matrix: dict, ctx: dict,
                    cell_status: dict = None):
    """Build the final JSON dict from measured cells + static context.
    Returns None when no comparable cell exists.

    ``cell_status`` makes the matrix self-describing (per cell: "ok",
    "ok-reused", "anomaly", "mosaic-reject", "failed:<Type>",
    "skipped:<reason>", "not-run"); absent it is synthesized from the
    values alone ("ok" / "unknown")."""
    if cell_status is None:
        cell_status = {k: ("ok" if v is not None else "unknown")
                       for k, v in matrix.items()}
    # anomalous values never elect the headline or best cell
    candidates = {k: v for k, v in matrix.items()
                  if v is not None and "_double" not in k
                  and cell_status.get(k) != "anomaly"}
    if not candidates:
        return None
    # _double cells are a different workload (a second unroll's FLOPs) —
    # comparable to each other, not to the default config's cells
    best_label = max(candidates, key=candidates.get)
    default_label = ctx["default_label"]
    # the default cell elects the headline only when its measurement is
    # clean — an anomaly-flagged default (round-4 f32_spd4 class) must not
    # become the artifact's value/vs_baseline/MFU
    measured_label = (default_label if default_label in candidates
                      else best_label)
    seq_updates = matrix[measured_label]

    def _r(key):
        v = results.get(key)
        return v and round(v, 1)

    out = {
        "metric": "learner_sequence_updates_per_sec_per_chip",
        "value": round(seq_updates, 1),
        "unit": "sequences/s",
        "vs_baseline": round(seq_updates / REFERENCE_SEQ_UPDATES_PER_SEC, 2),
        "measured_config": measured_label,
        "default_config": default_label,
        "best_config": best_label,
        "xla_decode": _r("xla_decode"),
        "pallas_decode": _r("pallas_decode"),
        "xla_gather": _r("xla_gather"),
        "pallas_gather": _r("pallas_gather"),
        "matrix": {k: v and round(v, 1) for k, v in matrix.items()},
        "cell_status": {k: cell_status.get(k, "unknown") for k in matrix},
        "platform": ctx["platform"],
        "device_kind": ctx["device_kind"],
    }
    if ctx.get("defaults"):
        out["resolved_defaults"] = ctx["defaults"]
    if ctx.get("peak"):
        steps_per_sec = seq_updates / ctx["batch_size"]
        out["model_tflops_per_sec"] = round(
            steps_per_sec * ctx["flops_per_step"] / 1e12, 1)
        out["mfu_vs_bf16_peak"] = round(
            steps_per_sec * ctx["flops_per_step"] / ctx["peak"], 4)
    return out


if __name__ == "__main__":
    run_bench()
