"""Production-scale soak: sustained training at reference scale on one chip.

The e2e test suite runs at toy shapes (24x24 frames, capacity 800); this
drives the DEFAULT configuration — capacity 500k env steps at 84x84x4,
exact-gather padded storage, bf16 + pallas + spd16 on TPU — through a
sustained window (default 30 min) and reports what a production deployment
would hit (VERDICT r4 #3):

  * replay_init at full capacity (the HBM guard refuses with numbers
    instead of OOMing if the ring cannot fit);
  * a FULL ring fill + wrap before training (ring-lap correctness at
    scale), then continuous ingestion at the reference's collect:learn
    ratio so the ring keeps wrapping during training;
  * steps/s sampled per minute — steady-state drift after the wrap is the
    headline ("post-wrap slowdown" would indicate fragmentation/layout
    trouble);
  * device memory stats at init / after fill / end (peak bytes in use);
  * checkpoint cadence: full orbax saves on a wall-clock interval,
    timed.

Ingestion uses a device-resident synthetic block re-added with varying
priorities (one host->device transfer total): the soak measures the
DEVICE side — ring behavior, HBM, steady-state step time — not actor
throughput, which the orchestrator/chaos tests cover.

Reference analog: the reference trains multi-day runs at this capacity
(/root/reference/config.py, /root/reference/worker.py:40-43); it publishes
no soak artifact. Output: one JSON line, machine-readable.
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np


def _mem_stats():
    # the ONE memory_stats wrapper (telemetry/resources.py), same
    # backend-optional fallback this helper always had: {} on CPU or when
    # the call raises, the summary byte counters otherwise
    from r2d2_tpu.telemetry.resources import (SUMMARY_KEYS,
                                              device_memory_stats)
    return device_memory_stats(keys=SUMMARY_KEYS)


def run_soak(duration_s: float = 1800.0, capacity: int = 500_000,
             checkpoint_interval_s: float = 300.0,
             save_dir: str = "/tmp/r2d2_soak",
             config_overrides: dict = None) -> dict:
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    import jax

    from r2d2_tpu.config import Config
    from r2d2_tpu.learner import create_train_state
    from r2d2_tpu.learner.train_step import (make_learner_step,
                                             make_multi_learner_step)
    from r2d2_tpu.models import NetworkApply
    from r2d2_tpu.replay import ReplaySpec, replay_add, replay_init
    from r2d2_tpu.replay.device_replay import replay_size
    from r2d2_tpu.replay.synthetic import make_synthetic_block
    from r2d2_tpu.runtime.checkpoint import save_checkpoint

    overrides = {"replay.capacity": capacity, "runtime.save_dir": save_dir}
    overrides.update(config_overrides or {})
    cfg = Config().replace(**overrides)
    spec = ReplaySpec.from_config(cfg)
    action_dim = 18                         # full Atari action set
    dev = jax.devices()[0]
    out = {"metric": "soak", "device_kind": dev.device_kind,
           "platform": dev.platform, "capacity": capacity,
           "num_blocks": spec.num_blocks,
           "exact_gather": bool(spec.exact_gather),
           "ring_gib": round(spec.device_ring_bytes / 2**30, 2),
           "duration_target_s": duration_s}
    print(f"soak: {dev.platform} ({dev.device_kind}), ring "
          f"{out['ring_gib']} GiB over {spec.num_blocks} blocks, "
          f"exact_gather={spec.exact_gather}", file=sys.stderr)

    # --- init (the HBM guard fires here on an oversized ring) -----------
    t0 = time.time()
    rs = replay_init(spec)
    jax.block_until_ready(rs.tree)
    out["init_s"] = round(time.time() - t0, 1)
    out["mem_after_init"] = _mem_stats()

    # --- one full ring lap BEFORE training ------------------------------
    # one host block, device-committed once; re-adds vary only priorities
    # (jitted in replay_add) so the fill is dispatch-bound, not
    # host-transfer-bound
    rng = np.random.default_rng(0)
    block = jax.device_put(make_synthetic_block(spec, rng))
    t0 = time.time()
    wrap_extra = max(2, spec.num_blocks // 50)
    for i in range(spec.num_blocks + wrap_extra):
        rs = replay_add(spec, rs, block)
        if i % 200 == 0:            # bound the in-flight dispatch queue
            jax.block_until_ready(rs.tree)
    jax.block_until_ready(rs.tree)
    out["fill_s"] = round(time.time() - t0, 1)
    out["ring_laps_fill"] = round(
        (spec.num_blocks + wrap_extra) / spec.num_blocks, 3)
    # OBSERVED wrap evidence (not derived from the loop bounds): a full
    # buffer and a pointer that came back around the ring
    out["buffer_steps_after_fill"] = int(replay_size(rs))
    out["block_ptr_after_fill"] = int(rs.block_ptr)
    out["mem_after_fill"] = _mem_stats()
    print(f"soak: ring filled+wrapped in {out['fill_s']}s "
          f"(buffer={out['buffer_steps_after_fill']} steps, "
          f"ptr={out['block_ptr_after_fill']})", file=sys.stderr)

    # --- steady-state training with interleaved ingestion ---------------
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    ts = create_train_state(jax.random.PRNGKey(0), net, cfg.optim)
    spd = cfg.runtime.resolved_steps_per_dispatch()
    if spd > 1:
        step = make_multi_learner_step(net, spec, cfg.optim,
                                       cfg.network.use_double, spd)
    else:
        step = make_learner_step(net, spec, cfg.optim, cfg.network.use_double)

    t0 = time.time()
    ts, rs, m = step(ts, rs)
    jax.block_until_ready(m["loss"])
    out["compile_s"] = round(time.time() - t0, 1)

    # ingestion cadence at the reference collect:learn shape: one block
    # (block_length env steps) per block_length/ratio train steps
    ratio = max(float(cfg.replay.max_env_steps_per_train_step), 1.0)
    dispatches_per_add = max(1, int(round(
        cfg.replay.block_length / ratio / spd)))

    start = time.time()
    deadline = start + duration_s
    next_minute = start + 60.0
    next_ckpt = start + checkpoint_interval_s
    timeline = []                 # per-minute steps/s
    ckpt_times = []
    adds = dispatches = 0
    window_dispatches = 0
    window_t0 = start
    losses = []
    while time.time() < deadline:
        ts, rs, m = step(ts, rs)
        dispatches += 1
        window_dispatches += 1
        if dispatches % dispatches_per_add == 0:
            rs = replay_add(spec, rs, block)
            adds += 1
        if dispatches % 25 == 0:  # bound the dispatch queue + sample loss
            jax.block_until_ready(m["loss"])
            losses.append(float(np.asarray(m["loss"]).reshape(-1)[-1]))
        now = time.time()
        if now >= next_minute:
            jax.block_until_ready(m["loss"])
            now = time.time()
            timeline.append(round(
                window_dispatches * spd / (now - window_t0), 1))
            window_t0, window_dispatches = now, 0
            next_minute += 60.0
            print(f"soak: minute {len(timeline)}: "
                  f"{timeline[-1]} steps/s", file=sys.stderr)
        if now >= next_ckpt:
            tck = time.time()
            save_checkpoint(save_dir, cfg.env.game_name,
                            len(ckpt_times) + 1, 0, ts.params, ts.opt_state,
                            ts.target_params, int(ts.step),
                            adds * cfg.replay.block_length,
                            config_json=cfg.to_json())
            ckpt_times.append(round(time.time() - tck, 1))
            next_ckpt += checkpoint_interval_s
    jax.block_until_ready(m["loss"])
    total = time.time() - start

    out["train_s"] = round(total, 1)
    out["train_steps"] = dispatches * spd
    out["steps_per_sec_mean"] = round(dispatches * spd / total, 1)
    out["steps_per_sec_timeline"] = timeline
    out["ring_laps_train"] = round(adds / spec.num_blocks, 3)
    out["checkpoint_save_s"] = ckpt_times
    out["losses_sampled"] = [round(x, 4) for x in losses[-5:]]
    out["mem_end"] = _mem_stats()
    if len(timeline) >= 4:
        first = np.mean(timeline[:2])
        last = np.mean(timeline[-2:])
        out["steady_state_drift_pct"] = round(100 * (last - first) / first, 2)
    return out


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seconds", type=float,
                   default=float(os.environ.get("R2D2_SOAK_SECONDS", 1800)))
    p.add_argument("--capacity", type=int, default=500_000)
    p.add_argument("--checkpoint-interval", type=float, default=300.0)
    p.add_argument("--save-dir", default="/tmp/r2d2_soak")
    p.add_argument("--override", action="append", default=[],
                   help="dotted config override key=value (repeatable)")
    def _env_float(name, fallback):
        try:
            return float(os.environ.get(name) or fallback)
        except ValueError:
            return fallback

    p.add_argument("--e2e-seconds", type=float,
                   default=_env_float("R2D2_SOAK_E2E_SECONDS", 0.0),
                   help="also run the end-to-end actors→learner throughput "
                        "phase (tools/e2e_bench.py: process-mode vector "
                        "actors feeding the real learner; reports "
                        "env-steps/s and learner steps/s together); 0 = off")
    p.add_argument("--e2e-envs-per-actor", type=int, default=16)
    p.add_argument("--chaos-seconds", type=float,
                   default=_env_float("R2D2_SOAK_CHAOS_SECONDS", 0.0),
                   help="also run the chaos phase (tools/chaos.py): train "
                        "with injected crash-loop + hang faults and report "
                        "what supervision did (restarts, hang detections, "
                        "breaker trips) alongside proof training kept "
                        "advancing; 0 = off")
    p.add_argument("--chaos-actor-mode", choices=("thread", "process"),
                   default="process")
    args = p.parse_args(argv)
    overrides = {}
    for ov in args.override:
        k, _, v = ov.partition("=")
        try:                       # JSON value where it parses (numbers,
            overrides[k] = json.loads(v)   # lists, booleans) ...
        except (json.JSONDecodeError, ValueError):
            overrides[k] = v       # ... plain string otherwise ("tennis")
    out = run_soak(args.seconds, args.capacity, args.checkpoint_interval,
                   args.save_dir, overrides)
    if args.e2e_seconds > 0:
        # system-level phase AFTER the device soak: the chip is released by
        # then, and a failure here must not lose the soak numbers
        from r2d2_tpu.tools.e2e_bench import run_e2e
        try:
            # same --override set as the soak phase (user overrides beat
            # run_e2e's CPU-reduced defaults), so an on-TPU soak can run
            # the e2e phase at the reference training shape
            out["e2e"] = run_e2e(args.e2e_seconds,
                                 envs_per_actor=args.e2e_envs_per_actor,
                                 overrides=overrides)
        except Exception as e:     # pragma: no cover - defensive
            out["e2e"] = {"error": repr(e)}
    if args.chaos_seconds > 0:
        # chaos phase LAST, same failure isolation as the e2e phase: a
        # wedged fault-injection run must not lose the soak numbers
        from r2d2_tpu.tools.chaos import run_chaos
        try:
            out["chaos"] = run_chaos(args.chaos_seconds,
                                     actor_mode=args.chaos_actor_mode)
        except Exception as e:     # pragma: no cover - defensive
            out["chaos"] = {"error": repr(e)}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
