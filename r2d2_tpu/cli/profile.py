"""Profile the fused learner step and print per-op time attribution.

    python -m r2d2_tpu.cli.profile --steps 20 --out /tmp/r2d2_prof
    python -m r2d2_tpu.cli.profile --summarize /tmp/r2d2_prof  # re-analyze

Config overrides apply as everywhere (--replay.batch_size=64 ...); the
defaults profile the reference-scale learner on the current backend
(SURVEY §5.1 — the reference has no profiling hooks at all).
"""

import argparse
import sys


def main(argv=None) -> None:
    from r2d2_tpu.utils import pin_platform
    pin_platform()
    argv = list(sys.argv[1:] if argv is None else argv)
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=20,
                   help="train steps inside the trace window")
    p.add_argument("--out", default="/tmp/r2d2_profile",
                   help="trace output directory (tensorboard-compatible)")
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--summarize", default=None, metavar="TRACE_DIR",
                   help="skip capture; summarize an existing trace dir")
    args, config_overrides = p.parse_known_args(argv)

    from r2d2_tpu.config import Config, parse_overrides
    from r2d2_tpu.tools.profile_step import (
        capture_step_trace, format_summary, summarize_trace,
        traced_step_count)

    trace_dir = args.summarize
    if trace_dir is not None and config_overrides:
        p.error(f"unrecognized arguments with --summarize: "
                f"{config_overrides} (config overrides only apply to "
                "capture runs)")
    if trace_dir is None:
        cfg = parse_overrides(Config(), config_overrides)
        if not any("replay.capacity" in str(o) for o in config_overrides):
            # a trimmed-but-realistic default capacity; an
            # explicit --replay.capacity override always wins
            cfg = cfg.replace(
                **{"replay.capacity": min(cfg.replay.capacity, 25_600)})
        trace_dir = capture_step_trace(cfg, args.steps, args.out)
        print(f"trace written to {trace_dir} (tensorboard --logdir works)",
              file=sys.stderr)
    steps = traced_step_count(trace_dir)
    if steps is None:
        steps = args.steps
        print(f"warning: no profile_meta.json in {trace_dir}; ms/step "
              f"assumes --steps={steps}", file=sys.stderr)
    summary = summarize_trace(trace_dir, top=args.top)
    print(format_summary(summary, steps))


if __name__ == "__main__":
    main()
