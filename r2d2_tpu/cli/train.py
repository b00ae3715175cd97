"""Training CLI (ref /root/reference/train.py).

    python -m r2d2_tpu.cli.train --env.game_name=Fake --actor.num_actors=2
    python -m r2d2_tpu.cli.train --env.game_name=ALE/Boxing --env.env_type=-v5
    python -m r2d2_tpu.cli.train --multiplayer.enabled=true  # self-play stacks

    # fully on-device acting (Anakin): fused env+policy+emit scan colocated
    # with the learner — no actor fleet (README "On-device acting")
    python -m r2d2_tpu.cli.train --env.game_name=Grid --actor.on_device=true \
        --env.episode_len=120 --replay.block_length=40

Extra (non-config) flags:
    --actor-mode=thread|process   actor execution mode (default: process
                                  single-host, thread multihost)
    --max-steps=N                 stop after N learner steps
    --max-seconds=S               wall-clock bound
"""

import sys

from r2d2_tpu.config import Config, parse_overrides
from r2d2_tpu.runtime.orchestrator import train


def main(argv=None):
    """Run training; returns what ``train()`` returned (the player stacks,
    or None on the supervised / multihost routes) so a caller such as
    ``chip_smoke.py`` can check the run instead of trusting the exit."""
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    argv = list(sys.argv[1:] if argv is None else argv)
    actor_mode, max_steps, max_seconds = None, None, None
    rest = []
    for arg in argv:
        if arg.startswith("--actor-mode="):
            actor_mode = arg.split("=", 1)[1]
        elif arg.startswith("--max-steps="):
            max_steps = int(arg.split("=", 1)[1])
        elif arg.startswith("--max-seconds="):
            max_seconds = float(arg.split("=", 1)[1])
        else:
            rest.append(arg)
    cfg = parse_overrides(Config(), rest)

    def log(record: dict) -> None:
        print(" | ".join(f"{k}={v}" for k, v in record.items() if v is not None),
              flush=True)

    if cfg.runtime.auto_resume:
        # learner supervision (ISSUE 18): run train() as a supervised
        # child process — a crash relaunches from the newest checkpoint
        # (plus the replay snapshot under runtime.snapshot_interval);
        # SIGTERM/SIGINT forward to the child for a clean preemption
        # stop. Raises for multi-process multihost jobs (the cluster
        # scheduler supervises those).
        from r2d2_tpu.runtime.supervisor import supervise_train
        supervise_train(cfg, actor_mode=actor_mode or "process",
                        max_steps=max_steps, max_seconds=max_seconds)
        return

    if cfg.mesh.multihost and cfg.mesh.num_processes > 1:
        # multi-controller pod: run this same CLI on every host with its
        # own --mesh.process_id; the lockstep loop keeps dispatch cadences
        # identical across processes (parallel/multihost.py). Defaults to
        # thread-mode actors there; --actor-mode=process spawns CPU-pinned
        # actor processes fed through the shm ring instead.
        from r2d2_tpu.parallel.multihost import train_multihost
        train_multihost(cfg, max_training_steps=max_steps,
                        max_seconds=max_seconds,
                        actor_mode=actor_mode or "thread", log_fn=log)
        return

    return train(cfg, max_training_steps=max_steps, max_seconds=max_seconds,
                 actor_mode=actor_mode or "process", log_fn=log)


if __name__ == "__main__":
    main()
