"""Learner supervision + auto-resume (ISSUE 18 tentpole, rung c).

The actor fleet has had a supervisor since PR 3 (WorkerHealth: hang
watchdog, backoff ladder, crash-loop breaker) — but the LEARNER process
itself was the last single point of failure: an OOM, a preempted VM, or
a plain bug killed the whole run and a human had to relaunch with
``--runtime.resume=<path>`` by hand. This module closes that loop:

  * the training run becomes a CHILD process of a thin supervisor
    (``supervise_train``; ``cli/train.py`` routes here under
    ``runtime.auto_resume``);
  * a child that dies is relaunched from its newest checkpoint
    (``latest_checkpoint``) — with the snapshot plane on
    (``runtime.snapshot_interval``), the relaunch also restores the
    replay buffer contents, so learning resumes at most one snapshot
    interval behind where it died;
  * SIGTERM/SIGINT (preemption) forwards to the child, whose clean-stop
    path writes the final checkpoint + replay snapshot; the supervisor
    then exits WITHOUT relaunching — a preemption is not a crash;
  * repeated failures ride the SAME WorkerHealth policy the actor fleet
    uses (one slot, no heartbeat board): exponential backoff between
    relaunches, and the crash-loop breaker turns a doomed run into one
    loud error instead of an infinite relaunch mill.

The child's restart ordinal crosses the spawn boundary in the
``R2D2_SUPERVISOR_RESTARTS`` env var, which the learner's recovery
telemetry block surfaces — the ``recovery_loop`` alert rule reads it.

The child pid is published to ``{save_dir}/learner.pid`` (rewritten per
spawn) so the kill drill (tools/chaos.py --kill-learner) can SIGKILL the
actual training process, not the supervisor.
"""

import logging
import os
import signal
import time
from typing import Optional

log = logging.getLogger(__name__)

RESTARTS_ENV = "R2D2_SUPERVISOR_RESTARTS"


def _pid_path(save_dir: str) -> str:
    return os.path.join(save_dir or ".", "learner.pid")


def _child_entry(cfg_dict: dict, actor_mode: str,
                 max_steps: Optional[int], max_seconds: Optional[float],
                 restarts: int) -> None:
    """Spawn target for one training incarnation (module-level: the
    ``spawn`` start method pickles by reference). The restart ordinal is
    exported BEFORE the heavy imports so everything in the child —
    including the recovery telemetry block — sees it."""
    os.environ[RESTARTS_ENV] = str(restarts)
    from r2d2_tpu.config import Config
    from r2d2_tpu.runtime.orchestrator import train
    from r2d2_tpu.utils import enable_compile_cache, pin_platform
    pin_platform()
    enable_compile_cache()
    cfg = Config.from_dict(cfg_dict)

    def log_fn(record: dict) -> None:
        print(" | ".join(f"{k}={v}" for k, v in record.items()
                         if v is not None), flush=True)

    train(cfg, max_training_steps=max_steps, max_seconds=max_seconds,
          actor_mode=actor_mode, log_fn=log_fn)


def supervise_train(cfg, *, actor_mode: str = "process",
                    max_steps: Optional[int] = None,
                    max_seconds: Optional[float] = None) -> int:
    """Run training under supervision; returns the number of relaunches
    performed. Blocks until the run completes, a stop signal arrives, or
    the crash-loop breaker trips (which raises — a run that cannot stay
    up is an error, not a silent exit)."""
    import multiprocessing as mp

    from r2d2_tpu.runtime.checkpoint import latest_checkpoint
    from r2d2_tpu.runtime.feeder import WorkerHealth

    if cfg.mesh.multihost and cfg.mesh.num_processes > 1:
        raise NotImplementedError(
            "runtime.auto_resume supervises the single-host train() child; "
            "multihost jobs are supervised by their cluster scheduler — "
            "rely on runtime.resume + the rank-0 snapshot twin instead")

    ctx = mp.get_context("spawn")
    # ONE slot, no heartbeat board: the learner child has no heartbeat
    # row — liveness IS process liveness; the ladder/breaker knobs are
    # the same runtime.* fields the actor fleet uses
    health = WorkerHealth.from_runtime(1, None, cfg.runtime)
    save_dir = cfg.runtime.save_dir or "."
    # the checkpoint namespace this supervisor resumes from: player 0,
    # or the one player this job runs under per-player-job composition
    player = (cfg.multiplayer.player_id
              if (cfg.multiplayer.enabled and cfg.multiplayer.player_id >= 0)
              else 0)
    deadline = time.time() + max_seconds if max_seconds else None

    state = {"child": None, "stopping": False}

    def _forward(signum, frame):
        # preemption path: relay the stop to the child (whose clean-stop
        # path writes the final checkpoint + replay snapshot) and stop
        # relaunching — a requested stop is not a crash
        state["stopping"] = True
        child = state["child"]
        if child is not None and child.pid is not None:
            try:
                os.kill(child.pid, signal.SIGTERM)
            except (ProcessLookupError, OSError):
                pass

    prev_handlers = {}
    import threading
    if threading.current_thread() is threading.main_thread():
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev_handlers[sig] = signal.signal(sig, _forward)
            except (ValueError, OSError):
                pass

    cfg_dict = cfg.to_dict()
    restarts = 0
    pid_file = _pid_path(save_dir)
    try:
        while True:
            remaining = None
            if deadline is not None:
                remaining = deadline - time.time()
                if remaining <= 0:
                    break
            # the child is NOT a daemon: it spawns the actor fleet (a
            # daemonic process may not have children)
            child = ctx.Process(
                target=_child_entry,
                args=(cfg_dict, actor_mode, max_steps, remaining, restarts),
                name=f"learner-child-{restarts}")
            child.start()
            state["child"] = child
            os.makedirs(save_dir, exist_ok=True)
            with open(pid_file, "w") as f:
                f.write(str(child.pid))
            while child.is_alive():
                child.join(timeout=0.25)
            code = child.exitcode
            if state["stopping"]:
                log.info("supervisor: stop requested; child exited %s — "
                         "not relaunching", code)
                break
            if code == 0:
                break                       # run completed
            # crash: negative exitcode = killed by signal
            now = time.time()
            log.warning(
                "supervisor: learner child died (exitcode %s) after %d "
                "prior restart(s) — routing through relaunch", code,
                restarts)
            health.on_failure(0, now)
            if health.is_parked(0):
                raise RuntimeError(
                    f"learner crash-loop breaker tripped: "
                    f"{restarts + 1} failures within "
                    f"{cfg.runtime.restart_window_s:.0f}s — giving up "
                    f"(last exitcode {code})")
            while not health.respawn_due(0, time.time()):
                if state["stopping"]:
                    break
                time.sleep(0.05)
            if state["stopping"]:
                break
            health.on_spawn(0)
            restarts += 1
            # relaunch from the newest checkpoint; the restore path also
            # reloads the replay snapshot (runtime.restore_replay). No
            # checkpoint yet (died during warm-up) = fresh start.
            ckpt = latest_checkpoint(save_dir, cfg.env.game_name, player)
            cfg_dict = cfg.to_dict()
            cfg_dict["runtime"]["resume"] = ckpt or ""
            cfg_dict["runtime"]["pretrain"] = ""
            log.warning("supervisor: relaunch %d resuming from %s",
                        restarts, ckpt or "<no checkpoint — fresh start>")
    finally:
        child = state["child"]
        if child is not None and child.is_alive():
            child.terminate()
            child.join(timeout=10.0)
            if child.is_alive():
                child.kill()
                child.join(timeout=2.0)
        try:
            os.remove(pid_file)
        except OSError:
            pass
        for sig, handler in prev_handlers.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass
    return restarts
