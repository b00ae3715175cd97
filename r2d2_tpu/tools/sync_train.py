"""Deterministic synchronous collect:learn training — the bit-reproducible
single-stream loop.

With free-running actor threads the collect:learn interleaving — and the
learning outcome — swings with host scheduling (measured: the same config
scored eval returns anywhere in 25-86 across identical invocations,
PERF.md). This loop removes the scheduler from the result entirely: exactly
``replay.max_env_steps_per_train_step`` env steps per learner step, one
thread, seeds pinned — the same run twice is bit-identical.

Two consumers:
  * the learnability acceptance test (tests/test_learnability.py) — the CI
    stand-in for the reference's Atari Boxing curve
    (/root/reference/README.md:38-40);
  * the genetic search's ``--fitness-mode=sync`` (cli/genetic.py) — genome
    selection on a deterministic signal instead of scheduler noise.

The threaded/process orchestrations (runtime/orchestrator.py) remain the
production path; this is the measurement instrument.
"""

from typing import Sequence, Tuple

from r2d2_tpu.config import Config


def sync_train(cfg: Config, train_steps: int, collect_eps: float,
               seed: int = 0, param_refresh_interval: int = 10,
               deadline: float = None):
    """Train ``train_steps`` learner steps with synchronous collection at
    the pinned ``replay.max_env_steps_per_train_step`` ratio (must be set
    >= 1 in ``cfg``). Returns ``(net, learner)`` with the trained state.

    Deterministic given ``(cfg, seed)``: one env, one behavior policy at
    ``collect_eps``, refreshed from the learner every
    ``param_refresh_interval`` steps. ``deadline`` (a ``time.time()``
    value) raises TimeoutError when exceeded — a wall-clock escape hatch
    for oversized configs; note a run that hits it is no longer a
    deterministic function of the config alone.
    """
    import time
    from r2d2_tpu.actor.local_buffer import LocalBuffer
    from r2d2_tpu.actor.policy import ActorPolicy
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    ratio = int(cfg.replay.max_env_steps_per_train_step)
    if ratio < 1:
        raise ValueError(
            "sync_train needs replay.max_env_steps_per_train_step >= 1 "
            f"(got {cfg.replay.max_env_steps_per_train_step}) — the ratio "
            "IS the collection schedule here")
    if cfg.replay.placement != "device":
        raise ValueError(
            "sync_train requires replay.placement='device': the host "
            "placement's async prefetch/write-back threads sample "
            "concurrently with ingestion, which breaks the "
            "bit-reproducibility this loop exists to provide")
    env = create_env(cfg.env, seed=seed)
    net = NetworkApply(env.action_space.n, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net, seed=seed)
    policy = ActorPolicy(net, learner.train_state.params, collect_eps,
                         seed=seed)
    lb = LocalBuffer(learner.spec, policy.action_dim, cfg.optim.gamma,
                     cfg.optim.priority_eta)

    obs = env.reset()
    policy.observe_reset(obs)
    lb.reset(obs)

    def collect_one():
        nonlocal obs
        action, q, hidden = policy.act()
        next_obs, reward, done, _ = env.step(action)
        policy.observe(next_obs, action)
        lb.add(action, reward, next_obs, q, hidden)
        if done:
            learner.ingest(lb.finish(None))
            obs = env.reset()
            policy.observe_reset(obs)
            lb.reset(obs)
        elif len(lb) == learner.spec.block_length:
            learner.ingest(lb.finish(policy.bootstrap_q()))

    def check_deadline():
        if deadline is not None and time.time() > deadline:
            raise TimeoutError(
                f"sync_train exceeded its wall-clock bound at "
                f"{learner.training_steps}/{train_steps} steps")

    try:
        while not learner.ready:
            collect_one()
            check_deadline()
        while learner.training_steps < train_steps:
            for _ in range(ratio):      # exact collect:learn ratio
                collect_one()
            learner.step()
            if learner.training_steps % param_refresh_interval == 0:
                policy.update_params(learner.train_state.params)
            check_deadline()
    finally:
        env.close()    # every exit path — failing genomes must not leak fds
    return net, learner


def greedy_return(net, params, env_cfg, seed: int,
                  max_steps: int = 100_000) -> float:
    """One greedy (ε=0) episode's summed reward; deterministic given seed."""
    from r2d2_tpu.actor.policy import ActorPolicy
    from r2d2_tpu.envs.factory import create_env
    env = create_env(env_cfg, seed=seed)
    policy = ActorPolicy(net, params, epsilon=0.0, seed=seed)
    obs = env.reset()
    policy.observe_reset(obs)
    total, done, steps = 0.0, False, 0
    while not done and steps < max_steps:
        action, _, _ = policy.act()
        obs, reward, done, _ = env.step(action)
        policy.observe(obs, action)
        total += reward
        steps += 1
    env.close()
    return total


def sync_fitness(cfg: Config, train_steps: int,
                 eval_seeds: Sequence[int] = (123, 456),
                 collect_eps: float = 0.4, seed: int = 0,
                 max_seconds: float = None) -> float:
    """Deterministic fitness: sync-train then mean greedy return over
    ``eval_seeds``. The same ``(cfg, seeds)`` scores bit-identically.
    ``max_seconds`` bounds the whole evaluation (TimeoutError past it)."""
    import time

    import numpy as np
    deadline = time.time() + max_seconds if max_seconds else None
    net, learner = sync_train(cfg, train_steps, collect_eps, seed=seed,
                              deadline=deadline)
    # the next genome's Learner installs the process's compile monitor
    learner.stop_background()
    returns = []
    for s in eval_seeds:
        if deadline is not None and time.time() > deadline:
            raise TimeoutError("sync_fitness exceeded its wall-clock bound "
                               "during greedy evaluation")
        returns.append(
            greedy_return(net, learner.train_state.params, cfg.env, s))
    return float(np.mean(returns))
