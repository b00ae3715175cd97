"""The plain float32 reference against the program's loss at a tiny size on
the CPU, in float32: single and double-Q, at both cells' window ratios. And
the comparison itself: a program computing a class below what it states fails
it, and a tied argmax under double-Q does not."""

import jax
import numpy as np
import pytest

from benchmarks import harness, traffic
from benchmarks.reference import check

BENCH = harness.load_benchmark()
POOL = {"pool_blocks": 4, "priority_range": [0.1, 2.0], "reward_scale": 1.0}
ACTION_DIM = 6


def _tiny_learner(tmp_path, config_name, seed=0, **extra):
    """The configuration's tiny CPU twin, its ring filled from the seed, and
    a target net that differs from the online one."""
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner

    overrides = harness.program_overrides(
        harness.config_doc(BENCH, config_name), {}, rehearse=True)
    cfg = harness.build_config({**overrides, "runtime.save_interval": 0,
                                **extra}, str(tmp_path), seed)
    net = NetworkApply(ACTION_DIM, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    learner = Learner(cfg, net, 0, seed=seed)
    traffic.fill_ring(learner, ACTION_DIM, POOL, seed)
    learner.train_state = learner.train_state.replace(
        target_params=net.init(jax.random.PRNGKey(seed + 99)))
    return learner


# Tolerance of the float32 comparison (benchmarks/reference/check.py): 5e-5
# of each output's largest magnitude. Both sides here are float32 on one
# backend and differ only in the order of summation, which measures <= 1e-6.
@pytest.mark.parametrize("config_name,double", [
    ("r2d2-ref", False), ("r2d2-ref", True),
    ("r2d2-paper", False), ("r2d2-paper", True)])
def test_program_loss_matches_reference_in_float32(tmp_path, config_name,
                                                   double):
    learner = _tiny_learner(tmp_path, config_name,
                            **{"network.use_double": double})
    try:
        out = check.check_learner(learner, "r2d2", 8, seed=7)
    finally:
        learner.stop_background()
    assert out["compute_dtype"] == "float32"
    assert out["tolerance"] == check.TOLERANCE["float32"] == 5e-5
    assert out["ok"], out
    assert max(out["errors"].values()) < 5e-6, out["errors"]
    assert out["valid_steps"] > 0 and out["stable_steps"] > 0
    # the windows keep the cells' ratios: 40+10+5 and 40+80+5
    seq = learner.cfg.sequence
    want = {"r2d2-ref": (8, 2, 1), "r2d2-paper": (8, 16, 1)}[config_name]
    assert (seq.burn_in_steps, seq.learning_steps, seq.forward_steps) == want


def test_lower_precision_than_stated_fails(tmp_path):
    """A bf16 program passes the bf16 tolerance and fails the float32 one: a
    cell whose configuration states float32 and computes in bf16 is caught."""
    learner = _tiny_learner(tmp_path, "r2d2-paper", **{"network.bf16": "on"})
    try:
        program, reference, weights, dtype = check.program_and_reference(
            learner, "r2d2", 8, seed=7)
    finally:
        learner.stop_background()
    assert dtype == "bfloat16"
    as_stated = check.compare(program, reference, weights,
                              check.TOLERANCE["bfloat16"])
    as_float32 = check.compare(program, reference, weights,
                               check.TOLERANCE["float32"])
    assert as_stated["ok"], as_stated
    assert not as_float32["ok"]
    assert as_float32["errors"]["q_chosen"] > 10 * check.TOLERANCE["float32"]


def _outputs(q, td, tie_gap):
    valid = np.ones_like(q)
    w = np.ones(q.shape[0])
    return {"loss": 0.5 * np.sum(td ** 2) / valid.sum(),
            "priorities": 0.9 * td.max(1) + 0.1 * td.mean(1), "q_chosen": q,
            "abs_td": td, "valid": valid, "tie_gap": tie_gap}, w


def test_a_tied_argmax_is_read_apart_and_a_wrong_value_is_not():
    q = np.array([[1.0, -2.0], [0.5, 0.25]])
    td = np.array([[0.3, 0.1], [0.2, 0.4]])
    gap = np.array([[1.0, 1.0], [1.0, 1e-9]])      # the last step is a tie
    reference, w = _outputs(q, td, gap)
    flipped = td.copy()
    flipped[1, 1] = 0.9                 # another action's target swapped in
    program, _ = _outputs(q, flipped, gap)
    out = check.compare(program, reference, w, 1e-3)
    assert out["ok"] and out["stable_steps"] == 3, out
    wrong = td.copy()
    wrong[0, 0] = 0.35                  # a stable step is off
    program, _ = _outputs(q, wrong, gap)
    assert not check.compare(program, reference, w, 1e-3)["ok"]
    program, _ = _outputs(q, td, gap)
    program["loss"] *= 1.01             # the reduction itself is off
    assert not check.compare(program, reference, w, 1e-3)["ok"]
    program, _ = _outputs(q, td, gap)
    program["q_chosen"] = q + np.nan
    assert not check.compare(program, reference, w, 1e-3)["ok"]


def test_a_small_td_does_not_magnify_the_rounding_of_q():
    """A trained agent: |td| is a hundredth of |Q|. An error of 5e-4 |Q| in
    each is inside 1e-3 of |Q|, though it is 5% of |td| (what failed two of
    five runs of ``r2d2-ref.anakin`` on the chip before errors were put in
    units of |Q|)."""
    q = np.array([[1.0, -0.8], [0.9, 0.7]])
    td = np.array([[0.010, 0.008], [0.009, 0.007]])
    gap = np.full_like(q, np.inf)
    reference, w = _outputs(q, td, gap)
    program, _ = _outputs(q + 5e-4, td + 5e-4, gap)
    out = check.compare(program, reference, w, 1e-3)
    assert out["ok"], out
    assert out["errors"]["abs_td"] == pytest.approx(5e-4)
    program, _ = _outputs(q, td + 5e-3, gap)       # 5e-3 |Q| is not
    assert not check.compare(program, reference, w, 1e-3)["ok"]
