"""The comparison that decides ``correct``: the program's loss against the
configuration's plain reference on a few sequences drawn from the ring it
trains on. The reference is the module beside this file that the
configuration names (``"reference": "r2d2"``); its ``from_config(cfg)``
returns ``fn(params, target_params, batch fields)``, which gives ``loss``,
``priorities``, ``q_chosen``, ``abs_td``, ``valid`` and ``tie_gap``.

Every error is an absolute difference in units of the batch's largest |Q|
(``q_scale``). Q itself is held to the tolerance. |td| = |target - Q| is a
difference of two values that each carry Q's error (the target is
h(r + gamma h^-1(Q')), whose slope in Q' is about gamma), so |td|, and the
priorities and the loss made of it, are held to twice the tolerance in the
same units; measuring them against their own size instead would let a trained
agent's small |td| magnify Q's rounding (it did: PERF.md, Findings, PR 22).

Tolerances:

  * float32 program: 5e-5. Unit round-off is 6e-8; dot products of up to
    3,136 terms and a 55-to-125-step recurrence in another order of
    summation measure about 1e-6 (``tests/benchmarks/test_bm_reference.py``).
    A bf16 program is off by about 1e-2 and fails this by two orders.
  * bf16 program (what ``network.bf16=auto`` resolves to on a TPU: bf16
    operands and activations, float32 accumulation inside a product): 5e-2.
    bf16 rounds to 8 bits (relative step 2^-8 = 3.9e-3) at every layer and
    at every step of the recurrent state; the chip measured 0.3e-2 to 1.4e-2
    on Q over 55 runs of three cells, typically 0.4e-2 to 1.0e-2 (PERF.md,
    Findings, PR 22), so this is 3.5 times the worst seen. Arithmetic a class
    below (8-bit floats, 3 or 2 mantissa bits, a step 16 or 32 times coarser)
    lands near 1.5e-1 and fails, and so does a product that accumulates in
    bf16 over thousands of terms (about 1e-1).
"""

import dataclasses
from typing import Any, Dict

import numpy as np

from benchmarks.harness import load_named

TOLERANCE = {"float32": 5e-5, "bfloat16": 5e-2}


def sample_sequences(learner, n: int, seed: int):
    """``n`` sequences (``n`` from every shard of a sharded ring) drawn by
    the program's own ``replay_sample`` from the learner's ring, fetched to
    the host in storage types. The ring is read in place, never copied."""
    import jax
    from r2d2_tpu.replay.device_replay import replay_sample

    spec = dataclasses.replace(learner.spec, batch_size=n)
    key = jax.random.PRNGKey(seed)
    if learner.mesh is None:
        return jax.device_get(replay_sample(spec, learner.replay_state, key))

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def local(state, k):
        shard = jax.tree_util.tree_map(lambda x: x[0], state)
        me = jax.lax.axis_index("dp")
        out = replay_sample(spec, shard, jax.random.fold_in(k, me))
        return jax.tree_util.tree_map(lambda x: x[None], out)

    sampled = jax.jit(shard_map(local, mesh=learner.mesh,
                                in_specs=(P("dp"), P()), out_specs=P("dp"),
                                check_vma=False))(learner.replay_state, key)
    return jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), jax.device_get(sampled))


def _error(got, want, scale: float, where=None) -> float:
    """Largest |got - want| (where ``where``) in units of ``scale``."""
    diff = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    if where is not None:
        diff = diff[where]
    return float(np.max(diff) / scale) if diff.size else 0.0


def compare(program: Dict[str, Any], reference: Dict[str, Any],
            is_weights, tolerance: float, eta: float = 0.9) -> Dict[str, Any]:
    """Errors of the program's outputs against the reference's, and whether
    all are inside their limits (the module's docstring says which).

    Q on the valid learning steps and |td| are compared step by step. Steps
    whose bootstrap action is a near tie (the reference's ``tie_gap`` is
    within what the tolerance allows two Q values to move, 2 x tolerance x
    ``q_scale``) are left out of what depends on that action: |td|, and the
    loss and the priorities, which are re-made over the other steps from each
    side's own |td|. That the program's own loss and priorities are the stated
    reductions of its own |td| over all valid steps is held apart, to float32
    rounding (``*_reduction``)."""
    valid = np.asarray(reference["valid"]) > 0
    q_scale = float(np.max(np.abs(np.asarray(reference["q_chosen"])[valid])))
    stable = valid & (np.asarray(reference["tie_gap"])
                      > 2.0 * tolerance * q_scale)
    w = np.asarray(is_weights, np.float64)[:, None]

    def reductions(abs_td, where):
        td = np.where(where, np.asarray(abs_td, np.float64), 0.0)
        count = np.maximum(where.sum(axis=1), 1)
        loss = 0.5 * np.sum(w * td ** 2) / max(valid.sum(), 1)
        return loss, eta * td.max(axis=1) + (1.0 - eta) * td.sum(axis=1) / count

    own_loss, own_prio = reductions(program["abs_td"], valid)
    got_loss, got_prio = reductions(program["abs_td"], stable)
    want_loss, want_prio = reductions(reference["abs_td"], stable)
    td_ref = np.where(stable, np.asarray(reference["abs_td"], np.float64), 0.0)
    # d(0.5 w td^2) = w td d(td): the loss moves by mean(w |td|) per unit |td|
    loss_unit = max(np.sum(w * td_ref) / max(valid.sum(), 1), 1e-30) * q_scale
    errors = {
        "q_chosen": _error(program["q_chosen"], reference["q_chosen"],
                           q_scale, valid),
        "abs_td": _error(program["abs_td"], reference["abs_td"], q_scale,
                         stable),
        "priorities": _error(got_prio, want_prio, q_scale),
        "loss": _error(got_loss, want_loss, loss_unit),
        "loss_reduction": _error(program["loss"], own_loss,
                                 max(abs(own_loss), 1e-30)),
        "priority_reduction": _error(program["priorities"], own_prio,
                                     max(float(np.max(own_prio)), 1e-30)),
    }
    finite = all(np.isfinite(np.asarray(program[k])).all()
                 for k in ("loss", "priorities", "q_chosen", "abs_td"))
    limits = {"q_chosen": tolerance, "abs_td": 2 * tolerance,
              "priorities": 2 * tolerance, "loss": 2 * tolerance,
              "loss_reduction": 1e-5, "priority_reduction": 1e-5}
    return {
        "ok": bool(finite and all(errors[k] <= limits[k] for k in errors)),
        "errors": errors, "limits": limits, "tolerance": tolerance,
        "finite": bool(finite),
        "q_scale": q_scale, "td_scale": float(np.max(td_ref)),
        "sequences": int(valid.shape[0]),
        "stable_steps": int(stable.sum()), "valid_steps": int(valid.sum()),
    }


def program_and_reference(learner, reference_name: str, n: int, seed: int):
    """Draw ``n`` sequences from ``learner``'s ring; return what the
    program's loss (``make_loss_fn``, as the train step calls it, with the
    learner's own parameters) and the reference ``reference_name`` make of
    them, the batch's importance weights, and the program's compute type."""
    import jax
    from r2d2_tpu.learner.train_step import make_loss_fn
    from r2d2_tpu.replay.structs import SampleBatch

    cfg, net = learner.cfg, learner.net
    double = cfg.network.use_double
    batch = sample_sequences(learner, n, seed)
    spec = dataclasses.replace(learner.spec,
                               batch_size=int(batch.idxes.shape[0]))
    params = jax.device_get(learner.train_state.params)
    target = jax.device_get(learner.train_state.target_params)

    loss, aux = jax.jit(make_loss_fn(net, spec, cfg.optim, double))(
        params, target, batch)
    program = jax.device_get({"loss": loss, **{
        k: aux[k] for k in ("priorities", "q_chosen", "abs_td")}})

    fields = {f.name: getattr(batch, f.name)
              for f in dataclasses.fields(SampleBatch)
              if getattr(batch, f.name) is not None}
    ref_fn = load_named("reference", reference_name).from_config(cfg)
    reference = jax.device_get(ref_fn(params, target, fields))
    dtype = "bfloat16" if net.config.bf16 else "float32"
    return program, reference, batch.is_weights, dtype


def check_learner(learner, reference_name: str, n: int, seed: int
                  ) -> Dict[str, Any]:
    """Hold the program's loss on ``n`` sequences of ``learner``'s ring to
    the reference, at the tolerance of the compute type the program states."""
    program, reference, weights, dtype = program_and_reference(
        learner, reference_name, n, seed)
    out = compare(program, reference, weights, TOLERANCE[dtype],
                  eta=learner.cfg.optim.priority_eta)
    out["compute_dtype"] = dtype
    return out
