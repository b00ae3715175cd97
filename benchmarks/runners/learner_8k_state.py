"""Runner of the ``learner-8k-state`` mix: ``runners/learner_long.py``'s
loop, values and facts, with the comparison that decides ``correct`` widened
to the state the ``kda_mla_moe`` core carries.

``reference/check.py`` holds the program's Q and |td| to the reference at
one limit per compute type. Beside it, on the same sequences drawn from the
ring and the same parameters, the state row that the online window leaves,
by part: Kimi Delta Attention's state S, the convolutions' stored inputs
and the latent cache, each part's error the largest |program - reference|
over its largest |reference|, in the worst layer that has such a part. The
limit is one per compute type (``STATE``), and lies between what the sound
program reads on a TPU v5e and what its controls read: the chunks' carry
dropped, the decay ignored, and the products on 8-bit operands (PERF.md
gives the four readings).

All of it stands in the comparison's result under ``state`` (the run's
``reference:`` line); its ``ok``, which decides ``correct``, holds only
where ``check.py``'s limits and the state's hold.

``runners/learner.py`` takes its comparison from the module ``check``; this
runner hands it ``check_learner`` below for the run, and back after.
"""

import dataclasses
import types
from typing import Any, Dict

import numpy as np

from benchmarks.reference import check, r2d2_kimi
from benchmarks.runners import learner, learner_long

load_program = learner.load_program

# float32: the CPU's twin reads 1e-6 (tests/benchmarks/test_bm_kimi.py);
# bf16: at the cell's widths over a 2,048-position window on the CPU the
# sound program read at most 0.73% (the state) and its 8-bit twin at least
# 6.1% (the latent cache) in every part, so the limit lies a factor of three
# from each; PERF.md gives the chip's readings
STATE = {"float32": 1e-4, "bfloat16": 2.5e-2}


def program_row(net, spec, params, batch, use_pallas: bool) -> np.ndarray:
    """The state row (B, 2, half) the program's online window over
    ``batch`` leaves, from the forward the learner's loss runs."""
    import jax
    from r2d2_tpu.learner.train_step import _decode_inputs

    def forward(p, b):
        stacked, last_action = _decode_inputs(net, spec, b, use_pallas)
        (_, row), _ = net.module.apply(p, stacked, last_action, b.hidden,
                                       True, mutable=["moe", "core"])
        return row

    return np.asarray(jax.device_get(jax.jit(forward)(params, batch)))


def compare_state(row, reference: Dict[str, Any], core: Dict[str, Any],
                  dtype: str) -> Dict[str, Any]:
    """The program's state row against the reference's (the module's
    docstring): the worst layer's error of each part, its limit, and each
    layer's errors beside them."""
    got, want = (r2d2_kimi.parts(np.asarray(x, np.float64), core)
                 for x in (row, reference["state_row"]))
    per_layer = {
        name: [float(np.max(np.abs(g - w)) / max(np.max(np.abs(w)), 1e-30))
               for g, w in zip(got[name], want[name])]
        for name in want}
    return {"errors": {name: max(v) for name, v in per_layer.items()},
            "limits": {name: STATE[dtype] for name in per_layer},
            "per_layer": per_layer}


def widen(out: Dict[str, Any], found: Dict[str, Any]) -> Dict[str, Any]:
    """``check.compare``'s result ``out`` with ``compare_state``'s beside it
    under ``state``, and ``ok`` only where the limits of both hold."""
    return {**out, "state": found, "ok": bool(out["ok"] and all(
        found["errors"][k] <= found["limits"][k] for k in found["errors"]))}


def check_learner(learner_, reference_name: str, n: int, seed: int
                  ) -> Dict[str, Any]:
    """``check.check_learner``, widened by ``compare_state`` on the same
    ``n`` sequences (``check.sample_sequences`` draws them again from the
    same key)."""
    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_obs_decode

    program, reference, weights, dtype = check.program_and_reference(
        learner_, reference_name, n, seed)
    out = check.compare(program, reference, weights, check.TOLERANCE[dtype],
                        eta=learner_.cfg.optim.priority_eta)
    out["compute_dtype"] = dtype
    batch = check.sample_sequences(learner_, n, seed)
    spec = dataclasses.replace(learner_.spec,
                               batch_size=int(batch.idxes.shape[0]))
    row = program_row(
        learner_.net, spec, learner_.train_state.params, batch,
        resolve_pallas_obs_decode(learner_.cfg.optim.pallas_obs_decode))
    return widen(out, compare_state(
        row, reference, dataclasses.asdict(learner_.cfg.network.core), dtype))


def run(ctx) -> Dict[str, Any]:
    plain = learner.check
    learner.check = types.SimpleNamespace(check_learner=check_learner)
    try:
        return learner_long.run(ctx)
    finally:
        learner.check = plain
