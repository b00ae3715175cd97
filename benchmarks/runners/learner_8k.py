"""Runner of the ``learner-8k`` mix: ``runners/learner_long.py``'s loop,
values and facts, with the comparison that decides ``correct`` widened to
what the learned sparse attention adds (the ``sparse_attn_moe`` core).

``reference/check.py`` holds the program's Q and |td| to the reference at
one limit per compute type. At random weights a query's attention over
thousands of keys is near-uniform, so which 2,048 keys it reads moves Q less
than bf16's rounding does: a program that attends every key it sees, or one
whose products take 8-bit operands, passes that limit (PERF.md, section 7).
Beside it, on the same sequences drawn from the ring and the same
parameters, the online window's ``choice``: the (query, key) pairs that one
side chose and the other did not, as a share of the pairs the reference's
float32 ``lax.top_k`` chose, in the worst layer. Each query that sees more
than ``index_topk`` keys chooses exactly that many on both sides, so a pair
the program misses is one it chose instead: the share is twice the pairs it
missed. Its limit is one per compute type, as ``check.py``'s, and in bf16
lies between what the sound program reads on a TPU v5e and what it reads
with its products on 8-bit operands (``CHOICE``; PERF.md, section 7, gives
both readings).

Read beside it, with no limit: per layer the share of the reference's pairs
the program chose too, and the indexer loss (its KL's mean over the
learning positions, ``r2d2_keye.learning_mean`` on both sides), the
program's and the reference's, with the worst layer's relative error. On
a TPU v5e 8-bit operands move that loss no more than bf16 does, so no limit
on it lies between the two readings. All of it stands in the comparison's
result under ``choice`` (the run's ``reference:`` line); its ``ok``, which
decides ``correct``, holds only where ``check.py``'s limits and the
choice's hold.

``runners/learner.py`` takes its comparison from the module ``check``; this
runner hands it ``check_learner`` below for the run, and back after.
"""

import dataclasses
import types
from typing import Any, Dict, Tuple

import numpy as np

from benchmarks.reference import check, r2d2_keye
from benchmarks.runners import learner, learner_long

load_program = learner.load_program

# float32: the CPU's twin chooses the reference's pairs key for key
# (tests/benchmarks/test_bm_keye.py); bf16, on one TPU v5e: the sound
# program read 0.77 to 0.96% over seven draws, its 8-bit twin 5.4%, the
# program that attends every key it sees 132%
CHOICE = {"float32": 1e-3, "bfloat16": 2.5e-2}


def program_choice(net, spec, params, batch, use_pallas: bool
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """The program's online window over ``batch``: the keys each query
    chose (layers, B, T, M + T) and the indexer's loss at each position
    (layers, B, T), from the forward the learner's loss runs (what the core
    sows, ``intermediates`` and ``core``)."""
    import jax
    from r2d2_tpu.learner.train_step import _decode_inputs
    from r2d2_tpu.models.cores.experts import moe_counters

    def forward(p, b):
        stacked, last_action = _decode_inputs(net, spec, b, use_pallas)
        _, sown = net.module.apply(p, stacked, last_action, b.hidden, True,
                                   mutable=["core", "intermediates"])
        return sown["intermediates"], moe_counters(sown, "core")["loss"]

    chosen, kl = jax.device_get(jax.jit(forward)(params, batch))
    layers = chosen[net.core.scope]
    return np.stack([np.asarray(layers[f"layers_{i}"]["self_attn"][
        "chosen"][0]) for i in range(len(layers))]), np.asarray(kl)


def compare_choice(chosen, kl, reference: Dict[str, Any], fields,
                   dtype: str) -> Dict[str, Any]:
    """The program's choice against the reference's (the module's
    docstring): its error and limit, and the readings beside it."""
    want = np.asarray(reference["chosen"])
    axes = tuple(range(1, want.ndim))
    pairs = np.maximum(want.sum(axis=axes), 1)
    differ = (np.asarray(chosen) != want).sum(axis=axes)
    both = (np.asarray(chosen) & want).sum(axis=axes)
    got_loss = np.asarray(r2d2_keye.learning_mean(kl, fields), np.float64)
    want_loss = np.asarray(r2d2_keye.learning_mean(
        reference["indexer_kl"], fields), np.float64)
    return {
        "errors": {"choice": float(np.max(differ / pairs))},
        "limits": {"choice": CHOICE[dtype]},
        "choice_agreement": [float(x) for x in both / pairs],
        "indexer_loss_error": float(np.max(
            np.abs(got_loss - want_loss)
            / np.maximum(np.abs(want_loss), 1e-30))),
        "indexer_loss_per_layer": [[float(g), float(w)]
                                   for g, w in zip(got_loss, want_loss)],
    }


def widen(out: Dict[str, Any], found: Dict[str, Any]) -> Dict[str, Any]:
    """``check.compare``'s result ``out`` with ``compare_choice``'s beside
    it under ``choice``, and ``ok`` only where the limits of both hold.
    ``out``'s own errors and limits stay as they are: they are the numbers
    the result line's ``compared`` carries in every cell."""
    return {**out, "choice": found, "ok": bool(out["ok"] and all(
        found["errors"][k] <= found["limits"][k] for k in found["errors"]))}


def check_learner(learner_, reference_name: str, n: int, seed: int
                  ) -> Dict[str, Any]:
    """``check.check_learner``, widened by ``compare_choice`` on the same
    ``n`` sequences (``check.sample_sequences`` draws them again from the
    same key)."""
    from r2d2_tpu.ops.pallas_kernels import resolve_pallas_obs_decode
    from r2d2_tpu.replay.structs import SampleBatch

    program, reference, weights, dtype = check.program_and_reference(
        learner_, reference_name, n, seed)
    out = check.compare(program, reference, weights, check.TOLERANCE[dtype],
                        eta=learner_.cfg.optim.priority_eta)
    out["compute_dtype"] = dtype
    batch = check.sample_sequences(learner_, n, seed)
    spec = dataclasses.replace(learner_.spec,
                               batch_size=int(batch.idxes.shape[0]))
    chosen, kl = program_choice(
        learner_.net, spec, learner_.train_state.params, batch,
        resolve_pallas_obs_decode(learner_.cfg.optim.pallas_obs_decode))
    fields = {f.name: getattr(batch, f.name)
              for f in dataclasses.fields(SampleBatch)
              if getattr(batch, f.name) is not None}
    return widen(out, compare_choice(chosen, kl, reference, fields, dtype))


def run(ctx) -> Dict[str, Any]:
    plain = learner.check
    learner.check = types.SimpleNamespace(check_learner=check_learner)
    try:
        return learner_long.run(ctx)
    finally:
        learner.check = plain
