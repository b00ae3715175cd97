"""The held experts' grouped products against the chip's bf16 peak: the
model FLOPs they owe for the (position, expert) pairs that fell on held
experts in the traced steps (the program's ``moe`` counter ``pairs_held`` a
step, which the runner reads from the learner's flushed metrics;
``experts_flops`` of the configuration's count of model work), over the self
time of everything that implements them (``_moe.EXPERTS``: the operations
under ``moe_experts`` and the grouped products' own calls) and the peak. The
count is of pairs, not of the rows a chunked walk pads them to and not of the
forward pass recomputed in the backward: the same work reads the same
whether ``jax.lax.ragged_dot`` or a kernel does it, and a walk that pads more
reads lower. Nothing where the program counts no pairs (a core without
experts), where none fell on a held expert, or where the configuration's
count has no ``experts_flops``."""

from benchmarks import costs, harness
from benchmarks.layer_metrics import _moe


def read(ctx):
    counted = ctx.facts.get("moe_traced") or {}
    if ctx.trace is None or not counted.get("pairs_held") \
            or not counted.get("steps"):
        return None
    counts = harness.costs_of(ctx.config or {})
    seconds = sum(ctx.trace.self_under_s(token) for token in _moe.EXPERTS)
    steps = (len(ctx.trace.module_runs("loss"))
             * ctx.facts["steps_per_dispatch"])
    if not hasattr(counts, "experts_flops") or seconds <= 0 or not steps:
        return None
    flops = steps * counts.experts_flops(
        ctx.cfg, counted["pairs_held"] / counted["steps"])
    return 100.0 * flops / seconds / costs.peak(ctx.device_kind)["flops_bf16"]
