"""The sparse attention kernel (``ops/sparse_attention.py``: its forward and
its two backward kernels, the calls under ``dsa_attn``) against the chip's
bf16 peak: the model FLOPs it owes for the (query, key) pairs the traced
steps chose (the program's ``dsa`` counter, ``_dsa.pairs_per_step``;
``sparse_attn_flops`` of the configuration's count of model work) over the
kernels' device time and the peak. The pairs a block holds beside the
chosen and the forward recomputed in the backward are time, not work.
Nothing where the program counts no pairs, runs no such kernel, or the
configuration's count has no ``sparse_attn_flops``."""

from benchmarks import costs, harness
from benchmarks.layer_metrics import _dsa


def read(ctx):
    if ctx.trace is None:
        return None
    pairs = _dsa.pairs_per_step(ctx)
    counts = harness.costs_of(ctx.config or {})
    calls = ctx.trace.kernel_calls("dsa_attn")
    steps = (len(ctx.trace.module_runs("loss"))
             * ctx.facts.get("steps_per_dispatch", 0))
    if (not pairs or not calls or not steps
            or not hasattr(counts, "sparse_attn_flops")):
        return None
    seconds = sum(o.dur for o in calls) / 1e9
    flops = steps * counts.sparse_attn_flops(ctx.cfg, pairs)
    return 100.0 * flops / seconds / costs.peak(ctx.device_kind)["flops_bf16"]
