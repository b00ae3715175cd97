"""The chunked scan's kernels (``ops/kda.py``: the forward and the backward,
the calls under ``kda_scan``) against the chip's roofline: the least time
their counted work needs, the larger of the configuration's FLOPs
(``kda_scan_flops``) over the bf16 peak and its HBM bytes
(``kda_scan_bytes``) over the bandwidth, for the traced steps, over the
kernels' device time. The forward a backward kernel recomputes is time, not
work. Nothing where the program runs no such kernel or the configuration's
count has no ``kda_scan_flops``."""

from benchmarks import costs, harness
from benchmarks.layer_metrics import _kda


def read(ctx):
    if ctx.trace is None:
        return None
    counts = harness.costs_of(ctx.config or {})
    calls = ctx.trace.kernel_calls(_kda.SCAN)
    steps = (len(ctx.trace.module_runs("loss"))
             * ctx.facts.get("steps_per_dispatch", 0))
    if not calls or not steps or not hasattr(counts, "kda_scan_flops"):
        return None
    peak = costs.peak(ctx.device_kind)
    least = steps * max(counts.kda_scan_flops(ctx.cfg) / peak["flops_bf16"],
                        counts.kda_scan_bytes(ctx.cfg)
                        / peak["hbm_bytes_per_s"])
    return 100.0 * least / (sum(o.dur for o in calls) / 1e9)
