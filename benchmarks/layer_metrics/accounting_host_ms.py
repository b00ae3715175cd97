"""Host milliseconds of one iteration's ring accounting in the fused loop:
the median ``anakin/accounting`` span (``ring.advance`` and
``metrics.on_block`` once a lane, the counters). It moves the rate only if
the device has run out of queued work by then."""

from benchmarks.layer_metrics._program_span import median_ms


def read(ctx):
    return median_ms(ctx, "anakin/accounting")
