"""Host milliseconds of one log boundary of the fused loop: the median
``anakin/log`` span (``Learner.flush_metrics``, the acting statistics'
fetch, the record, the caller's hook). The loop dispatches nothing while it
lasts. Its first child, ``learner/device_sync``, waits out every dispatch
still queued, so on a thread that runs ahead of the device most of this is
queue depth on a busy device; the device idles for what follows the sync
(PERF.md, section 5)."""

from benchmarks.layer_metrics._program_span import median_ms


def read(ctx):
    return median_ms(ctx, "anakin/log")
