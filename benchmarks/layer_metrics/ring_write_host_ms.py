"""Host milliseconds inside one scan's ring write: the median
``ingest/commit`` span (the call of ``replay_add_many``), the host side of
the layer whose device side is ``ingest_ms``. Absent under ``mesh.dp`` > 1,
where the write is fused into the acting scan."""

from benchmarks.layer_metrics._program_span import median_ms


def read(ctx):
    return median_ms(ctx, "ingest/commit")
