"""Per-layer metrics, one reader a file: ``read(ctx) -> float | None`` takes
the metric from the trace summary, the spans or the counters in ``ctx``
(``benchmarks/run.py MetricContext``). A reader that finds nothing to read
returns None, and the metric is left out of the line. A reader finds its
programs, kernels and operations by a token of their scope path
(``TraceSummary.module_runs``, ``kernel_calls``, ``self_under_s``), so one
over a scope no scope table names is one more file here."""
