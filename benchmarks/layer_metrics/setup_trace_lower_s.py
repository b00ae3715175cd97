"""Seconds of set-up spent tracing the program's functions to jaxprs and
lowering them to MLIR: the union, on each thread, of the ``compile/trace``
and ``compile/lower`` spans (``telemetry/compile.py``), over the run."""

from benchmarks.layer_metrics._setup_span import rows, union_s


def read(ctx):
    found = rows(ctx, "compile/trace", "compile/lower")
    return union_s(found) if found else None
