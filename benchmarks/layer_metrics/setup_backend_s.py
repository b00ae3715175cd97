"""Seconds of set-up in XLA's builds and the persistent cache's reads and
loads of executables: the ``compile/backend`` spans of the run
(``telemetry/compile.py``; each tagged ``fn`` and ``cache`` hit, miss or
off)."""

from benchmarks.layer_metrics._setup_span import rows


def read(ctx):
    found = rows(ctx, "compile/backend")
    return sum(r["dur"] for r in found) if found else None
