"""Seconds of set-up in the ``Learner``'s construction: the
``learner/build`` span (the train state's init, a restore, the ring's
allocation and the compiles under them)."""

from benchmarks.layer_metrics._setup_span import rows


def read(ctx):
    found = rows(ctx, "learner/build")
    return sum(r["dur"] for r in found) if found else None
