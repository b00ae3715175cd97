"""Seconds of set-up in the step program's first execution: the self time
of the ``Learner``'s first dispatch (``learner/train_dispatch`` tagged
``first=1``: the call less the trace, lowering and build under it) and the
``learner/first_ready`` span that then blocks once on its outputs."""

from benchmarks.layer_metrics._setup_span import rows


def read(ctx):
    found = rows(ctx, "learner/train_dispatch", "learner/first_ready")
    ready = [r["dur"] for r in found if r["name"] == "learner/first_ready"]
    if not ready:
        return None
    first = [r["self"] for r in found
             if (r.get("tags") or {}).get("first") == 1]
    return sum(first) + sum(ready)
