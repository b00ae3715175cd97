"""What the readers of set-up's spans share (PR 38): the rows of the
program's span tree as it wrote them (``spans_player0.jsonl`` in the run's
``runtime.save_dir``: ``name``, ``ts`` on ``time.time()``, ``dur``, ``tid``,
``self``, ``tags``), and the union of intervals. Every compile of a run is
set-up's: one inside the timed window fails the check
``no_compile_in_window``. A reader returns None where the program wrote no
such span, as a checkout from before PR 38 writes none."""

import json
import os


def rows(ctx, *names):
    """The rows of the spans named ``names``: [] where there is none."""
    path = os.path.join(ctx.cfg.runtime.save_dir, "spans_player0.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        found = [json.loads(line) for line in f if line.strip()]
    return [r for r in found if r["name"] in names]


def union_s(found):
    """Seconds the rows' intervals cover on each thread, summed over the
    threads: spans of one thread that nest or overlap count once."""
    by_thread = {}
    for r in found:
        by_thread.setdefault(r["tid"], []).append((r["ts"],
                                                   r["ts"] + r["dur"]))
    total = 0.0
    for intervals in by_thread.values():
        end = float("-inf")
        for t0, t1 in sorted(intervals):
            if t1 > end:
                total += t1 - max(t0, end)
                end = t1
    return total
