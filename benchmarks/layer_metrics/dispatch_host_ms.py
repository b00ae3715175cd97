"""Host milliseconds inside the calls into the program, per iteration of the
cell's loop. A learner cell's iteration is one ``Learner.step()`` (the
benchmark's own ``dispatch`` span, median over the window); the fused loop's
is one acting-scan call plus one train dispatch (the program's own
``actor/act_scan`` and ``learner/train_dispatch`` stage spans, the medians
added). It moves the rate only where the device waits for the host."""

from statistics import median


def read(ctx):
    if ctx.trace is None:        # read in traced chip runs only
        return None
    own = ctx.values.get("dispatch_host_s")
    if own:
        return 1e3 * median(own)
    program = ctx.values.get("program_span_s", {})
    parts = [program.get(name) for name in ("actor/act_scan",
                                            "learner/train_dispatch")]
    if not all(parts):
        return None
    return 1e3 * sum(median(p) for p in parts)
