"""Device milliseconds of one fused train step: the duration of each
execution of the program that holds the loss (found by its ``loss`` scope,
not by its name), divided by the steps it fuses; the median execution."""

from statistics import median


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs("loss")
    if not runs:
        return None
    return median([m.dur for m in runs]) / 1e6 / ctx.facts["steps_per_dispatch"]
