"""Device milliseconds of one acting scan (``block_length`` steps of every
lane: env step, policy forward, block assembly): the median execution of the
program that holds the ``act_forward`` scope and no loss."""

from statistics import median


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs("act_forward", without=("loss",))
    return median([m.dur for m in runs]) / 1e6 if runs else None
