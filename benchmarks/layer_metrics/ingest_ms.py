"""Device milliseconds of one scan's ring write (``replay_add_many`` of the
scan's blocks): the median execution of the program that holds the
``replay_add`` scope and neither acting nor the loss."""

from statistics import median


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs("replay_add",
                                 without=("loss", "act_forward"))
    return median([m.dur for m in runs]) / 1e6 if runs else None
