"""Milliseconds per train step in which a device's operation line runs a
collective (the gradient all-reduce over ICI) and so no compute: the
collectives' self time on the device that waits longest, over the steps
traced."""


def read(ctx):
    if ctx.trace is None or ctx.facts["dp"] < 2:
        return None
    steps = (len(ctx.trace.module_runs("loss"))
             * ctx.facts["steps_per_dispatch"])
    return 1e3 * ctx.trace.collective_self_s() / steps if steps else None
