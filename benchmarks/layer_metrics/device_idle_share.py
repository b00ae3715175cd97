"""1 - (union of the device's operation intervals) / (traced window), as a
percentage; of the idlest device where there are several."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share()
