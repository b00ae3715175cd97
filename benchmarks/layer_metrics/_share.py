"""Shared by the ``*_self_share`` readers: the self time of the operations
under a scope (those whose scope path holds ``token``) as a share of the time
the device was busy."""


def self_share(ctx, token: str):
    if ctx.trace is None:
        return None
    under, busy = ctx.trace.self_under_s(token), ctx.trace.busy_s()
    if under <= 0 or busy <= 0:
        return None
    return 100.0 * under / busy
