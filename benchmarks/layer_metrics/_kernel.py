"""Shared by the ``k_*_roofline`` readers: a Pallas kernel's share of the
memory roofline. Both kernels only move bytes, so the bound is bytes needed
over the chip's HBM bandwidth; the share is that least time over the time the
kernel's calls took on the device. A kernel shows in the trace as a
``tpu_custom_call`` under the scope of the code that calls it; the calls are
counted from the trace (XLA merges two identical calls into one)."""

from benchmarks import costs


def roofline_share(ctx, scope: str, bytes_per_call: float):
    if ctx.trace is None:
        return None
    calls = ctx.trace.kernel_calls(scope)
    if not calls:
        return None
    seconds = sum(o.dur for o in calls) / 1e9
    least = (len(calls) * bytes_per_call
             / costs.peak(ctx.device_kind)["hbm_bytes_per_s"])
    return 100.0 * least / seconds
