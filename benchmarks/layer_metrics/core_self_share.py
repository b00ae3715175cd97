"""Self time of the operations under the memory core's scope ``mem_core``
(every layer of it, forward, recomputation and backward, online and target),
as a percentage of device busy time."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "mem_core")
