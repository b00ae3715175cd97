"""Self time of the operations under the ``lstm`` scope (the hoisted input
projection, the recurrent scan forward and backward, under double-Q the
target net's scan too), as a percentage of device busy time."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "lstm")
