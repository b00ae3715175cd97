"""Self time of the operations under the ``torso`` scope (the three
convolutions and the dense layer, forward and backward), as a percentage of
device busy time."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "torso")
