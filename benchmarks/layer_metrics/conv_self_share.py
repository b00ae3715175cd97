"""Self time of the operations under ``short_conv`` (the gated short
convolution of every conv layer: its norm, the input projection, the gates,
the taps and the output projection), as a percentage of device busy time.
Nothing in a program that has no such scope."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "short_conv")
