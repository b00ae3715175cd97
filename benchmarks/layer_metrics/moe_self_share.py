"""Self time of the expert layers' feed-forward halves (router, the sort
and gather of the pairs, the held experts' grouped products, the shared
expert, the combine), as a percentage of device busy time."""

from benchmarks.layer_metrics import _moe


def read(ctx):
    return _moe.summed_share(ctx, _moe.ALL)
