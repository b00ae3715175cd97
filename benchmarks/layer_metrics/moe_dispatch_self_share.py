"""Self time of what routing costs beside the products: the sort and gather
of the (position, expert) pairs and the experts' activation between the two
grouped products (``moe_dispatch``), and the way back with the weighted sum
(``moe_combine``), as a percentage of device busy time."""

from benchmarks.layer_metrics import _moe


def read(ctx):
    return _moe.summed_share(ctx, _moe.ROUTING)
