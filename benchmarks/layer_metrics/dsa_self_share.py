"""Self time of the sparse attention (the indexer's projections and scores,
the threshold, attention over the chosen keys, the heads' probabilities and
the indexer's loss: the four ``dsa_*`` scopes, forward and backward, online
and target), as a percentage of device busy time. Nothing in a program that
has no such scope."""

from benchmarks.layer_metrics import _dsa, _moe


def read(ctx):
    return _moe.summed_share(ctx, _dsa.SCOPES)
