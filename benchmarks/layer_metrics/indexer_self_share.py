"""Self time of the lightning indexer's part of the sparse attention: its
projections and scores (``dsa_indexer``), the exact top-k threshold and the
masks of the choice (``dsa_select``), and its loss with the heads'
probabilities it is held to (``dsa_kl``), as a percentage of device busy
time. Nothing in a program that has no such scope."""

from benchmarks.layer_metrics import _dsa, _moe


def read(ctx):
    return _moe.summed_share(ctx, _dsa.INDEXER)
