"""Self time of Kimi Delta Attention (its projections and convolutions, the
chunked scan and the gated output: the three ``kda_*`` scopes, forward,
recomputation and backward, online and target), as a percentage of device
busy time. Nothing in a program that has no such scope."""

from benchmarks.layer_metrics import _kda, _moe


def read(ctx):
    return _moe.summed_share(ctx, _kda.SCOPES)
