"""Self time of the operations under ``gqa_attn`` (the grouped-query
attention of every attention layer: its norm, projections, QK-norm,
rotation, scores, softmax and output), as a percentage of device busy time.
Nothing in a program that has no such scope."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "gqa_attn")
