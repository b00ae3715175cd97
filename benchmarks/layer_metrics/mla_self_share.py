"""Self time of the operations under ``mla_attn`` (the latent attention of
every layer: its norm, projections, rotation, scores, softmax and output),
as a percentage of device busy time."""

from benchmarks.layer_metrics._share import self_share


def read(ctx):
    return self_share(ctx, "mla_attn")
