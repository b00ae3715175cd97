"""The observation-decode kernel (``ops/pallas_kernels.py
stack_frames_pallas``: uint8 frame rows to stacked, normalised observations)
against the HBM roofline."""

from benchmarks import costs
from benchmarks.layer_metrics._kernel import roofline_share


def read(ctx):
    return roofline_share(
        ctx, "obs_decode",
        costs.decode_bytes_needed(ctx.cfg, ctx.facts["act_bytes"]))
