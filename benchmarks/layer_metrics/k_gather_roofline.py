"""The replay window gather (``ops/pallas_kernels.py
gather_rows_exact_pallas``: one async copy of each sampled sequence's frames
out of the ring) against the HBM roofline."""

from benchmarks import costs
from benchmarks.layer_metrics._kernel import roofline_share


def read(ctx):
    return roofline_share(ctx, "replay_sample",
                          costs.gather_bytes_needed(ctx.cfg))
