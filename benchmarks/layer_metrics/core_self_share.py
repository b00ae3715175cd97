"""Self time of the memory core (every layer of it, forward, recomputation
and backward, online and target): the operations under its scope
``mem_core`` and the grouped products' own calls, which the capture shows
with no scope (``_moe.GROUPED``), as a percentage of device busy time."""

from benchmarks.layer_metrics import _moe


def read(ctx):
    return _moe.summed_share(ctx, ("mem_core", _moe.GROUPED))
