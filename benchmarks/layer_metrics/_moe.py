"""Shared by the ``moe_*`` readers and ``k_experts_roofline``: the tokens
that find the expert layers' operations, and the summed self-time share of
several of them (``benchmarks/layer_metrics/_share.py`` for one)."""

from benchmarks.layer_metrics._share import self_share

ROUTING = ("moe_dispatch", "moe_combine")
# XLA:TPU names a grouped product's call ragged-dot-none and keeps no scope
GROUPED = "ragged-dot"
EXPERTS = ("moe_experts", GROUPED)
ALL = ("moe_router", "moe_shared") + EXPERTS + ROUTING


def summed_share(ctx, tokens):
    shares = [self_share(ctx, token) for token in tokens]
    found = [s for s in shares if s is not None]
    return sum(found) if found else None
