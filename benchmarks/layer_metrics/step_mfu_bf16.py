"""Model FLOP/s utilisation of the fused train step of a cell with the
``mla_moe`` memory core: the whole step's model FLOPs (torso, core and head;
``benchmarks/costs_mla_moe.py``) over the device time of one step (as
``train_step_ms`` takes it) and the chip's bf16 peak: this cell's share of
the chip. ``mfu_bf16`` counts the LSTM network and is not read in these
cells."""

from benchmarks import costs, costs_mla_moe
from benchmarks.layer_metrics import train_step_ms


def read(ctx):
    step_ms = train_step_ms.read(ctx)
    if step_ms is None or ctx.cfg.network.core.kind != "mla_moe":
        return None
    flops = costs_mla_moe.step_flops(ctx.cfg, ctx.facts["action_dim"])
    return (100.0 * flops / (step_ms / 1e3)
            / costs.peak(ctx.device_kind)["flops_bf16"])
