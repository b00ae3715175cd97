"""Model FLOP/s utilisation of the fused train step on the device: the
model's FLOPs per train step (the benchmark's own count,
``benchmarks/costs.py``) over the device time of one step (as
``train_step_ms`` takes it: the median execution of the program that holds
the loss, divided by the steps it fuses) and one chip's bf16 peak (every chip
runs the same step on its own batch). It is the step's device time in units
of the chip, so that two configurations can be read side by side."""

from statistics import median

from benchmarks import costs


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs("loss")
    if not runs:
        return None
    step_s = (median([m.dur for m in runs]) / 1e9
              / ctx.facts["steps_per_dispatch"])
    flops = costs.model_flops_per_step(ctx.cfg, ctx.facts["action_dim"])
    return 100.0 * flops / step_s / costs.peak(ctx.device_kind)["flops_bf16"]
