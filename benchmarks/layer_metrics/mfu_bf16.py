"""Model FLOP/s utilisation of the fused train step on the device: the
model's FLOPs per train step (the benchmark's own count: the module the
configuration names under ``costs``, ``harness.costs_of``, so a new
configuration brings its count as a file and joins this metric by a name in a
list) over the device time of one step (as ``train_step_ms`` takes it: the
median execution of the program that holds the loss, divided by the steps it
fuses) and one chip's bf16 peak (every chip runs the same step on its own
batch). It is the step's device time in units of the chip, so that two
configurations can be read side by side, and the whole step's share beside
the kernels' rooflines. Nothing where the configuration names no count."""

from statistics import median

from benchmarks import costs, harness


def read(ctx):
    if ctx.trace is None:
        return None
    runs = ctx.trace.module_runs("loss")
    counts = harness.costs_of(ctx.config or {})
    if not runs or counts is None:
        return None
    step_s = (median([m.dur for m in runs]) / 1e9
              / ctx.facts["steps_per_dispatch"])
    flops = counts.step_flops(ctx.cfg, ctx.facts["action_dim"])
    return 100.0 * flops / step_s / costs.peak(ctx.device_kind)["flops_bf16"]
