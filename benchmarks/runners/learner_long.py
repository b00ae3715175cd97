"""Runner of the ``learner-long`` mix: ``runners/learner.py``'s loop, checks,
values and facts, for a train step of a quarter of a second. That runner cuts
its window into sub-windows of ``subwindow_steps`` train steps; here the mix
gives the sub-window in dispatches (``subwindow_dispatches``, each
``runtime.steps_per_dispatch`` fused steps), so that five or six sub-windows
fit the measured seconds. One sub-window in 25 holds the step's learning
diagnostics (every 200 steps); the median does not see it."""

from benchmarks.runners import learner

load_program = learner.load_program


def run(ctx):
    ctx.traffic["subwindow_steps"] = (
        ctx.traffic["subwindow_dispatches"]
        * ctx.cfg.runtime.resolved_steps_per_dispatch())
    return learner.run(ctx)
