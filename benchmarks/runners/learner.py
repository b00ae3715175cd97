"""Runner of the ``learner`` cells: the program's ``Learner`` on a full ring,
no actors. ``Learner.ingest`` fills the ring from the traffic mix's block
pool, ``Learner.step`` is the measured call (one dispatch =
``steps_per_dispatch`` fused train steps, on every chip of ``mesh.dp``).

The window is cut into sub-windows of a fixed amount of work, each closed by
blocking on its losses, so every reading ends on finished device work. A
sub-window is the mix's ``subwindow_steps`` train steps rounded up to whole
dispatches: one period of the step's learning diagnostics (a ``lax.cond``
branch of tens of ms every ``telemetry.learning_interval`` = 200 steps), so
that every sub-window holds one run of the branch whatever the program's
speed (13 dispatches of 16 are 208 steps: one sub-window in 25 holds two). A
sub-window of half a period would hold the branch or not, 3% apart, and the
median would jump between the two. ``Learner.flush_metrics`` (the losses and
the diagnostics to the host, tens of ms during which the device waits) runs
as the trainer's loop runs it, every ``runtime.log_interval`` seconds. The
rate reported is the median sub-window's, so neither a flush nor a stall
moves it; the window lasts its seconds and at least ``MIN_SUBWINDOWS``
sub-windows, so a host that stood still for some of them delays the close and
does not decide ``correct``, which is for answers. A traced run times one
sub-window (for ``dispatch_host_ms``) and then traces; where the program counts what its experts were routed (the
record's ``moe`` block, a core with experts), the counts of the traced
dispatches alone go into ``facts`` (``moe_traced``), read from the learner's
metrics after the flush that follows the capture: nothing is added to a
window, and a program without the counter carries none.
"""

import statistics
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import harness, traffic
from benchmarks.reference import check


# fewest sub-windows a timed window holds: with three, the median is a
# sub-window that no single stall of the host fell into
MIN_SUBWINDOWS = 3


def _replicas_equal(params) -> bool:
    """Every leaf of a replicated tree bit-equal on all its devices."""
    import jax
    for leaf in jax.tree_util.tree_leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s) for s in shards[1:]):
            return False
    return True


class _Loop:
    """The trainer's inner loop, cut into blocks of dispatches."""

    def __init__(self, learner, spans: harness.HostSpans):
        self.learner, self.spans = learner, spans
        self.dispatched = 0
        self.last_flush = time.perf_counter()

    def flush(self) -> Optional[Dict[str, Any]]:
        """The program's flush; returns the ``moe`` block it made of the
        dispatches since the one before (what a training run's record would
        carry), None where the program counts no routing or had nothing new
        to count."""
        with self.spans.span("metrics_flush"):
            self.learner.flush_metrics()
        self.last_flush = time.perf_counter()
        return getattr(self.learner.metrics, "_moe", None)

    def dispatches(self, n: int) -> List[Any]:
        """``n`` dispatches, blocked on; then the flush if its interval has
        passed. Returns the dispatches' losses."""
        import jax
        losses = []
        for _ in range(n):
            with self.spans.span("dispatch"):
                losses.append(self.learner.step()["loss"])
        self.dispatched += n
        with self.spans.span("block_wait"):
            jax.block_until_ready(losses)
        if (time.perf_counter() - self.last_flush
                >= self.learner.cfg.runtime.log_interval):
            self.flush()
        return jax.device_get(losses)


def _moe_totals(block: Dict[str, Any]) -> Dict[str, int]:
    """Train steps, and over them and the expert layers the pairs that fell
    on held experts and the sorted rows the experts' walk took in, of one of
    the program's ``moe`` blocks (``telemetry/learning.py MoeAggregator``;
    ``rows_walked`` is 0 in a checkout from before that counter)."""
    layers = block["layers"]
    return {"steps": block["steps"],
            "pairs_held": sum(layer["pairs_held"] for layer in layers),
            "rows_walked": sum(layer.get("rows_walked", 0)
                               for layer in layers)}


def load_program() -> None:
    """Import what ``run`` takes from the program (tens of seconds on the
    chip's machine: see PERF.md, Findings, set-up)."""
    import r2d2_tpu.envs.factory  # noqa: F401
    import r2d2_tpu.models.network  # noqa: F401
    import r2d2_tpu.runtime.learner_loop  # noqa: F401


def run(ctx) -> Dict[str, Any]:
    from jax.sharding import NamedSharding, PartitionSpec

    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner
    from r2d2_tpu.utils.platform import announce_runtime

    cfg, params, spans = ctx.cfg, ctx.traffic, ctx.spans
    resolved = announce_runtime(cfg)["resolved"]
    probe = create_env(cfg.env, seed=ctx.seed)     # as train() finds the
    action_dim = probe.action_space.n              # action count
    probe.close()
    net = NetworkApply(action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    harness.stamp(ctx.process_start, "network described")
    t0 = time.perf_counter()
    learner = Learner(cfg, net, 0, seed=ctx.seed)
    dp = learner.mesh.shape["dp"] if learner.mesh is not None else 1
    k = cfg.runtime.resolved_steps_per_dispatch()
    t_built = time.perf_counter()

    try:
        replicated = (NamedSharding(learner.mesh, PartitionSpec())
                      if learner.mesh is not None else None)
        with spans.span("ingest_commit"):
            writes = traffic.fill_ring(learner, action_dim, params["replay"],
                                       ctx.seed, replicated)
        t_filled = time.perf_counter()
        reference = check.check_learner(
            learner, ctx.reference, params["check_sequences"], ctx.seed + 1)
        t_checked = time.perf_counter()

        # warm-up: the first dispatch builds or loads the step program, the
        # flushes the host's
        loop = _Loop(learner, spans)
        loop.dispatches(1)
        loop.flush()
        loop.dispatches(params["warmup_dispatches"])
        loop.flush()
        n_sub = -(-params["subwindow_steps"] // k)
        setup_end = time.perf_counter()
        print(f"setup: learner {t_built - t0:.1f}s fill {t_filled - t_built:.1f}s "
              f"({writes} writes) reference {t_checked - t_filled:.1f}s "
              f"warm-up {setup_end - t_checked:.1f}s; {n_sub} dispatches a "
              "sub-window", flush=True)

        builds0 = ctx.compiles.builds
        rates, bad, attempted = [], 0, 0
        deadline = setup_end + (0.0 if ctx.trace is not None
                                else ctx.seconds)
        while True:
            ts = time.perf_counter()
            losses = loop.dispatches(n_sub)
            te = time.perf_counter()
            rates.append(n_sub * k / (te - ts))
            attempted += n_sub
            bad += sum(not np.isfinite(np.asarray(x)).all() for x in losses)
            # a window closes on time and on work: a stall of the host that
            # eats the seconds still leaves the median sub-windows to stand on
            if te >= deadline and (len(rates) >= MIN_SUBWINDOWS
                                   or ctx.trace is not None):
                break
        window_end = time.perf_counter()
        builds_in_window = ctx.compiles.builds - builds0

        if ctx.trace is not None:
            loop.flush()       # so that none falls into the traced dispatches
            ctx.trace.begin()
            losses = loop.dispatches(params["trace_dispatches"])
            ctx.trace.end()
            attempted += len(losses)
            bad += sum(not np.isfinite(np.asarray(x)).all() for x in losses)

        moe = loop.flush()
        # in a traced run the dispatches since the flush before are the
        # traced ones: their routing counters go to the readers
        counted = ({"moe_traced": _moe_totals(moe)}
                   if ctx.trace is not None and moe else {})
        steps = learner.training_steps
        dispatched = loop.dispatched * k
        checks = {
            "reference": reference["ok"],
            "losses_finite": bad == 0,
            "no_compile_in_window": builds_in_window == 0,
            "step_counters": (steps == dispatched
                              and int(learner.train_state.step) == dispatched),
            "ring_full": (learner.ring.buffer_steps
                          == cfg.replay.capacity * dp),
        }
        if learner.mesh is not None:
            checks["replicas_bit_equal"] = _replicas_equal(
                learner.train_state.params)
        steps_per_s = statistics.median(rates)
        return {
            "checks": checks, "attempted": attempted, "failed": bad,
            "reference": reference,
            "values": {
                "seq_updates_per_s": steps_per_s * cfg.replay.batch_size * dp,
                "setup_s": setup_end - ctx.process_start,
                "hbm_peak_gib":
                    harness.device_facts()["memory_peak_bytes"] / 2**30,
                "dispatch_host_s": spans.durations(
                    "dispatch", setup_end, window_end),
            },
            "facts": {"action_dim": action_dim, "steps_per_dispatch": k,
                      "dp": dp, "subwindows": len(rates),
                      "dispatches_per_subwindow": n_sub,
                      "resolved": resolved,
                      "act_bytes": 2 if net.config.bf16 else 4, **counted},
        }
    finally:
        learner.stop_background()
