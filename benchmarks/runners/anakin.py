"""Runner of the ``anakin`` cells: the program's own fused act+train loop,
``run_anakin_train`` (what ``cli.train --actor.on_device=true`` runs). One
iteration is one acting scan (``block_length`` steps of ``anakin_lanes``
jitted envs and the policy forward), the ring write of its blocks, and one
train dispatch, alternating on one thread.

The loop hands out no handle on its inner calls, so this runner steers it
from ``log_fn``, which the loop calls on its own thread every
``runtime.log_interval`` seconds, right after ``Learner.flush_metrics`` has
fetched the pending losses: every record therefore closes on finished device
work, and the counters in it are exact. Warm-up lasts until the ring has
wrapped once; then the window opens; when it has run its length and holds
``MIN_INTERVALS`` intervals (in a traced run ``traced_window_seconds``, enough
for ``dispatch_host_ms``, and then the traced intervals) ``log_fn`` raises
``_Stop``, and the loop's own ``finally`` closes it. A stop hook in the loop would be cleaner (PERF.md, Open questions).

The loop dates a record before it flushes and calls ``log_fn``, so whatever
the flush (which waits for the iterations the host is ahead) and ``log_fn``
spend (the reference check, starting the profiler) counts towards the next
interval: the record after a slow one comes one iteration later. The window's
first interval and a traced interval are therefore one iteration long, and on
the chip so is every second one (0.207 s, then 4.07 s of twenty iterations;
PERF.md, Open questions). The rate is the median interval's: of six, the mean
of the slowest long one and the fastest short one.

The loop builds its ``Learner`` itself; the runner needs it for the reference
check and the device-side counters, and takes it by standing a recording
subclass in for the name the loop looks up. The subclass changes nothing.
"""

import json
import os
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness
from benchmarks.reference import check


# fewest log intervals a timed window holds (a whole one has six: the loop's
# records come in turn one iteration and some twenty apart, ``interval_s`` in
# ``facts``; the flush's wait for what the host has dispatched ahead counts
# towards the next record, which seems to be why). A window that a
# stall of the host fell into closes on five: the median is then a sound
# interval, of the one-iteration kind, which reads 1% under the long kind
MIN_INTERVALS = 5


class _Stop(Exception):
    """Raised from ``log_fn`` to end the program's loop."""


def _program_spans(save_dir: str, since: float, until: float) -> List[dict]:
    """The stage spans the program wrote (``spans_player0.jsonl``: name,
    ``ts`` on ``time.time()``, ``dur``) that lie inside [since, until]."""
    path = os.path.join(save_dir, "spans_player0.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows
            if r["ts"] >= since and r["ts"] + r["dur"] <= until]


def load_program() -> None:
    """Import what ``run`` takes from the program."""
    import r2d2_tpu.runtime.anakin_loop  # noqa: F401


def run(ctx) -> Dict[str, Any]:
    from r2d2_tpu.runtime import anakin_loop
    from r2d2_tpu.runtime.learner_loop import Learner

    cfg, params = ctx.cfg, ctx.traffic
    k = cfg.runtime.resolved_steps_per_dispatch()
    scan_steps = cfg.actor.anakin_lanes * cfg.replay.block_length
    window_s = (ctx.seconds if ctx.trace is None
                else min(ctx.seconds, params["traced_window_seconds"]))
    built: List[Learner] = []

    class Recorded(Learner):
        def __init__(self, *args, **kwargs):
            harness.stamp(ctx.process_start, "the loop builds its Learner")
            super().__init__(*args, **kwargs)
            built.append(self)
            harness.stamp(ctx.process_start, "Learner built")

    # one record per log interval: (perf_counter, wall clock, env steps,
    # train steps, interval's mean loss)
    state: Dict[str, Any] = {"phase": "warmup", "window": [], "traced": []}

    def log_fn(record: dict) -> None:
        now, wall = time.perf_counter(), time.time()
        row = (now, wall, record["env_steps"], record["training_steps"],
               record["loss"])
        if state["phase"] == "warmup":
            ring_steps = built[0].ring.num_blocks * cfg.replay.block_length
            if (record["buffer_size"] < ring_steps
                    or not record["training_steps"]):
                return
            # ring wrapped and training: the traffic is in place
            harness.stamp(ctx.process_start, "ring wrapped")
            state["reference"] = check.check_learner(
                built[0], ctx.reference, params["check_sequences"],
                ctx.seed + 1)
            state["builds0"] = ctx.compiles.builds
            harness.stamp(ctx.process_start, "reference checked")
            now, wall = time.perf_counter(), time.time()
            state["window"].append((now, wall) + row[2:])
            state["phase"] = "window"
        elif state["phase"] == "window":
            state["window"].append(row)
            # the window closes on time and on work: a host that stood still
            # for some of the seconds delays the close (the median interval
            # is then one it did not fall into) and does not decide
            # ``correct``, which is for answers
            if (now - state["window"][0][0] < window_s
                    or (ctx.trace is None
                        and len(state["window"]) - 1 < MIN_INTERVALS)):
                return
            state["builds1"] = ctx.compiles.builds
            if ctx.trace is None:
                raise _Stop
            ctx.trace.begin()
            state["traced"].append(
                (time.perf_counter(), time.time()) + row[2:])
            state["phase"] = "trace"
        else:
            state["traced"].append(row)
            if len(state["traced"]) > params["trace_intervals"]:
                ctx.trace.end()
                raise _Stop

    anakin_loop.Learner = Recorded
    try:
        anakin_loop.run_anakin_train(
            cfg, max_seconds=params["loop_cap_seconds"], log_fn=log_fn)
        raise harness.BenchError(
            "the loop ended before the window did (phase "
            f"{state['phase']}): raise loop_cap_seconds")
    except _Stop:
        pass
    finally:
        anakin_loop.Learner = Learner
        if ctx.trace is not None:
            ctx.trace.end()

    learner, window = built[0], state["window"]
    dp = learner.mesh.shape["dp"] if learner.mesh is not None else 1
    pairs = list(zip(window, window[1:]))
    rates = [(b[2] - a[2]) / (b[0] - a[0]) for a, b in pairs]
    env_steps = window[-1][2] - window[0][2]
    train_steps = window[-1][3] - window[0][3]
    rows = window[1:] + state["traced"][1:]
    bad = sum(r[4] is None or not np.isfinite(r[4]) for r in rows)
    checks = {
        "reference": state["reference"]["ok"],
        "losses_finite": bad == 0,
        "no_compile_in_window": state["builds1"] == state["builds0"],
        # one scan and one train dispatch an iteration, nothing dropped
        "step_counters": (env_steps % scan_steps == 0 and train_steps % k == 0
                          and env_steps // scan_steps == train_steps // k
                          and int(learner.train_state.step)
                          == learner.training_steps),
        "ring_full": learner.ring.buffer_steps == cfg.replay.capacity * dp,
    }
    # host time inside the program's calls, per iteration, from its own
    # stage spans (what the loop's thread did between device dispatches)
    host: Dict[str, List[float]] = {}
    for s in _program_spans(cfg.runtime.save_dir, window[0][1], window[-1][1]):
        host.setdefault(s["name"], []).append(s["dur"])
    external = []
    if state["traced"]:
        external = [(s["name"], s["ts"], s["ts"] + s["dur"])
                    for s in _program_spans(cfg.runtime.save_dir,
                                            state["traced"][0][1],
                                            state["traced"][-1][1])]
    return {
        "checks": checks,
        "attempted": env_steps // scan_steps + train_steps // k,
        "failed": bad, "reference": state["reference"],
        "values": {
            "env_steps_per_s": statistics.median(rates),
            "setup_s": window[0][0] - ctx.process_start,
            "hbm_peak_gib": harness.device_facts()["memory_peak_bytes"] / 2**30,
            "program_span_s": host,
        },
        "external_spans": external,
        "facts": {"action_dim": learner.net.action_dim,
                  "steps_per_dispatch": k, "dp": dp,
                  "lanes": cfg.actor.anakin_lanes,
                  "scan_steps": cfg.replay.block_length,
                  "intervals": len(rates),
                  "interval_s": [round(b[0] - a[0], 4) for a, b in pairs],
                  "interval_rates": [round(r, 1) for r in rates],
                  "act_bytes": 2 if learner.net.config.bf16 else 4},
    }
