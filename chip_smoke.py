#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

    python3 chip_smoke.py [--out DIR]

Drives the three programs a user starts, once each, at the full width of
``Config()`` (84x84x4 frames, Nature torso, cnn_out 1024, LSTM-512, dueling
head, batch 128 x 55-step windows, device replay at the default 500,000-step
capacity), through their normal entry points, with random weights from the
default seed:

  trainer  ``cli.train --max-steps=64``: two spawned CPU actors -> shm ring
           -> batched ingest -> the fused sample+unroll+Adam+priority step;
  anakin   ``cli.train --actor.on_device=true``: the fused act+train loop on
           the jitted Fake env, 64 lanes;
  serve    ``cli.serve`` (random init) answering 16 socket clients;
  dp4      the trainer and the anakin loop again with ``--mesh.dp=4`` — only
           when JAX reports >= 4 devices (else ``dp4: not run``).

It FAILS — non-zero exit, no result line — unless
``jax.devices()[0].platform == "tpu"``, and on any failed check: too few
learner steps, a non-finite loss, an unfilled replay, a crashed or respawned
actor child, a block transport other than the shm ring, a missing final
checkpoint, an unanswered / shed / timed-out request, a device peak that
says the replay ring was copied. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One process per chip: this parent never imports jax. Each phase is a child
process (``--phase NAME``) that holds the chip alone, runs the entry point
in-process, checks what came out, and reports compile seconds apart from run
seconds, persistent-cache hits and ``peak_bytes_in_use``; its memory is gone
before the next phase builds its own 6.6 GiB ring. Run files (checkpoints,
metrics, spans) go under ``--out`` (default: a temp dir, removed at the end),
never into the checkout; compiled programs go where
``r2d2_tpu.utils.platform.compile_cache_dir()`` says.
"""

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "PHASE_RESULT "
TOTAL_BUDGET_S = 1150.0          # the driver allows 1200 s, compiles included
PHASE_CAP_S = {"trainer": 480.0, "anakin": 300.0, "serve": 300.0,
               "dp4_trainer": 480.0, "dp4_anakin": 300.0}
MIN_STEPS = 64
SERVE_CLIENTS = 16
SERVE_STEPS = 20                 # requests per client after the reset


# ---------------------------------------------------------------------------
# parent: no jax here


def _kill_group(proc: subprocess.Popen) -> None:
    """Stop a phase child and everything it started (actor processes)."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            continue


def _run_phase_child(name: str, out: str, timeout_s: float, live: dict):
    """Run one phase in its own session; echo its output; return its
    PHASE_RESULT dict, or None when it died, timed out or reported none."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name,
         "--out", out],
        cwd=HERE, stdout=subprocess.PIPE, text=True, start_new_session=True)
    live["proc"] = proc
    timed_out = threading.Event()

    def on_deadline():
        timed_out.set()
        _kill_group(proc)

    timer = threading.Timer(timeout_s, on_deadline)
    timer.daemon = True
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith(RESULT_TAG):
                result = json.loads(line[len(RESULT_TAG):])
            else:
                print(f"[{name}] {line}", end="", flush=True)
        proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)           # stray grandchildren, if any
        live["proc"] = None
    if timed_out.is_set():
        print(f"chip_smoke: phase {name} exceeded {timeout_s:.0f}s — killed",
              file=sys.stderr)
        return None
    if proc.returncode != 0:
        print(f"chip_smoke: phase {name} exited {proc.returncode}",
              file=sys.stderr)
        return None
    return result


def _print_phase(name: str, res: dict) -> None:
    facts = res["facts"]
    print(f"== {name}: {'ok' if res['ok'] else 'FAILED'} in "
          f"{res['wall_s']:.1f}s (compile {res['compile_s']:.1f}s in "
          f"{res['compiles']} programs, run {res['run_s']:.1f}s; cache hits "
          f"{res['cache_hits']}, misses {res['cache_misses']}; peak device "
          f"memory {res['peak_bytes_in_use'] / 2**30:.2f} GiB)")
    for k, v in facts.items():
        print(f"   {k}: {v}")
    for k, passed in res["checks"].items():
        print(f"   [{'PASS' if passed else 'FAIL'}] {k}")


def parent_main(out_arg: str) -> int:
    if not os.path.isdir(os.path.join(HERE, "r2d2_tpu")):
        print("chip_smoke: the r2d2_tpu package is not beside this script; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    out = out_arg or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(out, exist_ok=True)
    live = {"proc": None}

    def on_signal(signum, frame):
        if live["proc"] is not None:
            _kill_group(live["proc"])
        sys.exit(128 + signum)

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)

    t0 = time.time()
    results = {}
    try:
        phases = ["trainer", "anakin", "serve", "dp4_trainer", "dp4_anakin"]
        for name in phases:
            if name.startswith("dp4"):
                count = results["trainer"]["device"]["count"]
                if count < 4:
                    print(f"== dp4: not run ({count} device)")
                    break
            left = TOTAL_BUDGET_S - (time.time() - t0)
            res = _run_phase_child(name, os.path.join(out, name),
                                   min(PHASE_CAP_S[name], max(left, 1.0)),
                                   live)
            if res is None:
                print(f"chip_smoke: FAILED — phase {name} gave no result",
                      file=sys.stderr)
                return 1
            _print_phase(name, res)
            if not res["ok"]:
                failed = [k for k, v in res["checks"].items() if not v]
                print(f"chip_smoke: FAILED — phase {name}: "
                      + "; ".join(failed), file=sys.stderr)
                return 1
            results[name] = res
        first = results["trainer"]
        print("== chip_smoke passed in "
              f"{time.time() - t0:.1f}s on {first['device']['kind']} x"
              f"{first['device']['count']}; compile "
              f"{sum(r['compile_s'] for r in results.values()):.1f}s, cache "
              f"hits {sum(r['cache_hits'] for r in results.values())}, "
              f"misses {sum(r['cache_misses'] for r in results.values())}; "
              f"jax {first['versions']['jax']} jaxlib "
              f"{first['versions']['jaxlib']} libtpu "
              f"{first['versions']['libtpu']}")
        print(json.dumps({"ok": True, "device": first["device"]}))
        return 0
    finally:
        if not out_arg:
            shutil.rmtree(out, ignore_errors=True)


# ---------------------------------------------------------------------------
# phase children: each holds the chip alone


class _CompileStats:
    """Compile seconds and persistent-cache traffic, from jax.monitoring."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0

    def install(self) -> "_CompileStats":
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, duration_secs: float,
                     **kwargs) -> None:
        # wraps compile_or_get_cached: a cache hit costs its read time here
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration_secs
            self.compiles += 1


def _peak_device_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def _records(path: str) -> list:
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def check_training(st, out: str, *, host_actors: bool, dp: int = 1):
    """Checks + facts for one finished training run (a PlayerStack or an
    AnakinStack as ``cli.train.main`` returned it) — read off the run's own
    objects and files, not off its exit code."""
    import jax
    import numpy as np

    from r2d2_tpu.runtime.checkpoint import (latest_checkpoint,
                                             restore_checkpoint)

    cfg, learner, metrics = st.cfg, st.learner, st.metrics
    steps = int(learner.training_steps)
    # every interval's mean loss, plus the tail flushed after the last log
    losses = [r["loss"] for r in _records(
        os.path.join(out, "metrics_player0.jsonl")) if r.get("loss") is not None]
    tail = metrics.training_steps - metrics.last_training_steps
    if tail > 0:
        losses.append(metrics.sum_loss / tail)
    ckpt = latest_checkpoint(out, cfg.env.game_name, 0)
    ckpt_step = int(restore_checkpoint(ckpt)["step"]) if ckpt else -1
    ring_bytes = int(learner.spec.device_ring_bytes)
    peak = _peak_device_bytes()

    facts = {
        "learner_steps": steps,
        "losses_flushed": int(metrics.training_steps),
        "loss_interval_means": [round(float(x), 5) for x in losses],
        "env_steps": int(learner.env_steps),
        "replay_steps": int(learner.ring.buffer_steps),
        "final_checkpoint": f"{os.path.basename(ckpt)} step {ckpt_step}"
                            if ckpt else None,
        "ring_bytes_per_device": ring_bytes,
    }
    checks = {
        f"learner steps >= {MIN_STEPS}": steps >= MIN_STEPS,
        "every loss flushed and finite": (
            metrics.training_steps == steps and len(losses) > 0
            and all(math.isfinite(x) for x in losses)),
        "replay filled past learning_starts": (
            learner.ring.buffer_steps >= cfg.replay.learning_starts),
        "env steps > 0": learner.env_steps > 0,
        "final checkpoint holds the last step": ckpt_step == steps,
        # every program that touches the ring must alias it in place; a
        # peak near twice the ring means one of them copied it
        "peak device memory < 1.5x the ring": 0 < peak < 1.5 * ring_bytes,
    }
    if host_actors:
        transport = type(st.queue._q).__name__
        exitcodes = [p.exitcode for p in st.processes
                     if hasattr(p, "exitcode")]
        spans = [os.path.join(out, f"spans_p0_a{i}.jsonl")
                 for i in range(cfg.actor.num_actors)]
        facts.update({
            "block_transport": transport,
            "actor_exitcodes": exitcodes,
            "actor_restarts": st.health.restarts,
            "actor_hangs": st.health.hangs_detected,
        })
        checks.update({
            "blocks came over the shm ring": transport == "ShmBlockRing",
            f"{cfg.actor.num_actors} actor children came up beside the "
            "chip holder": (len(exitcodes) == cfg.actor.num_actors and all(
                os.path.exists(s) and os.path.getsize(s) > 0
                for s in spans)),
            "no actor crash, hang or respawn": (
                st.health.restarts == 0 and st.health.hangs_detected == 0
                and st.health.breaker_trips == 0 and not st._seen_dead
                and all(c in (0, -signal.SIGTERM) for c in exitcodes)),
        })
    if dp > 1:
        ring_devs = {d for leaf in jax.tree_util.tree_leaves(
            learner.replay_state) for d in leaf.sharding.device_set}
        agree = all(
            all(np.array_equal(np.asarray(s.data),
                               np.asarray(leaf.addressable_shards[0].data))
                for s in leaf.addressable_shards)
            for leaf in jax.tree_util.tree_leaves(learner.train_state.params))
        facts["replay_shard_devices"] = sorted(d.id for d in ring_devs)
        checks[f"replay shards sit on {dp} distinct devices"] = (
            len(ring_devs) == dp and learner.mesh.devices.size == dp)
        checks[f"the {dp} parameter replicas agree"] = agree
    return checks, facts


def phase_trainer(out: str, extra=(), dp: int = 1):
    from r2d2_tpu.cli import train as cli_train
    stacks = cli_train.main([f"--max-steps={MIN_STEPS}", "--max-seconds=400",
                             f"--runtime.save_dir={out}", f"--mesh.dp={dp}",
                             *extra])
    return check_training(stacks[0], out, host_actors=True, dp=dp)


def phase_anakin(out: str, extra=(), dp: int = 1):
    from r2d2_tpu.cli import train as cli_train
    # episode_len must be a multiple of block_length (400) for the fused
    # scan's fixed-length blocks; nothing else departs from Config()
    stacks = cli_train.main(["--actor.on_device=true", "--env.episode_len=400",
                             f"--max-steps={MIN_STEPS}", "--max-seconds=240",
                             f"--runtime.save_dir={out}", f"--mesh.dp={dp}",
                             *extra])
    return check_training(stacks[0], out, host_actors=False, dp=dp)


def phase_serve(out: str, extra=()):
    """``cli.serve`` on the main thread (it owns the signal handlers), 16
    socket clients on threads; the clients' end stops the server."""
    import socket

    import numpy as np

    from r2d2_tpu.cli import serve as cli_serve
    from r2d2_tpu.config import Config, parse_overrides
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.serve import RemotePolicy, SocketChannel

    cfg = parse_overrides(Config(), list(extra))
    probe = create_env(cfg.env, seed=cfg.runtime.seed)
    action_dim = probe.action_space.n
    probe.close()
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    answered = [0] * SERVE_CLIENTS
    bad_actions = [0] * SERVE_CLIENTS
    errors = []
    retries = {"timeouts": 0, "reconnects": 0, "shed": 0}
    lock = threading.Lock()
    ready = threading.Barrier(SERVE_CLIENTS)

    def client(i: int) -> None:
        rng = np.random.default_rng(i)
        shape = (cfg.env.frame_height, cfg.env.frame_width)
        try:
            # the listener opens only once every bucket is compiled; dial
            # on the channel's own backoff ladder until then
            channel = SocketChannel("127.0.0.1", port, connect_retries=600,
                                    backoff_max_s=0.5, eager_connect=True)
            remote = RemotePolicy(channel, action_dim, 0.0, seed=i,
                                  client_id=i)
            ready.wait(timeout=60)
            remote.observe_reset(rng.integers(0, 255, shape, np.uint8))
            for _ in range(SERVE_STEPS):
                action, q, hidden = remote.act()
                ok = (0 <= action < action_dim and q.shape == (action_dim,)
                      and np.isfinite(q).all() and np.isfinite(hidden).all())
                answered[i] += 1
                bad_actions[i] += 0 if ok else 1
                remote.observe(rng.integers(0, 255, shape, np.uint8), action)
            with lock:
                retries["timeouts"] += remote.timeouts
                retries["reconnects"] += remote.reconnects
                retries["shed"] += remote.shed_retries
            remote.close()
        except Exception as e:   # reported as a failed check, with its text
            with lock:
                errors.append(f"client {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(SERVE_CLIENTS)]

    def stop_when_done() -> None:
        for t in threads:
            t.join()
        for err in errors:
            print(f"chip_smoke: {err}", file=sys.stderr, flush=True)
        os.kill(os.getpid(), signal.SIGTERM)    # cli.serve's clean stop

    for t in threads:
        t.start()
    threading.Thread(target=stop_when_done, daemon=True).start()
    rc = cli_serve.main(["--save-dir", out, "--seconds", "240",
                         f"--serve.port={port}", *extra])

    records = _records(os.path.join(out, "serve_metrics.jsonl"))
    blocks = [r["serving"] for r in records if "serving" in r]
    served = sum(b["requests"] for b in blocks)
    replies = sum(b["replies"] for b in blocks)
    expired = sum(b["expired"] for b in blocks)
    fill = max((b["batch"]["fill_p99"] or 0 for b in blocks), default=0)
    want = SERVE_CLIENTS * SERVE_STEPS
    facts = {
        "clients": SERVE_CLIENTS,
        "requests_answered": sum(answered),
        "server_requests": served,
        "server_replies": replies,
        "server_batches": records[-1]["batches"] if records else 0,
        "largest_batch_fill": fill,
        "client_retries": dict(retries),
        "client_errors": errors,
    }
    checks = {
        "cli.serve returned 0": rc == 0,
        f"all {want} requests answered": (sum(answered) == want
                                          and not errors),
        "every action in range, every Q and state finite":
            sum(bad_actions) == 0 and sum(answered) > 0,
        "nothing shed, expired, timed out or re-dialled": (
            expired == 0 and not any(retries.values())
            and all("admission" not in b or b["admission"]["shed"] == 0
                    for b in blocks)),
        "the server counted every request": served >= want and replies >= want,
        "micro-batches above 1 ran": fill > 1,
    }
    return checks, facts


PHASES = {
    "trainer": phase_trainer, "anakin": phase_anakin, "serve": phase_serve,
    # both training programs once more over a 4-device mesh
    "dp4_trainer": lambda out: phase_trainer(out, dp=4),
    "dp4_anakin": lambda out: phase_anakin(out, dp=4),
}
# what Config()'s "auto" switches must resolve to on the chip
EXPECTED_RESOLVED = {"bf16": True, "pallas_obs_decode": True,
                     "pallas_sample_gather": True,
                     "pallas_exact_gather": True, "steps_per_dispatch": 16,
                     "ingest_batch_blocks": 8}


def phase_main(name: str, out: str) -> int:
    t0 = time.time()
    os.makedirs(out, exist_ok=True)
    from r2d2_tpu.utils.platform import enable_compile_cache, runtime_report
    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU — jax.devices()[0].platform is "
              f"{dev.platform!r} ({dev.device_kind}); nothing was run",
              file=sys.stderr)
        return 3
    stats = _CompileStats().install()
    from r2d2_tpu.config import Config
    # (the entry point prints this report itself, as its ``runtime:`` line)
    report = runtime_report(Config())

    checks, facts = PHASES[name](out)
    resolved = report["resolved"]
    checks["auto switches resolved to their TPU side"] = all(
        resolved[k] == v for k, v in EXPECTED_RESOLVED.items())
    from r2d2_tpu.telemetry.costmodel import peak_spec
    peak_spec(dev.device_kind)      # raises if the chip is not in the table
    wall = time.time() - t0
    result = {
        "phase": name,
        "ok": all(checks.values()),
        "checks": checks,
        "facts": facts,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {k: report[k] for k in ("jax", "jaxlib", "libtpu")},
        "resolved": resolved,
        "compile_cache": report["compile_cache"],
        "wall_s": round(wall, 1),
        "compile_s": round(stats.compile_s, 1),
        "run_s": round(wall - stats.compile_s, 1),
        "compiles": stats.compiles,
        "cache_hits": stats.hits,
        "cache_misses": stats.misses,
        "peak_bytes_in_use": _peak_device_bytes(),
    }
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--out", default="",
                   help="directory for run files (default: a temp dir, "
                        "removed at the end)")
    p.add_argument("--phase", default="", choices=["", *PHASES],
                   help=argparse.SUPPRESS)   # internal: run one phase here
    args = p.parse_args(argv)
    if args.phase:
        return phase_main(args.phase, args.out)
    return parent_main(args.out)


if __name__ == "__main__":
    sys.exit(main())
