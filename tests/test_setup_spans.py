"""Set-up as spans of the program's one tree (PR 38): the compile monitor's
phases from jax's own events, the Learner's build and first dispatch, who
writes the spans file, and the benchmark's four readers of them."""

import json
import os
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from r2d2_tpu.telemetry import CompileMonitor, Telemetry, active_monitor
from tests.test_runtime import tiny_config

SETUP_READERS = ["setup_trace_lower_s", "setup_backend_s",
                 "setup_first_run_s", "setup_build_s"]


def _rows(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.fixture
def persistent_cache(tmp_path):
    """A persistent compile cache in ``tmp_path`` that takes every program,
    and the process's cache settings as they were afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update(keys[0], str(tmp_path / "cache"))
    jax.config.update(keys[1], 0.0)
    jax.config.update(keys[2], -1)
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_monitor_spans_each_phase_and_the_caches_answer(persistent_cache):
    tele = Telemetry()
    mon = CompileMonitor(tele).install()

    def body(x):
        return jnp.tanh(x) @ x

    f = jax.jit(body)
    x = jnp.ones((4, 4))
    try:
        with tele.stage("learner/train_dispatch", iter="setup"):
            f(x).block_until_ready()                 # built: a miss
        jax.clear_caches()
        with tele.stage("learner/train_dispatch", iter="setup"):
            f(x).block_until_ready()                 # read back: a hit
    finally:
        mon.uninstall()
    rows = tele.spans.drain()
    dispatches = [r for r in rows if r["name"] == "learner/train_dispatch"]
    mine = [[r for r in rows if r["parent"] == d["id"]
             and (r.get("tags") or {}).get("fn") in ("body", "jit(body)")]
            for d in dispatches]
    for kids, cache in zip(mine, ("miss", "hit")):
        by = {r["name"]: r for r in kids}
        assert set(by) == {"compile/trace", "compile/lower",
                           "compile/backend"}
        assert by["compile/trace"]["tags"] == {"fn": "body"}
        assert by["compile/lower"]["tags"] == {"fn": "jit(body)"}
        backend = by["compile/backend"]["tags"]
        assert backend["fn"] == "jit(body)" and backend["cache"] == cache
        assert ("cache_read_s" in backend) == (cache == "hit")
        # phases in order, on the span tree's clock, inside their dispatch
        order = [by[n] for n in ("compile/trace", "compile/lower",
                                 "compile/backend")]
        assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-6
                   for a, b in zip(order, order[1:]))
        assert all(r["iter"] == "setup" for r in kids)
    # jnp.tanh and the product trace inside ``body``'s trace: no span of
    # their own
    assert not [r for r in rows if (r.get("tags") or {}).get("fn")
                in ("tanh", "matmul")]
    totals = mon.totals()
    assert totals["cache_hits"] >= 1 and totals["cache_misses"] >= 1
    backend = [r for r in rows if r["name"] == "compile/backend"]
    assert totals["cache_hits"] + totals["cache_misses"] == len(backend)
    assert totals["backend_s"] == pytest.approx(
        sum(r["dur"] for r in backend), abs=2e-3)
    traced = [r for r in rows if r["name"] in ("compile/trace",
                                               "compile/lower")]
    assert totals["trace_lower_s"] == pytest.approx(
        sum(r["dur"] for r in traced), abs=2e-3)


def _span(name, ts, dur, tid="MainThread", **extra):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "id": 0,
            "parent": None, "iter": None, "self": dur, **extra}


def _ctx(tmp_path, rows):
    if rows is not None:
        with open(tmp_path / "spans_player0.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return SimpleNamespace(cfg=SimpleNamespace(
        runtime=SimpleNamespace(save_dir=str(tmp_path))))


def test_union_counts_nested_and_overlapping_spans_once_a_thread(tmp_path):
    from benchmarks.layer_metrics import setup_trace_lower_s
    rows = [_span("compile/trace", 10.0, 4.0),       # the step's trace
            _span("compile/trace", 11.0, 1.0),       # nested inside it
            _span("compile/lower", 13.5, 1.0),       # overlaps its end
            _span("compile/trace", 20.0, 0.5),
            _span("compile/trace", 10.0, 2.0, tid="stager"),
            _span("compile/backend", 14.5, 3.0)]     # not trace or lowering
    assert setup_trace_lower_s.read(_ctx(tmp_path, rows)) == pytest.approx(
        4.5 + 0.5 + 2.0)


READINGS = {
    "setup_trace_lower_s": 1.5,         # trace 1.0 ∪ lower 0.5
    "setup_backend_s": 2.25,            # 2.0 (a miss) + 0.25 (a hit)
    "setup_first_run_s": 0.75 + 0.5,    # the first dispatch's self + ready
    "setup_build_s": 3.0,
}


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_reader_reads_its_spans(tmp_path, metric):
    from benchmarks import harness
    rows = [
        _span("learner/build", 0.0, 3.0, iter="setup"),
        _span("compile/trace", 4.0, 1.0),
        _span("compile/lower", 5.0, 0.5),
        _span("compile/backend", 5.5, 2.0, tags={"fn": "jit(step)",
                                                 "cache": "miss"}),
        _span("compile/backend", 8.0, 0.25, tags={"fn": "jit(f)",
                                                  "cache": "hit"}),
        _span("learner/train_dispatch", 3.5, 4.5,
              tags={"k": 4, "step": 0, "first": 1}, self=0.75),
        _span("learner/first_ready", 8.0, 0.5),
        _span("learner/train_dispatch", 9.0, 0.1, tags={"k": 4, "step": 4}),
    ]
    read = harness.reader_of(metric).read
    assert read(_ctx(tmp_path, rows)) == pytest.approx(READINGS[metric])


@pytest.mark.parametrize("metric", SETUP_READERS)
def test_reader_finds_nothing_without_its_span_or_the_file(tmp_path, metric):
    from benchmarks import harness
    read = harness.reader_of(metric).read
    assert read(_ctx(tmp_path, None)) is None
    # the spans a checkout from before PR 38 writes
    rows = [_span("learner/train_dispatch", 1.0, 0.1, tags={"k": 4}),
            _span("learner/device_sync", 2.0, 0.1)]
    assert read(_ctx(tmp_path, rows)) is None


# the cells the four were declared in (PR 38); later cells join the lists.
# ``lfm2-core.learner-long`` is not among them: test_bm_lfm2.py holds its
# per-layer metrics to an exact set, a file only a ``benchmark`` PR may edit
PR38_CELLS = ["r2d2-ref.learner", "r2d2-paper.learner", "r2d2-ref.anakin",
              "moonlight-core.learner-long"]


def test_benchmark_declares_the_four_in_every_cell_of_their_pr():
    from benchmarks import harness
    from tests.benchmarks import bm_structure
    bench = harness.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in SETUP_READERS:
        m = entries[name]
        assert (m["layer"], m["moves"], m["source"], m["unit"],
                m["better"]) == ("setup", "setup_s", "program_span", "s",
                                 "lower")
        assert bm_structure.in_order(PR38_CELLS, m["workloads"])
    assert bm_structure.in_order(SETUP_READERS,
                                 bm_structure.per_layer_names(bench))


def _learner(cfg, **kwargs):
    from r2d2_tpu.models.network import NetworkApply
    from r2d2_tpu.runtime.learner_loop import Learner
    net = NetworkApply(4, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    return Learner(cfg, net, **kwargs)


def test_a_learner_on_its_own_writes_its_set_up_and_starts_no_thread(
        tmp_path):
    cfg = tiny_config(tmp_path)
    threads = set(threading.enumerate())
    learner = _learner(cfg)
    assert learner.metrics.telemetry is learner._own_telemetry
    assert active_monitor() is learner.compile_monitor is not None
    learner.step()
    path = tmp_path / "spans_player0.jsonl"
    # written as set-up closes, before any stop
    written = _rows(path)
    learner.step()
    assert set(threading.enumerate()) == threads, "no thread started"
    learner.stop_background()
    assert active_monitor() is None
    rows = _rows(path)
    assert rows[:len(written)] == written           # appended, not rewritten
    assert len({r["id"] for r in rows}) == len(rows)
    by_id = {r["id"]: r for r in rows}
    (build,) = [r for r in rows if r["name"] == "learner/build"]
    assert build["parent"] is None and build["iter"] == "setup"
    assert build["pid"] == "learner-p0"
    children = {r["name"] for r in rows if r["parent"] == build["id"]}
    assert {"learner/create_train_state", "learner/apply_restore",
            "learner/replay_init"} <= children
    # build-time compiles hang under what made them
    made = [r for r in rows if r["name"] == "compile/backend"
            and r["iter"] == "setup"]
    assert {by_id[r["parent"]]["name"] for r in made} >= {
        "learner/create_train_state"}
    dispatches = [r for r in rows if r["name"] == "learner/train_dispatch"]
    assert len(dispatches) == 2
    first, second = sorted(dispatches, key=lambda r: r["ts"])
    assert first["tags"]["first"] == 1 and "first" not in second["tags"]
    (ready,) = [r for r in rows if r["name"] == "learner/first_ready"]
    assert ready["parent"] == first["parent"]
    assert by_id[ready["parent"]]["name"] == "learner/step"
    assert first["ts"] + first["dur"] <= ready["ts"] + 1e-6
    # the step program's build sits inside its first dispatch, whose own
    # time is what the phases leave
    step = [r for r in rows if r["parent"] == first["id"]]
    assert {r["name"] for r in step} >= {"compile/trace", "compile/lower",
                                         "compile/backend"}
    assert first["self"] == pytest.approx(
        first["dur"] - sum(r["dur"] for r in step), abs=1e-6)
    assert {r["name"] for r in written} >= {
        "learner/build", "learner/train_dispatch", "learner/first_ready"}


def test_a_learner_handed_a_telemetry_builds_none_and_writes_nothing(
        tmp_path):
    from r2d2_tpu.runtime.metrics import TrainMetrics
    cfg = tiny_config(tmp_path)
    tele = Telemetry.from_config(cfg, name="caller")
    metrics = TrainMetrics(0, str(tmp_path))
    metrics.set_telemetry(tele)
    path = tmp_path / "spans_player0.jsonl"
    tele.start_drain(str(path))
    learner = _learner(cfg, metrics=metrics)
    try:
        assert learner._own_telemetry is None
        assert learner.metrics.telemetry is tele
        assert learner.compile_monitor._telemetry is tele
        learner.step()
        # set-up's end wakes the one writer, the caller's drain thread
        deadline = time.time() + 10
        while not os.path.getsize(path) and time.time() < deadline:
            time.sleep(0.05)
    finally:
        learner.stop_background()
        tele.close()
    rows = _rows(path)
    assert {r["pid"] for r in rows} == {"caller"}
    assert len({r["id"] for r in rows}) == len(rows), "one writer"
    assert [r["name"] for r in rows].count("learner/build") == 1
    assert [r["name"] for r in rows].count("learner/first_ready") == 1


def test_telemetry_off_builds_no_telemetry_and_no_monitor(tmp_path):
    cfg = tiny_config(tmp_path, **{"telemetry.enabled": False})
    learner = _learner(cfg)
    learner.step()
    learner.stop_background()
    assert learner._own_telemetry is None and learner.compile_monitor is None
    assert not os.path.exists(tmp_path / "spans_player0.jsonl")


def test_the_player_stack_takes_over_its_learners_monitor(tmp_path):
    from r2d2_tpu.envs.factory import create_env
    from r2d2_tpu.runtime.orchestrator import PlayerStack
    cfg = tiny_config(tmp_path)
    probe = create_env(cfg.env)
    stack = PlayerStack(cfg, 0, probe.action_space.n)
    try:
        mon = stack.compile_monitor
        assert mon is stack.learner.compile_monitor is active_monitor()
        # bound to the stack's Telemetry: cli.train records compile spans,
        # the build's included, and the resources block counts them
        assert mon._telemetry is stack.telemetry
        assert stack.resources.compile_monitor is mon
        assert mon.totals()["compiles_total"] > 0
        built = [r for r in stack.telemetry.spans.drain()
                 if r["name"] == "compile/backend"]
        assert built and all(r["iter"] == "setup" for r in built)
    finally:
        stack.close()
    assert active_monitor() is None


def test_spans_export_shows_a_build_under_its_parent(tmp_path):
    from r2d2_tpu.tools.inspect import export_chrome_trace
    tele = Telemetry()
    mon = CompileMonitor(tele).install()
    try:
        with tele.stage("learner/train_dispatch", iter="setup", first=1):
            jax.jit(lambda x: x * 3.0 - 1.0)(jnp.ones(5)).block_until_ready()
    finally:
        mon.uninstall()
    with open(tmp_path / "spans_player0.jsonl", "w") as f:
        for ev in tele.spans.drain():
            f.write(json.dumps({**ev, "pid": "learner-p0"}) + "\n")
    out = tmp_path / "trace.json"
    assert export_chrome_trace(str(tmp_path), str(out)) > 0
    with open(out) as f:
        events = json.load(f)
    events = events["traceEvents"] if isinstance(events, dict) else events
    spans = [e for e in events if e["ph"] == "X"]
    (dispatch,) = [e for e in spans if e["name"] == "learner/train_dispatch"]
    builds = [e for e in spans if e["name"].startswith("compile/")]
    assert {e["name"] for e in builds} == {
        "compile/trace", "compile/lower", "compile/backend"}
    for e in builds:
        assert e["args"]["parent"] == dispatch["args"]["id"]
        assert "lambda" in e["args"]["fn"]
        assert dispatch["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= dispatch["ts"] + dispatch["dur"] + 1
    (backend,) = [e for e in builds if e["name"] == "compile/backend"]
    assert backend["args"]["cache"] in ("hit", "miss", "off")


def test_inspect_panel_shows_what_the_builds_cost():
    from r2d2_tpu.telemetry.compile import _BACKEND, _TRACE
    from r2d2_tpu.tools.inspect import render_resources
    mon = CompileMonitor()
    mon._on_phase_start(_TRACE, 10.0, "step")
    mon._on_phase_end(_TRACE, 10.0, 10.4)
    mon._on_phase_start(_BACKEND, 10.4, "jit(step)")
    mon._on_cache("hit", read_s=0.2)
    mon._on_phase_end(_BACKEND, 10.4, 10.7)
    text = render_resources({"compile": mon.interval_summary()})
    assert "trace+lower=0.4s cache hits=1 misses=0" in text
    # a record from before PR 38 renders as it did
    assert "trace+lower" not in render_resources(
        {"compile": {"compiles_total": 1, "compile_time_s_total": 0.3}})
