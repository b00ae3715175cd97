"""Telemetry subsystem tests (ISSUE 4): histogram percentiles and merge,
span tracer ring semantics, cross-process board aggregation, the
aggregated TrainMetrics record (including PR-2/3 schema stability and the
logparse round-trip), profiler capture lifecycle, and a slow end-to-end
slice proving the whole pipeline emits fleet-wide stage percentiles.
"""

import json
import os
import pickle
import threading
import time

import numpy as np
import pytest

from r2d2_tpu.telemetry import (NBUCKETS, NULL_TELEMETRY, STAGES,
                                LogHistogram, ProfilerCapture, SpanTracer,
                                StageTimers, Telemetry, TelemetryBoard,
                                bucket_bounds, bucket_index, bucket_mid,
                                chrome_trace_events, percentile, summarize)
from r2d2_tpu.tools.logparse import parse_jsonl, parse_log


# ---------------------------------------------------------------------------
# histograms

def test_bucket_index_monotonic_and_bounded():
    durations = [1e-9, 1e-7, 1e-6, 1e-5, 1e-3, 0.1, 1.0, 10.0, 99.0, 1e4]
    idx = [bucket_index(d) for d in durations]
    assert idx == sorted(idx)
    assert all(0 <= i < NBUCKETS for i in idx)
    assert bucket_index(0.0) == 0
    assert bucket_index(1e9) == NBUCKETS - 1


def test_bucket_value_inside_bounds():
    for i in (0, 1, 17, NBUCKETS - 1):
        lo, hi = bucket_bounds(i)
        assert lo < bucket_mid(i) < hi
        # a duration at the midpoint maps back into its own bucket
        assert bucket_index(bucket_mid(i)) == i


def test_percentile_known_distribution():
    h = LogHistogram()
    # 90 fast observations at ~1 ms, 10 slow at ~1 s: P50 must report the
    # fast mode, P99 the slow tail — the exact property interval means hide
    for _ in range(90):
        h.add(1e-3)
    for _ in range(10):
        h.add(1.0)
    p50, p99 = h.percentile(0.50), h.percentile(0.99)
    assert 0.5e-3 < p50 < 2e-3
    assert 0.5 < p99 < 2.0
    assert h.total == 100


def test_percentile_resolution_is_bucket_bounded():
    # one observation: every percentile reports its bucket midpoint, and
    # the midpoint is within one bucket's growth factor (~33%) of truth
    h = LogHistogram()
    h.add(0.0123)
    lo, hi = bucket_bounds(bucket_index(0.0123))
    assert lo <= h.percentile(0.5) <= hi
    assert hi / lo < 1.4


def test_histogram_merge_equals_combined():
    rng = np.random.default_rng(0)
    a, b, both = LogHistogram(), LogHistogram(), LogHistogram()
    for d in rng.uniform(1e-5, 1e-2, 200):
        a.add(d), both.add(d)
    for d in rng.uniform(1e-3, 1.0, 300):
        b.add(d), both.add(d)
    merged = a.merge(b)
    np.testing.assert_array_equal(merged.counts, both.counts)
    for q in (0.5, 0.95, 0.99):
        assert merged.percentile(q) == both.percentile(q)


def test_empty_histogram():
    h = LogHistogram()
    assert h.percentile(0.5) is None
    assert h.summarize() is None
    assert summarize(np.zeros(NBUCKETS, np.int64)) is None


def test_summarize_schema():
    h = LogHistogram()
    h.add(0.01)
    s = h.summarize()
    assert set(s) == {"count", "p50_ms", "p95_ms", "p99_ms"}
    assert s["count"] == 1
    assert s["p50_ms"] == s["p99_ms"]
    assert 5.0 < s["p50_ms"] < 20.0          # ms units


# ---------------------------------------------------------------------------
# stage timers

def test_stage_timers_take_is_per_interval():
    st = StageTimers()
    st.observe("actor/env_step", 1e-3)
    st.observe("actor/env_step", 2e-3)
    st.observe("ingest/commit", 0.1)
    first = st.take()
    assert first.sum() == 3
    assert first[STAGES.index("actor/env_step")].sum() == 2
    # nothing new -> empty interval; cumulative stays monotonic
    assert st.take().sum() == 0
    st.observe("ingest/commit", 0.2)
    assert st.take().sum() == 1
    assert st.cumulative().sum() == 4


def test_stage_timers_unknown_stage_raises():
    with pytest.raises(KeyError):
        StageTimers().observe("actor/definitely_not_a_stage", 1.0)


def test_stage_timers_thread_safety():
    st = StageTimers()

    def worker():
        for _ in range(500):
            st.observe("actor/forward", 1e-4)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert st.cumulative().sum() == 2000


# ---------------------------------------------------------------------------
# span tracer

def test_span_tracer_records_and_drains():
    tr = SpanTracer(ring_size=64)
    tr.record("a", 1.0, 1.5, {"k": 1})
    tr.record("b", 2.0, 2.25)
    events = tr.drain()
    assert [e["name"] for e in events] == ["a", "b"]
    assert events[0]["dur"] == pytest.approx(0.5)
    assert events[0]["tags"] == {"k": 1}
    assert "tid" in events[0]
    assert tr.drain() == []          # drained


def test_span_tracer_ring_drops_oldest():
    tr = SpanTracer(ring_size=16)
    for i in range(40):
        tr.record(f"s{i}", float(i), float(i) + 0.1)
    events = tr.drain()
    assert len(events) == 16
    assert events[-1]["name"] == "s39"   # newest survives
    assert tr.dropped == 40 - 16


def test_span_tracer_disabled_is_noop():
    tr = SpanTracer(ring_size=16, enabled=False)
    tr.record("a", 0.0, 1.0)
    assert tr.drain() == []
    # spans off alone: a stage still feeds its histogram row, records no
    # span, and a name outside STAGES has nothing left to do
    tele = Telemetry(spans=False)
    with tele.stage("actor/act_scan", lanes=2):
        pass
    with tele.stage("anakin/poll"):
        pass
    assert tele.spans.drain() == []
    assert tele.interval_summary()["actor/act_scan"]["count"] == 1


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: counts what is open."""
    open_now = 0

    def __init__(self, name, **meta):
        self.name, self.meta = name, meta

    def __enter__(self):
        type(self).open_now += 1
        return self

    def __exit__(self, *exc):
        type(self).open_now -= 1


def test_stage_closes_span_and_annotation_on_raise(monkeypatch):
    tele = Telemetry()
    monkeypatch.setattr(_FakeAnnotation, "open_now", 0)
    tele.spans._annotate = _FakeAnnotation
    with pytest.raises(RuntimeError):
        with tele.stage("anakin/iteration", iter=4):
            with tele.stage("boom", slot=3):
                assert _FakeAnnotation.open_now == 2
                raise RuntimeError("x")
    assert _FakeAnnotation.open_now == 0
    assert tele.spans._local.stack == []
    boom, root = sorted(tele.spans.drain(), key=lambda e: -e["id"])
    assert boom["name"] == "boom" and boom["tags"] == {"slot": 3}
    assert boom["parent"] == root["id"] and boom["iter"] == 4
    # the next span on this thread is a root again
    with tele.stage("after"):
        pass
    (after,) = tele.spans.drain()
    assert after["parent"] is None and after["iter"] is None


def test_stage_nests_parent_iter_self_across_two_threads():
    tele = Telemetry()
    barrier = threading.Barrier(2)

    def loop(iteration):
        barrier.wait(timeout=10)      # both roots open at once
        with tele.stage("anakin/iteration", iter=iteration) as root:
            with tele.stage("actor/act_scan", lanes=1):
                time.sleep(0.02)
                barrier.wait(timeout=10)
            with tele.stage("learner/step"):
                with tele.stage("learner/train_dispatch"):
                    time.sleep(0.01)
                # a span that is over already (the compile listener's
                # path) hangs under the open one
                now = time.time()
                tele.record_span("compile/backend", now - 0.004, now,
                                 {"fn": "f"})
            time.sleep(0.005)
            root.tag(train_steps=1)

    threads = [threading.Thread(target=loop, args=(i,), name=f"loop{i}")
               for i in (10, 11)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    events = tele.spans.drain()
    assert len({e["id"] for e in events}) == len(events) == 10
    for i in (10, 11):
        mine = {e["name"]: e for e in events if e["tid"] == f"loop{i}"}
        root, step = mine["anakin/iteration"], mine["learner/step"]
        assert root["parent"] is None and root["tags"] == {"train_steps": 1}
        assert all(e["iter"] == i for e in mine.values())
        assert mine["actor/act_scan"]["parent"] == root["id"]
        assert step["parent"] == root["id"]
        assert mine["learner/train_dispatch"]["parent"] == step["id"]
        assert mine["compile/backend"]["parent"] == step["id"]
        # self = duration less what the direct children cover
        assert root["self"] == pytest.approx(
            root["dur"] - mine["actor/act_scan"]["dur"] - step["dur"])
        assert 0.004 < root["self"] < root["dur"]
        assert step["self"] == pytest.approx(
            step["dur"] - mine["learner/train_dispatch"]["dur"]
            - mine["compile/backend"]["dur"])
        leaf = mine["learner/train_dispatch"]
        assert leaf["self"] == pytest.approx(leaf["dur"])
    # the stage histogram was fed where the name is one of STAGES, only
    summary = tele.interval_summary()
    assert summary["actor/act_scan"]["count"] == 2
    assert summary["learner/train_dispatch"]["count"] == 2
    assert "anakin/iteration" not in summary


def test_stage_disabled_reads_no_clock(monkeypatch):
    from r2d2_tpu.telemetry import core, spans

    def no_clock():
        raise AssertionError("a disabled stage read the clock")

    off = Telemetry(enabled=False)
    monkeypatch.setattr(core.time, "time", no_clock)
    monkeypatch.setattr(spans.time, "time", no_clock)
    with off.stage("actor/act_scan", iter=1, lanes=2) as st:
        st.tag(more=1)
    with NULL_TELEMETRY.stage("learner/step"):
        pass
    assert off.spans.drain() == []


def test_stage_jsonl_row_keeps_old_keys_and_gains_the_tree(tmp_path):
    tele = Telemetry(name="anakin-p0", flush_interval_s=60.0)
    path = str(tmp_path / "spans_player0.jsonl")
    tele.start_drain(path)
    t0 = time.time()
    with tele.stage("anakin/iteration", iter=0, step=16):
        with tele.stage("actor/act_scan", lanes=64):
            pass
    tele.close()
    root, child = parse_jsonl(path)
    # what benchmarks/runners/anakin.py and tools/inspect.py read
    assert {"name", "ts", "dur", "tid", "tags", "pid"} <= set(child)
    assert child["pid"] == "anakin-p0" and child["tid"] == "MainThread"
    assert child["tags"] == {"lanes": 64}
    assert t0 <= root["ts"] <= child["ts"] <= time.time()   # unix seconds
    assert child["ts"] + child["dur"] <= root["ts"] + root["dur"]
    assert set(child) - {"name", "ts", "dur", "tid", "tags", "pid"} == {
        "id", "parent", "iter", "self"}
    assert (child["parent"], child["iter"]) == (root["id"], 0)


def test_span_tracer_prunes_dead_thread_rings():
    tr = SpanTracer(ring_size=16)
    for i in range(3):
        t = threading.Thread(target=lambda i=i: tr.record(
            f"w{i}", float(i), float(i) + 0.1))
        t.start()
        t.join()
    assert len(tr._rings) == 3
    events = tr.drain()
    assert len(events) == 3
    # drained rings of dead threads are pruned — a crash-looping soak's
    # respawned workers must not grow the registry without bound
    assert tr._rings == []


def test_span_tracer_multi_thread_rings():
    tr = SpanTracer(ring_size=64)

    def worker(i):
        tr.record(f"w{i}", float(i), float(i) + 0.1)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.record("main", 10.0, 10.1)
    events = tr.drain()
    assert {e["name"] for e in events} == {"w0", "w1", "w2", "main"}
    assert len({e["tid"] for e in events}) == 4


# ---------------------------------------------------------------------------
# cross-process board

def test_board_publish_read_roundtrip_via_pickle():
    board = TelemetryBoard(2)
    try:
        attached = pickle.loads(pickle.dumps(board))   # the spawn path
        counts = np.zeros((len(STAGES), NBUCKETS), np.int64)
        counts[STAGES.index("actor/forward"), 10] = 7
        attached.publish(1, counts)
        table = board.read()
        assert table.shape == (2, len(STAGES), NBUCKETS)
        assert table[1, STAGES.index("actor/forward"), 10] == 7
        assert table[0].sum() == 0
        attached.close()
    finally:
        board.close()


def test_board_take_deltas_interval_and_slot_reset():
    board = TelemetryBoard(2)
    try:
        row = np.zeros((len(STAGES), NBUCKETS), np.int64)
        fwd = STAGES.index("actor/forward")
        row[fwd, 5] = 10
        board.publish(0, row)
        d1 = board.take_deltas()
        assert d1[fwd, 5] == 10
        # cumulative grows by 5 -> next interval sees exactly the 5
        row[fwd, 5] = 15
        board.publish(0, row)
        assert board.take_deltas()[fwd, 5] == 5
        # respawn: slot restarts from zero, then publishes 3 — the reset
        # detection must take the fresh cumulative as the delta, never a
        # clipped negative
        board.reset_slot(0)
        row2 = np.zeros_like(row)
        row2[fwd, 5] = 3
        board.publish(0, row2)
        assert board.take_deltas()[fwd, 5] == 3
    finally:
        board.close()


def test_telemetry_facade_merges_local_and_board():
    board = TelemetryBoard(1)
    try:
        worker = Telemetry(name="worker", board=pickle.loads(
            pickle.dumps(board)), slot=0)
        worker.observe("actor/env_step", 1e-3)
        worker.observe("actor/env_step", 1e-3)
        worker.flush()
        agg = Telemetry(name="agg")
        agg.attach_board(board)
        agg.observe("learner/train_dispatch", 0.05)
        summary = agg.interval_summary()
        assert summary["actor/env_step"]["count"] == 2
        assert summary["learner/train_dispatch"]["count"] == 1
        # interval consumed: a second take with no new data is empty
        assert agg.interval_summary() == {}
    finally:
        board.close()


def test_null_telemetry_is_inert():
    NULL_TELEMETRY.observe("actor/env_step", 1.0)
    NULL_TELEMETRY.record_span("x", 0.0, 1.0)
    with NULL_TELEMETRY.stage("y", iter=0) as st:
        st.tag(z=1)
    assert NULL_TELEMETRY.spans.drain() == []
    assert NULL_TELEMETRY.interval_summary() == {}
    assert not NULL_TELEMETRY.enabled


def test_telemetry_drain_thread_flushes_spans_and_board(tmp_path):
    board = TelemetryBoard(1)
    try:
        worker = Telemetry(name="w", board=board, slot=0,
                           flush_interval_s=0.05)
        path = str(tmp_path / "spans_w.jsonl")
        worker.start_drain(path)
        worker.observe("actor/block_emit", 0.01)
        worker.record_span("actor/block_emit", 1.0, 1.01)
        time.sleep(0.3)
        worker.close()
        events = parse_jsonl(path)
        assert any(e["name"] == "actor/block_emit" for e in events)
        assert events[0]["pid"] == "w"
        assert board.read().sum() == 1
    finally:
        board.close()


# ---------------------------------------------------------------------------
# chrome-trace export

def test_chrome_trace_events_schema():
    tr = SpanTracer(ring_size=16)
    tr.record("stage/a", 1.0, 1.5, {"slot": 0})
    events = chrome_trace_events(tr.drain(), pid="actor-0", pid_index=3)
    x = [e for e in events if e["ph"] == "X"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(x) == 1
    # the tree rides in args beside the tags (a root: no parent, no iter)
    assert x[0]["args"] == {"slot": 0, "id": 1}
    assert x[0]["ts"] == pytest.approx(1.0e6)
    assert x[0]["dur"] == pytest.approx(0.5e6)
    assert x[0]["pid"] == 3
    assert {m["name"] for m in meta} == {"process_name", "thread_name"}


def test_export_chrome_trace_merges_files(tmp_path):
    from r2d2_tpu.tools.inspect import export_chrome_trace
    for proc in ("p0_a0", "player0"):
        with open(tmp_path / f"spans_{proc}.jsonl", "w") as f:
            for i in range(3):
                f.write(json.dumps({
                    "name": "actor/block_emit", "ts": 100.0 + i,
                    "dur": 0.5, "tid": "t", "pid": proc, "id": 10 + i,
                    "parent": 9, "iter": 4, "self": 0.5}) + "\n")
    out = str(tmp_path / "trace.json")
    n = export_chrome_trace(str(tmp_path), out)
    assert n == 6
    trace = json.load(open(out))
    x = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(x) == 6
    assert x[0]["args"] == {"id": 10, "parent": 9, "iter": 4}
    assert len({e["pid"] for e in x}) == 2   # one pid row per process


# ---------------------------------------------------------------------------
# TrainMetrics aggregation + schema stability + logparse round-trip

# Every key PR 2 (ingestion observability) and PR 3 (worker health) added
# to the periodic record — the aggregation refactor must not lose one.
PR23_RECORD_KEYS = {
    # base
    "t", "buffer_size", "buffer_speed", "env_steps", "avg_episode_return",
    "training_steps", "training_speed", "loss", "dropped_priority_updates",
    # PR 2: ingestion observability
    "ingest_blocks_total", "ingest_drains", "ingest_blocks_per_drain",
    "ingest_drain_latency_ms", "ingest_queue_depth", "ingest_pause_time",
    # PR 3: worker health
    "actor_restarts", "actor_hangs_detected", "actor_breaker_trips",
    "actor_parked_slots", "shm_slots_recovered", "ingest_stall_dumps",
    "heartbeat_age_max_s",
}


def _metrics(tmp_path, **kwargs):
    from r2d2_tpu.runtime.metrics import TrainMetrics
    return TrainMetrics(0, str(tmp_path), **kwargs)


def test_record_schema_stability_with_telemetry(tmp_path):
    m = _metrics(tmp_path)
    tele = Telemetry(name="t")
    m.set_telemetry(tele)
    tele.observe("learner/train_dispatch", 0.02)
    m.on_block(20, 1.5)
    m.on_train_step(0.5)
    record = m.log(10.0)
    missing = PR23_RECORD_KEYS - set(record)
    assert not missing, f"aggregation refactor dropped keys: {missing}"
    assert "stages" in record and "telemetry_dropped_spans" in record
    assert record["stages"]["learner/train_dispatch"]["count"] == 1


def test_record_omits_stages_when_disabled(tmp_path):
    m = _metrics(tmp_path)     # default telemetry attr is NULL
    record = m.log(10.0)
    assert "stages" not in record
    assert "telemetry_dropped_spans" not in record
    assert PR23_RECORD_KEYS <= set(record)


def test_jsonl_roundtrip_of_aggregated_record(tmp_path):
    m = _metrics(tmp_path)
    tele = Telemetry(name="t")
    m.set_telemetry(tele)
    for _ in range(5):
        tele.observe("actor/env_step", 1e-3)
    tele.observe("ingest/commit", 0.2)
    m.on_block(20, 2.0)
    written = m.log(5.0)
    tele.observe("actor/env_step", 1e-3)
    written2 = m.log(5.0)
    records = parse_jsonl(str(tmp_path / "metrics_player0.jsonl"))
    assert len(records) == 2
    assert records[0] == json.loads(json.dumps(written))
    assert records[1]["stages"]["actor/env_step"]["count"] == 1
    assert records[0]["stages"]["ingest/commit"]["p99_ms"] > \
        records[0]["stages"]["actor/env_step"]["p99_ms"]
    assert json.loads(json.dumps(written2)) == records[1]
    # the human log alongside still parses with the reference parser
    parsed = parse_log(str(tmp_path / "train_player0.log"))
    assert len(parsed.buffer_sizes) == 2


def test_parse_jsonl_skips_partial_trailing_line(tmp_path):
    path = tmp_path / "m.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"a": 1}) + "\n")
        f.write('{"a": 2, "tr')          # writer mid-append
    assert parse_jsonl(str(path)) == [{"a": 1}]


def test_metrics_fresh_run_truncates_resume_appends(tmp_path):
    m1 = _metrics(tmp_path)
    m1.log(1.0)
    m1.log(1.0)
    # resume: both the human log and the JSONL keep their history
    m2 = _metrics(tmp_path, resume=True)
    m2.log(1.0)
    assert len(parse_jsonl(str(tmp_path / "metrics_player0.jsonl"))) == 3
    assert len(parse_log(str(tmp_path / "train_player0.log")).buffer_sizes) == 3
    # fresh: both truncate
    m3 = _metrics(tmp_path)
    m3.log(1.0)
    assert len(parse_jsonl(str(tmp_path / "metrics_player0.jsonl"))) == 1
    assert len(parse_log(str(tmp_path / "train_player0.log")).buffer_sizes) == 1


def test_put_patient_observes_queue_wait():
    import queue

    from r2d2_tpu.runtime.feeder import put_patient
    q = queue.Queue(maxsize=4)
    tele = Telemetry(name="t")
    assert put_patient(q, "block", should_stop=lambda: False,
                       telemetry=tele)
    summary = tele.interval_summary()
    assert summary["actor/queue_put"]["count"] == 1


# ---------------------------------------------------------------------------
# config

def test_config_missing_telemetry_section_defaults():
    from r2d2_tpu.config import Config
    d = Config().to_dict()
    d.pop("telemetry")
    cfg = Config.from_dict(d)                # pre-telemetry checkpoint
    assert cfg.telemetry.enabled is True
    assert Config.from_json(Config().to_json()).telemetry.ring_size == 4096


def test_config_validates_telemetry_fields():
    from r2d2_tpu.config import Config
    with pytest.raises(ValueError, match="ring_size"):
        Config().replace(**{"telemetry.ring_size": 2})
    with pytest.raises(ValueError, match="flush_interval_s"):
        Config().replace(**{"telemetry.flush_interval_s": 0.0})
    with pytest.raises(ValueError, match="profile_at_step"):
        Config().replace(**{"runtime.profile_at_step": -1})


# ---------------------------------------------------------------------------
# profiler capture lifecycle (monkeypatched jax.profiler: the state
# machine is what satellite 2 fixes — no real trace needed)

class _FakeProfiler:
    def __init__(self):
        self.starts = 0
        self.stops = 0
        self.active = False

    def start_trace(self, out_dir):
        if self.active:
            raise RuntimeError("trace already active")
        self.active = True
        self.starts += 1

    def stop_trace(self):
        if not self.active:
            raise RuntimeError("no trace active")
        self.active = False
        self.stops += 1


@pytest.fixture
def fake_profiler(monkeypatch):
    import jax
    fake = _FakeProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    return fake


def test_profiler_capture_stop_is_idempotent(fake_profiler):
    cap = ProfilerCapture()
    cap.stop()                       # no capture: must not touch jax
    assert fake_profiler.stops == 0
    assert cap.start("/tmp/x")
    assert not cap.start("/tmp/y")   # second start refused, no state harm
    cap.stop()
    cap.stop()                       # the old double-stop path: now a no-op
    assert fake_profiler.starts == 1
    assert fake_profiler.stops == 1
    assert cap.captures == 1


def test_profiler_capture_poll_bounds_window(fake_profiler):
    cap = ProfilerCapture()
    cap.start("/tmp/x", duration_s=10.0)
    t0 = time.time()
    assert not cap.poll(t0 + 5.0)
    assert cap.active
    assert cap.poll(t0 + 11.0)
    assert not cap.active
    assert not cap.poll(t0 + 12.0)   # already stopped


def test_profiler_trace_contextmanager_stops_on_raise(fake_profiler):
    from r2d2_tpu.telemetry.profiler import trace
    with pytest.raises(RuntimeError, match="boom"):
        with trace("/tmp/x"):
            assert fake_profiler.active
            raise RuntimeError("boom")
    assert not fake_profiler.active
    assert fake_profiler.stops == 1


# ---------------------------------------------------------------------------
# inspector rendering

def test_render_record_includes_stage_table():
    from r2d2_tpu.tools.inspect import render_record
    record = {"t": 12.0, "env_steps": 100, "training_steps": 4,
              "buffer_size": 80, "buffer_speed": 10.0,
              "training_speed": 0.4, "loss": 0.1,
              "ingest_blocks_total": 5, "ingest_queue_depth": 0,
              "ingest_pause_time": 0.0, "actor_restarts": 1,
              "stages": {"actor/forward": {"count": 3, "p50_ms": 1.0,
                                           "p95_ms": 2.0, "p99_ms": 3.0}}}
    frame = render_record(record, [{"rank": 1, "t": 11.0,
                                    "stages": {"x": {}}}])
    assert "actor/forward" in frame
    assert "p99 ms" in frame
    assert "restarts=1" in frame
    # host rows render as the per-rank fleet panel (ISSUE 12 replaced
    # the one-line "host rank r: N stages" summary)
    assert "per-rank" in frame and "rank 1" in frame


def test_render_record_without_telemetry():
    from r2d2_tpu.tools.inspect import render_record
    frame = render_record({"t": 1.0})
    assert "telemetry.enabled" in frame


# ---------------------------------------------------------------------------
# the fused loop's span tree: one tiny run_anakin_train under a CPU
# jax.profiler capture, with a retrace forced from log_fn

def test_compile_monitor_hangs_a_build_under_the_open_stage():
    from r2d2_tpu.telemetry import CompileMonitor
    from r2d2_tpu.telemetry.compile import _BACKEND, _LOWER, _TRACE
    tele = Telemetry()
    mon = CompileMonitor(tele)          # not installed: the callbacks alone
    with tele.stage("anakin/iteration", iter=7):
        with tele.stage("learner/train_dispatch"):
            t0 = time.time()
            mon._on_phase_start(_TRACE, t0, "step")
            mon._on_phase_start(_TRACE, t0 + 0.01, "tanh")   # nested: no span
            mon._on_phase_end(_TRACE, t0 + 0.01, t0 + 0.02)
            mon._on_phase_end(_TRACE, t0, t0 + 0.05)
            mon._on_phase_start(_LOWER, t0 + 0.05, "jit(step)")
            mon._on_phase_end(_LOWER, t0 + 0.05, t0 + 0.1)
            mon._on_phase_start(_BACKEND, t0 + 0.1, "jit(step)")
            mon._on_cache("miss")
            mon._on_phase_end(_BACKEND, t0 + 0.1, t0 + 0.35)
    rows = tele.spans.drain()
    spans = {e["name"]: e for e in rows}
    assert [e["name"] for e in rows].count("compile/trace") == 1
    build = spans["compile/backend"]
    assert build["tags"] == {"fn": "jit(step)", "cache": "miss"}
    assert build["dur"] == pytest.approx(0.25)
    assert build["ts"] == pytest.approx(t0 + 0.1)
    dispatch = spans["learner/train_dispatch"]
    for name in ("compile/trace", "compile/lower", "compile/backend"):
        assert spans[name]["parent"] == dispatch["id"]
        assert spans[name]["iter"] == 7
    assert spans["compile/trace"]["tags"] == {"fn": "step"}
    assert spans["compile/trace"]["dur"] == pytest.approx(0.05)
    # the dispatch's own time is what the three phases leave
    assert dispatch["self"] == pytest.approx(dispatch["dur"] - 0.35)
    assert mon.compiles == 1 and mon.cache_misses == 1
    assert mon.totals()["trace_lower_s"] == pytest.approx(0.1)
    # without a Telemetry it only counts, as before
    bare = CompileMonitor()
    bare._on_phase_start(_BACKEND, t0, "f")
    bare._on_phase_end(_BACKEND, t0, t0 + 0.1)
    assert bare.compiles == 1 and bare.cache_misses == 0


ITERATION_CHILDREN = ["actor/act_scan", "ingest/commit", "anakin/accounting",
                      "learner/step", "anakin/poll", "anakin/log"]
LOG_CHILDREN = ["learner/device_sync", "learner/diag_flush",
                "anakin/stats_fetch", "metrics/record", "anakin/log_fn"]
RETRACE_AT = 12      # the log_fn call (= iteration) that forces the retrace


@pytest.fixture(scope="module")
def fused_loop_run(tmp_path_factory):
    """One run of the fused loop, small enough for the CPU, that logs at
    every iteration: each iteration then ends on ``device_sync``, so its
    children hold the device's work and not only the enqueues."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from r2d2_tpu.config import Config
    from r2d2_tpu.runtime.anakin_loop import run_anakin_train

    out = tmp_path_factory.mktemp("fused_loop")
    lanes, block = 8, 40
    cfg = Config().replace(**{
        "env.game_name": "Fake", "env.frame_height": 24,
        "env.frame_width": 24, "env.frame_stack": 2,
        "env.episode_len": block,
        "network.hidden_dim": 16, "network.cnn_out_dim": 32,
        "network.conv_layers": ((8, 4, 2), (16, 3, 1)),
        "sequence.burn_in_steps": 4, "sequence.learning_steps": 5,
        "sequence.forward_steps": 3,
        "replay.capacity": 1600, "replay.block_length": block,
        "replay.batch_size": 16, "replay.learning_starts": 160,
        "actor.on_device": True, "actor.anakin_lanes": lanes,
        "runtime.save_interval": 0, "runtime.save_dir": str(out),
        "runtime.log_interval": 1e-6,
    })
    probe = jax.jit(lambda x: x * 2 + 1)
    records = []

    def log_fn(record):
        # the n-th call comes from iteration n: every iteration logs
        if len(records) == 0:
            probe(np.zeros(2, np.float32))
        elif len(records) == RETRACE_AT:
            probe(np.zeros(3, np.float32))       # same function, new shape
        records.append(record)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out / "trace"), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test_clock",
                                          unix_ns=time.time_ns()):
            pass
        stacks = run_anakin_train(cfg, max_training_steps=30,
                                  max_seconds=300, log_fn=log_fn)
    finally:
        jax.profiler.stop_trace()
    (capture,) = glob.glob(str(out / "trace" / "**" / "*.xplane.pb"),
                           recursive=True)
    host_events = []
    for plane in ProfileData.from_file(capture).planes:
        if plane.name == "/host:CPU":
            host_events += [(e.name, e.start_ns, dict(e.stats))
                            for line in plane.lines for e in line.events
                            if "/" in e.name or e.name == "test_clock"]
    return {"rows": parse_jsonl(str(out / "spans_player0.jsonl")),
            "records": records, "host_events": host_events,
            "learner": stacks[0].learner, "lanes": lanes, "block": block}


def test_fused_loop_iteration_is_tiled_by_its_children(fused_loop_run):
    rows, records = fused_loop_run["rows"], fused_loop_run["records"]
    kids = {}
    for r in rows:
        kids.setdefault(r["parent"], []).append(r)
    roots = [r for r in rows if r["name"] == "anakin/iteration"]
    assert [r["iter"] for r in roots] == list(range(len(roots)))
    assert len(roots) == len(records) >= 30
    assert all(r["parent"] is None for r in roots)
    trained = [r for r in roots if r["tags"]["train_steps"]]
    assert len(trained) >= 30
    for root in trained:
        names = [k["name"] for k in sorted(kids[root["id"]],
                                           key=lambda k: k["ts"])]
        assert names == ITERATION_CHILDREN
        by = {k["name"]: k for k in kids[root["id"]]}
        assert [k["name"] for k in sorted(
            kids[by["anakin/log"]["id"]], key=lambda k: k["ts"])
        ] == LOG_CHILDREN
        step = sorted(kids[by["learner/step"]["id"]], key=lambda k: k["ts"])
        # the run's first dispatch, and only it, is set-up's: tagged, and
        # followed by the one block on its outputs
        first = root is trained[0]
        assert [k["name"] for k in step] == (
            ["learner/train_dispatch"]
            + (["learner/first_ready"] if first else []))
        assert (step[0]["tags"].get("first") == 1) == first
        # everything under a root carries its identifier
        assert all(k["iter"] == root["iter"] for k in kids[root["id"]])
        # counts at the same boundaries
        tags = root["tags"]
        assert tags["env_steps_written"] == (fused_loop_run["lanes"]
                                             * fused_loop_run["block"])
        assert tags["blocks_written"] == fused_loop_run["lanes"]
        assert tags["train_steps"] == 1 and tags["paused"] is False
        assert root["self"] == pytest.approx(
            root["dur"] - sum(k["dur"] for k in kids[root["id"]]))
    # the counters at a root's start chain through the iterations
    for a, b in zip(roots, roots[1:]):
        assert b["tags"]["env_steps"] == (a["tags"]["env_steps"]
                                          + a["tags"]["env_steps_written"])
        assert b["tags"]["step"] == a["tags"]["step"] + a["tags"]["train_steps"]
    learner = fused_loop_run["learner"]
    assert (roots[-1]["tags"]["step"] + roots[-1]["tags"]["train_steps"]
            == learner.training_steps)
    # a coverage count, not a speed: the first two iterations compile
    steady = roots[2:]
    covered = [1.0 - r["self"] / r["dur"] for r in steady]
    assert sum(c >= 0.95 for c in covered) >= 0.9 * len(steady), sorted(
        covered)[:5]
    # names that are stages kept their histogram rows, new names have none
    stages = set()
    for record in records:
        stages |= set(record["stages"])
    assert {"actor/act_scan", "ingest/commit", "learner/train_dispatch",
            "learner/device_sync"} <= stages
    assert not stages - set(STAGES)


def test_fused_loop_spans_stand_on_the_profilers_clock(fused_loop_run):
    rows, events = fused_loop_run["rows"], fused_loop_run["host_events"]
    (clock,) = [e for e in events if e[0] == "test_clock"]
    offset_ns = clock[1] - clock[2]["unix_ns"]
    on_plane = {stats["id"]: (name, start, stats)
                for name, start, stats in events if "id" in stats}
    assert {name for name, _, _ in on_plane.values()} >= set(
        ["anakin/iteration"] + ITERATION_CHILDREN + LOG_CHILDREN
        + ["learner/train_dispatch"])
    late_ms = []
    for row in rows:
        name, start_ns, stats = on_plane[row["id"]]
        assert name == row["name"]
        assert stats.get("parent") == row["parent"]
        assert stats.get("iter") == row["iter"]
        late_ms.append(abs(row["ts"] * 1e9 + offset_ns - start_ns) / 1e6)
    late_ms.sort()
    assert len(late_ms) > 300
    assert late_ms[len(late_ms) // 2] < 1.0
    assert late_ms[int(0.9 * len(late_ms))] < 1.0


def test_fused_loop_retrace_names_its_iteration_and_call(fused_loop_run):
    rows = fused_loop_run["rows"]
    by_id = {r["id"]: r for r in rows}
    # the loop's builds (the Learner's own, under ``iter="setup"``, aside)
    builds = [r for r in rows if r["name"] == "compile/backend"
              and isinstance(r["iter"], int)]
    # warm-up's builds sit in the iterations that made them
    assert {by_id[b["parent"]]["name"] for b in builds
            if b["iter"] < RETRACE_AT} >= {
        "actor/act_scan", "ingest/commit", "learner/train_dispatch"}
    (retrace,) = [b for b in builds if b["iter"] >= RETRACE_AT]
    assert retrace["iter"] == RETRACE_AT
    assert "lambda" in retrace["tags"]["fn"]
    hook = by_id[retrace["parent"]]
    assert hook["name"] == "anakin/log_fn"
    root = by_id[by_id[hook["parent"]]["parent"]]
    assert root["name"] == "anakin/iteration" and root["iter"] == RETRACE_AT
    assert hook["ts"] <= retrace["ts"] + 1e-3
    assert retrace["ts"] + retrace["dur"] <= hook["ts"] + hook["dur"] + 1e-3
    # the monitor's own account agrees: one retrace, after warm-up
    last = fused_loop_run["records"][-1]["resources"]["compile"]
    assert last["retraces_total"] == 1


# ---------------------------------------------------------------------------
# end-to-end slice (slow): the full pipeline emits fleet-wide stage
# percentiles, spans export to a loadable Chrome trace, and
# runtime.profile_at_step triggers a mid-run capture

@pytest.mark.slow
def test_e2e_thread_telemetry_and_midrun_capture(tmp_path):
    import glob

    from r2d2_tpu.runtime.orchestrator import train
    from r2d2_tpu.tools.inspect import export_chrome_trace
    from tests.test_runtime import tiny_config

    cfg = tiny_config(tmp_path, **{
        "runtime.profile_at_step": 5,
        "runtime.save_interval": 0,
        "runtime.log_interval": 1.0,
        "telemetry.flush_interval_s": 0.3,
    })
    records = []
    stacks = train(cfg, max_training_steps=25, max_seconds=180,
                   actor_mode="thread", log_fn=records.append)
    assert stacks[0].learner.training_steps >= 25
    stages = set()
    for r in records:
        stages |= set(r.get("stages") or {})
    # the acceptance bar: >= 6 distinct pipeline stages aggregated into
    # the per-interval record
    assert len(stages) >= 6, f"only {sorted(stages)}"
    assert {"actor/forward", "actor/env_step", "actor/block_emit",
            "learner/train_dispatch"} <= stages
    for name in stages:
        for r in records:
            if name in (r.get("stages") or {}):
                assert {"count", "p50_ms", "p95_ms", "p99_ms"} <= set(
                    r["stages"][name])
    # spans drained to disk and export to a valid Chrome trace
    out = str(tmp_path / "trace.json")
    assert export_chrome_trace(str(tmp_path), out) > 0
    trace = json.load(open(out))
    assert any(e.get("ph") == "X" for e in trace["traceEvents"])
    # the mid-run capture fired (profile_at_step=5 < 25 steps)
    assert glob.glob(str(tmp_path / "xprof" / "**" / "*.trace.json.gz"),
                     recursive=True) or \
        glob.glob(str(tmp_path / "xprof" / "**" / "*.xplane.pb"),
                  recursive=True)


@pytest.mark.slow
def test_e2e_telemetry_kill_switch(tmp_path):
    from r2d2_tpu.runtime.orchestrator import train
    from tests.test_runtime import tiny_config

    cfg = tiny_config(tmp_path, **{
        "telemetry.enabled": False,
        "runtime.save_interval": 0,
        "runtime.log_interval": 1.0,
    })
    records = []
    train(cfg, max_training_steps=10, max_seconds=120,
          actor_mode="thread", log_fn=records.append)
    assert records
    assert all("stages" not in r for r in records)
    assert not list(tmp_path.glob("spans_*.jsonl"))
