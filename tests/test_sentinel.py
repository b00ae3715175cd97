"""Resource/compilation observability + alerting sentinel tests (ISSUE 7):
the declarative rule engine's semantics, retrace detection on a real
shape-churning jit, the resource monitor (device stats, buffer
attribution, board RSS aggregation, OOM forensics), record-schema
stability for PR4/5-era readers, the sentinel/regress CLIs, and the
chaos-driven e2e slices proving injected faults raise the right alerts.
"""

import json
import logging
import os
import time

import numpy as np
import pytest

from r2d2_tpu.config import Config
from r2d2_tpu.telemetry.alerts import (AlertEngine, AlertRule, default_rules,
                                       record_value)

from tests.test_runtime import tiny_config
from tests.test_telemetry import PR23_RECORD_KEYS


def _engine(*rules, **kwargs):
    return AlertEngine(rules, **kwargs)


# ---------------------------------------------------------------------------
# rule / engine units


def test_alert_rule_validation():
    with pytest.raises(ValueError, match="unknown kind"):
        AlertRule("x", "spike", ("a",), 1.0)
    with pytest.raises(ValueError, match="window"):
        AlertRule("x", "drop", ("a",), 0.5, window=1)


def test_record_value_walks_paths():
    rec = {"a": {"b": {"c": 3}}, "flat": 1.5, "none": None,
           "s": "str", "l": [1]}
    assert record_value(rec, ("a", "b", "c")) == 3.0
    assert record_value(rec, ("flat",)) == 1.5
    assert record_value(rec, ("a", "missing")) is None
    assert record_value(rec, ("none",)) is None
    assert record_value(rec, ("s",)) is None
    assert record_value(rec, ("l",)) is None
    assert record_value(rec, ("flat", "deeper")) is None


def test_threshold_rule_edge_and_rearm():
    eng = _engine(AlertRule("hot", "threshold", ("v",), 10.0))
    assert eng.evaluate({"v": 5})["fired"] == []
    fired = eng.evaluate({"v": 12})["fired"]
    assert [a["rule"] for a in fired] == ["hot"]
    # persistent condition: active, but no re-fire
    out = eng.evaluate({"v": 15})
    assert out["fired"] == [] and out["active"] == ["hot"]
    # recovery re-arms, next crossing fires again
    assert eng.evaluate({"v": 5})["active"] == []
    assert [a["rule"] for a in eng.evaluate({"v": 11})["fired"]] == ["hot"]
    assert eng.fired_total == 2


def test_threshold_below_direction():
    eng = _engine(AlertRule("low", "threshold", ("v",), 0.05, below=True))
    assert eng.evaluate({"v": 0.5})["fired"] == []
    assert [a["rule"] for a in eng.evaluate({"v": 0.01})["fired"]] == ["low"]


def test_counter_rule_zero_baseline_then_edge():
    eng = _engine(AlertRule("c", "counter", ("n",), 1.0))
    # healthy counter at zero: nothing to report
    assert eng.evaluate({"n": 0})["fired"] == []
    assert eng.evaluate({"n": 0})["fired"] == []
    fired = eng.evaluate({"n": 1})["fired"]
    assert fired and fired[0]["delta"] == 1.0
    # pure edge semantics: one increment fires exactly once
    assert eng.evaluate({"n": 1})["fired"] == []
    # a missing record key holds the baseline, it doesn't reset it
    assert eng.evaluate({})["fired"] == []
    assert eng.evaluate({"n": 3})["fired"][0]["delta"] == 2.0


def test_counter_rule_first_record_already_carries_events():
    # events BEFORE the first log boundary (a warm-up hang) still alert:
    # the baseline is zero, not the first observation
    eng = _engine(AlertRule("c", "counter", ("n",), 1.0))
    fired = eng.evaluate({"n": 2})["fired"]
    assert fired and fired[0]["delta"] == 2.0
    assert eng.evaluate({"n": 2})["fired"] == []      # still exactly once


def test_drop_rule_fires_on_collapse_with_baseline():
    eng = _engine(AlertRule("tp", "drop", ("v",), 0.5, window=3))
    for _ in range(3):
        assert eng.evaluate({"v": 100.0})["fired"] == []
    fired = eng.evaluate({"v": 30.0})["fired"]
    assert fired and fired[0]["rule"] == "tp"
    assert fired[0]["baseline"] == pytest.approx(100.0)
    # recovery clears without a new fire
    assert eng.evaluate({"v": 90.0})["active"] == []


def test_drop_rule_warmup_zeros_never_arm():
    eng = _engine(AlertRule("tp", "drop", ("v",), 0.5, window=2))
    # zeros (warm-up / paused intervals) never enter the median, so the
    # rule cannot arm off a dead baseline and then fire on recovery
    for _ in range(5):
        assert eng.evaluate({"v": 0.0})["fired"] == []
    assert eng.evaluate({"v": 50.0})["fired"] == []   # first healthy obs
    assert eng.evaluate({"v": 60.0})["fired"] == []
    assert eng.evaluate({"v": 10.0})["fired"]         # now a real collapse


def test_growth_rule():
    eng = _engine(AlertRule("age", "growth", ("v",), 4.0, window=2))
    for v in (10.0, 12.0):
        assert eng.evaluate({"v": v})["fired"] == []
    assert eng.evaluate({"v": 20.0})["fired"] == []   # 20 < 4 x 11
    # window now [12, 20] -> baseline 16; 70 > 4 x 16 fires
    assert [a["rule"] for a in eng.evaluate({"v": 70.0})["fired"]] == ["age"]


def test_missing_data_holds_level_state():
    eng = _engine(AlertRule("hot", "threshold", ("v",), 10.0))
    eng.evaluate({"v": 12})
    # a record without the key (training pause, pre-PR7 reader) must not
    # read as recovery — otherwise the next sighting would re-fire
    out = eng.evaluate({})
    assert out["active"] == ["hot"] and out["fired"] == []
    assert eng.evaluate({"v": 12})["fired"] == []


def test_default_rules_parameterized_and_unique():
    t = Config().telemetry
    rules = default_rules(t)
    names = [r.name for r in rules]
    assert len(set(names)) == len(names)
    by_name = {r.name: r for r in rules}
    assert by_name["retrace_storm"].bound == float(t.alerts_retrace_storm)
    assert by_name["hbm_headroom"].below
    assert by_name["hbm_headroom"].path == ("resources",
                                            "hbm_headroom_frac_min")
    assert by_name["actor_stall"].kind == "counter"
    assert by_name["env_throughput_drop"].window == t.alerts_window


def test_engine_rejects_duplicate_rule_names():
    with pytest.raises(ValueError, match="duplicate"):
        _engine(AlertRule("a", "threshold", ("v",), 1.0),
                AlertRule("a", "counter", ("w",), 1.0))


def test_engine_jsonl_truncate_and_resume(tmp_path):
    path = str(tmp_path / "alerts_player0.jsonl")
    eng = _engine(AlertRule("hot", "threshold", ("v",), 1.0),
                  jsonl_path=path)
    eng.evaluate({"v": 2, "t": 1.0, "training_steps": 7, "env_steps": 70})
    rows = [json.loads(l) for l in open(path)]
    assert rows[0]["rule"] == "hot" and rows[0]["training_steps"] == 7
    # resume appends to the stream, fresh truncates (TrainMetrics contract)
    eng2 = _engine(AlertRule("hot", "threshold", ("v",), 1.0),
                   jsonl_path=path, resume=True)
    eng2.evaluate({"v": 2})
    assert len(open(path).readlines()) == 2
    _engine(AlertRule("hot", "threshold", ("v",), 1.0), jsonl_path=path)
    assert open(path).read() == ""


# ---------------------------------------------------------------------------
# compile / retrace telemetry


def _pxla_logger_state():
    logger = logging.getLogger("jax._src.interpreters.pxla")
    return (logger.level, logger.propagate, list(logger.handlers))


def test_compile_monitor_retrace_detection():
    """The detector on an intentionally shape-churning jit: post-warm
    compiles of a KNOWN fn with NEW avals are retraces (flagged with the
    offending avals); a new fn after warm-up is a late compile, not a
    retrace."""
    import jax
    import jax.numpy as jnp

    from r2d2_tpu.telemetry.compile import CompileMonitor, active_monitor

    before = _pxla_logger_state()
    mon = CompileMonitor().install()
    try:
        assert active_monitor() is mon

        def churner(x):
            return x * 2.0 + 1.0

        f = jax.jit(churner)
        f(jnp.ones((4,)))                  # warm-up compile
        assert mon.totals()["retraces_total"] == 0
        mon.mark_warm()
        f(jnp.ones((4,)))                  # cache hit: no event
        f(jnp.ones((8,)))                  # retrace 1
        f(jnp.ones((16,)))                 # retrace 2
        totals = mon.totals()
        assert totals["retraces_total"] == 2
        assert totals["compiles_total"] >= 3
        assert "churner" in totals["last_retrace"]["fn"]
        assert "16" in totals["last_retrace"]["avals"]
        # a NEW function post-warm is a late first compile, not a retrace
        g = jax.jit(lambda x: x - 1.0)
        g(jnp.ones((4,)))
        totals = mon.totals()
        assert totals["late_compiles"] >= 1
        assert totals["retraces_total"] == 2
    finally:
        mon.uninstall()
    assert active_monitor() is None
    assert _pxla_logger_state() == before     # logger restored exactly


def test_compile_monitor_interval_summary_consumes():
    from r2d2_tpu.telemetry.compile import CompileMonitor
    mon = CompileMonitor()
    mon._on_backend_compile(1.5)
    mon._on_compile("f", "f32[4]")
    s1 = mon.interval_summary()
    assert s1["compiles"] == 1 and s1["compile_time_s"] == 1.5
    s2 = mon.interval_summary()
    assert s2["compiles"] == 0 and s2["compiles_total"] == 1


def test_compile_monitor_single_active_slot():
    from r2d2_tpu.telemetry.compile import CompileMonitor, active_monitor
    a = CompileMonitor().install()
    b = CompileMonitor().install()     # displaces a (install deactivates)
    try:
        assert active_monitor() is b
        b._on_compile("f", "f32[1]")
        assert a.traced_compiles == 0 and b.traced_compiles == 1
    finally:
        b.uninstall()
    assert active_monitor() is None


def test_retrace_event_counting_via_signatures():
    from r2d2_tpu.telemetry.compile import CompileMonitor
    mon = CompileMonitor()
    mon._on_compile("f", "f32[4]")
    mon.mark_warm()
    mon._on_compile("f", "f32[4]")     # same avals: not a retrace
    assert mon.retraces == 0
    mon._on_compile("f", "f32[8]")
    assert mon.retraces == 1
    mon._on_compile("g", "f32[4]")     # new fn post-warm: late, no retrace
    assert mon.retraces == 1 and mon.late_compiles == 1
    assert mon.functions_seen() == {"f": 2, "g": 1}


def test_aot_coverage_report():
    from r2d2_tpu.telemetry.compile import aot_coverage
    cov = aot_coverage([1, 2, 4, 8], [1, 2, 8, 16])
    assert cov["missing"] == [4]
    assert cov["extra"] == [16]
    assert cov["expected"] == [1, 2, 4, 8]


# ---------------------------------------------------------------------------
# resource monitor


def test_device_memory_stats_backend_optional():
    from r2d2_tpu.telemetry.resources import SUMMARY_KEYS, device_memory_stats

    class Raises:
        def memory_stats(self):
            raise RuntimeError("unimplemented")

    class Reports:
        def memory_stats(self):
            return {"bytes_in_use": 7.0, "bytes_limit": 100,
                    "allocs": "not-a-number", "other": 3}

    assert device_memory_stats(Raises()) == {}
    full = device_memory_stats(Reports())
    assert full == {"bytes_in_use": 7, "bytes_limit": 100, "other": 3}
    assert device_memory_stats(Reports(), keys=SUMMARY_KEYS) == {
        "bytes_in_use": 7, "bytes_limit": 100}


def test_pytree_nbytes():
    from r2d2_tpu.telemetry.resources import pytree_nbytes
    tree = {"a": np.zeros((4, 4), np.float32), "b": [np.zeros(8, np.int64)],
            "c": "not-an-array"}
    assert pytree_nbytes(tree) == 4 * 4 * 4 + 8 * 8


def test_host_usage_reports_this_process():
    from r2d2_tpu.telemetry.resources import host_usage
    u = host_usage()
    assert u["rss_bytes"] > 0
    assert u["cpu_s"] > 0
    assert u["threads"] >= 1


def test_buffer_registry_semantics():
    from r2d2_tpu.telemetry.resources import BufferRegistry
    reg = BufferRegistry()
    reg.register("p0/ring", 100)
    reg.register("p0/params", 50)
    reg.register("p0/ring", 120)          # re-register overwrites
    assert reg.snapshot() == {"p0/ring": 120, "p0/params": 50}
    assert reg.total() == 170
    reg.unregister("p0/params")
    reg.unregister("never-registered")    # no-op, not an error
    assert reg.total() == 120
    reg.clear()
    assert reg.snapshot() == {}


def _stats_fn(in_use, limit=1000):
    return lambda d: {"bytes_in_use": in_use, "bytes_limit": limit,
                      "peak_bytes_in_use": in_use}


def test_resource_monitor_block_and_running_peak(tmp_path):
    from r2d2_tpu.telemetry.resources import BufferRegistry, ResourceMonitor
    reg = BufferRegistry()
    reg.register("p0/ring", 640)
    mon = ResourceMonitor(0, str(tmp_path), interval_s=0.0, registry=reg,
                          headroom_warn_frac=0.0,
                          stats_fn=_stats_fn(400))
    mon.sample()
    block = mon.block()
    dev = block["devices"][0]
    assert dev["bytes_in_use"] == 400 and dev["headroom_frac"] == 0.6
    assert block["hbm_headroom_frac_min"] == 0.6
    assert block["buffers"] == {"p0/ring": 640}
    assert block["buffers_total"] == 640
    assert block["host"]["rss_bytes"] > 0
    # host-side running peak survives an allocator whose own peak resets
    mon._stats_fn = _stats_fn(250)
    mon.sample()
    assert mon.block()["devices"][0]["peak_seen"] == 400


def test_resource_monitor_maybe_sample_cadence(tmp_path):
    from r2d2_tpu.telemetry.resources import ResourceMonitor
    mon = ResourceMonitor(0, str(tmp_path), interval_s=60.0,
                          stats_fn=_stats_fn(1))
    assert mon.maybe_sample(now=1000.0)
    assert not mon.maybe_sample(now=1030.0)     # inside the interval
    assert mon.maybe_sample(now=1061.0)


def test_resource_monitor_forensics_dump_one_shot(tmp_path):
    from r2d2_tpu.telemetry.resources import ResourceMonitor
    mon = ResourceMonitor(3, str(tmp_path), interval_s=0.0,
                          headroom_warn_frac=0.10,
                          stats_fn=_stats_fn(970))    # 3% headroom
    mon.sample()
    path = tmp_path / "resource_dump_player3.json"
    assert path.exists()
    dump = json.loads(path.read_text())
    assert "headroom" in dump["reason"]
    assert dump["devices"][0]["bytes_in_use"] == 970
    # one-shot latch (the nan_dump pattern): later samples don't rewrite
    mtime = path.stat().st_mtime
    mon.sample()
    assert mon.dump() is None
    assert path.stat().st_mtime == mtime


def test_board_gauges_publish_read_and_reset():
    from r2d2_tpu.telemetry import TelemetryBoard
    board = TelemetryBoard(3)
    try:
        board.publish_gauges(0, 100 << 20, 5000)
        board.publish_gauges(2, 50 << 20, 1000)
        g = board.read_gauges()
        assert g.shape == (3, 2)
        assert g[0, 0] == 100 << 20 and g[2, 1] == 1000
        assert g[1, 0] == 0
        # a respawned slot starts clean
        board.reset_slot(0)
        assert board.read_gauges()[0, 0] == 0
        # gauges don't disturb the histogram table (layout check)
        assert board.read().sum() == 0
    finally:
        board.close()
    assert board.read_gauges() is None      # live-only, unlike histograms


def test_resource_monitor_board_rss_aggregation(tmp_path):
    """Board RSS/CPU aggregation: per-slot gauges land in the block;
    cpu%% is differenced across samples, and a respawned slot's counter
    reset reads as the fresh value, not a negative rate."""
    from r2d2_tpu.telemetry import TelemetryBoard
    from r2d2_tpu.telemetry.resources import ResourceMonitor
    board = TelemetryBoard(2)
    try:
        mon = ResourceMonitor(0, str(tmp_path), interval_s=0.0, board=board,
                              stats_fn=lambda d: {})
        board.publish_gauges(0, 100 << 20, 1000)
        board.publish_gauges(1, 200 << 20, 4000)
        mon.sample(now=10.0)
        slots = mon.block()["actor_slots"]
        assert slots["rss_bytes"] == [100 << 20, 200 << 20]
        assert slots["cpu_pct"] == [None, None]      # no delta yet
        board.publish_gauges(0, 110 << 20, 3000)     # +2s cpu over 10s
        board.publish_gauges(1, 200 << 20, 1000)     # respawn: counter reset
        mon.sample(now=20.0)
        slots = mon.block()["actor_slots"]
        assert slots["cpu_pct"][0] == pytest.approx(20.0)
        assert slots["cpu_pct"][1] == pytest.approx(10.0)   # fresh value
    finally:
        board.close()


def test_telemetry_flush_publishes_resource_gauges():
    from r2d2_tpu.telemetry import Telemetry, TelemetryBoard
    board = TelemetryBoard(2)
    try:
        tele = Telemetry(name="w", board=board, slot=1,
                         resource_gauges=True)
        tele.observe("actor/env_step", 1e-3)
        tele.flush()
        g = board.read_gauges()
        assert g[1, 0] > 0 and g[1, 1] > 0          # rss, cpu_ms
        assert g[0, 0] == 0
    finally:
        board.close()


# ---------------------------------------------------------------------------
# record schema stability + config round-trip


def test_record_schema_identical_without_pillar(tmp_path):
    """telemetry.resources_enabled=False (or simply nothing attached):
    the record must be byte-identical to the PR4/5/6 schema — no
    'resources', no 'alerts', every pre-PR7 key intact."""
    from r2d2_tpu.runtime.metrics import TrainMetrics
    m = TrainMetrics(0, str(tmp_path))
    m.on_block(20, 1.0)
    m.on_train_step(0.5)
    record = m.log(2.0)
    assert "resources" not in record and "alerts" not in record
    assert PR23_RECORD_KEYS <= set(record)
    # what a PR4/5-era reader would parse from the stream
    from r2d2_tpu.tools.logparse import parse_jsonl
    rows = parse_jsonl(str(tmp_path / "metrics_player0.jsonl"))
    assert set(rows[0]) == set(record)


def test_record_carries_resources_then_alerts_see_them(tmp_path):
    """The resources block is assembled BEFORE the alert pass, so a
    machine-side rule (hbm_headroom) fires off the same record it rides
    in — and the firing lands in alerts_player{p}.jsonl."""
    from r2d2_tpu.runtime.metrics import TrainMetrics
    from r2d2_tpu.telemetry.resources import ResourceMonitor
    m = TrainMetrics(0, str(tmp_path))
    mon = ResourceMonitor(0, str(tmp_path), interval_s=0.0,
                          headroom_warn_frac=0.0,
                          stats_fn=_stats_fn(980))    # 2% headroom
    m.set_resources(mon.block)
    path = str(tmp_path / "alerts_player0.jsonl")
    m.set_sentinel(AlertEngine(default_rules(Config().telemetry),
                               jsonl_path=path))
    record = m.log(2.0)
    assert record["resources"]["hbm_headroom_frac_min"] == pytest.approx(
        0.02)
    assert "hbm_headroom" in [a["rule"] for a in record["alerts"]["fired"]]
    rows = [json.loads(l) for l in open(path)]
    assert rows[0]["rule"] == "hbm_headroom"
    assert rows[0]["severity"] == "crit"


def test_config_pre_pr7_dict_round_trips():
    cfg = Config()
    d = cfg.to_dict()
    tel = d["telemetry"]
    for k in list(tel):
        if k.startswith(("resources_", "alerts_", "compile_")):
            del tel[k]                     # a PR6-era checkpoint config
    restored = Config.from_dict(d)
    assert restored.telemetry.resources_enabled
    assert restored.telemetry.alerts_window == cfg.telemetry.alerts_window
    # full modern round-trip preserves overrides
    cfg2 = cfg.replace(**{"telemetry.alerts_retrace_storm": 7,
                          "telemetry.resources_interval_s": 3.0})
    assert Config.from_dict(
        cfg2.to_dict()).telemetry.alerts_retrace_storm == 7


@pytest.mark.parametrize("knob,value,match", [
    ("telemetry.resources_interval_s", 0.0, "resources_interval_s"),
    ("telemetry.resources_headroom_warn_frac", 1.5, "headroom_warn_frac"),
    ("telemetry.alerts_window", 1, "alerts_window"),
    ("telemetry.alerts_throughput_drop_frac", 0.0, "throughput_drop_frac"),
    ("telemetry.alerts_staleness_growth_factor", 1.0, "staleness_growth"),
    ("telemetry.alerts_hbm_headroom_frac", -0.1, "hbm_headroom_frac"),
    ("telemetry.alerts_retrace_storm", 0, "retrace_storm"),
])
def test_config_validates_pillar_knobs(knob, value, match):
    with pytest.raises(ValueError, match=match):
        Config().replace(**{knob: value})


# ---------------------------------------------------------------------------
# logparse + inspector


def test_alerts_series_partial_line_tolerance(tmp_path):
    from r2d2_tpu.tools.logparse import alerts_series
    path = tmp_path / "alerts_player0.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps({"t": 1.0, "training_steps": 5, "env_steps": 50,
                            "rule": "hot", "severity": "crit",
                            "value": 12.0, "bound": 10.0}) + "\n")
        f.write('{"t": 2.0, "rule": "tr')          # writer mid-append
    s = alerts_series(str(path))
    assert s["rule"] == ["hot"] and s["t"] == [1.0]
    assert s["severity"] == ["crit"] and s["bound"] == [10.0]


def test_resources_series_aligned_on_carrying_records():
    from r2d2_tpu.tools.logparse import resources_series
    records = [
        {"t": 1.0},                                 # pre-PR7 record: skipped
        {"t": 2.0, "training_steps": 10, "resources": {
            "devices": [{"id": 0, "bytes_in_use": 100},
                        {"id": 1, "bytes_in_use": 50}],
            "hbm_headroom_frac_min": 0.4,
            "host": {"rss_bytes": 777, "cpu_pct": 55.0},
            "buffers_total": 640,
            "compile": {"compiles_total": 3, "compile_time_s_total": 1.5,
                        "retraces_total": 1}},
         "alerts": {"active": ["hbm_headroom"], "fired": []}},
    ]
    s = resources_series(records)
    assert s["t"] == [2.0]
    assert s["bytes_in_use"] == [150]
    assert s["hbm_headroom"] == [0.4]
    assert s["host_rss"] == [777]
    assert s["retraces"] == [1]
    assert s["alerts_active"] == [1]


def test_render_record_anakin_mode_and_panels():
    from r2d2_tpu.tools.inspect import render_record
    record = {"t": 10.0, "env_steps": 1000, "training_steps": 50,
              "buffer_size": 500, "buffer_speed": 100.0,
              "training_speed": 5.0,
              "stages": {"actor/act_scan":
                         {"count": 5, "p50_ms": 1.0, "p95_ms": 2.0,
                          "p99_ms": 3.0}},
              "actor_restarts": 3,     # stale default keys must NOT render
              "resources": {"devices": [], "host": {"rss_bytes": 1 << 30},
                            "buffers": {"p0/anakin_carry": 1 << 20},
                            "buffers_total": 1 << 20},
              "alerts": {"active": [], "fired": []}}
    frame = render_record(record)
    assert "on-device (anakin" in frame
    assert "health:" not in frame              # no fleet panel on anakin
    assert "actor/act_scan" in frame
    assert "anakin_carry" in frame
    assert "alerts: none active" in frame
    # a fleet record still renders its health panel
    fleet = dict(record)
    del fleet["stages"]
    frame2 = render_record(fleet)
    assert "health: restarts=3" in frame2


def test_render_alerts_fired():
    from r2d2_tpu.tools.inspect import render_alerts
    out = render_alerts({"active": ["retrace_storm"],
                         "fired": [{"rule": "retrace_storm",
                                    "severity": "crit", "value": 5.0,
                                    "bound": 3.0}]})
    assert "ACTIVE: retrace_storm" in out
    assert "FIRED CRIT retrace_storm" in out


# ---------------------------------------------------------------------------
# sentinel CLI


def test_sentinel_replay_exit_codes(tmp_path):
    from r2d2_tpu.tools.sentinel import main
    path = tmp_path / "metrics_player0.jsonl"
    clean = [{"t": float(i), "buffer_speed": 100.0, "training_speed": 5.0}
             for i in range(4)]
    with open(path, "w") as f:
        for r in clean:
            f.write(json.dumps(r) + "\n")
    assert main(["--dir", str(tmp_path)]) == 0
    # a NaN record makes the replay exit nonzero (crit rule fired)
    with open(path, "a") as f:
        f.write(json.dumps({"t": 9.0, "learning":
                            {"nonfinite_steps": 2}}) + "\n")
    assert main(["--dir", str(tmp_path)]) == 1
    assert main(["--dir", str(tmp_path / "nowhere")]) == 2


def test_sentinel_replay_detects_throughput_collapse(tmp_path):
    from r2d2_tpu.tools.sentinel import build_engine, replay_stream
    records = [{"buffer_speed": 100.0 + i} for i in range(8)]
    records.append({"buffer_speed": 10.0})          # collapse vs median
    engine = build_engine()
    summary = replay_stream(records, engine, emit=lambda s: None)
    assert summary["by_rule"] == {"env_throughput_drop": 1}
    assert summary["crit"] == 1


def test_sentinel_override_changes_bounds(tmp_path):
    from r2d2_tpu.tools.sentinel import build_engine
    eng = build_engine({"telemetry.alerts_retrace_storm": 9})
    assert {r.name: r for r in eng.rules}["retrace_storm"].bound == 9.0


# ---------------------------------------------------------------------------
# regress gate


def _fake_artifact(env=1000.0, ratio=1.05):
    return {"metric": "e2e_throughput",
            "e2e_resources_ab": {
                "resources_on": {"env_steps_per_sec": env,
                                 "learner_steps_per_sec": env / 100.0,
                                 "seconds": 30.0},
                "env_steps_ratio": ratio,
                "env_steps_per_sec_cells": {"on": [env, env]},
                "config": {"replay.capacity": 1}}}


def test_regress_extracts_watched_metrics():
    from r2d2_tpu.tools.regress import extract_metrics
    m = extract_metrics(_fake_artifact())
    assert m["e2e_resources_ab.resources_on.env_steps_per_sec"] == 1000.0
    assert m["e2e_resources_ab.env_steps_ratio"] == 1.05
    assert not any("seconds" in k for k in m)       # unwatched scalar
    assert not any("cells" in k for k in m)         # lists skipped
    assert not any("config" in k for k in m)        # config skipped


def test_regress_gate_passes_unmodified_fails_20pct_drop(tmp_path):
    """ACCEPTANCE: the gate passes against a baseline snapshotted from
    the same artifacts, and fails on a synthetic 20% throughput
    regression fixture."""
    from r2d2_tpu.tools.regress import main
    base = tmp_path / "BASELINE.json"
    base.write_text(json.dumps({"metric": "x"}))
    art = tmp_path / "E2E_r99.json"
    art.write_text(json.dumps(_fake_artifact(env=1000.0)))
    argv = ["--baseline", str(base), "--dir", str(tmp_path)]
    assert main(argv + ["--update"]) == 0
    assert main(argv) == 0                           # unmodified tree
    # synthetic 20% throughput regression: must fail
    art.write_text(json.dumps(_fake_artifact(env=800.0)))
    assert main(argv) == 1
    # recovery + improvement: passes (higher is never a failure)
    art.write_text(json.dumps(_fake_artifact(env=1400.0)))
    assert main(argv) == 0
    # a vanished metric fails too (the silent way out)
    art.write_text(json.dumps({"metric": "x"}))
    assert main(argv) == 1


def test_regress_tolerance_table():
    from r2d2_tpu.tools.regress import metric_tolerance
    assert metric_tolerance("a.env_steps_ratio") == 0.10   # medians: tight
    assert metric_tolerance("a.env_steps_per_sec") == 0.15
    assert metric_tolerance("a.b.speedup_vs_scalar") == 0.15
    assert metric_tolerance("whatever", override=0.3) == 0.3


def test_regress_no_bench_section_is_usage_error(tmp_path):
    from r2d2_tpu.tools.regress import main
    base = tmp_path / "BASELINE.json"
    base.write_text(json.dumps({"metric": "x"}))
    assert main(["--baseline", str(base), "--dir", str(tmp_path)]) == 2
    assert main(["--baseline", str(tmp_path / "none.json")]) == 2


# ---------------------------------------------------------------------------
# e2e slices


def test_retrace_storm_alert_end_to_end(tmp_path):
    """ACCEPTANCE (retrace storm): an induced post-warm-up retrace storm
    — one jitted fn recompiled at churning shapes — lands in the record's
    compile block and fires the retrace_storm alert into
    alerts_player{p}.jsonl exactly once while the storm lasts."""
    import jax
    import jax.numpy as jnp

    from r2d2_tpu.runtime.metrics import TrainMetrics
    from r2d2_tpu.telemetry.compile import CompileMonitor
    from r2d2_tpu.telemetry.resources import ResourceMonitor

    mon = CompileMonitor().install()
    try:
        def stormy(x):
            return jnp.tanh(x) * 3.0

        f = jax.jit(stormy)
        f(jnp.ones((2,)))
        mon.mark_warm()

        m = TrainMetrics(0, str(tmp_path))
        res = ResourceMonitor(0, str(tmp_path), interval_s=0.0,
                              compile_monitor=mon, stats_fn=lambda d: {})
        m.set_resources(res.block)
        path = str(tmp_path / "alerts_player0.jsonl")
        m.set_sentinel(AlertEngine(default_rules(Config().telemetry),
                                   jsonl_path=path))
        record = m.log(1.0)                        # healthy interval
        assert record["alerts"]["fired"] == []

        for n in (3, 5, 7, 9):                     # the storm: 4 retraces
            f(jnp.ones((n,)))
        record = m.log(1.0)
        assert record["resources"]["compile"]["retraces_interval"] >= 3
        assert "retrace_storm" in [a["rule"]
                                   for a in record["alerts"]["fired"]]
        assert "stormy" in record["resources"]["compile"][
            "last_retrace"]["fn"]
        # storm continues: still active, but only ONE fired line so far
        f(jnp.ones((11,)))
        f(jnp.ones((13,)))
        f(jnp.ones((15,)))
        record = m.log(1.0)
        assert "retrace_storm" in record["alerts"]["active"]
        rows = [json.loads(l) for l in open(path)]
        assert [r["rule"] for r in rows] == ["retrace_storm"]
    finally:
        mon.uninstall()


@pytest.mark.slow
def test_chaos_hang_raises_actor_stall_alert_exactly_once(tmp_path):
    """ACCEPTANCE (chaos slice): a hang injected into one process-mode
    actor (``1:hang@block=1``) — the watchdog detects it, the hang
    counter reaches the periodic record, and the sentinel fires the
    ``actor_stall`` alert into alerts_player0.jsonl EXACTLY once (counter
    edge semantics: one hang, one alert). The resources block flows in
    the same run — per-actor-slot RSS aggregated off the telemetry board
    from real worker processes."""
    from r2d2_tpu.runtime.orchestrator import train

    records = []
    cfg = tiny_config(tmp_path, **{
        "actor.num_actors": 2,
        # wedges on its 1st emit — during warm-up, BEFORE the first
        # periodic record, which therefore already carries the count;
        # the zero-baseline counter semantics make that an edge too
        "actor.fault_spec": "1:hang@block=1",
        "runtime.save_interval": 0, "runtime.log_interval": 1.0,
        "runtime.supervise_interval_s": 0.5,
        "runtime.hang_timeout_s": 3.0,
        "runtime.hang_spawn_grace_s": 150.0,
        "runtime.restart_backoff_base_s": 0.5,
        "runtime.restart_backoff_max_s": 2.0,
        # one detection, no respawn loop: the respawned slot would hang
        # again and fire a SECOND legitimate stall alert
        "runtime.restart_dead_actors": False,
        "telemetry.resources_interval_s": 1.0,
    })
    stacks = train(cfg, max_training_steps=10**9, max_seconds=60,
                   actor_mode="process", log_fn=records.append)
    st = stacks[0]
    assert st.health.hangs_detected == 1
    hang_recs = [r for r in records if r["actor_hangs_detected"] >= 1]
    assert hang_recs, "hang counter never reached the metrics records"
    # the alert stream: actor_stall exactly once
    rows = [json.loads(l)
            for l in open(os.path.join(str(tmp_path),
                                       "alerts_player0.jsonl"))]
    stalls = [r for r in rows if r["rule"] == "actor_stall"]
    assert len(stalls) == 1, rows
    assert stalls[0]["severity"] == "crit"
    assert stalls[0]["delta"] == 1.0
    # machine-side evidence from the same run: resources block with the
    # board-aggregated per-slot RSS of the real actor processes
    withres = [r for r in records if r.get("resources")]
    assert withres
    slot_rss = [r["resources"].get("actor_slots", {}).get("rss_bytes")
                for r in withres]
    assert any(rss and max(rss) > 0 for rss in slot_rss), \
        "actor-slot RSS never aggregated off the board"
