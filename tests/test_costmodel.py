"""Cost-model & roofline observability tests (ISSUE 9): XLA cost-table
extraction across the step factories (incl. a sharded emulated-mesh
program), named_scope component annotations in the lowered HLO, the
trace→component attribution on the checked-in miniature trace, the
roofline report + analytic golden file, the exact-match costs gate, the
anakin scan's unroll twin, and record-schema stability under the
``telemetry.costmodel_enabled`` kill switch.
"""

import dataclasses
import glob
import json
import os
import shutil

import jax
import numpy as np
import pytest

from r2d2_tpu.config import Config, apex_epsilon
from r2d2_tpu.envs.factory import create_jax_env
from r2d2_tpu.models.network import NetworkApply
from r2d2_tpu.replay.structs import ReplaySpec
from r2d2_tpu.telemetry import costmodel, traceparse

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")
MINI_TRACE = os.path.join(DATA_DIR, "mini_trace.trace.json.gz")
GOLDEN = os.path.join(DATA_DIR, "roofline_analytic_golden.json")


def gate_cfg(**overrides) -> Config:
    cfg = costmodel.gate_config()
    return cfg.replace(**overrides) if overrides else cfg


def _net_and_spec(cfg):
    env = create_jax_env(cfg.env)
    spec = ReplaySpec.from_config(cfg)
    net = NetworkApply(env.action_dim, cfg.network, cfg.env.frame_stack,
                       cfg.env.frame_height, cfg.env.frame_width)
    return env, spec, net


def _learner_step_hlo(cfg) -> str:
    from r2d2_tpu.learner.train_step import (create_train_state,
                                             make_learner_step)
    from r2d2_tpu.replay.device_replay import replay_init
    _, spec, net = _net_and_spec(cfg)
    step = make_learner_step(net, spec, cfg.optim, cfg.network.use_double)
    ts = costmodel._sds(jax.eval_shape(
        lambda k: create_train_state(k, net, cfg.optim),
        jax.random.PRNGKey(0)))
    rs = costmodel._sds(jax.eval_shape(lambda: replay_init(spec)))
    return jax.jit(step).lower(ts, rs).compile().as_text()


# ---------------------------------------------------------------------------
# cost-table extraction across step factories


def test_cost_table_core_programs():
    table = costmodel.collect_cost_table(
        gate_cfg(), variants=("learner_step", "replay_add_many",
                              "replay_sample"))
    assert table["schema"] == 1 and table["backend"] == "cpu"
    progs = table["programs"]
    for name in ("learner_step", "replay_add_many", "replay_sample"):
        assert progs[name]["flops"] > 0, name
        assert progs[name]["bytes_accessed"] > 0, name
        assert progs[name]["argument_bytes"] > 0, name
    # the fused step subsumes a sample + tree work: strictly more flops
    assert progs["learner_step"]["flops"] > progs["replay_sample"]["flops"]


def test_cost_table_anakin_program():
    table = costmodel.collect_cost_table(gate_cfg(),
                                         variants=("anakin_act",))
    act = table["programs"]["anakin_act"]
    assert act["flops"] > 0 and act["bytes_accessed"] > 0
    assert act["lanes"] == gate_cfg().actor.anakin_lanes


def test_cost_table_sharded_emulated_mesh():
    # the conftest pins an 8-device virtual CPU platform; the sharded
    # variant builds its dp=2 shard_map program on it
    table = costmodel.collect_cost_table(
        gate_cfg(), variants=("learner_step_sharded", "learner_step_multi"))
    sharded = table["programs"]["learner_step_sharded"]
    assert sharded["flops"] > 0 and sharded["dp"] == 2
    multi = table["programs"]["learner_step_multi"]
    assert multi["flops"] > 0 and multi["steps_per_dispatch"] == 3


def test_cost_table_tp_program():
    table = costmodel.collect_cost_table(gate_cfg(),
                                         variants=("learner_step_tp",))
    tp = table["programs"]["learner_step_tp"]
    assert tp["flops"] > 0 and tp["mp"] == 2


def test_program_cost_is_deterministic():
    cfg = gate_cfg()
    a = costmodel.collect_cost_table(cfg, variants=("replay_sample",))
    b = costmodel.collect_cost_table(cfg, variants=("replay_sample",))
    assert a["programs"] == b["programs"]


# ---------------------------------------------------------------------------
# named_scope component annotations in the lowered HLO


def test_named_scopes_in_learner_hlo():
    # bare-token matching, exactly like traceparse.component_of: under
    # autodiff the scopes ride transform-decorated op_names
    # (jvp(loss)/..., transpose(jvp(loss))/...), so path-delimited
    # tokens would miss the backward ops
    hlo = _learner_step_hlo(gate_cfg())
    for token in ("/torso/", "/lstm/", "/head/", "sum_tree_update",
                  "sum_tree_sample", "replay_sample", "optimizer",
                  "loss", "obs_decode"):
        assert token in hlo, f"component scope {token!r} missing from HLO"


def test_named_scopes_in_anakin_hlo():
    from r2d2_tpu.actor.anakin import init_act_carry, make_anakin_act
    cfg = gate_cfg()
    env, spec, net = _net_and_spec(cfg)
    lanes = cfg.actor.anakin_lanes
    eps = [apex_epsilon(i, lanes, cfg.actor.base_eps, cfg.actor.eps_alpha)
           for i in range(lanes)]
    act = make_anakin_act(env, net, spec, num_lanes=lanes, epsilons=eps,
                          gamma=cfg.optim.gamma, priority=1.0,
                          near_greedy_eps=cfg.actor.near_greedy_eps)
    params = costmodel._sds(jax.eval_shape(net.init, jax.random.PRNGKey(0)))
    carry = costmodel._sds(jax.eval_shape(
        lambda k: init_act_carry(env, spec, lanes, k), jax.random.PRNGKey(1)))
    hlo = act.lower(params, carry,
                    jax.ShapeDtypeStruct((), np.int32)).compile().as_text()
    for token in ("env_step", "env_reset", "emit_blocks", "act_forward"):
        assert token in hlo, f"acting scope {token!r} missing from HLO"


def test_anakin_unroll_twin_bit_identical():
    # the cost model's fully-unrolled acting twin must be the SAME
    # program mathematically: every emitted block field bit-matches
    # (sum_reward compared with equal_nan — NaN is its designed
    # not-reported value)
    from r2d2_tpu.actor.anakin import init_act_carry, make_anakin_act
    cfg = gate_cfg()
    env, spec, net = _net_and_spec(cfg)
    lanes = cfg.actor.anakin_lanes
    eps = [apex_epsilon(i, lanes, cfg.actor.base_eps, cfg.actor.eps_alpha)
           for i in range(lanes)]
    params = net.init(jax.random.PRNGKey(0))

    def run(unroll):
        act = make_anakin_act(env, net, spec, num_lanes=lanes, epsilons=eps,
                              gamma=cfg.optim.gamma, priority=1.0,
                              near_greedy_eps=cfg.actor.near_greedy_eps,
                              unroll=unroll)
        carry = init_act_carry(env, spec, lanes, jax.random.PRNGKey(1))
        return act(params, carry, np.int32(1))[1]

    b1, b2 = run(1), run(spec.block_length)
    for f in b1.__dataclass_fields__:
        x, y = np.asarray(getattr(b1, f)), np.asarray(getattr(b2, f))
        if np.issubdtype(x.dtype, np.floating):
            assert np.array_equal(x, y, equal_nan=True), f
        else:
            assert np.array_equal(x, y), f


# ---------------------------------------------------------------------------
# analytic model + bench parity


def test_flops_parity_with_xla_cost_model():
    # the ISSUE 9 acceptance bar: the unroll twin's XLA flops and
    # costmodel.model_flops_per_step within 5% (XLA counts a while body
    # once, hence the twin; see the costmodel module docstring)
    cfg = gate_cfg()
    table = costmodel.collect_cost_table(cfg, variants=("learner_step",),
                                         unroll_scans=True)
    xla_flops = table["programs"]["learner_step"]["flops"]
    action_dim = table["action_dim"]
    analytic = costmodel.model_flops_per_step(cfg, action_dim,
                                              cfg.network.use_double)
    ratio = xla_flops / analytic
    assert 0.95 <= ratio <= 1.05, f"parity drifted: {ratio:.4f}"


def test_model_flops_single_source():
    # the component model quotes the same count, and walks the serial
    # chain once more (the target's forward) under double-DQN
    cfg = gate_cfg()
    for use_double, walks in ((False, 2), (True, 3)):
        analytic = costmodel.analytic_component_costs(cfg, 6, use_double)
        assert analytic["model_flops_per_step"] == \
            costmodel.model_flops_per_step(cfg, 6, use_double)
        assert analytic["serial_chain"]["iterations"] == \
            cfg.sequence.seq_len * walks
    # double-DQN adds exactly one extra unroll of every matmul
    single = costmodel.model_flops_per_step(cfg, 6, False)
    double = costmodel.model_flops_per_step(cfg, 6, True)
    assert double > single


def test_analytic_component_costs_structure():
    an = costmodel.analytic_component_costs(gate_cfg(), 6)
    comps = an["components"]
    assert set(comps) == {"torso", "lstm", "head", "sum_tree", "replay"}
    for name, c in comps.items():
        assert c["bytes"] > 0, name
        assert c["flops"] >= 0, name
    assert an["total_flops"] > 0
    assert 0 < an["serial_chain"]["share_of_total"] < 1
    # double-DQN, unfused: fwd + bwd + target fwd chain walks
    assert an["serial_chain"]["iterations"] == \
        gate_cfg().sequence.seq_len * 3


def test_analytic_golden_file():
    # deterministic pure math — exact golden comparison. Regenerate
    # deliberately (see tests/data/) when the model changes; a silent
    # drift here is exactly what the costs gate exists to catch.
    with open(GOLDEN) as f:
        golden = json.load(f)
    current = costmodel.analytic_component_costs(gate_cfg(),
                                                 golden["action_dim"])
    assert json.loads(json.dumps(current)) == golden["analytic"]


def test_peak_spec_table():
    # "TPU v5 lite" is the device_kind the v5e chip reports (PR 21's
    # chip_smoke run); its row carries the published peaks
    v5e = costmodel.peak_spec("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_gbps"] == 819.0
    assert not v5e["nominal"]
    # the test backend gets the flagged nominal row, never a real one
    assert costmodel.peak_spec("cpu")["nominal"] is True
    # any other device missing from the table is an error, not a default
    with pytest.raises(KeyError, match="weird accelerator"):
        costmodel.peak_spec("weird accelerator")


# ---------------------------------------------------------------------------
# traceparse on the checked-in miniature trace


def test_traceparse_mini_trace_attribution():
    s = traceparse.attribute_trace(MINI_TRACE)
    # >= 80% of device time attributed; the rest visible, never dropped
    assert s["attributed_frac"] >= 0.8
    assert s["components"]["unattributed"]["time_us"] == 90.0
    # the host plane's 100 ms python event is excluded from device time,
    # and the "XLA Modules" thread's whole-module enclosing span (1290
    # us under the SAME device pid in the fixture) is not double-counted
    # on top of the per-op "XLA Ops" events
    assert s["total_us"] == 1290.0
    assert not s["host_fallback"]
    for comp in ("torso", "lstm", "head", "sum_tree", "replay",
                 "env_step", "emit_blocks"):
        assert comp in s["components"], comp
    # shares sum to 1 over every component incl. unattributed
    assert sum(c["share"] for c in s["components"].values()) == \
        pytest.approx(1.0, abs=1e-4)
    assert traceparse.format_attribution(s)


def test_traceparse_dir_discovery(tmp_path):
    # the ProfilerCapture layout: plugins/profile/<ts>/*.trace.json.gz
    nested = tmp_path / "plugins" / "profile" / "2026_08_03"
    nested.mkdir(parents=True)
    shutil.copy(MINI_TRACE, nested / "host.trace.json.gz")
    s = traceparse.attribute_trace(str(tmp_path))
    assert s["total_us"] == 1290.0
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError):
        traceparse.attribute_trace(str(empty))


def test_traceparse_host_fallback():
    # a capture with no device plane (CPU backend) attributes ALL
    # tracks and says so
    events = [
        {"ph": "M", "pid": 7, "name": "process_name",
         "args": {"name": "python"}},
        {"ph": "X", "pid": 7, "tid": 1, "ts": 0, "dur": 10,
         "name": "jit(step)/torso/conv"},
    ]
    s = traceparse.attribute_trace(events)
    assert s["host_fallback"] and s["total_us"] == 10.0
    assert s["components"]["torso"]["time_us"] == 10.0


def test_traceparse_excludes_derived_thread_lines():
    # xprof derives whole-module / name-scope / framework-op lines from
    # the same op stream under the SAME device pid — counting them would
    # double- or triple-count every op (the real-capture layout; the
    # checked-in fixture carries the "XLA Modules" case)
    events = [
        {"ph": "M", "pid": 1, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 1, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 1, "tid": 2, "name": "thread_name",
         "args": {"name": "TensorFlow Name Scope"}},
        {"ph": "M", "pid": 1, "tid": 3, "name": "thread_name",
         "args": {"name": "Steps"}},
        {"ph": "X", "pid": 1, "tid": 1, "ts": 0, "dur": 100,
         "name": "fusion.1", "args": {"long_name": "jit/torso/conv"}},
        {"ph": "X", "pid": 1, "tid": 2, "ts": 0, "dur": 100,
         "name": "torso"},
        {"ph": "X", "pid": 1, "tid": 3, "ts": 0, "dur": 100,
         "name": "step 7"},
    ]
    s = traceparse.attribute_trace(events)
    assert s["total_us"] == 100.0
    assert s["components"]["torso"]["time_us"] == 100.0


def test_component_of_ordering():
    # nested network scopes beat their enclosing acting/loss scopes
    assert traceparse.component_of("jit/act_forward/torso/conv") == "torso"
    assert traceparse.component_of("jit/loss/reduce") == "loss"
    assert traceparse.component_of("jit/act_forward/argmax") == "act_forward"
    assert traceparse.component_of("copy.3") is None


# ---------------------------------------------------------------------------
# roofline report


def test_roofline_report_build():
    from r2d2_tpu.tools.roofline import build_report, format_report
    cfg = gate_cfg()
    report = build_report(cfg, "gate", step_time_ms=5.0,
                          peak=costmodel.peak_spec())
    ls = report["learner_step"]
    assert set(ls["components"]) == {"torso", "lstm", "head", "sum_tree",
                                     "replay"}
    for name, row in ls["components"].items():
        assert row["arithmetic_intensity"] >= 0
        assert row["bound"] in ("compute", "memory"), name
        assert row["pct_of_peak"] is not None
    assert ls["pct_of_peak_total"] > 0
    # acceptance: learner-step total FLOPs within 5% of the bench count
    assert report["parity"]["ratio"] == pytest.approx(1.0, abs=0.05)
    assert report["anakin_act"]["flops_per_env_step"] > 0
    assert "implied_tau_us_upper" in ls["serial_chain"]
    assert "roofline @" in format_report(report)


def test_roofline_cli_artifact(tmp_path):
    from r2d2_tpu.tools import roofline
    out = tmp_path / "ROOFLINE.json"
    assert roofline.main(["--preset", "gate", "--step-time-ms", "5",
                          "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert doc["schema"] == 1 and doc["learner_step"]["measured_ms"] == 5.0


# ---------------------------------------------------------------------------
# the costs regression gate


def test_compare_cost_tables_exact_gate():
    base = {"programs": {"learner_step": {"flops": 100.0, "bytes_accessed":
                                          50.0},
                         "replay_sample": {"flops": 10.0}}}
    ok = costmodel.compare_cost_tables(base, json.loads(json.dumps(base)))
    assert all(r["status"] == "ok" for r in ok)

    # an injected 2x FLOP change fails — in EITHER direction
    doubled = {"programs": {"learner_step": {"flops": 200.0,
                                             "bytes_accessed": 50.0},
                            "replay_sample": {"flops": 10.0}}}
    rows = costmodel.compare_cost_tables(base, doubled)
    changed = [r for r in rows if r["status"] == "CHANGED"]
    assert len(changed) == 1 and changed[0]["metric"] == "flops"
    assert changed[0]["delta_pct"] == 100.0
    halved = {"programs": {"learner_step": {"flops": 50.0,
                                            "bytes_accessed": 50.0},
                           "replay_sample": {"flops": 10.0}}}
    assert any(r["status"] == "CHANGED"
               for r in costmodel.compare_cost_tables(base, halved))

    # a vanished program is a failure too, never a silent pass
    missing = {"programs": {"learner_step": {"flops": 100.0,
                                             "bytes_accessed": 50.0}}}
    rows = costmodel.compare_cost_tables(base, missing)
    assert any(r["status"] == "missing" for r in rows)


def test_regress_gate_fires_on_injected_flops_change(tmp_path,
                                                     monkeypatch, capsys):
    # end-to-end through the regress CLI, with the expensive live
    # recompute stubbed by a fixture table: the baseline snapshots it,
    # the gate passes unchanged, then an injected 2x FLOP change in one
    # step factory fails the run
    from r2d2_tpu.tools import regress
    table = {"schema": 1, "backend": "cpu",
             "programs": {"learner_step": {"flops": 1000.0,
                                           "bytes_accessed": 500.0},
                          "anakin_act": {"flops": 80.0}}}
    current = {"v": json.loads(json.dumps(table))}
    monkeypatch.setattr(
        "r2d2_tpu.telemetry.costmodel.gate_table", lambda: current["v"])
    baseline = tmp_path / "BASELINE.json"
    baseline.write_text(json.dumps({"bench": {}}))
    art_dir = tmp_path / "artifacts"
    art_dir.mkdir()
    (art_dir / "E2E_r99.json").write_text(
        json.dumps({"env_steps_per_sec": 100.0}))
    assert regress.main(["--baseline", str(baseline), "--dir",
                         str(art_dir), "--update"]) == 0
    assert json.loads(baseline.read_text())["costs"] == table

    assert regress.main(["--baseline", str(baseline), "--dir",
                         str(art_dir)]) == 0          # unchanged: passes
    current["v"] = json.loads(json.dumps(table))
    current["v"]["programs"]["anakin_act"]["flops"] *= 2   # injected 2x
    assert regress.main(["--baseline", str(baseline), "--dir",
                         str(art_dir)]) == 1
    assert "CHANGED" in capsys.readouterr().out
    # --skip-costs keeps the bench-only behavior
    assert regress.main(["--baseline", str(baseline), "--dir",
                         str(art_dir), "--skip-costs"]) == 0


# ---------------------------------------------------------------------------
# record wiring + kill switch + config round-trip


def _learner(tmp_path, **overrides):
    from r2d2_tpu.runtime.learner_loop import Learner
    cfg = gate_cfg(**{"runtime.save_dir": str(tmp_path),
                      "runtime.save_interval": 0,
                      "runtime.steps_per_dispatch": 1, **overrides})
    _, _, net = _net_and_spec(cfg)
    return Learner(cfg, net, 0)


def test_costs_block_rides_exactly_one_record(tmp_path):
    learner = _learner(tmp_path)
    learner.flush_metrics()
    record = learner.metrics.log(1.0)
    costs = record["costs"]
    assert set(costs["components"]) == {"torso", "lstm", "head",
                                        "sum_tree", "replay"}
    assert costs["model_flops_per_step"] > 0
    assert costs["serial_chain"]["iterations"] > 0
    # static per config: exactly ONE record carries it
    learner.flush_metrics()
    assert "costs" not in learner.metrics.log(1.0)


def test_costs_killswitch_leaves_records_byte_identical(tmp_path):
    on = _learner(tmp_path / "on")
    off = _learner(tmp_path / "off",
                   **{"telemetry.costmodel_enabled": False})
    on.flush_metrics()
    off.flush_metrics()
    r_on, r_off = on.metrics.log(1.0), off.metrics.log(1.0)
    assert "costs" not in r_off
    # identical schema + content modulo the costs key and wall-clock t
    r_on.pop("costs")
    for r in (r_on, r_off):
        r.pop("t")
    assert json.dumps(r_on, sort_keys=True) == \
        json.dumps(r_off, sort_keys=True)


def test_costmodel_config_roundtrip():
    cfg = Config()
    assert cfg.telemetry.costmodel_enabled is True
    # pre-PR9 serialized configs (no costmodel field) load with default
    d = cfg.to_dict()
    del d["telemetry"]["costmodel_enabled"]
    assert Config.from_dict(d).telemetry.costmodel_enabled is True
    off = cfg.replace(**{"telemetry.costmodel_enabled": False})
    assert Config.from_json(
        off.to_json()).telemetry.costmodel_enabled is False


def test_inspect_costs_panel(tmp_path):
    # the inspector's cost/roofline panel (ISSUE 9 satellite): renders
    # from the record's one-shot costs block + the newest roofline
    # artifact, and digs the block out of the stream's history
    from r2d2_tpu.tools import inspect as inspect_tool
    learner = _learner(tmp_path)
    learner.flush_metrics()
    rec_with = learner.metrics.log(1.0)
    rec_after = learner.metrics.log(1.0)
    from r2d2_tpu.tools.roofline import build_report
    roofline = build_report(gate_cfg(), "gate", step_time_ms=5.0,
                            peak=costmodel.peak_spec("TPU v5 lite"))
    frame = inspect_tool.render_record(rec_after,
                                       costs=rec_with["costs"],
                                       roofline=roofline)
    assert "costs:" in frame and "torso" in frame
    assert "%pk" in frame                       # roofline %-of-peak joined
    # the history digger finds the one record that carried the block
    assert inspect_tool.costs_record([rec_with, rec_after]) \
        == rec_with["costs"]
    assert inspect_tool.costs_record([rec_after]) is None
    # a roofline artifact for a DIFFERENT shape (mtime-discovered, e.g.
    # the gate fixture next to a reference run) is ignored, not joined
    other = json.loads(json.dumps(roofline))
    other["parity"]["model_flops_per_step"] *= 10
    frame = inspect_tool.render_record(rec_after,
                                       costs=rec_with["costs"],
                                       roofline=other)
    assert "different shape" in frame and "%pk" not in frame


@pytest.mark.slow
def test_anakin_profile_at_step_capture(tmp_path):
    # the ISSUE 9 satellite: the fused on-device loop now honors the
    # one-shot runtime.profile_at_step capture trigger — the capture
    # lands where traceparse expects it
    from r2d2_tpu.runtime.anakin_loop import run_anakin_train
    cfg = gate_cfg(**{
        "actor.on_device": True, "actor.anakin_lanes": 2,
        "runtime.save_dir": str(tmp_path), "runtime.save_interval": 0,
        "runtime.steps_per_dispatch": 1, "runtime.log_interval": 2.0,
        "runtime.profile_at_step": 1,
        "replay.learning_starts": 40,
        "telemetry.resources_enabled": False,
    })
    stacks = run_anakin_train(cfg, max_training_steps=3, max_seconds=120)
    assert stacks[0].learner.training_steps >= 1
    traces = glob.glob(os.path.join(str(tmp_path), "xprof", "**",
                                    "*.trace.json.gz"), recursive=True)
    assert traces, "profile_at_step produced no capture in the fused loop"
    # and the capture parses through the component attribution
    s = traceparse.attribute_trace(os.path.join(str(tmp_path), "xprof"))
    assert s["total_us"] >= 0
