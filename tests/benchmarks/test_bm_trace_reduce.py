"""The reduction from a profiler capture to numbers, on a capture built by
hand (a nested ``while``, two devices, a collective, known gaps) and on a
small capture recorded on the chip and kept under
``benchmarks/trace/fixtures/``."""

import glob
import os

import pytest
from jax.profiler import ProfileData

from benchmarks import harness
from benchmarks.trace import reduce, xspace_text

SCOPE = "jit(multi_step)/jit(main)/while/body/"
TABLE = harness.scope_table({"scopes": "r2d2"})


def _ops(shift=0.0, stall=0.0):
    """One train-step program on a device's operation line, in ns: a conv, a
    ``while`` that encloses two fusions per iteration, a collective, Adam.
    ``stall`` lengthens the collective."""
    def op(name, start, dur, path, **stats):
        # named as a TPU capture names them: by the instruction's text
        return (f"%{name} = f32[8]{{0}} op(f32[8]{{0}} %p)", shift + start, dur,
                {"op_name": SCOPE + path, **stats})
    return [
        op("fusion.1", 1000, 1000, "torso/Conv_0/conv_general_dilated"),
        op("while.2", 2500, 6000, "lstm/while"),
        op("fusion.3", 2600, 2000, "lstm/while/body/dot_general"),
        op("custom-call.4", 5000, 3000, "lstm/while/body/pallas_call",
           kernel=1),
        op("all-reduce.5", 9000, 500 + stall, "pmean"),
        op("fusion.7", 9600 + stall, 0, "loss/sub"),     # no time of its own
        op("fusion.6", 10000 + stall, 1000, "optimizer/adam"),
    ]


def _capture(two_devices=False):
    host = [
        (harness.MARK_CLOCK, 0, 1, {"unix_ns": 1_790_000_000_000_000_000}),
        (harness.MARK_WINDOW_BEGIN, 500, 1, {}),
        ("dispatch", 600, 300, {}),
        ("block_wait", 900, 10000, {}),
        ("metrics_flush", 11200, 700, {}),
        ("PjitFunction(step)", 600, 250, {}),       # the runtime's own
        (harness.MARK_WINDOW_END, 12000, 1, {}),
    ]
    capture = {
        "/device:TPU:0": {
            "XLA Modules": [("jit_multi_step(1)", 1000, 10000, {})],
            "XLA Ops": _ops(),
            "Steps": [("0", 1000, 10000, {})],      # encloses, adds nothing
        },
        "/host:CPU": {"main": host},
    }
    if two_devices:
        capture["/device:TPU:1"] = {
            "XLA Modules": [("jit_multi_step(1)", 1000, 10500, {})],
            "XLA Ops": _ops(stall=500),
        }
    return ProfileData.from_text_proto(xspace_text.to_text(capture))


def test_self_time_sums_to_busy_and_a_parent_is_never_counted_with_its_child():
    s = reduce.summarize_data(_capture(), TABLE,
                              host_names=["dispatch", "block_wait",
                                          "metrics_flush"])
    by_scope = {k: round(v * 1e9) for k, v in s.self_by_scope().items()}
    # the while (6000 ns) holds 5000 ns of children: its own time is 1000
    assert by_scope == {"torso": 1000, "lstm": 6000, "unattributed": 500,
                        "loss": 0, "optimizer": 1000}
    busy = 1000 + 6000 + 500 + 1000
    assert round(s.busy_s() * 1e9) == busy
    assert s.self_total_s() == pytest.approx(s.busy_s(), rel=1e-9)
    while_op = next(o for o in s.devices[0].ops if o.name == "while.2")
    assert while_op.self_ns == 1000
    assert s.window == (500, 12000)
    assert s.idle_share() == pytest.approx(1 - busy / 11500)


def test_gaps_are_laid_to_the_host_spans_that_cover_them():
    s = reduce.summarize_data(_capture(), TABLE,
                              host_names=["dispatch", "block_wait",
                                          "metrics_flush"])
    idle = {k: round(v * 1e9) for k, v in s.idle_by_host_span().items()}
    # gaps: 500-1000, 2000-2500, 8500-9000, 9500-10000, 11000-12000
    assert idle == {
        "dispatch": 300,                       # 600-900
        "block_wait": 100 + 500 + 500 + 500,   # 900-1000 and three inside
        "metrics_flush": 700,                  # 11200-11900
        reduce.NO_HOST_SPAN: 100 + 200 + 100,  # 500-600, 11000-11200, 11900-
    }
    assert sum(idle.values()) == round(s.idle_share() * 11500)
    b = s.breakdown()
    assert b["idle_gaps"][0] == ["block_wait", pytest.approx(1600e-9)]
    assert b["device_ops"][0] == ["lstm:custom-call", pytest.approx(3000e-9)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert "PjitFunction(step)" not in idle    # not one of the benchmark's


def test_programs_are_found_by_the_scopes_they_hold():
    s = reduce.summarize_data(_capture(), TABLE)
    runs = s.module_runs("optimizer")
    assert [m.dur for m in runs] == [10000]
    assert s.module_runs("lstm", without=("optimizer",)) == []
    assert s.module_runs("act_forward") == []
    assert [o.name for o in s.kernel_calls("lstm")] == ["custom-call.4"]
    assert s.kernel_calls("torso") == []


def test_two_devices_busy_is_averaged_idle_and_collective_are_the_worst():
    s = reduce.summarize_data(_capture(two_devices=True), TABLE)
    assert len(s.devices) == 2
    assert round(s.busy_s() * 1e9) == (8500 + 9000) / 2
    # device 0 is the idler one (its collective returns sooner)
    assert s.idle_share() == pytest.approx(1 - 8500 / 11500)
    assert round(s.collective_self_s() * 1e9) == 1000
    one = reduce.summarize_data(_capture(two_devices=True), TABLE, chips=1)
    assert len(one.devices) == 1
    # the reader of the planned four-chip cell: over the 16 steps of the one
    # program traced, on the device that waits longest
    from benchmarks import run
    ctx = run.MetricContext(cfg=None, values={}, trace=s,
                            device_kind="TPU v5 lite",
                            facts={"steps_per_dispatch": 16, "dp": 2})
    reader = harness.reader_of("collective_exposed_ms")
    assert reader.read(ctx) == pytest.approx(1e-3 / 16)
    ctx.facts["dp"] = 1
    assert reader.read(ctx) is None


def test_a_reader_matches_a_scope_that_no_table_names():
    """An operation keeps its scope path; a reader finds its operations,
    programs and kernels by a token of it, whatever row of the table's split
    they fall in, and another table splits the same busy time otherwise."""
    s = reduce.summarize_data(_capture(), TABLE)
    assert s.self_under_s("Conv_0") == pytest.approx(1000e-9)
    assert s.self_under_s("pallas_call") == pytest.approx(3000e-9)
    assert s.self_under_s("while/body") == pytest.approx(
        s.busy_s())                        # every operation is in the step
    assert s.self_under_s("mamba") == 0.0
    assert [m.dur for m in s.module_runs("Conv_0")] == [10000]
    assert s.module_runs("Conv_0", without=("pmean",)) == []
    assert [o.name for o in s.kernel_calls("body")] == ["custom-call.4"]
    other = reduce.summarize_data(
        _capture(), [("pallas_call", "kernels"), ("lstm", "recurrence")])
    rows = {k: round(v * 1e9) for k, v in other.self_by_scope().items()}
    assert rows == {"kernels": 3000, "recurrence": 3000, "unattributed": 2500}
    assert other.self_total_s() == pytest.approx(other.busy_s(), rel=1e-9)
    bare = reduce.summarize_data(_capture())
    assert set(bare.self_by_scope()) == {"unattributed"}


@pytest.mark.parametrize("cell_name", [c["name"] for c in
                                       harness.load_benchmark()["workloads"]])
def test_every_reader_of_a_cell_reads_a_capture(cell_name):
    """Each per-layer metric a cell declares has a reader that takes a number
    or nothing from a reduced capture (here the hand-built one, as many
    devices as the cell has chips) and never raises."""
    from benchmarks import run

    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, cell_name)
    config = harness.config_doc(bench, cell["config"])
    cfg = harness.build_config(harness.program_overrides(
        config, harness.traffic_doc(cell["traffic"])), "unused", 0)
    summary = reduce.summarize_data(_capture(two_devices=cell["chips"] > 1),
                                    harness.scope_table(config),
                                    host_names=["dispatch", "block_wait"])
    ctx = run.MetricContext(
        cfg=cfg, trace=summary, device_kind="TPU v5 lite", config=config,
        values={"dispatch_host_s": [1e-3, 2e-3],
                "program_span_s": {"actor/act_scan": [1e-3],
                                   "learner/train_dispatch": [2e-3]}},
        facts={"steps_per_dispatch": 16, "dp": cell["chips"], "action_dim": 6,
               "act_bytes": 2})
    read = {}
    for m in harness.cell_metrics(bench, cell_name, "per_layer"):
        value = harness.reader_of(m["name"]).read(ctx)
        assert value is None or float(value) >= 0.0, m["name"]
        # under the name of its reading, whichever metric it moves here
        read[m["name"].rsplit(".", 1)[-1]] = value
    assert read["device_idle_share"] == pytest.approx(
        100 * summary.idle_share())
    # one program of 16 steps, 10,000 ns on the first device
    assert read["train_step_ms"] == pytest.approx(10000 / 1e6 / 16)
    assert read["dispatch_host_ms"] in (pytest.approx(1.5), pytest.approx(3.0))
    if "mfu_bf16" in read:
        flops = harness.costs_of(config).step_flops(cfg, 6)
        assert read["mfu_bf16"] == pytest.approx(
            100 * flops / (10000e-9 / 16) / 197e12)


def test_external_spans_ride_the_clock_marker():
    unix = 1_790_000_000.0
    s = reduce.summarize_data(
        _capture(), external_spans=[("learner/train_dispatch",
                                     unix + 600e-9, unix + 900e-9)])
    idle = s.idle_by_host_span()
    assert idle["learner/train_dispatch"] == pytest.approx(300e-9, rel=0.5)


def test_a_capture_without_device_operations_reduces_to_nothing():
    data = ProfileData.from_text_proto(xspace_text.to_text(
        {"/host:CPU": {"main": [("dispatch", 0, 10, {})]}}))
    assert reduce.summarize_data(data) is None


def _message(*fields):
    """A protobuf message from (number, bytes | int) fields."""
    out = bytearray()

    def varint(n):
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return

    for number, value in fields:
        if isinstance(value, int):
            varint(number << 3)
            varint(value)
        else:
            varint((number << 3) | 2)
            varint(len(value))
            out.extend(value)
    return bytes(out)


def test_scope_paths_are_read_from_the_hlo_the_capture_keeps():
    from benchmarks.trace import hlo_names

    def instruction(name, op_name):
        return _message((1, name.encode()), (2, b"fusion"), (35, 7),
                        (7, _message((1, b"dot"), (2, op_name.encode()))))

    hlo = _message((1, _message(
        (1, b"jit_step"),
        (3, _message((1, b"main"),
                     (2, instruction("fusion.12", "jit(step)/torso/conv")),
                     (2, instruction("while.3", "jit(step)/lstm/while")),
                     (2, _message((1, b"tuple.1"), (2, b"tuple"))))))))
    xspace = _message((1, _message(
        (2, b"/host:metadata"),
        (4, _message((1, 9), (2, _message(
            (1, 9), (2, b"jit_step(42)"),
            (5, _message((1, 1), (6, hlo))))))))),
        (1, _message((2, b"/host:CPU"))))
    scopes = hlo_names.program_scopes(xspace)
    assert scopes == {"jit_step(42)": {"fusion.12": "jit(step)/torso/conv",
                                       "while.3": "jit(step)/lstm/while"}}
    assert hlo_names.instruction_name(
        "%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") \
        == "fusion.12"
    # and the reduction joins them to the events by program and instruction
    capture = {"/device:TPU:0": {
        "XLA Modules": [("jit_step(42)", 0, 100, {})],
        "XLA Ops": [("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p)", 10, 50,
                     {})]}}
    s = reduce.summarize_data(
        ProfileData.from_text_proto(xspace_text.to_text(capture)), TABLE,
        program_scopes=scopes)
    assert s.self_by_scope() == {"torso": pytest.approx(50e-9)}


FIXTURES = sorted(glob.glob(os.path.join(
    harness.BENCH_DIR, "trace", "fixtures", "*.txt.gz")))


@pytest.mark.parametrize("path", FIXTURES, ids=os.path.basename)
def test_recorded_capture_reduces(path):
    """One dispatch of 16 train steps recorded on a TPU v5e (the fixtures'
    README says how)."""
    s = reduce.summarize_data(
        xspace_text.load(path), TABLE,
        host_names=["dispatch", "block_wait", "metrics_flush"])
    assert s is not None and len(s.devices) == 1
    by_scope = s.self_by_scope()
    # 41,000 nested operation events: self times add up to the busy time
    assert s.self_total_s() == pytest.approx(s.busy_s(), rel=1e-6)
    assert s.busy_s() == pytest.approx(0.2101, rel=1e-3)
    assert s.idle_share() == pytest.approx(0.0030, abs=2e-4)
    for scope in ("lstm", "torso", "head", "obs_decode", "replay_sample",
                  "sum_tree", "optimizer", "loss", "network_glue"):
        assert by_scope.get(scope, 0.0) > 0.0, scope
    named = sum(v for k, v in by_scope.items() if k != reduce.UNATTRIBUTED)
    assert named / s.self_total_s() > 0.8
    assert by_scope["torso"] > by_scope["lstm"]       # what the chip showed
    # the one program that holds the loss, its 16 steps' kernels
    (run,) = s.module_runs("loss")
    assert run.name.startswith("jit_multi_step(")
    assert len(s.kernel_calls("obs_decode")) == 16
    assert len(s.kernel_calls("replay_sample")) == 16
    # the device waits while the host is still dispatching
    idle = s.idle_by_host_span()
    assert max(idle, key=idle.get) == "dispatch"
    assert s.breakdown()["device_ops"][0][0] == "torso:fusion"


def test_there_is_a_recorded_capture():
    assert FIXTURES, "benchmarks/trace/fixtures holds no recorded capture"
