"""The benchmark's files fit together and fit the driver's contract; every
runner rehearses on the CPU down to the one JSON line; a cell, a
configuration and a per-layer metric dropped in as new files are found
without an edit to any file that is there."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bm_structure
from benchmarks import harness, run

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_benchmark_json_meets_the_contract():
    bm_structure.contract(BENCH)
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    # files under paths have plain names
    for path in BENCH["paths"]:
        for root, _, files in os.walk(os.path.join(harness.ROOT, path)):
            if "__pycache__" in root:
                continue
            for f in files:
                assert bm_structure.PLAIN_PATH.match(os.path.join(root, f)), \
                    (root, f)


def test_metrics_are_declared_once_with_unit_direction_and_bound():
    bm_structure.metrics_declared_once(BENCH)


@pytest.mark.parametrize("cell_name", CELLS)
def test_cell_resolves_to_its_files(cell_name):
    bm_structure.cell_resolves(BENCH, cell_name)


def test_every_config_has_a_cell_and_a_file_of_its_own():
    bm_structure.every_config_has_a_cell_and_a_file(BENCH)


def test_traffic_may_not_override_a_size():
    with pytest.raises(harness.BenchError, match="sizes"):
        harness.program_overrides({"overrides": {"replay.batch_size": 128}},
                                  {"overrides": {"replay.batch_size": 4}})


def test_a_reading_declared_again_keeps_its_reader():
    """``anakin.train_step_ms`` is ``train_step_ms`` where it moves another
    end-to-end metric: the part after the last dot names the reader."""
    assert (harness.reader_of("anakin.train_step_ms")
            is harness.reader_of("train_step_ms"))
    with pytest.raises(harness.BenchError, match="no_such_reading"):
        harness.reader_of("anakin.no_such_reading")


def test_rehearsal_parameters_lie_over_the_mix_only_in_a_rehearsal():
    mix = {"parameters": {"subwindow_steps": 200, "check_sequences": 8},
           "rehearsal_parameters": {"subwindow_steps": 4}}
    assert harness.traffic_parameters(mix) == mix["parameters"]
    assert harness.traffic_parameters(mix, rehearse=True) == {
        "subwindow_steps": 4, "check_sequences": 8}


SUBWINDOW_MIXES = sorted(
    mix for mix in (os.path.splitext(f)[0] for f in os.listdir(
        os.path.join(harness.BENCH_DIR, "workloads")))
    if "subwindow_steps" in harness.traffic_doc(mix)["parameters"])


@pytest.mark.parametrize("mix", SUBWINDOW_MIXES)
def test_a_subwindow_is_whole_periods_of_the_learning_diagnostics(mix):
    """The train step runs its learning diagnostics (tens of ms) under a
    ``lax.cond`` every ``telemetry.learning_interval`` steps. A sub-window
    that is not whole periods of it holds the branch in some readings and not
    in others, and the median jumps between the two."""
    from r2d2_tpu.config import Config
    steps = harness.traffic_doc(mix)["parameters"]["subwindow_steps"]
    assert steps % Config().telemetry.learning_interval == 0


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


# The four-chip cell PERF.md plans (its mix's file is here already): an entry
# of ``workloads`` is all it takes.
PLANNED = {"name": "r2d2-ref.learner-dp4", "config": "r2d2-ref",
           "traffic": "learner-dp4", "chips": 4, "why": "planned"}


@pytest.mark.parametrize("cell_name", CELLS + [PLANNED["name"]])
def test_runner_rehearses_on_cpu_down_to_the_json_line(
        cell_name, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    if cell_name == PLANNED["name"]:
        bench = {**BENCH, "workloads": BENCH["workloads"] + [PLANNED]}
        monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    rc = run.main(["--workload", cell_name, "--seed", "3", "--seconds", "1.5",
                   "--trace", "1", "--rehearse", "1"])
    out, err = capsys.readouterr()
    line = _last_json(out)
    if cell_name == PLANNED["name"]:
        assert '"replicas_bit_equal": true' in out
    assert rc == 0
    assert set(line) == LINE_KEYS | {"rehearsal", "compared"}
    # each number compared beside its limit: the line's last key, and the
    # last lines on standard error
    assert list(line)[-1] == "compared" and len(line["compared"]) == 6
    assert all(0 <= got <= limit for got, limit in line["compared"].values())
    assert [text.split(":")[0] for text in err.strip().splitlines()[-6:]] \
        == [f"compared {name}" for name in line["compared"]]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}, "a CPU run prints no metric"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"
    # a CPU's capture holds nothing for the device-trace readers; the routing
    # counters of the traced dispatch reach them where the core has experts
    assert "readers that found something:" in out
    (facts,) = [json.loads(text[len("facts: "):]) for text in out.splitlines()
                if text.startswith("facts: ")]
    if cell_name == bm_structure.MOONLIGHT:
        counted = facts["moe_traced"]
        assert counted["steps"] == facts["steps_per_dispatch"] == 2
        assert 0 < counted["pairs_held"] <= counted["rows_walked"]
    else:
        assert "moe_traced" not in facts


@pytest.mark.parametrize("cell_name, counted, fewest", [
    ("r2d2-ref.learner", "subwindows", 3), ("r2d2-ref.anakin", "intervals", 5)])
def test_a_window_whose_seconds_a_stall_ate_closes_on_work_and_is_correct(
        cell_name, counted, fewest, tmp_path, monkeypatch, capsys):
    """A host that stands still eats a window's seconds (the driver's run of
    ``r2d2-paper.learner`` that PR 31 was refused for held 2 sub-windows of
    4). ``correct`` is for answers: the window closes on its seconds and on
    the fewest sub-windows a median stands on, and no check counts them.
    Here the seconds are gone before the first sub-window ends."""
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    rc = run.main(["--workload", cell_name, "--seed", "11", "--seconds",
                   "0.001", "--trace", "0", "--rehearse", "1"])
    out, _ = capsys.readouterr()
    line = _last_json(out)
    (facts,) = [json.loads(text[len("facts: "):]) for text in out.splitlines()
                if text.startswith("facts: ")]
    (checks,) = [json.loads(text[len("checks: "):])
                 for text in out.splitlines() if text.startswith("checks: ")]
    assert rc == 0 and line["correct"] is True
    assert facts[counted] == fewest
    assert counted not in checks and all(checks.values())


def test_no_chip_no_number(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
                  "--trace", "0"])
    assert e.value.code not in (0, None)
    assert '"metrics"' not in capsys.readouterr().out


NEW_READER = '''"""A dropped-in reader of a counter."""


def read(ctx):
    return 42.0
'''

# a reader over a scope no scope table names: it matches its own token
NEW_SCOPE_READER = '''"""Self time of the first convolution (flax names it
``Conv_0``), as a percentage of device busy time."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.self_under_s("Conv_0") / ctx.trace.busy_s()
'''

# a reference of another name: here the same arithmetic, announced
NEW_REFERENCE = '''"""A dropped-in plain reference."""

from benchmarks.reference import r2d2


def from_config(cfg):
    print("reference: added_reference was asked")
    return r2d2.from_config(cfg)
'''

# what the dropped-in files make of the recorded capture, run in the copy
READ_FIXTURE = '''
import glob, json
from benchmarks import harness, run
from benchmarks.trace import reduce, xspace_text
config = harness.config_doc(harness.load_benchmark(), "added-config")
(path,) = glob.glob("benchmarks/trace/fixtures/*.txt.gz")
summary = reduce.summarize_data(xspace_text.load(path),
                                scopes=harness.scope_table(config))
ctx = run.MetricContext(cfg=None, values={}, facts={}, trace=summary,
                        device_kind="TPU v5 lite")
print(json.dumps({
    "share": harness.reader_of("added_scope_share").read(ctx),
    "rows": summary.self_by_scope(), "busy": summary.busy_s()}))
'''


def _run_copy(root, *args, pythonpath, script=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": pythonpath}
    command = (["-c", script] if script is not None
               else [os.path.join(root, "benchmarks", "run.py"), *args])
    return subprocess.run([sys.executable, *command], cwd=root, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture
def copy_of_benchmark(tmp_path):
    """Only ``BENCHMARK.json`` and the files under ``paths``."""
    root = str(tmp_path / "checkout")
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, path),
                        os.path.join(root, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    return root


def test_new_files_are_found_without_editing_any(copy_of_benchmark):
    """A configuration (with a scope table and a reference of its own), a
    traffic mix, a cell and two per-layer metrics, one of them over a scope
    that no table names, come as new files and entries alone."""
    root = copy_of_benchmark
    bench = json.loads(json.dumps(BENCH))
    config = harness.config_doc(BENCH, "r2d2-ref")
    config.update(name="added-config", scopes="added-scopes",
                  reference="added_reference")
    traffic = harness.traffic_doc("learner")
    traffic["name"] = "added-mix"
    files = {
        "benchmarks/configs/added-config.json": json.dumps(config),
        "benchmarks/workloads/added-mix.json": json.dumps(traffic),
        "benchmarks/trace/scopes/added-scopes.json": json.dumps(
            {"scopes": [["Dense_0", "torso_dense"], ["torso", "torso_convs"]]}),
        "benchmarks/reference/added_reference.py": NEW_REFERENCE,
        "benchmarks/layer_metrics/added_metric.py": NEW_READER,
        "benchmarks/layer_metrics/added_scope_share.py": NEW_SCOPE_READER,
    }
    before = {f: open(os.path.join(dp, f)).read()
              for dp, _, fs in os.walk(os.path.join(root, "benchmarks"))
              for f in fs if f.endswith((".py", ".json"))}
    for rel, text in files.items():
        with open(os.path.join(root, rel), "w") as f:
            f.write(text)
    bench["configs"].append({
        "name": "added-config", "source": config["source"],
        "file": "benchmarks/configs/added-config.json", "reduced": [],
        "why": "dropped in"})
    bench["workloads"].append({
        "name": "added-config.added-mix", "config": "added-config",
        "traffic": "added-mix", "chips": 1, "why": "dropped in"})
    for name in ("added_metric", "added_scope_share"):
        bench["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "device",
            "moves": "seq_updates_per_s",
            "workloads": ["added-config.added-mix"]})
    bench["end_to_end"][0]["workloads"].append("added-config.added-mix")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    done = _run_copy(root, "--workload", "added-config.added-mix", "--seed",
                     "5", "--seconds", "1", "--trace", "1", "--rehearse", "1",
                     pythonpath=harness.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    # the CPU's capture holds no device operation: only the counter reads
    assert "readers that found something: ['added_metric']" in done.stdout
    assert "reference: added_reference was asked" in done.stdout
    assert _last_json(done.stdout)["correct"] is True
    # the chip's recorded capture, through the added table and reader
    done = _run_copy(root, pythonpath=harness.ROOT, script=READ_FIXTURE)
    assert done.returncode == 0, done.stderr[-2000:]
    read = _last_json(done.stdout)
    assert set(read["rows"]) == {"torso_dense", "torso_convs", "unattributed"}
    assert sum(read["rows"].values()) == pytest.approx(read["busy"], rel=1e-6)
    # the first convolution, forward and backward, is a part of the torso
    convs = 100.0 * read["rows"]["torso_convs"] / read["busy"]
    assert 5.0 < read["share"] < convs
    after = {f: open(os.path.join(dp, f)).read()
             for dp, _, fs in os.walk(os.path.join(root, "benchmarks"))
             for f in fs if f.endswith((".py", ".json")) and f in before}
    assert after == before, "an existing file was edited"


def test_fails_where_only_the_benchmark_is(copy_of_benchmark):
    done = _run_copy(copy_of_benchmark, "--workload", CELLS[0], "--seed", "5",
                     "--seconds", "1", "--trace", "0", pythonpath="")
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
