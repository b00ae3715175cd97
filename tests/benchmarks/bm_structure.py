"""What the tests under ``tests/benchmarks/`` hold of ``BENCHMARK.json``'s
structure, each as a function of the loaded file (``bench``) and of the
checkout its files are found in (``root``). A test file calls them on the
file as it stands; ``test_bm_structure.py`` calls every one of them (``ALL``)
on a copy with a configuration, a cell and a per-layer entry appended, the
way a later PR adds them.

The rule they keep (PERF.md, section 2): an assertion holds that an accepted
entry is a member of its list and stands in its order among the others that
were accepted, never that it is the last of a list or that a list has so many
entries. A later PR appends to ``configs``, ``workloads``, ``per_layer`` and
to the ``workloads`` lists of the metrics its cell reports, and may touch no
file that is here."""

import copy
import json
import os
import re
import shutil

from benchmarks import harness

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_./-]+$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

ANAKIN = "r2d2-ref.anakin"
MOONLIGHT = "moonlight-core.learner-long"
LSTM_LEARNERS = ["r2d2-ref.learner", "r2d2-paper.learner"]
# PR 24's entries of ``per_layer``, with the layer of each
ANAKIN_SPAN_READERS = {"anakin.log_stall_ms": "orchestration",
                       "anakin.ring_write_host_ms": "ingest",
                       "anakin.accounting_host_ms": "orchestration"}
# the metrics ``moonlight-core.learner-long`` joined in PR 27
MOONLIGHT_JOINED = ["seq_updates_per_s", "dispatch_host_ms", "train_step_ms",
                    "torso_self_share", "k_decode_roofline",
                    "k_gather_roofline", "device_idle_share"]
# PR 31's entries of ``per_layer``, for that cell alone: (layer, better)
MOONLIGHT_READERS = {"core_self_share": ("memory_core", "lower"),
                     "mla_self_share": ("memory_core", "lower"),
                     "moe_self_share": ("memory_core", "lower"),
                     "moe_dispatch_self_share": ("memory_core", "lower"),
                     "k_experts_roofline": ("kernels", "higher")}


def cells_of(bench):
    return [c["name"] for c in bench["workloads"]]


def per_layer_names(bench):
    return [m["name"] for m in bench["per_layer"]]


def metric(bench, name):
    return next(m for m in bench["end_to_end"] + bench["per_layer"]
                if m["name"] == name)


def in_order(names, sequence):
    """``names`` all stand in ``sequence``, in their order (others may stand
    between, before and after them)."""
    if not set(names) <= set(sequence):
        return False
    places = [sequence.index(n) for n in names]
    return places == sorted(places)


def contiguous(names, sequence):
    """``names`` stand in ``sequence`` side by side, in their order."""
    if names[0] not in sequence:
        return False
    at = sequence.index(names[0])
    return sequence[at:at + len(names)] == list(names)


def contract(bench, root=harness.ROOT):
    """The limits the driver's contract sets on the file's entries (its size
    and the files' names: ``test_bm_harness.py``)."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert PLAIN_PATH.match(path) and len(path) <= 200
        assert not path.startswith("/") and ".." not in path.split("/")
        assert os.path.isdir(os.path.join(harness.ROOT, path))
    assert len(bench["command"]) <= 32
    for arg in bench["command"]:
        if os.path.exists(os.path.join(harness.ROOT, arg)):
            assert any(arg.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["configs"]) <= 24
    assert 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    cells = cells_of(bench)
    names = ([c["name"] for c in bench["configs"]] + cells
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names)), "a name is used once"
    assert all(NAME.match(n) for n in names)
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    four = [c for c in bench["workloads"] if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in bench["workloads"])
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def metrics_declared_once(bench, root=harness.ROOT):
    sources = {"device_trace", "program_span", "program_counter",
               "host_clock"}
    cells = cells_of(bench)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert m["unit"] and m["better"] in ("higher", "lower")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert e2e["setup_s"]["bound"] == 0.1
    for m in bench["per_layer"]:
        assert m["unit"] and m["better"] in ("higher", "lower")
        assert m["source"] in sources and m["layer"]
        assert m["moves"] in e2e and "bound" not in m
        assert m["unit"] == "%" or not m["name"].endswith("_roofline")
        assert callable(harness.reader_of(m["name"]).read)
        # a metric reports in the cells that report what it moves
        assert set(m.get("workloads", cells)) <= set(
            e2e[m["moves"]].get("workloads", cells)), m["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= set(cells)
        assert UNIT.match(m["unit"]), (m["name"], m["unit"])


def cell_resolves(bench, cell_name, root=harness.ROOT):
    cell = harness.find_cell(bench, cell_name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert any(entry["file"].startswith(p + "/") for p in bench["paths"])
    config = harness.config_doc(bench, cell["config"], root)
    traffic = harness.traffic_doc(cell["traffic"], root)
    runner = harness.load_named("runners", traffic["runner"])
    assert callable(runner.run) and callable(runner.load_program)
    # the configuration's plain reference, its rows of busy time and its
    # count of model work
    assert callable(harness.load_named("reference",
                                       config["reference"]).from_config)
    table = harness.scope_table(config, root)
    assert table and all(token and scope for token, scope in table)
    assert callable(harness.costs_of(config).step_flops)
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    # the full-size Config builds (sizes are consistent; nothing is allocated)
    cfg = harness.build_config(harness.program_overrides(config, traffic),
                               "unused", 0)
    assert cfg.mesh.dp == cell["chips"]
    # what the cell reports: set-up, another end-to-end metric, a layer's
    e2e = [m["name"] for m in harness.cell_metrics(bench, cell_name,
                                                   "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(bench, cell_name, "per_layer")


def every_cell_resolves(bench, root=harness.ROOT):
    for cell_name in cells_of(bench):
        cell_resolves(bench, cell_name, root)


def every_config_has_a_cell_and_a_file(bench, root=harness.ROOT):
    used = {c["config"] for c in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert used == {c["name"] for c in bench["configs"]}
    assert len(files) == len(set(files))


def anakin_span_readers(bench, root=harness.ROOT):
    """PR 24's three metrics are declared for the fused loop alone and keep
    their place: side by side, in their order, after what PR 22 left."""
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in ANAKIN_SPAN_READERS.items():
        assert declared[name] == {
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": layer,
            "moves": "env_steps_per_s", "workloads": [ANAKIN]}
    names = per_layer_names(bench)
    assert contiguous(list(ANAKIN_SPAN_READERS), names)
    assert in_order(["anakin.device_idle_share", "anakin.log_stall_ms"], names)
    in_cell = [m["name"] for m in harness.cell_metrics(bench, ANAKIN,
                                                       "per_layer")]
    assert set(ANAKIN_SPAN_READERS) <= set(in_cell)
    for cell in bench["workloads"]:
        if cell["name"] != ANAKIN:
            assert not set(ANAKIN_SPAN_READERS) & {
                m["name"] for m in harness.cell_metrics(
                    bench, cell["name"], "per_layer")}


def moonlight_cell_joins(bench, root=harness.ROOT):
    """``moonlight-core.learner-long`` reports the accepted metrics it joined
    in PR 27, after the two LSTM learner cells; its own readers (PR 31) are
    declared for it alone, each after every entry PRs 22 and 24 left."""
    for name in MOONLIGHT_JOINED + ["mfu_bf16"]:
        assert in_order(LSTM_LEARNERS + [MOONLIGHT],
                        metric(bench, name)["workloads"]), name
    assert MOONLIGHT not in metric(bench, "lstm_self_share")["workloads"]
    names = per_layer_names(bench)
    assert in_order(["anakin.accounting_host_ms"] + list(MOONLIGHT_READERS),
                    names)
    assert contiguous(list(MOONLIGHT_READERS), names)
    for name, (layer, better) in MOONLIGHT_READERS.items():
        assert metric(bench, name) == {
            "name": name, "unit": "%", "better": better,
            "source": "device_trace", "layer": layer,
            "moves": "seq_updates_per_s", "workloads": [MOONLIGHT]}
    in_cell = {m["name"] for m in harness.cell_metrics(bench, MOONLIGHT,
                                                       "per_layer")}
    assert set(MOONLIGHT_READERS) | {"mfu_bf16"} <= in_cell


def moonlight_config_entry(bench, root=harness.ROOT):
    entry = next(c for c in bench["configs"] if c["name"] == "moonlight-core")
    config = harness.config_doc(bench, "moonlight-core", root)
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced"]) == {
        "network.core.num_hidden_layers", "network.core.experts_held",
        "replay.capacity", "num_hidden_layers"}
    # the window and batch of r2d2-paper: the two cells differ in the core
    overrides = config["overrides"]
    paper = harness.config_doc(bench, "r2d2-paper", root)["overrides"]
    same = {k: v for k, v in overrides.items()
            if k in paper and k != "replay.capacity"}
    assert same == {k: v for k, v in paper.items() if k != "replay.capacity"}


MADE_UP_CELL = "made-up.learner"
MADE_UP_METRIC = "made-up.lstm_self_share"


def appended_copy(bench, root):
    """(copy, root): ``bench`` with a made-up configuration, a made-up
    learner cell (its name at the end of the ``workloads`` list of every
    metric in ``MOONLIGHT_JOINED``) and a made-up per-layer entry appended,
    as a later PR appends them, and under the empty directory ``root`` the
    data files they are found in. A later PR's test file holds its own
    structural assertions to such a copy too."""
    for part in ("configs", "workloads", os.path.join("trace", "scopes")):
        shutil.copytree(os.path.join(harness.BENCH_DIR, part),
                        os.path.join(root, "benchmarks", part))
    config = harness.config_doc(bench, "r2d2-paper")
    config["name"] = "made-up"
    with open(os.path.join(root, "benchmarks", "configs", "made-up.json"),
              "w") as f:
        json.dump(config, f)
    bench = copy.deepcopy(bench)
    bench["configs"].append({
        "name": "made-up", "source": config["source"],
        "file": "benchmarks/configs/made-up.json",
        "reduced": config["reduced"], "why": "made up"})
    bench["workloads"].append({
        "name": MADE_UP_CELL, "config": "made-up", "traffic": "learner",
        "chips": 1, "why": "made up"})
    for name in MOONLIGHT_JOINED:
        metric(bench, name)["workloads"].append(MADE_UP_CELL)
    bench["per_layer"].append({
        "name": MADE_UP_METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "train_step",
        "moves": "seq_updates_per_s", "workloads": [MADE_UP_CELL]})
    return bench, root


ALL = [contract, metrics_declared_once, every_cell_resolves,
       every_config_has_a_cell_and_a_file, anakin_span_readers,
       moonlight_cell_joins, moonlight_config_entry]
