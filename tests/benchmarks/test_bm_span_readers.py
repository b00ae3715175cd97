"""The per-layer metrics that read the fused loop's own spans
(``anakin.log_stall_ms``, ``anakin.ring_write_host_ms``,
``anakin.accounting_host_ms``): on a hand-built ``program_span_s``, on a
program that writes no such span, and on the cell's CPU rehearsal, where
the loop's span tree is read back from ``spans_player0.jsonl``."""

import json
import os

import pytest

import bm_structure
from benchmarks import harness, run

BENCH = harness.load_benchmark()
CELL = "r2d2-ref.anakin"
READERS = {"anakin.log_stall_ms": "anakin/log",
           "anakin.ring_write_host_ms": "ingest/commit",
           "anakin.accounting_host_ms": "anakin/accounting"}


def _ctx(program_span_s):
    values = {} if program_span_s is None else {
        "program_span_s": program_span_s}
    return run.MetricContext(cfg=None, values=values, facts={}, trace=None,
                             device_kind="cpu")


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_takes_the_median_of_its_span_in_ms(metric):
    read = harness.reader_of(metric).read
    spans = {name: [0.5] for name in READERS.values()}
    spans[READERS[metric]] = [0.004, 0.050, 0.002, 0.003]   # seconds
    assert read(_ctx(spans)) == pytest.approx(3.5)
    spans[READERS[metric]] = [0.007]
    assert read(_ctx(spans)) == pytest.approx(7.0)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_where_the_program_has_no_such_span(metric):
    """The parent commit's loop writes ``actor/act_scan`` and
    ``learner/train_dispatch`` only, a learner cell's runner collects no
    program spans at all: the reader returns None and does not raise."""
    read = harness.reader_of(metric).read
    assert read(_ctx({"actor/act_scan": [0.001],
                      "learner/train_dispatch": [0.002]})) is None
    assert read(_ctx({READERS[metric]: []})) is None
    assert read(_ctx(None)) is None


def test_the_three_metrics_are_declared_for_the_fused_loop_alone():
    assert list(bm_structure.ANAKIN_SPAN_READERS) == list(READERS)
    bm_structure.anakin_span_readers(BENCH)


def test_readers_find_the_loops_spans_in_the_rehearsal(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    rc = run.main(["--workload", CELL, "--seed", str(2**31 + 5), "--seconds",
                   "1.5", "--trace", "1", "--rehearse", "1"])
    out = capsys.readouterr().out
    assert rc == 0
    (found,) = [line for line in out.splitlines()
                if line.startswith("readers that found something:")]
    for name in READERS:
        assert f"'{name}'" in found
    assert json.loads(out.strip().splitlines()[-1])["metrics"] == {}
    # what the runner read them from: the loop's tree, as the program wrote it
    with open(os.path.join(tmp_path, CELL, "spans_player0.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    by_id = {r["id"]: r for r in rows}
    for span in READERS.values():
        mine = [r for r in rows if r["name"] == span]
        assert mine
        assert all(by_id[r["parent"]]["name"] == "anakin/iteration"
                   and r["iter"] == by_id[r["parent"]]["iter"] for r in mine)
    # a log boundary holds the device sync: the stall is at least the sync
    logs = [r for r in rows if r["name"] == "anakin/log"]
    syncs = {r["parent"]: r for r in rows
             if r["name"] == "learner/device_sync"}
    assert all(log["dur"] >= syncs[log["id"]]["dur"]
               for log in logs if log["id"] in syncs)
