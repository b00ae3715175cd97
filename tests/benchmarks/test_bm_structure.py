"""A later PR appends: a configuration, a cell (its name at the end of the
``workloads`` lists of the metrics it reports) and a per-layer entry. Every
structural assertion of ``tests/benchmarks/`` (``bm_structure.ALL``) holds on
such a copy of ``BENCHMARK.json``, and still binds what was accepted: an entry
taken out, or one put between the accepted ones, fails. PRs 24 and 27 had
pinned the last place of eight lists, so that no cell and no metric could
come after them (CHANGES.md, PR 31)."""

import pytest

import bm_structure
from benchmarks import harness
from bm_structure import MADE_UP_CELL, MADE_UP_METRIC

BENCH = harness.load_benchmark()


@pytest.fixture
def appended(tmp_path):
    return bm_structure.appended_copy(BENCH, str(tmp_path))


@pytest.mark.parametrize("holds", bm_structure.ALL,
                         ids=lambda f: f.__name__)
def test_structure_holds_with_a_cell_and_a_metric_appended(appended, holds):
    bench, root = appended
    holds(bench, root)


def test_the_copy_is_one_that_a_pin_on_a_lists_end_would_refuse(appended):
    """What PRs 24 and 27 asserted is false of the copy, so an assertion of
    that kind, come back, fails the test above."""
    bench, root = appended
    names = bm_structure.per_layer_names(bench)
    assert names[-1] == MADE_UP_METRIC
    assert names[:len(BENCH["per_layer"])] == \
        bm_structure.per_layer_names(BENCH)
    for name in bm_structure.MOONLIGHT_JOINED:
        cells = bm_structure.metric(bench, name)["workloads"]
        assert cells[-1] == MADE_UP_CELL != bm_structure.MOONLIGHT
        assert cells[:-1] == bm_structure.metric(BENCH, name)["workloads"]
    # the made-up cell reports what a learner cell has to
    assert len(harness.cell_metrics(bench, MADE_UP_CELL, "per_layer")) == 7
    assert [m["name"] for m in harness.cell_metrics(
        bench, MADE_UP_CELL, "end_to_end")] == [
            "seq_updates_per_s", "hbm_peak_gib", "setup_s"]


def _without_cell(bench):
    cells = bm_structure.metric(bench, "train_step_ms")["workloads"]
    cells.remove(bm_structure.MOONLIGHT)


def _cell_before_its_elders(bench):
    cells = bm_structure.metric(bench, "k_gather_roofline")["workloads"]
    cells.remove(bm_structure.MOONLIGHT)
    cells.insert(0, bm_structure.MOONLIGHT)


def _entry_between_the_span_readers(bench):
    at = bm_structure.per_layer_names(bench).index(
        "anakin.accounting_host_ms")
    bench["per_layer"].insert(at, bench["per_layer"].pop())


def _entry_before_what_was_accepted(bench):
    bench["per_layer"].insert(0, bench["per_layer"].pop(
        bm_structure.per_layer_names(bench).index("core_self_share")))


def _entry_without_its_cell(bench):
    bm_structure.metric(bench, "k_experts_roofline")["workloads"] = [
        "r2d2-paper.learner"]


def _metric_where_what_it_moves_is_not(bench):
    bm_structure.metric(bench, "mfu_bf16")["workloads"].append(
        bm_structure.ANAKIN)


@pytest.mark.parametrize("edit, holds", [
    (_without_cell, bm_structure.moonlight_cell_joins),
    (_cell_before_its_elders, bm_structure.moonlight_cell_joins),
    (_entry_between_the_span_readers, bm_structure.anakin_span_readers),
    (_entry_before_what_was_accepted, bm_structure.moonlight_cell_joins),
    (_entry_without_its_cell, bm_structure.moonlight_cell_joins),
    (_metric_where_what_it_moves_is_not, bm_structure.metrics_declared_once),
], ids=lambda f: f.__name__.lstrip("_"))
def test_membership_and_order_still_bind_what_was_accepted(appended, edit,
                                                           holds):
    bench, root = appended
    holds(bench, root)
    edit(bench)
    with pytest.raises(AssertionError):
        holds(bench, root)
