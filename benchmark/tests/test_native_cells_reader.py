"""The reader of the native redo's time per band cell: a number from a
registry with the cell counter, nothing (and no exception) from a
program without it or from a job that redid nothing."""

import pytest

from benchmark import harness

NAME = "align.native.ns_per_cell"


@pytest.mark.parametrize("cell", ["ecoli_ont.paf", "ecoli_ont_dp4.paf"])
def test_the_paf_cells_read_it(cell):
    c = harness.find_cell(harness.load_spec(), cell)
    assert NAME in c["readers"]


def _read(reg):
    cell = harness.find_cell(harness.load_spec(), "ecoli_ont.paf")
    return harness.load_reader(cell["readers"][NAME])(
        {"registry": reg, "draft_mb": 0.2, "trace": None})


def test_seconds_over_cells_in_ns():
    reg = {"align_seconds_native_redo": 0.25, "align_native_cells": 10**8,
           "align_native_vector_cells": 9 * 10**7, "align_bases_total": 1}
    assert _read(reg) == pytest.approx(2.5)


def test_nothing_without_the_counter_or_without_a_redo():
    # A program that predates the counter: the span's seconds alone.
    assert _read({"align_seconds_native_redo": 2.0,
                  "align_bases_total": 1000}) is None
    # Every overlap kept on the device: no span and no cells.
    assert _read({"align_bases_total": 1000, "align_native_cells": 0}) \
        is None
