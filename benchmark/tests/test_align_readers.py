"""The overlap-alignment readers of the program's ``align`` spans and
counters: numbers from a registry that has them, nothing (and no
exception) from the registry of a program that predates them."""

import pytest

from benchmark import harness

NEW = ("align.host_prep.s_per_mb", "align.collect.s_per_mb",
       "align.native.s_per_mb", "align.walk.s_per_mb",
       "align.native_base_share")


def _readers():
    cell = harness.find_cell(harness.load_spec(), "ecoli_ont.paf")
    return {n: harness.load_reader(cell["readers"][n]) for n in NEW}


def test_a_program_without_the_spans_reads_nothing():
    # What a job registry held before the spans: the logger's phases.
    reg = {"phase_seconds_initialize_aligned_overlaps": 14.0,
           "ovl_device_jobs": 300, "ovl_native_jobs": 90}
    ctx = {"registry": reg, "draft_mb": 0.2, "trace": None}
    assert {n: r(ctx) for n, r in _readers().items()} == \
        dict.fromkeys(NEW)


def test_the_spans_and_counters_are_read_per_mb():
    reg = {"align_seconds_revcomp": 0.1, "align_seconds_device_prep": 1.0,
           "align_seconds_device_dispatch": 0.5,
           "align_seconds_device_collect": 2.0,
           "align_seconds_native_redo": 8.0, "align_seconds_bp_walk": 0.4,
           "align_bases_total": 1000, "align_native_bases": 250}
    got = {n: r({"registry": reg, "draft_mb": 0.2, "trace": None})
           for n, r in _readers().items()}
    assert got == pytest.approx({
        "align.host_prep.s_per_mb": 8.0, "align.collect.s_per_mb": 10.0,
        "align.native.s_per_mb": 40.0, "align.walk.s_per_mb": 2.0,
        "align.native_base_share": 0.25})


def test_sam_input_reads_the_walk_and_the_reverse_complements():
    # No overlap needs aligning: only revcomp and bp_walk ran.
    reg = {"align_seconds_revcomp": 0.3, "align_seconds_bp_walk": 4.0,
           "align_bases_total": 0, "align_walk_overlaps": 900}
    got = {n: r({"registry": reg, "draft_mb": 0.5, "trace": None})
           for n, r in _readers().items()}
    assert got["align.host_prep.s_per_mb"] == pytest.approx(0.6)
    assert got["align.walk.s_per_mb"] == pytest.approx(8.0)
    assert got["align.native_base_share"] is None
