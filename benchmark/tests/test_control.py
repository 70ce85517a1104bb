"""The control of each cell's check comes out not correct: its answer
(the draft, every window left unpolished) fails the cell's limits on
three seeds, at a size a test can hold."""

import pytest

from benchmark import control, harness
from benchmark.tests.helpers import small_root


@pytest.mark.parametrize("cell", ["ecoli_ont.paf", "yeast_clr.sam",
                                  "ecoli_ont_dp4.paf"])
def test_the_control_reads_not_correct(tmp_path, monkeypatch, cell):
    root = small_root(tmp_path, cell, 30_000)
    monkeypatch.setattr(harness, "WORK_DIR", root + "/.bench_work")
    c = harness.find_cell(harness.load_spec(root), cell, root)
    for seed in (1, 2**31 + 1, 7_654_321):
        verdict = harness.check(c, control.control_jobs(c, seed, 2))
        assert verdict["correct"] is False
        assert verdict["numbers"]["missing"]["value"] == 0
        assert verdict["numbers"]["err_pct"]["value"] > \
            verdict["numbers"]["err_pct"]["limit"]
