"""Read self-correction (kF) through the harness, as files added alone.

The fixture under ``benchmark/tests/data/kF_small/`` is a kF
configuration, an all-vs-all mix and its limits: the files a later
change adds for a kF cell. Added to a copy of the benchmark, with an
entry each in ``BENCHMARK.json``, they run through ``harness.run`` on
the CPU (the program's jax backend, its kernels' XLA twins) with no
edit to any file the benchmark has: a sound run reads ``correct`` true
with every read due back, and the control and the faults that leave the
reads uncorrected or cut short read false.
"""

import filecmp
import os

import pytest

from benchmark import control, harness
from benchmark.tests.helpers import KF_CELL, KF_FIXTURE, kf_root
from benchmark.tests.test_faults import _dropped, _run, _unchanged


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return kf_root(tmp_path_factory.mktemp("kf"))


def test_the_fixture_only_adds_files(root):
    added = {os.path.relpath(os.path.join(d, n), KF_FIXTURE)
             for d, _, files in os.walk(KF_FIXTURE) for n in files}
    bench = os.path.join(root, "benchmark")
    seen = set()
    for d, _, files in os.walk(bench):
        for n in files:
            rel = os.path.relpath(os.path.join(d, n), bench)
            seen.add(rel)
            if rel not in added:
                assert filecmp.cmp(os.path.join(d, n),
                                   os.path.join(harness.BENCH_DIR, rel),
                                   shallow=False), rel
    assert added <= seen
    spec = harness.load_spec(root)
    assert spec["workloads"][:-1] == harness.load_spec()["workloads"]
    assert spec["end_to_end"] == harness.load_spec()["end_to_end"]
    assert spec["per_layer"] == harness.load_spec()["per_layer"]


def test_the_fixture_is_corrected_with_f(root):
    cell = harness.find_cell(harness.load_spec(root), KF_CELL, root)
    paths = ["reads.fastq", "overlaps.paf", "reads.fastq"]
    assert harness.cli_argv(cell, paths) == [
        "--backend", "jax", "-w", "500", "-q", "10.0", "-e", "0.3", "-m",
        "5", "-x", "-4", "-g", "-8", "-f", *paths]
    cells = {c["workload"]: c for c in harness.list_cells(root)}
    assert cells[KF_CELL]["traffic"] == "ava_small"


def test_a_sound_run_reads_correct_with_every_due_read_back(root,
                                                            monkeypatch):
    res = _run(KF_CELL, root, monkeypatch)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["checks"]["missing"]["value"] == 0
    assert set(res["checks"]) == {"missing", "err_pct"}
    # Reads with too few overlaps are counted, not judged.
    assert res["optional"]["reads"] > 0
    assert 0 <= res["optional"]["back"] <= res["optional"]["reads"]
    assert list(res)[-1] == "checks"
    job = os.path.join(root, ".bench_work", KF_CELL, "job0")
    with open(os.path.join(job, "reads.fastq"), "rb") as f:
        reads = control.parse_targets(f.read())
    with open(os.path.join(job, "polished.fasta")) as f:
        names = [ln[1:].split()[0] for ln in f if ln.startswith(">")]
    assert names and all(n[:-1] in reads and n.endswith("r")
                         for n in names)


def test_the_control_reads_not_correct(root, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", root + "/.bench_work")
    cell = harness.find_cell(harness.load_spec(root), KF_CELL, root)
    for seed in (1, 2**31 + 1, 7_654_321):
        verdict = harness.check(cell, control.control_jobs(cell, seed, 2))
        assert verdict["correct"] is False
        assert verdict["numbers"]["missing"]["value"] == 0
        assert verdict["optional"]["back"] == verdict["optional"]["reads"]
        assert verdict["numbers"]["err_pct"]["value"] > \
            verdict["numbers"]["err_pct"]["limit"]


@pytest.mark.parametrize("fault", [_unchanged, _dropped],
                         ids=["unchanged", "dropped"])
def test_a_broken_timed_path_reads_not_correct(root, monkeypatch, fault):
    from racon_tpu.ops.poa import PoaEngine
    monkeypatch.setattr(PoaEngine, "consensus_windows",
                        fault(PoaEngine.consensus_windows))
    res = _run(KF_CELL, root, monkeypatch)
    assert res["attempted"] >= 1
    assert res["correct"] is False, res["checks"]
