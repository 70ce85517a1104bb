"""Small copies of the benchmark's cells for tests on the CPU."""

import json
import os
import shutil

from benchmark import harness


def small_config(name: str, contig_bp: int, coverage: int = None) -> dict:
    """The configuration ``name`` with each contig cut to ``contig_bp``
    and its flanks to 5 kb."""
    with open(os.path.join(harness.BENCH_DIR, "configs", name + ".json")) as f:
        cfg = json.load(f)
    for c in cfg["contigs"]:
        c["bp"] = contig_bp
    cfg["ends"]["flank_bp"] = 5_000
    cfg["read_length"]["max"] = min(cfg["read_length"]["max"], 8_000)
    if coverage:
        cfg["coverage"] = coverage
    return cfg


def small_root(tmp_path, cell: str, contig_bp: int) -> str:
    """A checkout-like root holding ``BENCHMARK.json`` and the benchmark
    directory, with ``cell``'s configuration cut to ``contig_bp``."""
    root = str(tmp_path / "root")
    shutil.copytree(harness.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    name = cell.split(".")[0]
    with open(os.path.join(root, "benchmark", "configs", name + ".json"),
              "w") as f:
        json.dump(small_config(name, contig_bp), f)
    return root


# A read self-correction (kF) cell as the files a later change adds:
# a configuration, an all-vs-all mix and limits, under the benchmark's
# own directories, and entries in BENCHMARK.json.
KF_FIXTURE = os.path.join(harness.BENCH_DIR, "tests", "data", "kF_small")
KF_CELL = "kF_small.ava_small"


def kf_root(tmp_path) -> str:
    """A checkout-like root holding ``BENCHMARK.json`` and the benchmark
    directory, with the kF fixture's files added (none replaces a file
    that is there) and its configuration and cell appended to the
    spec."""
    root = str(tmp_path / "root")
    bench = os.path.join(root, "benchmark")
    shutil.copytree(harness.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for sub, _, files in os.walk(KF_FIXTURE):
        for name in files:
            rel = os.path.relpath(os.path.join(sub, name), KF_FIXTURE)
            dst = os.path.join(bench, rel)
            assert not os.path.exists(dst), rel
            shutil.copy(os.path.join(sub, name), dst)
    spec = harness.load_spec()
    config, traffic = KF_CELL.split(".")
    spec["configs"].append({
        "name": config, "source": "racon test/racon_test.cpp kF tests",
        "file": f"benchmark/configs/{config}.json", "reduced": [],
        "why": "read self-correction over all-vs-all PAF"})
    spec["workloads"].append({
        "name": KF_CELL, "config": config, "traffic": traffic, "chips": 1,
        "why": "8 kb at 10x, 2-4 kb reads, every read a target"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root
