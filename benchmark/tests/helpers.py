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
