"""The harness is driven by data: a configuration and a per-layer metric
added as new files (and named in BENCHMARK.json) are found without an
edit to any file of the harness."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

METRIC = '''"""A metric added by a later change."""


def read(ctx):
    return ctx["registry"].get("poa_windows_total")
'''


def test_a_new_configuration_and_metric_are_listed(tmp_path):
    root = str(tmp_path)
    shutil.copytree(harness.BENCH_DIR, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "ecoli_ont.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "ecoli_ont_x"
    with open(os.path.join(bench, "configs", "ecoli_ont_x.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "limits", "ecoli_ont_x.sam.json"), "w") as f:
        json.dump({"err_pct": 1.0, "block_pct": 1.0}, f)
    with open(os.path.join(bench, "metrics", "consensus.windows.py"),
              "w") as f:
        f.write(METRIC)
    spec["configs"].append({**spec["configs"][0], "name": "ecoli_ont_x",
                            "file": "benchmark/configs/ecoli_ont_x.json"})
    spec["workloads"].append({"name": "ecoli_ont_x.sam",
                              "config": "ecoli_ont_x", "traffic": "sam",
                              "chips": 1, "why": "a cell added by data"})
    spec["per_layer"].append({"name": "consensus.windows", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "consensus",
                              "moves": "polished_bases_per_s",
                              "workloads": ["ecoli_ont_x.sam"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)

    cells = {c["workload"]: c for c in harness.list_cells(root)}
    assert cells["ecoli_ont_x.sam"]["config"] == "ecoli_ont_x"
    assert cells["ecoli_ont_x.sam"]["per_layer"] == ["consensus.windows"]
    assert "consensus.windows" not in cells["ecoli_ont.paf"]["per_layer"]
    cell = harness.find_cell(spec, "ecoli_ont_x.sam", root)
    reader = harness.load_reader(cell["readers"]["consensus.windows"])
    assert reader({"registry": {"poa_windows_total": 7}}) == 7
    assert cell["traffic"]["overlaps"] == "sam"


def test_every_named_file_exists_and_run_py_lists_the_cells():
    out = subprocess.run([sys.executable, "benchmark/run.py", "--list"],
                         cwd=harness.ROOT, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    cells = [json.loads(ln) for ln in out.stdout.splitlines()]
    spec = harness.load_spec()
    assert [c["workload"] for c in cells] == \
        [w["name"] for w in spec["workloads"]]
    by_name = {c["workload"]: c for c in cells}
    assert len(by_name) == 3
    dp4 = by_name["ecoli_ont_dp4.paf"]
    assert dp4["chips"] == 4
    assert "device.chip_busy_imbalance" in dp4["per_layer"]
    assert set(by_name["ecoli_ont.paf"]["per_layer"]) < set(dp4["per_layer"])


def test_a_cell_gets_its_chips_on_the_command_line():
    spec = harness.load_spec()
    paths = ["reads.fastq", "overlaps.paf", "draft.fasta"]
    argv = {w["name"]: harness.cli_argv(harness.find_cell(spec, w["name"]),
                                        paths)
            for w in spec["workloads"]}
    # One chip: the CLI's single-device path, the argv as it always was.
    one_chip = ["--backend", "jax", "-w", "500", "-q", "10.0", "-e", "0.3",
                "-m", "5", "-x", "-4", "-g", "-8", *paths]
    assert argv["ecoli_ont.paf"] == one_chip
    assert argv["yeast_clr.sam"] == one_chip
    assert argv["ecoli_ont_dp4.paf"] == \
        ["--backend", "jax", "--dp", "4", *one_chip[2:]]


def test_without_an_accelerator_the_run_fails_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                          "ecoli_ont.paf", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], cwd=harness.ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copytree(harness.BENCH_DIR, str(tmp_path / "benchmark"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), str(tmp_path))
    out = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                          "ecoli_ont.paf", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], cwd=str(tmp_path),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
