#!/usr/bin/env python3
"""The control of a cell's correctness check: it has to come out false.

racon-tpu runs no model and states no numeric precision, so the control
breaks one guarantee the configuration states: every window with at
least three layers is replaced by the consensus of its layers. The
control skips the consensus, so every window keeps its target's bases
and each job's answer is its targets as they are: the draft in contig
polishing, the reads, under the names the program prints for the
corrected reads, in read self-correction (``-f``). The timed jobs of a
seed are generated as a run generates them and compared by the cell's
own check.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 --jobs 3

prints one JSON line per seed with the numbers and whether the check
calls them correct. The benchmark's runs never run it.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, reference  # noqa: E402
from benchmark.gen import ava  # noqa: E402


def parse_targets(data: bytes):
    """``{name: sequence}`` of a FASTA file or of a FASTQ file of
    four-line records (a target file as the generator writes it)."""
    if not data.startswith(b"@"):
        return reference.parse_fasta(data)
    lines = data.split(b"\n")
    return {lines[i][1:].split()[0].decode(): lines[i + 1]
            for i in range(0, len(lines) - 3, 4)}


def control_jobs(cell, seed: int, n_jobs: int):
    """The first ``n_jobs`` timed jobs of ``seed``, each answered by
    its targets as they are."""
    suffix = ava.CORRECTED \
        if cell["config"]["racon"].get("fragment_correction") else ""
    jobs = []
    for i in range(n_jobs):
        job = harness.timed_job(cell, seed, i)
        with open(job["paths"][2], "rb") as f:
            targets = parse_targets(f.read())
        job["contigs"] = {n + suffix: s for n, s in targets.items()}
        jobs.append(job)
    return jobs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--jobs", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(), args.workload)
    for seed in args.seeds:
        verdict = harness.check(cell, control_jobs(cell, seed, args.jobs))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          **verdict}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
