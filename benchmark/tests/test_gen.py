"""The generator: composed CIGARs, determinism, and a job the program
polishes."""

import json
import os
import re
import time

import numpy as np
import pytest

from benchmark import harness, reference
from benchmark.gen import genome
from benchmark.tests.helpers import small_config, small_root

RC = genome.RC


def replay(cigar: str, read_fwd: bytes, draft: bytes, pos0: int) -> bytes:
    """Spell the read from the draft as ``cigar`` says: each ``M``
    column takes the draft's base, each ``I`` and ``S`` column the
    read's own. Where the CIGAR is the read's alignment, the result
    has the read's length and differs from ``read_fwd`` only at the
    mismatched ``M`` columns; one shifted by a base differs at about
    three quarters of them."""
    out, q, t = [], 0, pos0
    for n, op in re.findall(r"(\d+)([MIDS])", cigar):
        n = int(n)
        if op == "M":
            out.append(draft[t:t + n])
        elif op in "IS":
            out.append(read_fwd[q:q + n])
        q += n if op in "MIS" else 0
        t += n if op in "MD" else 0
    if t > len(draft):
        raise ValueError("CIGAR runs past the draft")
    return b"".join(out)


def _fastq(path):
    with open(path, "rb") as f:
        lines = f.read().split(b"\n")
    return {lines[i][1:].decode(): lines[i + 1]
            for i in range(0, len(lines) - 3, 4)}


def _sam_job(tmp_path, seed=5):
    cfg = small_config("yeast_clr", 20_000)
    job = genome.make_job(cfg, {"overlaps": "sam", "reads": "fastq"},
                          genome.job_rng(seed, genome.TIMED_STREAM, 0),
                          str(tmp_path / f"job{seed}"))
    return cfg, job


def test_replaying_each_cigar_against_the_draft_spells_the_read(tmp_path):
    cfg, job = _sam_job(tmp_path)
    reads = _fastq(job["paths"][0])
    with open(job["paths"][2], "rb") as f:
        drafts = reference.parse_fasta(f.read())
    rates = cfg["read_error"]
    n = 0
    with open(job["paths"][1]) as f:
        for line in f:
            if line.startswith("@"):
                continue
            q, flag, t, pos, _, cigar = line.split("\t")[:6]
            seq = np.frombuffer(reads[q], np.uint8)
            fwd = (RC[seq][::-1] if int(flag) & 16 else seq).tobytes()
            spelled = replay(cigar, fwd, drafts[t], int(pos) - 1)
            assert len(spelled) == len(fwd)
            n_m = sum(int(k) for k, op in re.findall(r"(\d+)([MIDS])", cigar)
                      if op == "M")
            diff = sum(a != b for a, b in zip(spelled, fwd))
            # Only substitutions and facing insertions mismatch.
            assert diff <= n_m * (rates["sub"] + 0.02) + 5
            # A CIGAR one base off mismatches most of its columns.
            shift = 1 if int(pos) == 1 else -1
            shifted = replay(cigar, fwd, drafts[t], int(pos) - 1 + shift)
            assert sum(a != b for a, b in zip(shifted, fwd)) > n_m / 2
            n += 1
    assert n == job["reads"] > 50


def test_the_same_seed_gives_byte_identical_files(tmp_path):
    for traffic in ({"overlaps": "sam", "reads": "fastq"},
                    {"overlaps": "paf", "reads": "fasta"}):
        cfg = small_config("ecoli_ont", 15_000)
        a = genome.make_job(cfg, traffic, genome.job_rng(2**31 + 7, 2, 0),
                            str(tmp_path / "a"))
        b = genome.make_job(cfg, traffic, genome.job_rng(2**31 + 7, 2, 0),
                            str(tmp_path / "b"))
        c = genome.make_job(cfg, traffic, genome.job_rng(2**31 + 8, 2, 0),
                            str(tmp_path / "c"))
        for pa, pb, pc in zip(a["paths"], b["paths"], c["paths"]):
            with open(pa, "rb") as fa, open(pb, "rb") as fb, \
                    open(pc, "rb") as fc:
                da, db, dc = fa.read(), fb.read(), fc.read()
            assert da == db
            assert da != dc


def test_a_pooled_mix_gives_every_seed_the_same_jobs_in_its_own_order(
        tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_DIR", str(tmp_path))
    cell = {"name": "pooled", "config": small_config("ecoli_ont", 15_000),
            "traffic": {"overlaps": "paf", "reads": "fastq", "pool_jobs": 4}}

    def drafts(make, seed, n):
        out = []
        for i in range(n):
            with open(make(cell, seed, i)["paths"][2], "rb") as f:
                out.append(f.read())
        return out

    seeds = (2**31 + 11, 2**31 + 12, 2**31 + 13)
    orders = [drafts(harness.timed_job, s, 5) for s in seeds]
    assert drafts(harness.timed_job, seeds[0], 5) == orders[0]
    for o in orders:
        assert len(set(o[:4])) == 4
        assert set(o[:4]) == set(orders[0][:4])
        assert o[4] == o[0]
    assert len({tuple(o[:4]) for o in orders}) > 1
    # Without a pool each seed draws jobs of its own.
    del cell["traffic"]["pool_jobs"]
    assert (set(drafts(harness.timed_job, seeds[0], 2))
            .isdisjoint(drafts(harness.timed_job, seeds[1], 2)))


def test_a_pooled_run_warms_up_on_the_jobs_its_window_polishes(
        tmp_path, monkeypatch):
    root = small_root(tmp_path, "ecoli_ont.paf", 15_000)
    monkeypatch.delenv("RACON_TPU_CACHE", raising=False)
    monkeypatch.setattr(harness, "WORK_DIR", root + "/.bench_work")
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: {"platform": "cpu", "kind": "cpu",
                                   "count": n})
    seen = []

    def polish(cell, job, extra=()):
        t0 = time.perf_counter()
        with open(job["paths"][2], "rb") as f:
            draft = f.read()
        seen.append((os.path.basename(job["dir"]), draft))
        time.sleep(0.3)
        return {"wall_s": time.perf_counter() - t0, "registry": {},
                "contigs": reference.parse_fasta(draft), "failed": None}

    monkeypatch.setattr(harness, "polish", polish)
    lines = []
    assert harness.run("ecoli_ont.paf", 2**31 + 5, 1.0, False, root=root,
                       emit=lines.append) == 0
    n_win = json.loads(lines[0])["window"]["jobs"]
    warm, window = seen[:-n_win], seen[-n_win:]
    assert len(warm) >= n_win >= 2
    assert [d for _, d in window] == [d for _, d in warm[:n_win]]
    assert [n for n, _ in window] == [f"job{i}" for i in range(n_win)]


def _compiling_first(n_building: int, seen: list):
    """A stand-in for ``harness.polish``: the first ``n_building`` jobs
    build programs and take 10 s by their own clock, the rest 0.5 s;
    each answers with its draft."""
    import jax.monitoring

    def polish(cell, job, extra=()):
        building = len(seen) < n_building
        seen.append(os.path.basename(job["dir"]))
        if building:
            jax.monitoring.record_event_duration_secs(
                "/jax/core/compile/backend_compile_duration", 1.0)
        time.sleep(0.3)
        with open(job["paths"][2], "rb") as f:
            contigs = reference.parse_fasta(f.read())
        return {"wall_s": 10.0 if building else 0.5, "registry": {},
                "contigs": contigs, "failed": None}
    return polish


def _run_compiling_first(tmp_path, monkeypatch, trace):
    root = small_root(tmp_path, "ecoli_ont.paf", 15_000)
    monkeypatch.setattr(harness, "WORK_DIR", root + "/.bench_work")
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: {"platform": "cpu", "kind": "cpu",
                                   "count": n})
    seen = []
    monkeypatch.setattr(harness, "polish", _compiling_first(3, seen))
    lines = []
    assert harness.run("ecoli_ont.paf", 2**31 + 7, 1.0, trace, root=root,
                       emit=lines.append) == 0
    assert json.loads(lines[-1])["correct"] is False   # drafts
    return json.loads(lines[0]), seen


def test_a_pooled_warm_up_takes_its_pace_from_jobs_that_built_nothing(
        tmp_path, monkeypatch):
    # Three warm-up jobs compile; a pace taken from them (10 s) would
    # warm up two jobs for a 1 s window, and the window's later jobs
    # would compile inside it. At the clean jobs' 0.5 s a window holds
    # two, so four are warmed up.
    first, _ = _run_compiling_first(tmp_path, monkeypatch, False)
    assert first["setup"]["warmup_s"] == [10.0, 10.0, 10.0, 0.5]
    assert first["window"]["jobs"] >= 2


def test_a_traced_pooled_run_warms_up_on_its_one_job(tmp_path,
                                                     monkeypatch):
    # A traced window is the first timed job alone; warm-up polishes
    # that job once, however many programs it built.
    first, seen = _run_compiling_first(tmp_path, monkeypatch, True)
    assert first["setup"]["warmup_s"] == [10.0]
    assert first["window"]["jobs"] == 1
    assert seen == ["job0", "job0"]


def test_a_small_yeast_clr_sam_job_polishes_through_the_cli(tmp_path,
                                                            monkeypatch):
    monkeypatch.setattr(harness, "BACKEND", "native")
    cfg, job = _sam_job(tmp_path, seed=11)
    names = [c["name"] for c in cfg["contigs"]]
    res = harness.polish({"config": cfg, "chips": 1},
                         {"dir": os.path.dirname(job["paths"][0]),
                          "paths": job["paths"]})
    assert res["failed"] is None
    r = reference.compare_job(res["contigs"], dict(zip(names, job["truths"])))
    assert r["missing"] == []
    with open(job["paths"][2], "rb") as f:
        draft = reference.compare_job(reference.parse_fasta(f.read()),
                                      dict(zip(names, job["truths"])))
    assert r["errors"] * 2 < draft["errors"]
