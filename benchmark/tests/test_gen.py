"""The generator: composed CIGARs, determinism, and a job the program
polishes."""

import json
import os
import re
import time

import numpy as np
import pytest

from benchmark import harness, reference
from benchmark.gen import ava, genome
from benchmark.tests.helpers import KF_FIXTURE, small_config, small_root

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
    assert list(job["truths"]) == names
    r = reference.compare_job(res["contigs"], job["truths"])
    assert r["missing"] == []
    with open(job["paths"][2], "rb") as f:
        draft = reference.compare_job(reference.parse_fasta(f.read()),
                                      job["truths"])
    assert r["errors"] * 2 < draft["errors"]


# ------------------------------------------------ the kC cells, pinned

with open(os.path.join(harness.BENCH_DIR, "tests", "data",
                       "kc_jobs.json")) as _f:
    # The sha256 of each file (and of the truths, in the contigs'
    # order) of timed job 0 drawn from key 2**31 + 7, at each cell's
    # own size and cut to 15 kb, and the CLI's arguments: what the kC
    # cells measure, which no change for other cells may move.
    KC_JOBS = json.load(_f)


@pytest.mark.parametrize("size", ["small", "full"])
@pytest.mark.parametrize("name", sorted(KC_JOBS))
def test_the_kc_cells_jobs_and_arguments_are_as_they_were(tmp_path, name,
                                                          size):
    import hashlib
    spec = harness.load_spec()
    cell = harness.find_cell(spec, name)
    cfg = cell["config"] if size == "full" else \
        small_config(cell["config"]["name"], 15_000)
    job = genome.make_job(cfg, cell["traffic"],
                          genome.job_rng(2**31 + 7, genome.TIMED_STREAM, 0),
                          str(tmp_path))
    got = {}
    for path in job["paths"]:
        with open(path, "rb") as f:
            got[os.path.basename(path)] = hashlib.sha256(f.read()).hexdigest()
    got["truths"] = hashlib.sha256(
        b"".join(job["truths"][c["name"]] for c in cfg["contigs"])
    ).hexdigest()
    got["draft_bp"], got["reads"] = job["draft_bp"], job["reads"]
    assert got == KC_JOBS[name][size]
    assert harness.cli_argv(cell, [os.path.basename(p) for p in job["paths"]]
                            ) == KC_JOBS[name]["argv"]


# ------------------------------------------------ all-vs-all (kF) jobs

AVA = {"overlaps": "ava_paf", "reads": "fastq"}


def _kf_config(**change) -> dict:
    with open(os.path.join(KF_FIXTURE, "configs", "kF_small.json")) as f:
        return {**json.load(f), **change}


def _paf(path):
    with open(path) as f:
        return [ln.split("\t") for ln in f.read().splitlines()]


def _arc(read, bp: int) -> np.ndarray:
    """The truth bases of the circle that ``read`` covers, as a mask."""
    mask = np.zeros(bp, bool)
    mask[(read.start + np.arange(read.span)) % bp] = True
    return mask


def test_ava_reports_each_pair_that_shares_enough_truth_once(tmp_path):
    cfg = _kf_config()
    job = genome.make_job(cfg, AVA, genome.job_rng(2**31 + 9, 2, 0),
                          str(tmp_path))
    assert job["paths"][0] == job["paths"][2]
    layout = {r.name: r for r in ava.read_layout(cfg)}
    assert job["reads"] == len(layout) > 20
    bp = cfg["contigs"][0]["bp"]
    recs = _paf(job["paths"][1])
    pairs = [frozenset((rec[0], rec[5])) for rec in recs]
    assert all(len(p) == 2 for p in pairs)          # never a self pair
    assert len(set(pairs)) == len(pairs)             # each pair once
    arcs = {n: _arc(r, bp) for n, r in layout.items()}
    assert set(pairs) == {
        frozenset((a, b)) for a in layout for b in layout
        if a < b and (arcs[a] & arcs[b]).sum() >= genome.MIN_OVERLAP}
    for rec in recs:
        q, t = layout[rec[0]], layout[rec[5]]
        assert q.name < t.name                       # as minimap2 -X
        assert rec[4] == ("-" if q.reverse != t.reverse else "+")
        assert int(rec[9]) == (arcs[q.name] & arcs[t.name]).sum()


def test_ava_read_names_carry_no_position():
    layout = ava.read_layout(_kf_config())
    order = [int(r.name[1:]) for r in sorted(layout, key=lambda r: r.start)]
    assert sorted(order) == list(range(len(layout)))
    assert order != sorted(order) and order != sorted(order)[::-1]


def test_ava_coordinates_span_the_shared_truth_in_both_reads(tmp_path):
    # Without errors each range spells the shared truth exactly, in the
    # orientation the record's strand says; with them, the two ranges
    # align as two 10% reads do, and on the other strand they do not.
    exact = _kf_config(read_error={"sub": 0, "ins": 0, "del": 0})
    for cfg in (exact, _kf_config()):
        job = genome.make_job(cfg, AVA, genome.job_rng(2**31 + 9, 2, 0),
                              str(tmp_path / ("exact" if cfg is exact
                                              else "noisy")))
        reads = _fastq(job["paths"][0])
        for rec in _paf(job["paths"][1])[:40]:
            q0, q1, t0, t1 = (int(rec[k]) for k in (2, 3, 7, 8))
            assert 0 <= q0 < q1 <= int(rec[1]) == len(reads[rec[0]])
            assert 0 <= t0 < t1 <= int(rec[6]) == len(reads[rec[5]])
            q = np.frombuffer(reads[rec[0]][q0:q1], np.uint8)
            if rec[4] == "-":
                q = RC[q][::-1]
            t = reads[rec[5]][t0:t1]
            if cfg is exact:
                assert q.tobytes() == t and len(t) == int(rec[9])
            elif len(t) < 1500:
                ed, _ = reference.prefix_distance(q.tobytes(), t)
                assert ed < 0.3 * len(t)
                ed, _ = reference.prefix_distance(RC[q][::-1].tobytes(), t)
                assert ed > 0.4 * len(t)


def test_ava_truths_are_the_reads_without_errors(tmp_path):
    exact = _kf_config(read_error={"sub": 0, "ins": 0, "del": 0})
    job = genome.make_job(exact, AVA, genome.job_rng(5, 2, 0), str(tmp_path))
    reads = _fastq(job["paths"][0])
    assert {**job["truths"], **job["optional"]} == \
        {n + "r": s for n, s in reads.items()}
    assert job["draft_bp"] == sum(len(s) for s in reads.values())


def test_reads_laid_on_a_circle_see_level_coverage():
    # The proposed kF cell's reads: ecoli_ont's profiles over a 45 kb
    # stretch at 30x. A linear stretch would fall to about half its
    # coverage at each end.
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           "ecoli_ont.json")) as f:
        ont = json.load(f)
    bp, cov = 45_000, 30
    cfg = _kf_config(contigs=[{"name": "s", "bp": bp}], coverage=cov,
                     read_length=ont["read_length"],
                     reverse_share=ont["reverse_share"])
    depth = sum(_arc(r, bp).astype(int) for r in ava.read_layout(cfg))
    assert abs(depth.mean() - cov) < 0.05 * cov
    assert 0.7 * cov <= depth.min() and depth.max() <= 1.4 * cov
    assert depth[0] >= 0.7 * cov and depth[-1] >= 0.7 * cov


def test_ava_reads_due_back_have_three_overlaps_in_every_window(tmp_path):
    cfg = _kf_config()
    job = genome.make_job(cfg, AVA, genome.job_rng(2**31 + 9, 2, 0),
                          str(tmp_path))
    reads = _fastq(job["paths"][0])
    recs = _paf(job["paths"][1])
    w = cfg["racon"]["window_length"]
    due = set()
    for name, seq in reads.items():
        counts = [sum(1 for rec in recs if rec[5] == name
                      and int(rec[7]) < k + w and int(rec[8]) > k)
                  for k in range(0, len(seq), w)]
        if min(counts) >= 3:
            due.add(name + "r")
    assert set(job["truths"]) == due
    assert set(job["optional"]) == {n + "r" for n in reads} - due
    assert due and job["optional"]
