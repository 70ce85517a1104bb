"""The yardstick: Myers' edit distance against a plain dynamic program,
and the job comparison on known errors."""

import numpy as np
import pytest

from benchmark import reference


def _dp_prefix(p: bytes, t: bytes) -> int:
    prev = np.arange(len(t) + 1, dtype=np.int64)    # the start is fixed
    for i in range(1, len(p) + 1):
        cur = np.empty_like(prev)
        cur[0] = i
        for j in range(1, len(t) + 1):
            cur[j] = min(prev[j] + 1, cur[j - 1] + 1,
                         prev[j - 1] + (p[i - 1] != t[j - 1]))
        prev = cur
    return int(prev.min())                          # free end in t


def test_myers_matches_the_plain_dynamic_program():
    rng = np.random.default_rng(3)
    for _ in range(60):
        p = bytes(rng.choice(list(b"ACGT"), rng.integers(1, 70)))
        t = bytes(rng.choice(list(b"ACGT"), rng.integers(0, 90)))
        assert reference.prefix_distance(p, t)[0] == _dp_prefix(p, t)


def _truth(seed: int, n: int = 30_000) -> bytes:
    rng = np.random.default_rng(seed)
    return np.frombuffer(b"ACGT", np.uint8)[rng.integers(0, 4, n)].tobytes()


def test_compare_counts_planted_errors_and_missing_contigs():
    truth = _truth(4)
    pol = bytearray(truth)
    for pos in range(1_100, 30_000, 1_000):          # 29 substitutions
        pol[pos] = ord("A") if pol[pos] != ord("A") else ord("C")
    del pol[15_500]                                   # one deletion
    r = reference.compare_job({"c1": bytes(pol)}, {"c1": truth, "c2": truth})
    assert r["missing"] == ["c2"]
    assert r["errors"] == 30
    assert r["bases"] == len(truth)
    assert 0.1 <= r["worst_block_pct"] <= 0.12


def test_an_error_on_a_block_edge_is_charged_at_most_twice():
    truth = _truth(9)
    pol = bytearray(truth)
    for pos in range(reference.BLOCK - 1, 30_000, reference.BLOCK):
        pol[pos] = ord("A") if pol[pos] != ord("A") else ord("C")
    r = reference.compare_job({"c1": bytes(pol)}, {"c1": truth})
    assert 15 <= r["errors"] <= 30


@pytest.mark.parametrize("cut", ["prefix", "suffix", "middle"])
def test_a_contig_short_of_its_truth_is_charged_what_it_lacks(cut):
    truth = _truth(5)
    lost = 6_000
    pol = {"prefix": truth[:-lost], "suffix": truth[lost:],
           "middle": truth[:12_000] + truth[12_000 + lost:]}[cut]
    r = reference.compare_job({"c1": pol}, {"c1": truth})
    assert r["missing"] == []
    assert r["errors"] >= lost
    assert r["bases"] == len(truth)
    assert r["worst_block_pct"] >= 20.0


def test_an_empty_record_counts_as_missing():
    truth = _truth(6)
    r = reference.compare_job(reference.parse_fasta(b">c1\n>c2\nACGT\n"),
                              {"c1": truth, "c2": b"ACGT"})
    assert r["missing"] == ["c1"]
    assert r["errors"] == 0


def test_bases_past_the_truth_are_errors():
    truth = _truth(7)
    r = reference.compare_job({"c1": truth + _truth(8, 4_000)},
                              {"c1": truth})
    assert r["errors"] == 4_000
    assert r["bases"] == len(truth) + 4_000
