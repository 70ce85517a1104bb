"""The native aligner's vector pass against the scalar one it replaces.

``tests/fixtures/native_golden.json`` holds, for every case of the
seeded corpus below, the op count, the score and the sha256 of the op
string that the scalar banded pass (``band_pass`` in
racon_tpu/native/nw.cpp, before the vector pass existed) returned. The
vector pass must give every one of them back byte for byte: it computes
the same band cells with the same tie order, so any difference is a
fault, not a rounding.
"""

import ctypes
import hashlib
import json
import os

import numpy as np
import pytest

from racon_tpu.native import aligner as native
from racon_tpu.native.aligner import NativeAligner

GOLDEN = os.path.join(os.path.dirname(__file__), "fixtures",
                      "native_golden.json")
SCORINGS = [(5, -4, -8), (0, -1, -1), (1, -1, -1)]
ED = (0, -1, -1)


def _mutate(rng, seq, rate):
    """Substitutions, insertions and deletions at rate/3 each."""
    r = rng.random(len(seq))
    codes = seq.copy()
    sub = (r >= rate / 3) & (r < 2 * rate / 3)
    codes[sub] = (codes[sub] + rng.integers(1, 4, int(sub.sum()))) % 4
    counts = np.ones(len(seq), np.int64)
    counts[r < rate / 3] = 0
    ins = (r >= 2 * rate / 3) & (r < rate)
    counts[ins] = 2
    out = np.repeat(codes, counts)
    pos = np.cumsum(counts)[ins] - 1
    out[pos] = rng.integers(0, 4, len(pos))
    return out.astype(np.uint8)


def _random(rng, n, alphabet=5):
    return rng.integers(0, alphabet, n).astype(np.uint8)


def _pair(rng, n, q_rate, t_rate):
    truth = _random(rng, n, 4)
    return _mutate(rng, truth, q_rate), _mutate(rng, truth, t_rate)


def _group_random(rng):
    for m, x, g in SCORINGS:
        for k in range(25):
            yield (f"{m},{x},{g}/{k}", _random(rng, int(rng.integers(1, 400))),
                   _random(rng, int(rng.integers(1, 400))), (m, x, g), 0)


def _group_similar(rng):
    for m, x, g in SCORINGS:
        for k in range(10):
            q, t = _pair(rng, int(rng.integers(300, 3000)), 0.15, 0.02)
            yield f"{m},{x},{g}/{k}", q, t, (m, x, g), 0


def _group_redo(rng):
    """The geometry of the overlaps the device hands back: 12-20 kb of a
    10-20% error read against a 2% error draft."""
    for k in range(5):
        q, t = _pair(rng, int(rng.integers(12_000, 20_000)),
                     float(rng.uniform(0.10, 0.20)), 0.02)
        yield f"ed/{k}", q, t, ED, 0
    for m, x, g in ((5, -4, -8), (1, -1, -1)):
        q, t = _pair(rng, 14_000, 0.12, 0.02)
        yield f"{m},{x},{g}", q, t, (m, x, g), 0


def _group_balanced_indel(rng):
    for nx, nz in ((300, 1200), (1000, 3000)):
        X, Z = _random(rng, nx, 4), _random(rng, nz, 4)
        for m, x, g in SCORINGS:
            yield (f"{nx}/{m},{x},{g}", np.concatenate([X, Z]),
                   np.concatenate([Z, X]), (m, x, g), 0)


def _group_long_gap(rng):
    """Length differences |d| up to 2 kb, clean and under noise."""
    t = _random(rng, 4000, 4)
    q = np.concatenate([t[:1000], t[3000:]])
    for m, x, g in SCORINGS:
        yield f"del/{m},{x},{g}", q, t, (m, x, g), 0
        yield f"ins/{m},{x},{g}", t, q, (m, x, g), 0
    for k, cut in enumerate((500, 1200, 2000)):
        truth = _random(rng, 6000, 4)
        q = _mutate(rng, np.concatenate([truth[:2000], truth[2000 + cut:]]),
                    0.12)
        t = _mutate(rng, truth, 0.02)
        yield f"noisy_del/{k}", q, t, ED, 0
        yield f"noisy_ins/{k}", t, q, ED, 0


def _group_band0(rng):
    """Fixed initial bands, from narrower than one vector register up."""
    for k, band0 in enumerate((1, 2, 7, 15, 16, 31, 33, 64, 300)):
        m, x, g = SCORINGS[k % 3]
        q, t = _pair(rng, int(rng.integers(500, 1500)), 0.12, 0.02)
        yield f"{band0}/{m},{x},{g}", q, t, (m, x, g), band0


def _group_full(rng):
    for m, x, g in SCORINGS:
        q, t = _pair(rng, int(rng.integers(300, 800)), 0.15, 0.02)
        yield f"{m},{x},{g}", q, t, (m, x, g), 10_000


def _group_edge(rng):
    for lq, lt in ((0, 0), (0, 1), (1, 0), (0, 7), (7, 0), (1, 1), (1, 60),
                   (60, 1), (2, 2), (3, 40)):
        for m, x, g in SCORINGS:
            yield (f"{lq}x{lt}/{m},{x},{g}", _random(rng, lq, 4),
                   _random(rng, lt, 4), (m, x, g), 0)
    a = _random(rng, 1, 4)
    yield "1x1_equal", a, a.copy(), ED, 0


def _group_scoring_range(rng):
    """Scorings whose score differences do not fit 8-bit lanes, and a
    free gap (the full matrix at once)."""
    for m, x, g in ((100, -100, -30), (0, -200, -1), (1, -1, 0)):
        q, t = _pair(rng, int(rng.integers(300, 900)), 0.12, 0.02)
        yield f"{m},{x},{g}", q, t, (m, x, g), 0


GROUPS = {
    "random": _group_random, "similar": _group_similar,
    "redo": _group_redo, "balanced_indel": _group_balanced_indel,
    "long_gap": _group_long_gap, "band0": _group_band0,
    "full": _group_full, "edge": _group_edge,
    "scoring_range": _group_scoring_range,
}


def corpus(group):
    rng = np.random.default_rng(
        int(hashlib.sha256(group.encode()).hexdigest()[:8], 16))
    for name, q, t, scoring, band0 in GROUPS[group](rng):
        yield f"{group}/{name}", q, t, scoring, band0


def align_one(q, t, scoring, band0):
    """(op count, score, sha256 of the ops) from ``racon_nw_align``."""
    lib = native._load()
    m, x, g = scoring
    q = np.ascontiguousarray(q, np.uint8)
    t = np.ascontiguousarray(t, np.uint8)
    out = np.zeros(len(q) + len(t) + 1, np.uint8)
    score = ctypes.c_int32(0)
    n = lib.racon_nw_align(native._u8ptr(q), len(q), native._u8ptr(t),
                           len(t), m, x, g, band0, native._u8ptr(out),
                           ctypes.byref(score))
    return [int(n), int(score.value),
            hashlib.sha256(out[:max(n, 0)].tobytes()).hexdigest()]


def record():
    """The golden table of the library as built now."""
    return {name: align_one(q, t, scoring, band0)
            for group in GROUPS
            for name, q, t, scoring, band0 in corpus(group)}


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("group", sorted(GROUPS))
def test_ops_and_score_match_the_scalar_pass(group, golden):
    got = {name: align_one(q, t, scoring, band0)
           for name, q, t, scoring, band0 in corpus(group)}
    want = {k: v for k, v in golden.items() if k.startswith(group + "/")}
    assert got.keys() == want.keys()
    assert {k: v for k, v in got.items() if v != want[k]} == {}


def band_cells(lq, lt, klo, khi):
    i = np.arange(lq + 1)
    w = np.minimum(lt, i + khi) - np.maximum(0, i + klo) + 1
    return int(np.maximum(w, 0).sum())


def band_score(q, t, m, x, g, klo, khi):
    """H(lq, lt) over the paths inside the band k = j - i in [klo, khi]:
    rows in numpy, the left-gap chain of a row as a running maximum."""
    lq, lt = len(q), len(t)
    neg = -(1 << 40)
    j = np.arange(lt + 1)
    k = j[None, :] - np.arange(lq + 1)[:, None]
    inband = (k >= klo) & (k <= khi)
    prev = np.where(inband[0], j * g, neg)
    for i in range(1, lq + 1):
        sub = np.where(t == q[i - 1], m, x)
        tmp = np.full(lt + 1, neg)
        tmp[1:] = prev[:-1] + sub
        tmp = np.maximum(tmp, prev + g)
        tmp[0] = i * g
        tmp = np.where(inband[i], tmp, neg)
        tmp = j * g + np.maximum.accumulate(tmp - j * g)
        prev = np.where(inband[i], tmp, neg)
    return int(prev[lt])


def passes(q, t, m, x, g, band0):
    """The bands racon_nw_align runs, by its own acceptance rule."""
    lq, lt = len(q), len(t)
    if lq == 0 or lt == 0:
        return []
    d, dgap = lt - lq, abs(lt - lq)
    w = band0 if band0 > 0 else max(128, dgap + 64)
    if g >= 0:
        w = max(lq, lt)
    mmax = max(m, 0) * min(lq, lt)
    out = []
    while True:
        klo, khi = max(min(0, d) - w, -lq), min(max(0, d) + w, lt)
        out.append((klo, khi))
        score = band_score(q, t, m, x, g, klo, khi)
        if (klo <= -lq and khi >= lt) or \
                score >= mmax + g * (dgap + 2 * w + 2):
            return out
        n_g = (mmax - score - g - 1) // -g
        w = min(max(2 * w, (n_g - dgap) // 2 + 1), max(lq, lt))


def _dispatch_batch():
    """Records for band0 = 1 (an initial band of 3 cells, narrower than
    the vector pass takes): an equal pair the narrow band accepts, a
    long insertion that makes its one band wide, and swapped blocks
    whose narrow first band is not provably optimal, so a wide second
    band follows."""
    rng = np.random.default_rng(29)
    a = _random(rng, 300, 4)
    b = np.concatenate([a[:150], _random(rng, 100, 4), a[150:]])
    X, Z = _random(rng, 20, 4), _random(rng, 60, 4)
    return [(a, a.copy()), (a, b),
            (np.concatenate([X, Z]), np.concatenate([Z, X]))]


@pytest.mark.parametrize("threads", [1, 8])
def test_vector_cells_move_only_for_wide_bands(threads):
    pairs = _dispatch_batch()
    cells = np.full((len(pairs), 2), -1, np.int64)
    ops = NativeAligner(*ED, band=1, threads=threads).align_batch(
        pairs, cells=cells)
    bands = [passes(q, t, *ED, 1) for q, t in pairs]
    assert [len(b) for b in bands] == [1, 1, 2]
    want = [sum(band_cells(len(q), len(t), lo, hi) for lo, hi in b)
            for (q, t), b in zip(pairs, bands)]
    assert list(cells[:, 0]) == want
    lanes = native.vector_lanes()
    vec = [sum(band_cells(len(q), len(t), lo, hi) for lo, hi in b
               if lanes and hi - lo + 1 >= 7)
           for (q, t), b in zip(pairs, bands)]
    assert list(cells[:, 1]) == vec
    if lanes:
        assert vec[0] == 0 and vec[1] == want[1] and 0 < vec[2] < want[2]
    # The model of the passes scores the last band as the aligner does.
    assert [band_score(q, t, *ED, *b[-1]) for (q, t), b in
            zip(pairs, bands)] == [align_one(q, t, ED, 1)[1]
                                   for q, t in pairs]
    # The counts change nothing of the alignments.
    plain = NativeAligner(*ED, band=1, threads=threads).align_batch(pairs)
    assert all(np.array_equal(p, o) for p, o in zip(plain, ops))


def test_threads_give_the_same_ops_and_counts():
    pairs = [c[1:3] for g in ("similar", "long_gap", "band0")
             for c in corpus(g)]
    out = {}
    for threads in (1, 8):
        cells = np.zeros((len(pairs), 2), np.int64)
        ops = NativeAligner(threads=threads).align_batch(pairs, cells=cells)
        out[threads] = ([o.tobytes() for o in ops], cells.tolist())
    assert out[1] == out[8]
    assert all(c[0] > 0 for c in out[1][1])


def test_cells_must_fit_the_batch():
    pairs = _dispatch_batch()
    with pytest.raises(ValueError):
        NativeAligner().align_batch(pairs, cells=np.zeros((2, 2), np.int64))
    with pytest.raises(ValueError):
        NativeAligner().align_batch(pairs, cells=np.zeros((3, 2), np.int32))


if __name__ == "__main__":
    # Writes the table of the library as built now, one case a line.
    import sys
    rows = [f"{json.dumps(k)}: {json.dumps(v)}"
            for k, v in sorted(record().items())]
    sys.stdout.write("{\n" + ",\n".join(rows) + "\n}\n")
