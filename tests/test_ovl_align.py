"""Device overlap alignment (ops/ovl_align.py) vs the native path.

The device path computes breaking points straight from the banded
forward + column walk; the native path aligns, emits a CIGAR, and walks
it (models/overlap.py::breaking_points_from_cigar). Both must agree on
every handled overlap (same NW scoring and tie-breaks), and the device
must hand uncertifiable lanes back for fallback rather than emit them.
"""

import gzip
import os

import numpy as np
import pytest

from racon_tpu.models.polisher import create_polisher, PolisherType


def _write_dataset(tmp_path, n_reads=24, read_len=2400, seed=5,
                   rate=0.12, draft_len=40_000):
    """Tiny synthetic draft + reads + PAF with ``rate`` read-vs-draft
    error (default ~12%, ONT-class; the tiled ultralong tests use lower
    rates with longer reads — see test_ovl_tiled.py). ``read_len`` is
    one draft span for every read, or a sequence of one span per read
    (``n_reads`` is then its length)."""
    rng = np.random.default_rng(seed)
    if np.ndim(read_len):
        spans = [int(n) for n in read_len]
    else:
        spans = [read_len] * n_reads
    bases = np.frombuffer(b"ACGT", np.uint8)
    draft = bases[rng.integers(0, 4, draft_len)]

    def mutate(seq, rate):
        r = rng.random(len(seq))
        dele = r < rate / 3
        sub = (r >= rate / 3) & (r < 2 * rate / 3)
        ins = (r >= 2 * rate / 3) & (r < rate)
        counts = np.where(dele, 0, np.where(ins, 2, 1))
        starts = np.cumsum(counts) - counts
        out = np.zeros(int(counts.sum()), np.uint8)
        keep = ~dele
        base = np.where(sub, bases[rng.integers(0, 4, len(seq))], seq)
        out[starts[keep]] = base[keep]
        out[starts[ins] + 1] = bases[rng.integers(0, 4, int(ins.sum()))]
        return out

    rc = np.zeros(256, np.uint8)
    rc[bases] = np.frombuffer(b"TGCA", np.uint8)

    reads, paf = [], []
    for i, read_len in enumerate(spans):
        t0 = int(rng.integers(0, len(draft) - read_len))
        seg = mutate(draft[t0:t0 + read_len], rate)
        strand = i % 3 == 1
        out = rc[seg][::-1] if strand else seg
        reads.append((f"r{i}", out.tobytes()))
        paf.append(f"r{i}\t{len(out)}\t0\t{len(out)}\t"
                   f"{'-' if strand else '+'}\tdraft\t{len(draft)}\t"
                   f"{t0}\t{t0 + read_len}\t{read_len}\t{read_len}\t255")

    d = str(tmp_path)
    with gzip.open(f"{d}/reads.fasta.gz", "wb") as f:
        for name, data in reads:
            f.write(b">" + name.encode() + b"\n" + data + b"\n")
    with gzip.open(f"{d}/draft.fasta.gz", "wb") as f:
        f.write(b">draft\n" + draft.tobytes() + b"\n")
    with gzip.open(f"{d}/overlaps.paf.gz", "wb") as f:
        f.write(("\n".join(paf) + "\n").encode())
    return d


def _layer_snapshot(p):
    snap = []
    for w in p.windows:
        snap.append([
            (bytes(w.layer_data[i]), int(w.layer_begin[i]),
             int(w.layer_end[i]))
            for i in range(w.n_layers)])
    return snap


@pytest.mark.parametrize("window", [500, 1000])
def test_device_breaking_points_match_native(tmp_path, window):
    d = _write_dataset(tmp_path)
    args = (f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
            f"{d}/draft.fasta.gz", PolisherType.kC, window, 10.0, 0.3,
            5, -4, -8)
    pn = create_polisher(*args, backend="native")
    pn.initialize()
    pj = create_polisher(*args, backend="jax")
    pj.initialize()
    assert _layer_snapshot(pj) == _layer_snapshot(pn)


def test_overlength_jobs_fall_back(tmp_path):
    """Reads past the device budget must route to the native fallback
    and still produce layers (not silently drop)."""
    d = _write_dataset(tmp_path, n_reads=3, read_len=17_000, seed=7)
    args = (f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
            f"{d}/draft.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
            5, -4, -8)
    pn = create_polisher(*args, backend="native")
    pn.initialize()
    pj = create_polisher(*args, backend="jax")
    pj.initialize()
    assert _layer_snapshot(pj) == _layer_snapshot(pn)
    assert sum(w.n_layers for w in pj.windows) > 0
