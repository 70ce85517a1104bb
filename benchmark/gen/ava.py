"""Seeded read self-correction jobs: noisy reads and their all-vs-all PAF.

The job of racon's fragment correction (``-f``, kF): every read is a
target, and the reads that overlap it are its layers. The CLI takes
the reads file twice, ``racon reads.fastq overlaps.paf reads.fastq
-f``, as in racon's kF tests:

- a random truth for each contig of the configuration, read as a
  circle: reads are laid out on it (``read_layout``: log-normal spans
  clipped to the configuration's range and to half the circle, uniform
  starts, a share reverse-strand, until the coverage is met; the same
  layout for every job and seed), so every read sees level coverage
  and no read sits at an end;
- each read mutated at the read error profile (``genome.mutate``),
  with qualities (FASTQ) or without (FASTA);
- one PAF record for every unordered pair of reads that share at least
  ``genome.MIN_OVERLAP`` truth bases, each pair once and never a read
  with itself, as minimap2's all-vs-all presets (``-X``) report them:
  the query is the read whose name sorts first. Read names are a fixed
  permutation of the reads, so they say nothing of where a read lies
  (ONT read names are UUIDs). Coordinates go through both reads'
  mutations; the strand is the XOR of the reads' strands.

A read is corrected from the overlaps that target it alone, so a read
whose name sorts early gets few layers. ``make_job`` splits the reads
by the overlaps that reach into each of their windows: the program has
to give back every read with at least ``MIN_LAYERS`` in each window,
and may drop the others.

Everything is a pure function of the arguments, so a job regenerates
byte-identically from its seed.
"""

from __future__ import annotations

import os
from typing import Dict, List, NamedTuple

import numpy as np

from benchmark.gen import genome

# The layout's key: the reads' spans, starts, strands and names.
LAYOUT_TAG = 1
# Overlaps that reach into every window of a read for it to be due back.
MIN_LAYERS = 3
# The suffix the program adds to a corrected read's name.
CORRECTED = "r"


class Read(NamedTuple):
    contig: int
    start: int      # in the contig's truth, which wraps round at its end
    span: int
    reverse: bool
    name: str


def read_layout(cfg: Dict) -> List[Read]:
    """Where the reads of a job lie, drawn from a fixed key: every job
    of every run has the same reads over the same stretches, so a seed
    changes the bases, errors and qualities, not the amount of work.
    A span is at most half its contig, so two reads share at most one
    stretch of the circle."""
    if cfg["ends"]["reads"] != "circular":
        raise ValueError("an all-vs-all job lays its reads on a circle "
                         "(\"ends\": {\"reads\": \"circular\"})")
    rng = np.random.default_rng(np.random.SeedSequence([genome.LAYOUT_KEY,
                                                        LAYOUT_TAG]))
    placed = []
    for i_contig, contig in enumerate(cfg["contigs"]):
        bp = int(contig["bp"])
        target = bp * cfg["coverage"]
        spans = genome.read_spans(
            rng, 4 * target // cfg["read_length"]["min"] + 16,
            cfg["read_length"], bp // 2 + 1)
        spans = spans[:int(np.searchsorted(np.cumsum(spans), target)) + 1]
        starts = rng.integers(0, bp, len(spans))
        reverse = rng.random(len(spans)) < cfg["reverse_share"]
        placed += [(i_contig, int(p), int(s), bool(r))
                   for p, s, r in zip(starts, spans, reverse)]
    names = rng.permutation(len(placed))
    return [Read(*r, f"r{int(n)}") for r, n in zip(placed, names)]


def _shared(a: Read, b: Read, bp: int):
    """The truth stretch ``[lo, hi)`` that reads ``a`` and ``b`` of one
    contig of ``bp`` bases share, in ``a``'s unwrapped coordinates
    (``a.start <= lo``), and ``b``'s start in the same coordinates; None
    where they share less than ``genome.MIN_OVERLAP`` bases."""
    for shift in (-bp, 0, bp):
        b0 = b.start + shift
        lo, hi = max(a.start, b0), min(a.start + a.span, b0 + b.span)
        if hi - lo >= genome.MIN_OVERLAP:
            return lo, hi, b0
    return None


class _Mutated(NamedTuple):
    length: int
    starts: np.ndarray
    keep: np.ndarray
    ins: np.ndarray
    reverse: bool


def _range(m: _Mutated, a: int, b: int):
    """The read's bases ``[q0, q1)``, in the orientation it is stored,
    that truth bases ``[a, b)`` of its span became."""
    q0 = int(m.starts[a])
    q1 = int(m.starts[b - 1]) + int(m.keep[b - 1]) + int(m.ins[b - 1])
    return (m.length - q1, m.length - q0) if m.reverse else (q0, q1)


def _windows_reached(length: int, ranges: List[tuple], w: int) -> np.ndarray:
    """For each ``w``-base window of a read of ``length`` bases, how
    many of the target ranges ``[t0, t1)`` reach into it."""
    n = -(-length // w)
    cnt = np.zeros(n + 1, np.int64)
    for t0, t1 in ranges:
        cnt[t0 // w] += 1
        cnt[(t1 - 1) // w + 1] -= 1
    return np.cumsum(cnt)[:n]


def make_job(cfg: Dict, traffic: Dict, rng, out_dir: str) -> Dict:
    """Write one job into ``out_dir``: the reads (``reads.fastq`` or
    ``reads.fasta``) and ``overlaps.paf``.

    Returns the paths in the order the CLI takes them (the reads file
    twice), each read's truth in the orientation it is stored, keyed by
    the name the program prints for it, split into the reads due back
    (``truths``) and the others (``optional``), the target bases (every
    read's length: ``draft_bp``) and the number of reads."""
    if traffic["overlaps"] != "ava_paf" or \
            traffic["reads"] not in ("fastq", "fasta"):
        raise ValueError(f"unknown traffic formats {traffic['overlaps']!r}/"
                         f"{traffic['reads']!r}")
    if not cfg["racon"].get("fragment_correction"):
        raise ValueError("an all-vs-all job is corrected with -f "
                         "(\"fragment_correction\": true)")
    os.makedirs(out_dir, exist_ok=True)
    layout = read_layout(cfg)
    qmin, qmax = cfg["read_quality"]["min"], cfg["read_quality"]["max"]
    rates = (cfg["read_error"]["sub"], cfg["read_error"]["ins"],
             cfg["read_error"]["del"])
    circles = []
    for contig in cfg["contigs"]:
        truth = genome.BASES[rng.integers(0, 4, int(contig["bp"]))]
        circles.append(np.concatenate([truth, truth]))
    records: List[bytes] = []
    true_seqs: List[bytes] = []
    mutated: List[_Mutated] = []
    for r in layout:
        src = circles[r.contig][r.start:r.start + r.span]
        seg, starts, keep, ins = genome.mutate(rng, src, *rates)
        qual = rng.integers(33 + qmin, 33 + qmax + 1, len(seg),
                            dtype=np.uint8)
        seq, true = ((genome.RC[seg][::-1], genome.RC[src][::-1])
                     if r.reverse else (seg, src))
        name = r.name.encode()
        if traffic["reads"] == "fastq":
            records.append(b"@" + name + b"\n" + seq.tobytes() + b"\n+\n"
                           + qual.tobytes() + b"\n")
        else:
            records.append(b">" + name + b"\n" + seq.tobytes() + b"\n")
        true_seqs.append(true.tobytes())
        mutated.append(_Mutated(len(seg), starts, keep, ins, r.reverse))

    ovls: List[str] = []
    targeted: List[List[tuple]] = [[] for _ in layout]
    for i, a in enumerate(layout):
        for j in range(i + 1, len(layout)):
            b = layout[j]
            if b.contig != a.contig:
                continue
            s = _shared(a, b, int(cfg["contigs"][a.contig]["bp"]))
            if s is None:
                continue
            lo, hi, b0 = s
            ends = {i: _range(mutated[i], lo - a.start, hi - a.start),
                    j: _range(mutated[j], lo - b0, hi - b0)}
            q, t = (i, j) if a.name < b.name else (j, i)
            strand = "-" if a.reverse != b.reverse else "+"
            ovls.append(f"{layout[q].name}\t{mutated[q].length}\t"
                        f"{ends[q][0]}\t{ends[q][1]}\t{strand}\t"
                        f"{layout[t].name}\t{mutated[t].length}\t"
                        f"{ends[t][0]}\t{ends[t][1]}\t{hi - lo}\t"
                        f"{hi - lo}\t60")
            targeted[t].append(ends[t])

    w = int(cfg["racon"]["window_length"])
    truths: Dict[str, bytes] = {}
    optional: Dict[str, bytes] = {}
    for r, m, true, ranges in zip(layout, mutated, true_seqs, targeted):
        due = _windows_reached(m.length, ranges, w).min() >= MIN_LAYERS
        (truths if due else optional)[r.name + CORRECTED] = true
    paths = {"reads": os.path.join(out_dir, f"reads.{traffic['reads']}"),
             "overlaps": os.path.join(out_dir, "overlaps.paf")}
    with open(paths["reads"], "wb") as f:
        f.write(b"".join(records))
    with open(paths["overlaps"], "w") as f:
        f.write("\n".join(ovls) + "\n")
    return {"paths": [paths["reads"], paths["overlaps"], paths["reads"]],
            "truths": truths, "optional": optional,
            "draft_bp": sum(m.length for m in mutated),
            "reads": len(layout)}
