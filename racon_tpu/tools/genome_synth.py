"""Seeded genome-polishing jobs: truth, draft, noisy reads, PAF overlaps.

The generator behind chip_smoke.py. From one
seed it makes a random truth genome, a draft contig with about 2% error
(substitutions, insertions and deletions in equal parts), reads with
about 10% error, half of them on the reverse strand, with qualities,
and a PAF file that maps every read onto the draft. Everything is a
pure function of the arguments, so a job regenerates bit-identically.

Read lengths are either one fixed length or the ``"spread"``
distribution: log-normal around 6 kb, clipped to 2-20 kb, which puts
about 15% of reads above 10 kb. Reads past 8 kb take the tiled overlap
kernels (ops/ovl_align.py), shorter ones the untiled kernel.
"""

from __future__ import annotations

import os
from typing import Dict, Union

import numpy as np

BASES = np.frombuffer(b"ACGT", np.uint8)
RC = np.zeros(256, np.uint8)
RC[np.frombuffer(b"ACGT", np.uint8)] = np.frombuffer(b"TGCA", np.uint8)

DRAFT_ERROR = 0.02
READ_ERROR = 0.10
SPREAD_MIN, SPREAD_MAX = 2_000, 20_000


def mutate(rng, seq, rate):
    """Vectorized mutation (sub/ins/del each rate/3); returns (mutated,
    map) where map[i] = position of truth base i in the output (deleted
    bases map to the previous surviving position)."""
    n = len(seq)
    r = rng.random(n)
    dele = r < rate / 3
    sub = (r >= rate / 3) & (r < 2 * rate / 3)
    ins = (r >= 2 * rate / 3) & (r < rate)
    counts = np.where(dele, 0, np.where(ins, 2, 1))
    starts = np.cumsum(counts) - counts
    out = np.zeros(int(counts.sum()), np.uint8)
    keep = ~dele
    base = np.where(sub, BASES[rng.integers(0, 4, n)], seq)
    out[starts[keep]] = base[keep]
    out[starts[ins] + 1] = BASES[rng.integers(0, 4, int(ins.sum()))]
    posmap = np.maximum.accumulate(np.where(keep, starts, -1))
    posmap = np.maximum(posmap, 0).astype(np.int64)
    return out, posmap


def read_lengths(rng, n_reads: int, lengths: Union[int, str],
                 genome_bp: int) -> np.ndarray:
    """Per-read truth-span lengths: a fixed ``int`` or ``"spread"``."""
    cap = max(genome_bp - 1, 1)
    if lengths == "spread":
        ln = rng.lognormal(np.log(6_000), 0.5, n_reads)
        ln = np.clip(ln, SPREAD_MIN, SPREAD_MAX)
    else:
        ln = np.full(n_reads, int(lengths))
    return np.minimum(ln.astype(np.int64), cap)


def write_job(out_dir: str, genome_bp: int, coverage: int, seed: int,
              lengths: Union[int, str] = "spread") -> Dict:
    """Write ``draft.fasta``, ``reads.fastq`` and ``overlaps.paf`` into
    ``out_dir``; return the paths, the truth and draft (uint8 base
    arrays) and the read lengths."""
    os.makedirs(out_dir, exist_ok=True)
    rng = np.random.default_rng(seed)
    truth = BASES[rng.integers(0, 4, genome_bp)]
    draft, posmap = mutate(rng, truth, DRAFT_ERROR)
    paths = {k: os.path.join(out_dir, f)
             for k, f in (("draft", "draft.fasta"),
                          ("reads", "reads.fastq"),
                          ("paf", "overlaps.paf"))}
    with open(paths["draft"], "wb") as f:
        f.write(b">contig1\n" + draft.tobytes() + b"\n")

    mean = SPREAD_MIN * 3 if lengths == "spread" else int(lengths)
    n_est = genome_bp * coverage // max(min(mean, genome_bp), 1)
    spans = read_lengths(rng, 2 * n_est + 16, lengths, genome_bp)
    # Draw until the target coverage is met (spread lengths vary).
    n_reads = int(np.searchsorted(np.cumsum(spans),
                                  genome_bp * coverage)) + 1
    spans = spans[:min(n_reads, len(spans))]
    paf = []
    seq_lens = []
    with open(paths["reads"], "wb") as f:
        for i, span in enumerate(spans):
            span = int(span)
            p = int(rng.integers(0, genome_bp - span + 1))
            seg, _ = mutate(rng, truth[p:p + span], READ_ERROR)
            strand = rng.random() < 0.5
            if strand:
                seg = RC[seg][::-1]
            q = rng.integers(33 + 8, 33 + 40, len(seg),
                             dtype=np.uint8).tobytes()
            name = f"r{i}"
            f.write(b"@" + name.encode() + b"\n" + seg.tobytes() + b"\n+\n"
                    + q + b"\n")
            ts, te = int(posmap[p]), int(posmap[p + span - 1]) + 1
            paf.append(f"{name}\t{len(seg)}\t0\t{len(seg)}\t"
                       f"{'-' if strand else '+'}\tcontig1\t{len(draft)}\t"
                       f"{ts}\t{te}\t{span}\t{span}\t60")
            seq_lens.append(len(seg))
    with open(paths["paf"], "w") as f:
        f.write("\n".join(paf) + "\n")
    return {**paths, "truth": truth, "draft_codes": draft,
            "read_lengths": np.asarray(seq_lens, np.int64)}


def edit_distance(a: bytes, b: bytes) -> int:
    """Global edit distance between two base strings (native aligner at
    edit-distance scoring, ops replayed)."""
    from racon_tpu.native.aligner import NativeAligner
    from racon_tpu.ops.encode import encode_bases
    qa, ta = encode_bases(a), encode_bases(b)
    ops = np.asarray(NativeAligner(0, -1, -1).align_codes(
        np.ascontiguousarray(qa), np.ascontiguousarray(ta)))
    diag = ops == 0
    qi = np.cumsum(ops != 2) - 1
    ti = np.cumsum(ops != 1) - 1
    mism = qa[qi[diag]] != ta[ti[diag]]
    return int((~diag).sum() + mism.sum())


def sampled_errors(contig: bytes, truth: np.ndarray, n_samples: int = 20,
                   chunk: int = 10_000):
    """(errors, bases) over ``n_samples`` evenly spaced ``chunk``-base
    slices of ``contig``, each aligned against the matching truth slice
    with 2 kb of slop per side. The overhang the slop adds is subtracted
    instead of trimmed: a global alignment must delete it, and its
    tie-breaking scatters those deletions."""
    scale = len(contig) / len(truth)
    eds = tot = 0
    for s in np.linspace(0, max(len(contig) - chunk, 0),
                         n_samples).astype(int):
        pc = contig[s:s + chunk]
        ts = max(0, int(s / scale) - 2000)
        tc = truth[ts:ts + chunk + 4000].tobytes()
        eds += max(edit_distance(pc, tc) - (len(tc) - len(pc)), 0)
        tot += len(pc)
    return eds, tot
