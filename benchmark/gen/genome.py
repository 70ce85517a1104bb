"""Seeded polishing jobs: truth, draft, noisy reads, PAF or SAM overlaps.

A copy of the repository's seeded genome generator, kept with the
benchmark so that later changes to the program cannot change the
benchmark's inputs, and generalised to the parameters of a
configuration file (``benchmark/configs/<name>.json``) and a traffic
mix (``benchmark/traffic/<name>.json``):

- a random truth sequence for each contig of the configuration;
- a draft of each contig, mutated at the configuration's draft error
  profile (substitutions, insertions, deletions at separate rates);
- reads laid out along each contig (``read_layout``: log-normal truth
  spans clipped to the configuration's range, uniform starts, a share
  reverse-strand, until the coverage is met; the same layout for every
  job and seed), mutated at the read error profile, with qualities
  (FASTQ) or without (FASTA), as the traffic mix says;
- one overlap per read against the draft: PAF with the approximate
  coordinates (the program aligns it), or SAM with the CIGAR of the
  read against the draft composed through the truth coordinates, as an
  aligner would report it (``benchmark/gen/sam.py``).

A mix whose overlaps are ``ava_paf`` is a read self-correction job,
all-vs-all overlaps among the reads and no draft
(``benchmark/gen/ava.py``).

Everything is a pure function of the arguments, so a job regenerates
byte-identically from its seed.
"""

from __future__ import annotations

import os
from typing import Dict, List, Tuple

import numpy as np

from benchmark.gen import sam

BASES = np.frombuffer(b"ACGT", np.uint8)
RC = np.zeros(256, np.uint8)
RC[np.frombuffer(b"ACGT", np.uint8)] = np.frombuffer(b"TGCA", np.uint8)

# Stream tags: the warm-up job and the timed jobs of one seed never
# share a generator state, nor does the order of a pool of timed jobs;
# the reads' layout has a key of its own.
WARMUP_STREAM, TIMED_STREAM, ORDER_STREAM = 1, 2, 3
LAYOUT_KEY = 0
# Least truth bases a read shares with the contig to be reported as an
# overlap of it (reads in a flank may reach only a little way in).
MIN_OVERLAP = 1_000


def job_rng(seed: int, stream: int, index: int) -> np.random.Generator:
    """Generator for one job: any whole ``seed`` (negative or past 64
    bits is folded into 64 bits), a stream tag and the job's index."""
    return np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 64), stream, index]))


def mutate(rng, seq: np.ndarray, sub: float, ins: float, dele: float):
    """Mutate ``seq`` per base: delete with rate ``dele``, substitute
    with ``sub``, or insert one random base after it with ``ins``.

    Returns ``(out, starts, keep, ins_mask)``: the mutated bases, for
    each input base the index in ``out`` where its output starts, and
    the masks of the bases kept and of those followed by an insertion.
    """
    n = len(seq)
    r = rng.random(n)
    is_del = r < dele
    is_sub = (r >= dele) & (r < dele + sub)
    is_ins = (r >= dele + sub) & (r < dele + sub + ins)
    counts = np.where(is_del, 0, np.where(is_ins, 2, 1))
    starts = np.cumsum(counts) - counts
    out = np.zeros(int(counts.sum()), np.uint8)
    keep = ~is_del
    base = np.where(is_sub, BASES[rng.integers(0, 4, n)], seq)
    out[starts[keep]] = base[keep]
    out[starts[is_ins] + 1] = BASES[rng.integers(0, 4, int(is_ins.sum()))]
    return out, starts, keep, is_ins


def read_spans(rng, n: int, spec: Dict, cap: int) -> np.ndarray:
    """Truth spans of ``n`` reads: log-normal around ``spec["median"]``
    with shape ``spec["sigma"]``, clipped to ``[min, max]`` and to the
    contig."""
    ln = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    ln = np.clip(ln, spec["min"], spec["max"])
    return np.minimum(ln.astype(np.int64), max(cap - 1, 1))


def read_layout(cfg: Dict, i_contig: int) -> List[Tuple[int, int, bool]]:
    """Where the reads of contig ``i_contig`` lie: ``(start, span,
    reverse)`` in the contig's flanked truth, drawn until the coverage
    is met, keeping the reads that share at least ``MIN_OVERLAP`` bases
    with the contig.

    The layout is drawn from a fixed key, not from the seed: every job
    of every run has the same reads over the same windows, so the
    program's chunks keep one shape from job to job and seed to seed,
    and a seed changes the bases, errors and qualities, not the amount
    of work."""
    rng = np.random.default_rng(np.random.SeedSequence([LAYOUT_KEY,
                                                        i_contig]))
    bp = int(cfg["contigs"][i_contig]["bp"])
    flank = int(cfg["ends"]["flank_bp"])
    truncate = cfg["ends"]["reads"] == "truncated"
    length = bp + 2 * flank
    target = length * cfg["coverage"]
    spans = read_spans(rng, 4 * target // cfg["read_length"]["min"] + 16,
                       cfg["read_length"], length)
    spans = spans[:int(np.searchsorted(np.cumsum(spans), target)) + 1]
    out = []
    for span in spans.tolist():
        p = int(rng.integers(0, length - span + 1))
        reverse = bool(rng.random() < cfg["reverse_share"])
        a, b = max(p, flank), min(p + span, flank + bp)
        if truncate:
            # A linear chromosome: the molecule ends where it does.
            p, span = a, b - a
        if b - a >= MIN_OVERLAP:
            out.append((p, span, reverse))
    return out


def _rates(profile: Dict):
    return profile["sub"], profile["ins"], profile["del"]


def make_job(cfg: Dict, traffic: Dict, rng, out_dir: str) -> Dict:
    """Write one job into ``out_dir``: ``draft.fasta``, the reads
    (``reads.fastq`` or ``reads.fasta``) and the overlaps
    (``overlaps.paf`` or ``overlaps.sam``); under ``ava_paf``, the job
    ``ava.make_job`` writes.

    Returns the three paths in the order the CLI takes them, each
    contig's truth as bytes keyed by the name the program prints for
    it (the contig's own), and the draft's length in bases."""
    fmt_ovl = traffic["overlaps"]
    if fmt_ovl == "ava_paf":
        from benchmark.gen import ava   # ava builds on this module
        return ava.make_job(cfg, traffic, rng, out_dir)
    os.makedirs(out_dir, exist_ok=True)
    fmt_reads = traffic["reads"]
    if fmt_ovl not in ("paf", "sam") or fmt_reads not in ("fastq", "fasta"):
        raise ValueError(f"unknown traffic formats {fmt_ovl!r}/{fmt_reads!r}")
    qmin, qmax = cfg["read_quality"]["min"], cfg["read_quality"]["max"]
    read_rates = _rates(cfg["read_error"])
    draft_bytes: List[bytes] = []
    reads: List[bytes] = []
    ovls: List[str] = []
    truths: Dict[str, bytes] = {}
    header: List[str] = []
    draft_bp = 0
    n_read = 0
    flank = int(cfg["ends"]["flank_bp"])
    for i_contig, contig in enumerate(cfg["contigs"]):
        name, bp = contig["name"], int(contig["bp"])
        # The truth runs on past both ends of the contig, so coverage
        # stays level to the ends: reads there either overlap the draft
        # in part and are clipped (a stretch cut from a longer genome)
        # or end where the contig ends (a whole linear chromosome).
        full = BASES[rng.integers(0, 4, bp + 2 * flank)]
        truth = full[flank:flank + bp]
        draft, d_starts, d_keep, d_ins = mutate(rng, truth,
                                                *_rates(cfg["draft_error"]))
        truths[name] = truth.tobytes()
        draft_bp += len(draft)
        draft_bytes.append(b">" + name.encode() + b"\n" + draft.tobytes()
                           + b"\n")
        header.append(f"@SQ\tSN:{name}\tLN:{len(draft)}")
        for p, span, reverse in read_layout(cfg, i_contig):
            seg, r_starts, r_keep, r_ins = mutate(rng, full[p:p + span],
                                                  *read_rates)
            qual = rng.integers(33 + qmin, 33 + qmax + 1, len(seg),
                                dtype=np.uint8)
            # The truth range the read shares with the contig: [a, b).
            a, b = max(p, flank), min(p + span, flank + bp)
            ra, rb = a - p, b - p - 1
            qa = int(r_starts[ra])
            qb = int(r_starts[rb]) + int(r_keep[rb]) + int(r_ins[rb])
            da, db = a - flank, b - flank - 1
            ts = int(d_starts[da])
            te = int(d_starts[db]) + int(d_keep[db]) + int(d_ins[db])
            if fmt_ovl == "sam":
                try:
                    pos0, cigar = sam.compose_cigar(
                        r_keep[ra:rb + 1], r_ins[ra:rb + 1],
                        d_keep[da:db + 1], d_ins[da:db + 1], ts,
                        clip_l=qa, clip_r=len(seg) - qb)
                except ValueError:
                    continue
            seq = RC[seg][::-1] if reverse else seg
            rname = f"r{n_read}"
            n_read += 1
            if fmt_reads == "fastq":
                reads.append(b"@" + rname.encode() + b"\n" + seq.tobytes()
                             + b"\n+\n" + qual.tobytes() + b"\n")
            else:
                reads.append(b">" + rname.encode() + b"\n" + seq.tobytes()
                             + b"\n")
            if fmt_ovl == "paf":
                q0, q1 = ((len(seg) - qb, len(seg) - qa) if reverse
                          else (qa, qb))
                ovls.append(f"{rname}\t{len(seq)}\t{q0}\t{q1}\t"
                            f"{'-' if reverse else '+'}\t{name}\t"
                            f"{len(draft)}\t{ts}\t{te}\t{b - a}\t"
                            f"{b - a}\t60")
            else:
                ovls.append(sam.sam_line(rname, reverse, name, pos0, cigar))
    paths = {"draft": os.path.join(out_dir, "draft.fasta"),
             "reads": os.path.join(out_dir, f"reads.{fmt_reads}"),
             "overlaps": os.path.join(out_dir, f"overlaps.{fmt_ovl}")}
    with open(paths["draft"], "wb") as f:
        f.write(b"".join(draft_bytes))
    with open(paths["reads"], "wb") as f:
        f.write(b"".join(reads))
    with open(paths["overlaps"], "w") as f:
        if fmt_ovl == "sam":
            f.write("@HD\tVN:1.6\tSO:unsorted\n" + "\n".join(header) + "\n")
        f.write("\n".join(ovls) + "\n")
    return {"paths": [paths["reads"], paths["overlaps"], paths["draft"]],
            "truths": truths,
            "draft_bp": draft_bp,
            "reads": n_read}
