"""The yardstick that decides ``correct``: polished contigs against truth.

A polished contig is the consensus of the reads that cover it, and the
reads are noisy copies of the truth the generator drew from the seed.
So the plain reference of a polishing job is its truth sequence, and
the comparison is an edit distance: the polished contig is cut into
consecutive blocks, and each block is aligned against the truth from
where the previous block's alignment ended (its start fixed, its end
free), so the blocks together are one alignment of the whole contig
against a prefix of the truth. The truth no block reached is charged
as errors too: a contig that stops short, or is empty, reads as many
errors as the bases it lacks. Each block's end is the shortest prefix
that reaches its least distance, so an error on a block's last base can
be charged once more in the next block: the count never falls below the
contig's edit distance from its truth.

The edit distance is Myers' bit-vector algorithm (Myers 1999, in
Hyyrö's form for a fixed start) over Python integers: a plain
implementation that shares nothing with the program under test.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

BLOCK = 2_000       # bases of polished contig per aligned block
SLOP = 300          # truth bases a block's alignment may run past its length
REPORT_BLOCK = 10_000   # the worst-block number sums blocks to this size


def prefix_distance(pattern: bytes, text: bytes) -> Tuple[int, int]:
    """``(distance, end)``: the least edit distance between ``pattern``
    and any prefix of ``text``, and the end (exclusive) of the shortest
    prefix that reaches it."""
    m = len(pattern)
    if m == 0:
        return 0, 0
    peq: Dict[int, int] = {c: 0 for c in set(text)}
    for i, c in enumerate(pattern):
        peq[c] = peq.get(c, 0) | (1 << i)
    mask = (1 << m) - 1
    high = 1 << (m - 1)
    pv, mv, score = mask, 0, m
    best, best_end = m, 0
    for j, c in enumerate(text):
        eq = peq[c]
        xv = eq | mv
        xh = (((eq & pv) + pv) ^ pv) | eq
        ph = mv | (~(xh | pv) & mask)
        mh = pv & xh
        if ph & high:
            score += 1
        elif mh & high:
            score -= 1
        # The top row counts up along the text: the prefix starts at 0.
        ph = ((ph << 1) | 1) & mask
        mh = (mh << 1) & mask
        pv = mh | (~(xv | ph) & mask)
        mv = ph & xv
        if score < best:
            best, best_end = score, j + 1
    return best, best_end


def block_errors(polished: bytes, truth: bytes) -> List[Tuple[int, int]]:
    """``[(errors, bases), ...]`` for consecutive ``BLOCK``-base blocks
    of ``polished``, each aligned against the truth from where the
    previous block's alignment ended, and last, where the blocks did
    not reach the truth's end, the truth left over, all of it errors."""
    out = []
    t_pos = 0
    for s in range(0, len(polished), BLOCK):
        blk = polished[s:s + BLOCK]
        ed, end = prefix_distance(
            blk, truth[t_pos:t_pos + len(blk) + SLOP])
        out.append((ed, len(blk)))
        t_pos += end
    if t_pos < len(truth):
        out.append((len(truth) - t_pos, len(truth) - t_pos))
    return out


def parse_fasta(data: bytes) -> Dict[str, bytes]:
    """``{name: sequence}`` of a FASTA file, the name being the first
    word of the header; raises ``ValueError`` on a malformed file."""
    recs: Dict[str, List[bytes]] = {}
    cur = None
    for line in data.split(b"\n"):
        if not line:
            continue
        if line.startswith(b">"):
            words = line[1:].split()
            if not words:
                raise ValueError("FASTA header without a name")
            cur = words[0].decode()
            if cur in recs:
                raise ValueError(f"FASTA record {cur} twice")
            recs[cur] = []
        elif cur is None:
            raise ValueError("FASTA data before the first header")
        else:
            recs[cur].append(line)
    return {k: b"".join(v) for k, v in recs.items()}


def compare_job(polished: Dict[str, bytes], truths: Dict[str, bytes]) -> Dict:
    """Errors of one job's polished contigs against their truths.

    Returns the contigs missing from the output (absent, or records
    without bases), the job's errors and the bases they are counted
    over (per contig the longer of the polished contig and its truth),
    and the most errors in any ``REPORT_BLOCK`` bases of one contig
    (consecutive blocks summed), as a percentage."""
    missing = sorted(n for n in truths if not polished.get(n))
    errors = bases = 0
    worst = 0.0
    per = REPORT_BLOCK // BLOCK
    for name, truth in truths.items():
        if name in missing:
            continue
        blocks = block_errors(polished[name], truth)
        errors += sum(e for e, _ in blocks)
        bases += max(len(truth), len(polished[name]))
        for i in range(0, len(blocks), per):
            grp = blocks[i:i + per]
            n = sum(b for _, b in grp)
            if n >= REPORT_BLOCK // 2:
                worst = max(worst, 100.0 * sum(e for e, _ in grp) / n)
    return {"missing": missing, "errors": errors, "bases": bases,
            "worst_block_pct": worst}
