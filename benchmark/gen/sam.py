"""SAM records with the CIGAR of a read against the draft.

The generator knows where every read base and every draft base came
from in the truth, so the alignment of a read against the draft is
composed through the truth coordinates, one truth base at a time:

- the base itself: kept in both (``M``), only in the read (``I``), only
  in the draft (``D``), or in neither (no operation);
- a base inserted after it: in both (``M``, usually a mismatch), only
  in the read (``I``) or only in the draft (``D``).

Insertions at the two ends become soft clips and deletions there move
the start, so the CIGAR begins and ends with a match, as an aligner
reports it. The CIGAR is in the draft's orientation: for a
reverse-strand read it is the alignment of the read's reverse
complement, as SAM's flag 16 states.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

_M, _I, _D = 3, 1, 2   # code = in_read * 1 + in_draft * 2
_CHAR = np.zeros(4, dtype="<U1")
_CHAR[_M], _CHAR[_I], _CHAR[_D] = "M", "I", "D"


def compose_ops(r_keep, r_ins, d_keep, d_ins) -> np.ndarray:
    """Alignment operations (``_M``/``_I``/``_D`` codes) of the read
    against the draft over one truth range, from each side's masks of
    kept bases and of bases followed by an insertion."""
    prim = r_keep.astype(np.int8) + 2 * d_keep.astype(np.int8)
    ins = r_ins.astype(np.int8) + 2 * d_ins.astype(np.int8)
    ops = np.stack([prim, ins], axis=1).ravel()
    return ops[ops != 0]


def compose_cigar(r_keep, r_ins, d_keep, d_ins, draft_start: int,
                  clip_l: int = 0, clip_r: int = 0) -> Tuple[int, str]:
    """``(pos0, cigar)``: the 0-based draft position of the first
    aligned draft base, and the CIGAR string, for a read over a truth
    range whose first base starts at ``draft_start`` in the draft.
    ``clip_l`` and ``clip_r`` read bases lie outside the range (a read
    that runs past the contig's ends) and are soft-clipped."""
    ops = compose_ops(r_keep, r_ins, d_keep, d_ins)
    match = np.flatnonzero(ops == _M)
    if len(match) == 0:
        raise ValueError("read shares no base with the draft")
    first, last = int(match[0]), int(match[-1])
    head, tail = ops[:first], ops[last + 1:]
    pos0 = draft_start + int((head == _D).sum())
    clip_l += int((head == _I).sum())
    clip_r += int((tail == _I).sum())
    core = ops[first:last + 1]
    cut = np.flatnonzero(np.diff(core)) + 1
    starts = np.concatenate([[0], cut])
    lens = np.diff(np.concatenate([starts, [len(core)]]))
    body = "".join(np.char.add(lens.astype("U"),
                               _CHAR[core[starts]]).tolist())
    cigar = (f"{clip_l}S" if clip_l else "") + body + \
        (f"{clip_r}S" if clip_r else "")
    return pos0, cigar


def sam_line(qname: str, reverse: bool, rname: str, pos0: int,
             cigar: str) -> str:
    """One SAM record; the read's bases and qualities live in the
    reads file, so SEQ and QUAL are ``*``."""
    flag = 16 if reverse else 0
    return f"{qname}\t{flag}\t{rname}\t{pos0 + 1}\t60\t{cigar}\t*\t0\t0\t*\t*"
