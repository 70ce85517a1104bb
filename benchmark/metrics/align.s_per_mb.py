"""Overlap alignment: seconds per Mb of draft polished.

The program's ``aligned overlaps`` phase: device alignment, its native
redo, and the walk from CIGARs to breaking points. Over SAM input it
is the CIGAR walk alone."""


def read(ctx):
    v = ctx["registry"].get("phase_seconds_initialize_aligned_overlaps")
    return None if v is None else v / ctx["draft_mb"]
