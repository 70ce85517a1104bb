"""Overlap alignment: the walk from CIGARs to breaking points, s per Mb.

The program's ``align`` span ``bp_walk``: every overlap's breaking
points, walked along its CIGAR where the device did not already give
them, from ``align_seconds_bp_walk`` in the traced job's metrics
registry, per Mb of its draft. Nothing to read from a program without
this span."""


def read(ctx):
    v = ctx["registry"].get("align_seconds_bp_walk")
    return None if v is None else v / ctx["draft_mb"]
