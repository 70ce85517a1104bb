"""Overlap alignment: the native aligner's redo, s per Mb.

The program's ``align`` span ``native_redo``: operands, encoding and
the native aligner over the overlaps the device did not keep, from
``align_seconds_native_redo`` in the traced job's metrics registry, per
Mb of its draft; 0 where the program counted the job's alignment work
(``align_bases_total``) and nothing fell back. Nothing to read from a
program without these counters."""


def read(ctx):
    reg = ctx["registry"]
    if "align_bases_total" not in reg:
        return None
    return reg.get("align_seconds_native_redo", 0.0) / ctx["draft_mb"]
