"""Overlap alignment: share of the bases to align that the host aligned.

``align_native_bases / align_bases_total`` from the traced job's
metrics registry: query plus target bases the native aligner aligned,
over those of every overlap that needed aligning (no CIGAR). Nothing
to read where no overlap needed aligning (SAM input), or from a
program without these counters."""


def read(ctx):
    reg = ctx["registry"]
    total = reg.get("align_bases_total", 0)
    if not total:
        return None
    return reg.get("align_native_bases", 0) / total
