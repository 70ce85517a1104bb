"""Overlap alignment: host work before the device aligns, s per Mb.

The program's ``align`` spans ``revcomp`` (reverse complements),
``device_prep`` (operands, encoding, tile plans, shape buckets) and
``device_dispatch`` (packing and each chunk's dispatch), summed from
``align_seconds_<span>`` in the traced job's metrics registry, per Mb
of its draft. Over SAM input only ``revcomp`` runs. Nothing to read
from a program without these spans."""

SPANS = ("revcomp", "device_prep", "device_dispatch")


def read(ctx):
    reg = ctx["registry"]
    secs = [reg[k] for k in (f"align_seconds_{s}" for s in SPANS) if k in reg]
    return sum(secs) / ctx["draft_mb"] if secs else None
