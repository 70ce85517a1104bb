"""Overlap alignment: host blocked on the device's results, s per Mb.

The program's ``align`` span ``device_collect``: the host waiting in
each aligned chunk's result pull, then building the breaking points,
from ``align_seconds_device_collect`` in the traced job's metrics
registry, per Mb of its draft; 0 where the program counted the job's
alignment work (``align_bases_total``) and no chunk ran on the device.
Nothing to read from a program without these counters."""


def read(ctx):
    reg = ctx["registry"]
    if "align_bases_total" not in reg:
        return None
    return reg.get("align_seconds_device_collect", 0.0) / ctx["draft_mb"]
