"""Parse and ingest: seconds per Mb of draft polished.

The program's logger phases for loading the targets, the reads and the
overlaps (``phase_seconds_initialize_loaded_*`` in the metrics
registry), summed over the traced job, per Mb of its draft."""


def read(ctx):
    reg = ctx["registry"]
    secs = [v for k, v in reg.items()
            if k.startswith("phase_seconds_initialize_loaded_")]
    if not secs:
        return None
    return sum(secs) / ctx["draft_mb"]
