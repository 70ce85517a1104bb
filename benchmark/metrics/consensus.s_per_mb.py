"""Consensus: seconds per Mb of draft polished.

The program's ``generated consensus`` phase: scheduling, device chunk
programs, host fallback and the contigs' assembly."""


def read(ctx):
    v = ctx["registry"].get("phase_seconds_polish_generated_consensus")
    return None if v is None else v / ctx["draft_mb"]
