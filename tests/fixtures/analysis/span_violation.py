"""Seeded span-schema violations (lint fixture — never imported).

SPAN001: an emitted kind the obs_report.py validators don't know.
SPAN002: a known kind emitted without its required attrs, through the
tracer and through obs/metrics.py ``timed_span``.
"""


def run(tracer):
    with tracer.span("ghost_kind", "x"):                  # SPAN001
        pass
    with tracer.span("transfer", "h2d", note=1):          # SPAN002
        pass


def timed(obs_metrics):
    with obs_metrics.timed_span("align", "x"):            # SPAN002
        pass
