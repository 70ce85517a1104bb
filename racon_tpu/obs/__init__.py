"""Unified observability: tracing, metrics, fleet aggregation, export.

- :mod:`racon_tpu.obs.trace` — nested spans (run → phase → chunk →
  round → dispatch → transfer) emitted as JSONL when
  ``RACON_TPU_TRACE=<path>`` (or ``--trace``) is set; a no-op null
  tracer otherwise. Spans carry process-wide context attrs
  (``worker_id``/``shard``/``run_fp``) via ``set_context``.
- :mod:`racon_tpu.obs.metrics` — process-wide counter registry: the
  single source for the polisher's stderr scheduler summary,
  ``SchedTelemetry.as_extras()``, and the benchmark's readers, plus
  h2d/d2h transfer accounting (bytes, seconds, effective bandwidth)
  and dispatch / compile-cache counters. Every key has an explicit
  fleet merge kind (``merge_kind``: sum/max/last).
- :mod:`racon_tpu.obs.fleet` — the multi-process plane: per-worker
  metric shards (``obs/worker_<id>.metrics.jsonl``, atomically
  published, SIGTERM-flushed) and :func:`~racon_tpu.obs.fleet.aggregate`
  merging them with the ledger's ``events.jsonl`` into one fleet model.
- :mod:`racon_tpu.obs.export` — OpenMetrics/Prometheus text renderer
  for registries and fleet models, plus the ``RACON_TPU_METRICS_PORT``
  pull endpoint.

Schema and env vars are documented in docs/OBSERVABILITY.md;
``scripts/obs_report.py`` renders a trace into a per-stage breakdown
and ``scripts/obs_export.py`` emits OpenMetrics.
"""

from racon_tpu.obs.trace import Tracer, NullTracer, get_tracer, configure
from racon_tpu.obs.metrics import (MetricsRegistry, registry, reset,
                                   record_h2d, record_d2h,
                                   transfer_extras, publish_sched,
                                   sched_extras, sched_summary_line,
                                   merge_kind)

__all__ = [
    "Tracer", "NullTracer", "get_tracer", "configure",
    "MetricsRegistry", "registry", "reset",
    "record_h2d", "record_d2h", "transfer_extras",
    "publish_sched", "sched_extras", "sched_summary_line",
    "merge_kind",
]
