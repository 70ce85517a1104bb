"""Declared registry of every RACON_TPU_* environment gate.

Every environment read of a ``RACON_TPU_*`` name in racon_tpu/ and
scripts/ resolves through :func:`read` below, and every
entry here carries the doc file that holds its row.  The env-contract
rule in racon_tpu/analysis enforces the triangle in both directions:

  code read  ->  declared spec   (ENV001/ENV002: undeclared reads flag)
  spec       ->  code read       (ENV003: dead declarations flag)
  spec       ->  docs row        (ENV004: undocumented gates flag)
  docs row   ->  spec            (ENV005: documented-but-unread flags)

:func:`read` returns the *raw string* (declared default when unset) —
call sites keep their own parsing so the migration onto the registry is
byte-identical to the pre-registry behaviour.  The ``kind`` tag is
descriptive metadata for the linter and docs, not a parser.
"""

from __future__ import annotations

import os
from typing import Dict, NamedTuple


class EnvSpec(NamedTuple):
    name: str     # full RACON_TPU_* variable name
    default: str  # raw default returned by read() when unset
    kind: str     # "flag" | "int" | "float" | "str" | "path" | "spec"
    doc: str      # docs/*.md file carrying this gate's row
    help: str     # one-line summary (docs row seed)


REGISTRY: Dict[str, EnvSpec] = {}


def declare(name: str, default: str, kind: str, doc: str,
            help: str) -> str:
    """Register one gate; returns the name so modules can bind ENV_*
    constants directly to a declaration."""
    if not name.startswith("RACON_TPU_"):
        raise ValueError(f"[racon_tpu::envspec] not a RACON_TPU_* "
                         f"gate: {name!r}")
    if name in REGISTRY:
        raise ValueError(f"[racon_tpu::envspec] duplicate declaration "
                         f"for {name!r}")
    if kind not in ("flag", "int", "float", "str", "path", "spec"):
        raise ValueError(f"[racon_tpu::envspec] unknown kind {kind!r} "
                         f"for {name!r}")
    REGISTRY[name] = EnvSpec(name, default, kind, doc, help)
    return name


def read(name: str) -> str:
    """Raw environment read through the registry.  Raises KeyError on
    names that were never declared — the runtime counterpart of the
    env-contract lint rule."""
    spec = REGISTRY.get(name)
    if spec is None:
        raise KeyError(f"[racon_tpu::envspec] undeclared env gate "
                       f"{name!r}; declare it in "
                       f"racon_tpu/utils/envspec.py")
    return os.environ.get(name, spec.default)


# --------------------------------------------------------------------
# The registry.  Grouped by doc file; keep alphabetical within groups.
# --------------------------------------------------------------------

# docs/AVA.md — assembly-scale all-vs-all planning
declare("RACON_TPU_AVA_COMPACT", "", "int", "AVA.md",
        "sealed v2 manifest segments between compaction rewrites "
        "(default 64; 0 disables compaction)")
declare("RACON_TPU_AVA_COMPILE_BUDGET", "", "int", "AVA.md",
        "max distinct shape buckets the ava planner may emit; the "
        "bucket quantum doubles until the plan fits (default 8)")
declare("RACON_TPU_AVA_SEG", "", "int", "AVA.md",
        "checkpoint-manifest targets per v2 segment record; unset = "
        "256 for ava runs and 0 (v1 per-target records) for kC")
declare("RACON_TPU_AVA_WEIGHTED", "1", "flag", "AVA.md",
        "length-weighted shard partitioning when target offsets are "
        "published (default on; 0 = count-balanced bounds)")

# docs/CACHE.md — content-addressed result cache
declare("RACON_TPU_CACHE", "", "flag", "CACHE.md",
        "result-cache master gate: on by default for the daemon (the "
        "serial CLI needs --cache-dir); 0/false disables both tiers")
declare("RACON_TPU_CACHE_DIR", "", "path", "CACHE.md",
        "cache root override (daemon default: <state-dir>/cache)")
declare("RACON_TPU_CACHE_MAX_MB", "256", "int", "CACHE.md",
        "job-level CAS byte bound; LRU eviction keeps it under this")
declare("RACON_TPU_CACHE_WINDOWS", "", "flag", "CACHE.md",
        "window memoization gate: on whenever the cache is on; "
        "0/false keeps Tier 1 but disables the in-batcher memo")

# docs/DISTRIBUTED.md — fleet, ledger, autoscaler
declare("RACON_TPU_AUTOSCALE_DEADLINE_S", "", "float", "DISTRIBUTED.md",
        "autoscaler run deadline: give up replacing workers after this")
declare("RACON_TPU_AUTOSCALE_FAULT_PLAN", "", "path", "DISTRIBUTED.md",
        "JSON chaos plan (kill/straggle events) for the autoscaler")
declare("RACON_TPU_AUTOSCALE_INTERVAL_S", "", "float", "DISTRIBUTED.md",
        "supervisor poll interval between scaling decisions")
declare("RACON_TPU_AUTOSCALE_MAX", "", "int", "DISTRIBUTED.md",
        "upper bound on concurrently live autoscaled workers")
declare("RACON_TPU_AUTOSCALE_MAX_SPAWNS", "", "int", "DISTRIBUTED.md",
        "total spawn budget: cap on workers ever launched per run")
declare("RACON_TPU_AUTOSCALE_MIN", "", "int", "DISTRIBUTED.md",
        "lower bound on live workers while open work remains")
declare("RACON_TPU_DIST_AVOID", "", "str", "DISTRIBUTED.md",
        "comma list of shard ids this worker must not claim")
declare("RACON_TPU_DIST_POLL", "", "float", "DISTRIBUTED.md",
        "worker poll interval while waiting for claimable shards")
declare("RACON_TPU_DIST_SHARDS", "", "int", "DISTRIBUTED.md",
        "shard count override for ledger initialisation")
declare("RACON_TPU_SPLIT", "1", "flag", "DISTRIBUTED.md",
        "dynamic shard splitting gate (default on)")
declare("RACON_TPU_SPLIT_AFTER_S", "", "float", "DISTRIBUTED.md",
        "min seconds on one shard before a worker offers a split")
declare("RACON_TPU_SPLIT_DEPTH", "", "int", "DISTRIBUTED.md",
        "max split lineage depth (guards handoff cascades)")

# docs/GATEWAY.md — fleet-serve gateway
declare("RACON_TPU_GATE_FLEET", "0", "flag", "GATEWAY.md",
        "fleet-serve gate: route eligible daemon jobs to an "
        "autoscaled ledger fleet (default off = all jobs in-process)")
declare("RACON_TPU_GATE_FLEET_MIN_BYTES", "8388608", "int", "GATEWAY.md",
        "ava routing size threshold: fragment-correction jobs whose "
        "targets file is at least this many bytes go to the fleet "
        "(target COUNT misprices read-sized targets)")
declare("RACON_TPU_GATE_FLEET_MIN_TARGETS", "32", "int", "GATEWAY.md",
        "routing size threshold: jobs with at least this many target "
        "contigs go to the fleet")
declare("RACON_TPU_GATE_LEASE_S", "10", "float", "GATEWAY.md",
        "gateway lease term; a standby adopts after a primary misses "
        "renewals for this long")
declare("RACON_TPU_GATE_QUEUE_PRESSURE", "8", "int", "GATEWAY.md",
        "queue-pressure override: at this admission-queue depth even "
        "small jobs route to the fleet")
declare("RACON_TPU_GATE_STANDBY_POLL_S", "0.2", "float", "GATEWAY.md",
        "standby gateway lease poll interval")
declare("RACON_TPU_GATE_WORKERS", "2", "int", "GATEWAY.md",
        "fleet size cap per gateway-dispatched job (the autoscale "
        "max the supervisor is started with)")

# docs/INGEST.md — parallel data plane
declare("RACON_TPU_INGEST", "", "flag", "INGEST.md",
        "parallel ingest gate: chunked inflate + mmap readers "
        "(default on; 0/false = serial readers)")
declare("RACON_TPU_INGEST_WORKERS", "", "int", "INGEST.md",
        "inflate worker-pool size override")

# docs/KERNELS.md — device kernels and walk geometry
declare("RACON_TPU_NO_BAND", "", "flag", "KERNELS.md",
        "disable banded DP scoring (full-matrix fallback)")
declare("RACON_TPU_NO_PALLAS", "", "flag", "KERNELS.md",
        "force the XLA twin kernels instead of Pallas")
declare("RACON_TPU_OVL_TILED", "1", "flag", "KERNELS.md",
        "tiled ultralong overlap alignment gate (default on)")
declare("RACON_TPU_REDO", "", "flag", "KERNELS.md",
        "on-device wide-band redo of flagged windows (default on)")
declare("RACON_TPU_WALK_K", "", "int", "KERNELS.md",
        "column-walk chain length k (1, 2, or 4; default 4)")

# docs/OBSERVABILITY.md — tracing, metrics
declare("RACON_TPU_DP_TIMEOUT", "600", "float", "OBSERVABILITY.md",
        "per-point timeout for scripts/dp_scaling_bench.py workers")
declare("RACON_TPU_FLIGHT_EVENTS", "", "int", "OBSERVABILITY.md",
        "flight recorder ring capacity (default 256; 0 disables)")
declare("RACON_TPU_METRICS_PORT", "", "int", "OBSERVABILITY.md",
        "OpenMetrics pull endpoint port (unset = no endpoint)")
declare("RACON_TPU_OBS_DIR", "", "path", "OBSERVABILITY.md",
        "per-worker metrics snapshot directory (fleet obs plane)")
declare("RACON_TPU_OBS_FLUSH_S", "", "float", "OBSERVABILITY.md",
        "metrics snapshot flush interval override")
declare("RACON_TPU_TRACE", "", "path", "OBSERVABILITY.md",
        "span trace output directory (JSONL tracer gate)")
declare("RACON_TPU_TRACE_CTX", "", "str", "OBSERVABILITY.md",
        "inherited trace context handoff (trace_id:parent_span_id)")
declare("RACON_TPU_TRACE_XPROF", "", "flag", "OBSERVABILITY.md",
        "also capture an xprof/jax profiler trace alongside spans")

# docs/PIPELINE.md — streaming executor
declare("RACON_TPU_PIPELINE", "", "flag", "PIPELINE.md",
        "streaming pipeline gate (see pipeline/__init__ truth table)")
declare("RACON_TPU_PIPELINE_DEPTH", "", "int", "PIPELINE.md",
        "bounded-queue capacity per stage edge")
declare("RACON_TPU_WALK_ASYNC", "", "flag", "PIPELINE.md",
        "decoupled walk dispatches (0 forces fused forward+walk)")
declare("RACON_TPU_WALK_QUEUE", "", "int", "PIPELINE.md",
        "in-flight walk-input queue depth (budget-clamped)")

# docs/RESILIENCE.md — faults, retry, watchdog, deadlines
declare("RACON_TPU_DEADLINE_CELLS_PER_S", "", "float", "RESILIENCE.md",
        "dispatch deadline model: DP cells per second floor")
declare("RACON_TPU_DEADLINE_D2H", "", "float", "RESILIENCE.md",
        "fixed device-to-host transfer deadline override")
declare("RACON_TPU_DEADLINE_DISPATCH", "", "float", "RESILIENCE.md",
        "fixed dispatch deadline override")
declare("RACON_TPU_DEADLINE_H2D", "", "float", "RESILIENCE.md",
        "fixed host-to-device transfer deadline override")
declare("RACON_TPU_DEADLINE_MBPS", "", "float", "RESILIENCE.md",
        "transfer deadline model: MB/s floor")
declare("RACON_TPU_DEADLINE_SCALE", "", "float", "RESILIENCE.md",
        "global multiplier on every derived deadline")
declare("RACON_TPU_FAULTS", "", "spec", "RESILIENCE.md",
        "fault-injection spec (site[:action][@n][,...])")
declare("RACON_TPU_FAULT_HANG_S", "", "float", "RESILIENCE.md",
        "injected hang duration for the hang fault action")
declare("RACON_TPU_FAULT_STALL_S", "", "float", "RESILIENCE.md",
        "injected stall duration for the stall fault action")
declare("RACON_TPU_RETRY", "", "spec", "RESILIENCE.md",
        "retry policy overrides (attempts=..,base_s=..,...)")
declare("RACON_TPU_STALL_S", "", "float", "RESILIENCE.md",
        "pipeline stall-detector window override")
declare("RACON_TPU_STRAGGLER_FRAC", "", "float", "RESILIENCE.md",
        "straggler threshold as a fraction of fleet median rate")
declare("RACON_TPU_WATCHDOG_TERMINAL", "", "spec", "RESILIENCE.md",
        "terminal-breach limit (count or count/window_s)")

# docs/SERVER.md — resident daemon and cross-request batcher
declare("RACON_TPU_SERVE_BATCH", "256", "int", "SERVER.md",
        "cross-request batch capacity in windows per dispatch")
declare("RACON_TPU_SERVE_BATCH_WAIT_S", "0.05", "float", "SERVER.md",
        "max staging wait before a partial batch dispatches")
declare("RACON_TPU_SERVE_GRACE_S", "30", "float", "SERVER.md",
        "SIGTERM drain grace: seconds to finish in-flight jobs")
declare("RACON_TPU_SERVE_MAX_JOBS", "4", "int", "SERVER.md",
        "max concurrently running jobs (admission semaphore)")
declare("RACON_TPU_SERVE_QUEUE", "64", "int", "SERVER.md",
        "bounded admission queue depth in work items")
declare("RACON_TPU_SERVE_SPOOL_MB", "", "int", "SERVER.md",
        "in-memory result bytes per job before the stream spills to "
        "the job-directory spool file (default 8 MiB; 0 = never "
        "spill)")

# docs/SCHEDULER.md — shape-bucket scheduler
declare("RACON_TPU_ADAPTIVE", "", "flag", "SCHEDULER.md",
        "adaptive early-exit rounds (converged chunks stop early)")
declare("RACON_TPU_SCHED", "", "flag", "SCHEDULER.md",
        "shape-bucket scheduler gate (default on)")
