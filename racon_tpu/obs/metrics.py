"""Process-wide metrics registry — the single reporting source.

Every consumer reads this one registry: the polisher's stderr
scheduler summary (utils/logger.py), the scheduler's
``SchedTelemetry.as_extras()``, the snapshot the CLI writes into the
trace on exit, the fleet aggregator and OpenMetrics exporter, and the
benchmark's per-layer readers (benchmark/metrics/).
:func:`publish_sched` writes the canonical ``sched_*`` keys, :func:`sched_summary_line` formats the
stderr line from them, and :func:`transfer_extras` derives the h2d/d2h
byte / second / effective-bandwidth numbers recorded at the transfer
choke points (parallel/dispatch.py, ops/device_poa.py,
sched/scheduler.py).

Counter conventions (docs/OBSERVABILITY.md documents the full set):

- ``h2d_bytes`` / ``h2d_s`` / ``h2d_transfers`` — bytes shipped to the
  device, wall seconds of the ``device_put`` calls, call count.
  device_put is asynchronous, so ``h2d_s`` measures the synchronous
  (serialization + enqueue) portion — a lower bound on true transfer
  time, so the derived ``h2d_mb_per_s`` is an upper bound on the
  link's bandwidth.
- ``d2h_bytes`` / ``d2h_s`` / ``d2h_transfers`` — device pulls
  (``np.asarray`` on device values). A pull blocks until any residual
  compute drains, so ``d2h_s`` is "time blocked pulling results" and
  ``d2h_mb_per_s`` is a
  lower bound on link bandwidth.
- ``sched_flag_pulls`` / ``sched_flag_pull_s`` — the scheduler's
  per-round convergence-flag pulls. These sync on compute, so their
  time is accounted separately and never enters the bandwidth estimate.
- ``device_dispatches`` — jitted chunk/round executions launched.
- ``jax_cache_entries_start`` / ``jax_cache_entries_added`` — persistent
  compile-cache population at enable time and entries added since
  (= compiles this process paid; 0 on a fully warm cache), from
  utils/jaxcache.py.

No device syncs anywhere: every value rides on host data the pipeline
already had in hand.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Optional

from racon_tpu.obs import flightrec as _flightrec
from racon_tpu.obs import trace as _trace


class MetricsRegistry:
    """Flat name -> value store: numeric counters plus JSON-ready
    structured values (lists/dicts). Keys starting with ``_`` are
    internal and excluded from snapshots. Mutations of the process
    registry additionally land in the flight-recorder ring
    (obs/flightrec.py) so a crash dump shows the final metric deltas;
    scratch registries stay out of the ring."""

    def __init__(self):
        self._lock = threading.Lock()
        self._v: Dict[str, object] = {}   # guarded-by: _lock

    def _flight(self, key: str, value) -> None:
        if self is _REGISTRY:
            _flightrec.note_metric(key, value)

    def inc(self, key: str, value: float = 1) -> None:
        with self._lock:
            self._v[key] = self._v.get(key, 0) + value
        self._flight(key, value)

    def set(self, key: str, value: object) -> None:
        with self._lock:
            self._v[key] = value
        self._flight(key, value)

    def max(self, key: str, value: float) -> None:
        """Keep the running maximum (gauge peaks, e.g. queue depth)."""
        with self._lock:
            cur = self._v.get(key)
            if cur is None or value > cur:
                self._v[key] = value
        self._flight(key, value)

    def apply(self, fn) -> None:
        """Run ``fn(values_dict)`` under the registry lock — the single
        mutation point for multi-key read-modify-write updates. A
        recorder that composes ``get``/``inc``/``set`` instead takes
        and releases the lock per call, and two pipeline/streaming
        threads interleaving between those calls drop updates or
        publish a ratio computed from mismatched numerator/denominator
        reads."""
        with self._lock:
            fn(self._v)

    def get(self, key: str, default: object = 0) -> object:
        with self._lock:
            return self._v.get(key, default)

    def reset(self) -> None:
        with self._lock:
            self._v.clear()

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {k: v for k, v in self._v.items()
                    if not k.startswith("_")}


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def reset() -> None:
    _REGISTRY.reset()


# ------------------------------------------------------------ histograms

#: Fixed-bucket latency histograms: family name -> ascending log-spaced
#: upper bucket bounds (seconds, ``le`` semantics; one implicit +Inf
#: overflow bucket rides at the end). The set of families IS the
#: histogram registry: merge_kind() answers ``hist`` for exactly these
#: keys, METRIC_SPECS carries one MERGE_HIST row per family, the
#: OpenMetrics exporter renders each as a ``_bucket``/``_sum``/
#: ``_count`` family, and the HIS001 lint rule keeps all of that
#: consistent with the record_hist() call sites.
HIST_BUCKETS = {
    "dispatch_round_s": (0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                         0.5, 1.0, 2.5, 5.0, 10.0),
    "h2d_transfer_s": (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5),
    "serve_job_latency_s": (0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                            5.0, 10.0, 25.0, 60.0, 120.0),
    "serve_queue_wait_s": (0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                           1.0, 2.5, 5.0, 10.0, 30.0),
    "walk_hidden_s": (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                      0.1, 0.25, 0.5, 1.0, 2.5),
}


def record_hist(name: str, value: float,
                reg: Optional[MetricsRegistry] = None) -> None:
    """Record one observation into the fixed-bucket histogram ``name``
    (a :data:`HIST_BUCKETS` family). The registry value is a dict
    ``{"buckets": [c0, ..., cN, overflow], "sum": s, "count": n}``
    with non-cumulative per-bucket counts — per-bucket SUM is the fleet
    merge, and the exporter derives the cumulative ``le`` series."""
    reg = reg if reg is not None else _REGISTRY
    bounds = HIST_BUCKETS[name]
    value = float(value)

    def _mutate(v):
        h = v.get(name)
        if h is None:
            h = v[name] = {"buckets": [0] * (len(bounds) + 1),
                           "sum": 0.0, "count": 0}
        idx = len(bounds)
        for i, bound in enumerate(bounds):
            if value <= bound:
                idx = i
                break
        h["buckets"][idx] += 1
        h["sum"] = round(h["sum"] + value, 6)
        h["count"] += 1

    reg.apply(_mutate)
    if reg is _REGISTRY:
        _flightrec.note_metric(name, round(value, 6))


def hist_quantile(hist: Dict, q: float, bounds) -> float:
    """The q-quantile (0..1) estimated from a histogram dict by linear
    interpolation inside the landing bucket; the overflow bucket clamps
    to the last finite bound. 0.0 on an empty histogram."""
    count = int(hist.get("count", 0))
    if count <= 0:
        return 0.0
    target = q * count
    seen = 0
    lo = 0.0
    for i, c in enumerate(hist["buckets"]):
        hi = float(bounds[i]) if i < len(bounds) else float(bounds[-1])
        if c and seen + c >= target:
            frac = (target - seen) / c
            return round(lo + (hi - lo) * min(max(frac, 0.0), 1.0), 6)
        seen += c
        lo = hi
    return round(float(bounds[-1]), 6)


def hist_percentiles(name: str,
                     reg: Optional[MetricsRegistry] = None
                     ) -> Dict[str, float]:
    """``{name_p50, name_p95, name_p99}`` from the recorded buckets;
    empty when the family has no observations."""
    reg = reg if reg is not None else _REGISTRY
    h = reg.get(name, None)
    if not isinstance(h, dict) or not h.get("count"):
        return {}
    bounds = HIST_BUCKETS[name]
    return {f"{name}_p{p}": hist_quantile(h, p / 100.0, bounds)
            for p in (50, 95, 99)}


# ------------------------------------------------------------- transfers

def record_h2d(nbytes: int, seconds: float,
               reg: Optional[MetricsRegistry] = None,
               name: str = "h2d") -> None:
    """Account one host-to-device transfer (and trace it when tracing
    is on)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("h2d_bytes", int(nbytes))
    reg.inc("h2d_s", float(seconds))
    reg.inc("h2d_transfers")
    record_hist("h2d_transfer_s", float(seconds), reg)
    _trace.get_tracer().point("transfer", name, dur_s=float(seconds),
                              bytes=int(nbytes), dir="h2d")


def record_d2h(nbytes: int, seconds: float,
               reg: Optional[MetricsRegistry] = None,
               name: str = "d2h") -> None:
    """Account one device-to-host pull whose value was already computed
    (so ``seconds`` measures transfer, not compute wait)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("d2h_bytes", int(nbytes))
    reg.inc("d2h_s", float(seconds))
    reg.inc("d2h_transfers")
    _trace.get_tracer().point("transfer", name, dur_s=float(seconds),
                              bytes=int(nbytes), dir="d2h")


def record_flag_pull(nbytes: int, seconds: float,
                     reg: Optional[MetricsRegistry] = None) -> None:
    """The scheduler's per-round flag pull: a sync point, so its time
    includes compute wait and stays out of the bandwidth estimate."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("sched_flag_pulls")
    reg.inc("sched_flag_pull_s", float(seconds))


def transfer_extras(reg: Optional[MetricsRegistry] = None
                    ) -> Dict[str, object]:
    """Derived transfer numbers for reports."""
    reg = reg if reg is not None else _REGISTRY
    out: Dict[str, object] = {}
    for d in ("h2d", "d2h"):
        b = int(reg.get(f"{d}_bytes", 0))
        s = float(reg.get(f"{d}_s", 0.0))
        n = int(reg.get(f"{d}_transfers", 0))
        if not n:
            continue
        out[f"{d}_bytes"] = b
        out[f"{d}_s"] = round(s, 4)
        out[f"{d}_transfers"] = n
        if s > 0:
            out[f"{d}_mb_per_s"] = round(b / s / 1e6, 3)
    n = int(reg.get("sched_flag_pulls", 0))
    if n:
        out["sched_flag_pulls"] = n
        out["sched_flag_pull_s"] = round(
            float(reg.get("sched_flag_pull_s", 0.0)), 4)
    n = int(reg.get("device_dispatches", 0))
    if n:
        out["device_dispatches"] = n
    return out


# ----------------------------------------------------------- resilience

def _site_key(site: str) -> str:
    """Metric-key slug for a call-site name ("h2d/chunk" -> "h2d_chunk")."""
    return site.replace("/", "_").replace(".", "_")


def record_retry(site: str, attempt: int, delay_s: float, error: str,
                 injected: bool,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account one retried attempt at a resilience-wrapped call site
    (racon_tpu/resilience/retry.py) and trace it as a ``retry`` span."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("res_retry_total")
    reg.inc(f"res_retry_site_{_site_key(site)}")
    reg.inc("res_retry_backoff_s", float(delay_s))
    _trace.get_tracer().point("retry", site, attempt=int(attempt),
                              error=error, injected=int(bool(injected)))


def record_retry_exhausted(site: str, attempts: int,
                           reg: Optional[MetricsRegistry] = None) -> None:
    """A retry loop gave up; the caller degrades or aborts."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("res_retry_exhausted")
    _trace.get_tracer().point("retry", f"{site}/exhausted",
                              attempt=int(attempts), error="exhausted",
                              injected=0)


def record_fault(site: str, index: int, action: str,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account one injected fault (racon_tpu/resilience/faults.py)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("res_fault_injected_total")
    reg.inc(f"res_fault_site_{_site_key(site)}")
    _trace.get_tracer().point("fault", site, index=int(index),
                              action=action)


def record_watchdog_breach(site: str, deadline_s: float, waited_s: float,
                           terminal: bool = False,
                           reg: Optional[MetricsRegistry] = None) -> None:
    """Account one fail-slow deadline breach
    (racon_tpu/resilience/watchdog.py) and trace it as a ``watchdog``
    span; terminal breaches (the self-eviction trigger) additionally
    bump ``res_watchdog_terminal_total``."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("res_watchdog_breach_total")
    reg.inc(f"res_watchdog_site_{_site_key(site)}")
    if terminal:
        reg.inc("res_watchdog_terminal_total")
    _flightrec.note_breach(site, deadline_s, waited_s, terminal)
    _trace.get_tracer().point("watchdog", site, dur_s=float(waited_s),
                              deadline_s=float(deadline_s),
                              waited_s=round(float(waited_s), 6),
                              terminal=int(bool(terminal)))


def record_stall(window_s: float, n_stages: int,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account one pipeline stall-detector firing (no stage progressed
    for a full window) and trace it as a ``stall`` span."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("pipe_stall_events")
    _trace.get_tracer().point("stall", "pipeline",
                              window_s=float(window_s),
                              stages=int(n_stages))


def record_degraded(n_windows: int,
                    reg: Optional[MetricsRegistry] = None) -> None:
    """A chunk exhausted its retries and its windows were re-polished
    on the host-fallback consensus path."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("res_degraded_chunks")
    reg.inc("res_degraded_windows", int(n_windows))


def record_ckpt(event: str, tid: int, nbytes: int,
                reg: Optional[MetricsRegistry] = None) -> None:
    """Account one checkpoint event: ``commit`` (contig durably
    retired), ``skip`` (resume re-emitted a committed contig), or
    ``resume`` (store opened with N committed contigs in ``tid``)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc(f"res_ckpt_{event}s" if event != "resume" else
            "res_ckpt_resumes")
    if event == "commit":
        reg.inc("res_ckpt_bytes", int(nbytes))
    _trace.get_tracer().point("checkpoint", event, tid=int(tid),
                              bytes=int(nbytes))


# ----------------------------------------------------- distributed work

def record_dist(event: str, shard, worker, value: float = 1,
                reg: Optional[MetricsRegistry] = None, **attrs) -> None:
    """Account one distributed-ledger event (racon_tpu/distributed/):
    ``claims`` / ``shards_stolen`` / ``leases_expired`` /
    ``lease_renewals`` / ``leases_lost`` / ``contigs_polished`` /
    ``contigs_repolished`` / ``contigs_resumed`` /
    ``shards_completed`` / ``steal_latency_s`` / ``recovery_wall_s`` /
    ``merges`` — each lands as the counter
    ``dist_<event>`` (incremented by ``value``) plus a ``dist`` trace
    span carrying the shard id and worker identity. ``shard`` is -1 for
    run-level events (merge)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc(f"dist_{event}", value)
    _trace.get_tracer().point("dist", event, shard=int(shard),
                              worker=str(worker), **attrs)


def set_dist(key: str, value: object,
             reg: Optional[MetricsRegistry] = None) -> None:
    """Set a distributed gauge (``dist_workers``, ``dist_shards``,
    ``dist_n_targets`` — fleet shape, not counters)."""
    reg = reg if reg is not None else _REGISTRY
    reg.set(f"dist_{key}", value)


# ------------------------------------------- overlap-alignment counters

def record_ovl(device_jobs: int, native_jobs: int, tiles: int,
               reg: Optional[MetricsRegistry] = None) -> None:
    """Account one device_breaking_points batch (ops/ovl_align.py):
    ``device_jobs`` overlaps whose breaking points the device produced
    (untiled + tiled, minus uncertified), ``native_jobs`` overlaps
    routed to the native aligner (over budget OR uncertified), and
    ``tiles`` query-axis tiles executed by the tiled ultralong path.
    ``ovl_device_fraction`` is the running device share — the headline
    number for ROADMAP item 3 (it was pinned ~0 for ultralong inputs
    before the tiled path existed)."""
    reg = reg if reg is not None else _REGISTRY

    def _mutate(v):
        # One lock for the whole read-modify-write: the device fraction
        # must be derived from the same totals its increments produced,
        # and ovl batches land concurrently from pipeline stage threads.
        v["ovl_device_jobs"] = v.get("ovl_device_jobs", 0) + int(device_jobs)
        v["ovl_native_jobs"] = v.get("ovl_native_jobs", 0) + int(native_jobs)
        v["ovl_tiles_exec"] = v.get("ovl_tiles_exec", 0) + int(tiles)
        total = v["ovl_device_jobs"] + v["ovl_native_jobs"]
        if total > 0:
            v["ovl_device_fraction"] = round(
                v["ovl_device_jobs"] / total, 4)

    reg.apply(_mutate)


@contextlib.contextmanager
def timed_span(kind: str, name: str, **attrs):
    """A tracer span that also times itself into the registry.

    Opens ``get_tracer().span(kind, name, **attrs)`` (a no-op with
    tracing off, a profiler annotation under ``RACON_TPU_TRACE_XPROF``)
    and, on exit, adds its wall seconds to ``<kind>_seconds_<name>``
    whether tracing is on or not. Yields the span, so a caller can
    ``.add()`` attrs it learns inside."""
    t0 = time.perf_counter()
    with _trace.get_tracer().span(kind, name, **attrs) as sp:
        try:
            yield sp
        finally:
            _REGISTRY.inc(f"{kind}_seconds_{name}",
                          time.perf_counter() - t0)


# --------------------------------------------------- wide-band redo

def record_redo(device_windows: int, host_windows: int,
                reg: Optional[MetricsRegistry] = None) -> None:
    """Account one wide-band redo pass (ops/redo.py): ``device_windows``
    flagged windows the on-device second pass resolved,
    ``host_windows`` windows still unresolved after it (saturation
    class, or certificate failure at the widened band) that fall back
    to the host consensus. Zero host windows on the redo smoke's
    windows is the acceptance criterion it pins."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("redo_passes")
    reg.inc("redo_device_windows", int(device_windows))
    reg.inc("redo_host_windows", int(host_windows))


# ------------------------------------------------------- ingest plane

def record_ingest_inflate(mode: str, bytes_in: int, bytes_out: int,
                          seconds: float, blocks: int,
                          reg: Optional[MetricsRegistry] = None) -> None:
    """Account one gzip source's inflate totals (io/inflate.py, called
    once when the source finishes): the inflate plan (``bgzf`` /
    ``members`` / ``stream``), compressed bytes consumed, decompressed
    bytes produced, summed worker-pool inflate seconds (may exceed wall
    on the parallel paths — that is the point), and blocks/members
    inflated. Emits one ``ingest`` trace span per source."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("ingest_bytes_in", int(bytes_in))
    reg.inc("ingest_bytes_out", int(bytes_out))
    reg.inc("ingest_inflate_s", float(seconds))
    reg.inc("ingest_blocks", int(blocks))
    _trace.get_tracer().point("ingest", f"inflate/{mode}",
                              dur_s=float(seconds), mode=mode,
                              bytes=int(bytes_out), blocks=int(blocks))


def record_ingest_parse(mode: str, seconds: float, records: int,
                        raw_bytes: int,
                        reg: Optional[MetricsRegistry] = None) -> None:
    """Account one file's parse totals: the reader plan (``indexed`` /
    ``serial`` / ``prefetch``), seconds spent turning bytes into
    records (on the prefetch thread when overlapped, inline otherwise),
    records produced, and raw (decompressed) bytes consumed. Emits one
    ``ingest`` trace span per file."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("ingest_parse_s", float(seconds))
    reg.inc("ingest_records", int(records))
    reg.inc("ingest_raw_bytes", int(raw_bytes))
    _trace.get_tracer().point("ingest", f"parse/{mode}",
                              dur_s=float(seconds), mode=mode,
                              bytes=int(raw_bytes), records=int(records))


def record_ingest_wait(seconds: float,
                       reg: Optional[MetricsRegistry] = None) -> None:
    """Account consumer time blocked on ingest — the ONLY ingest term
    on the run's critical path when prefetch overlaps. Serial (non-
    prefetch) ingest books its whole parse wall here."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("ingest_wait_s", float(seconds))


def set_ingest_fraction(wall_s: float,
                        reg: Optional[MetricsRegistry] = None) -> None:
    """Derive and set the ``ingest_fraction_of_wall`` gauge = critical-
    path ingest wait / total run wall (cli.py, end of run). A fraction
    near 0 with nonzero ingest_parse_s means the overlap worked."""
    reg = reg if reg is not None else _REGISTRY
    if wall_s > 0:
        wait = float(reg.get("ingest_wait_s", 0.0))
        reg.set("ingest_fraction_of_wall", round(wait / wall_s, 4))


# ------------------------------------------------------ pipeline gauges

def record_stage(name: str, busy_s: float, stall_in_s: float,
                 stall_out_s: float, items: int,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account one pipeline stage's lifetime totals (called when the
    stage thread exits; racon_tpu/pipeline/stages.py). ``busy`` is time
    in the stage's work function, ``stall`` time blocked on its input
    (starved) or output (choked) queue — together they say which stage
    bounds the pipeline. ``pipe_stage_compute_busy_s`` doubles as the
    device-busy term of the overlap-efficiency ratio."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc(f"pipe_stage_{name}_busy_s", float(busy_s))
    reg.inc(f"pipe_stage_{name}_stall_in_s", float(stall_in_s))
    reg.inc(f"pipe_stage_{name}_stall_out_s", float(stall_out_s))
    reg.inc(f"pipe_stage_{name}_items", int(items))


def record_queue(name: str, peak: int, put_wait_s: float,
                 get_wait_s: float,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account one bounded queue's gauges (peak depth is a max across
    pipeline runs, blocked times accumulate)."""
    reg = reg if reg is not None else _REGISTRY
    reg.max(f"pipe_queue_{name}_peak", int(peak))
    reg.inc(f"pipe_queue_{name}_put_wait_s", float(put_wait_s))
    reg.inc(f"pipe_queue_{name}_get_wait_s", float(get_wait_s))


def record_pipeline_wall(seconds: float,
                         reg: Optional[MetricsRegistry] = None) -> None:
    """Account one stream_consensus invocation's wall time — the
    denominator of overlap efficiency."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("pipe_runs")
    reg.inc("pipe_wall_s", float(seconds))


def pipeline_extras(reg: Optional[MetricsRegistry] = None
                    ) -> Dict[str, object]:
    """The registry's pipe_* keys as a JSON-ready dict (folded into
    the CLI's exit snapshot / obs_report "Pipeline" section), plus the derived overlap efficiency
    = device-busy (compute stage) / pipeline wall. Empty when no
    pipeline ran."""
    reg = reg if reg is not None else _REGISTRY
    if not int(reg.get("pipe_runs", 0)):
        return {}
    out: Dict[str, object] = {}
    for k, v in sorted(reg.snapshot().items()):
        if k.startswith("pipe_"):
            out[k] = round(v, 4) if isinstance(v, float) else v
    wall = float(reg.get("pipe_wall_s", 0.0))
    busy = float(reg.get("pipe_stage_compute_busy_s", 0.0))
    if wall > 0:
        out["pipe_overlap_efficiency"] = round(busy / wall, 4)
    return out


# -------------------------------------------- decoupled-walk telemetry


def record_walk(walk_s: float, overlap_s: float, dispatches: int,
                fused_chunks: int, queue_peak: int, enabled: bool,
                reg: Optional[MetricsRegistry] = None) -> None:
    """Account one stream_consensus invocation's decoupled-walk
    telemetry (pipeline/streaming.py walk stage):

    - ``walk_s``       seconds spent inside walk dispatches (the walk
      stage's synchronized dispatch+collect window);
    - ``overlap_s``    the portion of that during which at least one
      OTHER chunk's forward dispatch was in flight — the latency the
      decoupling actually hid;
    - ``dispatches``   decoupled walk dispatches issued;
    - ``fused_chunks`` chunks that took the fused fallback;
    - ``queue_peak``   peak depth of the in-flight walk-input queue;
    - ``enabled``      whether the decoupled path was active at all.

    The derived ``walk_hidden_fraction`` = overlap / walk seconds is
    the walk overlap smoke's headline."""
    reg = reg if reg is not None else _REGISTRY
    reg.set("walk_async_enabled", int(bool(enabled)))
    reg.inc("walk_seconds", float(walk_s))
    reg.inc("walk_overlap_s", float(overlap_s))
    reg.inc("walk_dispatches", int(dispatches))
    reg.inc("walk_fused_chunks", int(fused_chunks))
    reg.max("walk_queue_peak", int(queue_peak))
    if dispatches:
        record_hist("walk_hidden_s", float(overlap_s), reg)
    total = float(reg.get("walk_seconds", 0.0))
    if total > 0:
        reg.set("walk_hidden_fraction",
                round(float(reg.get("walk_overlap_s", 0.0)) / total, 4))


def walk_extras(reg: Optional[MetricsRegistry] = None
                ) -> Dict[str, object]:
    """The registry's walk_* keys as a JSON-ready dict (folded into
    the CLI's exit snapshot). Empty when no streaming run recorded walk
    telemetry (record_walk never ran)."""
    reg = reg if reg is not None else _REGISTRY
    if reg.get("walk_async_enabled", None) is None:
        return {}
    out: Dict[str, object] = {}
    for k, v in sorted(reg.snapshot().items()):
        if k.startswith("walk_"):
            out[k] = round(v, 4) if isinstance(v, float) else v
    return out


# ---------------------------------------------------------- serve plane

def record_serve_job(event: str, job: str, tenant: str,
                     trace_id: str = "-", parent_id: int = 0,
                     reg: Optional[MetricsRegistry] = None) -> int:
    """Account one daemon job-lifecycle event (racon_tpu/server/):
    ``submitted`` / ``completed`` / ``failed`` / ``cancelled`` /
    ``resumed`` — each lands as the counter ``serve_jobs_<event>``
    plus a ``serve`` trace span carrying the job id, tenant, and the
    job's trace context (``"-"``/0 when the caller has none, e.g. a
    test driving the batcher directly). Returns the span id — the
    ``submitted`` span is the root the daemon mints the job's
    :class:`~racon_tpu.obs.trace.TraceContext` from."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc(f"serve_jobs_{event}")
    return _trace.get_tracer().point("serve", event, job=str(job),
                                     tenant=str(tenant),
                                     trace_id=str(trace_id),
                                     parent_id=int(parent_id))


def record_serve_batch(n_windows: int, capacity: int, jobs, tenants,
                       wait_s: float, round_s: float = 0.0,
                       trace_ids=(), parent_ids=(),
                       reg: Optional[MetricsRegistry] = None) -> None:
    """Account one cross-request batch dispatch
    (racon_tpu/server/batch.py): windows carried, the jobs/tenants that
    contributed, the summed staging wait its items paid, the dispatch
    round's wall (``dispatch_round_s`` histogram), and the trace
    contexts riding the batched items (comma-joined into the span's
    ``trace_id`` so a mixed batch appears in every contributing job's
    timeline). The derived ``serve_batch_occupancy`` gauge — mean
    windows per dispatch over the bucket capacity — is the headline:
    strictly higher under concurrent jobs than one-at-a-time is the
    server smoke's acceptance gate. Stamps ``serve_rate_wall_s`` so
    readers can tell a live gauge from one re-served forever after the
    final dispatch."""
    reg = reg if reg is not None else _REGISTRY
    cap = max(int(capacity), 1)

    def _mutate(v):
        # One lock for the whole read-modify-write: the occupancy ratio
        # must be derived from the same totals its increments produced.
        v["serve_batches"] = v.get("serve_batches", 0) + 1
        v["serve_batch_windows"] = \
            v.get("serve_batch_windows", 0) + int(n_windows)
        v["serve_tenant_wait_s"] = \
            v.get("serve_tenant_wait_s", 0.0) + float(wait_s)
        v["serve_batch_occupancy"] = round(
            v["serve_batch_windows"] / (v["serve_batches"] * cap), 4)
        v["serve_rate_wall_s"] = round(time.time(), 3)

    reg.apply(_mutate)
    if round_s > 0:
        record_hist("dispatch_round_s", float(round_s), reg)
    tid = ",".join(sorted({str(t) for t in trace_ids if t})) or "-"
    pid = int(next(iter(parent_ids), 0))
    _trace.get_tracer().point("serve", "batch",
                              job=",".join(str(j) for j in jobs),
                              tenant=",".join(str(t) for t in tenants),
                              windows=int(n_windows), capacity=cap,
                              wait_s=round(float(wait_s), 6),
                              trace_id=tid, parent_id=pid)


def set_serve_active(n: int,
                     reg: Optional[MetricsRegistry] = None) -> None:
    """Set the daemon's in-flight job gauge (submitted or running,
    not yet terminal)."""
    reg = reg if reg is not None else _REGISTRY
    reg.set("serve_active_jobs", int(n))


def set_serve_rate(jobs_per_min: float,
                   reg: Optional[MetricsRegistry] = None) -> None:
    """Set the daemon's completion-rate gauge (completed jobs over
    daemon uptime minutes; recomputed at each completion) plus the
    ``serve_rate_wall_s`` freshness stamp: MERGE_LAST gauges re-serve
    their final value forever, so obs_report flags them stale once the
    stamp trails the trace end by more than 5x the flush cadence."""
    reg = reg if reg is not None else _REGISTRY

    def _mutate(v):
        v["serve_jobs_per_min"] = round(float(jobs_per_min), 4)
        v["serve_rate_wall_s"] = round(time.time(), 3)

    reg.apply(_mutate)


# ------------------------------------------------------------ gate plane

#: Gateway lifecycle events -> the counter each lands in. The mapping
#: IS the gate counter registry: record_gate refuses unknown events,
#: so a typo'd call site fails loudly instead of minting an
#: undeclared key.
_GATE_EVENT_KEYS = {
    "route_fleet": "gate_routed_fleet",
    "route_local": "gate_routed_local",
    "adopt": "gate_adoptions",
    "fleet_run": "gate_fleet_runs",
}


def record_gate(event: str, job: str, tenant: str,
                trace_id: str = "-", parent_id: int = 0,
                reg: Optional[MetricsRegistry] = None,
                wall_s: Optional[float] = None, **attrs) -> int:
    """Account one gateway event (racon_tpu/gateway/, docs/GATEWAY.md):
    ``route_fleet`` / ``route_local`` — the dispatch decision for an
    accepted job; ``adopt`` — a standby gateway fenced a dead primary
    and took over its journal; ``fleet_run`` — one fleet execution
    finished streaming back (its ``wall_s`` accumulates into
    ``gate_fleet_wall_s``). Each event is a counter bump plus a
    ``gate`` trace span carrying the job's trace context, so the
    per-job timeline shows the routing decision between the daemon's
    ``serve`` spans and the fleet's worker spans. Returns the span
    id."""
    reg = reg if reg is not None else _REGISTRY
    try:
        key = _GATE_EVENT_KEYS[event]
    except KeyError:
        raise ValueError(f"[racon_tpu::metrics] unknown gate event "
                         f"{event!r}") from None
    reg.inc(key)
    if wall_s is not None:
        reg.inc("gate_fleet_wall_s", float(wall_s))
        attrs["wall_s"] = round(float(wall_s), 6)
    return _trace.get_tracer().point("gate", event, job=str(job),
                                     tenant=str(tenant),
                                     trace_id=str(trace_id),
                                     parent_id=int(parent_id), **attrs)


def set_gate_fleet_target(n: int,
                          reg: Optional[MetricsRegistry] = None) -> None:
    """Set the gateway's fleet sizing gauge — the worker target the
    service policy (gateway/policy.py) chose on its latest supervisor
    tick."""
    reg = reg if reg is not None else _REGISTRY
    reg.set("gate_fleet_target", int(n))


# ------------------------------------------------------------ ava plane


def record_ava_plan(plan,
                    reg: Optional[MetricsRegistry] = None) -> None:
    """Publish the ava shape-bucket plan (racon_tpu/ava/planner.py,
    docs/AVA.md): target count, bucket count vs the compile budget,
    the quantum the budget loop settled on, and the padding overhead
    it cost. All gauges — every worker computes the identical plan
    from the published offsets, so the fleet merge takes the last."""
    reg = reg if reg is not None else _REGISTRY
    reg.set("ava_targets", int(plan.n_targets))
    reg.set("ava_buckets", int(plan.n_buckets))
    reg.set("ava_quantum", int(plan.quantum))
    reg.set("ava_compile_budget", int(plan.budget))
    reg.set("ava_pad_frac", round(float(plan.pad_frac), 4))


# --------------------------------------------------- result cache plane


def record_cache(tier: str, outcome: str, n: int = 1, nbytes: int = 0,
                 reg: Optional[MetricsRegistry] = None) -> None:
    """Account result-cache events (racon_tpu/cache/, docs/CACHE.md).
    ``tier`` is ``job`` (the on-disk CAS) or ``window`` (the
    in-batcher consensus memo); ``outcome`` is ``hit`` / ``miss`` /
    ``store`` / ``evict`` / ``verify_fail``. ``n`` batches per-window
    probes into one call so a 256-window chunk is one registry pass
    and one trace point, not 256; ``nbytes`` (stores) feeds
    ``cache_bytes``. The derived ``cache_hit_ratio`` gauge is
    recomputed inside the same registry pass so it can never drift
    from the totals it summarizes."""
    reg = reg if reg is not None else _REGISTRY

    def _mutate(v):
        if outcome == "hit":
            v["cache_hits_total"] = \
                v.get("cache_hits_total", 0) + int(n)
        elif outcome == "miss":
            v["cache_misses_total"] = \
                v.get("cache_misses_total", 0) + int(n)
        elif outcome == "store":
            v["cache_stores_total"] = \
                v.get("cache_stores_total", 0) + int(n)
        elif outcome == "evict":
            v["cache_evictions_total"] = \
                v.get("cache_evictions_total", 0) + int(n)
        elif outcome == "verify_fail":
            v["cache_verify_fail_total"] = \
                v.get("cache_verify_fail_total", 0) + int(n)
        else:
            raise ValueError(f"[racon_tpu::metrics] unknown cache "
                             f"outcome {outcome!r}")
        if nbytes:
            v["cache_bytes"] = v.get("cache_bytes", 0) + int(nbytes)
        seen = v.get("cache_hits_total", 0) + \
            v.get("cache_misses_total", 0)
        if seen:
            v["cache_hit_ratio"] = round(
                v.get("cache_hits_total", 0) / seen, 4)

    reg.apply(_mutate)
    _trace.get_tracer().point("cache", outcome, tier=str(tier),
                              outcome=str(outcome), n=int(n),
                              bytes=int(nbytes))


# ------------------------------------------------------- sched telemetry

#: Canonical sched_* registry keys (docs/SCHEDULER.md documents each).
SCHED_KEYS = ("sched_rounds", "sched_windows", "sched_chunks",
              "sched_rounds_hist", "sched_survivor_frac",
              "sched_rounds_saved_frac", "sched_repack_overhead_s",
              "sched_dispatches_saved")


def publish_sched(telem, reg: Optional[MetricsRegistry] = None) -> None:
    """Write a SchedTelemetry's counters into the registry under the
    canonical ``sched_*`` keys — the one place their shape is defined."""
    reg = reg if reg is not None else _REGISTRY
    reg.set("sched_rounds", telem.rounds)
    reg.set("sched_windows", telem.windows)
    reg.set("sched_chunks", telem.chunks)
    reg.set("sched_rounds_hist",
            {str(k): v for k, v in sorted(telem.hist.items())})
    reg.set("sched_survivor_frac",
            [round(f, 4) for f in telem.survivor_frac()])
    reg.set("sched_rounds_saved_frac", round(telem.rounds_saved_frac(), 4))
    reg.set("sched_repack_overhead_s", round(telem.repack_s, 4))
    reg.set("sched_dispatches_saved", telem.dispatches_saved)


def sched_extras(reg: Optional[MetricsRegistry] = None
                 ) -> Dict[str, object]:
    """The registry's sched_* keys as a JSON-ready dict."""
    reg = reg if reg is not None else _REGISTRY
    return {k: reg.get(k) for k in SCHED_KEYS}


# ------------------------------------------------- fleet merge semantics

#: Merge kinds for cross-worker aggregation (racon_tpu/obs/fleet.py).
#: Every registry key has exactly one kind, decided by
#: :func:`merge_kind`, so the fleet aggregator never guesses:
#:
#: - ``sum``  — monotone counters (bytes, events, seconds of work);
#:   the fleet value is the sum over workers.
#: - ``max``  — peak gauges (queue depths); fleet value is the max.
#: - ``last`` — point-in-time gauges and per-run snapshots (fleet
#:   shape, cache population, derived ratios, structured sched
#:   telemetry); summing them across workers would be meaningless, so
#:   the most recent worker snapshot wins.
#: - ``hist`` — fixed-bucket histograms (:data:`HIST_BUCKETS`); the
#:   fleet value is the per-bucket sum (plus summed sum/count), which
#:   is exact: bucket bounds are declared per family, so every worker
#:   bins identically.
MERGE_SUM = "sum"
MERGE_MAX = "max"
MERGE_LAST = "last"
MERGE_HIST = "hist"

#: Exact keys whose fleet merge is ``last`` (point-in-time gauges).
#: ``sched_flag_pulls``/``sched_flag_pull_s`` are NOT here — despite
#: the prefix they are inc'd counters, so they sum.
_MERGE_LAST_KEYS = frozenset({
    "dist_workers", "dist_shards", "dist_n_targets",
    "ovl_device_fraction", "walk_chain_len",
    "pipe_overlap_efficiency",
    "jax_cache_enabled", "jax_cache_entries_start",
    "jax_cache_entries_added",
    "sched_rounds", "sched_windows", "sched_chunks",
    "sched_rounds_hist", "sched_survivor_frac",
    "sched_rounds_saved_frac", "sched_repack_overhead_s",
    "sched_dispatches_saved",
    # The autoscaler's target-size gauge (distributed/autoscaler.py):
    # folded into the fleet model from the supervisor heartbeat, never
    # summed across workers.
    "fleet_target_workers",
    # Ingest plane gauges (io/ingest.py): per-run derived ratio and the
    # gate state — the ingest_* byte/second/record counters sum.
    "ingest_fraction_of_wall", "ingest_enabled",
    # Decoupled-walk gauges (record_walk above): gate state and the
    # derived hidden fraction — the walk_* second/dispatch counters sum
    # and walk_queue_peak maxes via its suffix.
    "walk_async_enabled", "walk_hidden_fraction",
    # Daemon gauges (racon_tpu/server/): in-flight jobs, mean batch
    # occupancy, completion rate — the serve_* event/window counters
    # sum and serve_queue_depth_peak maxes via its suffix.
    "serve_active_jobs", "serve_batch_occupancy", "serve_jobs_per_min",
    # Freshness stamp for the two gauges above (set_serve_rate /
    # record_serve_batch): the latest wall clock wins.
    "serve_rate_wall_s",
    # Result-cache derived gauge (record_cache above): the hit ratio
    # re-derives from the totals on every event, so the most recent
    # snapshot wins — the cache_* hit/miss/store/evict counters sum.
    "cache_hit_ratio",
    # Gateway gauge (racon_tpu/gateway/): the policy's latest fleet
    # sizing decision — the gate_* routed/adoption/run counters sum.
    "gate_fleet_target",
    # Ava plane gauges (record_ava_plan above): the bucket plan is
    # identical on every worker, so the latest snapshot wins.
    "ava_targets", "ava_buckets", "ava_quantum", "ava_compile_budget",
    "ava_pad_frac",
})


def merge_kind(key: str) -> str:
    """The fleet merge kind for a registry key (docs/OBSERVABILITY.md
    documents the table). Unknown keys default to ``sum`` — new
    counters aggregate correctly without registration; a new gauge must
    be added to ``_MERGE_LAST_KEYS`` (or end in ``_peak``) or the fleet
    number is wrong, which tests/test_fleet_obs.py pins for the known
    key set."""
    if key in HIST_BUCKETS:
        return MERGE_HIST
    if key in _MERGE_LAST_KEYS:
        return MERGE_LAST
    if key.endswith("_peak"):
        return MERGE_MAX
    return MERGE_SUM


#: Declared metric contract — one row per registry key family, the
#: ground truth the metrics-contract lint rule (racon_tpu/analysis,
#: MET001–MET004) checks against the recorded keys, merge_kind(), and
#: the docs/OBSERVABILITY.md producer table. Each row is
#: ``(pattern, merge kind, doc token)``: ``*`` in a pattern matches one
#: runtime-named segment (site slug, stage name, phase slug); the doc
#: token must appear verbatim in docs/OBSERVABILITY.md. Exact keys
#: sort before the wildcard that would shadow them. ``_``-prefixed
#: keys are internal (excluded from snapshots) and carry no row.
METRIC_SPECS = (
    ("adaptive_early_exits", MERGE_SUM, "adaptive_early_exits"),
    ("adaptive_rounds_executed", MERGE_SUM, "adaptive_rounds_executed"),
    ("adaptive_rounds_scheduled", MERGE_SUM, "adaptive_rounds_scheduled"),
    ("align_bases_total", MERGE_SUM, "align_bases_total"),
    ("align_native_bases", MERGE_SUM, "align_native_bases"),
    ("align_native_cells", MERGE_SUM, "align_native_cells"),
    ("align_native_vector_cells", MERGE_SUM, "align_native_vector_cells"),
    ("align_seconds_*", MERGE_SUM, "align_seconds_"),
    ("align_walk_overlaps", MERGE_SUM, "align_walk_overlaps"),
    ("ava_buckets", MERGE_LAST, "ava_buckets"),
    ("ava_compile_budget", MERGE_LAST, "ava_compile_budget"),
    ("ava_pad_frac", MERGE_LAST, "ava_pad_frac"),
    ("ava_quantum", MERGE_LAST, "ava_quantum"),
    ("ava_targets", MERGE_LAST, "ava_targets"),
    ("cache_bytes", MERGE_SUM, "cache_bytes"),
    ("cache_evictions_total", MERGE_SUM, "cache_evictions_total"),
    ("cache_hit_ratio", MERGE_LAST, "cache_hit_ratio"),
    ("cache_hits_total", MERGE_SUM, "cache_hits_total"),
    ("cache_misses_total", MERGE_SUM, "cache_misses_total"),
    ("cache_stores_total", MERGE_SUM, "cache_stores_total"),
    ("cache_verify_fail_total", MERGE_SUM, "cache_verify_fail_total"),
    ("d2h_bytes", MERGE_SUM, "d2h_bytes"),
    ("d2h_s", MERGE_SUM, "d2h_s"),
    ("d2h_transfers", MERGE_SUM, "d2h_transfers"),
    ("device_dispatches", MERGE_SUM, "device_dispatches"),
    ("dist_n_targets", MERGE_LAST, "dist_n_targets"),
    ("dist_shards", MERGE_LAST, "dist_shards"),
    ("dist_workers", MERGE_LAST, "dist_workers"),
    ("dist_*", MERGE_SUM, "dist_claims"),
    ("dp_shard_lanes_*", MERGE_SUM, "dp_shard_lanes_"),
    ("dispatch_round_s", MERGE_HIST, "dispatch_round_s"),
    ("fleet_target_workers", MERGE_LAST, "fleet_target_workers"),
    ("flight_dump_write_s", MERGE_SUM, "flight_dump_write_s"),
    ("flight_dumps_total", MERGE_SUM, "flight_dumps_total"),
    ("gate_fleet_target", MERGE_LAST, "gate_fleet_target"),
    ("gate_*", MERGE_SUM, "gate_routed_fleet"),
    ("h2d_bytes", MERGE_SUM, "h2d_bytes"),
    ("h2d_s", MERGE_SUM, "h2d_s"),
    ("h2d_transfer_s", MERGE_HIST, "h2d_transfer_s"),
    ("h2d_transfers", MERGE_SUM, "h2d_transfers"),
    ("ingest_blocks", MERGE_SUM, "ingest_blocks"),
    ("ingest_bytes_in", MERGE_SUM, "ingest_bytes_in"),
    ("ingest_bytes_out", MERGE_SUM, "ingest_bytes_out"),
    ("ingest_enabled", MERGE_LAST, "ingest_enabled"),
    ("ingest_fraction_of_wall", MERGE_LAST, "ingest_fraction_of_wall"),
    ("ingest_inflate_s", MERGE_SUM, "ingest_inflate_s"),
    ("ingest_parse_s", MERGE_SUM, "ingest_parse_s"),
    ("ingest_raw_bytes", MERGE_SUM, "ingest_raw_bytes"),
    ("ingest_records", MERGE_SUM, "ingest_records"),
    ("ingest_wait_s", MERGE_SUM, "ingest_wait_s"),
    ("jax_cache_enabled", MERGE_LAST, "jax_cache_enabled"),
    ("jax_cache_entries_added", MERGE_LAST, "jax_cache_entries_added"),
    ("jax_cache_entries_start", MERGE_LAST, "jax_cache_entries_start"),
    ("ovl_device_fraction", MERGE_LAST, "ovl_device_fraction"),
    ("ovl_device_jobs", MERGE_SUM, "ovl_device_jobs"),
    ("ovl_native_jobs", MERGE_SUM, "ovl_native_jobs"),
    ("ovl_tiles_exec", MERGE_SUM, "ovl_tiles_exec"),
    ("phase_seconds_*", MERGE_SUM, "phase_seconds_"),
    ("pipe_overlap_efficiency", MERGE_LAST, "pipe_overlap_efficiency"),
    ("pipe_queue_*_get_wait_s", MERGE_SUM, "pipe_queue_"),
    ("pipe_queue_*_peak", MERGE_MAX, "pipe_queue_"),
    ("pipe_queue_*_put_wait_s", MERGE_SUM, "pipe_queue_"),
    ("pipe_runs", MERGE_SUM, "pipe_runs"),
    ("pipe_stage_*_busy_s", MERGE_SUM, "pipe_stage_"),
    ("pipe_stage_*_items", MERGE_SUM, "pipe_stage_"),
    ("pipe_stage_*_stall_in_s", MERGE_SUM, "pipe_stage_"),
    ("pipe_stage_*_stall_out_s", MERGE_SUM, "pipe_stage_"),
    ("pipe_stall_events", MERGE_SUM, "pipe_stall_events"),
    ("pipe_wall_s", MERGE_SUM, "pipe_wall_s"),
    ("poa_host_windows", MERGE_SUM, "poa_host_windows"),
    ("poa_windows_total", MERGE_SUM, "poa_windows_total"),
    ("redo_device_windows", MERGE_SUM, "redo_device_windows"),
    ("redo_host_windows", MERGE_SUM, "redo_host_windows"),
    ("redo_passes", MERGE_SUM, "redo_passes"),
    ("res_ckpt_*", MERGE_SUM, "res_ckpt_commits"),
    ("res_degraded_chunks", MERGE_SUM, "res_degraded_chunks"),
    ("res_degraded_windows", MERGE_SUM, "res_degraded_windows"),
    ("res_fault_injected_total", MERGE_SUM, "res_fault_injected_total"),
    ("res_fault_site_*", MERGE_SUM, "res_fault_site_"),
    ("res_retry_backoff_s", MERGE_SUM, "res_retry_backoff_s"),
    ("res_retry_exhausted", MERGE_SUM, "res_retry_exhausted"),
    ("res_retry_site_*", MERGE_SUM, "res_retry_site_"),
    ("res_retry_total", MERGE_SUM, "res_retry_total"),
    ("res_watchdog_breach_total", MERGE_SUM, "res_watchdog_breach_total"),
    ("res_watchdog_site_*", MERGE_SUM, "res_watchdog_site_"),
    ("res_watchdog_terminal_total", MERGE_SUM,
     "res_watchdog_terminal_total"),
    ("sched_dispatches_saved", MERGE_LAST, "sched_"),
    ("sched_flag_pull_s", MERGE_SUM, "sched_flag_pull_s"),
    ("sched_flag_pulls", MERGE_SUM, "sched_flag_pulls"),
    ("sched_repack_overhead_s", MERGE_LAST, "sched_"),
    ("sched_rounds", MERGE_LAST, "sched_"),
    ("sched_rounds_hist", MERGE_LAST, "sched_"),
    ("sched_rounds_saved_frac", MERGE_LAST, "sched_"),
    ("sched_survivor_frac", MERGE_LAST, "sched_"),
    ("sched_chunks", MERGE_LAST, "sched_"),
    ("sched_windows", MERGE_LAST, "sched_"),
    ("serve_active_jobs", MERGE_LAST, "serve_active_jobs"),
    ("serve_batch_occupancy", MERGE_LAST, "serve_batch_occupancy"),
    ("serve_batch_windows", MERGE_SUM, "serve_batch_windows"),
    ("serve_batches", MERGE_SUM, "serve_batches"),
    ("serve_job_latency_s", MERGE_HIST, "serve_job_latency_s"),
    ("serve_jobs_per_min", MERGE_LAST, "serve_jobs_per_min"),
    ("serve_jobs_*", MERGE_SUM, "serve_jobs_"),
    ("serve_queue_depth_peak", MERGE_MAX, "serve_queue_depth_peak"),
    ("serve_queue_wait_s", MERGE_HIST, "serve_queue_wait_s"),
    ("serve_rate_wall_s", MERGE_LAST, "serve_rate_wall_s"),
    ("serve_tenant_wait_s", MERGE_SUM, "serve_tenant_wait_s"),
    ("walk_async_enabled", MERGE_LAST, "walk_async_enabled"),
    ("walk_chain_len", MERGE_LAST, "walk_chain_len"),
    ("walk_dispatches", MERGE_SUM, "walk_dispatches"),
    ("walk_fused_chunks", MERGE_SUM, "walk_fused_chunks"),
    ("walk_hidden_fraction", MERGE_LAST, "walk_hidden_fraction"),
    ("walk_hidden_s", MERGE_HIST, "walk_hidden_s"),
    ("walk_overlap_s", MERGE_SUM, "walk_overlap_s"),
    ("walk_queue_peak", MERGE_MAX, "walk_queue_peak"),
    ("walk_seconds", MERGE_SUM, "walk_seconds"),
)


def merge_values(key: str, values) -> object:
    """Fold per-worker values for ``key`` by its merge kind. Non-numeric
    values (sched hist dicts, fraction lists) always take the last —
    there is no meaningful sum/max for them — except histogram dicts,
    which fold per-bucket."""
    vals = [v for v in values if v is not None]
    if not vals:
        return None
    kind = merge_kind(key)
    if kind == MERGE_HIST:
        n = len(HIST_BUCKETS[key]) + 1
        out = {"buckets": [0] * n, "sum": 0.0, "count": 0}
        for v in vals:
            if not isinstance(v, dict):
                continue
            for i, c in enumerate(v.get("buckets", ())[:n]):
                out["buckets"][i] += int(c)
            out["sum"] = round(out["sum"] + float(v.get("sum", 0.0)), 6)
            out["count"] += int(v.get("count", 0))
        return out
    numeric = all(isinstance(v, (int, float)) and
                  not isinstance(v, bool) for v in vals)
    if not numeric or kind == MERGE_LAST:
        return vals[-1]
    if kind == MERGE_MAX:
        return max(vals)
    total = sum(vals)
    return round(total, 6) if isinstance(total, float) else total


# -------------------------------------------------- phases and windows

def _phase_slug(msg: str) -> str:
    """Registry-key slug for a logger phase message:
    ``"[racon_tpu::Polisher::initialize] loaded sequences"`` ->
    ``"initialize_loaded_sequences"``."""
    msg = msg.strip()
    if msg.startswith("[") and "]" in msg:
        head, _, rest = msg.partition("]")
        msg = head[1:].rsplit("::", 1)[-1] + " " + rest
    out = []
    for ch in msg.lower():
        out.append(ch if ch.isalnum() else "_")
    slug = "_".join(filter(None, "".join(out).split("_")))
    return slug[:64] or "unnamed"


def record_phase_seconds(msg: str, seconds: float,
                         reg: Optional[MetricsRegistry] = None) -> None:
    """Account one completed logger phase (utils/logger.py) as
    ``phase_seconds_<slug>`` plus the ``phase_seconds_total`` roll-up —
    the per-worker phase decomposition the fleet aggregator and the
    OpenMetrics exporter publish (the trace-span equivalent only exists
    when tracing is on)."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc(f"phase_seconds_{_phase_slug(msg)}", float(seconds))
    reg.inc("phase_seconds_total", float(seconds))


def record_windows(n: int,
                   reg: Optional[MetricsRegistry] = None) -> None:
    """Account ``n`` polished windows (ops/poa.py consensus_windows).
    ``poa_windows_total`` is cumulative across chunks, contigs, and
    shards — unlike ``sched_windows`` (a per-run snapshot overwritten
    by each polisher instance), so per-worker windows/s in the fleet
    report divides this by the snapshot's wall clock."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("poa_windows_total", int(n))


def record_host_windows(n: int,
                        reg: Optional[MetricsRegistry] = None) -> None:
    """Account ``n`` windows a device engine polished on the host path
    (ops/poa.py ``_consensus_host``): jumbo or over-cap geometry, redo
    leftovers, degraded chunks. Their device share is
    ``1 - poa_host_windows / poa_windows_total``."""
    reg = reg if reg is not None else _REGISTRY
    reg.inc("poa_host_windows", int(n))


def record_dp_shard_lanes(n_jobs: int, B: int, n_shards: int,
                          reg: Optional[MetricsRegistry] = None) -> None:
    """Account a dp-sharded chunk dispatch: its ``n_jobs`` real lanes
    are packed first into ``B`` lanes split evenly over ``n_shards``
    devices, so shard ``i`` receives the real lanes of its slice.
    ``dp_shard_lanes_<i>`` says which devices got work (0 = padding
    only)."""
    reg = reg if reg is not None else _REGISTRY
    per = B // n_shards
    for i in range(n_shards):
        reg.inc(f"dp_shard_lanes_{i}",
                int(min(max(n_jobs - i * per, 0), per)))


def sched_summary_line(reg: Optional[MetricsRegistry] = None) -> str:
    """The polisher's one-line stderr scheduler summary, formatted from
    the registry (format kept stable across the registry refactor)."""
    reg = reg if reg is not None else _REGISTRY
    hist = reg.get("sched_rounds_hist", {}) or {}
    hist_s = " ".join(f"r{k}:{v}" for k, v in
                      sorted(hist.items(), key=lambda kv: int(kv[0])))
    saved = float(reg.get("sched_rounds_saved_frac", 0.0))
    repack = float(reg.get("sched_repack_overhead_s", 0.0))
    return (f"windows={reg.get('sched_windows', 0)} "
            f"chunks={reg.get('sched_chunks', 0)} "
            f"frozen[{hist_s}] "
            f"rounds_saved={saved:.0%} "
            f"repack={repack:.3f}s")
