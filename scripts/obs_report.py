"""Render a racon_tpu JSONL trace into a per-stage breakdown table.

A trace (RACON_TPU_TRACE=<path> or ``--trace``) carries the run's
per-stage decomposition (spans) and its closing metrics snapshot; this
script renders both.

Usage:
    python scripts/obs_report.py TRACE.jsonl            # breakdown table
    python scripts/obs_report.py TRACE.jsonl --validate # schema check
    python scripts/obs_report.py TRACE.jsonl --fleet LEDGER_DIR
                                         # + per-shard lease timeline,
                                         #   steals, per-worker rates

``--validate`` exits non-zero unless the trace is well-formed: a begin
header, JSON-parseable lines, required span keys, non-negative timings,
parents that exist, children contained in their parent's interval, and
well-typed fleet context attrs (``worker_id``/``shard``/``run_fp`` —
one run fingerprint per trace) (the contract documented in
docs/OBSERVABILITY.md; ci.sh gates it).

``--fleet`` aggregates the worker metric shards + events.jsonl under a
ledger directory (racon_tpu/obs/fleet.py) into a ``fleet:`` section;
shards stamped by different run fingerprints are a hard error, never a
silent merge.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional

REQUIRED_SPAN_KEYS = ("id", "parent", "kind", "name", "t0", "dur_s")

#: Per-kind attribute contract (docs/OBSERVABILITY.md): spans of these
#: kinds must carry the listed attrs or downstream aggregation (the
#: transfer table, the pipeline section) silently under-counts.
KIND_REQUIRED_ATTRS = {
    "transfer": ("bytes", "dir"),
    "stage": ("items", "busy_s", "stall_s"),
    "queue": ("peak", "capacity", "items"),
    "retry": ("attempt", "error"),
    "fault": ("index", "action"),
    "checkpoint": ("tid", "bytes"),
    # One host stretch of the overlap-alignment phase (models/
    # polisher.py, ops/ovl_align.py, via obs/metrics.py timed_span):
    # revcomp, device_prep/_dispatch/_collect, native_redo, bp_walk.
    "align": ("overlaps",),
    # One distributed-ledger event (claim/steal/renew/commit/merge,
    # racon_tpu/distributed/): which shard, and which worker did it.
    "dist": ("shard", "worker"),
    # One watchdog deadline breach (resilience/watchdog.py): how long
    # the site was allowed and how long it actually waited.
    "watchdog": ("deadline_s", "waited_s"),
    # One pipeline stall-detector firing (pipeline/stages.py): the
    # silence window that tripped it and how many stages were frozen.
    "stall": ("window_s", "stages"),
    # One ingest-plane event (io/inflate.py inflate/<plan>,
    # obs/metrics.py parse/<reader>): which plan ran and how many
    # decompressed/raw bytes it moved.
    "ingest": ("mode", "bytes"),
    # One decoupled final-round walk dispatch (pipeline/streaming.py
    # walk stage over ops/colwalk.py::dispatch_walk): geometry of the
    # chunk whose traceback it finishes.
    "walk": ("lanes", "windows"),
    # One serve-plane event (racon_tpu/server/, obs/metrics.py): a job
    # lifecycle transition (submitted/resumed/completed/...) or a
    # cross-request batch dispatch; job/tenant are comma-joined lists
    # on batch points so one dispatch names every rider. trace_id /
    # parent_id tie the point into its job's cross-process timeline
    # ("-" / 0 when the caller has no context).
    "serve": ("job", "tenant", "trace_id", "parent_id"),
    # One result-cache event (racon_tpu/cache/ via obs/metrics.py
    # record_cache): which tier (job CAS / window memo) and which
    # outcome (hit/miss/store/evict/verify_fail) — per-window probes
    # arrive batched, one point per chunk.
    "cache": ("tier", "outcome"),
    # One fleet-serve gateway event (racon_tpu/gateway/ via
    # obs/metrics.py record_gate): a routing decision
    # (route_fleet/route_local), a standby adoption, or a finished
    # fleet execution — same trace-context attrs as serve points, so
    # the per-job timeline shows gateway → supervisor → workers.
    "gate": ("job", "tenant", "trace_id", "parent_id"),
}

# Span kinds that carry no required attributes — structural intervals
# whose payload is just name + duration. Together with
# KIND_REQUIRED_ATTRS this is the closed set of legal span kinds: the
# span-schema lint rule (racon_tpu/analysis, SPAN001–SPAN003) checks
# every Tracer emission against the union, both directions.
ATTR_FREE_KINDS = ("chunk", "dispatch", "phase", "pipeline", "round",
                   "run")

# Span intervals are rounded to 1e-6 on write and a parent's clock stops
# fractionally after its children's; allow that much slack in nesting.
EPS = 5e-3


class TraceError(ValueError):
    pass


def load_trace(path: str) -> Dict[str, object]:
    """Parse a trace file into {begin, spans (by id), metrics}."""
    begin = None
    metrics = None
    spans: Dict[int, dict] = {}
    with open(path, "r", encoding="utf-8") as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as exc:
                raise TraceError(f"line {ln}: not valid JSON ({exc})")
            ev = obj.get("ev")
            if ev == "begin":
                begin = obj
            elif ev == "span":
                spans[obj.get("id")] = obj
            elif ev == "metrics":
                metrics = obj
            elif ev is None:
                raise TraceError(f"line {ln}: missing 'ev' key")
    return {"begin": begin, "spans": spans, "metrics": metrics}


def validate(tr: Dict[str, object]) -> List[str]:
    """Return a list of schema violations (empty = valid)."""
    errs: List[str] = []
    if tr["begin"] is None:
        errs.append("no begin header")
    elif tr["begin"].get("schema") != 1:
        errs.append(f"unknown schema {tr['begin'].get('schema')!r}")
    spans: Dict[int, dict] = tr["spans"]
    for sid, s in spans.items():
        for k in REQUIRED_SPAN_KEYS:
            if k not in s:
                errs.append(f"span {sid}: missing key {k!r}")
        if not isinstance(s.get("id"), int):
            errs.append(f"span {sid}: non-integer id")
        for k in ("t0", "dur_s"):
            v = s.get(k)
            if not isinstance(v, (int, float)) or v < 0:
                errs.append(f"span {sid}: {k} must be a non-negative "
                            f"number, got {v!r}")
        for k in KIND_REQUIRED_ATTRS.get(s.get("kind"), ()):
            if k not in s:
                errs.append(f"span {sid}: kind {s.get('kind')!r} missing "
                            f"attr {k!r}")
        # Fleet context attrs (set_context, racon_tpu/obs/trace.py):
        # optional, but when present they must be usable by the fleet
        # aggregation — a mistyped worker_id/shard silently breaks the
        # per-worker grouping downstream.
        if "worker_id" in s and not isinstance(s["worker_id"], str):
            errs.append(f"span {sid}: worker_id must be a string, got "
                        f"{s['worker_id']!r}")
        if "shard" in s and (not isinstance(s["shard"], int) or
                             isinstance(s["shard"], bool)):
            errs.append(f"span {sid}: shard must be an integer, got "
                        f"{s['shard']!r}")
        if "run_fp" in s and not isinstance(s["run_fp"], str):
            errs.append(f"span {sid}: run_fp must be a string, got "
                        f"{s['run_fp']!r}")
        if "trace_id" in s and not isinstance(s["trace_id"], str):
            errs.append(f"span {sid}: trace_id must be a string, got "
                        f"{s['trace_id']!r}")
        if "parent_id" in s and (not isinstance(s["parent_id"], int) or
                                 isinstance(s["parent_id"], bool)):
            errs.append(f"span {sid}: parent_id must be an integer, "
                        f"got {s['parent_id']!r}")
        parent = s.get("parent")
        if parent is not None:
            p = spans.get(parent)
            if p is None:
                errs.append(f"span {sid}: parent {parent} not in trace")
            else:
                if s["t0"] < p["t0"] - EPS:
                    errs.append(f"span {sid}: starts before parent "
                                f"{parent}")
                if s["t0"] + s["dur_s"] > \
                        p["t0"] + p["dur_s"] + EPS:
                    errs.append(f"span {sid}: ends after parent {parent}")
    fps = sorted({s["run_fp"] for s in spans.values()
                  if isinstance(s.get("run_fp"), str)})
    if len(fps) > 1:
        errs.append("mixed run_fp across spans: " +
                    ", ".join(fp[:12] for fp in fps) +
                    " — one trace must belong to one run")
    return errs


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024 or unit == "GB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.1f} GB"


def _agg(rows: List[dict]):
    total = sum(s["dur_s"] for s in rows)
    return len(rows), total


def render(tr: Dict[str, object], out=None,
           fleet_dir: Optional[str] = None) -> None:
    """Print the per-stage breakdown (the hand-built delta table,
    automated)."""
    if out is None:
        # Resolved at call time, not def time: test harnesses (capsys)
        # swap sys.stdout per test, and this module may have been
        # imported under a different one.
        out = sys.stdout
    spans: Dict[int, dict] = tr["spans"]
    if not spans:
        print("(empty trace: no spans)", file=out)
        return
    runs = [s for s in spans.values() if s["kind"] == "run"]
    wall = max((s["t0"] + s["dur_s"] for s in spans.values()))
    run_dur = runs[0]["dur_s"] if runs else wall
    base = runs[0]["name"] if runs else "(no run span)"
    print(f"run: {base}  wall={run_dur:.3f}s  spans={len(spans)}",
          file=out)

    # Per-kind > per-name aggregation, phases in time order.
    by_kind: Dict[str, List[dict]] = {}
    for s in spans.values():
        by_kind.setdefault(s["kind"], []).append(s)

    for kind in ("phase", "pipeline", "stage", "chunk", "round",
                 "align", "dispatch"):
        rows = by_kind.get(kind)
        if not rows:
            continue
        print(f"\n{kind:>8}  {'count':>5}  {'total_s':>9}  {'%run':>6}"
              f"  name", file=out)
        by_name: Dict[str, List[dict]] = {}
        for s in sorted(rows, key=lambda s: s["t0"]):
            by_name.setdefault(s["name"], []).append(s)
        for name, group in by_name.items():
            n, tot = _agg(group)
            pct = 100.0 * tot / run_dur if run_dur else 0.0
            print(f"{'':>8}  {n:>5}  {tot:>9.3f}  {pct:>5.1f}%  {name}",
                  file=out)

    transfers = by_kind.get("transfer", [])
    if transfers:
        print(f"\ntransfer  {'count':>5}  {'total_s':>9}  {'bytes':>10}"
              f"  {'MB/s':>8}  dir", file=out)
        for d in ("h2d", "d2h"):
            rows = [s for s in transfers if s.get("dir") == d]
            if not rows:
                continue
            n, tot = _agg(rows)
            nb = sum(s.get("bytes", 0) for s in rows)
            bw = nb / tot / 1e6 if tot > 0 else 0.0
            print(f"{'':>8}  {n:>5}  {tot:>9.3f}  {_fmt_bytes(nb):>10}"
                  f"  {bw:>8.3f}  {d}", file=out)

    # Coverage: how much of the run the traced stages account for. The
    # phase spans partition the run's wall clock (chunk/round/dispatch
    # spans nest inside them and would double-count); without phases,
    # fall back to direct children of the run span.
    if runs:
        rows = by_kind.get("phase") or [
            s for s in spans.values() if s.get("parent") == runs[0]["id"]]
        cov = sum(s["dur_s"] for s in rows)
        pct = 100.0 * cov / run_dur if run_dur else 0.0
        print(f"\ncoverage: traced stages sum {cov:.3f}s = {pct:.1f}% "
              f"of run wall", file=out)

    m = tr["metrics"]
    _render_ingest(m, by_kind, out)
    _render_pipeline(m, out)
    _render_resilience(m, by_kind, out)
    _render_dist(m, by_kind, out)
    _render_server(m, by_kind, out, trace_end_unix=_trace_end_unix(tr))
    _render_hist(m, out)
    _render_cache(m, by_kind, out)
    _render_ava(m, out)
    if fleet_dir:
        _render_fleet(fleet_dir, out)
    _render_redo(m, out)
    if m:
        keys = [k for k in sorted(m) if k != "ev"]
        print("\nmetrics:", file=out)
        for k in keys:
            print(f"  {k} = {m[k]}", file=out)


def _render_ingest(m, by_kind, out) -> None:
    """The "ingest:" section: data-plane totals (bytes through the
    inflate pool, parse/wait split, fraction of wall) plus one line per
    ``ingest`` span (which inflate plan / reader each file used). Runs
    that never booked ingest accounting print nothing."""
    m = m or {}
    if not (int(m.get("ingest_records", 0) or 0)
            or int(m.get("ingest_blocks", 0) or 0)):
        return
    bin_ = int(m.get("ingest_bytes_in", 0) or 0)
    bout = int(m.get("ingest_bytes_out", 0) or 0)
    raw = int(m.get("ingest_raw_bytes", 0) or 0)
    print(f"\ningest: records={int(m.get('ingest_records', 0) or 0)}  "
          f"raw={raw / 1e6:.1f}MB  "
          f"inflate={bin_ / 1e6:.1f}→{bout / 1e6:.1f}MB "
          f"({int(m.get('ingest_blocks', 0) or 0)} block(s))", file=out)
    print(f"  inflate={float(m.get('ingest_inflate_s', 0) or 0):.3f}s  "
          f"parse={float(m.get('ingest_parse_s', 0) or 0):.3f}s  "
          f"wait={float(m.get('ingest_wait_s', 0) or 0):.3f}s"
          + (f"  fraction_of_wall="
             f"{float(m['ingest_fraction_of_wall']):.4f}"
             if "ingest_fraction_of_wall" in m else ""), file=out)
    for s in by_kind.get("ingest", []):
        print(f"  {s['name']:<16} {s.get('bytes', 0) / 1e6:>8.1f}MB  "
              f"{s['dur_s']:.3f}s", file=out)


_STAGE_SUFFIXES = ("_busy_s", "_stall_in_s", "_stall_out_s", "_items")
_QUEUE_SUFFIXES = ("_peak", "_put_wait_s", "_get_wait_s")


def _pipe_names(m: dict, prefix: str, suffixes) -> List[str]:
    names = set()
    for k in m:
        if not k.startswith(prefix):
            continue
        for suf in suffixes:
            if k.endswith(suf):
                names.add(k[len(prefix):-len(suf)])
    return sorted(names)


def _render_pipeline(m, out) -> None:
    """The "Pipeline" section: per-stage busy/stall, per-queue gauges,
    and overlap efficiency (device-busy / pipeline wall), all from the
    ``pipe_*`` metrics the streaming executor records."""
    if not m or not int(m.get("pipe_runs", 0) or 0):
        return
    wall = float(m.get("pipe_wall_s", 0.0))
    print(f"\npipeline: runs={int(m['pipe_runs'])}  wall={wall:.3f}s",
          file=out)
    stages = _pipe_names(m, "pipe_stage_", _STAGE_SUFFIXES)
    if stages:
        print(f"{'stage':>8}  {'items':>5}  {'busy_s':>9}  "
              f"{'stall_in':>9}  {'stall_out':>9}", file=out)
        for name in stages:
            g = lambda suf: m.get(f"pipe_stage_{name}{suf}", 0)  # noqa: E731
            print(f"{name:>8}  {int(g('_items')):>5}  "
                  f"{float(g('_busy_s')):>9.3f}  "
                  f"{float(g('_stall_in_s')):>9.3f}  "
                  f"{float(g('_stall_out_s')):>9.3f}", file=out)
    queues = _pipe_names(m, "pipe_queue_", _QUEUE_SUFFIXES)
    if queues:
        print(f"{'queue':>8}  {'peak':>5}  {'put_wait':>9}  "
              f"{'get_wait':>9}", file=out)
        for name in queues:
            g = lambda suf: m.get(f"pipe_queue_{name}{suf}", 0)  # noqa: E731
            print(f"{name:>8}  {int(g('_peak')):>5}  "
                  f"{float(g('_put_wait_s')):>9.3f}  "
                  f"{float(g('_get_wait_s')):>9.3f}", file=out)
    eff = m.get("pipe_overlap_efficiency")
    if eff is None and wall > 0:
        eff = float(m.get("pipe_stage_compute_busy_s", 0.0)) / wall
    if eff is not None:
        print(f"overlap efficiency: {float(eff):.3f} "
              "(compute busy / pipeline wall)", file=out)
    stalls = int(m.get("pipe_stall_events", 0) or 0)
    if stalls:
        print(f"stalls: {stalls} detector firing(s) — frozen stages "
              "were dumped to stderr and re-polished on the host",
              file=out)


def _render_resilience(m, by_kind, out) -> None:
    """The "Resilience" section: retry/fault/degradation/checkpoint
    counters plus the per-site retry spans, all from the ``res_*``
    metrics and ``retry``/``fault``/``checkpoint`` spans the resilience
    package records. Quiet runs (no res_* activity) print nothing."""
    m = m or {}
    res = {k: v for k, v in m.items() if k.startswith("res_")}
    spans = (by_kind.get("retry", []) + by_kind.get("fault", []) +
             by_kind.get("checkpoint", []))
    if not res and not spans:
        return
    print(f"\nresilience: retries={int(m.get('res_retry_total', 0))}  "
          f"exhausted={int(m.get('res_retry_exhausted', 0))}  "
          f"faults={int(m.get('res_fault_injected_total', 0))}  "
          f"degraded_windows={int(m.get('res_degraded_windows', 0))}",
          file=out)
    sites = sorted(k[len("res_retry_site_"):] for k in res
                   if k.startswith("res_retry_site_"))
    if sites:
        print(f"{'site':>24}  {'retries':>7}  {'faults':>6}", file=out)
        for site in sites:
            print(f"{site:>24}  "
                  f"{int(res.get(f'res_retry_site_{site}', 0)):>7}  "
                  f"{int(res.get(f'res_fault_site_{site}', 0)):>6}",
                  file=out)
    breaches = int(m.get("res_watchdog_breach_total", 0))
    if breaches:
        wsites = sorted(k[len("res_watchdog_site_"):] for k in res
                        if k.startswith("res_watchdog_site_"))
        per = "  ".join(
            f"{s}={int(res[f'res_watchdog_site_{s}'])}" for s in wsites)
        print(f"watchdog: breaches={breaches}  "
              f"terminal={int(m.get('res_watchdog_terminal_total', 0))}"
              f"  stalls={int(m.get('pipe_stall_events', 0))}",
              file=out)
        if per:
            print(f"  breach sites: {per}", file=out)
    backoff = float(m.get("res_retry_backoff_s", 0.0))
    if backoff:
        print(f"backoff slept: {backoff:.3f}s", file=out)
    commits = int(m.get("res_ckpt_commits", 0))
    skips = int(m.get("res_ckpt_skips", 0))
    if commits or skips or int(m.get("res_ckpt_resumes", 0)):
        print(f"checkpoint: commits={commits}  resumed_skips={skips}  "
              f"bytes={_fmt_bytes(float(m.get('res_ckpt_bytes', 0)))}",
              file=out)


def _render_dist(m, by_kind, out) -> None:
    """The "Distributed" section: fleet shape, claim/steal/lease
    counters, and per-worker event counts, from the ``dist_*`` metrics
    and ``dist`` spans the work ledger records (docs/DISTRIBUTED.md).
    Single-process runs (no dist_* activity) print nothing."""
    m = m or {}
    dist = {k: v for k, v in m.items() if k.startswith("dist_")}
    spans = by_kind.get("dist", [])
    if not dist and not spans:
        return
    print(f"\ndistributed: workers={int(m.get('dist_workers', 0))}  "
          f"shards={int(m.get('dist_shards', 0))}  "
          f"targets={int(m.get('dist_n_targets', 0))}", file=out)
    print(f"  claims={int(m.get('dist_claims', 0))}  "
          f"stolen={int(m.get('dist_shards_stolen', 0))}  "
          f"lease_renewals={int(m.get('dist_lease_renewals', 0))}  "
          f"leases_lost={int(m.get('dist_leases_lost', 0))}", file=out)
    print(f"  contigs: polished={int(m.get('dist_contigs_polished', 0))}"
          f"  resumed={int(m.get('dist_contigs_resumed', 0))}  "
          f"repolished={int(m.get('dist_contigs_repolished', 0))}",
          file=out)
    rels = int(m.get("dist_releases", 0))
    evics = int(m.get("dist_self_evictions", 0))
    if rels or evics:
        print(f"  releases={rels}  self_evictions={evics}  "
              "(fail-slow: lease given back before the term expired)",
              file=out)
    lat = float(m.get("dist_steal_latency_s", 0.0))
    rec = float(m.get("dist_recovery_wall_s", 0.0))
    if lat or rec:
        print(f"  steal latency {lat:.3f}s  recovery wall {rec:.3f}s",
              file=out)
    if spans:
        by_worker: Dict[str, int] = {}
        for s in spans:
            by_worker[str(s.get("worker"))] = \
                by_worker.get(str(s.get("worker")), 0) + 1
        workers = ", ".join(f"{w}: {n}" for w, n in
                            sorted(by_worker.items()))
        print(f"  events by worker: {workers}", file=out)


def _trace_end_unix(tr) -> Optional[float]:
    """Wall-clock instant of the last span end: the begin header's
    unix_time plus the latest relative span end. None when the trace
    has no absolute anchor (old traces, empty traces)."""
    begin = tr.get("begin") or {}
    t0 = begin.get("unix_time")
    spans = tr.get("spans") or {}
    if not isinstance(t0, (int, float)) or not spans:
        return None
    return float(t0) + max(
        (s["t0"] + s["dur_s"] for s in spans.values()
         if isinstance(s.get("t0"), (int, float)) and
         isinstance(s.get("dur_s"), (int, float))), default=0.0)


def _render_server(m, by_kind, out, trace_end_unix=None) -> None:
    """The "server:" section: daemon job lifecycle totals, the
    cross-request batcher's packing efficiency, and per-tenant event
    counts, from the ``serve_*`` metrics and ``serve`` points the
    server plane records (docs/SERVER.md). Runs that never served
    (no serve_* activity) print nothing."""
    m = m or {}
    serve = {k: v for k, v in m.items() if k.startswith("serve_")}
    spans = by_kind.get("serve", [])
    if not serve and not spans:
        return
    print(f"\nserver: submitted={int(m.get('serve_jobs_submitted', 0))}"
          f"  completed={int(m.get('serve_jobs_completed', 0))}  "
          f"failed={int(m.get('serve_jobs_failed', 0))}  "
          f"cancelled={int(m.get('serve_jobs_cancelled', 0))}  "
          f"resumed={int(m.get('serve_jobs_resumed', 0))}", file=out)
    batches = int(m.get("serve_batches", 0) or 0)
    if batches:
        print(f"  batches={batches}  "
              f"windows={int(m.get('serve_batch_windows', 0))}  "
              f"occupancy={float(m.get('serve_batch_occupancy', 0)):.4f}"
              f"  queue_peak={int(m.get('serve_queue_depth_peak', 0))}  "
              f"tenant_wait={float(m.get('serve_tenant_wait_s', 0)):.3f}"
              f"s", file=out)
    rate = m.get("serve_jobs_per_min")
    if rate is not None:
        # Rate/occupancy gauges are only as fresh as their last stamp:
        # a snapshot much older than the trace's end (> the fleet
        # staleness budget, 5x the flush cadence) is flagged so nobody
        # reads a dead daemon's last throughput as current.
        stale = ""
        stamp = m.get("serve_rate_wall_s")
        if isinstance(stamp, (int, float)) and trace_end_unix:
            import os
            sys.path.insert(0, os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            from racon_tpu.obs.export import SUPERVISOR_STALE_FACTOR
            from racon_tpu.obs.fleet import DEFAULT_FLUSH_S
            age = float(trace_end_unix) - float(stamp)
            if age > SUPERVISOR_STALE_FACTOR * DEFAULT_FLUSH_S:
                stale = (f"  [STALE: gauges last updated {age:.1f}s "
                         f"before trace end]")
        print(f"  throughput: {float(rate):.4f} job(s)/min{stale}",
              file=out)
    if spans:
        # Batch points carry comma-joined tenant lists; split them so a
        # tenant's count includes every dispatch it rode in.
        by_tenant: Dict[str, int] = {}
        for s in spans:
            for tenant in str(s.get("tenant", "?")).split(","):
                by_tenant[tenant] = by_tenant.get(tenant, 0) + 1
        tenants = ", ".join(f"{t}: {n}" for t, n in
                            sorted(by_tenant.items()))
        print(f"  events by tenant: {tenants}", file=out)


def _render_hist(m, out) -> None:
    """The "latency:" section: p50/p95/p99 for every histogram family
    in the metrics snapshot, interpolated from the fixed log-spaced
    buckets declared in obs/metrics.HIST_BUCKETS. Snapshots with no
    recorded histograms print nothing."""
    m = m or {}
    hists = {k: v for k, v in sorted(m.items())
             if isinstance(v, dict) and "buckets" in v
             and int(v.get("count", 0) or 0)}
    if not hists:
        return
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from racon_tpu.obs.metrics import HIST_BUCKETS, hist_quantile
    print(f"\nlatency:  {'count':>6}  {'p50':>9}  {'p95':>9}  "
          f"{'p99':>9}  family", file=out)
    for name, h in hists.items():
        bounds = HIST_BUCKETS.get(name)
        if bounds is None:
            continue
        p50, p95, p99 = (hist_quantile(h, q, bounds)
                         for q in (0.50, 0.95, 0.99))
        print(f"{'':>8}  {int(h['count']):>6}  {p50:>9.4f}  "
              f"{p95:>9.4f}  {p99:>9.4f}  {name}", file=out)


def _render_job(root: str, trace_id: str, out=None) -> int:
    """The ``--job TRACE_ID`` mode: stitch one job's causal timeline
    out of every per-process trace under ``<root>/obs`` (the fleet
    merge step, obs/fleet.assemble_job_timeline), then render any
    flight-recorder dumps beside it and the aggregated latency
    histograms. Returns an exit code; refusals (no such trace, mixed
    runs) surface as errors, never empty reports."""
    import os
    if out is None:
        out = sys.stdout
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from racon_tpu.obs import flightrec
    from racon_tpu.obs.fleet import (FleetObsError, aggregate,
                                     assemble_job_timeline, obs_dir_for)
    try:
        tl = assemble_job_timeline(root, trace_id)
    except (FleetObsError, OSError) as exc:
        print(f"[obs_report] error: {exc}", file=sys.stderr)
        return 1
    print(f"job {tl['trace_id']}: {tl['n_spans']} span(s) across "
          f"{tl['n_processes']} process(es)", file=out)
    for src in sorted(tl["sources"]):
        print(f"  {src}: {tl['sources'][src]} span(s)", file=out)
    t_base = tl["spans"][0]["t_abs"] if tl["spans"] else 0.0
    print(f"\n{'t_rel_s':>9}  {'dur_s':>8}  {'source':<22}  span",
          file=out)
    for s in tl["spans"]:
        name = f"{s['kind']}/{s['name']}"
        extra = ""
        if s.get("kind") == "serve":
            extra = f"  job={s.get('job')} tenant={s.get('tenant')}"
        elif s.get("kind") == "gate":
            extra = f"  job={s.get('job')} tenant={s.get('tenant')}"
            if s.get("decision"):
                extra += f" decision={s.get('decision')}"
            if s.get("reason"):
                extra += f" reason={s.get('reason')}"
        elif "worker_id" in s:
            extra = f"  worker={s['worker_id']}"
        print(f"{s['t_abs'] - t_base:>9.3f}  {s['dur_s']:>8.3f}  "
              f"{s['src']:<22}  {name}{extra}", file=out)
    flights = flightrec.list_flights(obs_dir_for(root))
    for path in flights:
        try:
            fl = flightrec.load_flight(path)
        except ValueError as exc:
            print(f"\nflight {os.path.basename(path)}: unreadable "
                  f"({exc})", file=out)
            continue
        h = fl["header"]
        tear = "" if fl["clean"] else "  [TORN: clean prefix shown]"
        print(f"\nflight {os.path.basename(path)}: pid={h['pid']}  "
              f"reason={h['reason']}  {len(fl['events'])} event(s)"
              f"{tear}", file=out)
        for e in fl["events"][-8:]:
            print(f"  {json.dumps(e, sort_keys=True)}", file=out)
    try:
        _render_hist(aggregate(root).get("fleet", {}), out)
    except (FleetObsError, OSError):
        pass  # no metric shards next to the traces — timeline stands
    return 0


def _render_cache(m, by_kind, out) -> None:
    """The "cache:" section: result-store totals (hits/misses/stores/
    evictions/verify failures), the derived hit ratio and stored
    bytes, and per-tier event counts, from the ``cache_*`` metrics and
    ``cache`` points the content-addressed result cache records
    (docs/CACHE.md). Runs that never probed the cache print nothing."""
    m = m or {}
    cache = {k: v for k, v in m.items() if k.startswith("cache_")}
    spans = by_kind.get("cache", [])
    if not cache and not spans:
        return
    print(f"\ncache: hits={int(m.get('cache_hits_total', 0))}  "
          f"misses={int(m.get('cache_misses_total', 0))}  "
          f"stores={int(m.get('cache_stores_total', 0))}  "
          f"evictions={int(m.get('cache_evictions_total', 0))}  "
          f"verify_fail={int(m.get('cache_verify_fail_total', 0))}",
          file=out)
    ratio = m.get("cache_hit_ratio")
    if ratio is not None:
        print(f"  hit_ratio={float(ratio):.4f}  "
              f"bytes={int(m.get('cache_bytes', 0))}", file=out)
    if spans:
        by_tier: Dict[str, int] = {}
        for s in spans:
            key = f"{s.get('tier', '?')}/{s.get('outcome', '?')}"
            by_tier[key] = by_tier.get(key, 0) + int(s.get("n", 1))
        tiers = ", ".join(f"{t}: {n}" for t, n in
                          sorted(by_tier.items()))
        print(f"  events by tier: {tiers}", file=out)


def _render_ava(m, out) -> None:
    """The "ava:" section: the shape-bucket plan (targets, buckets vs
    the compile budget, quantum, padding overhead; docs/AVA.md). kC
    runs record no ``ava_*`` keys and print nothing."""
    m = m or {}
    if not any(k.startswith("ava_") for k in m):
        return
    print(f"\nava: targets={int(m.get('ava_targets', 0))}  "
          f"buckets={int(m.get('ava_buckets', 0))}"
          f"/{int(m.get('ava_compile_budget', 0))}  "
          f"quantum={int(m.get('ava_quantum', 0))}  "
          f"pad_frac={float(m.get('ava_pad_frac', 0) or 0):.4f}",
          file=out)


def _render_fleet(fleet_dir: str, out) -> None:
    """The "Fleet" section (``--fleet LEDGER_DIR``): the cross-worker
    view from the worker metric shards + events.jsonl — per-worker
    rates (stragglers flagged), merged counters, the autoscaler's
    supervisor heartbeat when one attached, and the per-shard lease
    timeline (claim/renew/steal/release/complete plus
    ``split->child`` markers, renew runs compressed; split-child
    lanes lead with their ancestry chain). Mixed-run
    shard directories raise FleetObsError in the aggregator; main()
    turns that into a clear exit-1 error."""
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from racon_tpu.obs.fleet import aggregate
    model = aggregate(fleet_dir)
    elastic = ""
    if model.get("splits") or model.get("spawns") or \
            model.get("retires"):
        elastic = (f"  splits={model.get('splits', 0)}  "
                   f"spawns={model.get('spawns', 0)}  "
                   f"retires={model.get('retires', 0)}")
    print(f"\nfleet: workers={model['n_workers']}  "
          f"steals={model['steals']}{elastic}  "
          f"run_fp={model['run_fp'][:12]}", file=out)
    sup = model.get("supervisor")
    if sup:
        done = "done" if sup.get("done") else "running"
        print(f"  supervisor: target={sup.get('target_workers', '?')}  "
              f"live={sup.get('live_workers', '?')}  "
              f"spawned={sup.get('spawned_total', '?')}  "
              f"retired={sup.get('workers_retired', 0)}  "
              f"evicted={sup.get('workers_evicted', 0)}  "
              f"[{done}]", file=out)
    print(f"  {'worker':>16}  {'windows/s':>9}  {'wall_s':>8}  "
          f"{'final':>5}  {'snapshots':>9}", file=out)
    for wid in sorted(model["workers"]):
        w = model["workers"][wid]
        seq = w.get("seq")
        flag = "  STRAGGLER" if w.get("straggler") else ""
        print(f"  {wid:>16}  {w['windows_per_sec']:>9.1f}  "
              f"{w['wall_s']:>8.2f}  "
              f"{'yes' if w['final'] else 'no':>5}  "
              f"{(seq + 1 if isinstance(seq, int) else '?'):>9}"
              f"{flag}",
              file=out)
        phases = w.get("phase_seconds", {})
        if phases:
            top = sorted(phases.items(), key=lambda kv: -kv[1])[:3]
            line = "  ".join(f"{name}={secs:.2f}s"
                             for name, secs in top)
            print(f"  {'':>16}  phases: {line}", file=out)
    stragglers = model.get("stragglers") or []
    if stragglers:
        print("  stragglers: " + ", ".join(stragglers) +
              "  (windows/s below the fleet-median fraction, "
              "obs/fleet.py)", file=out)
    timeline = model.get("timeline", {})
    lineage = model.get("lineage") or {}
    if timeline:
        print("  lease timeline:", file=out)
        t_base = min((e["t"] for lane in timeline.values()
                      for e in lane if isinstance(e.get("t"),
                                                  (int, float))),
                     default=0.0)
        for name in sorted(timeline):
            parts = []
            for e in timeline[name]:
                t = e.get("t")
                at = (f"@{t - t_base:.1f}s"
                      if isinstance(t, (int, float)) else "")
                if e["ev"] == "renew":
                    parts.append(f"renew x{e['n']} [{e['worker']}]")
                elif e["ev"] == "steal":
                    parts.append(
                        f"steal [{e['worker']}<-{e.get('victim')}] "
                        f"{at}")
                elif e["ev"] == "split":
                    parts.append(f"split->{e.get('child')} "
                                 f"[{e['worker']}] {at}")
                else:
                    parts.append(f"{e['ev']} [{e['worker']}] {at}")
            # A split child's lane leads with its full ancestry so the
            # reader can trace every donated range back to its seed
            # shard without cross-referencing lanes.
            chain, seen = [], set()
            parent = lineage.get(name)
            while parent is not None and parent not in seen:
                seen.add(parent)
                chain.append(parent)
                parent = lineage.get(parent)
            anc = (" (< " + " < ".join(chain) + ")") if chain else ""
            print(f"    {name}{anc}: " + " -> ".join(parts), file=out)


def _render_redo(m, out) -> None:
    """The "Redo" section: where flagged windows were resolved (the
    on-device wide-band pass vs the host fallback) and the walk's
    dependent-gather chain length, from the ``redo_*`` counters and the
    ``walk_chain_len`` gauge (docs/KERNELS.md "Wide-band device redo").
    Runs with no flagged windows print only the chain gauge."""
    m = m or {}
    passes = int(m.get("redo_passes", 0) or 0)
    chain = m.get("walk_chain_len")
    if not passes and chain is None:
        return
    if passes:
        dev = int(m.get("redo_device_windows", 0))
        host = int(m.get("redo_host_windows", 0))
        tail = "" if host else "  (host untouched mid-polish)"
        print(f"\nredo: passes={passes}  device_windows={dev}  "
              f"host_windows={host}{tail}", file=out)
    if chain is not None:
        lead = "" if passes else "\n"
        print(f"{lead}walk chain: {int(chain)} dependent gather(s) "
              "per column scan", file=out)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(argv if argv is not None else sys.argv[1:])
    do_validate = "--validate" in argv
    argv = [a for a in argv if a != "--validate"]
    fleet_dir = None
    if "--fleet" in argv:
        i = argv.index("--fleet")
        try:
            fleet_dir = argv[i + 1]
        except IndexError:
            print("[obs_report] error: --fleet needs a ledger/obs "
                  "directory", file=sys.stderr)
            return 2
        del argv[i:i + 2]
    job_trace = None
    if "--job" in argv:
        i = argv.index("--job")
        try:
            job_trace = argv[i + 1]
        except IndexError:
            print("[obs_report] error: --job needs a trace id",
                  file=sys.stderr)
            return 2
        del argv[i:i + 2]
    paths = [a for a in argv if not a.startswith("--")]
    if len(paths) != 1 or len(argv) != len(paths):
        print("usage: obs_report.py TRACE.jsonl [--validate] "
              "[--fleet LEDGER_DIR] | obs_report.py ROOT_DIR "
              "--job TRACE_ID", file=sys.stderr)
        return 2
    if job_trace is not None:
        # --job mode: the positional is a run/ledger root holding an
        # obs/ directory of per-process traces, not a single trace.
        return _render_job(paths[0], job_trace)
    try:
        tr = load_trace(paths[0])
    except (OSError, TraceError) as exc:
        print(f"[obs_report] error: {exc}", file=sys.stderr)
        return 1
    if do_validate:
        errs = validate(tr)
        if errs:
            for e in errs:
                print(f"[obs_report] invalid: {e}", file=sys.stderr)
            return 1
        print(f"[obs_report] valid: {len(tr['spans'])} spans, "
              f"schema {tr['begin'].get('schema')}")
        return 0
    try:
        render(tr, fleet_dir=fleet_dir)
    except Exception as exc:
        # FleetObsError (mixed run_fp shards, empty obs dir) and
        # unreadable ledgers surface as a clear error, never a silent
        # partial report.
        from racon_tpu.obs.fleet import FleetObsError
        if not isinstance(exc, (FleetObsError, OSError)):
            raise
        print(f"[obs_report] error: {exc}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
