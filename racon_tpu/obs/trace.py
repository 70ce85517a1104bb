"""Structured run tracer: nested spans as JSONL.

Disabled by default (the module-level :data:`NULL` tracer is a no-op on
every call — no file handle, no clock reads beyond the caller's own).
Enable by pointing ``RACON_TPU_TRACE`` at a file path, or pass
``--trace <path>`` to the CLI (which calls :func:`configure`).

Trace format (one JSON object per line):

- ``{"ev": "begin", "schema": 1, "unix_time": ..., "unix_ns": ...}`` —
  first line; ``unix_ns`` is the epoch time of ``t0 = 0``, so
  ``unix_ns + t0 * 1e9`` puts a span on the JAX profiler's clock.
- ``{"ev": "span", "id": N, "parent": M|null, "kind": ..., "name": ...,
  "t0": seconds-since-begin, "dur_s": ..., ...attrs}`` — one line per
  *closed* span; children therefore appear before their parent. ``kind``
  is one of run/phase/chunk/round/dispatch/transfer (plus free-form
  kinds from future callers); numeric attrs (bytes, lanes, rounds, ...)
  ride at the top level of the object.
- ``{"ev": "metrics", ...}`` — a metrics-registry snapshot, written by
  :meth:`Tracer.finish` (the CLI and bench call it on exit).

``RACON_TPU_TRACE_XPROF=1`` additionally wraps every span in a
``jax.profiler.TraceAnnotation`` so spans land in XLA device profiles;
it is off by default because it imports jax at first span.

Spans nest per thread (a thread-local stack supplies ``parent``); file
writes are serialized by a lock. Close-time emission keeps the hot path
to two ``time.perf_counter()`` calls and one dict build per span.
"""

from __future__ import annotations

import json
import os
from racon_tpu.utils import envspec
import threading
import time
from typing import Optional

SCHEMA_VERSION = 1

ENV_TRACE = "RACON_TPU_TRACE"
ENV_XPROF = "RACON_TPU_TRACE_XPROF"
ENV_TRACE_CTX = "RACON_TPU_TRACE_CTX"

# How many hex chars of the JobSpec fingerprint become the trace id.
TRACE_ID_LEN = 16


class TraceContext:
    """Cross-process trace correlation: ``trace_id`` names the job (a
    prefix of the JobSpec fingerprint, so every process that polishes
    the same job derives the same id) and ``parent_id`` is the span id,
    in the minting process, that causally precedes the handoff. The
    encoded form (``"<trace_id>:<parent_id>"``) rides the
    ``RACON_TPU_TRACE_CTX`` environment variable and the ledger's
    ``meta.json``; :func:`parse_trace_ctx` treats anything malformed as
    absent, so a garbled handoff degrades to a fresh root trace instead
    of crashing the worker."""

    __slots__ = ("trace_id", "parent_id")

    def __init__(self, trace_id: str, parent_id: int):
        self.trace_id = trace_id
        self.parent_id = parent_id

    def encode(self) -> str:
        return f"{self.trace_id}:{self.parent_id}"

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"TraceContext({self.trace_id!r}, {self.parent_id})"

    def __eq__(self, other) -> bool:
        return (isinstance(other, TraceContext)
                and other.trace_id == self.trace_id
                and other.parent_id == self.parent_id)


def mint_trace_context(fingerprint: str, parent_id: int = 0) -> TraceContext:
    """Derive a job's trace context from its run fingerprint and the
    span that minted it (the daemon's ``serve submitted`` point)."""
    return TraceContext(str(fingerprint)[:TRACE_ID_LEN], int(parent_id))


def parse_trace_ctx(text) -> Optional[TraceContext]:
    """Decode ``"<trace_id>:<parent_id>"``; None on anything malformed
    (empty, missing separator, non-integer parent, blank id)."""
    if not text or not isinstance(text, str):
        return None
    head, sep, tail = text.strip().partition(":")
    if not sep or not head:
        return None
    try:
        parent = int(tail)
    except ValueError:
        return None
    return TraceContext(head, parent)


def env_trace_ctx() -> str:
    """The raw (already-validated) encoded context from the
    environment, or "" — the ledger stores this string verbatim in
    meta.json so late-joining workers can adopt it."""
    ctx = parse_trace_ctx(envspec.read(ENV_TRACE_CTX))
    return ctx.encode() if ctx is not None else ""


def adopt_trace_context(encoded=None, tracer=None) -> Optional[TraceContext]:
    """Adopt a handed-off trace context into the process tracer's
    span context. ``encoded=None`` reads ``RACON_TPU_TRACE_CTX``.
    Malformed or absent input is NOT an error: the process keeps a
    fresh root trace (returns None, sets nothing). Never raises."""
    if encoded is None:
        try:
            encoded = envspec.read(ENV_TRACE_CTX)
        except Exception:
            return None
    ctx = parse_trace_ctx(encoded)
    if ctx is None:
        return None
    tr = tracer if tracer is not None else get_tracer()
    tr.set_context(trace_id=ctx.trace_id, parent_id=ctx.parent_id)
    return ctx


class _NullSpan:
    """Shared no-op span: context manager with inert add/end."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **attrs) -> "_NullSpan":
        return self

    def end(self) -> None:
        pass


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every operation is a no-op."""

    enabled = False

    def span(self, kind: str, name: str, **attrs):
        return _NULL_SPAN

    def emit(self, kind: str, name: str, t0_perf: float, dur_s: float,
             **attrs) -> int:
        return 0

    def point(self, kind: str, name: str, dur_s: float = 0.0,
              **attrs) -> int:
        return 0

    def set_context(self, **attrs) -> None:
        pass

    def snapshot_stack(self) -> list:
        return []

    def install_stack(self, stack: list) -> None:
        pass

    def finish(self, metrics: Optional[dict] = None) -> None:
        pass


NULL = NullTracer()


class _Span:
    __slots__ = ("tracer", "id", "parent", "kind", "name", "attrs",
                 "t0", "_xprof", "_done")

    def __init__(self, tracer: "Tracer", kind: str, name: str, attrs: dict):
        self.tracer = tracer
        self.kind = kind
        self.name = name
        self.attrs = attrs
        self._xprof = None
        self._done = False
        self.id, self.parent = tracer._push(self)
        self.t0 = time.perf_counter()
        if tracer._xprof:
            try:
                import jax
                self._xprof = jax.profiler.TraceAnnotation(
                    f"{kind}:{name}")
                self._xprof.__enter__()
            except Exception:
                self._xprof = None

    def add(self, **attrs) -> "_Span":
        """Attach counters to the span (merged into its JSONL record)."""
        self.attrs.update(attrs)
        return self

    def end(self) -> None:
        if self._done:
            return
        self._done = True
        dur = time.perf_counter() - self.t0
        if self._xprof is not None:
            try:
                self._xprof.__exit__(None, None, None)
            except Exception:
                pass
        self.tracer._pop(self, dur)

    def __enter__(self) -> "_Span":
        return self

    def __exit__(self, *exc) -> bool:
        self.end()
        return False


class Tracer:
    """JSONL span writer (see module docstring for the format)."""

    enabled = True

    def __init__(self, path: str):
        self.path = path
        # The origin on both clocks: the tightest of three back-to-back
        # (perf_counter, epoch ns) pairs, so ``unix_ns + t0 * 1e9``
        # puts any span on the profiler's epoch clock.
        _, self._t0, unix_ns = min(
            (b - a, (a + b) / 2, u) for a, u, b in
            ((time.perf_counter(), time.time_ns(), time.perf_counter())
             for _ in range(3)))
        self._lock = threading.Lock()
        self._local = threading.local()
        # Ids start at 1: a TraceContext's parent_id of 0 means "no
        # parent span" (fresh root), so no real span may claim it.
        self._next_id = 1                 # guarded-by: _lock
        # Process-wide span attributes (worker_id/shard/run_fp) merged
        # into every span record; explicit span attrs win on key clash.
        self._context: dict = {}          # guarded-by: _lock
        self._xprof = envspec.read(ENV_XPROF) not in ("", "0",
                                                            "false")
        # Spans stream to a ``.part`` sidecar; finish() promotes it to
        # ``path`` atomically, so readers of ``path`` never observe a
        # half-written trace (a killed run leaves only the sidecar).
        self._part = path + ".part"
        self._fh = open(self._part, "w",  # lint: atomic-ok (streamed sidecar; finish() promotes via atomic_finalize)
                        encoding="utf-8")
        self._write({"ev": "begin", "schema": SCHEMA_VERSION,
                     "unix_time": unix_ns / 1e9, "unix_ns": unix_ns})

    # ------------------------------------------------------------- internals

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _write(self, obj: dict) -> None:
        line = json.dumps(obj, separators=(",", ":"))
        if obj.get("ev") == "span":
            from racon_tpu.obs import flightrec
            flightrec.note_span(obj)
        with self._lock:
            if self._fh is None:
                return
            self._fh.write(line + "\n")
            self._fh.flush()

    def _push(self, span: _Span):
        st = self._stack()
        parent = st[-1].id if st else None
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        st.append(span)
        return sid, parent

    def _pop(self, span: _Span, dur: float) -> None:
        st = self._stack()
        # Tolerate out-of-order ends (manual .end() mixed with with-blocks):
        # remove the span wherever it sits.
        if span in st:
            st.remove(span)
        self._write({"ev": "span", "id": span.id, "parent": span.parent,
                     "kind": span.kind, "name": span.name,
                     "t0": round(span.t0 - self._t0, 6),
                     "dur_s": round(dur, 6),
                     **self._context, **span.attrs})

    # ------------------------------------------------------------ public API

    def span(self, kind: str, name: str, **attrs) -> _Span:
        """Open a nested span; close with ``with`` or ``.end()``."""
        return _Span(self, kind, name, attrs)

    def emit(self, kind: str, name: str, t0_perf: float, dur_s: float,
             **attrs) -> int:
        """Record a span that already ran, from its own perf_counter
        start (utils/logger.py phases use this: the logger only learns
        the phase name when the phase ends). Returns the span id so
        callers can mint a :class:`TraceContext` parented on it."""
        st = self._stack()
        parent = st[-1].id if st else None
        with self._lock:
            sid = self._next_id
            self._next_id += 1
        self._write({"ev": "span", "id": sid, "parent": parent,
                     "kind": kind, "name": name,
                     "t0": round(max(t0_perf - self._t0, 0.0), 6),
                     "dur_s": round(max(dur_s, 0.0), 6),
                     **self._context, **attrs})
        return sid

    def point(self, kind: str, name: str, dur_s: float = 0.0,
              **attrs) -> int:
        """Record an instantaneous-ish event (e.g. one transfer) ending
        now, with ``dur_s`` of lead time. Returns the span id."""
        return self.emit(kind, name, time.perf_counter() - dur_s, dur_s,
                         **attrs)

    def set_context(self, **attrs) -> None:
        """Merge process-wide attributes (``worker_id``/``shard``/
        ``run_fp``) into every subsequent span record. ``None`` values
        drop the key — workers call ``set_context(shard=None)`` when a
        lease is released. Explicit per-span attrs shadow the context
        on clashes, so recorders keep full control of their own keys."""
        with self._lock:
            for k, v in attrs.items():
                if v is None:
                    self._context.pop(k, None)
                else:
                    self._context[k] = v

    def snapshot_stack(self) -> list:
        """A COPY of the calling thread's open-span stack, for handing
        to a helper thread (watchdog guard workers) so spans it emits
        keep their parents."""
        return list(self._stack())

    def install_stack(self, stack: list) -> None:
        """Adopt ``stack`` (from :meth:`snapshot_stack`) as THIS
        thread's span stack. The list is copied, so a thread abandoned
        mid-job can never corrupt the donor's stack; spans opened and
        closed on this thread pop themselves as usual, and spans from
        the donor stack are parent references only — this thread must
        not close them."""
        self._local.stack = list(stack)

    def finish(self, metrics: Optional[dict] = None) -> None:
        """Write a final metrics snapshot, then atomically promote the
        ``.part`` sidecar to the configured path."""
        if metrics:
            self._write({"ev": "metrics", **metrics})
        with self._lock:
            if self._fh is None:
                return
            self._fh.close()
            self._fh = None
        from racon_tpu.utils.atomicio import atomic_finalize
        atomic_finalize(self._part, self.path)


_tracer: Optional[object] = None


def configure(path: Optional[str] = None):
    """Install the process tracer. ``path=None`` reads RACON_TPU_TRACE;
    empty/unset keeps tracing disabled. Idempotent for the same path;
    a new path replaces (and closes) the previous tracer."""
    global _tracer
    path = path or envspec.read(ENV_TRACE)
    if not path:
        if _tracer is None:
            _tracer = NULL
        return _tracer
    if isinstance(_tracer, Tracer):
        if _tracer.path == path:
            return _tracer
        _tracer.finish()
    _tracer = Tracer(path)
    return _tracer


def get_tracer():
    """The process tracer; configures from the environment on first use
    so library runs honor RACON_TPU_TRACE without CLI involvement."""
    if _tracer is None:
        return configure()
    return _tracer
