"""On-device wide-band redo: second pass for flagged windows.

Windows whose consensus outgrew the chunk's padded anchor width, whose
banded optimum failed the escape certificate, or whose walk saturated an
up-run counter come back from collect_chunk as ``None`` entries (the
sticky ``ovf`` flag). Through PR 7 every such window bounced to the
unbounded HOST consensus (PoaEngine._redo_trunc) — correct, but it
breaks SPMD cleanliness: one straggler window serializes the whole
process behind a CPU re-polish.

This module re-runs the flagged subset ON DEVICE first, through the
same ChunkPlan / dispatch_chunk / collect_chunk machinery with two
budgets widened:

* **anchor slack** — ``la_grow`` quadruples (4 * LA_GROW = 256 growth
  slots), so a consensus that legitimately outgrew the first pass's LA
  padding fits the redo's;
* **band width** — the plan's band doubles (2x-W), clamped to the
  LA - 128 ceiling the banded kernel needs; past the clamp the redo
  runs FULL-WIDTH (band_w = 0), which cannot fail the escape
  certificate at all.

Windows still flagged after the wide pass are returned to the caller
for the host fallback. Exactly two classes can remain: saturated
up-run counters (the packed-byte U field caps at U_SAT — no band width
changes the alignment's up-runs; see ops/colwalk.py) and windows whose
consensus outgrew even the quadrupled slack. Neither occurs at bench
geometry, so the host redo becomes a final fallback that never fires
there — the redo smoke (scripts/redo_smoke.py) pins exactly that, and
byte-identity with the host path rides the engine's existing
device == host contract (the redo runs the same program, just wider).

``RACON_TPU_REDO=0`` disables the device pass (PR 5/7 behavior: every
flagged window host-repolishes). Counters: obs record_redo publishes
``redo_device_windows`` / ``redo_host_windows`` / ``redo_passes``.

Redo dispatches stay FUSED forward+walk even when the streaming
executor runs the decoupled-walk stage (ops/colwalk.py): a redo is
rare tail work serialized behind the chunk it repairs — there is no
following forward dispatch to hide its walk behind, so decoupling it
would add a dispatch boundary for zero overlap.
"""

from __future__ import annotations

import os
from racon_tpu.utils import envspec
from typing import List, Tuple

import numpy as np

REDO_ENV = "RACON_TPU_REDO"


def redo_enabled() -> bool:
    """The wide-band device redo is on unless RACON_TPU_REDO=0 (the
    host consensus redo is the fallback either way — off just means
    every flagged window takes it)."""
    return envspec.read(REDO_ENV) not in ("0", "false")


def _widen(plan) -> None:
    """Widen a redo ChunkPlan's band in place: 2x the first-pass width,
    full-width past the LA - 128 ceiling (a band that wide would not
    beat the full kernel, and full width cannot fail the certificate)."""
    if plan.band_w:
        w2 = 2 * plan.band_w
        plan.band_w = w2 if w2 + 128 <= plan.LA else 0


def device_redo(windows: List, *, match: int, mismatch: int, gap: int,
                ins_scale, rounds: int, mesh=None, jobs_cap: int = 2048,
                log=None
                ) -> Tuple[List[Tuple[object, bytes, np.ndarray]], List]:
    """Re-run flagged windows through a wide-band device pass.

    Returns ``(resolved, remaining)``: ``resolved`` is a list of
    (window, consensus codes bytes, coverage array) the caller applies;
    ``remaining`` the windows that must take the host path (still
    flagged after the wide pass, over the element budget even at the
    minimum chunk, or a retry-exhausted dispatch).
    """
    from racon_tpu.obs.trace import get_tracer
    from racon_tpu.ops.device_poa import (ChunkPlan, LA_GROW,
                                          MAX_DIR_ELEMS, collect_chunk,
                                          dispatch_chunk)
    from racon_tpu.resilience.retry import RetryExhausted

    tracer = get_tracer()
    ndp = mesh.shape["dp"] if mesh is not None else 1
    resolved: List[Tuple[object, bytes, np.ndarray]] = []
    remaining: List = []

    # Redo sets are small (a handful of windows per run at realistic
    # noise), so chunking stays simple: greedy groups under the job cap,
    # each its own plan — the widened geometry is a fresh executable
    # anyway, and sharing the first pass's caps would defeat the point.
    groups: List[List] = []
    cur: List = []
    jobs = 0
    for w in windows:
        if cur and jobs + w.n_layers > jobs_cap:
            groups.append(cur)
            cur, jobs = [], 0
        cur.append(w)
        jobs += w.n_layers
    if cur:
        groups.append(cur)

    for k, ws in enumerate(groups):
        plan = ChunkPlan(ws, la_grow=4 * LA_GROW, n_shards=ndp)
        _widen(plan)
        cols = plan.band_w if plan.band_w else plan.LA
        if plan.B // ndp * plan.Lq * cols > MAX_DIR_ELEMS:
            # The widened geometry overflows the flat-index budget even
            # for this (already minimal) group: host path, not a
            # silently narrower redo.
            remaining.extend(ws)
            continue
        try:
            with tracer.span("chunk", f"redo{k}", windows=len(ws),
                             lanes=plan.B, jobs=plan.n_jobs):
                packed = dispatch_chunk(
                    plan, match=match, mismatch=mismatch, gap=gap,
                    ins_scale=ins_scale, rounds=rounds, mesh=mesh)
                codes, covs = collect_chunk(plan, packed)
        except RetryExhausted:
            remaining.extend(ws)
            continue
        for w, c, cv in zip(ws, codes, covs):
            if c is None:
                remaining.append(w)
            else:
                resolved.append((w, c, cv))
    return resolved, remaining
