"""Reduce a JAX profiler trace to device busy time, kernel time and gaps.

Input: the ``*.xplane.pb`` that ``jax.profiler.start_trace`` wrote for
one traced job. Output (``reduce_file``):

- ``window_s``: the traced interval: the program's ``run:racon_tpu``
  span (bridged into the trace by ``RACON_TPU_TRACE_XPROF``), else the
  extent of all device and annotation events;
- ``busy_by_chip``: for each chip of the cell, in device order, the
  union of its operations' intervals inside the window; a chip that ran
  no operation is busy 0 s. ``busy_s`` is their mean, ``idle_share``
  ``1 - busy_s/window``;
- ``programs``: for each jitted program (HLO module, by name), the
  device seconds of each operation name inside it, summed over chips.
  A Pallas kernel is one operation named after its kernel function
  (``fw_dirs_band``, ``fw_dirs_band_tile``), so a kernel's time inside
  the programs that call it is read from here;
- ``device_ops``: the ten ``program/operation`` names with the most
  device time, control-flow operations (which contain others) left out;
- ``idle_gaps``: the ten host activities under which the chips sat
  idle longest, averaged over the chips (a chip that ran nothing is
  idle over the whole window): each gap between a chip's operations is
  named by the innermost program span open on the host across its middle
  (``kind:name``, e.g. ``dispatch:ovl_chunk``), else by the program's
  logger phase open then (``phase:<name>``, from the job's span file,
  put on the trace's clock by the ``run`` span that both record), else
  ``no span``; the gaps' seconds are summed per name.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

RUN_SPAN = "run:racon_tpu"
# The trace names an operation by its HLO text ("%fw_dirs_band.1 = (...)
# custom-call(...)") and a program by its jitted name and a fingerprint
# ("jit_sched_rounds(1181...)"); both are shortened to the name alone.
OP_NAME = re.compile(r"^%?([A-Za-z_][\w-]*?)(?:\.\d+)?\s*=")
PROGRAM_NAME = re.compile(r"\(\d+\)$")
# The overlap aligner's programs (ops/ovl_align.py); every other
# program the polisher runs belongs to the consensus path.
OVERLAP_PROGRAMS = re.compile(r"breaking_points")
# Control-flow operations contain the operations of their bodies.
CONTAINERS = {"while", "conditional", "call"}
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# Program spans carry "<kind>:<name>" (racon_tpu/obs/trace.py).
SPAN_NAME = re.compile(r"^[a-z_]+:[A-Za-z0-9_./-]+$")

Interval = Tuple[float, float]


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def op_name(text: str) -> str:
    m = OP_NAME.match(text)
    return m.group(1) if m else text.split(" ")[0][:40]


def program_name(text: str) -> str:
    return PROGRAM_NAME.sub("", text)


def _module_of(ev, modules: List[Tuple[float, float, str]],
               starts: List[float]) -> str:
    """The program running at the operation's middle (programs run one
    at a time on a chip)."""
    mid = ev.start_ns + ev.duration_ns / 2
    i = bisect.bisect_right(starts, mid) - 1
    if i >= 0 and modules[i][0] <= mid <= modules[i][1]:
        return modules[i][2]
    return "unknown"


def load_phases(spans_path: str) -> Tuple[Optional[float], List]:
    """``(run_t0_s, [(t0_s, t1_s, "phase:<name>"), ...])`` from the
    program's JSONL span file (racon_tpu/obs/trace.py), on its own
    clock; ``(None, [])`` when it is missing or has no run span."""
    run_t0, phases = None, []
    try:
        with open(spans_path) as f:
            for line in f:
                rec = json.loads(line)
                if rec.get("ev") != "span":
                    continue
                if rec["kind"] == "run":
                    run_t0 = rec["t0"]
                elif rec["kind"] == "phase":
                    name = rec["name"].split("]")[-1].strip()
                    phases.append((rec["t0"], rec["t0"] + rec["dur_s"],
                                   "phase:" + name.replace(" ", "_")))
    except (OSError, ValueError, KeyError):
        return None, []
    return run_t0, phases


def reduce_xspace(pd, phases=(None, []), chips: Optional[int] = None
                  ) -> Optional[Dict]:
    """The reduction of one loaded ``jax.profiler.ProfileData`` (with
    the job's logger phases from :func:`load_phases`, if any) over the
    cell's ``chips``: devices 0 to ``chips - 1``, each counted whether
    or not it ran an operation (None: every device plane the trace
    holds). None when no such chip ran an operation."""
    ops_by_dev: Dict[int, List[Tuple[float, float, str, str]]] = {}
    planes: List[int] = []
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            planes.append(int(m.group(1)))
            lines = {ln.name: ln for ln in plane.lines}
            if OPS_LINE not in lines:
                continue
            modules = []
            if MODULES_LINE in lines:
                modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                  program_name(e.name))
                                 for e in lines[MODULES_LINE].events)
            starts = [m[0] for m in modules]
            ops = [(e.start_ns, e.start_ns + e.duration_ns, op_name(e.name),
                    _module_of(e, modules, starts))
                   for e in lines[OPS_LINE].events if e.duration_ns > 0]
            if ops:
                ops_by_dev[planes[-1]] = ops
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if SPAN_NAME.match(e.name):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if chips is None:
        chips = max(planes, default=-1) + 1
    ops_by_dev = {d: ops for d, ops in ops_by_dev.items() if d < chips}
    if not ops_by_dev:
        return None
    runs = [(s, e) for s, e, n in spans if n == RUN_SPAN]
    if runs:
        lo, hi = min(s for s, _ in runs), max(e for _, e in runs)
    else:
        every = [t for ops in ops_by_dev.values() for o in ops
                 for t in o[:2]]
        every += [t for s in spans for t in s[:2]]
        lo, hi = min(every), max(every)
    window_ns = hi - lo
    # The phases' clock starts at the tracer's creation; the run span is
    # on both clocks.
    if runs and phases[0] is not None:
        shift = min(s for s, _ in runs) - phases[0] * 1e9
        named_phases = [(t0 * 1e9 + shift, t1 * 1e9 + shift, n)
                        for t0, t1, n in phases[1]]
    else:
        named_phases = []

    busy_ns = [0.0] * chips
    programs: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    by_op: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    # What names an idle instant: the innermost program span open then,
    # else the logger phase open then.
    labels = [(s, e, n, (0, e - s)) for s, e, n in spans if n != RUN_SPAN]
    labels += [(s, e, n, (1, e - s)) for s, e, n in named_phases]
    for dev in range(chips):
        inside = [o for o in ops_by_dev.get(dev, ())
                  if o[1] > lo and o[0] < hi]
        for s, e, name, module in inside:
            d = (min(e, hi) - max(s, lo)) / 1e9
            programs[module][name] += d
            if name not in CONTAINERS:
                by_op[f"{module}/{name}"] += d
        busy = _clip(_union([(o[0], o[1]) for o in inside]), lo, hi)
        busy_ns[dev] = sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for name, ns in _label_time(idle, labels).items():
            gaps[name] += ns / 1e9
    mean_ns = sum(busy_ns) / chips
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:10]]
    return {"window_s": window_ns / 1e9,
            "busy_s": mean_ns / 1e9,
            "idle_share": 1.0 - mean_ns / window_ns,
            "chips": chips,
            "devices": len(ops_by_dev),
            "busy_by_chip": [ns / 1e9 for ns in busy_ns],
            "programs": {m: dict(v) for m, v in programs.items()},
            "device_ops": top(by_op),
            "idle_gaps": top({k: v / chips for k, v in gaps.items()})}


def _label_time(idle: List[Interval], labels) -> Dict[str, float]:
    """Nanoseconds of the ``idle`` intervals under each label: a sweep
    over every boundary, each piece going to the active label of least
    ``(kind, duration)``, or to ``no span``."""
    points = []
    for s, e in idle:
        points += [(s, 1, None), (e, -1, None)]
    for lab in labels:
        points += [(lab[0], 1, lab), (lab[1], -1, lab)]
    points.sort(key=lambda p: (p[0], p[1]))
    out: Dict[str, float] = defaultdict(float)
    active = set()
    in_gap = 0
    last = None
    for t, step, lab in points:
        if in_gap and last is not None and t > last:
            best = min(active, key=lambda a: a[3], default=None)
            out[best[2] if best else "no span"] += t - last
        if lab is None:
            in_gap += step
        elif step > 0:
            active.add(lab)
        else:
            active.discard(lab)
        last = t
    return out


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def reduce_file(path: str, spans_path: Optional[str] = None,
                chips: Optional[int] = None) -> Optional[Dict]:
    from jax.profiler import ProfileData
    phases = load_phases(spans_path) if spans_path else (None, [])
    return reduce_xspace(ProfileData.from_file(path), phases, chips)


def reduce_dir(trace_dir: str, spans_path: Optional[str] = None,
               chips: Optional[int] = None) -> Optional[Dict]:
    """The reduction of the newest trace under ``trace_dir`` over the
    cell's ``chips``; None when there is none or no chip of the cell
    ran an operation."""
    path = find_xplane(trace_dir)
    return reduce_file(path, spans_path, chips) if path else None


def kernel_seconds(red: Optional[Dict], kernel: str, overlap: bool
                   ) -> Optional[float]:
    """Device seconds of the operations named by the regular expression
    ``kernel`` inside the overlap-alignment programs (``overlap``) or
    inside every other program (the consensus path); None when the
    trace holds no such operation."""
    if red is None:
        return None
    kre = re.compile(kernel)
    hits = [t for m, ops in red["programs"].items()
            if bool(OVERLAP_PROGRAMS.search(m)) == overlap
            for n, t in ops.items() if kre.search(n)]
    return sum(hits) if hits else None
