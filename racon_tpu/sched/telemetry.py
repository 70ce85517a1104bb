"""Round telemetry for the convergence scheduler.

Counters only — every value is fed from flags the scheduler already
pulls to the host for control flow, so recording costs no extra device
syncs. Reporting routes through the metrics registry
(racon_tpu/obs/metrics.py): ``publish_sched`` writes the canonical
``sched_*`` keys the polisher's stderr summary and the trace's metrics
snapshot both read, so the serialized and printed views cannot drift (keys
documented in docs/SCHEDULER.md and docs/OBSERVABILITY.md).
"""

from __future__ import annotations

from typing import Dict, List


class SchedTelemetry:
    """Per-run convergence counters.

    ``rounds`` is the engine's total round count R (refine_rounds + 1).
    A window's ``rounds_used`` is the number of refinement rounds it
    actually executed before freezing: R means it never converged early
    (or the schedule is too short to detect), smaller values are the
    scheduler's win. Overflow (host-redo) windows freeze early too and
    count at their freeze round — their device rounds stop mattering
    the moment the sticky flag rises.
    """

    def __init__(self, rounds: int):
        self.rounds = int(rounds)
        self.windows = 0                  # real windows entering the sched
        self.chunks = 0
        # rounds_used -> windows frozen after exactly that many rounds
        self.hist: Dict[int, int] = {}
        # windows that EXECUTED round r (r -> count); survivor fractions
        # derive from this against self.windows
        self._alive: Dict[int, int] = {}
        self.repack_s = 0.0               # host planning + index h2d
        self.dispatches_saved = 0         # round-dispatches early-exited

    # ------------------------------------------------------------ recording

    def record_chunk(self, n_windows: int) -> None:
        self.chunks += 1
        self.windows += int(n_windows)

    def record_round(self, r: int, n_alive: int) -> None:
        """``n_alive`` windows executed refinement round ``r``."""
        self._alive[int(r)] = self._alive.get(int(r), 0) + int(n_alive)

    def record_freeze(self, rounds_used: int, n_windows: int) -> None:
        if n_windows:
            k = int(rounds_used)
            self.hist[k] = self.hist.get(k, 0) + int(n_windows)

    def record_repack(self, seconds: float) -> None:
        self.repack_s += float(seconds)

    def record_skip(self, n_dispatches: int) -> None:
        """A chunk fully converged with ``n_dispatches`` rounds unrun."""
        self.dispatches_saved += int(n_dispatches)

    # ------------------------------------------------------------- reporting

    def survivor_frac(self) -> List[float]:
        """Fraction of windows that executed round r, for r in 0..R-1."""
        if not self.windows:
            return [0.0] * self.rounds
        return [self._alive.get(r, 0) / self.windows
                for r in range(self.rounds)]

    def rounds_saved_frac(self) -> float:
        """Fraction of total window-rounds the scheduler skipped."""
        if not self.windows:
            return 0.0
        executed = sum(self._alive.get(r, 0) for r in range(self.rounds))
        return 1.0 - executed / (self.windows * self.rounds)

    def as_extras(self) -> Dict[str, object]:
        """JSON-serializable counters (the registry's sched_* keys)."""
        from racon_tpu.obs.metrics import (MetricsRegistry, publish_sched,
                                           sched_extras)
        reg = MetricsRegistry()
        publish_sched(self, reg)
        return sched_extras(reg)

    def summary(self) -> str:
        """One line for the polisher's stderr log (registry-formatted)."""
        from racon_tpu.obs.metrics import (MetricsRegistry, publish_sched,
                                           sched_summary_line)
        reg = MetricsRegistry()
        publish_sched(self, reg)
        return sched_summary_line(reg)
