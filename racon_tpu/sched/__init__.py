"""Convergence-aware refinement scheduling (docs/SCHEDULER.md).

The fixed-round device engine (ops/device_poa.py) runs every window
through all ``refine_rounds + 1`` alignment+merge rounds; on real
polishing data most windows reach a fixed point by round 2 and the
remaining rounds replay them unchanged. This subsystem sits between the
polisher's chunk planner and the device engine and

  (a) detects per-window fixed points ON DEVICE — a cheap reduction
      appended to the merge step (ops/device_merge.aggregate_flags /
      converged_windows);
  (b) freezes converged windows immediately: every round also assembles
      the SAME votes at the final-round insertion scale, so a frozen
      window's output is bit-identical to what the fixed engine's last
      round would produce (see sched/rounds.py for the argument);
  (c) repacks surviving lanes into dense bucketed batches between
      rounds (sched/repack.py) and early-exits whole dispatches when a
      chunk fully converges;
  (d) emits round telemetry (sched/telemetry.py) through
      utils/logger.py and into the metrics registry.

``RACON_TPU_SCHED=0`` falls back to the fixed-round single-dispatch
engine.
"""

from racon_tpu.sched.repack import RepackPlan
from racon_tpu.sched.scheduler import ConvergenceScheduler, sched_enabled
from racon_tpu.sched.telemetry import SchedTelemetry

__all__ = ["ConvergenceScheduler", "RepackPlan", "SchedTelemetry",
           "sched_enabled"]
