"""Chunk driver for convergence-aware refinement.

ConvergenceScheduler.run_chunk replaces the fixed engine's single
all-rounds dispatch (device_poa.device_chunk_packed) with a short
dispatch chain:

    sched_unpack ─ sched_rounds(rounds 0..1, detect) ─┐
      ┌───────────────────────────────────────────────┘
      │ per surviving round r = 2..R-1:
      │   d2h: conv + ovf flags (the only per-round d2h pull)
      │   host: RepackPlan  ─ h2d: index vectors (a few KB)
      │   sched_repack ─ sched_rounds(round r, detect, traced `last`)
      └─ early exit when every window froze
    sched_pack ─ collect_chunk (unchanged d2h layout)

Rounds 0 and 1 fuse into one dispatch because detection cannot fire
before round 1 (see device_merge.converged_windows) — no window could
exit earlier, so splitting them would only add dispatch latency. From
round 2 on, each round runs on a repacked survivor batch whose shrinking
shapes land on ChunkPlan's coarse buckets; the tail dispatches share
one executable because ``last`` is traced, not static.

The consensus a frozen window records is the final-scale dual assembly
of its detection round's votes — bit-identical to the fixed engine's
last round (the replay argument lives in sched/rounds.py). Overflowed
windows freeze immediately too: their sticky flag already routes them
to the unbounded host redo, so further device rounds are wasted work.

This path keeps FUSED forward+walk dispatches: every round's walk
feeds the per-round convergence flag pull, so no walk here is free of
dependent anchor state — the decoupled-walk stage
(pipeline/streaming.py, ops/colwalk.py::dispatch_walk) applies only to
the fixed-round engine, whose FINAL walk nothing consumes until
retirement. stream_consensus falls back to fused dispatches whenever
this scheduler is active.
"""

from __future__ import annotations

import os
from racon_tpu.utils import envspec
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from racon_tpu.obs.metrics import (record_flag_pull, record_h2d,
                                   registry as obs_registry)
from racon_tpu.obs.trace import get_tracer
from racon_tpu.sched.repack import RepackPlan
from racon_tpu.sched.telemetry import SchedTelemetry


def sched_enabled() -> bool:
    """Convergence scheduling is on unless RACON_TPU_SCHED=0 (the
    fixed-round single-dispatch engine is the fallback)."""
    return envspec.read("RACON_TPU_SCHED") not in ("0", "false")


class ConvergenceScheduler:
    """Runs ChunkPlans to consensus with per-window early exit.

    ``scales`` is PoaEngine's per-round insertion-scale schedule
    (_round_scales): all non-final entries must be equal — the dual
    assembly's bit-identity argument needs every replayable round to
    share one scale. The engine's [base]*(R-1) + [final] schedule
    satisfies this by construction; a hand-built schedule that doesn't
    is rejected here rather than silently producing divergent output.
    """

    def __init__(self, *, match: int, mismatch: int, gap: int,
                 scales: Sequence[float], mesh=None,
                 telemetry: Optional[SchedTelemetry] = None):
        self.match, self.mismatch, self.gap = match, mismatch, gap
        scales = tuple(float(s) for s in scales)
        if not scales:
            raise ValueError("[racon_tpu::ConvergenceScheduler] empty "
                             "scale schedule")
        if len(set(scales[:-1])) > 1:
            raise ValueError(
                "[racon_tpu::ConvergenceScheduler] non-final insertion "
                f"scales must be uniform, got {scales} — convergence "
                "freezing replays rounds and cannot honor a per-round "
                "varying scale (use RACON_TPU_SCHED=0)")
        self.rounds = len(scales)
        self.scale = scales[0] if len(scales) > 1 else scales[-1]
        self.scale_final = scales[-1]
        self.mesh = mesh
        self.telemetry = telemetry if telemetry is not None \
            else SchedTelemetry(self.rounds)

    # ------------------------------------------------------------------ h2d

    def put_chunk(self, plan) -> Tuple[object, object]:
        """Start the (async) h2d of a chunk's two packed byte buffers.

        Call for chunk i+1 before running chunk i's rounds: device_put
        returns immediately, so the transfer overlaps compute — the
        scheduler's replacement for the fixed path's depth-2 dispatch
        pipeline (its per-round host syncs preclude dispatch-level
        overlap, but h2d is the transfer phase worth hiding).
        """
        from racon_tpu.ops.device_poa import put_chunk_bufs
        return put_chunk_bufs(plan, mesh=self.mesh)

    # ------------------------------------------------------------------ run

    def run_chunk(self, plan, bufs: Optional[Tuple[object, object]] = None
                  ) -> Tuple[List[Optional[bytes]],
                             List[Optional[np.ndarray]]]:
        """Polish one ChunkPlan; returns collect_chunk's (codes, covs).

        ``bufs`` takes a pre-transferred put_chunk result; None ships
        the buffers here.
        """
        from racon_tpu.ops.device_poa import (_use_pallas, collect_chunk,
                                              round_band_width)
        from racon_tpu.sched.rounds import (sched_pack, sched_repack,
                                            sched_rounds, sched_unpack)
        import jax

        R = self.rounds
        telem = self.telemetry
        ndp = self.mesh.shape["dp"] if self.mesh is not None else 1
        band_w = (0 if envspec.read("RACON_TPU_NO_BAND")
                  not in ("", "0", "false") else plan.band_w)
        # Same per-chunk walk-depth selection as dispatch_chunk: pick k
        # at the round-0 (widest) band so every dispatch shares one k.
        from racon_tpu.ops.budget import walk_k_for
        nxt_k = walk_k_for(plan.B // ndp * plan.Lq * band_w) \
            if band_w else 1
        from racon_tpu.ops.colwalk import chain_len
        obs_registry().set("walk_chain_len",
                           chain_len(plan.LA, nxt_k if band_w else 1))
        statics = dict(match=self.match, mismatch=self.mismatch,
                       gap=self.gap, scale=self.scale,
                       scale_final=self.scale_final, Lq=plan.Lq,
                       LA=plan.LA, mesh=self.mesh, nxt_k=nxt_k)

        if bufs is None:
            bufs = self.put_chunk(plan)
        job_buf, win_buf = bufs
        tracer = get_tracer()
        reg = obs_registry()
        (bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf,
         out_codes, out_cov, out_total, out_ovf) = sched_unpack(
            job_buf, win_buf, Lq=plan.Lq, LA=plan.LA, n_win=plan.n_win)
        reg.inc("device_dispatches")

        n_real = plan.n_real_win
        telem.record_chunk(n_real)
        trash = plan.n_win
        real = np.zeros(plan.n_win, bool)
        real[:n_real] = True
        cur_win_h = plan.win          # host copy of the lane->window map
        cur_orig = np.arange(plan.n_win, dtype=np.int32)
        orig_ids = cur_orig

        # Rounds 0..pre-1 fused (detection fires on the last of them).
        pre = min(2, R)
        pallas = _use_pallas(plan.B // ndp, plan.Lq, plan.LA)
        for r in range(pre):
            telem.record_round(r, n_real)
        with tracer.span("round", f"rounds0-{pre - 1}", lanes=plan.B,
                         windows=n_real):
            (bb, bbw, alen, begin, end, ovf, conv, out_codes, out_cov,
             out_total, out_ovf, rounds_run) = sched_rounds(
                bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf,
                out_codes, out_cov, out_total, out_ovf, orig_ids, pre == R,
                n_win=plan.n_win, pallas=pallas,
                band_ws=tuple(round_band_width(band_w, r)
                              for r in range(pre)),
                detect=R >= 2, **statics)
        reg.inc("device_dispatches")
        exec_dev = rounds_run        # device scalar; pulled via sched_pack
        executed = pre

        n_alive = n_real
        cur_B, cur_nwin = plan.B, plan.n_win
        while executed < R and n_alive > 0:
            # The only per-round d2h: two bool vectors for control flow
            # (they feed telemetry for free). This pull is the sync
            # point, so its time (compute wait + round trip) is
            # accounted separately from the transfer bandwidth keys.
            from racon_tpu.resilience.retry import call as retry_call

            def _pull_flags():
                t_pull = time.perf_counter()
                conv_h = np.asarray(conv)
                ovf_h = np.asarray(ovf)
                record_flag_pull(conv_h.nbytes + ovf_h.nbytes,
                                 time.perf_counter() - t_pull)
                return conv_h, ovf_h

            conv_h, ovf_h = retry_call("sched/flags", _pull_flags)
            frozen = real & (conv_h | ovf_h)
            telem.record_freeze(executed, int(frozen.sum()))
            surv = real & ~conv_h & ~ovf_h
            n_alive = int(surv.sum())
            if n_alive == 0:
                telem.record_skip(R - executed)
                break

            # Repack pays only when the survivor set lands in a SMALLER
            # shape bucket (lane axis or a >=2x window-axis drop) —
            # otherwise the repacked dispatch runs the same padded
            # shapes and the gather/flag-pull overhead is pure loss. In
            # that case fuse every remaining round into one dispatch on
            # the current layout (the fixed engine's program, detection
            # off): low-convergence chunks cost one flag pull over the
            # fixed path instead of a sync per round.
            from racon_tpu.ops.device_poa import _bucket_b, _round_up
            n_wc = surv.shape[0]
            n_lanes = int(np.count_nonzero(
                (cur_win_h < n_wc) & surv[np.minimum(cur_win_h, n_wc - 1)]))
            B2 = _round_up(_bucket_b(max(n_lanes, 1)), 128 * ndp)
            nw2 = _round_up(n_alive, 32)
            if B2 >= cur_B and 2 * nw2 > cur_nwin:
                for r in range(executed, R):
                    telem.record_round(r, n_alive)
                tail_ws = tuple(round_band_width(band_w, r)
                                for r in range(executed, R))
                # The fused tail runs the remaining rounds blind (no
                # per-round flag pull); the adaptive while_loop form
                # stops its device loop at the chunk's fixed point
                # instead of always running all R - executed rounds.
                adapt = (envspec.read("RACON_TPU_ADAPTIVE")
                         not in ("0", "false")
                         and len(tail_ws) >= 2
                         and len(set(tail_ws)) == 1)
                with tracer.span("round", f"rounds{executed}-{R - 1}",
                                 lanes=cur_B, windows=n_alive,
                                 fused_tail=1):
                    (bb, bbw, alen, begin, end, ovf, conv, out_codes,
                     out_cov, out_total, out_ovf, rounds_run) = \
                        sched_rounds(
                        bb, bbw, alen, begin, end, q, qw8, lq, w_read,
                        win, ovf, out_codes, out_cov, out_total, out_ovf,
                        orig_ids, True, n_win=cur_nwin, pallas=pallas,
                        band_ws=tail_ws, detect=False, adaptive=adapt,
                        **statics)
                reg.inc("device_dispatches")
                exec_dev = exec_dev + rounds_run
                executed = R
                break

            t0 = time.perf_counter()
            rp = RepackPlan(surv, cur_win_h, cur_orig, trash=trash,
                            n_shards=ndp)
            def _put_repack():
                t_put = time.perf_counter()
                if self.mesh is None:
                    lane_idx_d, new_win_d, win_map_d, win_real_d = \
                        jax.device_put((rp.lane_idx, rp.new_win,
                                        rp.win_map, rp.win_real))
                else:
                    from jax.sharding import (NamedSharding,
                                              PartitionSpec as P)
                    rep = NamedSharding(self.mesh, P())
                    lane_idx_d = jax.device_put(rp.lane_idx, rep)
                    win_map_d = jax.device_put(rp.win_map, rep)
                    win_real_d = jax.device_put(rp.win_real, rep)
                    new_win_d = jax.device_put(
                        rp.new_win, NamedSharding(self.mesh, P("dp")))
                record_h2d(rp.lane_idx.nbytes + rp.new_win.nbytes +
                           rp.win_map.nbytes + rp.win_real.nbytes,
                           time.perf_counter() - t_put, name="h2d/repack")
                return lane_idx_d, new_win_d, win_map_d, win_real_d

            from racon_tpu.ops.budget import transfer_deadline_s
            lane_idx_d, new_win_d, win_map_d, win_real_d = \
                retry_call("h2d/repack", _put_repack,
                           deadline_s=transfer_deadline_s(
                               rp.lane_idx.nbytes + rp.new_win.nbytes +
                               rp.win_map.nbytes + rp.win_real.nbytes,
                               "h2d"))
            with tracer.span("dispatch", "repack", lanes=rp.B,
                             windows=n_alive):
                (bb, bbw, alen, begin, end, q, qw8, lq, w_read, ovf) = \
                    sched_repack(bb, bbw, alen, begin, end, q, qw8, lq,
                                 w_read, ovf, lane_idx_d, new_win_d,
                                 win_map_d, win_real_d, mesh=self.mesh)
            reg.inc("device_dispatches")
            win = new_win_d
            cur_win_h = rp.new_win
            cur_orig = rp.orig_ids
            real = rp.win_real
            orig_ids = rp.orig_ids
            cur_B, cur_nwin = rp.B, rp.n_win
            telem.record_repack(time.perf_counter() - t0)

            telem.record_round(executed, n_alive)
            pallas = _use_pallas(rp.B // ndp, plan.Lq, plan.LA)
            with tracer.span("round", f"round{executed}", lanes=rp.B,
                             windows=n_alive):
                (bb, bbw, alen, begin, end, ovf, conv, out_codes, out_cov,
                 out_total, out_ovf, rounds_run) = sched_rounds(
                    bb, bbw, alen, begin, end, q, qw8, lq, w_read, win,
                    ovf, out_codes, out_cov, out_total, out_ovf, orig_ids,
                    executed == R - 1, n_win=rp.n_win, pallas=pallas,
                    band_ws=(round_band_width(band_w, executed),),
                    detect=True, **statics)
            reg.inc("device_dispatches")
            exec_dev = exec_dev + rounds_run
            executed += 1

        if n_alive > 0:
            # Whoever was still live froze on the schedule's last round.
            telem.record_freeze(R, n_alive)

        packed = sched_pack(out_codes, out_cov, out_total, out_ovf,
                            exec_dev, R)
        reg.inc("device_dispatches")
        return collect_chunk(plan, packed)
