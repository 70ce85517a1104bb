"""Device-resident POA consensus engine — the TPU hot path.

Round-2's engine shipped alignment ops to the host and merged in numpy
every refinement round, paying a transfer and a synchronized dispatch
per round. This engine keeps the whole refinement loop on device:

  h2d once:  encoded layer codes/weights, backbone anchors, spans
  per round (no host sync, chained dispatch):
    - job geometry from spans (full-span 1% rule, src/window.cpp:82)
    - shifted target buffer by gather from the current anchors
    - banded NW forward (Pallas kernel on TPU, XLA fallback elsewhere)
    - batched banded traceback (one scan for all lanes)
    - vote extraction + window aggregation + assembly + compaction
      (racon_tpu/ops/device_merge.py) -> next round's anchors + spans,
      all device-side
  d2h once:  compact consensus codes + coverage + lengths + edge stats

Semantics match PoaEngine's numpy path bit-for-bit on integer weights
(differentially tested) on a single device. Banded-alignment exactness is
certified per lane every round by an escape-bound score check (see
racon_tpu/ops/pallas/band_kernel.py): a lane whose banded score cannot
provably beat every band-leaving path flags its window for re-polish on
the unbounded host path. The dp-sharded path (device_chunk_packed with a
mesh) is near-bit-identical to single-device: its one psum per round may
reassociate f32 vote sums, so sub-epsilon ties can break differently
(tests accept rare single-window divergence; see
tests/test_device_merge.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

from racon_tpu.models.window import Window, window_arrays
from racon_tpu.ops.encode import ALPHABET
from racon_tpu.ops import flat as flatmod
from racon_tpu.ops.flat import PAD_OP
from racon_tpu.ops.budget import max_dir_elems
from racon_tpu.utils import envspec

# Per-lane-tensor element budget for the dirs/nxt planes (the column
# walk's flat gather index and the HBM single-buffer ceiling). Derived
# in ONE place — racon_tpu/ops/budget.py — shared with ovl_align so the
# two admission paths can never drift apart again (the former hand-set
# 1.6e9 here vs the re-derived 1.9e9 there silently rejected the 8 kb
# genome overlap geometry by 0.7%).
MAX_DIR_ELEMS = max_dir_elems(1)

# Anchor slack for insertion growth across rounds. Consensus length
# tracks backbone length within ~2% on real data; 64 covers that many
# times over at w=500-class windows, and a window whose consensus DOES
# outgrow the padded width raises the sticky ovf flag and re-polishes on
# the unbounded host path — the slack is a throughput knob (walk steps,
# vote channels, and merge gathers all scale with LA), not a correctness
# bound.
LA_GROW = 64


def _round_up(n: int, mult: int) -> int:
    return ((max(n, 1) + mult - 1) // mult) * mult


def _bucket_b(n: int) -> int:
    """Batch-dim bucket: coarse grid so chunks reuse compiled executables."""
    for cap in (128, 256, 512, 1024, 2048):
        if n <= cap:
            return cap
    return _round_up(n, 1024)


# Executable-reuse cap history, per process: every differently-shaped
# chunk executable is a fresh multi-second XLA compile, so later
# runs pad up to a previously-compiled (Lq, LA) pair when one covers them
# within 2x per dim (beyond that, recompiling is cheaper than the padded
# compute). jax's executable cache keys on the same shapes, so a history
# hit is a compile-cache hit. Mutations are lock-guarded: concurrent
# PoaEngine use from multiple threads would otherwise race the sets
# (worst case a missed reuse => redundant compile, never wrong results).
import threading as _threading
_HISTORY_LOCK = _threading.Lock()
_CAP_HISTORY: set = set()
_BAND_HISTORY: set = set()


def run_caps(lq: int, la: int) -> Tuple[int, int]:
    """(lq_cap, la_cap) covering a run's max layer/backbone lengths, on a
    coarse grid."""
    # LA pads on a 128 grid; with the 64-slot growth slack, runs of the
    # same workload (e.g. consecutive jobs, lengths ~w..w+6%)
    # still land in one bucket, and the former 256 grid wasted up to 20%
    # of every LA-proportional cost (walk steps, channels, gathers).
    need = (_round_up(lq, 128), _round_up(la + LA_GROW, 128))
    if 128 * need[0] * need[1] > MAX_DIR_ELEMS:
        # Unusable even at the minimum batch bucket (caller falls back to
        # the host path) — don't record it, or it would shadow smaller
        # usable pairs for later runs.
        return need
    with _HISTORY_LOCK:
        best = None
        for c in _CAP_HISTORY:
            if (need[0] <= c[0] <= 2 * need[0] and
                    need[1] <= c[1] <= 2 * need[1] and
                    128 * c[0] * c[1] <= MAX_DIR_ELEMS and
                    (best is None or c[0] * c[1] < best[0] * best[1])):
                best = c
        if best is None:
            best = need
            _CAP_HISTORY.add(need)
        return best


def window_band_delta(w: Window) -> int:
    """Max |lt0 - lq| over a window's layers at round-0 geometry — THE
    band-width input, shared by ChunkPlan (per chunk) and
    PoaEngine._run_band_width (per run) so chunk sizing and chunk
    padding can never disagree. Mirrors _round_core's on-device
    full-span rule (src/window.cpp:82)."""
    L = len(w.backbone)
    if w.n_layers == 0:
        return 0
    offs = L // 100
    b = np.clip(np.asarray(w.layer_begin, np.int64), 0, L - 1)
    e = np.maximum(
        np.minimum(np.asarray(w.layer_end, np.int64), L - 1), b)
    lqs = np.array([len(d) for d in w.layer_data], np.int64)
    full = (b < offs) & (e > L - offs)
    lt0 = np.where(full, L, e - b + 1)
    return int(np.abs(lt0 - lqs).max())


def band_width_for(max_delta: int) -> int:
    """Band slots covering a max length-difference with >=64 slack per
    side, on the 128 grid. 64 keeps the per-lane escape bound easily
    satisfiable on real polishing data (wl >= 64 certifies every lambda
    window) while cutting band cells ~25-33% vs the former 128; lanes
    whose optimum needs a wider corridor fail the bound and re-polish on
    the unbounded host path — exactness never rests on the slack."""
    return _round_up(max_delta + 2 * 64 + 1, 128)


def dir_elems(n_jobs: int, max_lq: int, max_bb: int) -> int:
    """Dirs-tensor element count for a chunk, with ChunkPlan's padding."""
    return (_bucket_b(n_jobs) * _round_up(max_lq, 128) *
            _round_up(max_bb + LA_GROW, 128))


class ChunkPlan:
    """Host-side padded arrays for one device chunk (static shapes).

    All dims pad onto coarse grids — B via ``_bucket_b``, Lq/LA via the
    run-level caps from ``run_caps``, n_win onto multiples of 32 (dummy
    windows with a 1-base zero anchor) — so every chunk of a run, and
    repeated runs in one process, share a single compiled executable.
    """

    def __init__(self, windows: List[Window], la_grow: int = LA_GROW,
                 lq_cap: Optional[int] = None, la_cap: Optional[int] = None,
                 n_shards: int = 1, band_cap: Optional[int] = None):
        self.windows = windows
        jobs_q: List[np.ndarray] = []
        jobs_w: List[np.ndarray] = []
        begin: List[int] = []
        end: List[int] = []
        win: List[int] = []
        anchors: List[np.ndarray] = []
        anchor_w: List[np.ndarray] = []
        for wi, w in enumerate(windows):
            lays, bb, bw = window_arrays(w)
            for codes, wts, b, e in lays:
                jobs_q.append(codes)
                jobs_w.append(wts)
                begin.append(b)
                end.append(e)
                win.append(wi)
            anchors.append(bb)
            anchor_w.append(bw)

        self.n_real_win = len(windows)
        self.n_win = _round_up(len(windows), 32)
        self.n_jobs = len(jobs_q)
        # Each mesh shard needs a 128-lane-aligned slice of the job axis.
        B = _round_up(_bucket_b(self.n_jobs), 128 * n_shards)
        max_lq = max(len(q) for q in jobs_q)
        LA0 = max(len(a) for a in anchors)
        Lq = lq_cap if lq_cap is not None else _round_up(max_lq, 128)
        LA = la_cap if la_cap is not None else _round_up(LA0 + la_grow, 128)
        if max_lq > Lq or LA0 + la_grow > LA:
            raise ValueError("[racon_tpu::ChunkPlan] caps below chunk max")
        self.B, self.Lq, self.LA = B, Lq, LA
        self.steps = Lq + LA

        self.q = np.zeros((B, Lq), np.uint8)
        # Weights ship as uint8 (value+1, 0 = padding) and decode on device
        # — a 4x smaller h2d than f32 weights.
        self.qw8 = np.zeros((B, Lq), np.uint8)
        self.lq = np.ones(B, np.int32)
        self.w_read = np.zeros(B, np.float32)
        # Padded lanes point at a dummy extra window (n_win) so their votes
        # aggregate into a discarded row.
        self.win = np.full(B, self.n_win, np.int32)
        self.begin = np.zeros(B, np.int32)
        self.end = np.ones(B, np.int32)
        if self.n_jobs:
            # Bulk fill (one masked scatter per plane, segment means
            # via prefix-sum differences) — the former per-job
            # assignment loop was a genome-scale host cost.
            nj = self.n_jobs
            lens = np.fromiter((len(q) for q in jobs_q), np.int64, nj)
            flat_q = np.concatenate(jobs_q)
            flat_w = np.concatenate(jobs_w).astype(np.float64)
            mask = np.arange(Lq)[None, :] < lens[:, None]
            self.q[:nj][mask] = flat_q
            # Weights are non-negative for all parser-fed inputs (the FASTQ
            # parser rejects quality bytes below '!'), so host and device
            # paths agree by construction on CLI data. The clip stays as
            # defense-in-depth for direct-API Windows built with malformed
            # quality, where uint8 wrap would otherwise vote at max weight.
            # Cap 126: the vote extraction packs weights as 7-bit fields
            # (device_merge.extract_votes_cols), and any real Phred weight
            # is <= '~' - '!' = 93.
            self.qw8[:nj][mask] = \
                np.clip(flat_w, 0, 126).astype(np.uint8) + 1
            self.lq[:nj] = lens
            # Segment means via prefix sums (safe for empty segments,
            # unlike reduceat whose clipped offsets corrupt a trailing
            # empty job's neighbor). Bit-equality with the host engine's
            # per-job _Job.w_read (f64 .mean()) holds because weights
            # are integer-valued by the parser contract (Phred ints or
            # 1.0), making every f64 summation order exact; fractional
            # direct-API weights could differ in the last ulp.
            offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
            cs = np.concatenate([[0.0], np.cumsum(flat_w)])
            sums = cs[offs + lens] - cs[offs]
            self.w_read[:nj] = np.where(
                lens > 0, sums / np.maximum(lens, 1), 0.0)
            self.win[:nj] = win
            self.begin[:nj] = begin
            self.end[:nj] = end

        Nw = self.n_win + 1   # + dummy row for padded lanes
        self.bb = np.zeros((Nw, LA), np.uint8)
        self.bbw = np.zeros((Nw, LA), np.float32)
        self.alen = np.ones(Nw, np.int32)
        for wi in range(self.n_real_win):
            L = len(anchors[wi])
            self.bb[wi, :L] = anchors[wi]
            self.bbw[wi, :L] = anchor_w[wi]
            self.alen[wi] = L

        # Static band width for the banded forward: covers every job's
        # round-0 |lt - lq| with >=128 slack each side (later rounds can
        # shift geometry — the in-round escape bound re-certifies every
        # lane every round). 0 disables banding when a band would not
        # beat the full-width kernel.
        W = band_width_for(max((window_band_delta(w) for w in windows),
                               default=0))
        if band_cap is not None and W > band_cap:
            # The caller sized chunks assuming banded dirs of at most
            # band_cap columns from the same shared geometry; a wider
            # chunk here would overflow the int32 dirs budget silently.
            raise ValueError(
                "[racon_tpu::ChunkPlan] band width exceeds the caller's "
                f"sizing cap ({W} > {band_cap})")
        if W + 128 > LA:
            # Band would not beat full width here; don't record W either,
            # or an unusable entry could shadow smaller fitting widths
            # for later chunks (same pitfall run_caps guards against).
            self.band_w = 0
        else:
            # Reuse a previously-compiled band width when one covers
            # this chunk within 2x, fits this LA, and stays under the
            # caller's ceiling (chunk sizing may have assumed banded
            # dirs of at most band_cap columns). band_w is a static arg;
            # workload noise across runs must not force fresh
            # multi-second compiles.
            ceil = min(LA - 128, band_cap) if band_cap else LA - 128
            with _HISTORY_LOCK:
                best = None
                for c in _BAND_HISTORY:
                    if (W <= c <= 2 * W and c <= ceil and
                            (best is None or c < best)):
                        best = c
                if best is None:
                    _BAND_HISTORY.add(W)
                    best = W
            self.band_w = best

    def packed_bufs(self):
        """(job_buf u8[B, 2*Lq+20], win_buf u8[Nw+1, 5*LA+4]) — every
        chunk input concatenated into two byte buffers so each chunk is
        TWO h2d transfers instead of ten (per-transfer latency, not
        bytes, dominated h2d at bench scale). Layout must match
        device_chunk_packed's unpack slicing exactly; the job buffer is
        dp-shardable along axis 0, the window buffer replicates."""
        B, Lq, LA = self.B, self.Lq, self.LA
        job = np.empty((B, 2 * Lq + 20), np.uint8)
        job[:, :Lq] = self.q
        job[:, Lq:2 * Lq] = self.qw8
        sc = job[:, 2 * Lq:]
        sc[:, 0:4] = self.begin.astype(np.int32).view(np.uint8).reshape(B, 4)
        sc[:, 4:8] = self.end.astype(np.int32).view(np.uint8).reshape(B, 4)
        sc[:, 8:12] = self.lq.astype(np.int32).view(np.uint8).reshape(B, 4)
        sc[:, 12:16] = self.win.astype(np.int32).view(np.uint8).reshape(B, 4)
        sc[:, 16:20] = self.w_read.astype(np.float32).view(np.uint8) \
            .reshape(B, 4)
        Nw1 = self.n_win + 1
        winb = np.empty((Nw1, 5 * LA + 4), np.uint8)
        winb[:, :LA] = self.bb
        winb[:, LA:5 * LA] = self.bbw.astype(np.float32).view(np.uint8) \
            .reshape(Nw1, 4 * LA)
        winb[:, 5 * LA:] = self.alen.astype(np.int32).view(np.uint8) \
            .reshape(Nw1, 4)
        return job, winb


def _use_pallas(B: int, Lq: int, LA: int) -> bool:
    import os
    import jax
    from racon_tpu.ops.pallas.flat_kernel import TB, CH
    if envspec.read("RACON_TPU_NO_PALLAS") not in ("", "0", "false"):
        return False                               # debug/safety valve
    if jax.default_backend() != "tpu":
        return False
    return B % TB == 0 and Lq % CH == 0 and LA % 128 == 0


def _packed_byte_slice(tab, start, L: int):
    """Batched contiguous byte slice via i32-packed dynamic_slice.

    Equivalent to ``vmap(lambda s: dynamic_slice(tab, (s,), (L,)))`` for
    a uint8 table with ``start >= 0`` and ``start + L <= tab.size``, but
    each per-lane DMA moves L/4 + 1 int32 words instead of L bytes. The
    band/tbuf build is bound by per-lane DMA *descriptor* latency, which
    scales with element count, not bytes — packing 4 cells per word
    cuts the descriptor count 4x. The start&3 phase is
    recovered from four STATIC byte slices with three selects; a
    per-element phase gather here would reintroduce exactly the cost the
    slice-mode build removed (a round-5 chip ablation).
    """
    import jax
    import jax.numpy as jnp

    # Worst case is phase 3: bytes [3, 3 + L) of the fetched window, so
    # the window must span L + 3 bytes -> L // 4 + 2 words.
    n4 = L // 4 + 2
    # Round the table up to whole words plus two words of slack so the
    # word slice covering any start phase stays in range:
    #   (start >> 2) + n4 <= floor((start + L) / 4) + 2
    #                     <= floor(size / 4) + 2.
    pad = (-tab.shape[0]) % 4 + 8
    tabp = jnp.concatenate([tab, jnp.zeros((pad,), tab.dtype)])
    w32 = jax.lax.bitcast_convert_type(tabp.reshape(-1, 4), jnp.int32)
    ws = jax.vmap(
        lambda s: jax.lax.dynamic_slice(w32, (s,), (n4,)))(start >> 2)
    by = jax.lax.bitcast_convert_type(ws, jnp.uint8).reshape(
        ws.shape[0], n4 * 4)
    ph = start & 3
    out = by[:, 0:L]
    for r_ in (1, 2, 3):
        out = jnp.where((ph == r_)[:, None], by[:, r_:r_ + L], out)
    return out


def _lane_fwd(bb, alen, begin, end, q, lq, win, *,
              match, mismatch, gap, Lq, LA, pallas, band_w=0,
              nxt_k=2):
    """Job geometry + NW forward for every lane of one refinement round
    (traced body, one shard's view): the half of _lane_votes that ends
    at the packed direction planes, before any serialized traceback.

    Returns ``(dirs, nxt, nxt2, lt, t_off, klo, esc0)``: the forward's
    packed cell plane plus the k-step predecessor planes (``nxt`` /
    ``nxt2`` are None below their depth; see docs/KERNELS.md), the
    per-lane geometry vectors the walk re-uses verbatim (``klo`` is
    None on the flat path), and ``esc0`` — the band-escape certificate
    term, f32[B], already resolved from ``hlast`` here so the decoupled
    walk dispatch never needs the score plane (None on the flat path,
    whose only inexactness signal is walk saturation).

    The fused round (_lane_votes) and the decoupled walk dispatch
    (ops/colwalk.py walk_chunk_packed) both build on this body, so the
    split is bit-identical by construction.
    """
    import jax
    import jax.numpy as jnp

    B = q.shape[0]
    L = jnp.take(alen, win)                             # anchor len per job
    b_c = jnp.clip(begin, 0, L - 1)
    e_c = jnp.clip(end, b_c, L - 1)
    # uint32 offset = 0.01 * L, strict end > L - offset (window.cpp:82).
    # Integer floor-div matches the host's f64 `int(0.01 * L)` exactly for
    # all realistic L (f64 0.01 is slightly above 1/100, so truncation
    # equals floor division); f32 on device would disagree near multiples
    # of 100 (e.g. L=300).
    offs = L // 100
    full = (b_c < offs) & (e_c > L - offs)
    t_off = jnp.where(full, 0, b_c).astype(jnp.int32)
    lt = jnp.where(full, L, e_c - b_c + 1).astype(jnp.int32)

    flat = bb.reshape(-1)
    if band_w:
        # Diagonal band (racon_tpu/ops/pallas/band_kernel.py): per-lane
        # geometry pre-baked into a shifted target buffer; exactness per
        # lane is certified by the same escape bound as the native
        # aligner, and failing lanes route their windows to the host
        # redo path via the sticky ovf flag.
        from racon_tpu.ops.pallas.band_kernel import (
            fw_dirs_band, fw_dirs_band_xla, band_geometry)
        klo, wl = band_geometry(lq, lt, band_w)
        PW = band_w + Lq
        y = jnp.arange(PW, dtype=jnp.int32)[None, :]
        rel = klo[:, None] + y                     # slice-relative index
        okb = (rel >= 0) & (rel < lt[:, None])
        # Per-lane slices are CONTIGUOUS runs of the anchor table, so a
        # batched dynamic_slice (slice-mode gather) replaces the element
        # gather (its cost on the current code is not measured on the
        # chip) and the i32-packed variant moves 4 cells per descriptor
        # word on top (_packed_byte_slice); the padding margins make
        # every start index in-range, the okb mask reproduces the clip
        # semantics bit-for-bit (every phase-spill byte it could expose
        # is masked).
        tab = jnp.concatenate(
            [jnp.zeros((PW,), flat.dtype), flat,
             jnp.zeros((PW,), flat.dtype)])
        start = win * LA + t_off + klo + PW
        sl = _packed_byte_slice(tab, start, PW)
        tband = jnp.where(okb, sl, 7).astype(jnp.uint8)
        fwd = fw_dirs_band if pallas else fw_dirs_band_xla
        if nxt_k >= 4:
            dirs, nxt, nxt2, hlast = fwd(tband, q.T, klo, lq,
                                         match=match, mismatch=mismatch,
                                         gap=gap, W=band_w, nxt_k=4)
        else:
            dirs, nxt, hlast = fwd(tband, q.T, klo, lq,
                                   match=match, mismatch=mismatch,
                                   gap=gap, W=band_w)
            nxt2 = None
            if nxt_k < 2:           # single-step reference walk
                nxt = None
        # Escape bound (see nw.cpp): banded score must beat any path
        # that leaves the band, else the lane's window is re-polished on
        # the unbounded host path. Any out-of-band path carries at least
        # |lt-lq| + 2(wl+1) gap ops; those consume query/target bases
        # unpaired, so its diagonal-op count is at most
        # min(lq,lt) - (wl+1) and its score at most
        #   max(m,0)*(min(lq,lt) - wl - 1) + g*(|lt-lq| + 2wl + 2).
        # (The former bound omitted the "- wl - 1" term; at narrow
        # bands that looseness re-routed most REAL windows to the host:
        # 92/96 lambda windows at W=128, round-5 measurement.)
        xend = jnp.clip(lt - lq - klo, 0, band_w - 1)
        score = jnp.take_along_axis(hlast, xend[:, None], axis=1)[:, 0]
        bound = (jnp.maximum(match, 0) * (jnp.minimum(lq, lt) - wl - 1) +
                 gap * (jnp.abs(lt - lq) + 2 * wl + 2))
        esc0 = ((score < bound) | (wl < 16)).astype(jnp.float32)
        return dirs, nxt, nxt2, lt, t_off, klo, esc0
    else:
        # Full-width absolute coordinates: tbuf[b, x] = anchor slice
        # (same batched dynamic_slice trick as the banded path).
        x = jnp.arange(LA, dtype=jnp.int32)[None, :]
        ok = x < lt[:, None]
        tab = jnp.concatenate(
            [flat, jnp.zeros((LA,), flat.dtype)])
        start = win * LA + t_off
        sl = _packed_byte_slice(tab, start, LA)
        tbuf = jnp.where(ok, sl, 7).astype(jnp.uint8)
        if pallas:
            from racon_tpu.ops.pallas.flat_kernel import fw_dirs_pallas
            dirs = fw_dirs_pallas(tbuf, q.T,
                                  match=match, mismatch=mismatch, gap=gap)
        else:
            dirs = flatmod.fw_dirs_xla(tbuf, q.T,
                                       match=match, mismatch=mismatch,
                                       gap=gap)
        return dirs, None, None, lt, t_off, None, None


def _lane_walk(dirs, nxt, nxt2, lt, t_off, klo, esc0, q, qw8, lq,
               w_read, *, LA, pallas, band_w=0):
    """Column-walk traceback + vote extraction over _lane_fwd's planes
    (traced body, one shard's view) — the serialized-gather half of a
    round, the part the decoupled walk dispatch takes off the critical
    path.

    Returns (votes dict for dm.aggregate_votes, esc_w f32[B]) exactly
    as _lane_votes always has: ``esc0`` (the forward's escape term)
    plus walk saturation.
    """
    import jax.numpy as jnp
    from racon_tpu.ops import device_merge as dm
    from racon_tpu.ops.colwalk import col_walk

    if band_w:
        cols = col_walk(dirs, lq, lt, klo, t_off, LA=LA,
                        layout="band_t" if pallas else "band", nxt=nxt,
                        nxt2=nxt2)
    else:
        cols = col_walk(dirs, lq, lt, None, t_off, LA=LA, layout="flat")
    votes = dm.extract_votes_cols(cols, q, qw8, w_read, lt, t_off, LA)
    # Saturated up-run counters make the walk inexact for that lane —
    # same redo route as the band escape bound.
    sat_w = cols["sat"].astype(jnp.float32)
    esc_w = sat_w if esc0 is None else esc0 + sat_w
    return votes, esc_w


def _lane_votes(bb, alen, begin, end, q, qw8, lq, w_read, win, *,
                match, mismatch, gap, Lq, LA, pallas, band_w=0,
                nxt_k=2):
    """Job geometry + NW forward + column-walk + vote extraction for
    every lane of one refinement round (traced body, one shard's view).

    The shared front half of a round: the fixed-round engine
    (_round_core) and the convergence scheduler's detecting round
    (racon_tpu/sched/rounds.py) both consume its output, so the two
    dispatch paths run one implementation of the alignment contract.
    Internally it is _lane_fwd (geometry + forward planes) composed
    with _lane_walk (traceback + votes) — the decoupled walk dispatch
    runs the same two bodies split across two executables, which is
    what makes it bit-identical to this fused form.

    ``nxt_k`` (static; 2 or 4) selects the banded walk's predecessor
    depth — at 4 the forward also emits the u16 ``nxt2`` hop plane and
    the column walk undoes four anchor positions per dependent gather
    (budget.walk_k_for picks it per geometry; the flat path has no nxt
    plane and ignores it).

    Returns (votes dict of per-job channels for dm.aggregate_votes,
    esc_w f32[B] — positive where the banded walk's exactness
    certificate failed and the lane's window must re-polish on the
    redo path).
    """
    dirs, nxt, nxt2, lt, t_off, klo, esc0 = _lane_fwd(
        bb, alen, begin, end, q, lq, win, match=match, mismatch=mismatch,
        gap=gap, Lq=Lq, LA=LA, pallas=pallas, band_w=band_w, nxt_k=nxt_k)
    return _lane_walk(dirs, nxt, nxt2, lt, t_off, klo, esc0, q, qw8, lq,
                      w_read, LA=LA, pallas=pallas, band_w=band_w)


def _remap_state(codes, total, map_b, map_e, bb, alen, begin, end, win,
                 LA: int):
    """Next-round anchors (dummy row re-appended) and spans remapped
    through the merge's coordinate maps — the shared back half of a
    round's state update (``bb``/``alen``/``begin``/``end`` are the
    round's INPUT state; returns the new anchor table, lengths, and
    per-lane spans)."""
    import jax
    import jax.numpy as jnp

    L = jnp.take(alen, win)                             # anchor len per job
    new_bb = jnp.concatenate([codes, bb[-1:]], axis=0)
    new_alen = jnp.concatenate(
        [jnp.clip(total, 1, LA), alen[-1:]], axis=0).astype(jnp.int32)
    mb_flat = map_b.reshape(-1)
    me_flat = map_e.reshape(-1)
    winc = jnp.minimum(win, map_b.shape[0] - 1)
    nb = jnp.where(begin < L,
                   jnp.take(mb_flat, winc * LA + jnp.clip(begin, 0, LA - 1)),
                   0).astype(jnp.int32)
    tot_j = jnp.take(jnp.clip(total, 1, LA), winc)
    ne = jnp.where(end < L,
                   jnp.take(me_flat, winc * LA + jnp.clip(end, 0, LA - 1)),
                   tot_j - 1).astype(jnp.int32)
    return new_bb, new_alen, nb, ne


def _round_core(bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf, *,
                match, mismatch, gap, ins_scale, Lq, n_win,
                LA, pallas, band_w=0, nxt_k=2, detect=False,
                axis_name=None):
    """One alignment + merge round (traced body, single shard's view).

    Returns (new_bb, new_bbw, new_alen, new_begin, new_end, cov, ovf,
    conv). ``ovf`` is a sticky per-window flag: consensus outgrew the
    padded anchor width this round (or any earlier one) and was
    truncated — the host must re-run those windows (the host path is
    unbounded). ``conv`` is the per-window fixed-point flag
    (device_merge.converged_windows) when ``detect`` is on, all-False
    otherwise — the adaptive round exit in device_chunk_packed skips
    remaining non-final rounds once every window is conv or ovf.

    Under shard_map the job (B) axis is sharded over ``axis_name`` while
    window arrays are replicated; the only collective is one psum of the
    per-window vote accumulators (jobs of one window may live on any
    shard) — windows are otherwise independent, matching the reference's
    per-window fan-out (src/polisher.cpp:457-469).
    """
    votes, esc_w = _lane_votes(
        bb, alen, begin, end, q, qw8, lq, w_read, win, match=match,
        mismatch=mismatch, gap=gap, Lq=Lq, LA=LA, pallas=pallas,
        band_w=band_w, nxt_k=nxt_k)
    return _merge_round(votes, esc_w, bb, bbw, alen, begin, end, win,
                        ovf, ins_scale=ins_scale, n_win=n_win, LA=LA,
                        detect=detect, axis_name=axis_name)


def _merge_round(votes, esc_w, bb, bbw, alen, begin, end, win, ovf, *,
                 ins_scale, n_win, LA, detect=False, axis_name=None):
    """Vote aggregation through state remap — the back half of a round
    (traced body). Shared verbatim by the fused round (_round_core
    above) and the decoupled walk dispatch (ops/colwalk.py
    walk_chunk_packed), so the two paths assemble consensus through one
    implementation; see _round_core for the output contract."""
    import jax
    import jax.numpy as jnp
    from racon_tpu.ops import device_merge as dm

    # The band-escape per-window sum rides aggregate_votes' membership
    # matrix and the same single psum as the votes.
    acc = dm.aggregate_votes(votes, win, n_win + 1, extras={"_esc": esc_w})
    if axis_name is not None:
        acc = {k: jax.lax.psum(v, axis_name) for k, v in acc.items()}
    wesc = acc.pop("_esc", None)
    acc = {k: v[:-1] for k, v in acc.items()}       # drop padded-lane row
    acc = dm.add_backbone(acc, bb[:-1], bbw[:-1], alen[:-1])
    asm = dm.assemble(acc, alen[:-1], ins_scale)
    codes, cov, total = dm.compact(asm, LA)
    map_b, map_e = dm.coord_maps(asm, alen[:-1], LA)

    new_bb, new_alen, nb, ne = _remap_state(
        codes, total, map_b, map_e, bb, alen, begin, end, win, LA)
    new_bbw = jnp.zeros_like(bbw)
    ovf = ovf | (total > LA)
    if wesc is not None:
        ovf = ovf | (wesc[:-1] > 0)
    if detect:
        # Same fixed-point predicate as the convergence scheduler
        # (sched/rounds.py): span-change flags ride one extra membership
        # matmul (and one extra psum under dp — nb/ne only exist after
        # the coordinate maps, so they cannot ride the votes' psum).
        chg = ((nb != begin) | (ne != end)).astype(jnp.float32)
        wchg = dm.aggregate_flags(chg, win, n_win + 1)
        if axis_name is not None:
            wchg = jax.lax.psum(wchg, axis_name)
        conv = dm.converged_windows(codes, total, bb[:-1], alen[:-1],
                                    wchg[:-1])
    else:
        conv = jnp.zeros(n_win, dtype=bool)
    return new_bb, new_bbw, new_alen, nb, ne, cov, ovf, conv


def round_band_width(band_w: int, r: int) -> int:
    """Band width for refinement round ``r`` of a chunk.

    Round 0 aligns against the raw backbone and needs the full chunk
    band; later rounds align against a near-converged consensus whose
    spans were remapped through the previous merge, so the optimum hugs
    the diagonal and a narrower band suffices — exactness is still
    certified per lane per round by the escape bound, with failures
    taking the host redo route. 192 (not 128): at wl ~= 95 the
    tightened bound sits ~1000 below real noisy-read scores, where
    W=128's wl ~= 63 made it marginal and re-routed 58/96 lambda
    windows (round-5 measurement; Mosaic only needs W % 8, not % 128).

    Shared by every dispatch path (device_chunk_packed, the decoupled
    forward/walk pair, and the convergence scheduler), so they all run
    one band schedule.
    """
    return band_w if (r == 0 or not band_w) else min(band_w, 192)


def _make_round_fn(*, match, mismatch, gap, ins_scale, Lq, n_win, LA,
                   pallas, band_w, mesh, nxt_k=2, detect=False):
    """One round callable: plain _round_core, or its dp-sharded shard_map
    when a mesh is given (the single place the sharding contract lives).

    Job-axis arrays shard over "dp", window arrays replicate, and the
    only collective is _round_core's one psum of the per-window vote
    accumulators. check_vma=False: the Pallas kernels' out_shapes carry
    no varying-mesh-axes annotation, which the checker (TPU path only)
    rejects; the in/out specs below state the contract explicitly.
    """
    core = functools.partial(
        _round_core, match=match, mismatch=mismatch, gap=gap,
        ins_scale=ins_scale, Lq=Lq, n_win=n_win, LA=LA, pallas=pallas,
        band_w=band_w, nxt_k=nxt_k, detect=detect,
        axis_name=None if mesh is None else "dp")
    if mesh is None:
        return core
    from jax.sharding import PartitionSpec as P
    from racon_tpu.utils.jaxcompat import shard_map
    rep = P()
    job = P("dp")
    return shard_map(
        core, mesh=mesh,
        in_specs=(rep, rep, rep, job, job, job, job, job, job, job, rep),
        out_specs=(rep, rep, rep, job, job, rep, rep, rep),
        check_vma=False)


def _unpack_job(job_buf, Lq: int):
    """Slice ChunkPlan.packed_bufs()' job byte layout back into per-lane
    arrays (traced body): ``(q, qw8, begin, end, lq, win, w_read)``.
    Split out of _unpack_bufs so the decoupled walk dispatch (which
    carries its round state as live device arrays, not the win buffer)
    can recover the round-invariant job fields from the same layout
    contract."""
    import jax
    import jax.numpy as jnp

    def i32(col):
        return jax.lax.bitcast_convert_type(col, jnp.int32)

    q = job_buf[:, :Lq]
    qw8 = job_buf[:, Lq:2 * Lq]
    sc = job_buf[:, 2 * Lq:]
    B = job_buf.shape[0]
    begin = i32(sc[:, 0:4].reshape(B, 1, 4))[:, 0]
    end = i32(sc[:, 4:8].reshape(B, 1, 4))[:, 0]
    lq = i32(sc[:, 8:12].reshape(B, 1, 4))[:, 0]
    win = i32(sc[:, 12:16].reshape(B, 1, 4))[:, 0]
    w_read = jax.lax.bitcast_convert_type(
        sc[:, 16:20].reshape(B, 1, 4), jnp.float32)[:, 0]
    return q, qw8, begin, end, lq, win, w_read


def _unpack_bufs(job_buf, win_buf, Lq: int, LA: int):
    """Slice ChunkPlan.packed_bufs()' concatenated byte layouts back into
    round-state arrays (traced body). The layout contract lives here and
    in packed_bufs, nowhere else.

    Returns (q, qw8, begin, end, lq, win, w_read, bb, bbw, alen).
    """
    import jax
    import jax.numpy as jnp

    def i32(col):
        return jax.lax.bitcast_convert_type(col, jnp.int32)

    q, qw8, begin, end, lq, win, w_read = _unpack_job(job_buf, Lq)
    Nw1 = win_buf.shape[0]
    bb = win_buf[:, :LA]
    bbw = jax.lax.bitcast_convert_type(
        win_buf[:, LA:5 * LA].reshape(Nw1, LA, 4), jnp.float32)
    alen = i32(win_buf[:, 5 * LA:].reshape(Nw1, 1, 4))[:, 0]
    return q, qw8, begin, end, lq, win, w_read, bb, bbw, alen


@functools.partial(
    __import__("jax").jit,
    static_argnames=("match", "mismatch", "gap", "ins_scale", "Lq",
                     "n_win", "LA", "pallas", "band_w", "rounds",
                     "adaptive", "mesh", "nxt_k"))
def device_chunk_packed(job_buf, win_buf, *, match, mismatch, gap,
                        ins_scale, Lq, n_win, LA, pallas, band_w, rounds,
                        adaptive=False, mesh=None, nxt_k=2):
    """One chunk end to end in ONE jit dispatch from TWO byte buffers.

    Inputs arrive as ChunkPlan.packed_bufs()' concatenated layouts (two
    h2d transfers instead of ten — per-transfer latency dominated h2d
    at bench scale) and every refinement round plus the output packing
    runs inside a single executable (one synchronized dispatch per
    chunk, not per round). With ``mesh``, each round is the
    dp-sharded shard_map from _make_round_fn sequenced inside the
    same program (one psum per round, as before); the job buffer is
    sharded along jobs, the window buffer replicated.

    ``ins_scale`` may be a float or a per-round tuple of length
    ``rounds`` (PoaEngine passes a schedule — see its ins_scale_final).

    ``adaptive`` (static; dispatch_chunk gates it on RACON_TPU_ADAPTIVE
    and the schedule shape) rewrites the unrolled round chain as
    round 0, a while_loop over the replayable middle rounds (shared
    band width and scale — one trace), and the final round. The loop
    exits as soon as EVERY window is converged or overflowed: skipped
    middle rounds are exact replays for converged windows (the
    convergence scheduler's proof, sched/rounds.py) and discarded work
    for overflowed ones (host redo), so the packed output is
    bit-identical to the full chain while a converged chunk pays
    3 rounds instead of ``rounds``. Requires rounds >= 3 and uniform
    non-final scales; the caller checks both.
    """
    import jax.numpy as jnp

    (q, qw8, begin, end, lq, win, w_read, bb, bbw, alen) = \
        _unpack_bufs(job_buf, win_buf, Lq, LA)
    state, cov, rexec0 = _rounds_before_final(
        bb, bbw, alen, begin, end, q, qw8, lq, w_read, win,
        match=match, mismatch=mismatch, gap=gap, ins_scale=ins_scale,
        Lq=Lq, n_win=n_win, LA=LA, pallas=pallas, band_w=band_w,
        rounds=rounds, adaptive=adaptive, mesh=mesh, nxt_k=nxt_k)
    bb, bbw, alen, begin, end, ovf = state
    scales = ins_scale if isinstance(ins_scale, tuple) \
        else (ins_scale,) * rounds
    # Final round always runs (final-scale assembly).
    final = _make_round_fn(
        match=match, mismatch=mismatch, gap=gap, ins_scale=scales[-1],
        Lq=Lq, n_win=n_win, LA=LA, pallas=pallas,
        band_w=round_band_width(band_w, rounds - 1), mesh=mesh,
        nxt_k=nxt_k, detect=False)
    bb, bbw, alen, begin, end, cov, ovf, conv = final(
        bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf)
    return _pack_body(bb[:-1], cov, alen[:-1], ovf, rexec0 + 1,
                      jnp.int32(rounds))


def _rounds_before_final(bb, bbw, alen, begin, end, q, qw8, lq, w_read,
                         win, *, match, mismatch, gap, ins_scale, Lq,
                         n_win, LA, pallas, band_w, rounds, adaptive,
                         mesh, nxt_k):
    """Refinement rounds 0 .. rounds-2 of a chunk (traced body): the
    shared prefix of the fused program (device_chunk_packed) and the
    forward-only program (device_chunk_fwd), factored out so the
    decoupled walk path replays the exact round chain the fused path
    compiles — same calls, same order, same jaxpr prefix.

    Returns ``((bb, bbw, alen, begin, end, ovf), cov, rexec0)`` where
    ``rexec0`` (traced int32) counts the rounds executed so far — the
    caller's final round adds one.
    """
    import jax
    import jax.numpy as jnp

    ovf = jnp.zeros(n_win, dtype=bool)
    conv = jnp.zeros(n_win, dtype=bool)
    cov = None

    scales = ins_scale if isinstance(ins_scale, tuple) \
        else (ins_scale,) * rounds

    def make_round(bw, sc, det):
        return _make_round_fn(
            match=match, mismatch=mismatch, gap=gap, ins_scale=sc,
            Lq=Lq, n_win=n_win, LA=LA, pallas=pallas, band_w=bw,
            mesh=mesh, nxt_k=nxt_k, detect=det)

    if not adaptive:
        for r in range(rounds - 1):
            bw = round_band_width(band_w, r)
            bb, bbw, alen, begin, end, cov, ovf, conv = \
                make_round(bw, scales[r], False)(
                    bb, bbw, alen, begin, end, q, qw8, lq, w_read, win,
                    ovf)
        rexec0 = jnp.int32(rounds - 1)
    else:
        # Round 0 (full band): detection cannot fire — its input anchor
        # carries backbone quality weights and is not a replayable state
        # (device_merge.converged_windows).
        bb, bbw, alen, begin, end, cov, ovf, conv = \
            make_round(round_band_width(band_w, 0), scales[0], False)(
                bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf)
        # Middle rounds 1..rounds-2: one executable (round_band_width is
        # constant for r >= 1 and the non-final scales are uniform).
        # Padded dummy windows (zero anchors, no lanes) reproduce their
        # state from round 1 on, so the all-windows predicate terminates.
        mid = make_round(round_band_width(band_w, 1), scales[1], True)

        def cond(c):
            k = c[0]
            return (k < rounds - 1) & ~jnp.all(c[7] | c[8])

        def body(c):
            k, bb, bbw, alen, begin, end, cov, ovf, conv = c
            bb, bbw, alen, begin, end, cov, ovf, conv = mid(
                bb, bbw, alen, begin, end, q, qw8, lq, w_read, win, ovf)
            return (k + 1, bb, bbw, alen, begin, end, cov, ovf, conv)

        (k, bb, bbw, alen, begin, end, cov, ovf, conv) = \
            jax.lax.while_loop(cond, body, (jnp.int32(1), bb, bbw, alen,
                                            begin, end, cov, ovf, conv))
        rexec0 = k
    return (bb, bbw, alen, begin, end, ovf), cov, rexec0


@functools.partial(
    __import__("jax").jit,
    static_argnames=("match", "mismatch", "gap", "ins_scale", "Lq",
                     "n_win", "LA", "pallas", "band_w", "rounds",
                     "adaptive", "nxt_k"))
def device_chunk_fwd(job_buf, win_buf, *, match, mismatch, gap,
                     ins_scale, Lq, n_win, LA, pallas, band_w, rounds,
                     adaptive=False, nxt_k=2):
    """The forward/refinement half of a chunk in one jit dispatch: all
    non-final rounds fully fused (identical chain to
    device_chunk_packed, including the adaptive while_loop), then the
    FINAL round's geometry + NW forward only — its serialized traceback
    walk is NOT run here.

    Returns the packed direction planes plus everything the standalone
    walk dispatch (ops/colwalk.py walk_chunk_packed) needs to finish the
    chunk byte-identically: ``(dirs, nxt, nxt2, lt, t_off, klo, esc0,
    bb, bbw, alen, begin, end, ovf, rexec0)`` — the plane tuple from
    _lane_fwd at the final round's band width, the carried round state
    ENTERING the final round, and the executed-round count so far
    (None leaves where depth/layout elides a plane; jit treats them as
    empty pytree nodes). Every refinement round before the final one
    already consumed its own walk inside this program — only the last
    walk has no dependent anchor state, which is exactly why it alone
    can leave the critical path (pipeline/streaming.py walk stage).

    Single-device only: the decoupled path is gated off under a dp mesh
    (the walk-side vote psum would need the mesh threaded through a
    second executable for no overlap win — the per-shard walk still
    serializes on the same chips).
    """
    (q, qw8, begin, end, lq, win, w_read, bb, bbw, alen) = \
        _unpack_bufs(job_buf, win_buf, Lq, LA)
    state, _cov, rexec0 = _rounds_before_final(
        bb, bbw, alen, begin, end, q, qw8, lq, w_read, win,
        match=match, mismatch=mismatch, gap=gap, ins_scale=ins_scale,
        Lq=Lq, n_win=n_win, LA=LA, pallas=pallas, band_w=band_w,
        rounds=rounds, adaptive=adaptive, mesh=None, nxt_k=nxt_k)
    bb, bbw, alen, begin, end, ovf = state
    dirs, nxt, nxt2, lt, t_off, klo, esc0 = _lane_fwd(
        bb, alen, begin, end, q, lq, win, match=match, mismatch=mismatch,
        gap=gap, Lq=Lq, LA=LA, pallas=pallas,
        band_w=round_band_width(band_w, rounds - 1), nxt_k=nxt_k)
    return (dirs, nxt, nxt2, lt, t_off, klo, esc0,
            bb, bbw, alen, begin, end, ovf, rexec0)


def _pack_body(codes, cov, alen, ovf, rounds_exec, rounds_sched):
    """Flatten codes/cov/lengths/overflow into one uint8 buffer for a
    single d2h transfer (each synchronized pull pays a fixed latency).
    The byte layout is the contract collect_chunk unpacks.
    ``rounds_exec``/``rounds_sched`` (int32 scalars, 8 trailing bytes)
    record how many refinement rounds the chunk actually executed vs.
    had scheduled — the adaptive early exit's telemetry rides the same
    pull."""
    import jax
    import jax.numpy as jnp
    c16 = jnp.clip(cov, 0, 32767).astype(jnp.int16)
    tail = alen.astype(jnp.int32)
    rr = jnp.stack([jnp.asarray(rounds_exec).astype(jnp.int32),
                    jnp.asarray(rounds_sched).astype(jnp.int32)])
    return jnp.concatenate([
        codes.reshape(-1),
        jax.lax.bitcast_convert_type(c16, jnp.uint8).reshape(-1),
        jax.lax.bitcast_convert_type(tail, jnp.uint8).reshape(-1),
        ovf.astype(jnp.uint8),
        jax.lax.bitcast_convert_type(rr, jnp.uint8).reshape(-1),
    ])


def put_chunk_bufs(plan: ChunkPlan, mesh=None) -> Tuple[object, object]:
    """Start the (async) h2d of a chunk's two packed byte buffers.

    ``jax.device_put`` returns immediately, so calling this for chunk
    i+1 before chunk i's results sync overlaps the transfer with
    compute — the primitive behind both the scheduler's prefetch
    (sched/scheduler.py::put_chunk) and the streaming pipeline's h2d
    stage (racon_tpu/pipeline/streaming.py). The recorded seconds cover
    only the synchronous serialization/enqueue portion.
    """
    import time
    import jax
    from racon_tpu.obs.metrics import record_dp_shard_lanes, record_h2d
    from racon_tpu.resilience.retry import call as retry_call

    job_h, win_h = plan.packed_bufs()

    def _put():
        t0 = time.perf_counter()
        if mesh is None:
            job_buf, win_buf = jax.device_put((job_h, win_h))
        else:
            from jax.sharding import NamedSharding, PartitionSpec
            job_buf = jax.device_put(
                job_h, NamedSharding(mesh, PartitionSpec("dp")))
            win_buf = jax.device_put(
                win_h, NamedSharding(mesh, PartitionSpec()))
        record_h2d(job_h.nbytes + win_h.nbytes,
                   time.perf_counter() - t0, name="h2d/chunk")
        return job_buf, win_buf

    # The transfer retries whole: device_put is idempotent from the
    # host buffers, and a RetryExhausted here is the degradation signal
    # the engine catches to route the chunk to the host path.
    from racon_tpu.ops.budget import transfer_deadline_s
    bufs = retry_call(
        "h2d/chunk", _put,
        deadline_s=transfer_deadline_s(job_h.nbytes + win_h.nbytes,
                                       "h2d"))
    if mesh is not None:
        record_dp_shard_lanes(plan.n_jobs, job_h.shape[0],
                              mesh.shape["dp"])
    return bufs


def dispatch_chunk(plan: ChunkPlan, *, match: int, mismatch: int,
                   gap: int, ins_scale: float, rounds: int, mesh=None,
                   bufs: Optional[Tuple[object, object]] = None):
    """Ship a chunk to the device and chain all refinement rounds —
    returns the (still in-flight) packed output array. No host sync:
    the caller may dispatch further chunks before collecting, so h2d of
    chunk i+1 overlaps chunk i's compute.

    TWO h2d byte buffers, then the whole chunk (all rounds + output
    packing) as ONE dispatch — per-transfer and per-dispatch latency
    otherwise dominate. ``bufs`` takes a pre-transferred
    :func:`put_chunk_bufs` result so a caller can overlap the h2d with
    earlier compute; None ships the buffers here.
    """
    ndp = mesh.shape["dp"] if mesh is not None else 1
    pallas = _use_pallas(plan.B // ndp, plan.Lq, plan.LA)
    band_w = (0 if envspec.read("RACON_TPU_NO_BAND")
              not in ("", "0", "false") else plan.band_w)
    # Walk depth for this chunk's banded forwards. Selected at the
    # round-0 (widest) band so every round of the chunk shares one k:
    # the k=4 nxt2 plane must fit the element budget at the largest
    # per-round geometry. The flat fallback has no nxt planes at all.
    from racon_tpu.ops.budget import walk_k_for
    nxt_k = walk_k_for(plan.B // ndp * plan.Lq * band_w) if band_w else 1
    from racon_tpu.ops.colwalk import chain_len
    from racon_tpu.obs.metrics import registry as obs_registry
    obs_registry().set("walk_chain_len",
                       chain_len(plan.LA, nxt_k if band_w else 1))
    if bufs is None:
        bufs = put_chunk_bufs(plan, mesh=mesh)
    job_buf, win_buf = bufs
    from racon_tpu.resilience.retry import call as retry_call
    # Adaptive early exit: only meaningful with at least one
    # skippable middle round, and only sound when every non-final
    # round shares one scale (the replay argument; PoaEngine's
    # schedule satisfies this by construction).
    sc = ins_scale if isinstance(ins_scale, tuple) \
        else (ins_scale,) * rounds
    adaptive = (envspec.read("RACON_TPU_ADAPTIVE")
                not in ("0", "false")
                and rounds >= 3 and len(set(sc[:-1])) <= 1)
    from racon_tpu.ops.budget import dispatch_deadline_s
    # Deadline scales with the chunk's forward-plane work: B reads
    # x Lq rows x band (or full LA) columns, once per round.
    cells = (plan.B * plan.Lq * (band_w if band_w else plan.LA)
             * max(rounds, 1))
    packed = retry_call(
        "dispatch/chunk", device_chunk_packed, job_buf, win_buf,
        match=match, mismatch=mismatch, gap=gap, ins_scale=ins_scale,
        Lq=plan.Lq, n_win=plan.n_win, LA=plan.LA,
        pallas=pallas, band_w=band_w, rounds=rounds,
        adaptive=adaptive, mesh=mesh, nxt_k=nxt_k,
        deadline_s=dispatch_deadline_s(cells), compiles=True)
    obs_registry().inc("device_dispatches")
    return packed


def chunk_statics(plan: ChunkPlan, *, ins_scale, rounds: int) -> dict:
    """The per-chunk static selections dispatch_chunk makes (pallas /
    band width / walk depth / adaptive gate), as one dict — the
    decoupled path computes them ONCE here and threads the same values
    through both its executables, so the fwd and walk programs can
    never disagree about layout or depth. Single-device form (ndp=1):
    the decoupled walk is gated off under a mesh."""
    pallas = _use_pallas(plan.B, plan.Lq, plan.LA)
    band_w = (0 if envspec.read("RACON_TPU_NO_BAND")
              not in ("", "0", "false") else plan.band_w)
    from racon_tpu.ops.budget import walk_k_for
    nxt_k = walk_k_for(plan.B * plan.Lq * band_w) if band_w else 1
    sc = ins_scale if isinstance(ins_scale, tuple) \
        else (ins_scale,) * rounds
    adaptive = (envspec.read("RACON_TPU_ADAPTIVE")
                not in ("0", "false")
                and rounds >= 3 and len(set(sc[:-1])) <= 1)
    return {"pallas": pallas, "band_w": band_w, "nxt_k": nxt_k,
            "adaptive": adaptive}


def walk_plane_bytes_for(plan: ChunkPlan, *, ins_scale, rounds: int,
                         statics: Optional[dict] = None) -> int:
    """Device-resident bytes of the walk-input planes one queued chunk
    holds across the decoupled handoff — budget.walk_plane_bytes at the
    FINAL round's band width (the only round whose planes outlive their
    dispatch). The streaming executor's admission check compares this
    against budget.walk_queue_depth's aggregate cap."""
    from racon_tpu.ops.budget import walk_plane_bytes
    st = statics if statics is not None else \
        chunk_statics(plan, ins_scale=ins_scale, rounds=rounds)
    band_w = st["band_w"]
    W = round_band_width(band_w, rounds - 1) if band_w else plan.LA
    return walk_plane_bytes(plan.B, plan.Lq, W,
                            st["nxt_k"] if band_w else 1)


def dispatch_chunk_fwd(plan: ChunkPlan, *, match: int, mismatch: int,
                       gap: int, ins_scale, rounds: int,
                       bufs: Optional[Tuple[object, object]] = None):
    """Ship a chunk's forward/refinement half (device_chunk_fwd) —
    returns ``(fwd_out, meta)`` where ``fwd_out`` is the still-in-flight
    plane/state tuple and ``meta`` the static selections plus the live
    ``job_buf`` that ops/colwalk.py::dispatch_walk needs to finish the
    chunk. Same "dispatch/chunk" retry site and geometry deadline as
    the fused dispatch (it IS the chunk's forward dispatch); the walk
    dispatch adds its own "dispatch/walk" envelope.

    Single-device only (no ``mesh``): the streaming executor falls back
    to the fused path under dp — see device_chunk_fwd's docstring.
    """
    from racon_tpu.obs.metrics import registry as obs_registry
    from racon_tpu.ops.budget import dispatch_deadline_s
    from racon_tpu.ops.colwalk import chain_len
    from racon_tpu.resilience.retry import call as retry_call

    st = chunk_statics(plan, ins_scale=ins_scale, rounds=rounds)
    band_w = st["band_w"]
    obs_registry().set("walk_chain_len",
                       chain_len(plan.LA, st["nxt_k"] if band_w else 1))
    if bufs is None:
        bufs = put_chunk_bufs(plan)
    job_buf, win_buf = bufs
    cells = (plan.B * plan.Lq * (band_w if band_w else plan.LA)
             * max(rounds, 1))
    fwd_out = retry_call(
        "dispatch/chunk", device_chunk_fwd, job_buf, win_buf,
        match=match, mismatch=mismatch, gap=gap, ins_scale=ins_scale,
        Lq=plan.Lq, n_win=plan.n_win, LA=plan.LA,
        pallas=st["pallas"], band_w=band_w, rounds=rounds,
        adaptive=st["adaptive"], nxt_k=st["nxt_k"],
        deadline_s=dispatch_deadline_s(cells), compiles=True)
    obs_registry().inc("device_dispatches")
    meta = dict(st, job_buf=job_buf, ins_scale=ins_scale, rounds=rounds)
    return fwd_out, meta


def collect_chunk(plan: ChunkPlan, packed
                  ) -> Tuple[List[Optional[bytes]],
                             List[Optional[np.ndarray]]]:
    """Pull a dispatched chunk's packed output and unpack per window.

    Returns (consensus codes bytes per window, coverage arrays). A
    window whose consensus outgrew the padded anchor width (sticky
    ``ovf`` flag) yields ``None`` in both lists — the caller must re-run
    it on the unbounded host path instead of shipping a silently
    truncated string.
    """
    import time
    from racon_tpu.obs.metrics import record_d2h
    from racon_tpu.resilience.retry import call as retry_call

    def _pull():
        t0 = time.perf_counter()
        ph = np.asarray(packed)
        # The pull blocks until the chunk's compute drains too, so this
        # is "time blocked in d2h", an upper bound on pure transfer
        # (metrics module docstring discusses the bandwidth-estimate
        # semantics).
        record_d2h(ph.nbytes, time.perf_counter() - t0, name="d2h/chunk")
        return ph

    from racon_tpu.ops.budget import transfer_deadline_s
    # Packed output layout (below): Nw*LA codes + 2*Nw*LA cov(int16)
    # + 4*Nw alen + Nw ovf + 8 adaptive-round bytes.
    out_bytes = 3 * plan.n_win * plan.LA + 5 * plan.n_win + 8
    ph = retry_call("d2h/chunk", _pull,
                    deadline_s=transfer_deadline_s(out_bytes, "d2h"))
    Nw, LA = plan.n_win, plan.LA
    codes_h = ph[:Nw * LA].reshape(Nw, LA)
    cov_h = ph[Nw * LA:3 * Nw * LA].view(np.int16).reshape(Nw, LA)
    alen_h = ph[3 * Nw * LA:3 * Nw * LA + 4 * Nw].view(np.int32)[:Nw]
    base = 3 * Nw * LA + 4 * Nw
    ovf_h = ph[base:base + Nw] != 0
    rex = int(ph[base + Nw:base + Nw + 4].view(np.int32)[0])
    rsch = int(ph[base + Nw + 4:base + Nw + 8].view(np.int32)[0])
    from racon_tpu.obs.metrics import registry as obs_registry
    reg = obs_registry()
    reg.inc("adaptive_rounds_executed", rex)
    reg.inc("adaptive_rounds_scheduled", rsch)
    if rex < rsch:
        reg.inc("adaptive_early_exits")

    out_codes: List[Optional[bytes]] = []
    out_cov: List[Optional[np.ndarray]] = []
    for wi in range(plan.n_real_win):
        if ovf_h[wi]:
            out_codes.append(None)
            out_cov.append(None)
            continue
        L = int(alen_h[wi])
        out_codes.append(codes_h[wi, :L].tobytes())
        out_cov.append(cov_h[wi, :L].astype(np.int32))
    return out_codes, out_cov
