"""Device-side overlap alignment: PAF/MHAP breaking points on the TPU.

The reference aligns every CIGAR-less overlap with edlib on a CPU thread
pool, then walks the CIGAR base by base to find per-window breaking
points (src/polisher.cpp:351-364, src/overlap.cpp:179-282). At genome
scale this phase dominates initialize on the host.

TPU restructuring: overlaps batch through the same banded NW forward
kernel as window consensus (racon_tpu/ops/pallas/band_kernel.py), the
column-walk traceback (racon_tpu/ops/colwalk.py) yields the consuming
op + query index per TARGET column, and the breaking points fall out as
per-window first/last-match reductions over that column grid — no CIGAR
string ever materializes, and only [B, NW, 4] breaking-point rows leave
the device (a CIGAR d2h would be ~Lq+Lt bytes per overlap).

Exactness contract (same as the consensus engine): per-lane banded
optimality is certified by the tightened escape bound; lanes that fail
it — or whose walk saturated an up-run counter — are returned to the
caller for the native aligner fallback.

Length routing (round 7): jobs that fit the untiled whole-read budget
(~9 kb at the 128-lane grid) run exactly as before, bit-identically.
Longer jobs no longer skip the device: they route through the TILED
forward (``_tiled_chunk_breaking_points``) — a lax.scan over
query-axis tiles of the frontier-carrying band kernel
(band_kernel.fw_dirs_band_tile), with per-tile band re-centering, a
staircase escape certificate over the running band clearance, and a
stitched column walk over the per-tile slabs. Admission comes from
budget.tile_plan's (lanes, W, T, ch) tier table; only jobs no tier
admits (or whose certificate fails) reach the native path.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import functools
import os
from racon_tpu.utils import envspec

import numpy as np

from racon_tpu.ops.cigar import DIAG
from racon_tpu.ops.device_poa import _packed_byte_slice, _round_up
from racon_tpu.ops.pallas.band_kernel import TB   # lane grid (= chunk B)
from racon_tpu.ops.budget import (MIN_CH, VMEM_BUDGET as _VMEM_BUDGET,
                                  device_hbm_bytes, max_dir_elems,
                                  tile_plan, vmem_est as _vmem_est)
# Dirs/nxt-plane element budget: the column walk's flat gather index
# must stay under 2^31 and each plane's HBM buffer under the TPU's 2 GB
# single-buffer ceiling. Derived in racon_tpu/ops/budget.py, SHARED with
# the consensus engine — round 5's independently-maintained caps (1.6e9
# there, 1.9e9 here) disagreed by 0.7% and silently routed EVERY 8 kb
# genome overlap (128 x 8192 x 1536 = 1.61e9) to the native path.
MAX_DIR_ELEMS = max_dir_elems(1)


def _pick_tiles(W: int, Lq: int, nxt_k: int = 2) -> Tuple[int, int]:
    """(tb, ch) for the band kernel: full 128 lanes and the largest row
    tile the VMEM model admits. Never below ``MIN_CH``: the int32 qT
    block is (ch, 128) and Mosaic refuses a sublane dim under 8 (the
    former ch=4 tier did not compile). Admission guarantees MIN_CH
    fits."""
    for ch in (32, 16, MIN_CH):
        if Lq % ch == 0 and _vmem_est(W, Lq, ch, nxt_k) <= _VMEM_BUDGET:
            return TB, ch
    raise ValueError(
        f"[racon_tpu::ovl_align] no row tile fits VMEM at W={W}, "
        f"Lq={Lq}, nxt_k={nxt_k}")


def band_width_for_read(lq: int, lt: int) -> int:
    """Band width that certifies noisy long-read alignments.

    At edit-distance scoring (m=0, g=-1 — edlib parity) the tightened
    escape bound certifies iff ED_banded <= |lt-lq| + 2*wl + 2, so the
    half-width must exceed half the expected edit distance: read-vs-
    draft difference runs ~12-15% for ONT, hence wl ~ L/13 plus slack.
    Under-banding is safe (escape failure -> native fallback), just
    wasted device work. |lt - lq| rides on top.
    """
    return _round_up(abs(lt - lq) + 2 * (max(lq, lt) // 13 + 64) + 1, 128)


@functools.partial(
    __import__("jax").jit,
    static_argnames=("match", "mismatch", "gap", "W", "w_len", "NW", "Lq",
                     "LA", "pallas", "nxt_k"))
def _chunk_breaking_points(q, t, lq, lt, t_begin, *, match, mismatch, gap,
                           W, w_len, NW, Lq, LA, pallas, nxt_k=2):
    """One device chunk: banded forward + column walk + per-window
    first/last-match reduction.

    Returns (first_c, qi_f, last_c, qi_l  — all int32[B, NW], column/
    query indices RELATIVE to each lane's slice —, valid bool[B, NW],
    fail f32[B] nonzero where the lane needs the native fallback).
    """
    import jax
    import jax.numpy as jnp
    from racon_tpu.ops.colwalk import col_walk
    from racon_tpu.ops.pallas.band_kernel import (
        fw_dirs_band, fw_dirs_band_xla, band_geometry)

    B = q.shape[0]
    klo, wl = band_geometry(lq, lt, W)
    PW = W + Lq
    # Pre-shifted per-lane target window: tband[b, y] = t[b, klo_b + y],
    # built from the FLATTENED target table via the shared i32-packed
    # batched dynamic_slice (4 cells per descriptor word). A slice may
    # spill into the neighbouring lane's row where the old per-row
    # padded build read zeros — every such byte is out of [0, lt) and
    # the okb mask overwrites it, so tband is bit-identical.
    tab = jnp.concatenate(
        [jnp.zeros((PW,), jnp.uint8), t.reshape(-1),
         jnp.zeros((PW,), jnp.uint8)])
    y = jnp.arange(PW, dtype=jnp.int32)[None, :]
    rel = klo[:, None] + y
    okb = (rel >= 0) & (rel < lt[:, None])
    start = jnp.arange(B, dtype=jnp.int32) * LA + klo + PW
    sl = _packed_byte_slice(tab, start, PW)
    tband = jnp.where(okb, sl, 7).astype(jnp.uint8)

    if pallas:
        tb, ch = _pick_tiles(W, Lq, nxt_k)
        fwd = functools.partial(fw_dirs_band, tb=tb, ch=ch)
    else:
        fwd = fw_dirs_band_xla
    if nxt_k >= 4:
        dirs, nxt, nxt2, hlast = fwd(
            tband, q.T, klo, lq, match=match, mismatch=mismatch, gap=gap,
            W=W, nxt_k=4)
    else:
        dirs, nxt, hlast = fwd(
            tband, q.T, klo, lq, match=match, mismatch=mismatch, gap=gap,
            W=W)
        nxt2 = None
    cols = col_walk(dirs, lq, lt, klo, jnp.zeros(B, jnp.int32), LA=LA,
                    layout="band_t" if pallas else "band", nxt=nxt,
                    nxt2=nxt2)

    # Tightened escape bound (same derivation as device_poa._round_core).
    xend = jnp.clip(lt - lq - klo, 0, W - 1)
    score = jnp.take_along_axis(hlast, xend[:, None], axis=1)[:, 0]
    bound = (jnp.maximum(match, 0) * (jnp.minimum(lq, lt) - wl - 1) +
             gap * (jnp.abs(lt - lq) + 2 * wl + 2))
    fail = ((score < bound) | (wl < 16)).astype(jnp.float32) + \
        cols["sat"].astype(jnp.float32)

    # Consumer op / query index per target column c (walk step c + 1).
    op = cols["op_c"][:, 1:LA + 1].astype(jnp.int32)     # [B, LA]
    qi = cols["qi_c"][:, 1:LA + 1].astype(jnp.int32)
    c = jnp.arange(LA, dtype=jnp.int32)[None, :]
    is_m = (c < lt[:, None]) & (op == DIAG)
    # Window of column c (absolute target coordinate), relative to the
    # lane's first touched window.
    widx = (t_begin[:, None] + c) // w_len - (t_begin // w_len)[:, None]
    HUGE = 2 ** 30
    firsts, lasts, valids = [], [], []
    for k in range(NW):
        mask = is_m & (widx == k)
        firsts.append(jnp.min(jnp.where(mask, c, HUGE), axis=1))
        lasts.append(jnp.max(jnp.where(mask, c, -1), axis=1))
        valids.append(jnp.any(mask, axis=1))
    first_c = jnp.stack(firsts, axis=1)                  # [B, NW]
    last_c = jnp.stack(lasts, axis=1)
    valid = jnp.stack(valids, axis=1)
    qi_f = jnp.take_along_axis(qi, jnp.clip(first_c, 0, LA - 1), axis=1)
    qi_l = jnp.take_along_axis(qi, jnp.clip(last_c, 0, LA - 1), axis=1)
    return first_c, qi_f, last_c, qi_l, valid, fail


@functools.partial(
    __import__("jax").jit,
    static_argnames=("match", "mismatch", "gap", "W", "w_len", "NW", "Lq",
                     "LA", "T", "tb", "ch", "pallas", "nxt_k"))
def _tiled_chunk_breaking_points(q, t, lq, lt, t_begin, *, match, mismatch,
                                 gap, W, w_len, NW, Lq, LA, T, tb, ch,
                                 pallas, nxt_k=2):
    """One ULTRALONG device chunk: lax.scan over query-axis tiles of the
    frontier-carrying band kernel, then one stitched column walk.

    Per tile the scan (one kernel compile serves every tile — the row
    origin i0 is a runtime input):

    1. gathers the tile's pre-shifted target window at the CURRENT band
       origin klo (re-centered between tiles, so each tile is a straight
       band but the tile sequence forms a staircase that can track
       |lt - lq| <= W/2 of drift),
    2. runs fw_dirs_band_tile / its XLA twin with the carried frontier
       (H row i0, packed (N,U,C) metadata of row i0, running hlast),
    3. updates the running band clearance ``cmin`` — the certificate
       below needs the MINIMUM distance from any LIVE tile's band edges
       (``i0 < lq``) to the legal-origin interval, and
    4. re-centers klo on the frontier argmax with a W/4..3W/4 dead zone
       (no-drift reads keep klo fixed and are bit-identical to the
       untiled straight band) clamped to [max(0,d)-W+1, min(0,d)] — the
       clamp keeps both DP corners reachable, so the terminal cell
       x_end = lt - lq - klo stays inside [0, W) at every tile and the
       captured end score survives the frontier shifts. The frontier
       shifts by d = klo' - klo (score fill NEG, metadata fill
       UC_BOUNDARY, hlast fill NEG — a shifted-out terminal score would
       mean the clamp proof was violated, and NEG fails the certificate
       rather than fabricating a result).

    The per-tile klo values are stacked and handed to the column walk
    (colwalk.py tile_klo), which maps stored row r through tile
    r // T's origin; the dual-column nxt contract survives tile
    boundaries unchanged because nxt bytes carry predecessor VALUES,
    not band slots. Emissions are int32 (absolute query indices exceed
    int16 past 32 kb).

    Staircase escape certificate: a path leaving the tiled band must
    cross a band edge at some tile, where its clearance to the legal
    diagonals is at least cmin, so (same counting as the straight-band
    bound with wl := cmin)

        score >= max(m,0)*(min(lq,lt) - cmin - 1)
                 + gap*(|lt - lq| + 2*cmin + 2)

    certifies banded == global. With the dead zone inactive cmin == wl
    and this is exactly the untiled bound. Only live tiles (``i0 <
    lq``) enter cmin: an escaping path from (0, 0) to (lq, lt) crosses
    a band edge at some row r <= lq, and tile i0 holds rows i0+1 ..
    i0+T, so r lies in a tile with i0 < r <= lq. Tiles wholly past lq
    hold no row of any such path; a lane shorter than its bucket's Lq
    still runs them, and their re-centering on a dead frontier may
    drive klo into the clamp (harmless: the clamp keeps hlF's terminal
    cell in range), but their clearance bounds nothing.

    Returns the same tuple contract as _chunk_breaking_points.
    """
    import jax
    import jax.numpy as jnp
    from racon_tpu.ops.colwalk import col_walk
    from racon_tpu.ops.pallas.band_kernel import (
        fw_dirs_band_tile, fw_dirs_band_xla_tile, uc_boundary)

    BND = uc_boundary(nxt_k)

    B = q.shape[0]
    n_tiles = Lq // T
    NEG = -(2 ** 30)
    lanei = jnp.arange(B, dtype=jnp.int32)
    xr = jnp.arange(W, dtype=jnp.int32)[None, :]
    delta = lt - lq
    # Legal band-origin interval: klo must keep (0, 0) reachable
    # (klo <= 0 via klo_hi; start corner at x = -klo < W via klo_lo) and
    # the terminal (lq, lt) in band (x_end = delta - klo in [0, W)).
    klo_lo = jnp.maximum(0, delta) - (W - 1)
    klo_hi = jnp.minimum(0, delta)
    wl = (W - 1 - jnp.abs(delta)) // 2
    klo0 = jnp.clip(jnp.minimum(0, delta) - wl, klo_lo, klo_hi)
    j00 = klo0[:, None] + xr
    prev0 = jnp.where(j00 >= 0, j00 * gap, NEG).astype(jnp.int32)
    uc0 = jnp.full((B, W), BND, jnp.int32)
    hl0 = prev0

    PW = W + T
    tab = jnp.concatenate(
        [jnp.zeros((PW,), jnp.uint8), t.reshape(-1),
         jnp.zeros((PW,), jnp.uint8)])
    y = jnp.arange(PW, dtype=jnp.int32)[None, :]
    qT = q.T

    def tile_body(carry, i0):
        prev, uc, hl, klo, cmin = carry
        # Only tiles holding rows of the lane's alignment (rows i0+1..)
        # count: past lq the anchor chases a dead frontier into the clamp.
        cmin = jnp.where(
            i0 < lq,
            jnp.minimum(cmin, jnp.minimum(klo_hi - klo, klo - klo_lo)),
            cmin)
        # This tile's pre-shifted target window at the current origin:
        # tband[b, y] = t[b, klo_b + i0 + y] (bucketing guarantees
        # LA >= Lq, so the padded-table slice stays in range).
        rel = klo[:, None] + i0 + y
        okb = (rel >= 0) & (rel < lt[:, None])
        start = lanei * LA + klo + i0 + PW
        sl = _packed_byte_slice(tab, start, PW)
        tband = jnp.where(okb, sl, 7).astype(jnp.uint8)
        qT_t = jax.lax.dynamic_slice_in_dim(qT, i0, T, axis=0)
        i0v = jnp.full((B,), i0, jnp.int32)
        if pallas:
            fwd = functools.partial(fw_dirs_band_tile, tb=tb, ch=ch)
        else:
            fwd = fw_dirs_band_xla_tile
        if nxt_k >= 4:
            dirs, nxt, nxt2, hl2, prev2, uc2 = fwd(
                tband, qT_t, klo, lq, i0v, prev, uc, hl, match=match,
                mismatch=mismatch, gap=gap, W=W, nxt_k=4)
        else:
            dirs, nxt, hl2, prev2, uc2 = fwd(
                tband, qT_t, klo, lq, i0v, prev, uc, hl, match=match,
                mismatch=mismatch, gap=gap, W=W)
            nxt2 = None
        # Dead-zone re-centering on the frontier argmax (step 4 above).
        xstar = jnp.argmax(prev2, axis=1).astype(jnp.int32)
        shift = jnp.where(xstar < W // 4, xstar - W // 4,
                          jnp.where(xstar > (3 * W) // 4,
                                    xstar - (3 * W) // 4, 0))
        klo_n = jnp.clip(klo + shift, klo_lo, klo_hi)
        d = klo_n - klo
        xi = xr + d[:, None]
        okx = (xi >= 0) & (xi < W)
        xig = jnp.clip(xi, 0, W - 1)
        prev3 = jnp.where(
            okx, jnp.take_along_axis(prev2, xig, axis=1), NEG)
        uc3 = jnp.where(
            okx, jnp.take_along_axis(uc2, xig, axis=1), BND)
        hl3 = jnp.where(
            okx, jnp.take_along_axis(hl2, xig, axis=1), NEG)
        ys = (dirs, nxt, nxt2, klo) if nxt_k >= 4 else (dirs, nxt, klo)
        return (prev3, uc3, hl3, klo_n, cmin), ys

    i0s = jnp.arange(n_tiles, dtype=jnp.int32) * T
    carry0 = (prev0, uc0, hl0, klo0,
              jnp.full(klo0.shape, 2 ** 30, jnp.int32))
    if nxt_k >= 4:
        (_, _, hlF, kloF, cmin), (dslab, nslab, n2slab, klos) = \
            jax.lax.scan(tile_body, carry0, i0s)
    else:
        (_, _, hlF, kloF, cmin), (dslab, nslab, klos) = jax.lax.scan(
            tile_body, carry0, i0s)
        n2slab = None
    # Stacked per-tile slabs ARE the whole-read tensors: [n_tiles, T,
    # W, B] -> [Lq, W, B] (kernel layout; twin analogous) with rows in
    # global order.
    shape = (Lq, W, B) if pallas else (Lq, B, W)
    cells = dslab.reshape(shape)
    nxtp = nslab.reshape(shape)
    nxt2p = None if n2slab is None else n2slab.reshape(shape)
    cols = col_walk(cells, lq, lt, None, jnp.zeros(B, jnp.int32), LA=LA,
                    layout="band_t" if pallas else "band", nxt=nxtp,
                    nxt2=nxt2p, tile_klo=klos, tile_len=T, emit=jnp.int32)

    # hlF rides the frontier shifts, so the terminal cell is indexed
    # through the FINAL origin; the clamp proof keeps it in [0, W).
    xend = jnp.clip(lt - lq - kloF, 0, W - 1)
    score = jnp.take_along_axis(hlF, xend[:, None], axis=1)[:, 0]
    bound = (jnp.maximum(match, 0) * (jnp.minimum(lq, lt) - cmin - 1) +
             gap * (jnp.abs(delta) + 2 * cmin + 2))
    fail = ((score < bound) | (cmin < 16)).astype(jnp.float32) + \
        cols["sat"].astype(jnp.float32)

    op = cols["op_c"][:, 1:LA + 1]
    qi = cols["qi_c"][:, 1:LA + 1]
    c = jnp.arange(LA, dtype=jnp.int32)[None, :]
    is_m = (c < lt[:, None]) & (op == DIAG)
    widx = (t_begin[:, None] + c) // w_len - (t_begin // w_len)[:, None]
    # Scatter-reduce per window instead of the untiled path's per-window
    # Python loop: LA // w_len reaches ~230 at 114 kb reads, and the
    # loop's NW full-[B, LA] masked reductions would dominate the walk.
    wc = jnp.clip(widx, 0, NW - 1)
    rows = jnp.broadcast_to(lanei[:, None], (B, LA))
    HUGE = 2 ** 30
    first_c = jnp.full((B, NW), HUGE, jnp.int32).at[rows, wc].min(
        jnp.where(is_m, c, HUGE))
    last_c = jnp.full((B, NW), -1, jnp.int32).at[rows, wc].max(
        jnp.where(is_m, c, -1))
    valid = last_c >= 0
    qi_f = jnp.take_along_axis(qi, jnp.clip(first_c, 0, LA - 1), axis=1)
    qi_l = jnp.take_along_axis(qi, jnp.clip(last_c, 0, LA - 1), axis=1)
    # Trailing klos [n_tiles, B] is observability for tests/debugging
    # (which tiles re-centered); the collect loop reads out[:6] only.
    return first_c, qi_f, last_c, qi_l, valid, fail, klos


def _classify(pending, sequences):
    """Split ``pending`` into untiled device jobs ``(overlap, q_codes,
    t_codes, q_start)``, tiled jobs (the same plus their tile plan) and
    overlaps no device tier admits; returns those three lists."""
    from racon_tpu.ops.encode import encode_bases

    tiled_on = envspec.read("RACON_TPU_OVL_TILED") != "0"
    hbm = device_hbm_bytes() if tiled_on else 0
    jobs, tiled_jobs, over_budget = [], [], []
    for o in pending:
        qb, tb = o.alignment_operands(sequences)
        lq, lt = len(qb), len(tb)
        if lq < 1 or lt < 1:
            over_budget.append(o)
            continue
        q_start = o.q_begin if not o.strand else o.q_length - o.q_end
        W = _round_up(band_width_for_read(lq, lt), 512)
        lqp = _round_up(lq, 2048)
        if (TB * lqp * W <= MAX_DIR_ELEMS and
                _vmem_est(W, lqp, MIN_CH) <= _VMEM_BUDGET and
                max(lq, lt) < 2 ** 14):   # int16 walk emissions
            jobs.append((o, encode_bases(bytes(qb)),
                         encode_bases(bytes(tb)), q_start))
            continue
        plan = tile_plan(lq, lt, hbm_bytes=hbm) if tiled_on else None
        if plan is not None:
            tiled_jobs.append((o, encode_bases(bytes(qb)),
                               encode_bases(bytes(tb)), q_start, plan))
        else:
            over_budget.append(o)
    return jobs, tiled_jobs, over_budget


def _bucket(jobs):
    """RUN-level shape buckets ``(jobs, Lq, LA, W)`` of the untiled jobs.

    Every distinct (Lq, LA, W) triple is a fresh executable costing
    seconds to tens of seconds of compile — per-chunk shape maxima once
    turned the 2 Mb genome run's alignment phase into compile churn.
    Jobs sort by length and buckets grow greedily under the
    running-maxima budget (padded Lq from one job combined with the
    band width of another can overflow the int32 flat-index budget
    even when each job fits alone), so a uniform read set compiles
    exactly once; each bucket then executes in TB-lane chunks."""
    jobs.sort(key=lambda j: (len(j[1]), len(j[2])))
    buckets = []
    cur: List = []
    Lq = LA = W = 1
    for j in jobs:
        _, qc, tc, _ = j
        tLq = max(Lq, _round_up(len(qc), 2048))
        tLA = max(LA, _round_up(len(tc), 2048))
        tW = max(W, _round_up(band_width_for_read(len(qc), len(tc)), 512))
        if cur and (TB * tLq * tW > MAX_DIR_ELEMS or
                    _vmem_est(tW, tLq, MIN_CH) > _VMEM_BUDGET):
            buckets.append((cur, Lq, LA, W))
            cur = []
            tLq = _round_up(len(qc), 2048)
            tLA = _round_up(len(tc), 2048)
            tW = _round_up(band_width_for_read(len(qc), len(tc)), 512)
        Lq, LA, W = tLq, tLA, tW
        cur.append(j)
    if cur:
        buckets.append((cur, Lq, LA, W))
    return buckets


def _bucket_tiled(tiled_jobs):
    """One bucket ``(jobs, lanes, W, T, ch, Lq, LA, nxt_k)`` per tier.

    Every member passed tile_plan's element gate at ITS OWN padded Lq,
    and the bucket's running maxima only ever equal some member's
    padding, so one bucket per tier never overflows the cap. LA
    additionally rides up to Lq — the per-tile tband slice into the
    padded target table indexes lane*LA + klo + i0 + y and needs
    LA >= Lq to stay inside the neighbouring-lane slack
    (_tiled_chunk_breaking_points)."""
    tiled_buckets = []
    bytier = {}
    for j in tiled_jobs:
        bytier.setdefault(j[4].key(), []).append(j)
    for (lanes, W_t, T_t, ch_t, k_t), js in sorted(bytier.items()):
        js.sort(key=lambda j: (len(j[1]), len(j[2])))
        Lq_t = max(_round_up(len(j[1]), T_t) for j in js)
        LA_t = max(Lq_t, max(_round_up(len(j[2]), 2048) for j in js))
        tiled_buckets.append((js, lanes, W_t, T_t, ch_t, Lq_t, LA_t, k_t))
    return tiled_buckets


def _pack(sub, B, Lq, LA):
    """The chunk's host operands: ``sub``'s codes padded to B lanes."""
    q = np.zeros((B, Lq), np.uint8)
    t = np.zeros((B, LA), np.uint8)
    lq = np.ones(B, np.int32)
    lt = np.ones(B, np.int32)
    t_begin = np.zeros(B, np.int32)
    for b, job in enumerate(sub):
        o, qc, tc = job[:3]
        q[b, :len(qc)] = qc
        t[b, :len(tc)] = tc
        lq[b] = len(qc)
        lt[b] = len(tc)
        t_begin[b] = o.t_begin
    return q, t, lq, lt, t_begin


def _dispatch(buckets, tiled_buckets, window_length, *, match, mismatch,
              gap):
    """Dispatch every chunk before collecting any: jit calls are async,
    so chunk i+1's h2d overlaps chunk i's compute (h2d otherwise
    serializes with device time). Returns ``[(jobs, outputs), ...]``
    and the number of query-axis tiles the tiled chunks execute."""
    import jax
    from racon_tpu.obs import trace as _trace
    from racon_tpu.ops.budget import walk_k_for

    tracer = _trace.get_tracer()
    pallas = jax.default_backend() == "tpu"
    pending_out = []
    for bucket, Lq, LA, W in buckets:
        # Per-bucket walk depth: the u16 nxt2 plane must fit the element
        # cap at the BUCKET's padded geometry (walk_k_for degrades the
        # 8 kb genome overlaps to the dual-column walk) and its VMEM
        # block the smallest row tile.
        nxt_k = walk_k_for(TB * Lq * W)
        if nxt_k >= 4 and _vmem_est(W, Lq, MIN_CH, 4) > _VMEM_BUDGET:
            nxt_k = 2
        NW = LA // window_length + 2
        B = TB
        for s in range(0, len(bucket), B):
            sub = bucket[s:s + B]
            q, t, lq, lt, t_begin = _pack(sub, B, Lq, LA)
            with tracer.span("dispatch", "ovl_chunk", lanes=B, W=W):
                pending_out.append((sub, _chunk_breaking_points(
                    q, t, lq, lt, t_begin, match=match, mismatch=mismatch,
                    gap=gap, W=W, w_len=window_length, NW=NW, Lq=Lq, LA=LA,
                    pallas=pallas, nxt_k=nxt_k)))

    n_tiles_exec = 0
    for bucket, lanes, W, T, ch, Lq, LA, nxt_k in tiled_buckets:
        NW = LA // window_length + 2
        n_tiles = Lq // T
        for s in range(0, len(bucket), lanes):
            sub = bucket[s:s + lanes]
            # Lane count adapts down to the actual job count (pow2,
            # min 8): the stitched tensors scale with B, and a 3-job
            # tail chunk at 64 lanes would pay 21x the forward work.
            B = lanes
            while B // 2 >= max(8, len(sub)):
                B //= 2
            q, t, lq, lt, t_begin = _pack(sub, B, Lq, LA)
            with tracer.span("dispatch", "ovl_tiled_chunk", lanes=B,
                             W=W, tiles=n_tiles):
                pending_out.append((sub, _tiled_chunk_breaking_points(
                    q, t, lq, lt, t_begin, match=match, mismatch=mismatch,
                    gap=gap, W=W, w_len=window_length, NW=NW, Lq=Lq,
                    LA=LA, T=T, tb=B, ch=ch, pallas=pallas,
                    nxt_k=nxt_k)))
            n_tiles_exec += n_tiles
    return pending_out, n_tiles_exec


def _collect(pending_out, fallback) -> int:
    """Pull each chunk's results (blocking until the device has them),
    set the breaking points of every certified lane's overlap and append
    the rest to ``fallback``; returns how many were uncertified."""
    n_uncert = 0
    for sub, out in pending_out:
        # Untiled chunks return 6 fields; tiled chunks append a klos
        # observability field that the collect path does not consume.
        first_c, qi_f, last_c, qi_l, valid, fail = map(np.asarray, out[:6])
        for b, job in enumerate(sub):
            o, q_start = job[0], job[3]
            if fail[b]:
                fallback.append(o)
                n_uncert += 1
                continue
            v = valid[b]
            rows = np.stack([
                o.t_begin + first_c[b][v],
                q_start + qi_f[b][v],
                o.t_begin + last_c[b][v] + 1,
                q_start + qi_l[b][v] + 1,
            ], axis=1).astype(np.int64)
            o.breaking_points = rows
    return n_uncert


def device_breaking_points(pending, sequences, window_length: int, *,
                           match: int, mismatch: int, gap: int,
                           log=None) -> List:
    """Compute breaking points on device for as many overlaps as the
    budget admits; returns the overlaps that still need the native path
    (too long, escape-bound failure, or walk saturation).

    Sets ``o.breaking_points`` (int64[N, 4], reference row format) on
    every handled overlap — ``find_breaking_points`` then no-ops.
    Its three host stretches are the ``align`` spans ``device_prep``,
    ``device_dispatch`` and ``device_collect`` (obs/metrics.py
    ``timed_span``).
    """
    from racon_tpu.obs import metrics as obs_metrics

    with obs_metrics.timed_span("align", "device_prep",
                                overlaps=len(pending)):
        jobs, tiled_jobs, fallback = _classify(pending, sequences)
        buckets = _bucket(jobs)
        tiled_buckets = _bucket_tiled(tiled_jobs)
    # The two fallback causes are counted INDEPENDENTLY, at the point
    # each is known: n_budget at classification, n_uncert at collect.
    n_budget = len(fallback)
    n_device = len(jobs) + len(tiled_jobs)
    if not n_device:
        # A fully-rejected set must still say so — this exact condition
        # once hid the genome workload falling back wholesale.
        if log is not None and fallback:
            print(f"[racon_tpu::Polisher::initialize] all {len(pending)} "
                  "overlap alignments exceed the device length budget; "
                  "using the native path", file=log)
        obs_metrics.record_ovl(device_jobs=0, native_jobs=len(fallback),
                               tiles=0)
        return fallback

    with obs_metrics.timed_span("align", "device_dispatch",
                                overlaps=n_device):
        pending_out, n_tiles_exec = _dispatch(
            buckets, tiled_buckets, window_length, match=match,
            mismatch=mismatch, gap=gap)
    with obs_metrics.timed_span("align", "device_collect",
                                overlaps=n_device):
        n_uncert = _collect(pending_out, fallback)
    obs_metrics.record_ovl(
        device_jobs=n_device - n_uncert,
        native_jobs=len(fallback), tiles=n_tiles_exec)
    if log is not None and fallback:
        print(f"[racon_tpu::Polisher::initialize] {len(fallback)} of "
              f"{len(pending)} overlap alignments fall back to the "
              f"native path ({n_budget} over the device length budget, "
              f"{n_uncert} uncertified)", file=log)
    return fallback
