"""Tiled banded overlap alignment (ops/ovl_align.py round 7).

The tiled path runs the band forward kernel over query-axis tiles of T
rows, carrying the DP frontier (score row + packed N/U/C metadata +
last-row capture) between tiles and re-centering the band anchor at
tile boundaries. Its exactness contract: with the dead-zone anchor
fixed (no drift), every tile computes the SAME cells as the untiled
kernel, so outputs are bit-identical; with drift, the stitched walk and
the staircase escape certificate must still yield the native-identical
breaking points or hand the lane back uncertified.

These tests pin, bottom-up:
  * the frontier carry at the kernel level (chained tiled twin ==
    untiled twin on dirs/nxt/hlast),
  * chunk-level bit-identity vs the untiled chunk (single tile and
    multi-tile, no drift),
  * anchor re-centering through a controlled diagonal excursion,
  * lanes shorter than the chunk's Lq: the tiles past a lane's last row
    stay out of its certificate (every lane certifies, as it does alone
    at its own length), and an excursion past the band in the rows it
    does hold still fails it,
  * polisher-level device-vs-native layer equality on reads past the
    ~9 kb untiled ceiling, of one length and of mixed lengths in one
    tier, with the registry confirming zero native fallbacks,
  * the independent over-budget / uncertified fallback accounting,
  * the RACON_TPU_OVL_TILED env gate.
"""

import gzip
import io

import numpy as np
import pytest

import jax.numpy as jnp

from racon_tpu.models.polisher import create_polisher, PolisherType
from racon_tpu.obs import metrics as obs_metrics
from racon_tpu.ops import budget, ovl_align
from racon_tpu.ops.ovl_align import (_chunk_breaking_points,
                                     _tiled_chunk_breaking_points)
from racon_tpu.ops.pallas.band_kernel import (UC_BOUNDARY, band_geometry,
                                              fw_dirs_band_xla,
                                              fw_dirs_band_xla_tile)
from racon_tpu.utils.logger import Logger

from test_ovl_align import _layer_snapshot, _write_dataset

_BASES = np.frombuffer(b"ACGT", np.uint8)


def _mutate_codes(rng, tgt, err):
    """Mutate a 0..3 code array at ``err`` total error (del/sub/ins in
    equal thirds), returning the query codes."""
    out = []
    for base in tgt:
        r = rng.random()
        if r < err / 3:
            continue
        elif r < 2 * err / 3:
            out.append(int(rng.integers(0, 4)))
        else:
            out.append(int(base))
        if rng.random() < err / 3:
            out.append(int(rng.integers(0, 4)))
    return np.array(out, np.uint8)


def _pack_lanes(pairs, B, Lq, LA):
    """(query, target) code pairs in lanes 0.. of a B-lane chunk padded
    to the chunk geometry, the rest padding lanes, mirroring the
    dispatcher's packing."""
    q = np.zeros((B, Lq), np.uint8)
    t = np.zeros((B, LA), np.uint8)
    lq = np.ones(B, np.int32)
    lt = np.ones(B, np.int32)
    for b, (qq, tt) in enumerate(pairs):
        q[b, :len(qq)] = qq
        t[b, :len(tt)] = tt
        lq[b] = len(qq)
        lt[b] = len(tt)
    return q, t, lq, lt, np.zeros(B, np.int32)


def _mk_chunk(rng, read_len, err, B, Lq, LA):
    """B lanes of mutated (query, target) code pairs at random target
    offsets."""
    pairs, t_begin = [], np.zeros(B, np.int32)
    for b in range(B):
        tgt = rng.integers(0, 4, read_len).astype(np.uint8)
        pairs.append((_mutate_codes(rng, tgt, err), tgt))
        t_begin[b] = int(rng.integers(0, 700))
    return _pack_lanes(pairs, B, Lq, LA)[:4] + (t_begin,)


def _chunk_kw(W, Lq, LA):
    return dict(match=0, mismatch=-1, gap=-1, W=W, w_len=500,
                NW=LA // 500 + 2, Lq=Lq, LA=LA)


def _run_tiled(q, t, lq, lt, t_begin, *, W, T, Lq, LA):
    return [np.asarray(a) for a in _tiled_chunk_breaking_points(
        q, t, lq, lt, t_begin, T=T, tb=q.shape[0], ch=8, pallas=False,
        **_chunk_kw(W, Lq, LA))]


def _run_both(q, t, lq, lt, t_begin, *, W, T, Lq, LA):
    out_u = [np.asarray(a) for a in _chunk_breaking_points(
        q, t, lq, lt, t_begin, pallas=False, **_chunk_kw(W, Lq, LA))]
    return out_u, _run_tiled(q, t, lq, lt, t_begin, W=W, T=T, Lq=Lq,
                             LA=LA)


def test_single_tile_chunk_bit_identity():
    """One tile covering the whole read: the tiled chunk must reproduce
    the untiled chunk bit-for-bit on every output field, and certify
    every lane (fail == 0) at 10% error."""
    rng = np.random.default_rng(11)
    q, t, lq, lt, t_begin = _mk_chunk(rng, 1800, 0.10, B=8,
                                      Lq=2048, LA=2048)
    out_u, out_t = _run_both(q, t, lq, lt, t_begin, W=512, T=2048,
                             Lq=2048, LA=2048)
    for i, (a, b) in enumerate(zip(out_u, out_t)):
        assert np.array_equal(a, b), f"field {i} differs"
    assert not out_u[5].any()


def test_multi_tile_no_drift_chunk_bit_identity():
    """Two tiles, anchor never re-centers (drift stays in the dead
    zone): the frontier carry must make the stitched result identical
    to the untiled chunk."""
    rng = np.random.default_rng(12)
    q, t, lq, lt, t_begin = _mk_chunk(rng, 3900, 0.08, B=8,
                                      Lq=4096, LA=4096)
    out_u, out_t = _run_both(q, t, lq, lt, t_begin, W=512, T=2048,
                             Lq=4096, LA=4096)
    for i, (a, b) in enumerate(zip(out_u, out_t)):
        assert np.array_equal(a, b), f"field {i} differs"
    assert not out_u[5].any()
    # The klos observability field reports one row per tile; with the
    # anchor in the dead zone it never moves.
    klos = out_t[6]
    assert klos.shape[0] == 2
    assert np.array_equal(klos[0], klos[1])


def test_frontier_carry_matches_untiled_twin():
    """Kernel-level: chaining the tiled XLA twin across tiles with the
    carried (prev, uc, hlast) frontier reproduces the untiled twin's
    dirs/nxt/hlast exactly (same klo, so no re-centering involved)."""
    rng = np.random.default_rng(0)
    B, Lq, W, T = 8, 64, 128, 32
    lq = rng.integers(40, Lq + 1, B).astype(np.int32)
    lt = (lq + rng.integers(-5, 6, B)).clip(5).astype(np.int32)
    qT = rng.integers(0, 4, (Lq, B)).astype(np.uint8)
    klo, _ = band_geometry(jnp.asarray(lq), jnp.asarray(lt), W)
    klo_h = np.asarray(klo)
    ts = rng.integers(0, 4, (B, int(lt.max()))).astype(np.uint8)

    def band_window(row0, height):
        win = np.full((B, height), 7, np.uint8)
        for b in range(B):
            for y in range(height):
                j = klo_h[b] + row0 + y
                if 0 <= j < lt[b]:
                    win[b, y] = ts[b, j]
        return win

    M, X, G = 0, -1, -1
    du, nu, hu = fw_dirs_band_xla(jnp.asarray(band_window(0, W + Lq)),
                                  jnp.asarray(qT), klo, jnp.asarray(lq),
                                  match=M, mismatch=X, gap=G, W=W)

    NEG = -(2 ** 30)
    j0 = klo_h[:, None] + np.arange(W)[None, :]
    prev = jnp.asarray(np.where(j0 >= 0, j0 * G, NEG).astype(np.int32))
    uc = jnp.asarray(np.full((B, W), UC_BOUNDARY, np.int32))
    hl = prev
    ds, ns = [], []
    for tile in range(Lq // T):
        i0 = jnp.full((B,), tile * T, jnp.int32)
        d, n, hl, prev, uc = fw_dirs_band_xla_tile(
            jnp.asarray(band_window(tile * T, W + T)),
            jnp.asarray(qT[tile * T:(tile + 1) * T]),
            klo, jnp.asarray(lq), i0, prev, uc, hl,
            match=M, mismatch=X, gap=G, W=W)
        ds.append(np.asarray(d))
        ns.append(np.asarray(n))
    assert np.array_equal(np.concatenate(ds, axis=0), np.asarray(du))
    assert np.array_equal(np.concatenate(ns, axis=0), np.asarray(nu))
    assert np.array_equal(np.asarray(hl), np.asarray(hu))


def test_anchor_recentering_tracks_excursion():
    """A controlled diagonal excursion (300 spread deletions followed by
    300 spread insertions, net delta = 0) pushes the frontier argmax out
    of the dead zone, so the anchor must re-center mid-read — and the
    stitched walk through the re-centered tiles must still match the
    untiled chunk (whose straight W=1024 band also holds the path)."""
    rng = np.random.default_rng(4)
    n = 2000
    qq = rng.integers(0, 4, n).astype(np.uint8)
    # Rows 500..1400 drift to diagonal -300 (delete every 3rd base),
    # rows 1400..2000 drift back to 0 (insert after every 2nd base).
    mid = np.array([b for i, b in enumerate(qq[500:1400]) if i % 3 != 0],
                   np.uint8)
    tail = []
    for i, b in enumerate(qq[1400:2000]):
        tail.append(int(b))
        if i % 2 == 1:
            tail.append(int(rng.integers(0, 4)))
    tt = np.concatenate([qq[:500], mid, np.array(tail, np.uint8)])
    assert len(tt) == n  # net delta 0, excursion -300

    B, W, T, Lq, LA = 8, 1024, 256, 2048, 2048
    q = np.zeros((B, Lq), np.uint8)
    t = np.zeros((B, LA), np.uint8)
    q[0, :n] = qq
    t[0, :n] = tt
    # Lanes 1..7: drift-free copies; their anchor must never move.
    for b in range(1, B):
        q[b, :n] = qq
        t[b, :n] = qq
    lq = np.full(B, n, np.int32)
    lt = np.full(B, n, np.int32)
    t_begin = np.zeros(B, np.int32)

    out_u, out_t = _run_both(q, t, lq, lt, t_begin, W=W, T=T,
                             Lq=Lq, LA=LA)
    # Certified: the -300 excursion stays under the re-centered band's
    # clearance, and ED (<= 600) is under the staircase bound.
    assert not out_t[5].any()
    # The excursion lane re-centered at least once; drift-free lanes
    # never did.
    klos = out_t[6]
    assert len(np.unique(klos[:, 0])) > 1
    for b in range(1, B):
        assert len(np.unique(klos[:, b])) == 1
    # Same breaking points as the untiled band.
    for i, (a, b) in enumerate(zip(out_u, out_t)):
        assert np.array_equal(a, b), f"field {i} differs"


def test_mixed_length_chunk_short_lanes_match_own_length():
    """Lanes shorter than the chunk's Lq run tiles wholly past their last
    row, where the anchor chases a dead frontier into the clamp. Those
    tiles hold no row of the lane's alignment, so they must not count in
    the band clearance: every lane certifies, and each short lane's
    outputs equal that lane run alone at its own padded length."""
    rng = np.random.default_rng(25)
    W, T, Lq, LA, B = 512, 256, 2048, 2048, 8
    pairs = []
    for n in (180, 240, 300, 470, 400, 1990, 2000, 1950):
        tgt = rng.integers(0, 4, n).astype(np.uint8)
        pairs.append((_mutate_codes(rng, tgt, 0.07)[:Lq], tgt))
    out = _run_tiled(*_pack_lanes(pairs, B, Lq, LA), W=W, T=T, Lq=Lq,
                     LA=LA)
    assert not out[5].any(), out[5]
    for b, (qq, tt) in enumerate(pairs):
        own = -(-len(qq) // T) * T
        if own == Lq:
            continue
        alone = _run_tiled(*_pack_lanes([(qq, tt)], B, own, LA), W=W,
                           T=T, Lq=own, LA=LA)
        for i in range(6):
            assert np.array_equal(out[i][b], alone[i][0]), \
                f"lane {b} (lq {len(qq)}) field {i} differs"
        # The live tiles ran at the same band origins.
        assert np.array_equal(out[6][:own // T, b], alone[6][:, 0])
        # Past the lane's last row the anchor sits in the clamp (klo_lo =
        # max(0, lt - lq) - (W - 1)), at clearance 0: the lane certifies
        # because those tiles stay out of cmin.
        assert out[6][-1, b] == max(0, len(tt) - len(qq)) - (W - 1)


def test_short_lane_excursion_past_band_stays_uncertified():
    """Soundness: a short lane in a long chunk whose live rows hold an
    excursion wider than the band can follow (560 deletions, then 560
    insertions back to the main diagonal, against W = 512) must come
    back uncertified, as it does alone at its own length. Leaving the
    dead tiles out of the clearance must not loosen the bound for the
    rows the lane does hold."""
    rng = np.random.default_rng(9)
    qq = rng.integers(0, 4, 1500).astype(np.uint8)
    # Rows 100..1220: every other base missing from the target (the
    # path drifts to diagonal -560); rows 1220..1500: two bases inserted
    # after each (back to diagonal 0).
    ins = []
    for b in qq[1220:1500]:
        ins += [int(b), int(rng.integers(0, 4)), int(rng.integers(0, 4))]
    tt = np.concatenate([qq[:100], qq[100:1220:2],
                         np.array(ins, np.uint8)])
    assert len(tt) == len(qq)
    good = rng.integers(0, 4, 1900).astype(np.uint8)
    pairs = [(qq, tt), (_mutate_codes(rng, good, 0.05), good)]
    W, T, Lq, LA, B = 512, 256, 2048, 2048, 8
    out = _run_tiled(*_pack_lanes(pairs, B, Lq, LA), W=W, T=T, Lq=Lq,
                     LA=LA)
    assert out[5][0] and not out[5][1]
    alone = _run_tiled(*_pack_lanes(pairs[:1], B, 1536, LA), W=W, T=T,
                       Lq=1536, LA=LA)
    assert alone[5][0]


@pytest.mark.parametrize("read_len,rate", [(12_000, 0.03)])
def test_ultralong_device_matches_native(tmp_path, read_len, rate):
    """Reads past the untiled ~9 kb ceiling route through the tiled
    device path and must produce byte-identical layers to the native
    aligner — with ZERO native fallbacks, confirmed via the registry."""
    d = _write_dataset(tmp_path, n_reads=4, read_len=read_len, seed=11,
                       rate=rate)
    args = (f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
            f"{d}/draft.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
            5, -4, -8)
    pn = create_polisher(*args, backend="native")
    pn.initialize()
    obs_metrics.reset()
    pj = create_polisher(*args, backend="jax",
                         logger=Logger(stream=io.StringIO()))
    pj.initialize()
    assert _layer_snapshot(pj) == _layer_snapshot(pn)
    reg = obs_metrics.registry()
    assert reg.get("ovl_native_jobs") == 0
    assert reg.get("ovl_device_jobs") == 4
    assert reg.get("ovl_tiles_exec") >= 2
    assert reg.get("ovl_device_fraction") == 1.0
    assert float(reg.get("phase_seconds_initialize_aligned_overlaps")) > 0


def test_mixed_length_tiled_tier_matches_native(tmp_path):
    """Reads of clearly different lengths in one tile tier share one
    bucket padded to the longest: the ~9.5 kb reads run two tiles past
    their last row. They must still certify, and every layer must equal
    the native aligner's."""
    d = _write_dataset(tmp_path, read_len=[9_500, 13_000, 9_500],
                       seed=25, rate=0.03, draft_len=20_000)
    args = (f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
            f"{d}/draft.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
            5, -4, -8)
    pn = create_polisher(*args, backend="native")
    pn.initialize()
    obs_metrics.reset()
    pj = create_polisher(*args, backend="jax")
    pj.initialize()
    assert _layer_snapshot(pj) == _layer_snapshot(pn)
    reg = obs_metrics.registry()
    assert reg.get("ovl_native_jobs") == 0
    assert reg.get("ovl_device_jobs") == 3
    assert reg.get("ovl_tiles_exec") == 7


@pytest.mark.parametrize("read_len,rate", [(24_000, 0.025),
                                           (48_000, 0.025)])
def test_ultralong_deep_matches_native(tmp_path, monkeypatch, read_len,
                                      rate):
    """Stitching coverage at ONT-class lengths: 24 kb and 48 kb reads in
    the 16-lane W=2048 tier must stay device-handled and
    native-identical. A v5e's HBM does not admit these planes (their
    128-padded lane axis; budget.hbm_plane_budget), so the test runs as
    on a chip with 1 TiB of HBM: the XLA twin it runs here keeps lanes
    off the minor axis, and the tiling logic is what is under test."""
    monkeypatch.setattr(budget, "V5E_HBM_BYTES", 2 ** 40)
    d = _write_dataset(tmp_path, n_reads=2, read_len=read_len, seed=3,
                       rate=rate, draft_len=read_len + 12_000)
    args = (f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
            f"{d}/draft.fasta.gz", PolisherType.kC, 500, 10.0, 0.3,
            5, -4, -8)
    pn = create_polisher(*args, backend="native")
    pn.initialize()
    obs_metrics.reset()
    pj = create_polisher(*args, backend="jax")
    pj.initialize()
    assert _layer_snapshot(pj) == _layer_snapshot(pn)
    reg = obs_metrics.registry()
    assert reg.get("ovl_native_jobs") == 0
    assert reg.get("ovl_device_jobs") == 2


class _FakeOverlap:
    """Minimal overlap stub for driving device_breaking_points
    directly (classification + accounting, no PAF plumbing)."""

    strand = False

    def __init__(self, q, t):
        self._q, self._t = q, t
        self.q_begin, self.q_end, self.q_length = 0, len(q), len(q)
        self.t_begin = 0
        self.breaking_points = None

    def alignment_operands(self, sequences):
        return self._q, self._t


def _random_seq(rng, n):
    return _BASES[rng.integers(0, 4, n)].tobytes()


def test_fallback_accounting_counts_causes_independently():
    """One over-budget job (130 kb: no tile tier fits) plus one
    uncertified job (1.2 kb of unrelated sequence: escape bound fails)
    in the same batch must be reported as '1 over the device length
    budget, 1 uncertified' — the round-6 subtraction lumped both into
    one bucket."""
    rng = np.random.default_rng(2)
    big = _random_seq(rng, 130_000)
    pending = [
        _FakeOverlap(_random_seq(rng, 1200), _random_seq(rng, 1200)),
        _FakeOverlap(big, big),
    ]
    obs_metrics.reset()
    buf = io.StringIO()
    fb = ovl_align.device_breaking_points(
        pending, None, 500, match=5, mismatch=-4, gap=-8, log=buf)
    assert set(id(o) for o in fb) == set(id(o) for o in pending)
    assert "1 over the device length budget, 1 uncertified" in buf.getvalue()
    reg = obs_metrics.registry()
    assert reg.get("ovl_device_jobs") == 0
    assert reg.get("ovl_native_jobs") == 2
    assert reg.get("ovl_device_fraction") == 0.0


def test_tiled_env_gate_off_routes_native(tmp_path, monkeypatch):
    """RACON_TPU_OVL_TILED=0 disables the tiled path: an ultralong job
    that WOULD plan (tile_plan admits it) must fall back as over-budget
    without dispatching any device work. In a polisher job the native
    redo then counts its query + target bases in
    ``align_native_bases``, and no device span opens."""
    assert budget.tile_plan(10_000, 10_000) is not None
    monkeypatch.setenv("RACON_TPU_OVL_TILED", "0")
    rng = np.random.default_rng(8)
    o = _FakeOverlap(_random_seq(rng, 10_000), _random_seq(rng, 10_000))
    obs_metrics.reset()
    buf = io.StringIO()
    fb = ovl_align.device_breaking_points(
        [o], None, 500, match=5, mismatch=-4, gap=-8, log=buf)
    assert fb == [o]
    assert "exceed the device length budget" in buf.getvalue()
    reg = obs_metrics.registry()
    assert reg.get("ovl_native_jobs") == 1
    assert reg.get("ovl_device_jobs") == 0
    assert reg.get("ovl_tiles_exec") == 0

    d = _write_dataset(tmp_path, n_reads=1, read_len=10_000, seed=8,
                       rate=0.03, draft_len=14_000)
    obs_metrics.reset()
    p = create_polisher(f"{d}/reads.fasta.gz", f"{d}/overlaps.paf.gz",
                        f"{d}/draft.fasta.gz", PolisherType.kC, 500, 10.0,
                        0.3, 5, -4, -8, backend="jax")
    p.initialize()
    reg = obs_metrics.registry().snapshot()
    assert reg["ovl_native_jobs"] == 1 and reg["ovl_device_jobs"] == 0
    with gzip.open(f"{d}/reads.fasta.gz", "rb") as f:
        read = f.read().split(b"\n")[1]
    assert reg["align_native_bases"] == len(read) + 10_000
    assert reg["align_bases_total"] == reg["align_native_bases"]
    assert reg["align_walk_overlaps"] == 1
    assert reg["align_seconds_native_redo"] > 0
    assert "align_seconds_device_dispatch" not in reg
