"""Banded NW forward (ops/pallas/band_kernel.py): score exactness via
the escape bound, and engine-level equality against the full-width path.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from racon_tpu.ops.align import nw_oracle
from racon_tpu.ops.cigar import DIAG, UP, LEFT
from racon_tpu.ops.pallas.band_kernel import (band_geometry,
                                              fw_dirs_band_xla,
                                              fw_traceback_band)


def _score_of_ops(q, t, ops, m, x, g):
    qi = ti = s = 0
    for d in ops:
        if d == DIAG:
            s += m if q[qi] == t[ti] else x
            qi += 1
            ti += 1
        elif d == UP:
            s += g
            qi += 1
        elif d == LEFT:
            s += g
            ti += 1
    assert qi == len(q) and ti == len(t)
    return s


@pytest.mark.parametrize("scoring", [(5, -4, -8), (0, -1, -1)])
def test_band_scores_and_paths_match_oracle(scoring):
    """Random jobs whose optimum fits the band: the banded terminal
    score must equal the full NW optimum (escape-bound certified) and
    the traceback must be a valid path achieving it."""
    m, x, g = scoring
    rng = np.random.default_rng(8)
    B, Lq, W = 8, 64, 128
    # Mildly noisy pairs: small |lt - lq|, deviation far below W//2.
    qs, ts = [], []
    for _ in range(B):
        t = rng.integers(0, 4, int(rng.integers(40, 60)))
        keep = rng.random(len(t)) > 0.08
        q = t[keep]
        sub = rng.random(len(q)) < 0.06
        q = np.where(sub, rng.integers(0, 4, len(q)), q)
        qs.append(q.astype(np.uint8))
        ts.append(t.astype(np.uint8))
    lq = np.array([len(q) for q in qs], np.int32)
    lt = np.array([len(t) for t in ts], np.int32)
    qpad = np.zeros((B, Lq), np.uint8)
    for b in range(B):
        qpad[b, :lq[b]] = qs[b]
    klo, wl = band_geometry(jnp.asarray(lq), jnp.asarray(lt), W)
    klo_h = np.asarray(klo)
    tband = np.full((B, W + Lq), 7, np.uint8)
    for b in range(B):
        for y in range(W + Lq):
            j = klo_h[b] + y
            if 0 <= j < lt[b]:
                tband[b, y] = ts[b][j]
    dirs, _, hlast = fw_dirs_band_xla(
        jnp.asarray(tband), jnp.asarray(qpad.T), klo,
        jnp.asarray(lq), match=m, mismatch=x, gap=g, W=W)
    rev = fw_traceback_band(dirs, jnp.asarray(lq), jnp.asarray(lt), klo,
                            Lq + W)
    ops = np.asarray(jnp.flip(rev, axis=1))
    hlast = np.asarray(hlast)
    for b in range(B):
        o = [d for d in ops[b] if d != 3]
        osc, _ = nw_oracle(qs[b], ts[b], m, x, g)
        xend = lt[b] - lq[b] - klo_h[b]
        assert hlast[b, xend] == osc
        assert _score_of_ops(qs[b], ts[b], o, m, x, g) == osc


def test_engine_band_matches_full_width():
    """End-to-end: banded and full-width device paths produce identical
    consensus on synthetic noisy windows (band covers the optimum,
    identical tie-breaking)."""
    import os
    from synth_windows import build_windows
    from racon_tpu.ops.poa import PoaEngine

    ws_band = build_windows(8, 6, 200, seed=13)
    ws_full = build_windows(8, 6, 200, seed=13)
    assert PoaEngine(backend="jax").consensus_windows(ws_band) == 8
    os.environ["RACON_TPU_NO_BAND"] = "1"
    try:
        assert PoaEngine(backend="jax").consensus_windows(ws_full) == 8
    finally:
        del os.environ["RACON_TPU_NO_BAND"]
    for a, b in zip(ws_band, ws_full):
        assert a.consensus == b.consensus
