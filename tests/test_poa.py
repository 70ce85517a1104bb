"""Tests for the batched POA consensus engine (spoa replacement)."""

import numpy as np
import pytest

from racon_tpu.models.window import Window, WindowType
from racon_tpu.ops.encode import decode_bases
from racon_tpu.ops.poa import PoaEngine


def _noisy(rng, seq, rate):
    out = []
    for b in seq:
        r = rng.random()
        if r < rate / 3:
            continue  # deletion
        elif r < 2 * rate / 3:
            out.append(int(rng.integers(0, 4)))  # substitution
        elif r < rate:
            out.append(int(b))
            out.append(int(rng.integers(0, 4)))  # insertion
        else:
            out.append(int(b))
    return decode_bases(np.array(out, np.uint8))


def _make_window(rng, true, n_layers, rate=0.1, wtype=WindowType.TGS):
    backbone = _noisy(rng, true, rate)
    w = Window(0, 0, wtype, backbone, None)
    for _ in range(n_layers):
        lay = _noisy(rng, true, rate)
        w.add_layer(lay, None, 0, len(backbone) - 1)
    return w


@pytest.mark.parametrize("backend", ["native", "jax"])
def test_consensus_recovers_truth(backend):
    rng = np.random.default_rng(11)
    true = rng.integers(0, 4, 300).astype(np.uint8)
    true_b = decode_bases(true)
    w = _make_window(rng, true, 16, rate=0.1)
    eng = PoaEngine(backend=backend)
    assert eng.consensus_windows([w]) == 1
    assert w.polished
    from racon_tpu.ops.align import nw_oracle
    sc, _ = nw_oracle(w.consensus, true_b, 0, -1, -1)
    # 10% error backbone + 16 noisy layers must polish to (near) truth.
    assert -sc <= 3


def test_backends_agree():
    rng = np.random.default_rng(12)
    true = rng.integers(0, 4, 200).astype(np.uint8)
    w1 = _make_window(rng, true, 8, rate=0.08)
    w2 = Window(0, 0, WindowType.TGS, w1.backbone, None)
    for i in range(w1.n_layers):
        w2.add_layer(w1.layer_data[i], None, w1.layer_begin[i],
                     w1.layer_end[i])
    PoaEngine(backend="native").consensus_windows([w1])
    PoaEngine(backend="jax").consensus_windows([w2])
    assert w1.consensus == w2.consensus


def test_too_few_layers_keeps_backbone():
    w = Window(0, 0, WindowType.TGS, b"ACGTACGT", None)
    w.add_layer(b"ACGTACGT", None, 0, 7)
    eng = PoaEngine(backend="native")
    assert eng.consensus_windows([w]) == 0
    assert w.consensus == b"ACGTACGT"
    assert not w.polished


def test_quality_weights_break_ties():
    # Two high-quality layers voting one base beat two low-quality layers
    # voting another at the disputed position.
    backbone = b"AAAAACAAAA"
    w = Window(0, 0, WindowType.NGS, backbone, None)
    hi = bytes([33 + 40] * 10)
    lo = bytes([33 + 2] * 10)
    w.add_layer(b"AAAAAGAAAA", hi, 0, 9)
    w.add_layer(b"AAAAAGAAAA", hi, 0, 9)
    w.add_layer(b"AAAAATAAAA", lo, 0, 9)
    w.add_layer(b"AAAAATAAAA", lo, 0, 9)
    eng = PoaEngine(backend="native", refine_rounds=0)
    eng.consensus_windows([w])
    assert w.consensus == b"AAAAAGAAAA"


def test_ngs_windows_not_trimmed():
    # NGS windows skip the coverage trim (src/window.cpp:113-134).
    rng = np.random.default_rng(13)
    true = rng.integers(0, 4, 150).astype(np.uint8)
    backbone = decode_bases(true)
    w = Window(0, 0, WindowType.NGS, backbone, None)
    # Layers covering only the middle third.
    seg = backbone[50:100]
    for _ in range(6):
        w.add_layer(seg, None, 50, 99)
    PoaEngine(backend="native").consensus_windows([w])
    # Uncovered flanks survive in NGS mode.
    assert len(w.consensus) >= 140


def test_tgs_trim_drops_uncovered_flanks():
    rng = np.random.default_rng(14)
    true = rng.integers(0, 4, 150).astype(np.uint8)
    backbone = decode_bases(true)
    w = Window(0, 0, WindowType.TGS, backbone, None)
    seg = backbone[50:100]
    for _ in range(6):
        w.add_layer(seg, None, 50, 99)
    PoaEngine(backend="native").consensus_windows([w])
    # Coverage >= n_layers//2 only inside [50, 100).
    assert len(w.consensus) <= 60


def test_device_engine_two_shapes_one_process():
    """Differently shaped device-engine runs in sequence in one process.

    A module-level jax.Array constant once lowered as a hoisted
    executable parameter that the execution path then under-supplied
    (INVALID_ARGUMENT: "Execution supplied N buffers but compiled
    program expected N+1") on the second shape. The geometries give
    each run its own Lq/LA padding buckets, so each compiles its own
    executables; the failure reproduced on the CPU backend too."""
    from synth_windows import build_windows

    for n, cov, wlen, seed in ((6, 6, 120, 3), (5, 8, 150, 7),
                               (4, 10, 260, 11)):
        ws = build_windows(n, cov, wlen, seed=seed)
        eng = PoaEngine(backend="jax")
        assert eng.consensus_windows(ws) == n
        assert all(w.consensus for w in ws)
