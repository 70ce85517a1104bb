"""Synthetic polishing windows shared by the tests.

Three generators, each kept exactly as its tests first used it, so a
seed gives every test the same windows it always got:

- :func:`build_windows`: per window a hidden truth, a 10%-error
  backbone and ``coverage`` 10%-error layers (substitutions, deletions
  and insertions drawn in one vectorised pass).
- :func:`build_subdel_windows`: backbone and layers from
  :func:`mutate_subdel` (4% deletions, 4% substitutions), windows on
  three targets.
- :func:`build_stream_windows`: backbone and layers from
  :func:`mutate_indel` (:func:`mutate_subdel` plus 4% insertions),
  windows on seven targets, every ninth with no layers so a streamed
  run takes the inline backbone path as well as device chunks.

Imported by bare name (``from synth_windows import ...``), as the tests
import each other's helpers.
"""

import numpy as np

BASES = np.frombuffer(b"ACGT", np.uint8)


def build_windows(n_windows: int, coverage: int, wlen: int, seed: int = 0):
    """Vectorized synthetic polishing workload: per window a hidden truth
    sequence, a 10%-error backbone, and `coverage` 10%-error layers."""
    from racon_tpu.models.window import Window, WindowType
    from racon_tpu.ops.encode import decode_bases

    rng = np.random.default_rng(seed)
    windows = []
    for _ in range(n_windows):
        true = rng.integers(0, 4, wlen).astype(np.uint8)

        def noisy(rate=0.10):
            r = rng.random(wlen)
            dele = r < rate / 3
            sub = (r >= rate / 3) & (r < 2 * rate / 3)
            ins = (r >= 2 * rate / 3) & (r < rate)
            counts = np.where(dele, 0, np.where(ins, 2, 1))
            base = np.where(sub, rng.integers(0, 4, wlen).astype(np.uint8),
                            true)
            starts = np.cumsum(counts) - counts
            out = np.zeros(int(counts.sum()), np.uint8)
            keep = ~dele
            out[starts[keep]] = base[keep]
            out[starts[ins] + 1] = rng.integers(0, 4, int(ins.sum()))
            return decode_bases(out)

        backbone = noisy()
        qual = bytes(rng.integers(33 + 8, 33 + 25, len(backbone),
                                  dtype=np.uint8))
        w = Window(0, 0, WindowType.TGS, backbone, qual)
        for _ in range(coverage):
            lay = noisy()
            lq = bytes(rng.integers(33 + 8, 33 + 25, len(lay),
                                    dtype=np.uint8))
            w.add_layer(lay, lq, 0, len(backbone) - 1)
        windows.append(w)
    return windows


def mutate_subdel(rng, truth):
    out = []
    for b in truth:
        r = rng.random()
        if r < 0.04:
            continue
        out.append(int(BASES[rng.integers(0, 4)]) if r < 0.08 else int(b))
    return bytes(out)


def build_subdel_windows(n, seed=0, coverage=5, wlen=80):
    from racon_tpu.models.window import Window, WindowType
    rng = np.random.default_rng(seed)
    ws = []
    for i in range(n):
        truth = BASES[rng.integers(0, 4, wlen)]
        backbone = mutate_subdel(rng, truth)
        qual = bytes(rng.integers(43, 63, len(backbone), dtype=np.uint8))
        w = Window(i, i % 3, WindowType.TGS, backbone, qual)
        for _ in range(coverage):
            lay = mutate_subdel(rng, truth)
            lq = bytes(rng.integers(43, 63, len(lay), dtype=np.uint8))
            w.add_layer(lay, lq, 0, len(backbone) - 1)
        ws.append(w)
    return ws


def mutate_indel(rng, truth):
    out = []
    for b in truth:
        r = rng.random()
        if r < 0.04:
            continue                       # deletion
        out.append(int(BASES[rng.integers(0, 4)]) if r < 0.08 else int(b))
        if r > 0.96:
            out.append(int(BASES[rng.integers(0, 4)]))  # insertion
    return bytes(out)


def build_stream_windows(n, seed=0, coverage=5, wlen=80):
    """Synthetic polishing windows with trivial (no-layer) windows
    sprinkled in, so the stream exercises both the inline backbone path
    and device chunks. Same seed => bit-identical window set."""
    from racon_tpu.models.window import Window, WindowType
    rng = np.random.default_rng(seed)
    ws = []
    for i in range(n):
        truth = BASES[rng.integers(0, 4, wlen)]
        backbone = mutate_indel(rng, truth)
        qual = bytes(rng.integers(43, 63, len(backbone), dtype=np.uint8))
        w = Window(i, i % 7, WindowType.TGS, backbone, qual)
        cov = 0 if i % 9 == 8 else coverage
        for _ in range(cov):
            lay = mutate_indel(rng, truth)
            lq = bytes(rng.integers(43, 63, len(lay), dtype=np.uint8))
            w.add_layer(lay, lq, 0, len(backbone) - 1)
        ws.append(w)
    return ws
