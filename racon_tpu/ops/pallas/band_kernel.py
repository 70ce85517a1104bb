"""Pallas TPU kernel: banded NW forward in per-lane diagonal coordinates.

The full-width kernel (flat_kernel.py) computes H over all Lt target
columns; at bench shapes its dirs tensor is the HBM ceiling (~1.5 GB per
refinement round). This kernel restricts each job to a
static-width diagonal band of W slots centered on its own length
difference — band column x of row i is target column

    j = i + klo_b + x,      klo_b = min(0, lt_b - lq_b) - wl_b,
    wl_b = (W - 1 - |lt_b - lq_b|) // 2

so the diag neighbour of (i, x) is (i-1, x) (same lane), the up
neighbour is (i-1, x+1) (static shift by one), and the left-gap chain
stays a lane-local cummax — no dynamic roll anywhere (pltpu.roll with a
dynamic shift corrupted >512-lane rows on the Mosaic stack this was
written against).
The per-lane geometry lives entirely in a pre-shifted target buffer
built by the caller:

    tband[b, y] = anchor_b[klo_b + y]   for y in [0, W + Lq)

(row i's window is tband[:, i-1 : i-1+W] — a row-uniform dynamic lane
slice). Out-of-matrix cells carry -inf-like scores so no in-band path
crosses them; cells right of each job's lt hold garbage the traceback
never visits (it starts at (lq, lt) and moves down-left), exactly like
the full-width kernel's padding story.

Exactness: the kernel also emits each lane's final row H[lq_b] (captured
when the row counter passes lq_b), from which the caller reads the
terminal score and applies the same provable escape bound as the native
aligner (racon_tpu/native/nw.cpp): any path leaving half-width w needs
more than |lt-lq| + 2(w+1) gap ops, so

    score >= max(m,0)*min(lq,lt) + g*(|lt-lq| + 2*wl + 2)

proves the banded optimum is the global optimum. Lanes that fail the
bound are flagged and their windows re-polished on the unbounded host
path (the ovf redo route in PoaEngine) — with w >= 128 and 500-base
windows this is a theoretical safety valve, not a hot path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from racon_tpu.ops.budget import VMEM_LIMIT
from racon_tpu.ops.cigar import DIAG, UP, LEFT

_NEG = -(2 ** 30)
_NEG16 = -16384  # int16 kernel's -inf (see _score_dtype for the proof)
TB = 128   # jobs per grid program (sublanes)
CH = 32    # query rows per grid step
from racon_tpu.ops.flat import U_SAT  # single source (= K_INS + 1)


def uc_boundary(nxt_k: int = 2) -> int:
    """Packed row-0 / out-of-band frontier fill for a ``nxt_k``-deep
    predecessor plane: every 6-bit hop field (and the base (U, C) pair)
    decodes as (up_run 0, consumer LEFT) — the values the walk is forced
    to at the matrix boundary anyway. k=2 packs ``(N1 << 6) | (U << 2) |
    C`` (12 bits, the PR 5 layout); k=4 extends to ``(N3 << 18) |
    (N2 << 12) | (N1 << 6) | (U << 2) | C`` (24 bits)."""
    v = LEFT
    for _ in range(max(int(nxt_k) - 1, 1)):
        v = (v << 6) | LEFT
    return v


def _score_dtype(match: int, mismatch: int, gap: int, Lq: int, W: int):
    """int16 when every DP intermediate provably fits, else int32.

    The int16 scheme uses NEG16 = -16384 with one extra clamp (see
    _kernel): masked-cell chains stay <= NEG16 + gap at any real cell, so
    results are bit-identical to the int32 kernel as long as
      |jcol| * |gap| <= 16383   (jg magnitude; |jcol| <= Lq + W)
      match * Lq + (Lq + W) * |gap| <= 32767  (f = tmp - jg upper bound)
    hold — halving VPU register traffic for the whole forward pass.
    """
    a = max(abs(match), abs(mismatch), abs(gap))
    jgmax = (Lq + W) * abs(gap)
    if jgmax <= 16383 and max(match, 0) * Lq + a * Lq + jgmax <= 32767:
        # DISABLED: Mosaic on this stack cannot legalize vector int16
        # max ('failed to legalize operation arith.maxsi'), and the DP
        # row is max-heavy (11 of ~19 vector ops), so a compare+select
        # emulation would give back most of the halved register traffic.
        # Shape analysis kept for a future stack where i16 max lowers.
        return jnp.int32
    return jnp.int32


def _kernel(tbandT_ref, qT_ref, klo_ref, lq_ref, *refs, match, mismatch,
            gap, W, dtype, TB, CH, nxt_k=2):
    # Transposed layout: band slots x on SUBLANES, jobs on LANES. The
    # per-row moving target window is then a dynamic *sublane* slice
    # (supported by Mosaic at any offset), where the lane-major variant
    # would need a 128-aligned dynamic lane slice (rejected).
    if nxt_k >= 4:
        dirs_ref, nxt_ref, nxt2_ref, hlast_ref, prev_ref, ucprev_ref = refs
    else:
        dirs_ref, nxt_ref, hlast_ref, prev_ref, ucprev_ref = refs
        nxt2_ref = None
    c = pl.program_id(1)
    NEG = _NEG16 if dtype == jnp.int16 else _NEG   # Python int: inlines
    BND = uc_boundary(nxt_k)               # Python int: inlines
    xr = jax.lax.broadcasted_iota(jnp.int32, (W, TB), 0)
    klo = klo_ref[0]                       # [TB] int32
    lqv = lq_ref[0]                        # [TB] int32

    @pl.when(c == 0)
    def _():
        # prev[x] = H[0][klo + x] = (klo+x)*gap where klo+x >= 0 (the
        # j = 0 column holds 0 = H[0][0]); cells left of j=0 are -inf.
        j0 = klo[None, :] + xr
        init = jnp.where(j0 >= 0, j0 * gap, NEG).astype(dtype)
        prev_ref[:] = init
        hlast_ref[:] = init
        # UP-chain metadata boundary (row 0): no UP can start above row 1,
        # and a chain that reaches row 0 is consumed by the forced LEFT
        # walk along the top row — encode that as consumer dir LEFT.
        # N, U and C share one packed scratch (N << 6 | U << 2 | C,
        # extended by the N2/N3 hop fields at nxt_k=4): a long-read
        # overlap chunk's VMEM budget is tight (ovl_align), and
        # separate buffers cost another (W, TB) i32 block each. Row-0 N
        # is (U=0, C=LEFT) — the walk's forced top-row values — matching
        # what a reader at row 0 would be forced to anyway.
        ucprev_ref[:] = jnp.full((W, TB), BND, jnp.int32)

    def row(r, _):
        i = c * CH + r + 1                 # 1-based global row
        qrow = qT_ref[r]                   # [TB] int32
        # (int32 tband: Mosaic requires 8-aligned dynamic sublane
        # slices for narrower dtypes, and i - 1 is arbitrary.)
        tw = tbandT_ref[pl.dslice(i - 1, W), :]
        jcol = i + klo[None, :] + xr       # absolute target column j
        sub = jnp.where(tw == qrow[None, :], match, mismatch)
        sub = jnp.where(jcol >= 1, sub, NEG).astype(dtype)
        P = prev_ref[:]
        diag = P + sub                     # >= 2*NEG, exactly int16-min
        up = jnp.concatenate(
            [P[1:, :], jnp.full((1, TB), NEG, dtype)], axis=0) + \
            jnp.asarray(gap, dtype)
        tmp = jnp.maximum(diag, up)
        # j == 0 boundary column: H[i][0] = i*gap, entering at x0 = -i-klo.
        tmp = jnp.where(jcol == 0, i * gap, tmp).astype(dtype)
        # Clamp before the jg subtraction: masked cells carry 2*NEG and
        # would wrap int16 under "- jg" for negative jcol. Real cells are
        # far above NEG, and clamped masked chains still lose at every
        # real cell by >= |gap| (see _score_dtype).
        tmp = jnp.maximum(tmp, jnp.asarray(NEG, dtype))
        # Left-gap chain: shift-max ladder along sublanes (j grows with x).
        jg = (jcol * gap).astype(dtype)
        f = tmp - jg
        s = 1
        while s < W:
            f = jnp.maximum(
                f, jnp.concatenate(
                    [jnp.full((s, TB), NEG, dtype), f[:-s, :]],
                    axis=0))
            s *= 2
        h = f + jg
        h = jnp.where(jcol >= 0, h, NEG).astype(dtype)
        # The direction select stays in the score dtype end to end: a
        # mask from an int16 compare selecting int32 scalars needs an i1
        # relayout Mosaic rejects ("Invalid relayout ... vector<...xi1>"),
        # while same-width select + one plain convert lowers cleanly.
        d = jnp.where(h == diag, jnp.asarray(DIAG, dtype),
                      jnp.where(h == up, jnp.asarray(UP, dtype),
                                jnp.asarray(LEFT, dtype))).astype(jnp.int32)
        # UP-chain metadata for the column-walk traceback (colwalk.py):
        # cell (i, j)'s UP predecessor is (i-1, j) = band slot x+1 of the
        # previous row, so chains run along the +1 sublane shift. U counts
        # the chain length into this cell (saturating at U_SAT; saturated
        # lanes are re-polished on the host path), C carries the chain
        # top's consumer direction down the chain.
        isup = d == UP
        ucp = ucprev_ref[:]
        ucup = jnp.concatenate(
            [ucp[1:, :], jnp.full((1, TB), BND, jnp.int32)],
            axis=0)
        U = jnp.where(isup, jnp.minimum(((ucup >> 2) & 0xF) + 1, U_SAT), 0)
        C = jnp.where(isup, ucup & 3, d)
        # k-step predecessor metadata (the extra output planes): hop
        # field m is uc_m = the packed (U' << 2 | C') of pred^m — the
        # cell the walk visits after undoing m [UP run][consumer]
        # blocks, where pred^1 of (i, j) is (i - U - (C==DIAG), j - 1).
        # One gather then undoes nxt_k target columns (docs/KERNELS.md).
        # Each hop propagates by the same three static shifts, reading
        # the PREVIOUS hop's field (uc_m(cell) = uc_{m-1}(pred^1(cell))):
        #   UP:   inherit field m from the cell above (the whole chain
        #         shares its chain top's undo target, so pred^1 — and
        #         hence every deeper pred — is chain-invariant),
        #   DIAG: predecessor is (i-1, j-1) = prev row, same slot, so
        #         field m comes from the prev row's field m-1,
        #   LEFT: predecessor is (i, j-1) = this row, slot x-1: shift of
        #         this row's just-finalized field m-1 (U and C are
        #         finalized for the whole row before these selects).
        # Slot-0 LEFT reads a boundary fill — out-of-band predecessors
        # only occur on paths that fail the escape bound (redo route).
        ucnow = (U << 2) + C
        nleft = jnp.concatenate(
            [jnp.full((1, TB), LEFT, jnp.int32), ucnow[:-1, :]], axis=0)
        N = jnp.where(isup, (ucup >> 6) & 0x3F,
                      jnp.where(d == DIAG, ucp & 0x3F, nleft))
        dirs_ref[r] = (d + (C << 2) + (U << 4)).astype(jnp.uint8)
        nxt_ref[r] = N.astype(jnp.uint8)
        if nxt_k >= 4:
            n1left = jnp.concatenate(
                [jnp.full((1, TB), LEFT, jnp.int32), N[:-1, :]], axis=0)
            N2 = jnp.where(isup, (ucup >> 12) & 0x3F,
                           jnp.where(d == DIAG, (ucp >> 6) & 0x3F, n1left))
            n2left = jnp.concatenate(
                [jnp.full((1, TB), LEFT, jnp.int32), N2[:-1, :]], axis=0)
            N3 = jnp.where(isup, (ucup >> 18) & 0x3F,
                           jnp.where(d == DIAG, (ucp >> 12) & 0x3F, n2left))
            # u16 plane: hop 2 in the low byte, hop 3 in the high byte
            # (byte-aligned so the walk decodes without cross-byte
            # shifts beyond one >> 8).
            nxt2_ref[r] = ((N3 << 8) + N2).astype(jnp.uint16)
            ucprev_ref[:] = (N3 << 18) + (N2 << 12) + (N << 6) + ucnow
        else:
            ucprev_ref[:] = (N << 6) + ucnow
        prev_ref[:] = h
        # Capture each lane's true final row as the row counter passes it.
        hlast_ref[:] = jnp.where((lqv == i)[None, :], h, hlast_ref[:])
        return 0

    jax.lax.fori_loop(0, CH, row, 0)


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "W",
                                    "tb", "ch", "interpret", "nxt_k"))
def fw_dirs_band(tband: jnp.ndarray, qT: jnp.ndarray, klo: jnp.ndarray,
                 lq: jnp.ndarray, *, match: int, mismatch: int, gap: int,
                 W: int, tb: int = TB, ch: int = CH,
                 interpret: bool = False, nxt_k: int = 2):
    """Banded packed-cell tensors + final-row scores (Pallas, transposed).

    Args:
      tband: int32[B, W + Lq] pre-shifted targets (see module docstring).
      qT:    uint8/int32[Lq, B] queries, transposed.
      klo:   int32[B] per-lane band origin.
      lq:    int32[B] per-lane query lengths (for final-row capture).

    Returns (cells uint8[Lq, W, B], nxt uint8[Lq, W, B],
    hlast int32[B, W]) — note cells/nxt have band slots *before* jobs
    (kernel layout); fw_traceback_band takes ``transposed=True`` for it.
    hlast[b, x] = H[lq_b][lq_b + klo_b + x].
    Each cell byte packs ``dir | consumer_dir << 2 | up_run << 4``; the
    matching ``nxt`` byte packs the predecessor cell's
    ``consumer_dir | up_run << 2`` so one traceback gather undoes TWO
    target columns (see racon_tpu/ops/colwalk.py for the walk and
    docs/KERNELS.md for the contract; the plain direction is the low 2
    bits of the cell byte). With ``nxt_k=4`` a THIRD plane rides along —
    ``nxt2`` uint16[Lq, W, B] packing hops 2 and 3 (low/high byte) so
    one gather undoes FOUR target columns; the return becomes
    (cells, nxt, nxt2, hlast). B % tb == 0, Lq % ch == 0 required.
    ``tb``/``ch`` tile the lane/row grid: the defaults suit
    consensus-window shapes; long-read overlap alignment (W in the
    thousands, racon_tpu/ops/ovl_align.py) passes smaller row tiles so
    the blocks stay inside the explicit VMEM limit
    (racon_tpu/ops/budget.py::vmem_est; ``ch`` >= 8, the int32 qT
    block's sublane tiling).
    ``interpret`` runs the kernel in Pallas interpreter mode so CPU
    tier-1 tests exercise the exact kernel body (tests/
    test_kernels_interpret.py).
    """
    B = tband.shape[0]
    Lq = qT.shape[0]
    dtype = _score_dtype(match, mismatch, gap, Lq, W)
    kernel = functools.partial(_kernel, match=match, mismatch=mismatch,
                               gap=gap, W=W, dtype=dtype, TB=tb, CH=ch,
                               nxt_k=nxt_k)
    plane_spec = pl.BlockSpec((ch, W, tb), lambda b, c: (c, 0, b),
                              memory_space=pltpu.VMEM)
    out_specs = [plane_spec, plane_spec]
    out_shape = [jax.ShapeDtypeStruct((Lq, W, B), jnp.uint8),
                 jax.ShapeDtypeStruct((Lq, W, B), jnp.uint8)]
    if nxt_k >= 4:
        out_specs.append(plane_spec)
        out_shape.append(jax.ShapeDtypeStruct((Lq, W, B), jnp.uint16))
    out_specs.append(pl.BlockSpec((W, tb), lambda b, c: (0, b),
                                  memory_space=pltpu.VMEM))
    out_shape.append(jax.ShapeDtypeStruct((W, B), dtype))
    outs = pl.pallas_call(
        kernel,
        # Profiler traces name the kernel by this: keep it stable.
        name="fw_dirs_band",
        grid=(B // tb, Lq // ch),
        in_specs=[
            pl.BlockSpec((W + Lq, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, tb), lambda b, c: (c, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((W, tb), dtype),
                        pltpu.VMEM((W, tb), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tband.astype(jnp.int32).T, qT.astype(jnp.int32),
      klo[None, :], lq[None, :])
    if nxt_k >= 4:
        dirs, nxt, nxt2, hlast = outs
        return dirs, nxt, nxt2, hlast.T.astype(jnp.int32)
    dirs, nxt, hlast = outs
    return dirs, nxt, hlast.T.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "W",
                                    "nxt_k"))
def fw_dirs_band_xla(tband: jnp.ndarray, qT: jnp.ndarray, klo: jnp.ndarray,
                     lq: jnp.ndarray, *, match: int, mismatch: int,
                     gap: int, W: int, nxt_k: int = 2):
    """Row-scan twin of fw_dirs_band (CPU tests / non-TPU fallback);
    bit-identical outputs by construction (same score dtype selection,
    fills and clamps as the Pallas kernel). ``nxt_k=4`` adds the
    ``nxt2`` uint16 plane to the return, like the Pallas entry point."""
    B = tband.shape[0]
    Lq = qT.shape[0]
    dtype = _score_dtype(match, mismatch, gap, Lq, W)
    NEG = _NEG16 if dtype == jnp.int16 else _NEG
    xr = jnp.arange(W, dtype=jnp.int32)[None, :]
    t32 = tband.astype(jnp.int32)
    j0 = klo[:, None] + xr
    P0 = (jnp.where(j0 >= 0, j0 * gap, NEG) +
          jnp.zeros_like(t32[:, :1])).astype(dtype)
    hl0 = P0
    U0 = jnp.zeros((B, W), jnp.int32)
    C0 = jnp.full((B, W), LEFT, jnp.int32)
    N0 = jnp.full((B, W), LEFT, jnp.int32)
    deep = nxt_k >= 4
    hops0 = (N0, N0) if deep else ()

    def step(carry, inp):
        P, hl, Up, Cp, Np, *hp = carry
        i, qrow = inp
        tw = jax.lax.dynamic_slice_in_dim(t32, i - 1, W, axis=1)
        jcol = i + klo[:, None] + xr
        sub = jnp.where(tw == qrow[:, None], match, mismatch)
        sub = jnp.where(jcol >= 1, sub, NEG).astype(dtype)
        diag = P + sub
        up = jnp.concatenate(
            [P[:, 1:], jnp.full((B, 1), NEG, dtype)], axis=1) + \
            jnp.asarray(gap, dtype)
        tmp = jnp.maximum(diag, up)
        tmp = jnp.where(jcol == 0, i * gap, tmp).astype(dtype)
        tmp = jnp.maximum(tmp, jnp.asarray(NEG, dtype))
        jg = (jcol * gap).astype(dtype)
        f = tmp - jg
        s = 1
        while s < W:
            f = jnp.maximum(
                f, jnp.concatenate(
                    [jnp.full((B, s), NEG, dtype), f[:, :-s]],
                    axis=1))
            s *= 2
        h = f + jg
        h = jnp.where(jcol >= 0, h, NEG).astype(dtype)
        d = jnp.where(h == diag, DIAG,
                      jnp.where(h == up, UP, LEFT))
        isup = d == UP

        def shift_up(A, fill):
            return jnp.concatenate(
                [A[:, 1:], jnp.full((B, 1), fill, jnp.int32)], axis=1)

        def shift_left(A):
            return jnp.concatenate(
                [jnp.full((B, 1), LEFT, jnp.int32), A[:, :-1]], axis=1)

        uup = shift_up(Up, 0)
        cup = shift_up(Cp, LEFT)
        nup = shift_up(Np, LEFT)
        U = jnp.where(isup, jnp.minimum(uup + 1, U_SAT), 0)
        C = jnp.where(isup, cup, d)
        # k-step predecessor metadata — same three-shift propagation as
        # the Pallas kernel (see _kernel): UP inherits field m from
        # above, DIAG takes the previous row's same-slot field m-1,
        # LEFT this row's just-computed field m-1 at slot x-1.
        ucnow = (U << 2) + C
        N = jnp.where(isup, nup,
                      jnp.where(d == DIAG, (Up << 2) + Cp, shift_left(ucnow)))
        packed = (d + (C << 2) + (U << 4)).astype(jnp.uint8)
        hl = jnp.where((lq == i)[:, None], h, hl)
        # ONE stacked uint8 ys (not a tuple): a scan emitting a TUPLE of
        # narrow-dtype ys miscompiles under XLA CPU jit in jax 0.9 (the
        # reverse-scan int16 variant is the verified case, see
        # racon_tpu/ops/colwalk.py) — don't gamble on the forward form.
        # At nxt_k=4 the hop-2/3 bytes ride the same stacked u8 ys; the
        # u16 nxt2 plane is assembled OUTSIDE the scan.
        if deep:
            N2p, N3p = hp
            N2 = jnp.where(isup, shift_up(N2p, LEFT),
                           jnp.where(d == DIAG, Np, shift_left(N)))
            N3 = jnp.where(isup, shift_up(N3p, LEFT),
                           jnp.where(d == DIAG, N2p, shift_left(N2)))
            ys = jnp.stack([packed, N.astype(jnp.uint8),
                            N2.astype(jnp.uint8), N3.astype(jnp.uint8)],
                           axis=0)
            return (h, hl, U, C, N, N2, N3), ys
        return (h, hl, U, C, N), jnp.stack(
            [packed, N.astype(jnp.uint8)], axis=0)

    ii = jnp.arange(1, Lq + 1, dtype=jnp.int32)
    carry0 = (P0, hl0, U0, C0, N0) + hops0
    carry, ys = jax.lax.scan(step, carry0, (ii, qT.astype(jnp.int32)))
    hlast = carry[1]
    if deep:
        nxt2 = (ys[:, 2].astype(jnp.uint16) |
                (ys[:, 3].astype(jnp.uint16) << 8))
        return ys[:, 0], ys[:, 1], nxt2, hlast.astype(jnp.int32)
    return ys[:, 0], ys[:, 1], hlast.astype(jnp.int32)


UC_BOUNDARY = uc_boundary(2)   # row-0 / out-of-band packed (N,U,C)


def _kernel_tile(tbandT_ref, qT_ref, klo_ref, lq_ref, i0_ref, pin_ref,
                 ucin_ref, hlin_ref, *refs, match, mismatch, gap, W,
                 dtype, TB, CH, nxt_k=2):
    # Tiled variant of _kernel for the ultralong overlap path: identical
    # row recurrence, but rows are numbered from a runtime tile origin
    # i0 (so ONE compiled kernel serves every tile of a lax.scan over
    # tiles), and the DP frontier — last band row of scores, packed
    # (N << 6 | U << 2 | C) metadata, and the captured hlast — enters as
    # inputs and leaves as outputs instead of being scratch-initialized.
    # Kept as a separate body rather than a parameterization of _kernel:
    # the untiled kernel is the consensus path's pinned production
    # kernel, and Mosaic's lowering quirks make "refactor shared, hope
    # TPU lowering is unchanged" a bad trade against ~60 duplicated
    # lines (tests/test_tpu_compile.py compiles both for a v5e).
    if nxt_k >= 4:
        dirs_ref, nxt_ref, nxt2_ref, hlast_ref, prev_ref, ucprev_ref = refs
    else:
        dirs_ref, nxt_ref, hlast_ref, prev_ref, ucprev_ref = refs
        nxt2_ref = None
    c = pl.program_id(1)
    NEG = _NEG16 if dtype == jnp.int16 else _NEG
    BND = uc_boundary(nxt_k)
    xr = jax.lax.broadcasted_iota(jnp.int32, (W, TB), 0)
    klo = klo_ref[0]                       # [TB] int32 (this tile's band)
    lqv = lq_ref[0]                        # [TB] int32
    i0 = i0_ref[0][None, :]                # (1, TB) int32 tile row origin

    @pl.when(c == 0)
    def _():
        prev_ref[:] = pin_ref[:]
        ucprev_ref[:] = ucin_ref[:]
        hlast_ref[:] = hlin_ref[:]

    def row(r, _):
        rl = c * CH + r + 1                # 1-based row within the tile
        i = i0 + rl                        # (1, TB) global 1-based row
        qrow = qT_ref[r]                   # [TB] int32
        tw = tbandT_ref[pl.dslice(rl - 1, W), :]
        jcol = i + klo[None, :] + xr       # absolute target column j
        sub = jnp.where(tw == qrow[None, :], match, mismatch)
        sub = jnp.where(jcol >= 1, sub, NEG).astype(dtype)
        P = prev_ref[:]
        diag = P + sub
        up = jnp.concatenate(
            [P[1:, :], jnp.full((1, TB), NEG, dtype)], axis=0) + \
            jnp.asarray(gap, dtype)
        tmp = jnp.maximum(diag, up)
        tmp = jnp.where(jcol == 0, i * gap, tmp).astype(dtype)
        tmp = jnp.maximum(tmp, jnp.asarray(NEG, dtype))
        jg = (jcol * gap).astype(dtype)
        f = tmp - jg
        s = 1
        while s < W:
            f = jnp.maximum(
                f, jnp.concatenate(
                    [jnp.full((s, TB), NEG, dtype), f[:-s, :]],
                    axis=0))
            s *= 2
        h = f + jg
        h = jnp.where(jcol >= 0, h, NEG).astype(dtype)
        d = jnp.where(h == diag, jnp.asarray(DIAG, dtype),
                      jnp.where(h == up, jnp.asarray(UP, dtype),
                                jnp.asarray(LEFT, dtype))).astype(jnp.int32)
        isup = d == UP
        ucp = ucprev_ref[:]
        ucup = jnp.concatenate(
            [ucp[1:, :], jnp.full((1, TB), BND, jnp.int32)],
            axis=0)
        U = jnp.where(isup, jnp.minimum(((ucup >> 2) & 0xF) + 1, U_SAT), 0)
        C = jnp.where(isup, ucup & 3, d)
        ucnow = (U << 2) + C
        nleft = jnp.concatenate(
            [jnp.full((1, TB), LEFT, jnp.int32), ucnow[:-1, :]], axis=0)
        N = jnp.where(isup, (ucup >> 6) & 0x3F,
                      jnp.where(d == DIAG, ucp & 0x3F, nleft))
        dirs_ref[r] = (d + (C << 2) + (U << 4)).astype(jnp.uint8)
        nxt_ref[r] = N.astype(jnp.uint8)
        if nxt_k >= 4:
            n1left = jnp.concatenate(
                [jnp.full((1, TB), LEFT, jnp.int32), N[:-1, :]], axis=0)
            N2 = jnp.where(isup, (ucup >> 12) & 0x3F,
                           jnp.where(d == DIAG, (ucp >> 6) & 0x3F, n1left))
            n2left = jnp.concatenate(
                [jnp.full((1, TB), LEFT, jnp.int32), N2[:-1, :]], axis=0)
            N3 = jnp.where(isup, (ucup >> 18) & 0x3F,
                           jnp.where(d == DIAG, (ucp >> 12) & 0x3F, n2left))
            nxt2_ref[r] = ((N3 << 8) + N2).astype(jnp.uint16)
            ucprev_ref[:] = (N3 << 18) + (N2 << 12) + (N << 6) + ucnow
        else:
            ucprev_ref[:] = (N << 6) + ucnow
        prev_ref[:] = h
        hlast_ref[:] = jnp.where(lqv[None, :] == i, h, hlast_ref[:])
        return 0

    jax.lax.fori_loop(0, CH, row, 0)


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "W",
                                    "tb", "ch", "interpret", "nxt_k"))
def fw_dirs_band_tile(tband: jnp.ndarray, qT: jnp.ndarray,
                      klo: jnp.ndarray, lq: jnp.ndarray, i0: jnp.ndarray,
                      prev: jnp.ndarray, uc: jnp.ndarray,
                      hlast: jnp.ndarray, *, match: int, mismatch: int,
                      gap: int, W: int, tb: int = TB, ch: int = CH,
                      interpret: bool = False, nxt_k: int = 2):
    """One query-axis tile of the banded forward with an explicit DP
    frontier (Pallas).

    Args:
      tband: uint8/int32[B, W + T] targets pre-shifted for THIS tile:
             ``tband[b, y] = target_b[klo_b + i0_b + y]`` (fill 7).
      qT:    uint8/int32[T, B] this tile's query rows, transposed.
      klo:   int32[B] this tile's band origin (may differ per tile after
             re-centering; ops/ovl_align.py records the per-tile values
             for the stitched column walk).
      lq/i0: int32[B] query lengths / 0-based global row origin of the
             tile (rows i0+1 .. i0+T are computed; i0 is identical
             across lanes of one dispatch but ships as a lane vector so
             the kernel stays shape-stable under lax.scan).
      prev/uc/hlast: int32[B, W] carried frontier — H[i0] over the band,
             the packed ``(N << 6) | (U << 2) | C`` metadata of row i0
             (extended by the ``(N3 << 18) | (N2 << 12)`` hop fields at
             ``nxt_k=4``), and the running final-row capture. For tile 0
             the caller passes the same init the untiled kernel builds
             internally (j0*gap / uc_boundary(nxt_k) / init), making a
             single-tile call bit-identical to :func:`fw_dirs_band`.

    Returns (cells uint8[T, W, B], nxt uint8[T, W, B], hlast int32[B, W],
    prev int32[B, W], uc int32[B, W]) — the trailing three are the
    frontier after row i0+T, in the SAME band coordinates as the input
    (the caller shifts them when it re-centers klo for the next tile).
    With ``nxt_k=4`` the ``nxt2`` uint16[T, W, B] plane is inserted
    after ``nxt`` (6 outputs). Scores are always int32: frontier
    magnitudes grow with the GLOBAL query length, which this per-tile
    entry point cannot bound.
    """
    B = tband.shape[0]
    T = qT.shape[0]
    dtype = jnp.int32
    kernel = functools.partial(_kernel_tile, match=match,
                               mismatch=mismatch, gap=gap, W=W,
                               dtype=dtype, TB=tb, CH=ch, nxt_k=nxt_k)
    plane_spec = pl.BlockSpec((ch, W, tb), lambda b, c: (c, 0, b),
                              memory_space=pltpu.VMEM)
    # Frontier outputs persist across the sequential c steps via the
    # constant index map — same contract the untiled kernel's hlast
    # output already relies on.
    front_spec = pl.BlockSpec((W, tb), lambda b, c: (0, b),
                              memory_space=pltpu.VMEM)
    out_specs = [plane_spec, plane_spec]
    out_shape = [jax.ShapeDtypeStruct((T, W, B), jnp.uint8),
                 jax.ShapeDtypeStruct((T, W, B), jnp.uint8)]
    if nxt_k >= 4:
        out_specs.append(plane_spec)
        out_shape.append(jax.ShapeDtypeStruct((T, W, B), jnp.uint16))
    out_specs += [front_spec, front_spec, front_spec]
    out_shape += [jax.ShapeDtypeStruct((W, B), dtype),
                  jax.ShapeDtypeStruct((W, B), dtype),
                  jax.ShapeDtypeStruct((W, B), jnp.int32)]
    outs = pl.pallas_call(
        kernel,
        # Profiler traces name the kernel by this: keep it stable.
        name="fw_dirs_band_tile",
        grid=(B // tb, T // ch),
        in_specs=[
            pl.BlockSpec((W + T, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ch, tb), lambda b, c: (c, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tb), lambda b, c: (0, b),
                         memory_space=pltpu.VMEM),
            front_spec,
            front_spec,
            front_spec,
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tband.astype(jnp.int32).T, qT.astype(jnp.int32),
      klo[None, :], lq[None, :], i0[None, :],
      prev.astype(dtype).T, uc.astype(jnp.int32).T,
      hlast.astype(dtype).T)
    if nxt_k >= 4:
        dirs, nxt, nxt2, hl, pout, ucout = outs
        return (dirs, nxt, nxt2, hl.T.astype(jnp.int32),
                pout.T.astype(jnp.int32), ucout.T)
    dirs, nxt, hl, pout, ucout = outs
    return (dirs, nxt, hl.T.astype(jnp.int32), pout.T.astype(jnp.int32),
            ucout.T)


@functools.partial(jax.jit,
                   static_argnames=("match", "mismatch", "gap", "W",
                                    "nxt_k"))
def fw_dirs_band_xla_tile(tband: jnp.ndarray, qT: jnp.ndarray,
                          klo: jnp.ndarray, lq: jnp.ndarray,
                          i0: jnp.ndarray, prev: jnp.ndarray,
                          uc: jnp.ndarray, hlast: jnp.ndarray, *,
                          match: int, mismatch: int, gap: int, W: int,
                          nxt_k: int = 2):
    """Row-scan twin of fw_dirs_band_tile (CPU tests / non-TPU
    fallback); bit-identical outputs by construction. Cells/nxt come
    back [T, B, W] (vs the kernel's [T, W, B]), like the untiled pair.
    ``nxt_k=4`` inserts the ``nxt2`` uint16 plane after ``nxt``.
    """
    B = tband.shape[0]
    T = qT.shape[0]
    dtype = jnp.int32
    NEG = _NEG
    xr = jnp.arange(W, dtype=jnp.int32)[None, :]
    t32 = tband.astype(jnp.int32)
    P0 = prev.astype(dtype)
    hl0 = hlast.astype(dtype)
    U0 = (uc >> 2) & 0xF
    C0 = uc & 3
    N0 = (uc >> 6) & 0x3F
    deep = nxt_k >= 4
    hops0 = ((uc >> 12) & 0x3F, (uc >> 18) & 0x3F) if deep else ()

    def step(carry, inp):
        P, hl, Up, Cp, Np, *hp = carry
        rl, qrow = inp
        i = (i0 + rl)[:, None]             # (B, 1) global 1-based row
        tw = jax.lax.dynamic_slice_in_dim(t32, rl - 1, W, axis=1)
        jcol = i + klo[:, None] + xr
        sub = jnp.where(tw == qrow[:, None], match, mismatch)
        sub = jnp.where(jcol >= 1, sub, NEG).astype(dtype)
        diag = P + sub
        up = jnp.concatenate(
            [P[:, 1:], jnp.full((B, 1), NEG, dtype)], axis=1) + \
            jnp.asarray(gap, dtype)
        tmp = jnp.maximum(diag, up)
        tmp = jnp.where(jcol == 0, i * gap, tmp).astype(dtype)
        tmp = jnp.maximum(tmp, jnp.asarray(NEG, dtype))
        jg = (jcol * gap).astype(dtype)
        f = tmp - jg
        s = 1
        while s < W:
            f = jnp.maximum(
                f, jnp.concatenate(
                    [jnp.full((B, s), NEG, dtype), f[:, :-s]],
                    axis=1))
            s *= 2
        h = f + jg
        h = jnp.where(jcol >= 0, h, NEG).astype(dtype)
        d = jnp.where(h == diag, DIAG,
                      jnp.where(h == up, UP, LEFT))
        isup = d == UP

        def shift_up(A, fill):
            return jnp.concatenate(
                [A[:, 1:], jnp.full((B, 1), fill, jnp.int32)], axis=1)

        def shift_left(A):
            return jnp.concatenate(
                [jnp.full((B, 1), LEFT, jnp.int32), A[:, :-1]], axis=1)

        uup = shift_up(Up, 0)
        cup = shift_up(Cp, LEFT)
        nup = shift_up(Np, LEFT)
        U = jnp.where(isup, jnp.minimum(uup + 1, U_SAT), 0)
        C = jnp.where(isup, cup, d)
        ucnow = (U << 2) + C
        N = jnp.where(isup, nup,
                      jnp.where(d == DIAG, (Up << 2) + Cp,
                                shift_left(ucnow)))
        packed = (d + (C << 2) + (U << 4)).astype(jnp.uint8)
        hl = jnp.where((lq == i[:, 0])[:, None], h, hl)
        if deep:
            N2p, N3p = hp
            N2 = jnp.where(isup, shift_up(N2p, LEFT),
                           jnp.where(d == DIAG, Np, shift_left(N)))
            N3 = jnp.where(isup, shift_up(N3p, LEFT),
                           jnp.where(d == DIAG, N2p, shift_left(N2)))
            ys = jnp.stack([packed, N.astype(jnp.uint8),
                            N2.astype(jnp.uint8), N3.astype(jnp.uint8)],
                           axis=0)
            return (h, hl, U, C, N, N2, N3), ys
        return (h, hl, U, C, N), jnp.stack(
            [packed, N.astype(jnp.uint8)], axis=0)

    ii = jnp.arange(1, T + 1, dtype=jnp.int32)
    carry, ys = jax.lax.scan(
        step, (P0, hl0, U0, C0, N0) + hops0, (ii, qT.astype(jnp.int32)))
    if deep:
        Pf, hlf, Uf, Cf, Nf, N2f, N3f = carry
        ucout = ((N3f << 18) + (N2f << 12) + (Nf << 6) + (Uf << 2) + Cf)
        nxt2 = (ys[:, 2].astype(jnp.uint16) |
                (ys[:, 3].astype(jnp.uint16) << 8))
        return (ys[:, 0], ys[:, 1], nxt2, hlf.astype(jnp.int32),
                Pf.astype(jnp.int32), ucout)
    Pf, hlf, Uf, Cf, Nf = carry
    ucout = (Nf << 6) + (Uf << 2) + Cf
    return (ys[:, 0], ys[:, 1], hlf.astype(jnp.int32),
            Pf.astype(jnp.int32), ucout)


def band_geometry(lq, lt, W: int):
    """Per-lane (klo, wl) for a W-slot band (all int32 vectors)."""
    delta = lt - lq
    wl = (W - 1 - jnp.abs(delta)) // 2
    klo = jnp.minimum(0, delta) - wl
    return klo, wl


def fw_traceback_band(dirs: jnp.ndarray, lq: jnp.ndarray, lt: jnp.ndarray,
                      klo: jnp.ndarray, steps: int,
                      transposed: bool = False):
    """Traceback over banded packed cells: rev ops uint8[B, steps].

    Identical walk to flat.fw_traceback with the column index mapped to
    band coordinates x = j - i - klo per lane. ``transposed`` selects
    the Pallas kernel's [Lq, W, B] dirs layout (vs [Lq, B, W]). Legacy
    op-by-op walk kept for tests and the sp path; the production
    traceback is the column-walk (racon_tpu/ops/colwalk.py).
    """
    if transposed:
        Lq, W, B = dirs.shape
    else:
        Lq, B, W = dirs.shape
    d1 = dirs.reshape(-1)
    lane = jnp.arange(B, dtype=jnp.int32)

    def step(state, _):
        i, j = state
        done = (i == 0) & (j == 0)
        x = jnp.clip(j - i - klo, 0, W - 1)
        if transposed:
            idx = (jnp.maximum(i - 1, 0) * (B * W) + x * B + lane)
        else:
            idx = (jnp.maximum(i - 1, 0) * (B * W) + lane * W + x)
        dv = jnp.take(d1, idx) & 3        # low bits of the packed cell
        d = jnp.where(done, 3,
                      jnp.where(i == 0, LEFT,
                                jnp.where(j == 0, UP, dv))).astype(jnp.uint8)
        i = i - jnp.where((d == DIAG) | (d == UP), 1, 0).astype(i.dtype)
        j = j - jnp.where((d == DIAG) | (d == LEFT), 1, 0).astype(j.dtype)
        return (i, j), d

    (_, _), rev_ops = jax.lax.scan(
        step, (lq.astype(jnp.int32), lt.astype(jnp.int32)), None,
        length=steps)
    return rev_ops.T
