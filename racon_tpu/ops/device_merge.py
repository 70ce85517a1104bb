"""Device-resident consensus merge — the TPU replacement for the host
column-vote (racon_tpu/ops/poa.py::_merge_round/_scatter_jobs/_InsPileup).

Same semantics as the numpy reference implementation (which mirrors
spoa's add_alignment + generate_consensus, reference src/window.cpp:
100-111), restructured for TPU execution:

- **No scatters.** XLA lowers general scatter-adds on TPU to serialized
  updates; every per-op scatter in the numpy merge is reformulated as a
  gather. The key identity: in a global alignment, ops with the same
  "target positions consumed so far" value form one contiguous block
  ``[insertion run at gap v][the op consuming column v]``, so a per-lane
  ``searchsorted`` over that monotone counter finds, for every anchor
  column, the op that consumed it and the insertion run before it — all
  columns in parallel.
- **Aggregation is a matmul.** Per-job dense per-column vote channels are
  summed into per-window accumulators by a window-membership one-hot
  matrix ([Nw, B] @ [B, LA*C]) on the MXU — weights are integer-valued
  (Phred or 1.0), so f32 accumulation is exact below 2^24.
- **Variable-length output without host round-trips.** Emitted consensus
  lives in a padded [Nw, LA+1, K+1] slot layout (K insertion slots per
  gap + the column slot); compaction to dense per-window strings is a
  searchsorted gather over the valid-slot cumsum. Only the final compact
  consensus + coverage leave the device.

Deviations from the numpy reference (documented, covered by tolerance in
differential tests): insertion pileups cap at K columns per gap (the
reference is unbounded; >K-base unanimous insertions are truncated), and
accumulator dtype is f32 (reference f64) so sub-ulp tie-breaks can differ
when non-integer mean weights collide exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from racon_tpu.ops.cigar import DIAG, UP, LEFT
from racon_tpu.ops.flat import PAD_OP  # shared op padding marker
from racon_tpu.ops.flat import U_SAT as _U_SAT
from racon_tpu.ops.poa import _EPS as EPS  # shared tie-break epsilon

# Pileup columns per gap kept on device. Insertion runs longer than
# K_INS raise the walk's sticky redo flag (flat.U_SAT = K_INS + 1) and
# re-polish on the unbounded host path — unlike the former K=8/U_SAT=15
# scheme, device output is never silently truncated. 10 is measured on
# the reference lambda dataset: every window's max run is <= 10 (zero
# redos), where K=8 would redo 8/96 windows and K=4 68/96.
K_INS = 10
# The contract above only holds when the walk's saturation threshold
# tracks K (and extract_votes_cols' packed-word layout is hand-laid for
# K = 10); fail loudly at import if either is retuned alone. ValueError,
# not assert: asserts are stripped under `python -O`, and silently
# running with a mismatched layout corrupts every consensus.
if _U_SAT != K_INS + 1:
    raise ValueError(
        "[racon_tpu::device_merge] flat.U_SAT must equal K_INS + 1 "
        f"(U_SAT={_U_SAT}, K_INS={K_INS})")
if K_INS != 10:
    raise ValueError(
        "[racon_tpu::device_merge] extract_votes_cols' packed-word "
        f"layout is hand-laid for K_INS=10 (got {K_INS})")
NBASE = 5          # A C G T N
# Python int, NOT jnp.int32: a module-level jax.Array closed over by a
# jitted function lowers as a hoisted buffer parameter on some traces, and
# jax 0.9's execution path then under-supplies the executable ("Execution
# supplied 11 buffers but compiled program expected 12") — the root cause
# of the round-3 INVALID_ARGUMENT crash on TPU (guarded by
# tests/test_poa.py::test_device_engine_two_shapes_one_process). A Python
# scalar is always inlined.
_HI = 2 ** 30

_PREC = jax.lax.Precision.HIGHEST


def _onehot(idx, depth, dtype=jnp.float32):
    return (idx[..., None] == jnp.arange(depth, dtype=idx.dtype)).astype(
        dtype)


def _take1(a, idx):
    """take_along_axis on axis 1 with clipping."""
    return jnp.take_along_axis(a, jnp.clip(idx, 0, a.shape[1] - 1), axis=1)


def extract_votes(ops, q, qw, w_read, lt, t_off, LA: int,
                  pallas: bool = False):
    """Per-job anchor-aligned dense vote channels from right-aligned ops.

    Args:
      ops:    uint8[B, S] right-aligned (PAD_OP prefix), start->end order.
      q:      uint8[B, Lq] query codes.
      qw:     f32[B, Lq] per-base weights.
      w_read: f32[B] read-mean weight.
      lt:     int32[B] target (slice) lengths.
      t_off:  int32[B] slice offset in the window anchor.
      LA:     static anchor padding length.
      pallas: route the monotone count through the Pallas kernel.

    Returns dict of [B, LA(+1), ...] channel arrays (see code).

    Perf notes (measured in-program on TPU v5e at B=3072, S=1408):
    the broadcast compare-reduce for F cost ~380 ms under XLA — it is a
    Pallas kernel now (racon_tpu/ops/pallas/count_kernel.py, ~10 ms) —
    and per-column gathers cost ~10-25 ms *per call* regardless of
    width, so the ~23 take_along_axis calls of the first version are
    coalesced into 4 stacked gathers over channel stacks.
    """
    from racon_tpu.ops.pallas.count_kernel import (monotone_count_pallas,
                                                   monotone_count_xla)
    B, S = ops.shape
    Lq = q.shape[1]
    valid = ops != PAD_OP
    tcons = valid & (ops != UP)
    qcons = valid & (ops != LEFT)
    ct = jnp.cumsum(tcons, axis=1, dtype=jnp.int32)
    cq = jnp.cumsum(qcons, axis=1, dtype=jnp.int32)
    ct_excl = ct - tcons
    cq_excl = cq - qcons
    # Monotone block key: pads (a prefix) sort below every real op.
    X = jnp.where(valid, ct_excl, -1)

    # F[v] = first op index of block v, for v = p - t_off at every anchor
    # gap/column p in [0, LA]. (+1 row for F[v+1].) searchsorted-left
    # over a monotone key == count of keys < v; shifting X by t_off turns
    # the per-lane v grid into the plain arange the count kernel wants.
    Xs = X + t_off[:, None]
    if pallas and B % 128 == 0:
        F = monotone_count_pallas(Xs, LA + 2)        # [B, LA+2]
    else:
        F = monotone_count_xla(Xs, LA + 2)
    Fa = F[:, :-1]                                    # F(c) at p
    F1 = F[:, 1:]                                     # F(c+1) at p

    ltc = lt[:, None]
    pa = jnp.arange(LA + 2, dtype=jnp.int32)[None, :]
    c = (pa - t_off[:, None])[:, :-1]                 # slice-rel position at p
    in_cols = (c >= 0) & (c < ltc)                    # column p exists
    in_gaps = (c >= 0) & (c <= ltc)                   # gap p exists

    # Insertion run before column c: block minus its t-step (absent at c==lt).
    ins_len = jnp.where(in_gaps,
                        F1 - Fa - jnp.where(c < ltc, 1, 0), 0)  # [B, LA+1]

    # Stacked gather #1 (op axis): channels [cq_excl[min(s, S-1)],
    # cq_excl[s-1], ops[s-1]] read at s = F[p] give, per column, the
    # first-insertion q index (at p) and the column-consuming op's
    # q index / op code (at p+1, where F[p+1]-1 is the consumer).
    # The stack has S+1 rows because F reaches S whenever an alignment's
    # last op consumes its last column; boundary rows replicate the
    # clipped-take semantics of a plain gather at F-1 / F.
    ops32 = ops.astype(jnp.int32)
    stack_s = jnp.stack(
        [jnp.concatenate([cq_excl, cq_excl[:, -1:]], axis=1),
         jnp.concatenate([cq_excl[:, :1], cq_excl], axis=1),
         jnp.concatenate([ops32[:, :1], ops32], axis=1)],
        axis=-1)                                      # [B, S+1, 3]
    G = jnp.take_along_axis(
        stack_s, jnp.clip(F, 0, S)[:, :, None], axis=1)      # [B, LA+2, 3]
    qstart = G[:, :-1, 0]                             # q idx of first ins base
    qi = G[:, 1:, 1]                                  # q idx matched at c
    op_at = G[:, 1:, 2]                               # op consuming column c
    is_match = in_cols & (op_at == DIAG)

    # Stacked gather #2 (query axis) at qi: [base code, weight].
    qx = q.astype(jnp.int32)
    stack_qi = jnp.stack([qx.astype(jnp.float32), qw], axis=-1)
    Gqi = jnp.take_along_axis(
        stack_qi, jnp.clip(qi, 0, Lq - 1)[:, :, None], axis=1)
    colbase = Gqi[..., 0].astype(jnp.int32)
    colw = Gqi[..., 1]
    wq = jnp.where(is_match, colw, w_read[:, None])   # per-column path weight

    cols = in_cols[:, :LA]
    base_idx = jnp.where(is_match[:, :LA], colbase[:, :LA], NBASE)  # 5 = del
    col_w = jnp.where(cols, jnp.where(is_match[:, :LA], colw[:, :LA],
                                      w_read[:, None]), 0.0)
    col_oh = _onehot(base_idx, NBASE + 1)
    col_w_ch = col_oh * col_w[..., None]                       # [B, LA, 6]
    col_c_ch = col_oh[..., :NBASE] * (is_match[:, :LA] &
                                      cols)[..., None]         # [B, LA, 5]

    # Direct crossings: columns c-1 and c both consumed, no insertion between.
    crossed = (c >= 1) & (c <= ltc - 1) & (ins_len == 0)
    wq_prev = jnp.concatenate([w_read[:, None], wq[:, :LA]], axis=1)
    cross_w = jnp.where(crossed, 0.5 * (wq_prev + wq), 0.0)    # [B, LA+1]

    # Stacked gather #3 (query axis) at qstart: the k = 0..K-1 shifted
    # base/weight channels (pileup columns without per-k gathers) plus
    # the weight prefix sum at the run start. Tail-clamped pads replicate
    # take-with-clip semantics for runs ending at the query edge.
    qwcum = jnp.concatenate(
        [jnp.zeros((B, 1), jnp.float32), jnp.cumsum(qw, axis=1)], axis=1)
    qx_pad = jnp.concatenate(
        [qx, jnp.repeat(qx[:, -1:], K_INS - 1, axis=1)], axis=1)
    qw_pad = jnp.concatenate(
        [qw, jnp.repeat(qw[:, -1:], K_INS - 1, axis=1)], axis=1)
    chans = ([qx_pad[:, k:k + Lq].astype(jnp.float32)
              for k in range(K_INS)] +
             [qw_pad[:, k:k + Lq] for k in range(K_INS)] +
             [qwcum[:, :Lq]])
    stack_qs = jnp.stack(chans, axis=-1)              # [B, Lq, 2K+1]
    Gqs = jnp.take_along_axis(
        stack_qs, jnp.clip(qstart, 0, Lq - 1)[:, :, None], axis=1)
    b_k = Gqs[..., :K_INS].astype(jnp.int32)          # q[qstart+k]
    w_k = Gqs[..., K_INS:2 * K_INS]                   # qw[qstart+k]
    cum_start = Gqs[..., 2 * K_INS]                   # qwcum[qstart]

    # Insertions.
    has1 = in_gaps & (ins_len == 1)
    multi = in_gaps & (ins_len >= 2)
    b1 = b_k[..., 0]
    w1 = w_k[..., 0]
    ins1_oh = _onehot(jnp.where(has1, b1, NBASE), NBASE + 1)[..., :NBASE]
    ins1_w_ch = ins1_oh * jnp.where(has1, w1, 0.0)[..., None]
    ins1_c_ch = ins1_oh * has1[..., None]
    ins1_stop = jnp.where(has1, w1, 0.0)

    # Pileup columns k = 0..K-1 for multi-base runs (no gathers).
    pk_w, pk_c = [], []
    for k in range(K_INS):
        inrun = multi & (ins_len > k)
        oh = _onehot(jnp.where(inrun, b_k[..., k], NBASE),
                     NBASE + 1)[..., :NBASE]
        pk_w.append(oh * jnp.where(inrun, w_k[..., k], 0.0)[..., None])
        pk_c.append(oh * inrun[..., None])
    pile_w_ch = jnp.stack(pk_w, axis=2)               # [B, LA+1, K, 5]
    pile_c_ch = jnp.stack(pk_c, axis=2)

    # Run mean weight -> stop-weight by run length (lengths 2..K).
    # Stacked gather #4: weight prefix sum at the run end.
    run_sum = _take1(qwcum, qstart + ins_len) - cum_start
    wmean = jnp.where(multi, run_sum / jnp.maximum(ins_len, 1), 0.0)
    lw_oh = (jnp.clip(ins_len, 0, K_INS)[..., None] ==
             jnp.arange(2, K_INS + 1)[None, None, :])
    lenw_ch = lw_oh * (wmean * multi)[..., None]      # [B, LA+1, K-1]

    return {
        "col_w": col_w_ch, "col_c": col_c_ch,
        "cross_w": cross_w[..., None],
        "ins1_w": ins1_w_ch, "ins1_c": ins1_c_ch,
        "ins1_stop": ins1_stop[..., None],
        "pile_w": pile_w_ch.reshape(B, LA + 1, -1),
        "pile_c": pile_c_ch.reshape(B, LA + 1, -1),
        "lenw": lenw_ch,
    }


def extract_votes_cols(cols, q, qw8, w_read, lt, t_off, LA: int):
    """Per-job anchor-aligned dense vote channels from column-walk output.

    The production replacement for :func:`extract_votes`. The column-walk
    traceback (racon_tpu/ops/colwalk.py) already emits ``ins_len /
    qstart / op_c / qi_c`` keyed by anchor position, so no re-keying
    gathers are needed; the only gather left is ONE merged query-window
    read. Key facts: the consumer's query index ``qi`` differs from the
    run start ``qstart`` by at most 1, and every unflagged insertion run
    is at most K_INS bases (longer runs saturate the walk's up-run
    counter at U_SAT = K_INS + 1 and take the host redo route), so the
    window only spans K_INS + 1 query codes and K_INS + 1 weights around
    qstart - 1. TPU gather cost scales with the number of gathered
    ELEMENTS, not bytes (measured on the chip in round 5: a 26-channel
    u8 stacked gather costs ~100 ms at B=6144 where a 3-word i32 gather
    costs ~30 ms), so the window ships as FOUR packed
    i32 words per query position: 11 base codes at 3 bits each and 11
    weights at 7 bits each (weights are Phred + 1 <= 94 + 1 on any real
    FASTQ — ChunkPlan clips the encoding at 126 accordingly). The run
    weight sum is an in-register masked sum over the decoded window —
    exact, since weights are integers and partial sums stay far below
    2^24.

    Every channel value consumed downstream is bit-identical to
    extract_votes' (masked-out garbage may differ; all returned channels
    are masked). Insertion runs longer than U_SAT are handled by the
    walk's saturation redo flag, never by these channels.

    Args:
      cols: dict from colwalk.col_walk ([B, LA+2] int16 arrays).
      q: uint8[B, Lq] query codes.
      qw8: uint8[B, Lq] encoded weights (value + 1, 0 = padding).
      w_read, lt, t_off, LA: as extract_votes.
    """
    B, Lq = q.shape
    ltc = lt[:, None]
    pa = jnp.arange(LA + 1, dtype=jnp.int32)[None, :]
    c = pa - t_off[:, None]                  # anchor-relative position
    in_cols = (c >= 0) & (c < ltc)
    in_gaps = (c >= 0) & (c <= ltc)

    ins_len = jnp.where(in_gaps, cols["ins_len"][:, :LA + 1]
                        .astype(jnp.int32), 0)
    qstart = cols["qstart"][:, :LA + 1].astype(jnp.int32)
    # Column p's consumer was emitted by the walk step at p + 1.
    op_at = cols["op_c"][:, 1:].astype(jnp.int32)
    qi = cols["qi_c"][:, 1:].astype(jnp.int32)
    is_match = in_cols & (op_at == DIAG)

    # Merged query-window gather over the FULL LA+2 walk grid: offsets
    # 0..K_INS around qstart-1, packed into FOUR i32 words per query
    # position (see docstring — gather cost scales with element count).
    # Word layout (QO = K_INS + 1 = 11 offsets):
    #   word0: q[0..9]  at 3 bits each            (bits 0..29)
    #   word1: w[0..3]  at 7 bits each | q[10]<<28 (bits 0..30)
    #   word2: w[4..7]  at 7 bits each            (bits 0..27)
    #   word3: w[8..10] at 7 bits each            (bits 0..20)
    # Gap consumers (pileup/run channels at anchor p) read row p; the
    # column-p consumer's query index qi was emitted by walk step p+1
    # and satisfies qi in {qstart[p+1]-1, qstart[p+1]}, so its
    # base/weight read row p+1 of the same gather.
    QO = K_INS + 1
    qpad = jnp.concatenate(
        [q, jnp.repeat(q[:, -1:], QO, axis=1)], axis=1).astype(jnp.int32)
    wpad = jnp.minimum(jnp.concatenate(
        [qw8, jnp.repeat(qw8[:, -1:], QO, axis=1)], axis=1)
        .astype(jnp.int32), 127)
    word0 = sum((qpad[:, o:o + Lq] << (3 * o)) for o in range(10))
    word1 = sum((wpad[:, o:o + Lq] << (7 * o)) for o in range(4)) \
        + (qpad[:, 10:10 + Lq] << 28)
    word2 = sum((wpad[:, o:o + Lq] << (7 * (o - 4))) for o in range(4, 8))
    word3 = sum((wpad[:, o:o + Lq] << (7 * (o - 8))) for o in range(8, 11))
    stack = jnp.stack([word0, word1, word2, word3], axis=-1)
    qs_full = cols["qstart"].astype(jnp.int32)        # [B, LA+2]
    qsc_full = jnp.clip(qs_full, 0, Lq - 1)
    s0_full = jnp.maximum(qsc_full - 1, 0)
    Gfull = jnp.take_along_axis(stack, s0_full[:, :, None], axis=1)
    Gg = Gfull[:, :LA + 1]                            # gap rows (step p)

    def _q_at(g, o):
        if o == 10:
            return (g[..., 1] >> 28) & 7
        return (g[..., 0] >> (3 * o)) & 7

    def _w_at(g, o):
        w, s = divmod(o, 4)
        raw = (g[..., 1 + w] >> (7 * s)) & 127
        return jnp.maximum(raw.astype(jnp.float32) - 1.0, 0.0)

    o1 = (qsc_full - s0_full)[:, :LA + 1] == 1

    def sel_q(o):
        return jnp.where(o1, _q_at(Gg, o + 1), _q_at(Gg, o))

    def sel_w(o):
        return jnp.where(o1, _w_at(Gg, o + 1), _w_at(Gg, o))

    Gc = Gfull[:, 1:]                                 # column rows (p+1)
    qi1 = (jnp.clip(qi, 0, Lq - 1) - s0_full[:, 1:]) == 1
    colbase = jnp.where(qi1, _q_at(Gc, 1), _q_at(Gc, 0))
    colw = jnp.where(qi1, _w_at(Gc, 1), _w_at(Gc, 0))
    wq = jnp.where(is_match, colw, w_read[:, None])   # per-column weight

    # Integer-valued channels (one-hot counts, integer Phred weights)
    # are emitted in bfloat16 — exact for these values, and they ride
    # aggregate_votes' cheap bf16 MXU matmul (see its docstring).
    bf16 = jnp.bfloat16
    cols_m = in_cols[:, :LA]
    base_idx = jnp.where(is_match[:, :LA], colbase[:, :LA], NBASE)
    col_w = jnp.where(cols_m, jnp.where(is_match[:, :LA], colw[:, :LA],
                                        w_read[:, None]), 0.0)
    col_oh = _onehot(base_idx, NBASE + 1)
    col_w_ch = col_oh * col_w[..., None]                       # [B, LA, 6]
    col_c_ch = (col_oh[..., :NBASE].astype(bf16) *
                (is_match[:, :LA] &
                 cols_m)[..., None].astype(bf16))              # [B, LA, 5]

    # Direct crossings: columns c-1 and c both consumed, no insertion.
    crossed = (c >= 1) & (c <= ltc - 1) & (ins_len == 0)
    wq_prev = jnp.concatenate([w_read[:, None], wq[:, :LA]], axis=1)
    cross_w = jnp.where(crossed, 0.5 * (wq_prev + wq), 0.0)    # [B, LA+1]

    # Insertions.
    has1 = in_gaps & (ins_len == 1)
    multi = in_gaps & (ins_len >= 2)
    b1 = sel_q(0)
    w1 = sel_w(0)
    ins1_oh = _onehot(jnp.where(has1, b1, NBASE), NBASE + 1,
                      bf16)[..., :NBASE]
    ins1_w_ch = ins1_oh * jnp.where(has1, w1, 0.0)[..., None].astype(bf16)
    ins1_c_ch = ins1_oh * has1[..., None].astype(bf16)
    ins1_stop = jnp.where(has1, w1, 0.0).astype(bf16)

    # Pileup columns k = 0..K-1 for multi-base runs (no gathers).
    pk_w, pk_c = [], []
    for k in range(K_INS):
        inrun = multi & (ins_len > k)
        oh = _onehot(jnp.where(inrun, sel_q(k), NBASE), NBASE + 1,
                     bf16)[..., :NBASE]
        pk_w.append(oh * jnp.where(inrun, sel_w(k), 0.0)[..., None]
                    .astype(bf16))
        pk_c.append(oh * inrun[..., None].astype(bf16))
    pile_w_ch = jnp.stack(pk_w, axis=2)               # [B, LA+1, K, 5]
    pile_c_ch = jnp.stack(pk_c, axis=2)

    # Run mean weight -> stop-weight by run length (lengths 2..K); the
    # full run weight sum comes from the same window (runs past K_INS
    # never reach here — the walk's sat flag reroutes them).
    run_sum = sum(jnp.where(ins_len > k, sel_w(k), 0.0)
                  for k in range(K_INS))
    wmean = jnp.where(multi, run_sum / jnp.maximum(ins_len, 1), 0.0)
    lw_oh = (jnp.clip(ins_len, 0, K_INS)[..., None] ==
             jnp.arange(2, K_INS + 1)[None, None, :])
    lenw_ch = lw_oh * (wmean * multi)[..., None]      # [B, LA+1, K-1]

    return {
        "col_w": col_w_ch, "col_c": col_c_ch,
        "cross_w": cross_w[..., None],
        "ins1_w": ins1_w_ch, "ins1_c": ins1_c_ch,
        "ins1_stop": ins1_stop[..., None],
        "pile_w": pile_w_ch.reshape(B, LA + 1, -1),
        "pile_c": pile_c_ch.reshape(B, LA + 1, -1),
        "lenw": lenw_ch,
    }


def aggregate_votes(votes, win, n_win: int, extras=None):
    """Sum per-job channels into per-window accumulators via one-hot
    matmul. ``extras``: optional dict of per-job [B] scalars summed per
    window with the same membership matrix (returned under their keys).

    Channels arriving in bfloat16 aggregate through a DEFAULT-precision
    bf16 matmul with f32 accumulation — EXACT for their values, which
    are one-hot 0/1 counts and integer Phred weights <= 126 (both
    representable in bf16; MXU accumulation is f32 and per-window sums
    stay far below 2^24) — at a fraction of the HIGHEST-precision f32
    matmul the fractional channels (w_read-derived crossings, run-mean
    length weights) still require. extract_votes_cols emits the integer
    channels as bf16 for this reason; the all-f32 legacy extract_votes
    path just lands every channel in the f32 group.
    """
    B = win.shape[0]
    M = (jnp.arange(n_win, dtype=jnp.int32)[:, None] ==
         win[None, :])                                # [Nw, B] bool
    M32 = M.astype(jnp.float32)
    M16 = M.astype(jnp.bfloat16)

    def agg(xs):
        """Concatenated matmul per dtype group; returns [Nw, L, C_total]
        in the order of ``xs``."""
        groups = {}
        for x in xs:
            groups.setdefault(x.dtype == jnp.bfloat16, []).append(x)
        outs = {}
        for is16, grp in groups.items():
            flat = jnp.concatenate(grp, axis=-1).reshape(B, -1)
            Lc = flat.shape[1] // grp[0].shape[1]
            if is16:
                o = jnp.matmul(M16, flat,
                               preferred_element_type=jnp.float32)
            else:
                o = jnp.matmul(M32, flat, precision=_PREC)
            outs[is16] = iter(jnp.split(
                o.reshape(n_win, grp[0].shape[1], Lc),
                np.cumsum([g.shape[-1] for g in grp])[:-1], axis=-1))
        return jnp.concatenate(
            [next(outs[x.dtype == jnp.bfloat16]) for x in xs], axis=-1)

    col = agg([votes["col_w"], votes["col_c"]])
    gap = agg([votes["cross_w"], votes["ins1_w"], votes["ins1_c"],
               votes["ins1_stop"], votes["pile_w"], votes["pile_c"],
               votes["lenw"]])
    out = {}
    if extras:
        for k, v in extras.items():
            out[k] = jnp.matmul(M32, v[:, None], precision=_PREC)[:, 0]
    out["base_w"] = col[..., :NBASE + 1]              # [Nw, LA, 6] (5=del)
    out["base_c"] = col[..., NBASE + 1:]              # [Nw, LA, 5]
    i = 0
    out["direct_w"] = gap[..., i]; i += 1
    out["ins1_w"] = gap[..., i:i + NBASE]; i += NBASE
    out["ins1_c"] = gap[..., i:i + NBASE]; i += NBASE
    out["ins1_stop"] = gap[..., i]; i += 1
    out["pile_w"] = gap[..., i:i + K_INS * NBASE].reshape(
        gap.shape[0], gap.shape[1], K_INS, NBASE); i += K_INS * NBASE
    out["pile_c"] = gap[..., i:i + K_INS * NBASE].reshape(
        gap.shape[0], gap.shape[1], K_INS, NBASE); i += K_INS * NBASE
    out["lenw"] = gap[..., i:i + K_INS - 1]; i += K_INS - 1
    return out


def aggregate_flags(flags, win, n_win: int):
    """Per-window sums of one per-job scalar via the same membership
    matmul aggregate_votes rides ([Nw, B] @ [B, 1] — one MXU pass, the
    "cheap reduction appended to the merge step" of the convergence
    scheduler). Exact for 0/1 flags (f32 sums far below 2^24)."""
    M32 = (jnp.arange(n_win, dtype=jnp.int32)[:, None] ==
           win[None, :]).astype(jnp.float32)
    return jnp.matmul(M32, flags[:, None].astype(jnp.float32),
                      precision=_PREC)[:, 0]


def converged_windows(codes, total, bb_old, alen_old, wchg):
    """Per-window fixed-point predicate for the convergence scheduler.

    A window is converged when this round reproduced its own input
    anchor exactly — same length, same code bytes (both arrays are
    zero-padded past their lengths, so full-row equality composes with
    the length check), and no lane span moved through the coordinate
    maps (``wchg``: per-window sum of lane span-change flags; a
    consensus can match byte-for-byte while deletions and insertions
    offset each other and still shift spans, so byte equality alone is
    NOT a fixed point). Only meaningful from round 1 on: the round-0
    anchor carries backbone quality weights, later anchors re-vote with
    neutral weights, so round 0's input is not a replayable state.
    """
    return (total == alen_old) & (wchg == 0) & \
        jnp.all(codes == bb_old, axis=1)


def add_backbone(acc, bb, bbw, alen):
    """Fold the backbone's votes in (sequence 0, epsilon tie-break)."""
    Nw, LA = bb.shape
    p = jnp.arange(LA, dtype=jnp.int32)[None, :]
    vcol = p < alen[:, None]
    oh = _onehot(bb.astype(jnp.int32), NBASE + 1)[..., :NBASE]
    acc["base_w"] = acc["base_w"].at[..., :NBASE].add(
        oh * (jnp.where(vcol, bbw + EPS, 0.0))[..., None])
    acc["base_c"] = acc["base_c"] + oh * vcol[..., None]
    bw0 = bbw[:, :1]
    bwl = _take1(bbw, jnp.maximum(alen - 1, 0)[:, None])
    left = jnp.concatenate([bw0, bbw], axis=1)        # bw[p-1], bw[0] at p=0
    right = jnp.concatenate([bbw, bwl], axis=1)       # bw[p], bw[L-1] at p=L
    # Right operand must be bw[alen-1] at p == alen (anchors are padded).
    pg = jnp.arange(LA + 1, dtype=jnp.int32)[None, :]
    right = jnp.where(pg == alen[:, None], bwl, right)
    left = jnp.where(pg == alen[:, None], bwl, left)
    vgap = pg <= alen[:, None]
    cross = 0.5 * (left + right)
    acc["direct_w"] = acc["direct_w"] + jnp.where(vgap, cross + EPS, 0.0)
    return acc


def assemble(acc, alen, ins_scale: float):
    """Vote out consensus into a per-gap prefix layout + coordinate maps.

    Emission at a gap stops permanently at the first pileup column that
    loses to the stopped weight, so a gap's emitted insertion slots are
    always a PREFIX of its K_INS columns; the layout is therefore fully
    described by a per-gap emit count — no (LA+1)*(K+1) flat slot cumsum
    needed (the former slot layout's searchsorted compaction was the
    round's tail cost at K_INS = 10).

    Returns dict with:
      ins_codes i32 [Nw, LA+1, K] pileup winner codes
      ins_cnt   i32 [Nw, LA+1, K] their coverage counts
      e         i32 [Nw, LA+1] emitted insertion count per gap
      col_code  i32 [Nw, LA] column winner code
      col_cov   i32 [Nw, LA]
      start     i32 [Nw, LA+1] output position of gap p's first slot
      total     i32 [Nw] new consensus lengths
      pos       i32 [Nw, LA] landing position of each kept column
      kept      bool [Nw, LA]
    """
    base_w, base_c = acc["base_w"], acc["base_c"]
    Nw, LA, _ = base_c.shape
    p = jnp.arange(LA, dtype=jnp.int32)[None, :]
    vcol = p < alen[:, None]
    pg = jnp.arange(LA + 1, dtype=jnp.int32)[None, :]
    vgap = pg <= alen[:, None]

    best_code = jnp.argmax(base_w[..., :NBASE], axis=-1)
    best_w = jnp.take_along_axis(base_w[..., :NBASE], best_code[..., None],
                                 axis=-1)[..., 0]
    del_w = base_w[..., NBASE]
    kept = vcol & (del_w <= best_w)
    cov = jnp.take_along_axis(base_c, best_code[..., None], axis=-1)[..., 0]

    # Gap emission: K sequential pileup columns (col 0 folds single runs).
    stopped = acc["direct_w"] * ins_scale
    emit_prev = vgap
    ins_codes, ins_cnt = [], []
    e = jnp.zeros((Nw, LA + 1), jnp.int32)
    for k in range(K_INS):
        cw = acc["pile_w"][:, :, k, :]
        cc = acc["pile_c"][:, :, k, :]
        if k == 0:
            cw = cw + acc["ins1_w"]
            cc = cc + acc["ins1_c"]
        tot = jnp.sum(cw, axis=-1)
        em = emit_prev & (tot > stopped)
        bk = jnp.argmax(cw, axis=-1)
        ck = jnp.take_along_axis(cc, bk[..., None], axis=-1)[..., 0]
        ins_codes.append(bk)
        ins_cnt.append(ck.astype(jnp.int32))
        e = e + em
        emit_prev = em
        # stopped += len_w[k+1] (+ single-run stops after column 0)
        if k == 0:
            stopped = stopped + acc["ins1_stop"]
        if k + 1 >= 2 and (k + 1) - 2 < acc["lenw"].shape[-1]:
            stopped = stopped + acc["lenw"][..., (k + 1) - 2]

    ins_codes = jnp.stack(ins_codes, axis=2)          # [Nw, LA+1, K]
    ins_cnt = jnp.stack(ins_cnt, axis=2)

    # Unit p = gap p's emitted insertions, then column p (absent at LA).
    ulen = e + jnp.concatenate(
        [kept.astype(jnp.int32), jnp.zeros((Nw, 1), jnp.int32)], axis=1)
    cum_u = jnp.cumsum(ulen, axis=1, dtype=jnp.int32)
    start = cum_u - ulen                              # exclusive cumsum
    total = cum_u[:, -1]
    pos = start[:, :LA] + e[:, :LA]                   # column p's landing

    return {
        "ins_codes": ins_codes,
        "ins_cnt": ins_cnt,
        "e": e,
        "col_code": best_code.astype(jnp.int32),
        "col_cov": cov.astype(jnp.int32),
        "start": start,
        "total": total,
        "pos": pos,
        "kept": kept,
    }


def compact(asm, out_len: int):
    """Gather-based stream compaction of the per-gap prefix layout.

    For output position j: its unit g = #{p : start[p] <= j} - 1 (start
    is monotone), offset o = j - start[g]; the emitted symbol is pileup
    column o of gap g while o < e[g], else column g's winner.

    Returns (codes u8 [Nw, out_len], cov i32 [Nw, out_len], total i32[Nw]).
    Positions beyond ``total`` hold code 0 / cov 0.
    """
    start, e, total = asm["start"], asm["e"], asm["total"]
    Nw, LA1 = start.shape
    jj = jnp.arange(out_len, dtype=jnp.int32)
    # Count-leq over the monotone starts (the only O(LA^2) op left; it
    # replaces the former count over (LA+1)*(K+1) slots).
    g = jnp.sum(start[:, :, None] <= jj[None, None, :], axis=1,
                dtype=jnp.int32) - 1
    off = jj[None, :] - _take1(start, g)
    eg = _take1(e, g)
    is_ins = off < eg
    K = asm["ins_codes"].shape[2]
    flat_i = g * K + jnp.minimum(off, K - 1)
    ins_code = _take1(asm["ins_codes"].reshape(Nw, LA1 * K), flat_i)
    ins_cov = _take1(asm["ins_cnt"].reshape(Nw, LA1 * K), flat_i)
    gc = jnp.minimum(g, LA1 - 2)                      # column g (g < LA)
    col_code = _take1(asm["col_code"], gc)
    col_cov = _take1(asm["col_cov"], gc)
    live = jj[None, :] < total[:, None]
    codes = jnp.where(live, jnp.where(is_ins, ins_code, col_code), 0)
    cov = jnp.where(live, jnp.where(is_ins, ins_cov, col_cov), 0)
    return codes.astype(jnp.uint8), cov, total


def coord_maps(asm, alen, LA: int):
    """map_b / map_e: for every old-anchor position, the landing position of
    the nearest kept column at-or-after / at-or-before it (falling back to
    the last / first kept column, 0 when none are kept) — the coordinate
    maps refinement rounds use to re-slice layer spans."""
    kept, pos = asm["kept"], asm["pos"]
    Nw = kept.shape[0]
    posk = jnp.where(kept, pos, _HI)
    # reverse cummin
    map_b = jnp.flip(jax.lax.cummin(jnp.flip(posk, axis=1), axis=1), axis=1)
    posk2 = jnp.where(kept, pos, -_HI)
    map_e = jax.lax.cummax(posk2, axis=1)
    any_kept = jnp.any(kept, axis=1, keepdims=True)
    last_kept = jnp.max(jnp.where(kept, pos, -_HI), axis=1, keepdims=True)
    first_kept = jnp.min(jnp.where(kept, pos, _HI), axis=1, keepdims=True)
    map_b = jnp.where(map_b == _HI, last_kept, map_b)
    map_e = jnp.where(map_e == -_HI, first_kept, map_e)
    map_b = jnp.where(any_kept, map_b, 0)
    map_e = jnp.where(any_kept, map_e, 0)
    hi = jnp.maximum(asm["total"][:, None] - 1, 0)
    map_b = jnp.clip(map_b, 0, hi)
    map_e = jnp.clip(map_e, 0, hi)
    return map_b.astype(jnp.int32), map_e.astype(jnp.int32)
