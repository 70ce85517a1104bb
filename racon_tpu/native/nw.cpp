// Banded Needleman-Wunsch global aligner with traceback.
//
// Native (host) replacement for the reference's edlib dependency: racon
// calls edlibAlign(..., EDLIB_MODE_NW, EDLIB_TASK_PATH) once per PAF/MHAP
// overlap to recover a CIGAR (reference: src/overlap.cpp:198-213). Overlap
// spans reach tens of kilobases, so the full O(Lq*Lt) matrix is avoided
// with a diagonal band that doubles until the optimal path stays strictly
// inside it (the same adaptive-band idea edlib uses); a band covering the
// whole matrix is exact plain NW, so the loop always terminates with an
// optimal alignment.
//
// Semantics are kept identical to the JAX device kernel
// (racon_tpu/ops/align.py): linear gap, int32 scores, tie preference
// DIAG > UP > LEFT, op encoding 0=M (diag), 1=I (up, consumes query),
// 2=D (left, consumes target).
//
// Band coordinates: k = j - i, band k in [klo, khi], column b = k - klo.
// Moving to row i+1: diag neighbour keeps b, up neighbour is b+1 in the
// previous row, left neighbour is b-1 in the same row.
//
// Two passes compute the same band: the scalar row pass (band_pass) and
// a vector pass over anti-diagonals (diag_pass, x86 with SSE4.1 or
// AVX2) that keeps 8-bit score differences instead of scores. Both give
// every band cell the same score, so the same directions, ops and final
// score; the choice between them rests on the scoring and the band width
// alone (use_diag_pass).

#include <atomic>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>
#include <algorithm>
#include <memory>

#if defined(__AVX2__) || defined(__SSE4_1__)
#include <immintrin.h>
#endif

namespace {

constexpr int32_t kNegInf = INT32_MIN / 4;
enum Dir : uint8_t { kDiag = 0, kUp = 1, kLeft = 2 };

struct BandResult {
    int32_t n_ops = -1;
    int32_t score = kNegInf;
};

// One banded pass. ops_out is filled back-to-front and left in
// start->end order on return.
BandResult band_pass(const uint8_t* q, int32_t lq, const uint8_t* t,
                     int32_t lt, int32_t m, int32_t x, int32_t g,
                     int32_t klo, int32_t khi, uint8_t* ops_out) {
    BandResult res;
    const int32_t bandw = khi - klo + 1;

    std::vector<uint8_t> dirs(static_cast<size_t>(lq + 1) * bandw);
    std::vector<int32_t> prev(bandw + 1, kNegInf), cur(bandw + 1, kNegInf);
    // prev/cur have one sentinel slot at the end so the up-neighbour read
    // prev[b + 1] is always in range.

    // Row 0: H[0][j] = j*g for j in [max(0, klo), min(lt, khi)].
    {
        const int32_t jlo = std::max(0, klo), jhi = std::min(lt, khi);
        for (int32_t j = jlo; j <= jhi; ++j) {
            prev[j - klo] = j * g;
        }
    }

    for (int32_t i = 1; i <= lq; ++i) {
        const int32_t jlo = std::max(0, i + klo);
        const int32_t jhi = std::min(lt, i + khi);
        if (jlo > jhi) return res;  // band fell off the matrix
        const uint8_t qc = q[i - 1];
        uint8_t* drow = dirs.data() + static_cast<size_t>(i) * bandw;

        int32_t blo = jlo - i - klo;
        const int32_t bhi = jhi - i - klo;
        // j = 0 boundary handled outside the hot loops.
        if (jlo == 0) {
            cur[blo] = i * g;
            drow[blo] = kUp;
            ++blo;
        }
        // Branchless vectorizable phase: tmp = max(diag, up). For b in
        // [blo, bhi], j = i + klo + b >= 1, so t[j-1] = tj[b] is in range.
        const uint8_t* tj = t + (i + klo - 1);
        const int32_t* pv = prev.data();
        int32_t* cu = cur.data();
        for (int32_t b = blo; b <= bhi; ++b) {
            const int32_t sub = tj[b] == qc ? m : x;
            const int32_t diag = pv[b] + sub;
            const int32_t up = pv[b + 1] + g;
            cu[b] = diag > up ? diag : up;
        }
        // Serial phase: fold in the left-gap chain and label directions.
        int32_t left = (jlo == 0) ? cur[blo - 1] : kNegInf;
        for (int32_t b = blo; b <= bhi; ++b) {
            const int32_t diag = pv[b] + (tj[b] == qc ? m : x);
            int32_t h = cu[b];
            if (left + g > h) h = left + g;
            cu[b] = h;
            left = h;
            drow[b] = (h == diag) ? kDiag
                                  : (h == pv[b + 1] + g ? kUp : kLeft);
        }
        // Sentinels outside the valid window (the next row reads one slot
        // past each side; a full fill per row is wasted bandwidth).
        if (blo - 1 >= 0 && jlo != 0) cur[blo - 1] = kNegInf;
        if (blo - 2 >= 0) cur[blo - 2] = kNegInf;
        if (bhi + 1 < bandw + 1) cur[bhi + 1] = kNegInf;
        std::swap(prev, cur);
    }

    const int32_t bend = lt - lq - klo;
    if (bend < 0 || bend >= bandw) return res;
    res.score = prev[bend];
    if (res.score <= kNegInf / 2) return res;

    // Traceback from (lq, lt).
    int32_t i = lq, j = lt, pos = lq + lt;
    while (i > 0 || j > 0) {
        uint8_t d;
        if (i == 0) {
            d = kLeft;
        } else if (j == 0) {
            d = kUp;
        } else {
            const int32_t b = j - i - klo;
            if (b < 0 || b >= bandw) return res;  // should not happen
            d = dirs[static_cast<size_t>(i) * bandw + b];
        }
        ops_out[--pos] = d;
        if (d != kLeft) --i;
        if (d != kUp) --j;
    }
    res.n_ops = lq + lt - pos;
    if (pos > 0) {
        std::memmove(ops_out, ops_out + pos, res.n_ops);
    }
    return res;
}

// Number of band cells (i, j), 0 <= i <= lq, 0 <= j <= lt, that one pass
// over [klo, khi] computes.
int64_t band_cells(int32_t lq, int32_t lt, int32_t klo, int32_t khi) {
    int64_t n = 0;
    for (int32_t i = 0; i <= lq; ++i) {
        const int32_t w = std::min(lt, i + khi) - std::max(0, i + klo) + 1;
        if (w > 0) n += w;
    }
    return n;
}

#if defined(__AVX2__) || defined(__SSE4_1__)

#if defined(__AVX2__)
struct Lanes {
    using V = __m256i;
    using Mask = uint32_t;
    static constexpr int32_t kN = 32;
    static V load(const int8_t* p) {
        return _mm256_loadu_si256(reinterpret_cast<const V*>(p));
    }
    static void store(int8_t* p, V v) {
        _mm256_storeu_si256(reinterpret_cast<V*>(p), v);
    }
    static V set1(int8_t a) { return _mm256_set1_epi8(a); }
    static V eq(V a, V b) { return _mm256_cmpeq_epi8(a, b); }
    static V pick(V no, V yes, V m) { return _mm256_blendv_epi8(no, yes, m); }
    static V max(V a, V b) { return _mm256_max_epi8(a, b); }
    static V sub(V a, V b) { return _mm256_sub_epi8(a, b); }
    static Mask mask(V a) {
        return static_cast<Mask>(_mm256_movemask_epi8(a));
    }
};
#else
struct Lanes {
    using V = __m128i;
    using Mask = uint16_t;
    static constexpr int32_t kN = 16;
    static V load(const int8_t* p) {
        return _mm_loadu_si128(reinterpret_cast<const V*>(p));
    }
    static void store(int8_t* p, V v) {
        _mm_storeu_si128(reinterpret_cast<V*>(p), v);
    }
    static V set1(int8_t a) { return _mm_set1_epi8(a); }
    static V eq(V a, V b) { return _mm_cmpeq_epi8(a, b); }
    static V pick(V no, V yes, V m) { return _mm_blendv_epi8(no, yes, m); }
    static V max(V a, V b) { return _mm_max_epi8(a, b); }
    static V sub(V a, V b) { return _mm_sub_epi8(a, b); }
    static Mask mask(V a) { return static_cast<Mask>(_mm_movemask_epi8(a)); }
};
#endif

// Narrowest band (khi - klo + 1) the vector pass takes. Per band cell
// on a Xeon with AVX-512 (AVX2 and SSE4.1 builds alike), 1.5-6 kb pairs:
// the vector pass is 0.6-0.7x the scalar one at 3 cells, 0.7-0.9x at 5,
// 1.2-1.3x at 7, 1.6-1.8x at 9 and 20-28x at the default first band of
// 257 cells, where it takes 0.2-0.3 ns a cell.
constexpr int32_t kMinDiagBand = 7;

int32_t floor_half(int32_t a) { return a >= 0 ? a / 2 : -((1 - a) / 2); }

// The score differences of a band cell, u = H(i,j) - H(i-1,j) and
// v = H(i,j) - H(i,j-1), lie in [g, max(smax - g, g)] for every cell
// whose neighbours are in the band. Stored as u - g and v - g they fit
// signed 8-bit lanes when max(smax - 2g, 0) <= 127 and smin - 2g >= -127,
// which leaves -128 free to mark a neighbour outside the band.
bool diag_scoring_fits(int32_t m, int32_t x, int32_t g) {
    const int64_t smax = std::max(m, x), smin = std::min(m, x);
    return smax - 2 * static_cast<int64_t>(g) <= 127 &&
           smin - 2 * static_cast<int64_t>(g) >= -127;
}

bool use_diag_pass(int32_t m, int32_t x, int32_t g, int32_t klo,
                   int32_t khi) {
    return khi - klo + 1 >= kMinDiagBand && diag_scoring_fits(m, x, g);
}

// The band of band_pass swept by anti-diagonals r = i + j, Suzuki &
// Kasahara's difference recurrence (BMC Bioinformatics 19:45, 2018; the
// form of minimap2's ksw2_extz2_sse) for a linear gap. With
// z = H(i,j) - H(i-1,j-1), each cell needs only the differences of its
// up and left neighbours, both on anti-diagonal r-1:
//   z = max(s(i,j), v(i-1,j) + g, u(i,j-1) + g)
//   u(i,j) = z - v(i-1,j),  v(i,j) = z - u(i,j-1)
// and the cells of one anti-diagonal are independent, so a register
// holds kN consecutive i. Lanes hold u - g, v - g and z - 2g; a
// neighbour outside the band holds -128 and never wins nor ties. The
// direction test is band_pass's (z == s, then up, then left), stored as
// two bit planes per block of kN lanes; the score is summed along the
// traceback, which follows an optimal path.
BandResult diag_pass(const uint8_t* q, int32_t lq, const uint8_t* t,
                     int32_t lt, int32_t m, int32_t x, int32_t g,
                     int32_t klo, int32_t khi, uint8_t* ops_out) {
    using L = Lanes;
    using Mask = L::Mask;
    constexpr int32_t kN = L::kN;
    constexpr int8_t kAbsent = -128;
    BandResult res;
    if (lt - lq < klo || lt - lq > khi) return res;
    const int32_t R = lq + lt;
    // Cells of anti-diagonal r: i in [a, b]; interior (i, j >= 1) in
    // [max(a, 1), min(b, r - 1)].
    auto first_i = [&](int32_t r) {
        return std::max({0, r - lt, -floor_half(khi - r)});
    };
    auto last_i = [&](int32_t r) {
        return std::min({lq, r, floor_half(r - klo)});
    };
    const int32_t width = (khi - klo) / 2 + 1;
    const int64_t stride = width / kN + 2;  // blocks per anti-diagonal
    std::unique_ptr<Mask[]> planes(new Mask[(R + 1) * stride * 2]);

    // q[i-1] at qp[i]; t[r-i-1] at tp[lt - r + i]; padding covers the
    // lanes of a block that fall outside the anti-diagonal.
    std::vector<int8_t> qbuf(lq + kN + 1, -1), tbuf(lt + 2 * kN, -1);
    for (int32_t i = 0; i < lq; ++i) qbuf[i + 1] = static_cast<int8_t>(q[i]);
    for (int32_t k = 0; k < lt; ++k) {
        tbuf[kN + k] = static_cast<int8_t>(t[lt - 1 - k]);
    }
    const int8_t* qp = qbuf.data();
    const int8_t* tp = tbuf.data() + kN;
    // u and v of two anti-diagonals, indexed by i, from i = -1.
    const size_t n_uv = lq + kN + 2;
    std::vector<int8_t> uv(4 * n_uv, kAbsent);
    int8_t* U[2] = {uv.data() + 1, uv.data() + n_uv + 1};
    int8_t* V[2] = {uv.data() + 2 * n_uv + 1, uv.data() + 3 * n_uv + 1};

    const L::V ms = L::set1(static_cast<int8_t>(m - 2 * g));
    const L::V xs = L::set1(static_cast<int8_t>(x - 2 * g));
    for (int32_t r = 1; r <= R; ++r) {
        const int8_t* up_in = V[(r - 1) & 1];
        const int8_t* left_in = U[(r - 1) & 1];
        int8_t* u_out = U[r & 1];
        int8_t* v_out = V[r & 1];
        const int32_t a = first_i(r), b = last_i(r);
        const int32_t lo = std::max(a, 1), hi = std::min(b, r - 1);
        if (lo <= hi) {
            const int32_t blk0 = lo / kN;
            Mask* pl = planes.get() + static_cast<int64_t>(r) * stride * 2;
            const int8_t* tr = tp + (lt - r);
            for (int32_t i0 = blk0 * kN; i0 <= hi; i0 += kN, pl += 2) {
                const auto s = L::pick(xs, ms, L::eq(L::load(qp + i0),
                                                     L::load(tr + i0)));
                const auto up = L::load(up_in + i0 - 1);
                const auto left = L::load(left_in + i0);
                const auto z = L::max(s, L::max(up, left));
                L::store(u_out + i0, L::sub(z, up));
                L::store(v_out + i0, L::sub(z, left));
                const Mask is_diag = L::mask(L::eq(z, s));
                const Mask is_up = L::mask(L::eq(z, up));
                pl[0] = static_cast<Mask>(~is_diag & is_up);
                pl[1] = static_cast<Mask>(~is_diag & ~is_up);
            }
        }
        // Boundary cells (0, r) and (r, 0) step by one gap, and the
        // cells just outside [a, b] that anti-diagonal r + 1 reads are
        // outside the band.
        if (a == 0) v_out[0] = 0;
        if (b == r) u_out[r] = 0;
        if (a >= 1) v_out[a - 1] = kAbsent;
        u_out[b + 1] = kAbsent;
    }

    // Traceback from (lq, lt), as in band_pass.
    int32_t i = lq, j = lt, pos = lq + lt, score = 0;
    while (i > 0 || j > 0) {
        uint8_t d;
        if (i == 0) {
            d = kLeft;
        } else if (j == 0) {
            d = kUp;
        } else {
            const int32_t r = i + j;
            const int32_t lo = std::max(first_i(r), 1);
            const Mask* pl = planes.get() +
                             (static_cast<int64_t>(r) * stride +
                              (i / kN - lo / kN)) * 2;
            const int32_t lane = i % kN;
            d = static_cast<uint8_t>(((pl[0] >> lane) & 1) |
                                     (((pl[1] >> lane) & 1) << 1));
        }
        ops_out[--pos] = d;
        if (d == kDiag) {
            score += q[i - 1] == t[j - 1] ? m : x;
        } else {
            score += g;
        }
        if (d != kLeft) --i;
        if (d != kUp) --j;
    }
    res.n_ops = lq + lt - pos;
    res.score = score;
    if (pos > 0) {
        std::memmove(ops_out, ops_out + pos, res.n_ops);
    }
    return res;
}

#else

bool use_diag_pass(int32_t, int32_t, int32_t, int32_t, int32_t) {
    return false;
}

#endif

// One pass over [klo, khi] by whichever pass fits; cells (optional)
// gains the pass's band cells in [0] and, where the vector pass ran, in
// [1] too.
BandResult run_pass(const uint8_t* q, int32_t lq, const uint8_t* t,
                    int32_t lt, int32_t m, int32_t x, int32_t g,
                    int32_t klo, int32_t khi, uint8_t* ops_out,
                    int64_t* cells) {
    const bool vec = use_diag_pass(m, x, g, klo, khi);
    if (cells) {
        const int64_t n = band_cells(lq, lt, klo, khi);
        cells[0] += n;
        if (vec) cells[1] += n;
    }
#if defined(__AVX2__) || defined(__SSE4_1__)
    if (vec) return diag_pass(q, lq, t, lt, m, x, g, klo, khi, ops_out);
#endif
    return band_pass(q, lq, t, lt, m, x, g, klo, khi, ops_out);
}

// racon_nw_align, with cells (optional) gaining the band cells of
// every pass in [0] and those of the vector passes in [1].
int32_t align(const uint8_t* q, int32_t lq, const uint8_t* t, int32_t lt,
              int32_t m, int32_t x, int32_t g, int32_t band0,
              uint8_t* ops_out, int32_t* score_out, int64_t* cells) {
    if (lq < 0 || lt < 0) return -1;
    if (lq == 0) {
        std::memset(ops_out, kLeft, lt);
        if (score_out) *score_out = lt * g;
        return lt;
    }
    if (lt == 0) {
        std::memset(ops_out, kUp, lq);
        if (score_out) *score_out = lq * g;
        return lq;
    }

    int32_t w = band0 > 0 ? band0
                          : std::max<int32_t>(128, std::abs(lt - lq) + 64);
    // The escape bound below needs g < 0 (it divides by -g, and with
    // free gaps no banded score can ever prove exactness): g >= 0 runs
    // the full matrix directly.
    if (g >= 0) w = std::max(lq, lt);
    // Acceptance is a *provable* escape bound (Ukkonen banding
    // generalized to match-bonus scoring), not the untouched-edge
    // heuristic: a balanced long insertion+deletion can route the
    // optimal path outside the band while a sub-optimal in-band path
    // never touches the edge (ADVICE r2 #1; edlib is exact).
    //   Any path leaving the band [min(0,d)-w, max(0,d)+w] (d = lt-lq)
    //   needs >= |d| + 2(w+1) gap ops (reach the edge + return), and has
    //   at most min(lq,lt) matches, so it scores at most
    //     max(m,0)*min(lq,lt) + g*(|d| + 2w + 2).
    //   A banded score >= that bound therefore beats every escaping
    //   path, and the in-band DP is exact over in-band paths.
    // Typical polishing alignments accept on the first pass; the loop
    // terminates at the full matrix regardless.
    const int64_t dgap = std::abs(lt - lq);
    const int64_t mmax = static_cast<int64_t>(std::max(m, 0)) *
                         std::min(lq, lt);
    while (true) {
        const int32_t klo = std::max(std::min(0, lt - lq) - w, -lq);
        const int32_t khi = std::min(std::max(0, lt - lq) + w, lt);
        BandResult res =
            run_pass(q, lq, t, lt, m, x, g, klo, khi, ops_out, cells);
        if (klo <= -lq && khi >= lt) {
            // Full matrix — exact.
            if (res.n_ops >= 0) {
                if (score_out) *score_out = res.score;
                return res.n_ops;
            }
            return -1;
        }
        if (res.n_ops >= 0) {
            const int64_t escape =
                mmax + static_cast<int64_t>(g) * (dgap + 2 * w + 2);
            if (static_cast<int64_t>(res.score) >= escape) {
                if (score_out) *score_out = res.score;
                return res.n_ops;
            }
            // Jump straight to a width whose escape bound the current
            // (lower-bound) score already beats: the banded score only
            // improves as the band widens, so the next pass is
            // guaranteed to accept. Two passes total instead of a
            // doubling ladder.
            const int64_t n_g = (mmax - res.score + (-g) - 1) / (-g);
            const int64_t w_need = (n_g - dgap) / 2 + 1;
            w = static_cast<int32_t>(
                std::min<int64_t>(std::max<int64_t>(2 * w, w_need),
                                  std::max(lq, lt)));
        } else {
            w *= 2;
        }
    }
}

}  // namespace

extern "C" {

// Globally align q vs t; writes ops (0=M,1=I,2=D) into ops_out (capacity
// lq + lt). Returns the op count, or -1 on failure. band0 <= 0 selects an
// automatic initial half-width. score_out (optional) receives the score.
int32_t racon_nw_align(const uint8_t* q, int32_t lq, const uint8_t* t,
                       int32_t lt, int32_t m, int32_t x, int32_t g,
                       int32_t band0, uint8_t* ops_out, int32_t* score_out) {
    return align(q, lq, t, lt, m, x, g, band0, ops_out, score_out, nullptr);
}

// Lanes of the vector pass as built (32 with AVX2, 16 with SSE4.1), or 0
// where only the scalar pass exists.
int32_t racon_nw_vector_lanes() {
#if defined(__AVX2__) || defined(__SSE4_1__)
    return Lanes::kN;
#else
    return 0;
#endif
}

// Batched form over flat buffers. ops_off[i] must leave q_len[i]+t_len[i]
// capacity per record; ops_len[i] receives each op count (-1 on failure).
// Records fan out over n_threads OS threads (<=0 selects the hardware
// concurrency), the host analogue of the reference's per-overlap thread
// pool (src/polisher.cpp:351-364). cells_out (optional, 2 per record)
// receives each record's band cells over all its passes and those of
// them the vector pass computed. Returns 0 on success, first failing
// index + 1 otherwise.
int32_t racon_nw_align_batch(const uint8_t* q, const int64_t* q_off,
                             const int32_t* q_len, const uint8_t* t,
                             const int64_t* t_off, const int32_t* t_len,
                             int32_t n, int32_t m, int32_t x, int32_t g,
                             int32_t band0, int32_t n_threads,
                             uint8_t* ops_out,
                             const int64_t* ops_off, int32_t* ops_len,
                             int64_t* cells_out) {
    if (n_threads <= 0) {
        n_threads = static_cast<int32_t>(std::thread::hardware_concurrency());
        if (n_threads <= 0) n_threads = 1;
    }
    n_threads = std::min(n_threads, n);
    std::atomic<int32_t> next(0), rc(0);
    auto worker = [&]() {
        while (true) {
            const int32_t i = next.fetch_add(1);
            if (i >= n) return;
            int64_t* cells = nullptr;
            if (cells_out) {
                cells = cells_out + 2 * static_cast<int64_t>(i);
                cells[0] = cells[1] = 0;
            }
            ops_len[i] = align(q + q_off[i], q_len[i], t + t_off[i],
                               t_len[i], m, x, g, band0,
                               ops_out + ops_off[i], nullptr, cells);
            if (ops_len[i] < 0) {
                int32_t cur = rc.load();
                while ((cur == 0 || i + 1 < cur) &&
                       !rc.compare_exchange_weak(cur, i + 1)) {
                }
            }
        }
    };
    if (n_threads <= 1) {
        worker();
    } else {
        std::vector<std::thread> pool;
        pool.reserve(n_threads);
        for (int32_t k = 0; k < n_threads; ++k) pool.emplace_back(worker);
        for (auto& th : pool) th.join();
    }
    return rc.load();
}

}  // extern "C"
