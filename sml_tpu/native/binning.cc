// Host-side histogram-binning kernel (tree engine hot path).
//
// The reference's tree learners discretize on JVM executors
// (Spark ML findSplits, `SML/ML 06 - Decision Trees.py:98-118`); here the
// SEARCH of every value of a block of rows against its feature's quantile
// edges — the expensive part of make_bins/bin_with at 1M rows — is a C++
// kernel. Semantics mirror the NumPy path exactly: searchsorted(edges, x,
// 'left') for finite x, bin 0 for any non-finite value, and for a
// categorical slot rank[clip(int64(x), 0, card - 1)] (tree_impl
// ._bin_rows_numpy). A value equal to the fit's `missing` is read as NaN
// in either kind of slot (a NaN `missing` equals nothing: there is none).
//
// One call bins ROWS [0, n) of the matrix it is handed, all F columns,
// and writes them once, as contiguous rows of the result in its final
// dtype. It starts no thread: the caller (tree_impl._bin_columns) gives
// each block of rows to one task of the process's pool, ctypes releases
// the interpreter lock for the call, and no two tasks store into one
// cache line.
//
// Built on demand by native/build.py (g++ -O3); callers fall back to the
// NumPy implementation when no compiler is available. Only the two entry
// points have C linkage (a template cannot).

#include <cstdint>
#include <cmath>
#include <vector>

// The bin of a finite x: how many of the feature's edges lie strictly left
// of it. An edge row is padded with +inf to a whole number of LANES-wide
// groups, so the count runs over whole groups: a handful of vector
// compares and no branch that depends on the data (a binary search over
// the same 63 edges mispredicts its way down and took six times as long).
// The comparison is made in the input's own type: two float32 compare as
// their doubles do.
constexpr int32_t LANES = 16;

template <typename T>
static inline int32_t edges_left_of(const float* edges, int32_t n_edges,
                                    T x) {
    int32_t count = 0;
    for (int32_t j = 0; j < n_edges; j += LANES)
        for (int32_t k = 0; k < LANES; ++k)
            count += static_cast<T>(edges[j + k]) < x;
    return count;
}

// NumPy's float -> int64 cast on x86-64 (cvttsd2si), then clip to
// [0, card): what int64 cannot hold (NaN, +-inf, |x| >= 2^63) casts to
// INT64_MIN and so clips to 0.
static inline int64_t category(double x, int64_t card) {
    if (!(x > -1.0)) return 0;             // NaN, negatives, -inf
    if (!(x < 9223372036854775808.0)) return 0;   // +inf, past int64
    const int64_t id = static_cast<int64_t>(x);   // truncates toward zero
    return id < card ? id : card - 1;
}

// X is any strided 2-D block (row_stride / col_stride in ELEMENTS: a
// row-major float32 block and a Fortran-ordered float64 one both come as
// they are). Feature f is continuous where cards[f] == 0, with edge row
// edges[f * max_edges .. + n_edges[f]], ascending, then +inf up to
// max_edges, a multiple of LANES; else categorical, with rank table
// ranks[rank_lo[f] .. + cards[f]]. `missing` is compared in X's own type,
// as NumPy's `X == missing` is.
template <typename T, typename O>
static void bin_rows_impl(const T* X, int64_t n, int32_t F,
                          int64_t row_stride, int64_t col_stride,
                          const float* edges, const int32_t* n_edges,
                          int32_t max_edges, const int32_t* ranks,
                          const int64_t* rank_lo, const int64_t* cards,
                          T missing, O* out) {
    for (int64_t i = 0; i < n; ++i) {
        const T* row = X + i * row_stride;
        O* dst = out + i * F;
        for (int32_t f = 0; f < F; ++f) {
            const T raw = row[f * col_stride];
            const T x = raw == missing ? static_cast<T>(NAN) : raw;
            if (cards[f] > 0) {
                dst[f] = static_cast<O>(ranks[rank_lo[f] + category(
                    static_cast<double>(x), cards[f])]);
            } else if (!std::isfinite(x)) {
                dst[f] = 0;   // NaN/±inf → lowest bin, as in make_bins
            } else {
                dst[f] = static_cast<O>(edges_left_of(
                    edges + (int64_t)f * max_edges, n_edges[f], x));
            }
        }
    }
}

template <typename T>
static int bin_rows_out(const T* X, int64_t n, int32_t F, int64_t row_stride,
                        int64_t col_stride, const float* edges,
                        const int32_t* n_edges, int32_t max_edges,
                        const int32_t* ranks, const int64_t* rank_lo,
                        const int64_t* cards, double missing, void* out,
                        int32_t out_bytes) {
    const T miss = static_cast<T>(missing);
    switch (out_bytes) {
    case 1:
        bin_rows_impl<T, uint8_t>(X, n, F, row_stride, col_stride, edges,
                                  n_edges, max_edges, ranks, rank_lo, cards,
                                  miss, static_cast<uint8_t*>(out));
        return 0;
    case 2:
        bin_rows_impl<T, uint16_t>(X, n, F, row_stride, col_stride, edges,
                                   n_edges, max_edges, ranks, rank_lo, cards,
                                   miss, static_cast<uint16_t*>(out));
        return 0;
    case 4:
        bin_rows_impl<T, int32_t>(X, n, F, row_stride, col_stride, edges,
                                  n_edges, max_edges, ranks, rank_lo, cards,
                                  miss, static_cast<int32_t*>(out));
        return 0;
    }
    return 1;
}

// The rows of one categorical column grouped by category, ONCE: counts[c]
// = rows of category c, and (where there are labels) y's values category
// by category, each category's in row order: a counting sort, so that
// `grouped[lo:hi].mean()` sums the values a masked `y[ids == c].mean()`
// sums, in its order.
template <typename T, typename Y>
static void group_labels_impl(const T* col, int64_t n, int64_t card,
                              const Y* y, int64_t* counts, Y* grouped) {
    for (int64_t c = 0; c < card; ++c) counts[c] = 0;
    for (int64_t i = 0; i < n; ++i)
        ++counts[category(static_cast<double>(col[i]), card)];
    if (y == nullptr) return;
    std::vector<int64_t> next(card);
    int64_t lo = 0;
    for (int64_t c = 0; c < card; ++c) { next[c] = lo; lo += counts[c]; }
    for (int64_t i = 0; i < n; ++i)
        grouped[next[category(static_cast<double>(col[i]), card)]++] = y[i];
}

template <typename T>
static int group_labels_y(const T* col, int64_t n, int64_t card,
                          const void* y, int32_t y_bytes, int64_t* counts,
                          void* grouped) {
    // a label is moved, never read: any 4- or 8-byte dtype
    if (y == nullptr || y_bytes == 4)
        group_labels_impl(col, n, card, static_cast<const uint32_t*>(y),
                          counts, static_cast<uint32_t*>(grouped));
    else if (y_bytes == 8)
        group_labels_impl(col, n, card, static_cast<const uint64_t*>(y),
                          counts, static_cast<uint64_t*>(grouped));
    else
        return 1;
    return 0;
}

extern "C" {

// col: n contiguous float32 (x_bytes 4) or float64 (8); y: n contiguous
// labels of y_bytes each, or null (the counts alone). Returns 0, or 1 for
// a width it has no instantiation of.
int group_labels(const void* col, int32_t x_bytes, int64_t n, int64_t card,
                 const void* y, int32_t y_bytes, int64_t* counts,
                 void* grouped) {
    if (x_bytes == 4)
        return group_labels_y(static_cast<const float*>(col), n, card, y,
                              y_bytes, counts, grouped);
    if (x_bytes == 8)
        return group_labels_y(static_cast<const double*>(col), n, card, y,
                              y_bytes, counts, grouped);
    return 1;
}

// x_bytes: 4 = float32, 8 = float64; out_bytes: 1 = uint8, 2 = uint16,
// 4 = int32 (tree_impl.bin_dtype's three); missing: the value read as NaN
// (NaN: none). Returns 0, or 1 for a width it has no instantiation of
// (nothing written).
int bin_rows(const void* X, int32_t x_bytes, int64_t n, int32_t F,
             int64_t row_stride, int64_t col_stride, const float* edges,
             const int32_t* n_edges, int32_t max_edges, const int32_t* ranks,
             const int64_t* rank_lo, const int64_t* cards, double missing,
             void* out, int32_t out_bytes) {
    if (x_bytes == 4)
        return bin_rows_out(static_cast<const float*>(X), n, F, row_stride,
                            col_stride, edges, n_edges, max_edges, ranks,
                            rank_lo, cards, missing, out, out_bytes);
    if (x_bytes == 8)
        return bin_rows_out(static_cast<const double*>(X), n, F, row_stride,
                            col_stride, edges, n_edges, max_edges, ranks,
                            rank_lo, cards, missing, out, out_bytes);
    return 1;
}

}  // extern "C"
