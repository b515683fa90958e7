// Host-side histogram-binning kernel (tree engine hot path).
//
// The reference's tree learners discretize on JVM executors
// (Spark ML findSplits, `SML/ML 06 - Decision Trees.py:98-118`); here the
// per-feature quantile-edge SEARCH over the full column — the expensive
// part of make_bins/bin_with at 1M rows — runs as a threaded C++ kernel.
// Semantics mirror the NumPy path exactly: searchsorted(edges, x, 'left')
// for finite x, bin 0 for any non-finite value (tree_impl.make_bins).
//
// Built on demand by native/build.py (g++ -O3); callers fall back to the
// NumPy implementation when no compiler is available. Only the two entry
// points have C linkage (a template cannot).

#include <cstdint>
#include <cmath>
#include <thread>
#include <vector>

// One column: edges must be ascending; out[i] = #edges < x strictly left.
static void bin_column(const double* col, int64_t n, const float* edges,
                       int32_t n_edges, int32_t* out) {
    for (int64_t i = 0; i < n; ++i) {
        const double x = col[i];
        if (!std::isfinite(x)) {  // NaN/±inf → lowest bin, as in make_bins
            out[i] = 0;
            continue;
        }
        // branch-light lower_bound over the (tiny) edge array
        int32_t lo = 0, hi = n_edges;
        while (lo < hi) {
            const int32_t mid = (lo + hi) >> 1;
            if (static_cast<double>(edges[mid]) < x) lo = mid + 1;
            else hi = mid;
        }
        out[i] = lo;
    }
}

// Row-major (n, F) matrix; per-feature edge rows of length n_edges[f]
// inside an (F, max_edges) block. Features fan out over threads — columns
// are strided in the input, so each worker first packs its column.
// Templated over the input dtype: the fused feature path stages float32
// blocks, and a whole-matrix f64 conversion would double peak memory.
template <typename T>
static void bin_matrix_impl(const T* X, int64_t n, int32_t F,
                            const float* edges, const int32_t* n_edges,
                            int32_t max_edges, const uint8_t* is_categorical,
                            int32_t* out) {
    int hw = static_cast<int>(std::thread::hardware_concurrency());
    if (hw < 1) hw = 1;
    const int workers = F < hw ? F : hw;
    std::vector<std::thread> pool;
    pool.reserve(workers);
    for (int w = 0; w < workers; ++w) {
        pool.emplace_back([&, w]() {
            std::vector<double> colbuf(n);
            std::vector<int32_t> outbuf(n);
            for (int32_t f = w; f < F; f += workers) {
                if (is_categorical[f]) continue;  // host remaps those
                for (int64_t i = 0; i < n; ++i)
                    colbuf[i] = static_cast<double>(X[i * F + f]);
                bin_column(colbuf.data(), n, edges + (int64_t)f * max_edges,
                           n_edges[f], outbuf.data());
                for (int64_t i = 0; i < n; ++i) out[i * F + f] = outbuf[i];
            }
        });
    }
    for (auto& t : pool) t.join();
}

extern "C" {

void bin_matrix(const double* X, int64_t n, int32_t F, const float* edges,
                const int32_t* n_edges, int32_t max_edges,
                const uint8_t* is_categorical, int32_t* out) {
    bin_matrix_impl<double>(X, n, F, edges, n_edges, max_edges,
                            is_categorical, out);
}

void bin_matrix_f32(const float* X, int64_t n, int32_t F, const float* edges,
                    const int32_t* n_edges, int32_t max_edges,
                    const uint8_t* is_categorical, int32_t* out) {
    bin_matrix_impl<float>(X, n, F, edges, n_edges, max_edges,
                           is_categorical, out);
}

}  // extern "C"
