// Native hot-loop runtime: the ingest byte loops of the batch
// coordinator, run with the GIL released (ctypes drops it around every
// call). Two entry points, each dropping into an existing Python
// seam (docs/INTERNALS.md §18):
//
//   rt_classify    - single-pass tag partition over the drained ring
//                    items' class-code sidecar (the flat tagged-item
//                    layout rings.py publishes); returns in-order index
//                    lists per class for the Python routing half.
//   rt_pack_mbox   - scatter pre-flattened per-message int64 field
//                    values into the packed (NROWS, width) int32
//                    mailbox buffer (the columnwise encode of
//                    _build_mailbox without per-field Python passes).
//
// Python stays the policy owner and the byte-identical fallback; armed
// failpoints route around both (ra_tpu/faults.py).
//
// Build: g++ -O2 -shared -fPIC -o rt_native.so rt_native.cpp
// (no external deps).

#include <cstdint>

extern "C" {

// -- classify ---------------------------------------------------------------

// Partition item indexes by class code, order preserved within each
// class. codes[i] in [0, n_classes); out_idx must hold n entries and
// counts n_classes entries. After the call the indexes of class k
// occupy out_idx[sum(counts[0..k-1]) : +counts[k]] in arrival order.
// Returns 0, or -1 on an out-of-range code (caller falls back).
long rt_classify(
    const uint8_t* codes,
    long n,
    long n_classes,
    int32_t* out_idx,
    int32_t* counts
) {
    for (long k = 0; k < n_classes; k++) counts[k] = 0;
    for (long i = 0; i < n; i++) {
        if (codes[i] >= n_classes) return -1;
        counts[codes[i]]++;
    }
    // prefix offsets, then a stable fill
    long offs[256];
    long acc = 0;
    for (long k = 0; k < n_classes; k++) {
        offs[k] = acc;
        acc += counts[k];
    }
    for (long i = 0; i < n; i++)
        out_idx[offs[codes[i]]++] = (int32_t)i;
    return 0;
}

// -- mailbox pack -----------------------------------------------------------

// Scatter n messages x nf fields of row-major int64 values into the
// packed int32 mailbox: out[rows[f]*width + cols[k]] = vals[k*nf + f].
// Returns 0, or -1 on an out-of-range row/column (caller falls back).
long rt_pack_mbox(
    const int64_t* vals,
    const int32_t* cols,
    long n,
    const int32_t* rows,
    long nf,
    int32_t* out,
    long nrows,
    long width
) {
    for (long f = 0; f < nf; f++)
        if (rows[f] < 0 || rows[f] >= nrows) return -1;
    for (long k = 0; k < n; k++) {
        int32_t c = cols[k];
        if (c < 0 || c >= width) return -1;
        const int64_t* v = vals + k * nf;
        for (long f = 0; f < nf; f++)
            out[(long)rows[f] * width + c] = (int32_t)v[f];
    }
    return 0;
}

}  // extern "C"
