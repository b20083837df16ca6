// Run gather: copy runs of consecutive elements of one array into a
// compact output, back to back.  The device runtime's WHERE pass reads
// a frontier's candidate edges this way (tpu/runtime.py _EdgeRuns):
// the mirror's edge arrays are in (src, etype, rank, dst) order, so a
// vertex's edges of one OVER set are one run, and a run is a memcpy
// where numpy would build an index per element and gather through it.
#include <atomic>
#include <cstdint>
#include <cstring>

// the filter's verdict loop has to vectorise, which the Makefile's -O2
// does not ask for; the flag stops at pop_options, so neb_gather_runs
// below is compiled as the Makefile says
#pragma GCC push_options
#pragma GCC optimize("O3")
namespace {

constexpr int64_t kBlock = 1024;

template <typename Keep>
int64_t filter_runs(const double* values, const uint8_t* valid,
                    const int64_t* lo, const int64_t* cnt, int64_t n_runs,
                    int64_t* out, Keep keep) {
  // a block's verdicts as bytes (a loop the compiler vectorises), then
  // eight of them at a time: a WHERE keeps few, so most words are zero
  alignas(8) uint8_t ok[kBlock];
  int64_t n = 0, pos = 0;
  for (int64_t i = 0; i < n_runs; ++i) {
    for (int64_t done = 0; done < cnt[i]; done += kBlock) {
      const int64_t len = cnt[i] - done < kBlock ? cnt[i] - done : kBlock;
      const int64_t row = lo[i] + done;
      std::memcpy(ok, valid + row, static_cast<size_t>(len));
      // keeps the compiler from moving the value loads above the copy
      // of the valid bytes; it orders nothing between threads
      std::atomic_signal_fence(std::memory_order_seq_cst);
      const double* v = values + row;
      for (int64_t j = 0; j < len; ++j)
        ok[j] = (ok[j] != 0) & keep(v[j]);
      const int64_t words = (len + 7) / 8 * 8;
      std::memset(ok + len, 0, static_cast<size_t>(words - len));
      for (int64_t j = 0; j < words; j += 8) {
        uint64_t w;
        std::memcpy(&w, ok + j, 8);
        if (w == 0) continue;
        for (int64_t k = j; k < j + 8; ++k)
          if (ok[k]) out[n++] = pos + k;
      }
      pos += len;
    }
  }
  return n;
}

int64_t filter_op(const double* values, const uint8_t* valid,
                  const int64_t* lo, const int64_t* cnt, int64_t n_runs,
                  int32_t op, double c, int64_t* out) {
  switch (op) {
    case 0: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v < c; });
    case 1: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v <= c; });
    case 2: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v > c; });
    case 3: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v >= c; });
    case 4: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v == c; });
    case 5: return filter_runs(values, valid, lo, cnt, n_runs, out,
                               [c](double v) { return v != c; });
    default: return -1;
  }
}

}  // namespace
#pragma GCC pop_options

extern "C" {

// out must hold sum(cnt) * itemsize bytes; the caller has checked
// 0 <= lo[i] and lo[i] + cnt[i] <= the source's length.
void neb_gather_runs(const uint8_t* src, int64_t itemsize,
                     const int64_t* lo, const int64_t* cnt, int64_t n_runs,
                     uint8_t* out) {
  for (int64_t i = 0; i < n_runs; ++i) {
    const size_t bytes = static_cast<size_t>(cnt[i] * itemsize);
    std::memcpy(out, src + lo[i] * itemsize, bytes);
    out += bytes;
  }
}

// A WHERE that compares one double column with a constant, over
// candidate runs, in one pass: the positions (0-based over the runs
// laid back to back) of the candidates whose valid byte is set and
// whose value satisfies `value OP c`, ascending, into out; returns how
// many.  op: 0 <, 1 <=, 2 >, 3 >=, 4 ==, 5 != — IEEE comparisons of
// doubles, what numpy's float64 loops compute (a NaN satisfies only
// !=).  out must hold sum(cnt) positions; the caller has checked the
// runs against both arrays.  The code reads a block's valid bytes, then
// its values, in program order (a compiler barrier between them), as
// the numpy pass gathers a piece's valid before its values; like that
// pass it promises no ordering against another thread's stores.
int64_t neb_filter_runs_f64(const double* values, const uint8_t* valid,
                            const int64_t* lo, const int64_t* cnt,
                            int64_t n_runs, int32_t op, double c,
                            int64_t* out) {
  return filter_op(values, valid, lo, cnt, n_runs, op, c, out);
}

}  // extern "C"
