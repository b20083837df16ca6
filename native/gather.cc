// Run gather: copy runs of consecutive elements of one array into a
// compact output, back to back.  The device runtime's WHERE pass reads
// a frontier's candidate edges this way (tpu/runtime.py _EdgeRuns):
// the mirror's edge arrays are in (src, etype, rank, dst) order, so a
// vertex's edges of one OVER set are one run, and a run is a memcpy
// where numpy would build an index per element and gather through it.
#include <cstdint>
#include <cstring>

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

}  // extern "C"
