// The lane's way out (tpu/runtime.py _unpack_lanes): a leave cohort's
// fetched bitmaps become its leavers' ascending old-dense-id arrays in
// one pass a leaver, with no sort and no intermediate the size of the
// table.  A bitmap is nb bytes (nb a multiple of eight), bit k of byte
// j the vertex row k * nb + j (ell.lane_bitmap_rows); `inv` gives a
// row's old dense id.  A leaver's set rows are marked in a bitmap of n
// ID bits, which is then read off in order: the ids come out ascending
// whatever order the rows were met in, and a leaver of 280 rows and
// one of 330,000 take the same route.
#include <cstdint>
#include <cstring>
#include <vector>

static_assert(__BYTE_ORDER__ == __ORDER_LITTLE_ENDIAN__,
              "bit p of a loaded word is bit p & 7 of its byte p >> 3");

namespace {

inline uint64_t word_at(const uint8_t* p) {
  uint64_t w;
  std::memcpy(&w, p, 8);
  return w;
}

// x86-64's baseline has no popcount instruction: the build asks for it
// in this one function, which runs only where the processor has it
#if defined(__x86_64__)
__attribute__((target("popcnt")))
int64_t bits_of_popcnt(const uint8_t* own, int64_t nb) {
  int64_t found = 0;
  for (int64_t j = 0; j < nb; j += 8)
    found += __builtin_popcountll(word_at(own + j));
  return found;
}
#endif

int64_t bits_of(const uint8_t* own, int64_t nb) {
#if defined(__x86_64__)
  static const bool has_popcnt = __builtin_cpu_supports("popcnt");
  if (has_popcnt) return bits_of_popcnt(own, nb);
#endif
  int64_t found = 0;
  for (int64_t j = 0; j < nb; j += 8)
    found += __builtin_popcountll(word_at(own + j));
  return found;
}

}  // namespace

extern "C" {

// counts[i] = the set bits of leaver i's bitmap (row i of packed, rows
// `stride` bytes apart); returns their sum.  Sizes neb_unpack_lanes's
// out.
int64_t neb_count_lanes(const uint8_t* packed, int64_t stride, int64_t nb,
                        int64_t n_leavers, int64_t* counts) {
  int64_t total = 0;
  for (int64_t l = 0; l < n_leavers; ++l) {
    counts[l] = bits_of(packed + l * stride, nb);
    total += counts[l];
  }
  return total;
}

// Leaver by leaver, the ascending old dense ids of the set rows below
// n, back to back into out; counts[i] = how many leaver i gave;
// returns their sum.  out must hold neb_count_lanes's total; inv holds
// n ids in [0, n), each once.  The bits of rows from n on are zero by
// the extract's contract and are passed over if they are not, as is an
// id outside [0, n): nothing is read or written outside the arrays.
int64_t neb_unpack_lanes(const uint8_t* packed, int64_t stride, int64_t nb,
                         int64_t n_leavers, int64_t n, const int32_t* inv,
                         int64_t* counts, int64_t* out) {
  const int64_t id_words = (n + 63) / 64;
  std::vector<uint64_t> ids(static_cast<size_t>(id_words), 0);
  int64_t total = 0;
  for (int64_t l = 0; l < n_leavers; ++l) {
    const uint8_t* own = packed + l * stride;
    for (int64_t j = 0; j < nb; j += 8) {
      for (uint64_t w = word_at(own + j); w != 0; w &= w - 1) {
        const int64_t p = __builtin_ctzll(w);
        const int64_t row = (p & 7) * nb + j + (p >> 3);
        if (row >= n) continue;
        const uint32_t id = static_cast<uint32_t>(inv[row]);
        if (id >= static_cast<uint64_t>(n)) continue;
        ids[id >> 6] |= uint64_t{1} << (id & 63);
      }
    }
    // read the marks off in order and leave the words zero for the
    // next leaver
    int64_t* own_out = out + total;
    int64_t found = 0;
    for (int64_t i = 0; i < id_words; ++i) {
      uint64_t w = ids[static_cast<size_t>(i)];
      if (w == 0) continue;
      ids[static_cast<size_t>(i)] = 0;
      for (; w != 0; w &= w - 1)
        own_out[found++] = 64 * i + __builtin_ctzll(w);
    }
    counts[l] = found;
    total += found;
  }
  return total;
}

}  // extern "C"
