// ell_build — native ELL slot-table construction for the TPU batched
// traversal engine (the C++ counterpart of nebula_tpu/tpu/ell.py
// EllIndex.build; the numpy path stays as the fallback and as the
// differential-test oracle).
//
// Same layout contract as the Python builder:
//   * rows grouped by DST, one table per stored direction over ONE row
//     layout: a +etype row (u, v) is v's in-slot u (in-table), a
//     -etype row (v, u) is v's out-slot u (out-table); both hold the
//     etype's magnitude
//   * vertices relabeled so each degree bucket is contiguous and,
//     inside it, stands in descending order of in-degree (new id =
//     rank in (bucket_D, -in-degree, old_id) order): the rows a pull
//     has to gather at a column are a prefix of the bucket
//   * bucket width D = clamp(next_pow2(min(deg, cap)), min_d, cap),
//     deg = max(in-degree, out-degree)
//   * hub vertices (deg > cap) get extra rows appended after all real
//     vertices, the same rows in both tables; extra_owner maps each
//     extra row to its owner's new id
//   * slot padding: nbr = n_rows (the pinned-zero frontier row),
//     etype = 0 (never a real etype)
//
// ABI (ctypes, two-phase):
//   ell_build(src, dst, et, m, n, cap, min_d) -> handle (>=0) or -1
//   ell_counts(handle, out int64[4])   -> {n_rows, n_extras, n_buckets,
//                                          cells of ONE table}
//   ell_bucket_dims(handle, out int64[2*n_buckets])  (rows_b, D_b)...
//   ell_fill_split(handle, src, dst, et, m, perm, inv, extra_owner,
//                  in_nbr, in_et, out_nbr, out_et, et_itemsize)
//       fills caller-allocated buffers from the same edge rows; each
//       table's buckets are concatenated row-major in ascending-D
//       order, the etype columns in the caller's integer type
//       (et_itemsize 1, 2 or 4 bytes).
//   ell_free(handle)
#include <algorithm>
#include <cstdint>
#include <cstring>
#include <map>
#include <mutex>
#include <numeric>
#include <vector>

namespace {

struct EllResult {
  int64_t n = 0;
  int64_t n_rows = 0;
  std::vector<int32_t> perm, inv, extra_owner;
  std::vector<int64_t> bucket_rows, bucket_D;
  int64_t cap = 0, total_cells = 0;   // cells of ONE table
  // where a vertex's slots go, the same in both tables: the cell of
  // its main row's first slot, and of its first extra row's (hubs)
  std::vector<int64_t> main_cell, extra_cell;
};

// ell_fill_split's slot pass, over the etype column's integer type
template <typename E>
void fill_slots(const EllResult& r, const int32_t* src, const int32_t* dst,
                const int32_t* et, int64_t m, int32_t* const nbr_out[2],
                void* const et_out[2]) {
  std::vector<int64_t> fill[2];
  fill[0].assign(size_t(r.n), 0);
  fill[1].assign(size_t(r.n), 0);
  const int64_t cap = r.cap;
  // edge order = the stable sort by dst, per direction
  for (int64_t i = 0; i < m; i++) {
    int64_t v = dst[i];
    int side = et[i] > 0 ? 0 : 1;
    int64_t off = fill[side][v]++;
    // a hub's slots past its main row run on through its extra rows,
    // which sit one after the other in the cap bucket
    int64_t cell = off < cap ? r.main_cell[v] + off
                             : r.extra_cell[v] + (off - cap);
    nbr_out[side][cell] = r.perm[src[i]];
    static_cast<E*>(et_out[side])[cell] = E(et[i] > 0 ? et[i] : -et[i]);
  }
}

std::mutex g_mu;
std::map<int64_t, EllResult*> g_results;
int64_t g_next = 1;

int64_t next_pow2(int64_t x) {
  if (x <= 1) return 1;
  int64_t p = 1;
  while (p < x) p <<= 1;
  return p;
}

}  // namespace

extern "C" {

int64_t ell_build(const int32_t* src, const int32_t* dst,
                  const int32_t* et, int64_t m, int64_t n,
                  int64_t cap, int64_t min_d) {
  if (n < 0 || m < 0 || cap <= 0 || min_d <= 0) return -1;
  if (cap < min_d) cap = min_d;
  // out-of-range vertex ids would corrupt the heap here where the
  // numpy fallback raises cleanly — reject so the wrapper falls back
  for (int64_t i = 0; i < m; i++) {
    if (src[i] < 0 || src[i] >= n || dst[i] < 0 || dst[i] >= n) return -1;
  }
  auto* r = new EllResult();
  r->n = n;
  if (n == 0) {
    std::lock_guard<std::mutex> lk(g_mu);
    g_results[g_next] = r;
    return g_next++;
  }

  // per-direction degrees; a vertex is as wide as its larger side
  std::vector<int64_t> deg_side[2];
  deg_side[0].assign(n, 0);
  deg_side[1].assign(n, 0);
  for (int64_t i = 0; i < m; i++) deg_side[et[i] > 0 ? 0 : 1][dst[i]]++;
  std::vector<int64_t> deg(n, 0);
  for (int64_t v = 0; v < n; v++)
    deg[v] = std::max(deg_side[0][v], deg_side[1][v]);

  // bucket width per vertex + relabeling (stable sort by D, then the
  // fullest in-row first, ties by old id)
  std::vector<int64_t> D_v(n);
  for (int64_t v = 0; v < n; v++) {
    int64_t per_row = std::min(deg[v], cap);
    D_v[v] = std::min(std::max(next_pow2(per_row), min_d), cap);
  }
  std::vector<int32_t> vorder(n);
  std::iota(vorder.begin(), vorder.end(), 0);
  const std::vector<int64_t>& in_deg = deg_side[0];
  std::stable_sort(vorder.begin(), vorder.end(),
                   [&](int32_t a, int32_t b) {
                     return D_v[a] != D_v[b] ? D_v[a] < D_v[b]
                                             : in_deg[a] > in_deg[b];
                   });
  r->inv.assign(vorder.begin(), vorder.end());
  r->perm.resize(n);
  for (int64_t i = 0; i < n; i++) r->perm[vorder[i]] = int32_t(i);

  // hub extra rows
  std::vector<int64_t> first_extra(n, 0);
  int64_t n_extras = 0;
  for (int64_t v = 0; v < n; v++) {
    first_extra[v] = n + n_extras;
    if (deg[v] > cap) n_extras += (deg[v] + cap - 1) / cap - 1;
  }
  r->n_rows = n + n_extras;
  r->extra_owner.reserve(n_extras);
  for (int64_t v = 0; v < n; v++) {
    int64_t k = (deg[v] > cap) ? (deg[v] + cap - 1) / cap - 1 : 0;
    for (int64_t j = 0; j < k; j++) r->extra_owner.push_back(r->perm[v]);
  }

  // bucket layout (ascending D; extras live in the cap bucket)
  std::vector<int64_t> Ds;
  for (int64_t v = 0; v < n; v++) Ds.push_back(D_v[v]);
  std::sort(Ds.begin(), Ds.end());
  Ds.erase(std::unique(Ds.begin(), Ds.end()), Ds.end());
  std::map<int64_t, int64_t> rows_of;   // D -> row count
  for (int64_t v = 0; v < n; v++) rows_of[D_v[v]]++;
  if (n_extras) rows_of[cap] += n_extras;

  int64_t total_cells = 0;
  std::map<int64_t, int64_t> cell_base;  // D -> offset into flat arrays
  std::map<int64_t, int64_t> row_base;   // D -> first global row index
  int64_t row_cursor = 0;
  for (int64_t D : Ds) {
    cell_base[D] = total_cells;
    row_base[D] = row_cursor;
    total_cells += rows_of[D] * D;
    row_cursor += rows_of[D];
    r->bucket_rows.push_back(rows_of[D]);
    r->bucket_D.push_back(D);
  }
  r->cap = cap;
  r->total_cells = total_cells;
  r->main_cell.resize(n);
  r->extra_cell.assign(n, 0);
  for (int64_t v = 0; v < n; v++) {
    int64_t D = D_v[v];
    r->main_cell[v] = cell_base[D] + (int64_t(r->perm[v]) - row_base[D]) * D;
    // a non-hub never reaches its extra cell: off < D <= cap
    if (deg[v] > cap)
      r->extra_cell[v] =
          cell_base[cap] + (first_extra[v] - row_base[cap]) * cap;
  }

  std::lock_guard<std::mutex> lk(g_mu);
  g_results[g_next] = r;
  return g_next++;
}

int64_t ell_counts(int64_t handle, int64_t* out4) {
  std::lock_guard<std::mutex> lk(g_mu);
  auto it = g_results.find(handle);
  if (it == g_results.end()) return -1;
  auto* r = it->second;
  out4[0] = r->n_rows;
  out4[1] = int64_t(r->extra_owner.size());
  out4[2] = int64_t(r->bucket_D.size());
  out4[3] = r->total_cells;
  return 0;
}

int64_t ell_bucket_dims(int64_t handle, int64_t* out) {
  std::lock_guard<std::mutex> lk(g_mu);
  auto it = g_results.find(handle);
  if (it == g_results.end()) return -1;
  auto* r = it->second;
  for (size_t b = 0; b < r->bucket_D.size(); b++) {
    out[2 * b] = r->bucket_rows[b];
    out[2 * b + 1] = r->bucket_D[b];
  }
  return 0;
}

int64_t ell_fill_split(int64_t handle, const int32_t* src,
                       const int32_t* dst, const int32_t* et, int64_t m,
                       int32_t* perm, int32_t* inv, int32_t* extra_owner,
                       int32_t* in_nbr, void* in_et, int32_t* out_nbr,
                       void* out_et, int64_t et_itemsize) {
  std::lock_guard<std::mutex> lk(g_mu);
  auto it = g_results.find(handle);
  if (it == g_results.end()) return -1;
  auto* r = it->second;
  std::memcpy(perm, r->perm.data(), r->perm.size() * 4);
  std::memcpy(inv, r->inv.data(), r->inv.size() * 4);
  if (!r->extra_owner.empty())
    std::memcpy(extra_owner, r->extra_owner.data(),
                r->extra_owner.size() * 4);
  // padding first, straight into the caller's buffers: the sentinel
  // row, etype 0
  int32_t* const nbr_out[2] = {in_nbr, out_nbr};
  void* const et_out[2] = {in_et, out_et};
  for (int side = 0; side < 2; side++) {
    std::fill(nbr_out[side], nbr_out[side] + r->total_cells,
              int32_t(r->n_rows));
    std::memset(et_out[side], 0, size_t(r->total_cells * et_itemsize));
  }
  if (et_itemsize == 1)
    fill_slots<int8_t>(*r, src, dst, et, m, nbr_out, et_out);
  else if (et_itemsize == 2)
    fill_slots<int16_t>(*r, src, dst, et, m, nbr_out, et_out);
  else if (et_itemsize == 4)
    fill_slots<int32_t>(*r, src, dst, et, m, nbr_out, et_out);
  else
    return -1;
  return 0;
}

void ell_free(int64_t handle) {
  std::lock_guard<std::mutex> lk(g_mu);
  auto it = g_results.find(handle);
  if (it != g_results.end()) {
    delete it->second;
    g_results.erase(it);
  }
}

}  // extern "C"
