"""Batched ELL traversal engine — the TPU-fast path for multi-hop GO/BFS.

Why this exists: on TPU, XLA lowers arbitrary gather/scatter to a
*serial* per-element loop (~30 ns per accessed row, measured on v5e —
the per-row cost is flat whether the row is 1 byte or 2 KB).  A
single-query BFS hop over an m-edge graph therefore costs m x 30 ns no
matter how it is phrased, and loses to host numpy.  The TPU-native
answer is to *batch queries*: B concurrent traversals share one
[n, B] frontier matrix (stored one BIT per query lane, see "Bit-packed
frontier" below), so each (unavoidable) row access moves B query-bits
at once and the 30 ns is amortised B ways.  A hop becomes

    next[v, :] = OR_j  f[in_slot[v, j], :] * etype_ok[v, j]

which is D row-gathers plus a free reshape-reduce — no scatter at all.
This mirrors how the reference amortises per-request cost by bulking
vertices per StorageService RPC (storage.thrift GetNeighborsRequest
carries *lists* of vids per part; QueryBaseProcessor.inl:433-460
buckets them across worker threads) — here the bulking axis is queries
and the workers are TPU lanes.

Structure built host-side from the CsrMirror (build_ell):

  * vertices are **relabeled** so that all vertices of one degree
    bucket are contiguous, and inside a bucket stand in DESCENDING
    order of in-degree (new id = rank in (bucket_D, -in-degree, old_id)
    order); bucket outputs then concatenate into the next frontier
    with zero data movement.  A vertex's bucket is the power of two
    over the LARGER of its in- and out-degree (floored at ``min_d``,
    capped at ``cap``), so a row's in-slots fill a prefix of its
    columns that shrinks down the bucket: the rows that hold a real
    slot at column c or later are a PREFIX of the bucket's rows.  The
    index says how long, per table, bucket and column range
    (``reach``, pull_reach), and a pull's loop over a column range
    gathers that prefix only (_bucket_expand_packed).  Nothing outside
    this module may depend on the order of ids inside a bucket.
  * the slots live in TWO tables over that one row layout, one per
    stored direction (the mirror stores a reverse edge under -etype,
    csr.py), so a step reads only the direction it asks for:
      - the **in-table** ``bucket_nbr[b]`` / ``bucket_et[b]``
        ``[rows_b, D_b]``: row v holds the *new* ids u of its in-edges
        u -> v (the mirror's +etype rows).  A pull over +t gathers
        these; a push over -t (REVERSELY) scatters to them;
      - the **out-table** ``out_nbr[b]`` / ``out_et[b]``, same shapes:
        row u holds the targets v of its out-edges (the -etype rows).
        A push over +t scatters to these; a pull over -t gathers them.
    Both pad with a sentinel row ``n_rows`` whose frontier value is
    pinned to 0.  The table says the sign, so ``et`` holds the etype's
    MAGNITUDE, in the narrowest signed integer type that holds the
    mirror's largest (int8 up to 127, int16, int32; read off the input
    at build, part of shape_sig); padding is 0, never a real etype, so
    one static mask per query selects the OVER set.
  * hub vertices (larger degree > cap) own several rows in the largest
    bucket, the SAME extra rows in both tables; the extra rows are
    appended after all real vertices and OR-merged back into their
    owner row by a tiny scatter (hubs are rare, the scatter is
    O(#extra rows)).

The reference's analogue of this file is the storaged read hot loop
(QueryBoundProcessor::processVertex + QueryBaseProcessor.inl:336-405
per-vertex RocksDB prefix scans); the multi-chip variant replaces the
graphd scatter-gather + dedup (StorageClient.inl:74-159,
GoExecutor.cpp:377-431) with row-sharded expansion + an ICI all-gather
of the replicated frontier.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

INT16_INF = np.int16(2**15 - 1)


def _next_pow2(x: np.ndarray) -> np.ndarray:
    x = np.maximum(x.astype(np.int64), 1)
    return (1 << np.ceil(np.log2(x)).astype(np.int64)).astype(np.int64)


def _etype_dtype(edge_etype: np.ndarray) -> np.dtype:
    """Narrowest signed integer type that holds the largest |etype|."""
    top = max(int(np.max(edge_etype)), -int(np.min(edge_etype))) \
        if len(edge_etype) else 0
    for dt in (np.int8, np.int16):
        if top <= np.iinfo(dt).max:
            return np.dtype(dt)
    return np.dtype(np.int32)


# A pull's loop over a bucket is cut into this many equal column
# ranges, each gathering only the leading rows that hold a real slot
# there (EllIndex.reach, _bucket_expand_packed).  More ranges skip more
# padding and cost a loop with a gather each: 0.3-0.4 MB of program,
# which is device memory while it is loaded, and a turn of a loop has
# a floor of ~5 us whatever it gathers, so the narrow prefixes of the
# last ranges stop paying.  jit_hop alone on the v5e, every row live,
# over graph500-s20's in-table of 24,835,040 slots (PERF.md section 6,
# PR 39; slots gathered, ms a hop, program code, compile):
#   whole  24.84 M  69.6 ms  10.4 MB  6.4 s
#   1      24.04 M  67.5     10.5     6.5
#   2      22.06 M  61.9     12.2     6.3
#   4      20.18 M  57.3     14.5     5.8
#   8      18.99 M  55.2     18.6     8.1
#   16     18.62 M  54.3     23.5     9.9
# 2.8 ns a gathered slot up to 4 ranges, 2.9 from 8 on.  From 4 to 8
# the hop gains 2.1 ms (3.6 %) and each of the three programs that
# pull (jit_hop, jit_bfs, the windowed GO: a GO cell holds two of them
# loaded) takes 4.2 MB more, where device_bytes_per_edge's whole bound
# is 8 MB on that graph, and the windowed GO's scratch + code comes out
# ABOVE the whole sweep's (tests/test_hop_compile_tpu.py).  A speed
# choice only: every cut gives the same bits.
PULL_COLUMN_RANGES = 4

# A reach is rounded up to this many rows, so that a few absorbed
# edges do not move it: it is a static shape of every program that
# pulls (shape_sig), and a reach that moves is a compile.
PULL_REACH_STEP = 1024


def _range_bounds(D: int, ranges: int) -> List[int]:
    """Column bounds of a D-wide bucket's ranges: ``min(ranges, D)``
    equal ones (D and ``ranges`` powers of two; floor division keeps
    the bounds strictly rising for any other pair)."""
    R = max(1, min(int(ranges), D))
    return [r * D // R for r in range(R + 1)]


def _main_rows(n: int, nbrs) -> List[int]:
    """Main rows (global row < n: a vertex's own row) of each bucket of
    a table; the rest of the last bucket are hub extra rows and growth
    spares."""
    out, b0 = [], 0
    for nbr in nbrs:
        out.append(min(max(n - b0, 0), nbr.shape[0]))
        b0 += nbr.shape[0]
    return out


def _bucket_reach(nbr: np.ndarray, n_main: int, sentinel: int,
                  ranges: int, step: int) -> Tuple[int, ...]:
    """Per column range [c, c') of one bucket of one table: the number
    of leading main rows one of which holds a real slot at a column
    >= c, rounded up to whole ``step``s, one at the least (the first
    edge into a range nothing reached moves no shape either, and a
    bucket of a step's rows or fewer sweeps whole: a small graph's
    programs are the ones it had), and capped at ``n_main``.  Read off
    the slots (``nbr != sentinel``), so it holds for any content: every
    slot at a column >= c of a main row past the prefix is padding."""
    D = nbr.shape[1]
    real = nbr[:n_main] != np.int32(sentinel)
    # a row's last real column, -1 where it has none
    last = np.where(real.any(axis=1),
                    D - 1 - np.argmax(real[:, ::-1], axis=1), -1)
    out = []
    for c in _range_bounds(D, ranges)[:-1]:
        rows = np.flatnonzero(last >= c)
        reach = int(rows[-1]) + 1 if len(rows) else 0
        out.append(min(max(-(-reach // step), 1) * step, n_main))
    return tuple(out)


def pull_reach(ell: "EllIndex", ranges: Optional[int] = None,
               step: Optional[int] = None, tables=None) -> Tuple:
    """EllIndex.reach as the slot arrays say it: (in-table, out-table),
    each a tuple over the buckets of _bucket_reach's tuple over
    ``ranges`` column ranges (PULL_COLUMN_RANGES, PULL_REACH_STEP
    unless a test passes its own).  ``tables`` = the tables_host()
    indices to read anew; the others keep ``ell.reach``'s entry (an
    absorb rewrites a few buckets and shares the rest)."""
    ranges = PULL_COLUMN_RANGES if ranges is None else ranges
    step = PULL_REACH_STEP if step is None else step
    nb = len(ell.bucket_nbr)
    mains = _main_rows(ell.n, ell.bucket_nbr)
    return tuple(
        tuple(_bucket_reach(nbr, mains[b], ell.n_rows, ranges, step)
              if tables is None or side * nb + b in tables
              else ell.reach[side][b]
              for b, nbr in enumerate(nbrs))
        for side, nbrs in enumerate((ell.bucket_nbr, ell.out_nbr)))


class EllIndex:
    """Degree-bucketed slot tables, one per stored direction, over one
    relabeling of dense vertex ids."""

    __slots__ = ("n", "m", "perm", "inv", "bucket_D", "bucket_nbr",
                 "bucket_et", "out_nbr", "out_et", "extra_owner",
                 "n_rows", "reach", "_device", "_n_hubs")

    def __init__(self):
        self.n = 0                     # real vertices
        self.m = 0                     # slots filled (edge rows, both dirs)
        self.perm = np.zeros(0, np.int32)   # old dense id -> new id
        self.inv = np.zeros(0, np.int32)    # new id -> old dense id
        self.bucket_D: List[int] = []       # slot width per bucket (asc)
        # in-table: row v's in-edge sources (what a forward pull sweeps)
        self.bucket_nbr: List[np.ndarray] = []  # [rows_b, D_b] new ids
        self.bucket_et: List[np.ndarray] = []   # [rows_b, D_b] |etype|
        # out-table: row u's out-edge targets, same shapes
        self.out_nbr: List[np.ndarray] = []
        self.out_et: List[np.ndarray] = []
        self.extra_owner = np.zeros(0, np.int32)  # hub extra row -> new id
        self.n_rows = 0                # n + len(extra_owner)
        # (in-table, out-table) x bucket x column range: the leading
        # main rows a pull has to gather there (pull_reach); None = a
        # pull sweeps every row at every column
        self.reach: Optional[Tuple] = None
        self._device = None            # lazy jnp copies of bucket arrays
        self._n_hubs = None            # lazy count of distinct hub owners

    # -------------------------------------------------------------- build
    @staticmethod
    def build(edge_src: np.ndarray, edge_dst: np.ndarray,
              edge_etype: np.ndarray, n: int, cap: int = 512,
              min_d: int = 8, use_native: bool = True,
              growth_slack: int = 0) -> "EllIndex":
        """Group the mirror's edge rows by dst into the two bucketed
        slot tables.

        ``edge_*`` are the CsrMirror arrays (dense ids, signed etypes,
        both directions present): a +etype row (u, v) is v's in-slot u,
        a -etype row (v, u) is v's out-slot u.  ``cap`` bounds slot
        width; vertices with more slots in either direction get extra
        rows merged by the fix-up scatter.
        ``min_d`` floors the bucket width — fewer buckets compile into
        fewer fori kernels at the price of a little padding.
        ``growth_slack`` appends that many SPARE all-sentinel rows to
        the widest bucket (owner = the spare sentinel): an absorb
        window whose degree growth overflows a vertex's resident row
        can CLAIM one in place (plan_ell_absorb) instead of paying the
        re-bucketing rebuild — the in-place slot-growth path
        (docs/durability.md decision table).

        When the native library is loaded (native/ell_build.cc) the
        table construction runs in C++ — several times faster at
        multi-million-edge scale; the numpy path below is the fallback
        and the differential-test oracle (both produce identical
        arrays, tests/test_ell.py::test_native_builder_identical).
        """
        et_dt = _etype_dtype(edge_etype)
        if use_native:
            ell = EllIndex._build_native(edge_src, edge_dst, edge_etype,
                                         n, cap, min_d, et_dt)
            if ell is not None:
                return _append_growth_spares(ell, growth_slack)
        ell = EllIndex()
        ell.n = n
        m = len(edge_src)
        ell.m = m
        if n == 0:
            ell.n_rows = 0
            return _append_growth_spares(ell, 0)

        # rows are grouped by DST, one stable sort for both tables: a
        # +etype row is the owner's in-slot, a -etype row its out-slot
        order = np.argsort(edge_dst, kind="stable")
        es = np.asarray(edge_dst, np.int64)[order]   # row owner (dst)
        ed = np.asarray(edge_src, np.int64)[order]   # slot neighbor (src)
        ee = np.asarray(edge_etype, np.int64)[order]
        inward = ee > 0
        sides = []
        for sel in (inward, ~inward):
            sides.append((es[sel], ed[sel], np.abs(ee[sel]),
                          np.bincount(es[sel], minlength=n)
                          .astype(np.int64)))
        deg = np.maximum(sides[0][3], sides[1][3])

        cap = max(cap, min_d)
        per_row = np.minimum(deg, cap)
        D_v = np.clip(_next_pow2(per_row), min_d, cap)
        # by bucket, inside it the fullest in-row first, ties by old id
        vorder = np.lexsort((np.arange(n), -sides[0][3], D_v))
        perm = np.empty(n, np.int32)
        perm[vorder] = np.arange(n, dtype=np.int32)
        ell.perm = perm
        ell.inv = np.asarray(vorder, np.int32)

        # hub extra rows (larger degree > cap), appended after all real
        # vertices, the same rows in both tables
        hub_vs = np.nonzero(deg > cap)[0]
        n_extra_v = np.zeros(n, dtype=np.int64)          # extra rows per v
        n_extra_v[hub_vs] = np.ceil(deg[hub_vs] / cap).astype(np.int64) - 1
        first_extra = np.zeros(n, dtype=np.int64)        # v -> its 1st extra
        first_extra[1:] = np.cumsum(n_extra_v)[:-1]
        first_extra += n
        n_extras = int(n_extra_v.sum())
        ell.extra_owner = perm[np.repeat(np.arange(n), n_extra_v)] \
            .astype(np.int32)
        ell.n_rows = n + n_extras

        # bucket layout: new ids are contiguous per D (vorder sorted by D_v)
        Ds = sorted(set(D_v.tolist()))
        sentinel = np.int32(ell.n_rows)  # frontier row pinned to 0
        D_new = D_v[vorder]              # slot width per new id
        ell.bucket_D = [int(D) for D in Ds]
        # rows per bucket, the same in both tables; extras live in the
        # cap bucket
        bucket_rows = [int(np.count_nonzero(D_new == D))
                       + (n_extras if D == cap else 0) for D in Ds]
        for (s_es, s_ed, s_ee, s_deg), nbr_out, et_out in zip(
                sides, (ell.bucket_nbr, ell.out_nbr),
                (ell.bucket_et, ell.out_et)):
            # per-edge (row, col) destination slot in this direction
            row_start = np.concatenate([[0], np.cumsum(s_deg)])
            off = np.arange(len(s_es), dtype=np.int64) - row_start[s_es]
            k_of = off // cap
            col = np.where(k_of == 0, off, off % cap).astype(np.int64)
            row = np.where(k_of == 0, perm[s_es].astype(np.int64),
                           first_extra[s_es] + k_of - 1)
            bstart = 0
            for D, nb in zip(Ds, bucket_rows):
                nbr = np.full((nb, D), sentinel, dtype=np.int32)
                et = np.zeros((nb, D), dtype=et_dt)
                # buckets are contiguous in new-id order, and extra rows
                # (>= n) all belong to the last (cap) bucket
                sel = np.nonzero((row >= bstart) & (row < bstart + nb))[0]
                if len(sel):
                    flat = (row[sel] - bstart) * D + col[sel]
                    nbr.reshape(-1)[flat] = perm[s_ed[sel]]
                    et.reshape(-1)[flat] = s_ee[sel]
                nbr_out.append(nbr)
                et_out.append(et)
                bstart += nb
        return _append_growth_spares(ell, growth_slack)

    def spare_sentinel(self) -> int:
        """The extra_owner value marking an UNCLAIMED growth-spare row
        (== n_rows, the same out-of-range row the slot sentinel names:
        the hub merge scatter drops indices past the table, so an
        unclaimed spare merges nowhere)."""
        return self.n_rows

    @staticmethod
    def _build_native(edge_src, edge_dst, edge_etype, n: int, cap: int,
                      min_d: int, et_dt: np.dtype) -> Optional["EllIndex"]:
        """C++ builder via ctypes; None when the library is unavailable
        (callers fall back to the numpy path)."""
        import ctypes
        from ..native import lib
        L = lib()
        if L is None or not hasattr(L, "ell_fill_split"):
            return None              # absent or stale .so: numpy path
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)

        def p32(a):
            return a.ctypes.data_as(i32p)

        src = np.ascontiguousarray(edge_src, dtype=np.int32)
        dst = np.ascontiguousarray(edge_dst, dtype=np.int32)
        et = np.ascontiguousarray(edge_etype, dtype=np.int32)
        m = len(src)
        h = L.ell_build(p32(src), p32(dst), p32(et), m, n, cap, min_d)
        if h < 0:
            return None
        try:
            counts = np.zeros(4, dtype=np.int64)
            if L.ell_counts(h, counts.ctypes.data_as(i64p)) != 0:
                return None
            n_rows, n_extras, n_buckets, total_cells = counts.tolist()
            ell = EllIndex()
            ell.n = n
            ell.m = m
            ell.n_rows = int(n_rows)
            if n == 0:
                return ell
            dims = np.zeros(2 * n_buckets, dtype=np.int64)
            L.ell_bucket_dims(h, dims.ctypes.data_as(i64p))
            perm = np.zeros(n, dtype=np.int32)
            inv = np.zeros(n, dtype=np.int32)
            owner = np.zeros(max(n_extras, 1), dtype=np.int32)
            # one table's cells, each direction; the builder writes the
            # padding too
            cells = max(total_cells, 1)
            nbrs = [np.empty(cells, dtype=np.int32) for _ in range(2)]
            ets = [np.empty(cells, dtype=et_dt) for _ in range(2)]
            if L.ell_fill_split(h, p32(src), p32(dst), p32(et), m,
                                p32(perm), p32(inv), p32(owner),
                                p32(nbrs[0]), ets[0].ctypes.data,
                                p32(nbrs[1]), ets[1].ctypes.data,
                                et_dt.itemsize) != 0:
                return None
            ell.perm, ell.inv = perm, inv
            ell.extra_owner = owner[:n_extras]
            off = 0
            for b in range(n_buckets):
                rows, D = int(dims[2 * b]), int(dims[2 * b + 1])
                cells = rows * D
                ell.bucket_D.append(D)
                for out, flat in ((ell.bucket_nbr, nbrs[0]),
                                  (ell.bucket_et, ets[0]),
                                  (ell.out_nbr, nbrs[1]),
                                  (ell.out_et, ets[1])):
                    out.append(flat[off:off + cells].reshape(rows, D))
                off += cells
            return ell
        finally:
            L.ell_free(h)

    # -------------------------------------------------------------- device
    def device_arrays(self):
        """jnp copies of every resident array (cached): (in-table nbr,
        in-table et, out-table nbr, out-table et, extra_owner)."""
        if self._device is None:
            import jax.numpy as jnp
            self._device = tuple(
                [jnp.asarray(a) for a in group]
                for group in (self.bucket_nbr, self.bucket_et,
                              self.out_nbr, self.out_et)) \
                + (jnp.asarray(self.extra_owner),)
        return self._device

    # ----------------------------------------------------------- frontiers
    def start_frontier(self, start_dense_per_query: Sequence[np.ndarray],
                       B: Optional[int] = None) -> np.ndarray:
        """Host [n_rows+1, B] 0/1 lane matrix from per-query old-dense-id
        lists (pack_lanes_host turns it into the device layout)."""
        nq = len(start_dense_per_query)
        B = B or max(128, nq)
        f = np.zeros((self.n_rows + 1, B), dtype=np.int8)
        for q, starts in enumerate(start_dense_per_query):
            s = np.asarray(starts)
            s = s[(s >= 0) & (s < self.n)]
            f[self.perm[s], q] = 1
        return f

    def to_old(self, frontier_new: np.ndarray) -> np.ndarray:
        """[.., B] rows in new-id space -> old dense-id space."""
        return frontier_new[self.perm]

    # -------------------------------------------------------------- shape
    def shape_sig(self) -> Tuple:
        """Static shape signature: two EllIndexes with equal signatures
        can share one compiled kernel (tables ride as jit ARGUMENTS, so
        the XLA program depends only on shapes — a mirror rebuild with
        unchanged table shapes re-dispatches the cached executable
        instead of recompiling; see the kernel builders below)."""
        return (self.n, self.n_rows, len(self.extra_owner), self.n_hubs,
                tuple((nbr.shape[0], nbr.shape[1])
                      for nbr in self.bucket_nbr),
                self.reach, self.et_dtype.name)

    @property
    def et_dtype(self) -> np.dtype:
        """The etype columns' integer type (part of shape_sig: a table
        argument's dtype is part of the compiled program)."""
        return self.bucket_et[0].dtype if self.bucket_et \
            else np.dtype(np.int8)

    @property
    def n_hubs(self) -> int:
        """Distinct hub owners — the packed hub-merge's compact-slot
        count, part of shape_sig because it sizes a kernel argument."""
        if self._n_hubs is None:
            self._n_hubs = (int(len(np.unique(self.extra_owner)))
                            if len(self.extra_owner) else 0)
        return self._n_hubs

    def hub_merge(self) -> Tuple[np.ndarray, np.ndarray]:
        """(extra_slot int32[n_extras], hub_rows int32[n_hubs]): each
        extra row's index into the compact hub-owner list, and that
        list itself — the hop's OR-merge targets (a packed frontier
        cannot scatter-max duplicate owners: max of packed BYTES loses
        bits, so the merge runs per-bit over a compact per-hub
        accumulator and lands with ONE unique-row scatter; see
        _scatter_or_rows)."""
        if not len(self.extra_owner):
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        owners, slot = np.unique(self.extra_owner, return_inverse=True)
        return slot.astype(np.int32), owners.astype(np.int32)

    def hub_expansion(self) -> Tuple[np.ndarray, np.ndarray]:
        """(ecnt int32[n+1], e0 int32[n+1]): per-vertex extra-row run —
        hub vertex v owns rows [e0[v], e0[v] + ecnt[v]) in addition to
        its main row v (extras of one owner are contiguous by
        construction: EllIndex.build appends them in owner order).
        Non-hubs: ecnt 0, e0 n_rows.  The batched sparse kernel uses
        this to push out of a hub's spilled slots exactly."""
        ecnt = np.zeros(self.n + 1, np.int32)
        e0 = np.full(self.n + 1, self.n_rows, np.int32)
        if len(self.extra_owner):
            owners, first = np.unique(self.extra_owner, return_index=True)
            cnts = np.bincount(self.extra_owner, minlength=self.n)
            ecnt[:self.n] = cnts[:self.n].astype(np.int32)
            # unclaimed growth spares carry the out-of-range spare
            # sentinel as owner — scattering THAT into e0 would walk
            # off the array; they have no expansion until claimed
            real = owners < self.n
            e0[owners[real]] = (self.n + first[real]).astype(np.int32)
        return ecnt, e0

    def tables_host(self) -> List[Tuple[np.ndarray, np.ndarray]]:
        """Every (nbr, et) bucket pair, the in-table's buckets then the
        out-table's: table index ``side * n_buckets + b`` (what an
        absorb plan keys its replacement rows by)."""
        return list(zip(self.bucket_nbr, self.bucket_et)) \
            + list(zip(self.out_nbr, self.out_et))

    def kernel_args(self):
        """The device arrays every args-style kernel takes positionally:
        (owner, *bucket_nbr, *bucket_et, *out_nbr, *out_et) — the
        ``tables`` the kernels split with _read_sides."""
        nbr_dev, et_dev, onbr_dev, oet_dev, owner_dev = \
            self.device_arrays()
        return (owner_dev, *nbr_dev, *et_dev, *onbr_dev, *oet_dev)


# ====================================================================
# Kernels.  Built per (shape_sig, steps, etypes) and cached by the
# runtime; the ELL tables are passed as ARGUMENTS (not closed over), so
# one jitted fn serves every mirror whose tables have the same shapes,
# and the persistent compilation cache hits across processes.  (Closing
# over the tables embeds ~100 MB as HLO constants — measured 64 s
# compiles and 6x slower execution on v5e.)
# ====================================================================
def _etype_ok(jnp, et_col, mags: Tuple[int, ...]):
    """bool like ``et_col``: the slot's etype magnitude is one of
    ``mags``, compared in the column's own integer type (a magnitude
    the type cannot hold matches no slot of this mirror)."""
    ok = jnp.zeros(et_col.shape, dtype=bool)
    top = np.iinfo(et_col.dtype).max
    for t in mags:
        if 0 < t <= top:
            ok = ok | (et_col == np.asarray(t, et_col.dtype))
    return ok


def _split_signs(etypes: Tuple[int, ...]):
    """(magnitudes of the positive members, of the negative ones)."""
    return (tuple(t for t in etypes if t > 0),
            tuple(-t for t in etypes if t < 0))


def sides_read(etypes: Tuple[int, ...]) -> int:
    """How many of the two tables a step over ``etypes`` reads: one
    for a one-signed OVER set (forwards, or REVERSELY, which flips the
    whole set), two for a mixed-sign one (GO ... BIDIRECT names every
    edge type on both signs)."""
    return sum(1 for mags in _split_signs(etypes) if mags)


def _read_sides(etypes: Tuple[int, ...], tables, nb: int,
                push: bool = False):
    """The tables a frontier step over ``etypes`` reads, as
    (nbrs, ets, magnitudes) per table.  ``tables`` =
    (*in_nbr, *in_et, *out_nbr, *out_et), ``nb`` buckets each
    (EllIndex.kernel_args()[1:]).  A PULL over +t gathers the sources
    of in-edges (in-table), over -t those of out-edges (out-table); a
    PUSH — and every pair-list kernel, which expands a frontier row
    into its neighbours — takes its targets from the opposite table.
    A table with no member in ``etypes`` is not read at all."""
    ins = (tables[:nb], tables[nb:2 * nb])
    outs = (tables[2 * nb:3 * nb], tables[3 * nb:4 * nb])
    if push:
        ins, outs = outs, ins
    return [(*table, mags)
            for table, mags in zip((ins, outs), _split_signs(etypes))
            if mags]


def _segmented_hub_iota(jnp, cnt_raw, e0_vals, qid, EX: int,
                        sentinel: int, BIG_Q):
    """The hub-expansion core shared by the single-device and mesh
    sparse kernels: per-pair extra-row counts + first-row ids ->
    up to EX (row, qid) expansion pairs via a segmented iota over the
    compacted runs, with a wrap-free budget check.

    Per-pair counts clamp to c_lim (chosen so the int32 cumsum cannot
    wrap past 2^31 and silently CLEAR the overflow flag); any clamped
    entry flags overflow directly.  Dropped runs (rank >= EX) always
    coincide with the overflow flag, so results are never silently
    short."""
    c_in = cnt_raw.shape[0]
    c_lim = jnp.int32(max(1, (2**31 - 1) // max(c_in, 1)))
    over_big = jnp.any(cnt_raw > c_lim)
    cnt = jnp.minimum(cnt_raw, c_lim)
    tot = jnp.cumsum(cnt)
    total = tot[-1]
    overflow = over_big | (total > EX)
    s = (tot - cnt).astype(jnp.int32)
    has = cnt > 0
    rank = jnp.cumsum(has.astype(jnp.int32)) - 1
    pos = jnp.where(has, rank, EX)
    run_e0 = jnp.zeros((EX,), jnp.int32).at[pos].set(e0_vals,
                                                     mode="drop")
    run_q = jnp.full((EX,), BIG_Q).at[pos].set(qid, mode="drop")
    run_s = jnp.full((EX,), jnp.int32(2**30)).at[pos].set(s, mode="drop")
    j = jnp.arange(EX, dtype=jnp.int32)
    seg = jnp.searchsorted(run_s, j, side="right").astype(jnp.int32) - 1
    segc = jnp.clip(seg, 0, EX - 1)
    live = (j < jnp.minimum(total, EX)) & (seg >= 0)
    rows = jnp.where(live, run_e0[segc] + (j - run_s[segc]),
                     jnp.int32(sentinel))
    qs = jnp.where(live, run_q[segc], BIG_Q)
    return rows, qs, overflow


# ====================================================================
# Bit-packed (1-bit-per-lane) frontier — THE frontier layout.
#
# A frontier on the device is a uint8 [n_rows+1, W = ceil(B/8)] matrix:
# bit k of word j is query lane j*8+k.  A hop's D row-gathers are the
# cost, and a gathered row carries B bits of information, so a byte per
# lane would move 8x the traffic for nothing (the graph-accelerator
# survey's memory-bound analysis, PAPERS.md arxiv 1902.10130; its
# on-chip roofline share is not measured on today's code — ROADMAP
# S4).  The hop max is a bitwise OR and the etype mask a 0/1 word
# multiply, both free against the gather.  Nothing outside this module
# knows the layout: callers size with lanes_width, enter with
# pack_lanes_host and leave with unpack_lanes_host.
#
# The one op that needs care is the hub fix-up scatter: a scatter-max
# of packed BYTES onto duplicate owners drops bits (max(0b01, 0b10) =
# 0b10, OR = 0b11).  The merge instead max-scatters each extra row's 8
# BIT-PLANES into a compact [n_hubs, 8, W] accumulator (per-plane
# values are 0/1, so max IS or), recombines, and lands with one
# unique-row scatter — work stays O(n_extras x B), never O(n x B).
# ====================================================================
LANE_BITS = 8


def lanes_width(B: int) -> int:
    """uint8 words per frontier row for a B-query batch."""
    return -(-B // LANE_BITS)


def pack_lanes_host(f: np.ndarray) -> np.ndarray:
    """[R, B] truthy -> uint8 [R, ceil(B/8)] (little bit order: bit k
    of word j is lane j*8+k — matches the device pack/unpack below)."""
    return np.packbits(np.asarray(f) != 0, axis=1, bitorder="little")


def unpack_lanes_host(fp: np.ndarray, B: int) -> np.ndarray:
    """uint8 [R, W] -> bool [R, B]."""
    return np.unpackbits(fp, axis=1, bitorder="little")[:, :B] > 0


def _unpack_lanes(jnp, fp):
    """Device unpack: uint8 [R, W] -> int8 0/1 [R, W*8]."""
    R, W = fp.shape
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint8)
    bits = (fp[:, :, None] >> shifts[None, None, :]) & jnp.uint8(1)
    return bits.reshape(R, W * LANE_BITS).astype(jnp.int8)


def _pack_lanes(jnp, bits):
    """Device pack: truthy [R, B] (B % 8 == 0) -> uint8 [R, B//8]."""
    R, B = bits.shape
    w = jnp.asarray((1 << np.arange(LANE_BITS)).astype(np.uint8))
    b8 = (bits > 0).astype(jnp.uint8).reshape(R, B // LANE_BITS,
                                              LANE_BITS)
    return jnp.sum(b8 * w[None, None, :], axis=2, dtype=jnp.uint8)


def _scatter_or_rows(jnp, nxt, vals, slot, rows):
    """OR packed rows ``vals`` [k, W] into ``nxt`` at target rows
    ``rows[slot[i]]``: bit-plane max into a compact [n_slots, 8, W]
    accumulator (duplicate slots OR correctly because per-plane values
    are 0/1), then one gather-OR-set at the UNIQUE target rows.  Rows
    >= nxt.shape[0] are drop sentinels (padded slots)."""
    n_slots = rows.shape[0]
    if n_slots == 0:
        return nxt
    W = vals.shape[1]
    shifts = jnp.arange(LANE_BITS, dtype=jnp.uint8)
    planes = (vals[:, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    acc = jnp.zeros((n_slots, LANE_BITS, W), jnp.uint8) \
        .at[slot].max(planes)
    # distinct bit positions per plane: the sum IS the bitwise OR
    merged = jnp.sum(acc << shifts[None, :, None], axis=1,
                     dtype=jnp.uint8)
    safe = jnp.minimum(rows, nxt.shape[0] - 1)
    upd = nxt[safe] | merged
    return nxt.at[rows].set(upd, mode="drop")


def _pull_segments(D: int, n_main: int, reach):
    """What a bucket's pull loops over its main rows, as
    [(rows, c0, c1)]: the leading ``rows`` rows over the columns
    [c0, c1), the LAST columns first, so the rows only grow down the
    list.  Neighbouring ranges of one reach are one entry, a range no
    row reaches is none.  None where there is nothing to save: no reach
    was given, or every range reaches every main row, and the bucket
    takes one loop over all its rows."""
    if reach is None or all(r >= n_main for r in reach):
        return None
    bounds = _range_bounds(D, len(reach))
    segs: List[Tuple[int, int, int]] = []
    for r in reversed(range(len(reach))):
        # a reach never grows with the column by construction; hold it
        # to that whatever was handed in
        rows = max(min(reach[r], n_main), segs[-1][0] if segs else 0)
        if rows == 0:
            continue
        if segs and segs[-1][0] == rows:
            segs[-1] = (rows, bounds[r], segs[-1][2])
        else:
            segs.append((rows, bounds[r], bounds[r + 1]))
    return segs


def _bucket_swept(nb: int, D: int, n_main: int, reach) -> int:
    """Slots _bucket_expand_packed gathers in a bucket of ``nb`` rows:
    its segments' rows x columns and the rows past ``n_main`` whole."""
    segs = _pull_segments(D, n_main, reach)
    if segs is None:
        return nb * D
    return sum(rows * (c1 - c0) for rows, c0, c1 in segs) \
        + (nb - n_main) * D


def _bucket_expand_packed(jnp, jax, fp, nbr, et, mags, reach=None,
                          n_main: int = 0):
    """Expand one bucket of one table: OR over D slot word gathers, the
    OVER mask a 0/1 uint8 multiply per word.  THE hop inner loop —
    shared by the single-chip and sharded kernels so their semantics
    cannot skew.

    ``reach`` (EllIndex.reach's entry for this table and bucket, with
    ``n_main`` the bucket's rows < n) cuts the loop by column range:
    a range gathers the leading rows that hold a real slot there and
    no others, since every slot it skips names the pad row, which is
    zero.  The ranges run from the last to the first over ONE
    accumulator that grows with the reach (_pull_segments), so they
    share the bucket's D turns and no buffer spans the bucket before
    the first range needs it.  The rows past ``n_main`` (hub extra rows
    and growth spares, which stand in no order) take every column in a
    loop of their own.  ``None``, or a reach that saves nothing, is one
    loop over every row and every column."""
    nb, D = nbr.shape
    nbr_T = nbr.T
    ok_T = _etype_ok(jnp, et, mags).T.astype(jnp.uint8)
    W = fp.shape[1]
    segs = _pull_segments(D, n_main, reach)

    if segs is None:
        def body(j, acc):
            g = fp[nbr_T[j]]                   # [nb, W] word-gather
            return acc | (g * ok_T[j][:, None])

        acc0 = jnp.zeros((nb, W), dtype=jnp.uint8)
        return jax.lax.fori_loop(0, D, body, acc0)

    def sweep(acc, lo, c0, c1):
        """OR into ``acc`` the columns [c0, c1) of the rows
        [lo, lo + acc's rows)."""
        rows = acc.shape[0]

        def body(j, acc):
            idx = jax.lax.dynamic_slice(nbr_T, (j, lo), (1, rows))[0]
            ok = jax.lax.dynamic_slice(ok_T, (j, lo), (1, rows))[0]
            return acc | (fp[idx] * ok[:, None])

        return jax.lax.fori_loop(c0, c1, body, acc)

    acc = jnp.zeros((0, W), dtype=jnp.uint8)
    for rows, c0, c1 in segs:
        acc = sweep(jnp.pad(acc, ((0, rows - acc.shape[0]), (0, 0))),
                    0, c0, c1)
    acc = jnp.pad(acc, ((0, n_main - acc.shape[0]), (0, 0)))
    if nb == n_main:
        return acc
    tail = sweep(jnp.zeros((nb - n_main, W), dtype=jnp.uint8), n_main,
                 0, D)
    return jnp.concatenate([acc, tail], axis=0)


def _buckets_expand_packed(jnp, jax, fp, sides, n: int = 0,
                           reaches=None):
    """Per bucket, the OR of its expansion over each table in ``sides``
    (_read_sides: one table for a one-signed OVER set), each cut by
    its own entry of ``reaches`` (_side_reaches, ``n`` the real
    vertices; None: every row at every column).  Each bucket
    sits in a named scope, so a device trace's op names say which
    bucket a loop or fusion is (``hop/bucket_w<D>`` in the HLO
    op_name)."""
    outs = []
    mains = _main_rows(n, sides[0][0]) if sides else []
    for b in range(len(mains)):
        with jax.named_scope(f"hop/bucket_w{sides[0][0][b].shape[1]}"):
            acc = None
            for k, (nbrs, ets, mags) in enumerate(sides):
                o = _bucket_expand_packed(
                    jnp, jax, fp, nbrs[b], ets[b], mags,
                    reaches[k][b] if reaches is not None else None,
                    mains[b])
                acc = o if acc is None else acc | o
            outs.append(acc)
    return outs


def _side_reaches(ell, etypes: Tuple[int, ...]):
    """``ell.reach`` for the tables a PULL over ``etypes`` reads, in
    _read_sides' order; None where the index carries none."""
    if ell.reach is None:
        return None
    return [reach for reach, mags in zip(ell.reach, _split_signs(etypes))
            if mags]


def _hop_body_packed(jnp, jax, n: int, n_extras: int, sides,
                     eslot, hrows, fp, reaches=None):
    """One packed frontier advance by PULL: fp [n_rows+1, W] uint8 ->
    same, over the tables in ``sides`` (_read_sides), each bucket's
    loop cut by ``reaches`` (_side_reaches; None sweeps whole).  The
    hub merge sits in a named scope of its own (``hop/hub_merge``)."""
    outs = _buckets_expand_packed(jnp, jax, fp, sides, n, reaches)
    if not outs:
        return jnp.zeros_like(fp)
    nxt = jnp.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]
    if n_extras:
        extras = nxt[n:]
        with jax.named_scope("hop/hub_merge"):
            nxt = _scatter_or_rows(jnp, nxt, extras, eslot, hrows)
    pad = jnp.zeros((1, fp.shape[1]), dtype=jnp.uint8)
    return jnp.concatenate([nxt, pad], axis=0)


def make_batched_go_lanes_kernel(ell: EllIndex, steps: int,
                                 etypes: Tuple[int, ...],
                                 upto: bool = False,
                                 donate: bool = False,
                                 count: bool = False):
    """Batched GO — the dense path.

    fn(f0p uint8 [n_rows+1, W], eslot int32[n_extras],
       hrows int32[n_hubs], *tables) -> uint8 [n_rows+1, W] frontier
    after ``steps-1`` advances (lane q of word j is query j*8+q;
    unpack_lanes_host inverts; the final hop's edge set is
    frontier[src] & etype_ok, materialised by the caller — same split
    as kernels._go_body).  ``tables`` = (*bucket_nbr, *bucket_et,
    *out_nbr, *out_et) from EllIndex.kernel_args()[1:], of which the
    program reads those ``etypes`` has a member for (_read_sides); only
    static shapes are read off
    ``ell``, so the compiled fn serves any mirror with the same
    shape_sig.  With ``upto`` the output is the OR of every depth's
    frontier (0..steps-1 — GO UPTO's pre-final-hop vertex set; one
    extra OR per advance, free against the gather cost).  With
    ``count`` the signature gains a mirror-resident per-row final-hop
    degree vector and the output collapses to int32 [W*8] per-query
    candidate-edge counts — the COUNT(*) pushdown's fetch is B words
    instead of a bitmap:
    fn(f0p, eslot, hrows, deg int32[n_rows+1], *tables)."""
    import jax
    import jax.numpy as jnp
    n, n_extras, nb = ell.n, len(ell.extra_owner), len(ell.bucket_nbr)
    reaches = _side_reaches(ell, etypes)

    def advance(f0p, eslot, hrows, tables):
        sides = _read_sides(etypes, tables, nb)

        def one(_, f):
            return _hop_body_packed(jnp, jax, n, n_extras, sides,
                                    eslot, hrows, f, reaches)

        def one_acc(_, carry):
            f, acc = carry
            nxt = one(None, f)
            return nxt, acc | nxt

        if steps <= 1:
            return f0p
        if upto:
            _, out = jax.lax.fori_loop(0, steps - 1, one_acc, (f0p, f0p))
            return out
        return jax.lax.fori_loop(0, steps - 1, one, f0p)

    if count:
        def go(f0p, eslot, hrows, deg, *tables):
            out = advance(f0p, eslot, hrows, tables)
            bits = _unpack_lanes(jnp, out).astype(jnp.int32)
            # deg is zero for hub extra rows and the pad row, so junk
            # extras never count; [R1] @ [R1, B] -> [B]
            return deg @ bits
    else:
        def go(f0p, eslot, hrows, *tables):
            return advance(f0p, eslot, hrows, tables)

    # ``donate`` is the RUNTIME's dispatch configuration: _launch_dense
    # builds f0p fresh per dispatch, so handing the buffer to XLA lets
    # the hop loop reuse its HBM instead of holding both live (jaxaudit
    # verifies the claim on the traced pjit).  OPT-IN because a donated
    # frontier is CONSUMED: callers that re-dispatch one frontier (bench
    # drivers, parity tests) must keep the default
    return jax.jit(go, donate_argnums=(0,) if donate else ())


# ====================================================================
# Continuous hop-boundary batching — the seat-map kernels
# (docs/admission.md "Continuous dispatch").
#
# The windowed kernels above bake the hop count into the program and
# run a whole batch start-to-finish; the serving tier then pays a
# pooling wait + a device-idle gap between windows.  Continuous mode
# instead keeps ONE resident packed frontier pair on the device per
# (space, OVER set) stream and dispatches a SINGLE hop at a time; the
# 1-bit lane dimension is the seat map (graph/batch_dispatch.py
# _LaneLedger): a finishing query's lane bits clear at its last hop
# and a queued arrival's start frontier is scatter-merged into the
# freed lanes before the next hop dispatches.  No recompile moves:
# the lane width stays on the go_batch_widths rung ladder, only lane
# OCCUPANCY changes — and occupancy is data, not shape.
#
#   make_continuous_hop_kernel   one frontier advance + UPTO union:
#                                (fp, accp) -> (hop(fp), accp|hop(fp),
#                                info); both carriers donated (the
#                                stream owns them, nothing else ever
#                                reads the old generation of the pair).
#                                The program FOLLOWS THE FRONTIER: it
#                                counts the live slot rows it was
#                                handed and, on the device, takes one
#                                of two exact branches —
#                                  push  (<= HOP_PUSH_ROWS live slot
#                                        rows) each live row ORs its
#                                        words into its out-neighbours'
#                                        rows of a zeroed frontier:
#                                        work ~ the live rows' slots
#                                        in the table of its targets;
#                                  pull  (over the budget) the full
#                                        _hop_body_packed sweep of every
#                                        slot of the table of its
#                                        sources (each direction has a
#                                        table of its own: _read_sides).
#                                ``info`` says which ran and what it
#                                visited; nothing on the host chooses.
#                                The choice lives in ONE place,
#                                _make_frontier_step: every level of
#                                the batched BFS lanes program takes
#                                the same step under the same budget
#   make_lane_join_kernel        scatter-ADD of single lane bits into
#                                FREE lanes: a joiner's start rows, or
#                                its first frontier's where the seat
#                                takes its first hop (the host holds a
#                                start's neighbours).  Exact by the clear
#                                contract: a freed lane's bit is zero
#                                in every word it touches, and the host
#                                dedups (row, lane) pairs, so each add
#                                lands on a zero bit — add IS or (the
#                                same argument as
#                                _upload_frontier_packed's build)
#   make_lane_clear_kernel       AND with a per-word keep mask: the
#                                leavers' lane bits drop from both
#                                carriers in one fused op
#   make_lane_extract_kernel     pack each leaving LANE down the real
#                                vertex rows, eight rows a byte, plane
#                                by plane (per lane choosing the
#                                exact-depth frontier or the UPTO
#                                accumulator) — the d2h fetch is n / 8
#                                bytes per leaver, never a word column
#                                and never the matrix
#   make_lane_count_kernel       set bits per lane over the real vertex
#                                rows: a leaver whose statement is a
#                                k-hop neighbourhood count (YIELD
#                                DISTINCT e._dst | YIELD COUNT(*)) rode
#                                k hops and leaves with this number —
#                                B int32 cross the link, no column does
# ====================================================================
# Push budget of the continuous hop, in live SLOT ROWS (a live vertex's
# main row plus its hub extra rows).  The push pays XLA's row scatter,
# which the TPU runs one index after the other: 48.5 ns a slot on the
# v5e (24.8 us a 512-wide row; 1.5 us an 8-wide one, where the row's
# own turn of the loop is most of it) beside 2.2 ms fixed, against the
# pull's 2.8 ns a slot over every slot its reach leaves of the one
# table it reads (57.3 ms at 20.18 M of 24.84 M since PR 39; 69.6 ms
# for the whole table).  The budget is where the WORST push, every
# live row 512 wide, still undercuts the sweep: when it was set (PR
# 35) 2.2 ms + R x 24.8 us < 68.5 ms held to R = 2,670, and 2,048 rows
# (1.05 M slots, 53 ms) is the power of two under it; against PR 39's
# 57.3 ms the same rule gives R = 2,220, so 2,048 stands, with 4 ms of
# room where it had 15.  While a row held both directions the
# same rule gave 4,096: a sweep of 113.4 ms at 42.2 M slots, a push of
# 2.5 ms + 49.7 ns a slot, R < 4,360 (PERF.md §6, PR 35's chip runs;
# PR 25 first read 114 ms and 47 ns).  A speed choice only: both
# branches are exact.
HOP_PUSH_ROWS = 2048

# info vector of the continuous hop program (int32[3])
HOP_INFO_SPARSE, HOP_INFO_ROWS, HOP_INFO_SLOTS = 0, 1, 2


def table_slots(ell: EllIndex, etypes: Tuple[int, ...]) -> int:
    """Slots of the table(s) one pull over ``etypes`` reads (the two
    tables share their shapes): what a pull REPORTS
    (info[HOP_INFO_SLOTS], the bytes models of benchmark/ count the
    table), and what it gathers where the index carries no reach."""
    return sides_read(etypes) * int(
        sum(nbr.shape[0] * nbr.shape[1] for nbr in ell.bucket_nbr))


def swept_slots(ell: EllIndex, etypes: Tuple[int, ...]) -> int:
    """Slots one pull over ``etypes`` GATHERS: per table it reads and
    bucket, the reach prefixes times their column ranges and the rows
    past n whole (_bucket_swept) — table_slots less the padding the
    reach lets the loops skip.  Static, as the loops are."""
    reaches = _side_reaches(ell, etypes)
    if reaches is None:
        return table_slots(ell, etypes)
    mains = _main_rows(ell.n, ell.bucket_nbr)
    return int(sum(
        _bucket_swept(nbr.shape[0], nbr.shape[1], n_main, reach)
        for side in reaches
        for nbr, n_main, reach in zip(ell.bucket_nbr, mains, side)))


def _set_positions(jnp, mask, cap: int, group: int = 128):
    """Ascending indices of the first ``cap`` set entries of ``mask``
    (bool[R]), padded with R — jnp.nonzero(size=cap) in two levels,
    because a running sum over all R entries costs the TPU compiler
    half a minute at R = 670 k (PERF.md §6, PR 25) and a group of 128
    costs it nothing: count per group, find each wanted entry's group
    by its rank, then its place inside the group from the group's own
    running count (a 0/1 product with a triangle, exact in any matmul
    precision: the sums stay under 2^8)."""
    R = mask.shape[0]
    G = -(-R // group)
    m2 = jnp.pad(mask, (0, G * group - R)).reshape(G, group)
    per = jnp.sum(m2, axis=1, dtype=jnp.int32)
    upto = jnp.cumsum(per)
    j = jnp.arange(cap, dtype=jnp.int32)
    g = jnp.searchsorted(upto, j + 1, side="left").astype(jnp.int32)
    gc = jnp.minimum(g, G - 1)
    k = j - (upto[gc] - per[gc])            # rank inside the group
    tri = jnp.asarray(np.triu(np.ones((group, group), np.float32)))
    run = m2[gc].astype(jnp.float32) @ tri  # [cap, group] running count
    # entries whose running count is still <= k precede the wanted one
    pos = jnp.sum(run <= k[:, None].astype(jnp.float32), axis=1,
                  dtype=jnp.int32)
    return jnp.where(g < G, gc * group + pos, jnp.int32(R))


def _hop_push_packed(jnp, jax, n: int, n_rows: int, sides, owner, fp,
                     row_live, counts, cap: int):
    """One packed frontier advance by PUSH: every live slot row ORs its
    source's word row into the rows of its out-neighbours.

    ``row_live`` bool[n_rows] marks the live slot rows (a live vertex's
    main row and its hub extra rows — ``owner`` int32[n_extras] names
    an extra row's vertex), ``counts`` their number per bucket, at most
    ``cap`` in all.  ``sides`` are the tables the targets come from
    (_read_sides with push=True: a row's out-neighbours over +t are
    its out-table slots of magnitude t), so a row's scatter is as wide
    as its bucket in ONE table.  Targets inside one slot row
    take one value (old | source), so a plain set is exact there even
    where a row names a neighbour twice; rows run one after the other,
    so a target shared by two rows ORs both.  Rows >= n of the result
    stay zero (no slot points there) and the pad row is never written:
    masked and sentinel slots scatter out of range and drop."""
    R1 = n_rows + 1
    # bucket b's live rows are the run [sum(counts[:b]),
    # sum(counts[:b+1])) of the ascending list
    rows = _set_positions(jnp, row_live, cap)
    src = rows
    if owner is not None:
        src = jnp.where(rows < n, rows,
                        owner[jnp.clip(rows - n, 0, owner.shape[0] - 1)])
    # every live row's word row, read once here, so the loops below
    # touch ONE frontier-sized array, the carrier they write
    vals = fp[jnp.minimum(src, R1 - 1)]                # [cap, W]
    nxt = jnp.zeros_like(fp)
    rows = jnp.concatenate([rows, jnp.full((cap,), n_rows, jnp.int32)])
    lo = jnp.int32(0)
    bstart = 0
    for b, cnt in enumerate(counts):
        nb_rows, D = sides[0][0][b].shape
        with jax.named_scope(f"hop/push_w{D}"):
            # the bucket's live slot rows in one gather (entries past
            # ``cnt`` belong to later buckets and are never looped
            # over).  One gather, not a row read per loop turn: the TPU
            # keeps a table narrower than its 128 lanes column-major,
            # and a row read inside a loop makes the compiler lay the
            # WHOLE table out row-major first, 2 x 197 MB of copies a
            # hop for the 8-wide bucket of a 670 k-row table (PR 25,
            # the TPU compiler's memory analysis); a gather reads it
            # where it lies
            loc = jnp.clip(jax.lax.dynamic_slice(rows, (lo,), (cap,))
                           - bstart, 0, nb_rows - 1)
            tgts = [jnp.where(_etype_ok(jnp, ets[b][loc], mags),
                              nbrs[b][loc], R1)
                    for nbrs, ets, mags in sides]
            tgts = tgts[0] if len(tgts) == 1 \
                else jnp.concatenate(tgts, axis=1)

            def body(i, nxt, tgts=tgts, lo=lo):
                tgt = tgts[i]                              # [D]
                cur = nxt[jnp.minimum(tgt, R1 - 1)]        # [D, W]
                return nxt.at[tgt].set(cur | vals[lo + i][None, :],
                                       mode="drop")

            nxt = jax.lax.fori_loop(0, cnt, body, nxt)
        lo = lo + cnt
        bstart += nb_rows
    return nxt


def _make_frontier_step(ell: EllIndex, etypes: Tuple[int, ...],
                         push_rows: Optional[int] = None):
    """THE place where a frontier advance chooses push or pull — the
    continuous hop (make_continuous_hop_kernel) and every BFS level
    (make_batched_bfs_lanes_kernel) call the step this returns, so the
    rule exists once.

    step(fp uint8 [n_rows+1, W], eslot, hrows, tables) ->
    (next frontier, sparse bool, live slot rows int32, their slots
    int32).  It measures the frontier it is handed — the live slot
    rows: a real row v < n with any lane bit set, plus the hub extra
    rows of such a v (rows >= n of ``fp`` hold a previous pull's
    partial ORs and are never read as sources) — and takes, on the
    device, the push (_hop_push_packed) when they number at most
    ``push_rows`` (HOP_PUSH_ROWS unless a test passes its own), else
    the pull over every slot (_hop_body_packed, as the windowed
    kernels run it).  Each reads only the table(s) ``etypes`` has a
    member for (_read_sides): the pull the sources' side, the push the
    targets'.  Both are exact on rows < n and the pad row; they
    differ only in what they leave in the extra rows, which nothing
    reads.  ``slots`` is the live rows' widths in the tables read,
    whichever branch ran: a pull visited table_slots(ell, etypes)."""
    import jax
    import jax.numpy as jnp
    n, n_rows = ell.n, ell.n_rows
    n_extras, nb = len(ell.extra_owner), len(ell.bucket_nbr)
    n_sides = sides_read(etypes)
    reaches = _side_reaches(ell, etypes)
    if push_rows is None:
        push_rows = HOP_PUSH_ROWS

    def step(fp, eslot, hrows, tables):
        def pull(fp):
            return _hop_body_packed(jnp, jax, n, n_extras,
                                    _read_sides(etypes, tables, nb),
                                    eslot, hrows, fp, reaches)

        if not nb or not n_sides:      # empty graph or OVER set
            return pull(fp), jnp.bool_(False), jnp.int32(0), jnp.int32(0)
        with jax.named_scope("hop/frontier"):
            row_live = jnp.any(fp[:n] != 0, axis=1)
            owner = None
            if n_extras:
                # an unclaimed growth spare's owner is the spare
                # sentinel (>= n): it belongs to nobody
                owner = hrows[eslot]
                row_live = jnp.concatenate(
                    [row_live, (owner < n)
                     & row_live[jnp.minimum(owner, n - 1)]])
            counts, slots, b0 = [], jnp.int32(0), 0
            for nbr in tables[:nb]:
                c = jnp.sum(row_live[b0:b0 + nbr.shape[0]],
                            dtype=jnp.int32)
                counts.append(c)
                slots = slots + c * (nbr.shape[1] * n_sides)
                b0 += nbr.shape[0]
            live_rows = sum(counts)
            sparse = live_rows <= push_rows

        def push(fp):
            return _hop_push_packed(
                jnp, jax, n, n_rows,
                _read_sides(etypes, tables, nb, push=True), owner, fp,
                row_live, counts, push_rows)

        nxt = jax.lax.cond(sparse, push, pull, fp)
        return nxt, sparse, live_rows, slots

    return step


def make_continuous_hop_kernel(ell: EllIndex,
                               etypes: Tuple[int, ...],
                               donate: bool = True,
                               push_rows: Optional[int] = None):
    """One continuous-mode frontier advance.

    fn(fp uint8 [n_rows+1, W], accp uint8 [n_rows+1, W],
       eslot int32[n_extras], hrows int32[n_hubs], *tables)
    -> (fp', accp', info int32[3]): fp' is one packed hop of fp, accp'
    accumulates the union (the per-lane UPTO carrier — exact-depth
    lanes simply never read it).  Unlike the windowed kernels the hop
    count is NOT baked in: one jitted program serves every mix of
    per-query depths, so the cache key space per (mirror, OVER) family
    is ONE entry per lane-width rung.

    The advance is _make_frontier_step's: a push out of the live slot
    rows or the pull over every slot, chosen on the device.
    ``info`` = [1 if the push ran else 0, live slot rows, ELL slots
    the hop visited (the live rows' widths, or every slot of the
    table(s) ``etypes`` reads)]; the session reads it without waiting
    on the hop."""
    import jax
    import jax.numpy as jnp
    all_slots = table_slots(ell, etypes)
    step = _make_frontier_step(ell, etypes, push_rows)

    def hop(fp, accp, eslot, hrows, *tables):
        nxt, sparse, live_rows, slots = step(fp, eslot, hrows, tables)
        info = jnp.stack([sparse.astype(jnp.int32), live_rows,
                          jnp.where(sparse, slots,
                                    jnp.int32(all_slots))])
        return nxt, accp | nxt, info

    return jax.jit(hop, donate_argnums=(0, 1) if donate else ())


def make_lane_join_kernel(ell: EllIndex, donate: bool = True):
    """Merge queued arrivals' frontiers (a joiner's starts, or its
    first frontier where the seat takes its first hop:
    runtime._ContinuousGoSession.join) into their assigned free
    lanes: fn(fp, accp, rows int32[Sp], words int32[Sp], vals uint8[Sp])
    -> (fp', accp'), Sp a rung of LANE_JOIN_RUNGS.  ``vals[i]`` is the
    single lane bit 1 << (lane & 7)
    for row ``rows[i]`` / word ``words[i]``; padding scatters target the
    pad row, which is re-zeroed (it is every sentinel slot's gather
    source and must stay all-zero).  The accumulator gets the same bits:
    an UPTO union includes depth 0 (and an UPTO lane is always seated
    with its starts)."""
    import jax
    import jax.numpy as jnp
    pad_row = ell.n_rows

    def join(fp, accp, rows, words, vals):
        with jax.named_scope("lane/join"):
            fp = fp.at[rows, words].add(vals)
            fp = fp.at[pad_row, :].set(0)
            accp = accp.at[rows, words].add(vals)
            accp = accp.at[pad_row, :].set(0)
        return fp, accp

    return jax.jit(join, donate_argnums=(0, 1) if donate else ())


def make_lane_clear_kernel(donate: bool = True):
    """Drop leaving lanes from both resident carriers:
    fn(fp, accp, keep uint8[W]) -> (fp & keep, accp & keep).  ``keep``
    has the leavers' lane bits LOW; the freed bits are what makes the
    join kernel's scatter-add exact on reseat."""
    import jax

    def clear(fp, accp, keep):
        with jax.named_scope("lane/clear"):
            return fp & keep[None, :], accp & keep[None, :]

    return jax.jit(clear, donate_argnums=(0, 1) if donate else ())


# The row counts the join program is compiled for: a scatter table is
# padded to the least rung that holds it, and one of more rows than the
# top rung goes in as several programs of that rung.  A factor of four
# apart: a pad row costs the device what a real one does (the TPU
# scatters one index after the other, ~50 ns each into each carrier),
# so the worst padding, 3/4 of 512 rows, is 40 us.  The ladder ends at
# 512 because of what a program weighs on the device while it is
# loaded (PERF.md section 7, "Left by PR 37" (a)): compiled for the
# v5e at the cells' table, 0.14 MB of code at 8 rows and 0.24-0.26 MB
# at 16 to 512, but 2.3 MB from 1,024 on and 5.5 MB at 8,192, where
# the compiler sorts the indices first (tests/test_hop_compile_tpu.py).
LANE_JOIN_RUNGS = (8, 32, 128, 512)


def lane_join_rung(rows: int) -> int:
    """The least of LANE_JOIN_RUNGS that holds ``rows`` scatter rows,
    the top rung for more (the caller splits its table there)."""
    return next((r for r in LANE_JOIN_RUNGS if rows <= r),
                LANE_JOIN_RUNGS[-1])


def lane_bitmap_bytes(n: int) -> int:
    """Bytes of one leaving lane's bitmap: a bit a real vertex row,
    ceil(n / 8) bytes rounded up to whole 64-bit words (7 bytes at
    most), so the host counts its set bits a word at a time."""
    return 8 * (-(-n // 64))


def lane_bitmap_rows(at: np.ndarray, bit: np.ndarray,
                     n: int) -> np.ndarray:
    """The vertex rows of set bits of a lane's bitmap: bit ``bit`` of
    byte ``at`` is row ``bit * lane_bitmap_bytes(n) + at`` — the
    bitmap holds the rows plane by plane, eight planes of nb rows,
    plane k in bit k of every byte (make_lane_extract_kernel)."""
    return bit * lane_bitmap_bytes(n) + at


def lane_extract_rungs(B: int) -> Tuple[int, ...]:
    """The leaver counts the extract program is compiled for at the
    B-lane width rung: 4, the powers of two between 4 and B, then B.
    A leave cohort of k fetching leavers runs the least rung >= k
    (lane_extract_rung).  The rung is the program's one shape
    decision and depends on nothing but k.  The ladder starts at 4:
    the TPU pads an output of fewer rows to four, and on the v5e's
    link 1, 2 and 4 bitmaps of 80.8 kB take the same 0.5 ms; 8 take
    0.65, 16 0.85, 32 1.2, 64 1.8 and 128 3.0 (PERF.md section 6,
    PR 37).  A rung is half a second to a second of compile and half
    a megabyte of program on the device while it is loaded."""
    rungs, L = [], 4
    while L < B:
        rungs.append(L)
        L *= 2
    return tuple(rungs) + (B,)


def lane_extract_rung(k: int, B: int) -> int:
    """The least of lane_extract_rungs(B) that holds k <= B leavers."""
    return min(B, max(4, 1 << (k - 1).bit_length()))


def make_lane_extract_kernel(ell: EllIndex):
    """Hand back each leaving lane, bit-packed down the real vertex
    rows: fn(fp, accp, lanes int32[3, L]) -> uint8 [L, nb =
    lane_bitmap_bytes(n)].  ``lanes[:, l]`` = (word, bit, carrier) of
    leaver l: row l of the result holds bit ``bit`` of word ``word``
    of accp (carrier 1: an UPTO leaver reads the union accumulator)
    or of fp (carrier 0: an exact-depth leaver the frontier) over the
    rows v < n, eight rows a byte, PLANE BY PLANE: bit k of byte i is
    row k * nb + i (lane_bitmap_rows), so the pack is eight contiguous
    slices of the lane's column ORed together, shifted, and no row
    moves across the TPU's lanes (neighbouring rows in one byte,
    np.packbits' order, does that to every row: PERF.md section 6,
    PR 37).  The hub extra rows and growth spares (a pull's partial
    ORs), the pad row and the bits from n on are not packed: the host
    never reads them.  Padding leavers (word 0, bit 0, carrier 0)
    fill a rung; their rows are fetched and not read.

    One lane a turn of ``lax.map``, its word column cut out by one
    dynamic slice: the resident pair lies column-major on the TPU, so
    the column is one contiguous run and nothing frontier-sized is
    laid out anew (0.2 MB of temporaries and 0.5 MB of program at any
    rung, compiled for the v5e: tests/test_hop_compile_tpu.py).  Not
    donated: the carriers keep serving the lanes that stay seated —
    the output is a fresh fetch-sized buffer the host np.asarray()s
    while the NEXT hop computes (the double-buffer overlap,
    docs/admission.md)."""
    import jax
    import jax.numpy as jnp
    n = ell.n
    nb = lane_bitmap_bytes(n)

    def extract(fp, accp, lanes):
        def pack(lane):                         # (word, bit, carrier)
            # [1, rows]: the rows along the TPU's lanes, as they lie
            col = jnp.where(
                lane[2] != 0,
                jax.lax.dynamic_slice_in_dim(accp, lane[0], 1, 1),
                jax.lax.dynamic_slice_in_dim(fp, lane[0], 1, 1))[:n].T
            b = (col >> lane[1].astype(jnp.uint8)) & jnp.uint8(1)
            b = jnp.pad(b, ((0, 0), (0, nb * LANE_BITS - n)))
            out = b[:, :nb]
            for k in range(1, LANE_BITS):
                out = out | (b[:, k * nb:(k + 1) * nb] << jnp.uint8(k))
            return out[0]                       # [nb]

        with jax.named_scope("lane/extract"):
            return jax.lax.map(pack, lanes.T)   # [L, nb]

    return jax.jit(extract)


def make_lane_count_kernel(ell: EllIndex):
    """Set bits per lane of the resident frontier, over the real
    vertex rows: fn(fp uint8 [n_rows+1, W]) -> int32 [W*8], entry
    j*8+k the vertices whose bit k of word j is set.  A row v < n is
    one vertex of the mirror (its ELL row under the degree-bucket
    relabelling: every vertex has one, in both tables, a sink and a
    source too); rows n..n_rows-1 are the hub extra rows and growth
    spares, which after a pull hold partial ORs already merged into
    their owners' rows and are never read as sources, and row n_rows is
    the pad: none of them is counted, so a lane's count is its
    frontier's distinct vertices — what ``GO k STEPS ... YIELD DISTINCT
    e._dst | YIELD COUNT(*)`` returns after k hops (the distinct
    destinations of the k-th hop ARE the k-th frontier).  Not donated:
    the carrier keeps serving the lanes that stay seated.  The program
    reads n x W bytes and writes 4 B a lane (benchmark/count_bytes.py);
    its own jit name (``jit_count``) tells it from ``jit_hop`` in a
    device trace."""
    import jax
    import jax.numpy as jnp
    n = ell.n

    def count(fp):
        with jax.named_scope("lane/count"):
            real = fp[:n]
            # one plane a bit position: [n, W] 0/1 summed down the rows
            planes = [jnp.sum((real >> jnp.uint8(k)) & jnp.uint8(1),
                              axis=0, dtype=jnp.int32)
                      for k in range(LANE_BITS)]
            return jnp.stack(planes, axis=1).reshape(-1)

    return jax.jit(count)


# ====================================================================
# Incremental delta absorption — fold a committed edge overlay into
# the RESIDENT slot tables instead of rebuilding them (ROADMAP item 5,
# "serve writes at traffic").  Three pieces:
#
#   plan_ell_absorb        host: per affected owner row, recompute the
#                          full replacement slot rows (inserts fill
#                          sentinel slack in the main row and, for
#                          hubs, in the EXISTING extra rows — the spill
#                          path; deletes fold as tombstones: the dead
#                          slot's entry drops and the row compacts).
#                          None when a row outgrows its resident
#                          capacity (slot overflow past the hub
#                          budget) — the rebuild path then.
#   apply_ell_absorb_host  copy-on-write clone of the EllIndex with the
#                          replacement rows applied to the HOST bucket
#                          arrays (untouched buckets share memory; the
#                          old generation's arrays are never mutated —
#                          in-flight dispatches finish on them).
#   make_ell_absorb_kernel device: one row-scatter per bucket produces
#                          the next generation's device tables FROM the
#                          resident ones — the h2d upload is O(delta)
#                          replacement rows, never the O(table) full
#                          re-upload a rebuild pays (docs/roofline.md
#                          "The absorb cost model").  The resident
#                          input tables are NOT donated: they are the
#                          published generation in-flight dispatches
#                          still read (docs/durability.md).
#
# The conflict-free-scheduling framing (PAPERS.md arxiv 2202.11343)
# applies directly: updates are grouped host-side into whole
# replacement rows, so the device scatter has one writer per row and
# no read-modify-write hazards.
# ====================================================================
def _append_growth_spares(ell: EllIndex, slack: int) -> EllIndex:
    """Provision ``slack`` spare all-sentinel rows in the widest bucket
    of both tables (owner = the spare sentinel) so plan_ell_absorb can GROW an
    overflowing vertex's slot capacity in place — the degree-growth
    path that used to be an unconditional slot-overflow rebuild.
    Every pre-spare sentinel slot is re-pointed at the NEW pad row
    (the slot sentinel is n_rows by contract, and n_rows just grew);
    the tables are freshly built and unshared, so the rewrite is
    safe in place.  The index's reach is read here, off the finished
    tables: the sentinel it tells padding by moves with the spares."""
    if slack > 0 and ell.n and ell.bucket_nbr:
        old_sent = np.int32(ell.n_rows)
        new_sent = np.int32(ell.n_rows + int(slack))
        D = int(ell.bucket_nbr[-1].shape[1])
        for nbrs, ets in ((ell.bucket_nbr, ell.bucket_et),
                          (ell.out_nbr, ell.out_et)):
            for nbr in nbrs:
                nbr[nbr == old_sent] = new_sent
            nbrs[-1] = np.vstack(
                [nbrs[-1], np.full((int(slack), D), new_sent, np.int32)])
            ets[-1] = np.vstack(
                [ets[-1], np.zeros((int(slack), D), ets[-1].dtype)])
        ell.extra_owner = np.concatenate(
            [ell.extra_owner,
             np.full(int(slack), new_sent, np.int32)]).astype(np.int32)
        ell.n_rows = int(new_sent)
    ell.reach = pull_reach(ell)
    return ell


def plan_ell_absorb(ell: EllIndex,
                    ins_dst: np.ndarray, ins_src: np.ndarray,
                    ins_et: np.ndarray,
                    del_dst: np.ndarray, del_src: np.ndarray,
                    del_et: np.ndarray, claims_out: Optional[list] = None):
    """Replacement-row plan for absorbing overlay edges into ``ell``.

    Inputs are OLD-dense-id edge rows exactly as the CsrMirror stores
    them (both directions present as separate rows; reverse rides
    -etype): a +etype row lands in its dst's in-table row, a -etype
    row in its dst's out-table row.  Returns {table: (local_rows
    int32[k], nbr [k, D_b], et [k, D_b])}, ``table`` = side * n_buckets
    + b as EllIndex.tables_host orders them — the full new content of
    every affected row of each table — or
    None when any owner's new slot count outgrows its resident
    capacity (main row + existing extra rows), which only the rebuild
    can serve, or an inserted etype outgrows the etype column's integer
    type.  Work is O(delta x row width): only affected owners'
    rows are read and rewritten.

    In-place slot growth: when ``claims_out`` is a list and the index
    holds unclaimed growth spares (EllIndex.build growth_slack), an
    overflowing owner that is NOT already a hub claims enough spare
    rows to hold its new degree in whichever direction overflowed —
    ``(spare_index, owner_new_id)``
    pairs are appended to ``claims_out`` and the plan rewrites the
    claimed rows like any other (a claimed row is the owner's in both
    tables, as a hub's extra rows are).  Narrow by design:
    existing-vertex
    slot extension only — hubs (and previously-grown vertices, which
    look like hubs) and new-vertex ingest still take the rebuild, and
    claims always consume the LOWEST free spares so the free set stays
    a contiguous suffix (hub_expansion's contiguity contract)."""
    import bisect
    from collections import Counter

    if ell.n == 0:
        return None if (len(ins_dst) or len(del_dst)) else {}
    if _etype_dtype(ins_et).itemsize > ell.et_dtype.itemsize:
        return None                  # the column's type cannot say it
    sentinel = np.int32(ell.n_rows)
    ecnt, e0 = ell.hub_expansion()
    nb = len(ell.bucket_nbr)
    tables = ell.tables_host()
    bstarts: List[int] = []
    acc = 0
    for nbr in ell.bucket_nbr:
        bstarts.append(acc)
        acc += nbr.shape[0]
    free_spares: List[int] = []
    if claims_out is not None and len(ell.extra_owner):
        free_spares = np.nonzero(
            ell.extra_owner == np.int32(ell.spare_sentinel()))[0] \
            .tolist()

    # owner row -> side (0 in-table, 1 out-table) -> (deletes, inserts)
    owners: Dict[int, Dict[int, Tuple[Counter, list]]] = {}

    def owner_of(dst_old: int, et: int):
        by_side = owners.setdefault(int(ell.perm[dst_old]), {})
        return by_side.setdefault(0 if et > 0 else 1, (Counter(), []))

    for i in range(len(ins_dst)):
        et = int(ins_et[i])
        owner_of(int(ins_dst[i]), et)[1].append(
            (int(ell.perm[int(ins_src[i])]), abs(et)))
    for i in range(len(del_dst)):
        et = int(del_et[i])
        owner_of(int(del_dst[i]), et)[0][
            (int(ell.perm[int(del_src[i])]), abs(et))] += 1

    upd: Dict[int, Tuple[list, list, list]] = {}
    for r, by_side in owners.items():
        rows = [r] + list(range(int(e0[r]), int(e0[r]) + int(ecnt[r])))
        # (bucket, local row, width) of the owner's rows: the same in
        # both tables
        widths: List[Tuple[int, int, int]] = []
        for row in rows:
            b = bisect.bisect_right(bstarts, row) - 1
            widths.append((b, row - bstarts[b],
                           int(ell.bucket_nbr[b].shape[1])))
        total_w = sum(w for _b, _l, w in widths)
        new_entries: Dict[int, list] = {}
        for side, (dels_c, ins_l) in by_side.items():
            entries: list = []
            for b, local, _w in widths:
                nbr_row = tables[side * nb + b][0][local]
                et_row = tables[side * nb + b][1][local]
                fill = nbr_row != sentinel
                entries.extend(zip(nbr_row[fill].tolist(),
                                   et_row[fill].tolist()))
            if dels_c:
                left = Counter(dels_c)
                kept = []
                for ent in entries:
                    if left.get(ent, 0) > 0:
                        left[ent] -= 1
                    else:
                        kept.append(ent)
                if any(v > 0 for v in left.values()):
                    # a tombstone names an edge the table doesn't hold —
                    # the overlay and the tables disagree; only the
                    # rebuild can reconcile
                    return None
                entries = kept
            entries.extend(ins_l)
            new_entries[side] = entries
        over = max(len(e) for e in new_entries.values()) - total_w
        if over > 0:
            # in-place slot growth: claim spare rows for a NON-hub
            # owner whose degree outgrew its resident width (narrow
            # scope — a hub, or a vertex grown in an earlier window,
            # already owns extras whose contiguity a scattered claim
            # would break: those still rebuild)
            if not free_spares or int(ecnt[r]) > 0:
                return None      # slot overflow past the hub budget
            d_spare = int(ell.bucket_nbr[-1].shape[1])
            need = -(-over // d_spare)
            if need > len(free_spares):
                return None      # growth slack exhausted: rebuild
            take, free_spares[:need] = free_spares[:need], []
            for idx in take:
                row = ell.n + int(idx)
                b = bisect.bisect_right(bstarts, row) - 1
                widths.append((b, row - bstarts[b], d_spare))
                claims_out.append((int(idx), int(r)))
        for side, entries in new_entries.items():
            pos = 0
            for b, local, w in widths:
                take = entries[pos:pos + w]
                pos += w
                nn = np.full(w, sentinel, np.int32)
                ne = np.zeros(w, ell.et_dtype)
                if take:
                    nn[:len(take)] = [t[0] for t in take]
                    ne[:len(take)] = [t[1] for t in take]
                rb = upd.setdefault(side * nb + b, ([], [], []))
                rb[0].append(local)
                rb[1].append(nn)
                rb[2].append(ne)
    return {t: (np.asarray(v[0], np.int32), np.vstack(v[1]),
                np.vstack(v[2]))
            for t, v in upd.items()}


def apply_ell_absorb_host(ell: EllIndex, plan, m_new: int,
                          claims=()) -> EllIndex:
    """Next-generation EllIndex: identical shapes/permutation (cached
    kernels keyed by shape_sig keep serving), updated slot content.
    Buckets WITH updates are copied before the scatter; untouched
    buckets (and perm/inv — and extra_owner when no spare was
    claimed) share memory with the old generation, whose arrays stay
    exactly as published — the immutable-generation contract
    in-flight dispatches rely on.  ``claims`` are plan_ell_absorb's
    (spare_index, owner) growth claims: the next generation's
    extra_owner is a COPY with those spares assigned (table SHAPES
    still survive — only n_hubs, a kernel-argument size, moves)."""
    out = EllIndex()
    out.n, out.m = ell.n, m_new
    out.perm, out.inv = ell.perm, ell.inv
    out.bucket_D = list(ell.bucket_D)
    out.extra_owner = ell.extra_owner
    if claims:
        eo = ell.extra_owner.copy()
        for idx, owner in claims:
            eo[idx] = owner
        out.extra_owner = eo
    out.n_rows = ell.n_rows
    nb = len(ell.bucket_nbr)
    tables = ell.tables_host()
    for t, (rows, nn, ne) in plan.items():
        nbr, et = tables[t][0].copy(), tables[t][1].copy()
        nbr[rows] = nn
        et[rows] = ne
        tables[t] = (nbr, et)
    out.bucket_nbr = [nbr for nbr, _ in tables[:nb]]
    out.bucket_et = [et for _, et in tables[:nb]]
    out.out_nbr = [nbr for nbr, _ in tables[nb:]]
    out.out_et = [et for _, et in tables[nb:]]
    # the rewritten buckets' reach read anew off their slots (a row
    # past a prefix may have gained an in-edge), the others' kept
    out.reach = ell.reach
    if ell.reach is not None:
        out.reach = pull_reach(out, tables=set(plan))
    return out


def absorb_update_arrays(ell: EllIndex, plan):
    """Device-kernel argument form of an absorb plan: per table bucket
    (EllIndex.tables_host order),
    (rows, nbr_rows, et_rows) padded to ONE UNIFORM pow-2 count — the
    rung of the largest per-bucket update set — so the jitted scatter
    sees a bounded shape space.  Uniformity is what bounds it: a
    per-bucket ladder would make the cache key the cross product of
    rungs across buckets (each novel mix a fresh synchronous XLA
    compile under the per-space build lock), while one shared rung
    keeps the key space at log2(mirror_delta_max) entries — the budget
    the registry declares — for a few padded rows of h2d.  Pad entries
    scatter a sentinel-filled row at index ``bucket row count`` — out
    of range for the resident table, dropped by the kernel's
    mode="drop" (on padded SHARDED tables the same index lands in a
    padding row whose content is already all-sentinel, so the write is
    a no-op either way).  Returns (counts tuple — the kernel cache key
    — and the per-bucket arrays)."""
    per_bucket = []
    kmax = 1
    et_dt = ell.et_dtype
    for t, (nbr_np, _et) in enumerate(ell.tables_host()):
        nbk, D = nbr_np.shape
        rows, nn, ne = plan.get(t, (np.zeros(0, np.int32),
                                    np.zeros((0, D), np.int32),
                                    np.zeros((0, D), et_dt)))
        per_bucket.append((nbk, D, rows, nn, ne))
        kmax = max(kmax, len(rows))
    kp = max(8, 1 << (kmax - 1).bit_length())
    counts: List[int] = []
    outs = []
    for nbk, D, rows, nn, ne in per_bucket:
        k = len(rows)
        rp = np.full(kp, nbk, np.int32)
        pn = np.full((kp, D), np.int32(ell.n_rows), np.int32)
        pe = np.zeros((kp, D), et_dt)
        rp[:k] = rows
        pn[:k] = nn
        pe[:k] = ne
        counts.append(kp)
        outs.append((rp, pn, pe))
    return tuple(counts), outs


def _absorb_split(args, nb: int):
    """(rows, nbr updates, et updates, resident nbr tables, resident et
    tables), each in EllIndex.tables_host order (2 * nb entries), from
    an absorb kernel's positional arguments: the three update groups,
    then ``tables`` as every kernel takes them (*in_nbr, *in_et,
    *out_nbr, *out_et)."""
    t = 2 * nb
    tables = args[3 * t:]
    return (args[0:t], args[t:2 * t], args[2 * t:3 * t],
            tables[:nb] + tables[2 * nb:3 * nb],
            tables[nb:2 * nb] + tables[3 * nb:])


def _absorb_join(new_nbrs, new_ets, nb: int):
    """The next generation's ``tables``, in kernel_args order."""
    return tuple(new_nbrs[:nb]) + tuple(new_ets[:nb]) \
        + tuple(new_nbrs[nb:]) + tuple(new_ets[nb:])


def make_ell_absorb_kernel(ell: EllIndex, counts: Tuple[int, ...]):
    """fn(*rows_per_table, *nbr_upd_per_table, *et_upd_per_table,
    *tables) -> the new ``tables`` (*bucket_nbr, *bucket_et, *out_nbr,
    *out_et): whole-row
    scatter of the replacement rows into the resident tables.  The
    inputs are NOT donated — the old tables are the still-published
    generation — so the output generation is a fresh HBM allocation
    (transiently 2x table residency, priced in docs/roofline.md)."""
    import jax
    nb = len(ell.bucket_nbr)

    def absorb(*args):
        rows, un, ue, nbrs, ets = _absorb_split(args, nb)
        return _absorb_join(
            [nbrs[t].at[rows[t]].set(un[t], mode="drop")
             for t in range(2 * nb)],
            [ets[t].at[rows[t]].set(ue[t], mode="drop")
             for t in range(2 * nb)], nb)

    return jax.jit(absorb)


def make_sharded_ell_absorb_kernel(mesh, axis: str, ell: EllIndex,
                                   padded_rows, counts: Tuple[int, ...]):
    """Shard-local twin of make_ell_absorb_kernel for the row-sharded
    replicated-frontier tables (shard_ell; ``padded_rows`` per bucket,
    the same in both tables): the tiny replacement-row
    set replicates to every chip, and each shard applies ONLY the rows
    it owns (non-owned indices push out of range and drop) — zero
    declared collectives, zero ICI exchange; hub rows live in the cap
    bucket like any other row, and the serving-time hub re-replication
    path is untouched.  The scatter runs INSIDE shard_map, so the SPMD
    partitioner never sees a cross-shard scatter-set (the exact hazard
    the packed hub merge hit, PR 10)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    nb = len(ell.bucket_nbr)
    ks = mesh.shape[axis]

    def per_shard(*args):
        rows, un, ue, nbrs, ets = _absorb_split(args, nb)
        d = jax.lax.axis_index(axis)
        outs_n, outs_e = [], []
        for t in range(2 * nb):
            chunk = padded_rows[t % nb] // ks
            loc = rows[t] - d * chunk
            # a NEGATIVE local index would wrap (python-style) into a
            # neighbour's row — push every non-owned update out of
            # range instead, where mode="drop" discards it
            loc = jnp.where((loc >= 0) & (loc < chunk), loc,
                            jnp.int32(chunk))
            outs_n.append(nbrs[t].at[loc].set(un[t], mode="drop"))
            outs_e.append(ets[t].at[loc].set(ue[t], mode="drop"))
        return _absorb_join(outs_n, outs_e, nb)

    in_spec = (P(),) * (6 * nb) + (P(axis),) * (4 * nb)
    fn = shard_map(per_shard, mesh=mesh, in_specs=in_spec,
                   out_specs=(P(axis),) * (4 * nb), check_vma=False)
    return jax.jit(fn)


# info vector of the batched BFS program (int32[3]): levels the loop
# ran, how many of them pushed, and the ELL slots the PUSHED levels
# visited (a pulled level visited table_slots(ell, etypes): bfs_slots
# adds them on the host, where an integer cannot overflow)
BFS_INFO_LEVELS, BFS_INFO_PUSHED, BFS_INFO_PUSH_SLOTS = 0, 1, 2


def _bfs_levels_slots(info, pull_slots: int) -> int:
    """The pushed levels' slots plus ``pull_slots`` a pulled level."""
    pulled = int(info[BFS_INFO_LEVELS]) - int(info[BFS_INFO_PUSHED])
    return int(info[BFS_INFO_PUSH_SLOTS]) + pulled * pull_slots


def bfs_slots(ell: EllIndex, etypes: Tuple[int, ...], info) -> int:
    """ELL slots the levels of one BFS dispatch visited: a pushed
    level its live slot rows' widths, a pulled one every slot of the
    table(s) ``etypes`` reads."""
    return _bfs_levels_slots(info, table_slots(ell, etypes))


def bfs_swept(ell: EllIndex, etypes: Tuple[int, ...], info) -> int:
    """ELL slots the levels of one BFS dispatch GATHERED: bfs_slots
    with a pulled level at swept_slots(ell, etypes), what its loops
    gather, and not at the table's slots, what it reports."""
    return _bfs_levels_slots(info, swept_slots(ell, etypes))


def make_batched_bfs_lanes_kernel(ell: EllIndex, max_steps: int,
                                  etypes: Tuple[int, ...],
                                  stop_when_found: bool = True,
                                  donate: bool = False,
                                  push_rows: Optional[int] = None):
    """Batched BFS: the
    frontier rides the hop gathers 1-bit packed (the gather traffic is
    the level loop's cost center); the depth matrix stays per-lane (its
    updates are streaming elementwise, and it IS the result).  The loop
    exits early when every query either stalled or (``stop_when_found``,
    shortest mode) covered its targets.

    A level FOLLOWS ITS FRONTIER: it is _make_frontier_step's advance,
    the one the continuous hop takes — a push out of the live slot rows
    while they number at most ``push_rows`` (HOP_PUSH_ROWS unless a
    test passes its own), the pull over every slot when they do not; a
    BFS from one source a lane starts with the smallest frontiers the
    system sees.  The two branches agree on rows < n and differ in the
    hub extra rows (>= n), so the loop reads rows < n only: depths,
    ``levels`` and the stall test are the same whichever branch ran a
    level.

    fn(f0p, t0p, eslot, hrows, *tables) -> (depth [n_rows+1, B] (int8
    with -1 = unreachable when max_steps fits — the transfer is 2x
    smaller and depths are tiny — else int16 with INT16_INF), info
    int32[3] = [levels the loop ran, levels that pushed, ELL slots the
    pushed levels visited]; bfs_slots(ell, etypes, info) is what all the
    levels visited).  Rows >= n of the depth matrix stay as the start
    frontier leaves them, unreached (it holds no bit there): nothing
    the host reads (EllIndex.to_old).  Both frontier matrices are built
    fresh per dispatch by runtime._bfs_depths, which opts in to
    ``donate`` (see make_batched_go_lanes_kernel for why the default
    stays off)."""
    import jax
    import jax.numpy as jnp
    n = ell.n
    small = max_steps <= 120
    advance = _make_frontier_step(ell, etypes, push_rows)

    def bfs(f0p, t0p, eslot, hrows, *tables):
        real = (jnp.arange(f0p.shape[0]) < n)[:, None]
        tb = _unpack_lanes(jnp, t0p) > 0
        d0 = jnp.where(_unpack_lanes(jnp, f0p) > 0, jnp.int16(0),
                       INT16_INF)

        def cond(state):
            d, fp, step = state[:3]
            go_on = (step < max_steps) & (fp[:n] != 0).any()
            if stop_when_found:
                go_on = go_on & (tb & (d == INT16_INF)).any()
            return go_on

        def body(state):
            d, fp, step, pushed, push_slots = state
            nxtp, sparse, _rows, slots = advance(fp, eslot, hrows,
                                                 tables)
            newly = (_unpack_lanes(jnp, nxtp) > 0) & (d == INT16_INF) \
                & real
            d = jnp.where(newly, (step + 1).astype(jnp.int16), d)
            return (d, _pack_lanes(jnp, newly), step + 1,
                    pushed + sparse.astype(jnp.int32),
                    push_slots + jnp.where(sparse, slots, 0))

        zero = jnp.int32(0)
        d, _, levels, pushed, push_slots = jax.lax.while_loop(
            cond, body, (d0, f0p, zero, zero, zero))
        if small:
            d = jnp.where(d == INT16_INF, -1, d).astype(jnp.int8)
        return d, jnp.stack([levels, pushed, push_slots])

    return jax.jit(bfs, donate_argnums=(0, 1) if donate else ())


def dense_hop_bytes(ell: EllIndex, etypes: Tuple[int, ...],
                    lane_bytes_per_row: int, steps: int) -> int:
    """HBM traffic model of one dense GO dispatch: per advance, each
    bucket row of each table the OVER set reads (_read_sides) pays D
    word-gathers of ``lane_bytes_per_row`` (= lanes_width(B)), and each
    row an accumulator read+write; the hub fix-up and
    pad are O(n_extras) noise.  The roofline numbers in runtime_stats
    and docs/roofline.md come from THIS model so they are
    comparable."""
    k = sides_read(etypes)
    per_advance = sum(nbr.shape[0] * (k * nbr.shape[1] + 2)
                      for nbr in ell.bucket_nbr) * lane_bytes_per_row
    return max(steps - 1, 1) * per_advance


def sparse_caps(c0: int, d_max: int, steps: int, cap: int,
                growth: int = 8) -> Tuple[int, ...]:
    """Static per-hop pair-list capacities for the sparse batched GO.

    Per-hop sort size is caps[h] * d_max, so caps drive the kernel's
    cost directly (measured on v5e: 131k-pair caps → 350 ms/dispatch,
    8-growth caps → ~100 ms).  Intermediate caps grow geometrically
    from the start capacity (``growth`` ~ the expected out-degree); the
    FINAL cap gets the full budget since the last frontier is the
    result.  A hop that outgrows its cap reports overflow and the
    caller reruns dense — capacity tuning is a performance knob, never
    a correctness one."""
    caps = [max(8, c0)]
    for h in range(max(steps - 1, 0)):
        hard = max(8, caps[-1]) * max(d_max, 1)   # can't exceed expansion
        if h == steps - 2:
            caps.append(min(cap, hard))
        else:
            caps.append(min(cap, hard,
                            max(8, c0) * (max(growth, 2) ** (h + 1))))
    return tuple(caps)


def sparse_limit_cap(caps: Tuple[int, ...], c0: int, limit: int) -> int:
    """Static output capacity of a LIMIT-reduced sparse GO: every kept
    vertex has final-hop degree >= 1, so a query keeps at most
    ``limit`` pairs, and at most c0 queries are live (each live query
    holds >= 1 start pair) — limit * c0 is a TRUE bound, rounded to a
    power of two and never above the unreduced cap."""
    return int(min(caps[-1],
                   1 << (max(8, limit * max(c0, 1)) - 1).bit_length()))


def _gather_out_slots(jnp, gids, sides, bucket_ranges, sentinel: int,
                      d_max: int):
    """[g, d_max * len(sides)] neighbour ids of each row in ``gids``
    over the tables in ``sides`` (_read_sides with push=True), sentinel
    where a slot is padding, masked, or the row is not in range.
    ``bucket_ranges[b]`` = (first local row's id, rows held, lowest and
    one past the highest id the bucket owns): the single-device kernel
    holds whole buckets, a mesh device one block of each.  THE
    pair-list expansion — shared by the single-device and the
    frontier-sharded sparse kernels so their semantics cannot skew."""
    g = gids.shape[0]
    cands = []
    for nbrs, ets, mags in sides:
        cand = jnp.full((g, d_max), jnp.int32(sentinel))
        for nbr, et, (start, held, lo, hi) in zip(nbrs, ets,
                                                  bucket_ranges):
            D = nbr.shape[1]
            loc = gids - start
            inb = (loc >= 0) & (loc < held) & (gids >= lo) & (gids < hi)
            safe = jnp.where(inb, loc, 0)
            rows = nbr[safe]                      # [g, D] row-gathers
            ok = inb[:, None] & _etype_ok(jnp, et[safe], mags)
            block = jnp.where(ok, rows, sentinel)
            if D < d_max:
                block = jnp.pad(block, ((0, 0), (0, d_max - D)),
                                constant_values=sentinel)
            cand = jnp.where(inb[:, None], block, cand)
        cands.append(cand)
    if not cands:
        return jnp.full((g, d_max), jnp.int32(sentinel))
    return cands[0] if len(cands) == 1 else jnp.concatenate(cands, axis=1)


def make_batched_sparse_go_kernel(ell: EllIndex, steps: int,
                                  etypes: Tuple[int, ...],
                                  caps: Tuple[int, ...],
                                  qmax: int = 1024,
                                  upto: bool = False,
                                  limit: Optional[int] = None,
                                  count: bool = False):
    """Sparse batched GO — B queries' frontiers ride ONE flat sorted
    (query, vertex) pair list instead of a dense [n_rows, B] bitmap.

    Per hop: bucketed row-gathers pull each pair's out-slots (a row's
    OUT-neighbours over +T are its out-table slots of magnitude T:
    _read_sides with push=True), then a lexicographic
    sort + shift-compare dedups (query, vertex) pairs and compacts them
    to the next static cap.  Work scales with the LIVE frontier (the
    reference's per-vertex prefix scans touch only frontier vertices
    too — QueryBaseProcessor.inl:336-405), not with the whole table the way
    the dense pull does; at interactive frontier sizes this is an order
    of magnitude less device work AND the result transfer is the pair
    list, not a bitmap.

    Hub vertices (slot spill: extra rows in the cap bucket) are pushed
    EXACTLY: before each hop's gather, every frontier vertex expands
    into its extra-row run ((ecnt, e0) from EllIndex.hub_expansion) via
    a bounded segmented-iota, so the gather sees the spilled slots too.
    The expansion budget per hop equals the hop's pair cap; exceeding
    it (a frontier touching hubs with more total extra rows than the
    cap) sets the overflow flag — exactness, never correctness, is the
    only thing capacity tuning trades.

    Overflow past ``caps[h]`` (deduped pairs) or past the hub budget
    sets the overflow flag; the caller MUST rerun the batch on the
    dense kernel then.

    fn(ids int32[caps[0]] new-id space (sentinel n_rows = inactive),
       qid int32[caps[0]], ecnt int32[n+1], e0 int32[n+1], *tables) ->
    int32 [2 + 2*caps[-1]]: [count, overflow, qids..., ids...] with the
    live pairs sorted by (qid, id) — a single array so the host pays one
    transfer.

    With ``limit`` (the LIMIT-n pushdown, ROADMAP item 2) the signature
    gains a mirror-resident final-hop degree vector —
    fn(ids, qid, ecnt, e0, deg int32[n_rows+1], *tables) — and the
    final pair list is cut on device to each query's shortest
    new-id-order prefix whose cumulative degree covers ``limit`` rows
    (zero-degree vertices contribute no final rows and are dropped),
    compacted to sparse_limit_cap pairs: the fetch shrinks from the
    full caps[-1] tail to ~limit pairs per live query."""
    import jax
    import jax.numpy as jnp
    n, n_rows = ell.n, ell.n_rows
    sentinel = n_rows
    d_max = max(ell.bucket_D) if ell.bucket_D else 1
    nb_count = len(ell.bucket_nbr)
    has_hubs = len(ell.extra_owner) > 0
    bucket_ranges = []
    acc = 0
    for nbr_np in ell.bucket_nbr:
        nbk = nbr_np.shape[0]
        bucket_ranges.append((acc, nbk, acc, acc + nbk))
        acc += nbk
    BIG_Q = jnp.int32(2**30)
    # when (query, vertex) packs into one int32, the per-hop dedup is a
    # single-operand sort — measurably cheaper than the 2-key
    # lexicographic sort (the sort IS the sparse kernel's cost center).
    # The bound is qmax (the LARGEST query index a batch can carry, the
    # dispatcher's go_batch_max), NOT caps[0]: fewer surviving starts
    # than queries is common (unknown vids drop), and a qid above the
    # gate would wrap the packed key and mis-attribute rows
    R1 = n_rows + 1
    pack32 = qmax * R1 <= 2**31 - 1
    I32_MAX = jnp.int32(2**31 - 1)

    def expand_hubs(ids, qid, ecnt, e0, EX):
        """Bounded hub expansion: (q, v) pairs -> up to EX extra-row
        pairs (q, e) covering every frontier hub's spilled slot rows
        (_segmented_hub_iota does the run decoding + budget check)."""
        raw = jnp.where(ids == sentinel, 0, ecnt[jnp.minimum(ids, n)])
        return _segmented_hub_iota(jnp, raw, e0[jnp.minimum(ids, n)],
                                   qid, EX, sentinel, BIG_Q)

    # hub-expansion budget: each of the batch's <= qmax queries can
    # expand each of the graph's extra rows at most once, so
    # n_extras_total * qmax is a TRUE upper bound — a nearly-hub-free
    # graph then pays almost nothing per hop, instead of statically
    # doubling every gather+sort (the kernel's cost center) just
    # because one hub exists somewhere.  Rounded to a power of two for
    # shape stability; capped at c_in (past that, overflow -> dense).
    n_extras_total = len(ell.extra_owner)
    ex_pow2 = 1 << max(n_extras_total * max(qmax, 1) - 1, 1).bit_length() \
        if n_extras_total else 0

    def hop(ids, qid, ecnt, e0, sides, c_out):
        c_in = ids.shape[0]
        if has_hubs:
            # push sources = main rows + every frontier hub's extra
            # rows, so a hub's spilled slots are visited exactly
            ext_rows, ext_q, ovf_hub = expand_hubs(ids, qid, ecnt, e0,
                                                   EX=min(c_in, ex_pow2))
            gids = jnp.concatenate([ids, ext_rows])
            gqs = jnp.concatenate([qid, ext_q])
        else:
            gids, gqs, ovf_hub = ids, qid, jnp.bool_(False)
        cand = _gather_out_slots(jnp, gids, sides, bucket_ranges,
                                 sentinel, d_max)
        flat_i = cand.reshape(-1)
        flat_q = jnp.repeat(gqs, cand.shape[1])
        out_i, out_q, cnt = dedup_compact(flat_q, flat_i, c_out)
        overflow = (cnt > c_out) | ovf_hub
        return out_i, out_q, overflow, cnt

    def dedup_compact(flat_q, flat_i, c_out):
        """Sort + shift-compare dedup of (query, vertex) pairs,
        compacted to ``c_out`` (sentinel/BIG_Q padded) — THE sparse
        kernel's cost center, shared by the per-hop compaction and the
        UPTO union merge so their dedup semantics cannot skew.  Pads
        (sentinel ids) are dropped by construction."""
        valid = flat_i != sentinel
        if pack32:
            key = jnp.where(valid, flat_q * R1 + flat_i, I32_MAX)
            srt = jnp.sort(key)
            uniq = (srt != I32_MAX) & (srt != jnp.roll(srt, 1))
            uniq = uniq.at[0].set(srt[0] != I32_MAX)
            pref = jnp.cumsum(uniq.astype(jnp.int32))
            cnt = pref[-1]
            pos = jnp.where(uniq & (pref <= c_out), pref - 1, c_out)
            out_k = jnp.full((c_out,), I32_MAX).at[pos].set(srt,
                                                            mode="drop")
            bad = out_k == I32_MAX
            out_q = jnp.where(bad, BIG_Q, out_k // R1)
            out_i = jnp.where(bad, sentinel, out_k % R1)
        else:
            key_q = jnp.where(valid, flat_q, BIG_Q)
            key_i = jnp.where(valid, flat_i, jnp.int32(0))
            sq, si = jax.lax.sort((key_q, key_i), num_keys=2, dimension=0)
            prev_q = jnp.roll(sq, 1)
            prev_i = jnp.roll(si, 1)
            uniq = (sq != BIG_Q) & ((sq != prev_q) | (si != prev_i))
            uniq = uniq.at[0].set(sq[0] != BIG_Q)
            pref = jnp.cumsum(uniq.astype(jnp.int32))
            cnt = pref[-1]
            pos = jnp.where(uniq & (pref <= c_out), pref - 1, c_out)
            out_q = jnp.full((c_out,), BIG_Q).at[pos].set(sq, mode="drop")
            out_i = jnp.full((c_out,), jnp.int32(sentinel)) \
                .at[pos].set(si, mode="drop")
            out_i = jnp.where(out_q == BIG_Q, sentinel, out_i)
        return out_i, out_q, cnt

    c_red = sparse_limit_cap(caps, caps[0], limit) \
        if limit is not None else None

    def limit_cut(ids, qid, deg, overflow):
        """Degree-weighted per-query prefix cut + compaction to c_red
        (pairs arrive sorted by (qid, id); segment bases ride a cummax
        over the nondecreasing exclusive cumsum)."""
        w = jnp.where(ids == sentinel, 0,
                      deg[jnp.minimum(ids, sentinel)])
        seg = qid != jnp.roll(qid, 1)
        seg = seg.at[0].set(True)
        cum = jnp.cumsum(w)
        excl = cum - w
        base = jax.lax.cummax(jnp.where(seg, excl, jnp.int32(-1)))
        keep = (ids != sentinel) & (w > 0) & ((excl - base) < limit)
        pref = jnp.cumsum(keep.astype(jnp.int32))
        kcnt = pref[-1]
        pos = jnp.where(keep, pref - 1, c_red)
        out_i = jnp.full((c_red,), jnp.int32(sentinel)) \
            .at[pos].set(ids, mode="drop")
        out_q = jnp.full((c_red,), BIG_Q).at[pos].set(qid, mode="drop")
        return out_i, out_q, kcnt, overflow | (kcnt > c_red)

    def go_impl(ids0, qid0, ecnt, e0, deg, *tables):
        sides = _read_sides(etypes, tables, nb_count, push=True)
        ids, qid = ids0, jnp.where(ids0 == sentinel, BIG_Q, qid0)
        overflow = jnp.bool_(False)
        cnt = jnp.sum(ids != sentinel).astype(jnp.int32)
        c_fin = caps[-1]
        if upto:
            # UPTO: the result is the UNION of the frontiers at depths
            # 0..steps-1 (the final hop materializes edges out of
            # every depth's vertices — GO UPTO semantics).  The
            # accumulator rides at the final capacity; each hop's
            # output merges in through the same dedup_compact
            acc_i = jnp.pad(ids, (0, c_fin - ids.shape[0]),
                            constant_values=sentinel)
            acc_q = jnp.pad(qid, (0, c_fin - qid.shape[0]),
                            constant_values=BIG_Q)
        for h in range(max(steps - 1, 0)):
            ids, qid, ovf_h, cnt = hop(ids, qid, ecnt, e0, sides,
                                       caps[h + 1])
            overflow = overflow | ovf_h
            if upto:
                acc_i, acc_q, cnt = dedup_compact(
                    jnp.concatenate([acc_q, qid]),
                    jnp.concatenate([acc_i, ids]), c_fin)
                overflow = overflow | (cnt > c_fin)
        if upto:
            ids, qid = acc_i, acc_q
        if count:
            # COUNT(*) pushdown: collapse the final pair list to per-
            # query candidate-edge counts — the fetch is qmax words,
            # never the caps[-1] pair tail
            w = jnp.where(ids == sentinel, 0,
                          deg[jnp.minimum(ids, sentinel)])
            qsafe = jnp.clip(qid, 0, qmax - 1)
            counts = jnp.zeros((qmax,), jnp.int32) \
                .at[qsafe].add(jnp.where(qid == BIG_Q, 0, w))
            head = jnp.stack([cnt, overflow.astype(jnp.int32)])
            return jnp.concatenate([head, counts])
        if limit is not None:
            ids, qid, cnt, overflow = limit_cut(ids, qid, deg, overflow)
        elif ids.shape[0] < c_fin:               # steps == 1: pad up
            padn = c_fin - ids.shape[0]
            ids = jnp.pad(ids, (0, padn), constant_values=sentinel)
            qid = jnp.pad(qid, (0, padn), constant_values=2**30)
        head = jnp.stack([cnt, overflow.astype(jnp.int32)])
        if pack32:
            # one packed q*R1+i word per pair — HALF the device->host
            # transfer
            key = jnp.where(qid == BIG_Q, I32_MAX,
                            qid * R1 + jnp.minimum(ids, sentinel))
            return jnp.concatenate([head, key])
        return jnp.concatenate(
            [head, jnp.where(qid == BIG_Q, -1, qid), ids])

    if limit is not None or count:
        go = jax.jit(go_impl)
    else:
        # unreduced signature stays (ids, qid, ecnt, e0, *tables) — the
        # deg vector only rides the LIMIT/COUNT-pushdown variants
        def go_nodeg(ids0, qid0, ecnt, e0, *tables):
            return go_impl(ids0, qid0, ecnt, e0, None, *tables)
        go = jax.jit(go_nodeg)

    go.pack32 = pack32              # host resolve unpacks accordingly
    go.R1 = R1
    return go


def sparse_go_pairs(kern, out: np.ndarray):
    """Decode a sparse-GO kernel's output array ->
    (cnt, overflow, qids, new_ids) — the one place that knows whether
    the kernel packed (q, i) into single words."""
    out = np.asarray(out)
    cnt, overflow = int(out[0]), bool(out[1])
    if getattr(kern, "pack32", False):
        keys = out[2:]
        keys = keys[keys != np.int32(2**31 - 1)]
        R1 = kern.R1
        return cnt, overflow, keys // R1, keys % R1
    c_fin = (len(out) - 2) // 2
    qids = out[2:2 + c_fin]
    ids = out[2 + c_fin:]
    live = qids >= 0
    return cnt, overflow, qids[live], ids[live]


# ====================================================================
# Multi-chip, two designs:
#
# 1. REPLICATED-FRONTIER dense (shard_ell + make_sharded_batched_*):
#    bucket rows sharded, the BIT-PACKED [n_rows+1, W] frontier
#    replicated and re-replicated per hop (all-gather over ICI).
#    Adding chips adds FLOPs but not servable scale — every chip still
#    holds the whole frontier matrix — but packing the lanes cuts BOTH
#    the per-hop ICI re-replication and the per-chip frontier gather
#    traffic 8x versus a byte per lane (docs/roofline.md; the
#    re-replication is the link cost meshaudit's ICI model prices).
#    Kept for the batched-BFS path.
#
# 2. FRONTIER-SHARDED sparse (build_sharded_ell +
#    make_frontier_sharded_sparse_go_kernel): the new-id row space is
#    split into k contiguous chunks; each device holds ONLY its chunk's
#    table rows, hub-run metadata, and live frontier pairs.  Each hop:
#    local gather -> route candidate (query, vertex) pairs to the
#    destination vertex's owner with jax.lax.all_to_all over ICI ->
#    owner-side dedup/compact -> local hub expansion (+ a second
#    all_to_all for spilled hub rows).  Per-chip memory is graph/k +
#    frontier/k, so 8 chips serve 8x the graph+frontier — the TPU form
#    of the reference's ID_HASH scatter-gather regrouping per hop
#    (StorageClient.h:176-196, GoExecutor.cpp:377-431; SURVEY §5.7).
# ====================================================================
def shard_ell(mesh, axis: str, ell: EllIndex):
    """Pad each bucket's rows to a multiple of the axis size and place
    both tables row-sharded.  Returns (tables, real_rows): ``tables``
    in kernel_args order (*in_nbr, *in_et, *out_nbr, *out_et),
    ``real_rows`` the unpadded row count per bucket."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    k = mesh.shape[axis]
    sharding = NamedSharding(mesh, P(axis))
    sentinel = np.int32(ell.n_rows)

    def place(a, fill):
        nb, D = a.shape
        padded = ((nb + k - 1) // k) * k if nb else k
        if padded != nb:
            a = np.concatenate(
                [a, np.full((padded - nb, D), fill, a.dtype)])
        return jax.device_put(a, sharding)

    tables = tuple(place(a, fill)
                   for group, fill in ((ell.bucket_nbr, sentinel),
                                       (ell.bucket_et, 0),
                                       (ell.out_nbr, sentinel),
                                       (ell.out_et, 0))
                   for a in group)
    return tables, [int(nbr.shape[0]) for nbr in ell.bucket_nbr]


def make_sharded_batched_go_kernel(mesh, axis: str, ell: EllIndex,
                                   steps: int, etypes: Tuple[int, ...],
                                   real_rows, donate: bool = False):
    """Sharded-bucket batched GO over a BIT-PACKED replicated frontier.

    fn(f0p replicated uint8 [n_rows+1, W], eslot, hrows, *tables) ->
    uint8 [n_rows+1, W] — same lane layout as the single-chip
    make_batched_go_lanes_kernel (pack_lanes_host / unpack_lanes_host
    invert), so the sharded result is bit-exact against it.  eslot/
    hrows are the hub OR-merge grouping (EllIndex.hub_merge);
    ``tables`` and ``real_rows`` are shard_ell's."""
    import jax
    hop = _make_sharded_hop_packed(mesh, axis, ell, etypes, real_rows)

    def go(f0p, eslot, hrows, *tables):
        return f0p if steps <= 1 else jax.lax.fori_loop(
            0, steps - 1, lambda _, f: hop(f, eslot, hrows, *tables),
            f0p)

    # donation contract matches the single-chip packed kernels: the
    # runtime builds f0p fresh per dispatch (single-use), opt-in only
    return jax.jit(go, donate_argnums=(0,) if donate else ())


def _make_sharded_hop_packed(mesh, axis: str, ell: EllIndex,
                             etypes: Tuple[int, ...], real_rows):
    """hop(fp, eslot, hrows, *tables) -> next packed frontier, with
    bucket rows expanded on their owning device and the result
    re-replicated over ICI.  Shared by the sharded GO and BFS builders
    (same split as _hop_body_packed vs its callers on the single-chip
    side); only the table(s) ``etypes`` reads enter the shard_map.
    The re-replication sharding constraint is THE per-hop ICI
    cost of this design — (k-1)/k of the [n_rows+1, W] frontier per
    chip per hop, declared in the kernel registry's COLLECTIVE_MODEL
    and priced by meshaudit's static traffic model."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax import shard_map

    n_buckets = len(real_rows)
    n_extras = len(ell.extra_owner)
    n = ell.n
    # the magnitudes each table read is masked by, in _read_sides order
    mags = [m for m in _split_signs(etypes) if m]
    n_sides = len(mags)

    def per_shard(fp, *flat):
        w = 2 * n_buckets
        sides = [(flat[i * w:i * w + n_buckets],
                  flat[i * w + n_buckets:(i + 1) * w], mags[i])
                 for i in range(n_sides)]
        return tuple(_buckets_expand_packed(jnp, jax, fp, sides))

    sharded_hop = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(),) + (P(axis),) * (2 * n_buckets * n_sides),
        out_specs=(P(axis),) * n_buckets,
        check_vma=False)

    replicate = NamedSharding(mesh, P())

    def hop(fp, eslot, hrows, *tables):
        if not n_buckets or not n_sides:     # empty graph or OVER set
            return jnp.zeros_like(fp)
        outs = sharded_hop(fp, *[
            a for nbrs, ets, _ in _read_sides(etypes, tables, n_buckets)
            for a in (*nbrs, *ets)])
        trimmed = [o[:r] for o, r in zip(outs, real_rows)]
        nxt = jnp.concatenate(trimmed, axis=0) \
            if len(trimmed) > 1 else trimmed[0]
        # re-replicate BEFORE the hub OR-merge: _scatter_or_rows ends
        # in a scatter-SET, which the SPMD partitioner cannot mask to
        # an identity on shards that don't own the target row (as it
        # can a scatter-max) — partitioned, it clamped the out-of-range
        # index onto each shard's LAST row and corrupted row
        # k*chunk-1 on every chip (caught by the mesh-driver
        # parity gate).  Replicated, the merge is the same tiny
        # O(n_extras x W) work on every chip, and the per-hop ICI
        # cost — (k-1)/k of the packed frontier — is unchanged.
        nxt = jax.lax.with_sharding_constraint(nxt, replicate)
        if n_extras:
            extras = nxt[n:]
            nxt = _scatter_or_rows(jnp, nxt, extras, eslot, hrows)
        pad = jnp.zeros((1, fp.shape[1]), dtype=jnp.uint8)
        return jnp.concatenate([nxt, pad], axis=0)

    return hop


def make_sharded_batched_bfs_kernel(mesh, axis: str, ell: EllIndex,
                                    max_steps: int,
                                    etypes: Tuple[int, ...], real_rows,
                                    stop_when_found: bool = True,
                                    donate: bool = False):
    """Sharded-bucket batched BFS depths — the multi-chip counterpart
    of make_batched_bfs_lanes_kernel, same depth/early-exit/compression
    semantics: the frontier rides the hops (and the per-hop ICI
    re-replication) bit-packed while the depth matrix stays per-lane
    (it IS the result).  fn(f0p, t0p, eslot, hrows, *tables) ->
    (depth [n_rows+1, B] (int8 with -1 = unreachable when max_steps
    fits, else int16), the levels the loop ran)."""
    import jax
    import jax.numpy as jnp
    hop = _make_sharded_hop_packed(mesh, axis, ell, etypes, real_rows)
    small = max_steps <= 120

    def bfs(f0p, t0p, eslot, hrows, *tables):
        tb = _unpack_lanes(jnp, t0p) > 0
        d0 = jnp.where(_unpack_lanes(jnp, f0p) > 0, jnp.int16(0),
                       INT16_INF)

        def cond(state):
            d, fp, step = state
            go_on = (step < max_steps) & (fp != 0).any()
            if stop_when_found:
                go_on = go_on & (tb & (d == INT16_INF)).any()
            return go_on

        def body(state):
            d, fp, step = state
            nxtp = hop(fp, eslot, hrows, *tables)
            newly = (_unpack_lanes(jnp, nxtp) > 0) & (d == INT16_INF)
            d = jnp.where(newly, (step + 1).astype(jnp.int16), d)
            return d, _pack_lanes(jnp, newly), step + 1

        d, _, levels = jax.lax.while_loop(
            cond, body, (d0, f0p, jnp.int32(0)))
        if small:
            d = jnp.where(d == INT16_INF, -1, d).astype(jnp.int8)
        return d, levels

    return jax.jit(bfs, donate_argnums=(0, 1) if donate else ())


# --------------------------------------------------------------------
# Frontier-sharded sparse GO (design 2 above)
# --------------------------------------------------------------------
class ShardedEll:
    """Per-device view of an EllIndex for the frontier-sharded kernel.

    The new-id row space [0, n_rows] splits into k contiguous chunks of
    ``chunk`` rows; device d owns rows [d*chunk, (d+1)*chunk).  Every
    bucket's intersection with a device's chunk becomes one local table
    block (padded to the max block size across devices so the stacked
    arrays [k, mx_b, D_b] shard evenly on the mesh axis).  Hub
    expansion metadata (ecnt, e0 per owner vertex) shards by the same
    chunks, so NOTHING a device holds scales with the whole graph or
    the whole frontier.  ``tables_s`` holds the blocks of both tables in
    kernel_args order (*in_nbr, *in_et, *out_nbr, *out_et).
    """

    __slots__ = ("k", "chunk", "bstarts", "mx", "D", "tables_s",
                 "starts_s", "ecnt_s", "e0_s", "n", "n_rows",
                 "n_extras", "_device")

    def __init__(self):
        self._device = None


def build_sharded_ell(ell: EllIndex, k: int) -> ShardedEll:
    """Split ``ell`` into k per-device chunks (host-side numpy)."""
    sh = ShardedEll()
    sh.k = k
    R1 = ell.n_rows + 1
    sh.chunk = -(-R1 // k)
    sh.n, sh.n_rows = ell.n, ell.n_rows
    sh.n_extras = len(ell.extra_owner)
    sh.bstarts, sh.mx, sh.D = [], [], []
    groups = ([], [], [], [])          # in nbr, in et, out nbr, out et
    starts = np.zeros((k, len(ell.bucket_nbr)), np.int32)
    sentinel = np.int32(ell.n_rows)
    bstart = 0
    for b, nbr in enumerate(ell.bucket_nbr):
        nb, D = nbr.shape
        lo = np.maximum(bstart, np.arange(k, dtype=np.int64) * sh.chunk)
        hi = np.minimum(bstart + nb,
                        (np.arange(k, dtype=np.int64) + 1) * sh.chunk)
        cnt = np.maximum(hi - lo, 0)
        mx = max(int(cnt.max()), 1) if nb else 1
        for group, src, fill in zip(
                groups, (nbr, ell.bucket_et[b], ell.out_nbr[b],
                         ell.out_et[b]), (sentinel, 0, sentinel, 0)):
            blk = np.full((k, mx, D), fill, src.dtype)
            for d in range(k):
                c = int(cnt[d])
                if c:
                    s0 = int(lo[d]) - bstart
                    blk[d, :c] = src[s0:s0 + c]
            group.append(blk)
        starts[:, b] = lo                 # global row id of each block
        sh.bstarts.append(bstart)
        sh.mx.append(mx)
        sh.D.append(D)
        bstart += nb
    sh.tables_s = tuple(a for group in groups for a in group)
    sh.starts_s = starts
    ecnt, e0 = ell.hub_expansion()        # length n+1, indexed by row<n
    pad = k * sh.chunk
    ec = np.zeros(pad, np.int32)
    ez = np.full(pad, ell.n_rows, np.int32)
    ec[:len(ecnt) - 1] = ecnt[:-1]        # rows >= n never expand
    ez[:len(e0) - 1] = e0[:-1]
    sh.ecnt_s = ec.reshape(k, sh.chunk)
    sh.e0_s = ez.reshape(k, sh.chunk)
    return sh


def sharded_device_args(mesh, axis: str, sh: ShardedEll):
    """device_put the per-device arrays with P(axis) on their leading
    dim (cached on the ShardedEll): (starts, ecnt, e0, tables)."""
    if sh._device is None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        s = NamedSharding(mesh, P(axis))
        sh._device = (
            jax.device_put(sh.starts_s, s),
            jax.device_put(sh.ecnt_s, s),
            jax.device_put(sh.e0_s, s),
            tuple(jax.device_put(a, s) for a in sh.tables_s),
        )
    return sh._device


def split_start_pairs_by_owner(sh: ShardedEll, new_ids: np.ndarray,
                               qids: np.ndarray, c0: int):
    """Host half of the launch: place each (query, start row) pair on
    the device owning the row.  Returns (ids [k, c0], qid [k, c0]);
    None when any device's share exceeds c0 (caller falls back)."""
    k, chunk = sh.k, sh.chunk
    sentinel = sh.n_rows
    ids = np.full((k, c0), sentinel, np.int32)
    qid = np.zeros((k, c0), np.int32)
    owner = new_ids // chunk
    for d in range(k):
        sel = owner == d
        c = int(sel.sum())
        if c > c0:
            return None
        ids[d, :c] = new_ids[sel]
        qid[d, :c] = qids[sel]
    return ids, qid


def _mesh_sparse_tools(jnp, jax, axis: str, k: int, chunk: int,
                       n: int, n_rows: int, bstarts, Ds,
                       etypes: Tuple[int, ...]):
    """Per-device building blocks shared by the frontier-sharded GO and
    BFS kernels: the local bucket-block gather, the all_to_all router,
    the owner-side pair dedup, and the local hub expansion.  All static
    metadata arrives as plain ints/lists so the returned closures never
    pin a ShardedEll (whose device-table cache is gigabytes)."""
    sentinel = n_rows
    d_max = max(Ds) if Ds else 1
    nb_count = len(Ds)
    BIG_Q = jnp.int32(2**30)
    bucket_end = [bstarts[b + 1] if b + 1 < nb_count else n_rows
                  for b in range(nb_count)]

    def local_gather(rows, tables, starts):
        """Candidate MAIN-row ids of each local row's out-slots
        (_gather_out_slots over the device's blocks of the table(s)
        ``etypes`` pushes through), sentinel elsewhere.  Rows are owned
        by this device by invariant; each selects exactly one bucket's
        local block by its global bucket range."""
        sides = _read_sides(etypes, tables, nb_count, push=True)
        ranges = [(starts[b], tables[b].shape[0], bstarts[b],
                   bucket_end[b]) for b in range(nb_count)]
        return _gather_out_slots(jnp, rows, sides, ranges, sentinel,
                                 d_max)

    def route(q, u, slot_cap):
        """Sort (q, u) pairs by destination owner and pack them into
        [k, slot_cap] per-destination slots (BIG_Q/sentinel padding).
        Returns (q_x, u_x, overflow)."""
        valid = u != sentinel
        dest = jnp.where(valid, u // chunk, jnp.int32(k))
        sd, sq, su = jax.lax.sort((dest, q, u), num_keys=3, dimension=0)
        off = jnp.searchsorted(sd, jnp.arange(k, dtype=jnp.int32))
        end = jnp.searchsorted(sd, jnp.arange(k, dtype=jnp.int32),
                               side="right")
        cnt = end - off
        overflow = jnp.any(cnt > slot_cap)
        idx = off[:, None] + jnp.arange(slot_cap)[None, :]
        take = jnp.arange(slot_cap)[None, :] < cnt[:, None]
        idxc = jnp.minimum(idx, sd.shape[0] - 1)
        q_x = jnp.where(take, sq[idxc], BIG_Q)
        u_x = jnp.where(take, su[idxc], sentinel)
        return q_x, u_x, overflow

    def exchange(q, u, slot_cap):
        """route + all_to_all in one step -> flat received pairs."""
        rq, ru, ovf = route(q, u, slot_cap)
        q_r = jax.lax.all_to_all(rq, axis, 0, 0, tiled=False)
        u_r = jax.lax.all_to_all(ru, axis, 0, 0, tiled=False)
        return q_r.reshape(-1), u_r.reshape(-1), ovf

    def dedup_compact(q, u, c_out):
        """Sort + unique (q, u) pairs, compact to c_out."""
        valid = u != sentinel
        kq = jnp.where(valid, q, BIG_Q)
        ku = jnp.where(valid, u, jnp.int32(0))
        sq, su = jax.lax.sort((kq, ku), num_keys=2, dimension=0)
        uniq = (sq != BIG_Q) & ((sq != jnp.roll(sq, 1))
                                | (su != jnp.roll(su, 1)))
        uniq = uniq.at[0].set(sq[0] != BIG_Q)
        pref = jnp.cumsum(uniq.astype(jnp.int32))
        cnt = pref[-1]
        pos = jnp.where(uniq & (pref <= c_out), pref - 1, c_out)
        out_q = jnp.full((c_out,), BIG_Q).at[pos].set(sq, mode="drop")
        out_u = jnp.full((c_out,), jnp.int32(sentinel)) \
            .at[pos].set(su, mode="drop")
        out_u = jnp.where(out_q == BIG_Q, sentinel, out_u)
        return out_q, out_u, cnt > c_out, cnt

    def expand_local_hubs(q, u, ecnt_l, e0_l, base, EX):
        """Local hub expansion over the device's OWN pairs (chunk-local
        ecnt/e0 lookups; _segmented_hub_iota does the run decoding +
        budget check); emitted extra-row pairs may be remote and are
        routed by the caller."""
        li = jnp.where(u == sentinel, 0, u - base)
        li = jnp.clip(li, 0, ecnt_l.shape[0] - 1)
        raw = jnp.where(u == sentinel, 0, ecnt_l[li])
        return _segmented_hub_iota(jnp, raw, e0_l[li], q, EX, sentinel,
                                   BIG_Q)

    return local_gather, route, exchange, dedup_compact, \
        expand_local_hubs, BIG_Q


def make_frontier_sharded_sparse_go_kernel(mesh, axis: str,
                                           sh: ShardedEll, steps: int,
                                           etypes: Tuple[int, ...],
                                           caps: Tuple[int, ...],
                                           cap_x: int, cap_e: int):
    """Frontier-sharded sparse batched GO over a 1-D mesh.

    ``caps`` are PER-DEVICE pair capacities per hop (total frontier
    capacity = k * caps[h]); ``cap_x`` bounds candidates shipped
    between any (source, destination) device pair per hop; ``cap_e``
    bounds hub extra-row pairs shipped per device pair.  Any exceeded
    bound sets the overflow flag on every device — exactness falls
    back, never correctness.

    fn(ids0 [k, caps[0]], qid0 [k, caps[0]], starts, ecnt, e0,
       *bucket tables) -> int32 [k, 2 + 2*caps[-1]] — per device
    [count, overflow, qids..., global row ids...], pairs sorted by
    (qid, row).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    # static metadata is COPIED out of ``sh`` here: the jitted kernel
    # lives in the runtime's kernel cache keyed by table SHAPES, so
    # closing over the ShardedEll itself would pin its cached device
    # tables (gigabytes) long after the mirror it came from is replaced
    k, chunk = sh.k, sh.chunk
    n, n_rows = sh.n, sh.n_rows
    bstarts = list(sh.bstarts)
    Ds = list(sh.D)
    sentinel = n_rows
    d_max = max(Ds) if Ds else 1
    nb_count = len(Ds)
    has_hubs = sh.n_extras > 0
    del sh

    (local_gather, route, _exchange, dedup_compact, expand_local_hubs,
     BIG_Q) = _mesh_sparse_tools(jnp, jax, axis, k, chunk, n, n_rows,
                                 bstarts, Ds, etypes)

    def per_device(ids0, qid0, starts, ecnt_l, e0_l, *tables):
        # leading mesh dim of 1 from shard_map: squeeze
        ids = ids0[0]
        qid = jnp.where(ids == sentinel, BIG_Q, qid0[0])
        starts = starts[0]
        ecnt_l, e0_l = ecnt_l[0], e0_l[0]
        tables = [t[0] for t in tables]
        d = jax.lax.axis_index(axis)
        base = (d * chunk).astype(jnp.int32)
        overflow = jnp.bool_(False)
        cnt = jnp.sum(ids != sentinel).astype(jnp.int32)
        ext_rows = None
        ext_q = None
        if has_hubs:                       # starts can be hubs too
            ext_rows, ext_q, ovf0 = expand_local_hubs(
                qid, ids, ecnt_l, e0_l, base, EX=ids.shape[0])
            rq, ru, ovf_r = route(ext_q, ext_rows, cap_e)
            ext_q_x = jax.lax.all_to_all(rq, axis, 0, 0, tiled=False)
            ext_u_x = jax.lax.all_to_all(ru, axis, 0, 0, tiled=False)
            ext_q = ext_q_x.reshape(-1)
            ext_rows = ext_u_x.reshape(-1)
            overflow = ovf0 | ovf_r

        for h in range(max(steps - 1, 0)):
            if has_hubs:
                g_rows = jnp.concatenate([ids, ext_rows])
                g_q = jnp.concatenate([qid, ext_q])
            else:
                g_rows, g_q = ids, qid
            cand = local_gather(g_rows, tables, starts)
            flat_u = cand.reshape(-1)
            flat_q = jnp.repeat(g_q, cand.shape[1])
            qx, ux, ovf_x = route(flat_q, flat_u, cap_x)
            qr = jax.lax.all_to_all(qx, axis, 0, 0, tiled=False)
            ur = jax.lax.all_to_all(ux, axis, 0, 0, tiled=False)
            qid, ids, ovf_c, cnt = dedup_compact(
                qr.reshape(-1), ur.reshape(-1), caps[h + 1])
            overflow = overflow | ovf_x | ovf_c
            if has_hubs and h < steps - 2:
                er, eq, ovf_e = expand_local_hubs(
                    qid, ids, ecnt_l, e0_l, base, EX=ids.shape[0])
                rq, ru, ovf_r = route(eq, er, cap_e)
                eq_x = jax.lax.all_to_all(rq, axis, 0, 0, tiled=False)
                eu_x = jax.lax.all_to_all(ru, axis, 0, 0, tiled=False)
                ext_q = eq_x.reshape(-1)
                ext_rows = eu_x.reshape(-1)
                overflow = overflow | ovf_e | ovf_r

        c_fin = caps[-1]
        if ids.shape[0] < c_fin:
            padn = c_fin - ids.shape[0]
            ids = jnp.pad(ids, (0, padn), constant_values=sentinel)
            qid = jnp.pad(qid, (0, padn), constant_values=2**30)
        # overflow anywhere poisons the whole dispatch (host reruns):
        ovf_all = jax.lax.psum(overflow.astype(jnp.int32), axis) > 0
        head = jnp.stack([cnt, ovf_all.astype(jnp.int32)])
        out = jnp.concatenate(
            [head, jnp.where(qid == BIG_Q, -1, qid), ids])
        return out[None, :]

    import jax as _jax
    in_spec = (P(axis),) * (5 + 4 * nb_count)
    fn = shard_map(per_device, mesh=mesh, in_specs=in_spec,
                   out_specs=P(axis), check_vma=False)
    return _jax.jit(fn)


def sharded_sparse_pairs(out: np.ndarray):
    """Decode the [k, 2+2c] kernel output -> (overflow, qids, row_ids)
    merged across devices."""
    out = np.asarray(out)
    k = out.shape[0]
    c = (out.shape[1] - 2) // 2
    overflow = bool(out[:, 1].any())
    qs, us = [], []
    for d in range(k):
        q = out[d, 2:2 + c]
        u = out[d, 2 + c:]
        live = q >= 0
        qs.append(q[live])
        us.append(u[live])
    return overflow, np.concatenate(qs), np.concatenate(us)


def make_frontier_sharded_sparse_bfs_kernel(mesh, axis: str,
                                            sh: ShardedEll,
                                            max_steps: int,
                                            etypes: Tuple[int, ...],
                                            cap: int, cap_x: int,
                                            cap_e: int,
                                            stop_when_found: bool = True):
    """Frontier-sharded batched BFS — FIND PATH's multi-chip device
    half with per-chip memory graph/k + depth/k (the replicated design
    keeps every chip holding the whole [n_rows+1, B] state; this one
    shards the depth matrix by the same vertex chunks the GO kernel
    uses and exchanges frontier pairs via all_to_all per level).

    Per level: local out-slot gather over the device's live pairs (+
    hub extra rows) -> route candidates to their owner -> owner keeps
    only rows whose depth is still unset, stamps them with the level,
    and they become the next local frontier.  Early exit mirrors
    make_batched_bfs_lanes_kernel: stop when every query stalled or (shortest
    mode) covered its targets — both reductions ride a psum.

    fn(ids0 [k, cap], qid0 [k, cap], tids [k, cap], tqid [k, cap],
       starts, ecnt, e0, *bucket tables) ->
    (depth [k, chunk, B] int16 (INT16_INF = unreached, rows in global
    new-id order chunk-major), overflow [k] int32) — a frontier or
    exchange outgrowing its cap flags overflow on every device and the
    caller reruns on the replicated-frontier kernel.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    k, chunk = sh.k, sh.chunk
    n, n_rows = sh.n, sh.n_rows
    bstarts = list(sh.bstarts)
    Ds = list(sh.D)
    nb_count = len(Ds)
    has_hubs = sh.n_extras > 0
    sentinel = n_rows
    del sh

    (local_gather, _route, exchange, dedup_compact, expand_local_hubs,
     BIG_Q) = _mesh_sparse_tools(jnp, jax, axis, k, chunk, n, n_rows,
                                 bstarts, Ds, etypes)

    def build(qmax: int):
        # qmax bounds the depth matrix's query axis [chunk, qmax]
        def per_device(ids0, qid0, tids, tqid, starts, ecnt_l, e0_l,
                       *tables):
            ids = ids0[0]
            qid = jnp.where(ids == sentinel, BIG_Q, qid0[0])
            t_i, t_q = tids[0], tqid[0]
            starts_l = starts[0]
            ecnt_l, e0_l = ecnt_l[0], e0_l[0]
            tables = [t[0] for t in tables]
            d = jax.lax.axis_index(axis)
            base = (d * chunk).astype(jnp.int32)

            depth = jnp.full((chunk, qmax), INT16_INF, jnp.int16)
            li0 = jnp.clip(ids - base, 0, chunk - 1)
            q0 = jnp.clip(qid, 0, qmax - 1)
            live0 = ids != sentinel
            depth = depth.at[li0, q0].min(
                jnp.where(live0, jnp.int16(0), INT16_INF))
            # local target mask [chunk, qmax]
            tgt = jnp.zeros((chunk, qmax), jnp.int8)
            tli = jnp.clip(t_i - base, 0, chunk - 1)
            tq = jnp.clip(t_q, 0, qmax - 1)
            tgt = tgt.at[tli, tq].max(
                jnp.where(t_i != sentinel, jnp.int8(1), jnp.int8(0)))

            def unfound_any(dep):
                u = jnp.any((tgt > 0) & (dep == INT16_INF))
                return jax.lax.psum(u.astype(jnp.int32), axis) > 0

            def frontier_any(i):
                c = jnp.sum((i != sentinel).astype(jnp.int32))
                return jax.lax.psum(c, axis) > 0

            def hub_pairs(q, u):
                if not has_hubs:
                    return (jnp.full((1,), jnp.int32(sentinel)),
                            jnp.full((1,), BIG_Q), jnp.bool_(False))
                er, eq, ovf = expand_local_hubs(q, u, ecnt_l, e0_l,
                                                base, EX=u.shape[0])
                eq2, er2, ovf_r = exchange(eq, er, cap_e)
                return er2, eq2, ovf | ovf_r

            def body(state):
                dep, ids, qid, step, _go, ovf = state
                er, eq, ovf_h = hub_pairs(qid, ids)
                g_rows = jnp.concatenate([ids, er])
                g_q = jnp.concatenate([qid, eq])
                cand = local_gather(g_rows, tables, starts_l)
                flat_u = cand.reshape(-1)
                flat_q = jnp.repeat(g_q, cand.shape[1])
                q_r, u_r, ovf_x = exchange(flat_q, flat_u, cap_x)
                nq2, nu2, ovf_c, _cnt = dedup_compact(q_r, u_r, cap)
                # newly discovered = depth still unset at the owner
                li = jnp.clip(nu2 - base, 0, chunk - 1)
                qi = jnp.clip(nq2, 0, qmax - 1)
                fresh = (nu2 != sentinel) \
                    & (dep[li, qi] == INT16_INF)
                dep = dep.at[li, qi].min(
                    jnp.where(fresh, (step + 1).astype(jnp.int16),
                              INT16_INF))
                ids2 = jnp.where(fresh, nu2, sentinel)
                qid2 = jnp.where(fresh, nq2, BIG_Q)
                # overflow must be GLOBALLY agreed before it feeds the
                # loop condition: a device-local flag would make devices
                # disagree on whether to run another level, and the next
                # iteration's all_to_all deadlocks waiting for the
                # devices that already exited
                ovf_l = ovf_h | ovf_x | ovf_c
                ovf = ovf | (jax.lax.psum(ovf_l.astype(jnp.int32),
                                          axis) > 0)
                step = step + 1
                go_on = (step < max_steps) & frontier_any(ids2)
                if stop_when_found:
                    go_on = go_on & unfound_any(dep)
                return dep, ids2, qid2, step, go_on, ovf

            def cond(state):
                return state[4] & jnp.logical_not(state[5])

            pad_ids = jnp.full((cap,), jnp.int32(sentinel))
            pad_q = jnp.full((cap,), BIG_Q)
            ids_c = pad_ids.at[:ids.shape[0]].set(ids)
            qid_c = pad_q.at[:qid.shape[0]].set(qid)
            go0 = frontier_any(ids_c) & jnp.bool_(max_steps > 0)
            if stop_when_found:
                go0 = go0 & unfound_any(depth)
            state = (depth, ids_c, qid_c, jnp.int32(0), go0,
                     jnp.bool_(False))
            dep, _i, _q, _s, _g, ovf = jax.lax.while_loop(
                cond, body, state)
            return dep[None], ovf.astype(jnp.int32)[None]

        in_spec = (P(axis),) * (7 + 4 * nb_count)
        return jax.jit(shard_map(per_device, mesh=mesh,
                                 in_specs=in_spec,
                                 out_specs=(P(axis), P(axis)),
                                 check_vma=False))

    return build


# ====================================================================
# Kernel-registry entries (tpu/kernels.py KernelSpec) — the abstract
# signatures jaxaudit traces for the ELL kernel families, bucketed by
# the SAME pinned flag ladders the runtime dispatches on.
# ====================================================================
from .kernels import KernelSpec, register_kernel  # noqa: E402


def _packed_frontier_avals(fx, B):
    """(f0p, eslot, hrows) avals of the bit-packed dense kernels."""
    R1 = fx.ell.n_rows + 1
    return (fx.aval((R1, lanes_width(B)), np.uint8),
            fx.aval((len(fx.ell.extra_owner),), np.int32),
            fx.aval((fx.ell.n_hubs,), np.int32))


def _ell_go_buckets(fx):
    out = []
    for upto in (False, True):
        # audit-time instantiation: traced by jaxaudit, never
        # dispatched — not the serving hot path
        kern = make_batched_go_lanes_kernel(  # nebulint: disable=jax-hotpath
            fx.ell, fx.steps, fx.etypes,
            upto=upto, donate=True)
        for B in fx.widths:
            out.append((("ell_go_packed", fx.ell.shape_sig(), fx.etypes,
                         fx.steps, upto), kern,
                        _packed_frontier_avals(fx, B)
                        + fx.table_avals()[1:]))
    return out


def _ell_go_count_buckets(fx):
    R1 = fx.ell.n_rows + 1
    kern = make_batched_go_lanes_kernel(
        fx.ell, fx.steps, fx.etypes, count=True, donate=True)
    return [(("ell_go_count", fx.ell.shape_sig(), fx.etypes, fx.steps),
             kern,
             _packed_frontier_avals(fx, B)
             + (fx.aval((R1,), np.int32),) + fx.table_avals()[1:])
            for B in fx.widths]


def _ell_go_hop_buckets(fx):
    """Continuous-mode hop: ONE cache key per (mirror, OVER) family —
    the per-steps key dimension is gone (the host loop owns the hop
    count), and the push / pull choice is a conditional inside the
    program, so the retrace space is just the lane-width rung
    ladder."""
    kern = make_continuous_hop_kernel(fx.ell, fx.etypes, donate=True)
    out = []
    for B in fx.widths:
        pk = _packed_frontier_avals(fx, B)
        out.append((("ell_go_hop", fx.ell.shape_sig(), fx.etypes), kern,
                    (pk[0], pk[0], pk[1], pk[2])
                    + fx.table_avals()[1:]))
    return out


def _ell_lane_join_buckets(fx):
    kern = make_lane_join_kernel(fx.ell, donate=True)
    out = []
    for B in fx.widths:
        pk = _packed_frontier_avals(fx, B)
        for Sp in LANE_JOIN_RUNGS:  # every scatter-pad rung
            out.append((("ell_lane_join", fx.ell.shape_sig()), kern,
                        (pk[0], pk[0],
                         fx.aval((Sp,), np.int32),
                         fx.aval((Sp,), np.int32),
                         fx.aval((Sp,), np.uint8))))
    return out


def _ell_lane_clear_buckets(fx):
    kern = make_lane_clear_kernel(donate=True)
    out = []
    for B in fx.widths:
        pk = _packed_frontier_avals(fx, B)
        out.append((("ell_lane_clear", fx.ell.shape_sig()), kern,
                    (pk[0], pk[0],
                     fx.aval((lanes_width(B),), np.uint8))))
    return out


def _ell_lane_extract_buckets(fx):
    kern = make_lane_extract_kernel(fx.ell)
    out = []
    for B in fx.widths:
        pk = _packed_frontier_avals(fx, B)
        for L in lane_extract_rungs(B):     # every leaver-count rung
            out.append((("ell_lane_extract", fx.ell.shape_sig()), kern,
                        (pk[0], pk[0], fx.aval((3, L), np.int32))))
    return out


def _ell_lane_count_buckets(fx):
    kern = make_lane_count_kernel(fx.ell)
    return [(("ell_lane_count", fx.ell.shape_sig()), kern,
             (_packed_frontier_avals(fx, B)[0],)) for B in fx.widths]


def _sparse_go_buckets(fx):
    d_max = max(fx.ell.bucket_D) if fx.ell.bucket_D else 1
    n1 = fx.ell.n + 1
    out = []
    for upto in (False, True):
        for c0 in fx.c0s:
            caps = sparse_caps(c0, d_max, fx.steps, fx.sparse_cap,
                               growth=fx.sparse_growth)
            kern = make_batched_sparse_go_kernel(  # nebulint: disable=jax-hotpath
                fx.ell, fx.steps, fx.etypes, caps, qmax=fx.qmax,
                upto=upto)
            out.append((("sparse_go", fx.ell.shape_sig(), fx.etypes,
                         fx.steps, caps, fx.qmax, upto), kern,
                        (fx.aval((c0,), np.int32),
                         fx.aval((c0,), np.int32),
                         fx.aval((n1,), np.int32),
                         fx.aval((n1,), np.int32))
                        + fx.table_avals()[1:]))    # no owner arg
    return out


def _sparse_go_limit_buckets(fx):
    d_max = max(fx.ell.bucket_D) if fx.ell.bucket_D else 1
    n1 = fx.ell.n + 1
    R1 = fx.ell.n_rows + 1
    out = []
    for c0 in fx.c0s:
        caps = sparse_caps(c0, d_max, fx.steps, fx.sparse_cap,
                           growth=fx.sparse_growth)
        kern = make_batched_sparse_go_kernel(  # nebulint: disable=jax-hotpath
            fx.ell, fx.steps, fx.etypes, caps, qmax=fx.qmax,
            limit=fx.limit)
        out.append((("sparse_go_limit", fx.ell.shape_sig(), fx.etypes,
                     fx.steps, caps, fx.qmax, fx.limit), kern,
                    (fx.aval((c0,), np.int32),
                     fx.aval((c0,), np.int32),
                     fx.aval((n1,), np.int32),
                     fx.aval((n1,), np.int32),
                     fx.aval((R1,), np.int32))      # deg vector
                    + fx.table_avals()[1:]))
    return out


def _sparse_go_count_buckets(fx):
    d_max = max(fx.ell.bucket_D) if fx.ell.bucket_D else 1
    n1 = fx.ell.n + 1
    R1 = fx.ell.n_rows + 1
    out = []
    for c0 in fx.c0s:
        caps = sparse_caps(c0, d_max, fx.steps, fx.sparse_cap,
                           growth=fx.sparse_growth)
        kern = make_batched_sparse_go_kernel(  # nebulint: disable=jax-hotpath
            fx.ell, fx.steps, fx.etypes, caps, qmax=fx.qmax,
            count=True)
        out.append((("sparse_go_count", fx.ell.shape_sig(), fx.etypes,
                     fx.steps, caps, fx.qmax), kern,
                    (fx.aval((c0,), np.int32),
                     fx.aval((c0,), np.int32),
                     fx.aval((n1,), np.int32),
                     fx.aval((n1,), np.int32),
                     fx.aval((R1,), np.int32))      # deg vector
                    + fx.table_avals()[1:]))
    return out


def _ell_bfs_buckets(fx):
    out = []
    for shortest in (True, False):
        kern = make_batched_bfs_lanes_kernel(  # nebulint: disable=jax-hotpath
            fx.ell, fx.steps, fx.etypes,
            stop_when_found=shortest, donate=True)
        for B in fx.widths:
            pk = _packed_frontier_avals(fx, B)
            out.append((("ell_bfs_packed", fx.ell.shape_sig(),
                         fx.etypes, fx.steps, shortest), kern,
                        (pk[0], pk[0], pk[1], pk[2])
                        + fx.table_avals()[1:]))
    return out


def _absorb_update_avals(fx, kp: int):
    """(rows, nbr_upd, et_upd) avals per table bucket at padded count
    kp (EllIndex.tables_host order)."""
    tables = fx.ell.tables_host()
    return tuple(fx.aval((kp,), np.int32) for _ in tables) \
        + tuple(fx.aval((kp, nbr.shape[1]), np.int32)
                for nbr, _et in tables) \
        + tuple(fx.aval((kp, et.shape[1]), et.dtype)
                for _nbr, et in tables)


def _ell_absorb_buckets(fx):
    out = []
    for kp in (8, 64):              # the pow-2 update-count ladder's ends
        counts = tuple(kp for _ in fx.ell.tables_host())
        kern = make_ell_absorb_kernel(  # nebulint: disable=jax-hotpath
            fx.ell, counts)
        out.append((("ell_absorb", fx.ell.shape_sig(), counts), kern,
                    _absorb_update_avals(fx, kp)
                    + fx.table_avals()[1:]))
    return out


def _limit_d2h_bound(fx) -> int:
    d_max = max(fx.ell.bucket_D) if fx.ell.bucket_D else 1
    worst = 0
    for c0 in fx.c0s:
        caps = sparse_caps(c0, d_max, fx.steps, fx.sparse_cap,
                           growth=fx.sparse_growth)
        worst = max(worst, sparse_limit_cap(caps, c0, fx.limit))
    return 4 * (2 + 2 * worst)      # non-pack32 worst case


register_kernel(KernelSpec(
    "ell_go", make_batched_go_lanes_kernel, phase_kind="ell_go",
    # per steps value: one retrace per pinned batch width per
    # exact/upto variant (the runtime's prewarm compiles exactly these)
    budget=4, instantiate=_ell_go_buckets, donate=(0,), dispatch=(0,),
    frontier=(0,), packed=(0,)))
register_kernel(KernelSpec(
    "ell_go_count", make_batched_go_lanes_kernel,
    phase_kind="ell_go_count",
    # COUNT(*) pushdown: one retrace per pinned batch width
    budget=2, instantiate=_ell_go_count_buckets, donate=(0,),
    dispatch=(0,), frontier=(0,), packed=(0,),
    d2h_bytes_max=lambda fx: 4 * lanes_width(max(fx.widths)) * 8))
register_kernel(KernelSpec(
    "ell_go_hop", make_continuous_hop_kernel, phase_kind="ell_go_hop",
    # continuous dispatch: one retrace per lane-width rung, steps
    # folded out of the key entirely (the host tick loop owns depth);
    # push and pull are two branches of the ONE program, so the
    # frontier's size is data too.  Outputs: the resident pair's next
    # generation and the int32[3] info vector
    budget=2, instantiate=_ell_go_hop_buckets, donate=(0, 1),
    frontier=(0, 1), packed=(0, 1)))
register_kernel(KernelSpec(
    "ell_lane_join", make_lane_join_kernel, phase_kind="ell_lane_join",
    # one retrace per (width rung, scatter-pad rung) pair:
    # LANE_JOIN_RUNGS, which the session runs itself before its first
    # join (runtime._ContinuousGoSession._join_kernel)
    budget=48, instantiate=_ell_lane_join_buckets, donate=(0, 1),
    dispatch=(2, 3, 4), frontier=(0, 1), packed=(0, 1)))
register_kernel(KernelSpec(
    "ell_lane_clear", make_lane_clear_kernel,
    phase_kind="ell_lane_clear",
    budget=2, instantiate=_ell_lane_clear_buckets, donate=(0, 1),
    dispatch=(2,), frontier=(0, 1), packed=(0, 1)))
register_kernel(KernelSpec(
    "ell_lane_extract", make_lane_extract_kernel,
    phase_kind="ell_lane_extract",
    # one retrace per (width rung, leaver-count rung) pair
    budget=48, instantiate=_ell_lane_extract_buckets,
    dispatch=(2,), frontier=(0, 1), packed=(0, 1),
    # the leave-extract fetch is one bitmap of the vertex rows a
    # leaver — never a word column, never the [R1, W] matrix (a batch
    # where every seat leaves in one tick is bounded by its lanes)
    d2h_bytes_max=lambda fx: lane_bitmap_bytes(fx.ell.n)
    * max(fx.widths)))
register_kernel(KernelSpec(
    "ell_lane_count", make_lane_count_kernel,
    phase_kind="ell_lane_count",
    # one retrace per lane-width rung; reads the resident frontier in
    # place (no donation, nothing uploaded) and the fetch is one int32
    # a lane
    budget=2, instantiate=_ell_lane_count_buckets,
    frontier=(0,), packed=(0,),
    d2h_bytes_max=lambda fx: 4 * lanes_width(max(fx.widths)) * 8))
register_kernel(KernelSpec(
    "sparse_go", make_batched_sparse_go_kernel, phase_kind="sparse_go",
    # per steps value: one retrace per sparse c0 rung per variant
    budget=4, instantiate=_sparse_go_buckets, dispatch=(0, 1)))
register_kernel(KernelSpec(
    "sparse_go_limit", make_batched_sparse_go_kernel,
    phase_kind="sparse_go",
    # LIMIT pushdown: one retrace per sparse c0 rung per limit value
    # (limits themselves ride the dispatcher's shape key)
    budget=2, instantiate=_sparse_go_limit_buckets, dispatch=(0, 1),
    d2h_bytes_max=_limit_d2h_bound))
register_kernel(KernelSpec(
    "sparse_go_count", make_batched_sparse_go_kernel,
    phase_kind="sparse_go",
    # COUNT pushdown: one retrace per sparse c0 rung; the fetch is the
    # qmax count vector, never the caps[-1] pair tail
    budget=2, instantiate=_sparse_go_count_buckets, dispatch=(0, 1),
    d2h_bytes_max=lambda fx: 4 * (2 + fx.qmax)))
register_kernel(KernelSpec(
    "ell_bfs", make_batched_bfs_lanes_kernel, phase_kind="ell_bfs",
    # one retrace per pinned batch width per shortest/all variant; a
    # level's push and pull are two branches of the ONE program (the
    # hop's step).  Outputs: the depth matrix and the int32[3] info
    # vector (BFS_INFO_*)
    budget=4, instantiate=_ell_bfs_buckets, donate=(0, 1),
    dispatch=(0, 1), frontier=(0, 1), packed=(0, 1)))
register_kernel(KernelSpec(
    "ell_absorb", make_ell_absorb_kernel, phase_kind="ell_absorb",
    # one retrace per pow-2 update-count rung (log2(mirror_delta_max)
    # rungs bound the ladder); NO donation: the resident tables are
    # the still-published generation in-flight dispatches read — the
    # output generation must be a fresh allocation (docs/durability.md)
    budget=12, instantiate=_ell_absorb_buckets,
    dispatch=tuple(range(6))))


def _sharded_table_avals(fx, tables):
    return tuple(fx.aval(a.shape, a.dtype) for a in tables)


def _ell_sharded_arg_indices(fx):
    """Replicated-frontier sharded GO: everything after the
    (f0p, eslot, hrows) prefix is a row-sharded bucket table."""
    nb = len(fx.ell.bucket_nbr)
    return tuple(range(3, 3 + 4 * nb))


def _ell_bfs_sharded_arg_indices(fx):
    nb = len(fx.ell.bucket_nbr)
    return tuple(range(4, 4 + 4 * nb))


def _ell_go_sharded_mesh_buckets(fx, mesh):
    k = mesh.shape["parts"]
    tables, reals = shard_ell(mesh, "parts", fx.ell)
    kern = make_sharded_batched_go_kernel(
        mesh, "parts", fx.ell, fx.steps, fx.etypes, reals, donate=True)
    tables = _sharded_table_avals(fx, tables)
    return [(("ell_go_sharded", fx.ell.shape_sig(), fx.etypes,
              fx.steps, k), kern,
             _packed_frontier_avals(fx, B) + tables)
            for B in fx.widths]


def _ell_go_sharded_buckets(fx):
    return _ell_go_sharded_mesh_buckets(fx, fx.mesh())


def _ell_bfs_sharded_mesh_buckets(fx, mesh):
    k = mesh.shape["parts"]
    tables, reals = shard_ell(mesh, "parts", fx.ell)
    B = fx.widths[0]
    tables = _sharded_table_avals(fx, tables)
    out = []
    for shortest in (True, False):
        kern = make_sharded_batched_bfs_kernel(  # nebulint: disable=jax-hotpath
            mesh, "parts", fx.ell, fx.steps, fx.etypes, reals,
            stop_when_found=shortest, donate=True)
        pk = _packed_frontier_avals(fx, B)
        out.append((("ell_bfs_sharded", fx.ell.shape_sig(), fx.etypes,
                     fx.steps, shortest, k), kern,
                    (pk[0], pk[0], pk[1], pk[2]) + tables))
    return out


def _ell_bfs_sharded_buckets(fx):
    return _ell_bfs_sharded_mesh_buckets(fx, fx.mesh())


def _replicated_frontier_ici(fx, k):
    """Per-hop ICI cost of the replicated designs: the re-replication
    sharding constraint ships (k-1)/k of the packed [n_rows+1, W]
    frontier to every chip — bounded by the full frontier bytes."""
    return (fx.ell.n_rows + 1) * lanes_width(max(fx.widths))


register_kernel(KernelSpec(
    "ell_go_sharded", make_sharded_batched_go_kernel,
    phase_kind="ell_go_sharded",
    # per steps value: one retrace per pinned batch width
    budget=2, instantiate=_ell_go_sharded_buckets, donate=(0,),
    dispatch=(0,), frontier=(0,), packed=(0,),
    # COLLECTIVE_MODEL: the ONLY cross-chip movement is the per-hop
    # frontier re-replication (a sharding constraint the partitioner
    # lowers to an all-gather); any other collective — e.g. a full
    # bucket-table all-gather from a closure-captured device array —
    # is an undeclared regression
    mesh_instantiate=_ell_go_sharded_mesh_buckets,
    collective=(("sharding_constraint", ()),),
    ici_bytes=lambda fx, k: _replicated_frontier_ici(fx, k)
    * max(fx.steps - 1, 1),
    shard_args=_ell_sharded_arg_indices))
register_kernel(KernelSpec(
    "ell_bfs_sharded", make_sharded_batched_bfs_kernel,
    phase_kind="ell_bfs_sharded",
    budget=2, instantiate=_ell_bfs_sharded_buckets, donate=(0, 1),
    dispatch=(0, 1), frontier=(0, 1), packed=(0, 1),
    mesh_instantiate=_ell_bfs_sharded_mesh_buckets,
    collective=(("sharding_constraint", ()),),
    # per BFS level (the while body traces once)
    ici_bytes=_replicated_frontier_ici,
    shard_args=_ell_bfs_sharded_arg_indices))


def _ell_absorb_sharded_mesh_buckets(fx, mesh):
    k = mesh.shape["parts"]
    tables, _reals = shard_ell(mesh, "parts", fx.ell)
    nb = len(fx.ell.bucket_nbr)
    padded = [int(a.shape[0]) for a in tables[:nb]]
    out = []
    for kp in (8, 64):
        counts = tuple(kp for _ in fx.ell.tables_host())
        kern = make_sharded_ell_absorb_kernel(  # nebulint: disable=jax-hotpath
            mesh, "parts", fx.ell, padded, counts)
        out.append((("ell_absorb_sharded", fx.ell.shape_sig(), counts,
                     k), kern,
                    _absorb_update_avals(fx, kp)
                    + _sharded_table_avals(fx, tables)))
    return out


def _ell_absorb_sharded_buckets(fx):
    return _ell_absorb_sharded_mesh_buckets(fx, fx.mesh())


def _ell_absorb_sharded_arg_indices(fx):
    nb = len(fx.ell.bucket_nbr)
    return tuple(range(6 * nb, 10 * nb))


register_kernel(KernelSpec(
    "ell_absorb_sharded", make_sharded_ell_absorb_kernel,
    phase_kind="ell_absorb",
    budget=12, instantiate=_ell_absorb_sharded_buckets,
    dispatch=tuple(range(6)),
    mesh_instantiate=_ell_absorb_sharded_mesh_buckets,
    # COLLECTIVE_MODEL: EMPTY by design — absorption is shard-local
    # (each chip applies only the replacement rows it owns; the
    # replicated update upload is input placement, not a collective),
    # so a traced psum/all_gather here is a regression that would put
    # table maintenance on the ICI critical path
    collective=(),
    ici_bytes=lambda fx, k: 0,
    shard_args=_ell_absorb_sharded_arg_indices,
    shard_outs=tuple(range(4))))


# ------------------------------------------------ frontier-sharded (mesh)
def _mesh_sparse_shapes(fx, k):
    """runtime._launch_mesh_sparse's cap arithmetic at mesh size k, on
    the audit fixture's ladder head (the BFS path has its OWN
    arithmetic — _mesh_sparse_bfs_shapes below — because the runtime's
    _mesh_sparse_bfs sizes pair capacity off tpu_sparse_cap, not the
    per-hop GO ladder)."""
    d_max = max(fx.ell.bucket_D) if fx.ell.bucket_D else 1
    c0 = fx.c0s[0]
    caps = sparse_caps(c0, d_max, fx.steps, fx.sparse_cap,
                       growth=fx.sparse_growth)
    cap_x = max(256, caps[-1] // max(k // 2, 1))
    cap_e = max(64, c0)
    return c0, caps, cap_x, cap_e


def _mesh_sparse_bfs_shapes(fx, k):
    """runtime._mesh_sparse_bfs's cap arithmetic (runtime.py — cap =
    tpu_sparse_cap, cap_x/cap_e derived from it), so the audited
    buckets carry the REAL serving shapes: a regression that blows the
    exchange buffers or per-shard residency at the 2^17-pair caps must
    fail lint, not just at toy caps."""
    cap = fx.sparse_cap
    cap_x = max(256, cap // max(k // 2, 1))
    cap_e = max(64, cap // 8)
    return cap, cap_x, cap_e


def _mesh_sparse_go_mesh_buckets(fx, mesh):
    k = mesh.shape["parts"]
    sh = build_sharded_ell(fx.ell, k)
    c0, caps, cap_x, cap_e = _mesh_sparse_shapes(fx, k)
    kern = make_frontier_sharded_sparse_go_kernel(
        mesh, "parts", sh, fx.steps, fx.etypes, caps, cap_x=cap_x,
        cap_e=cap_e)
    avals = ((fx.aval((k, c0), np.int32), fx.aval((k, c0), np.int32),
              fx.aval(sh.starts_s.shape, np.int32),
              fx.aval(sh.ecnt_s.shape, np.int32),
              fx.aval(sh.e0_s.shape, np.int32))
             + _sharded_table_avals(fx, sh.tables_s))
    return [(("mesh_sparse_go", fx.ell.shape_sig(), fx.etypes,
              fx.steps, caps, k, cap_x, cap_e), kern, avals)]


def _mesh_sparse_go_buckets(fx):
    return _mesh_sparse_go_mesh_buckets(fx, fx.mesh())


def _mesh_sparse_bfs_mesh_buckets(fx, mesh):
    k = mesh.shape["parts"]
    sh = build_sharded_ell(fx.ell, k)
    cap, cap_x, cap_e = _mesh_sparse_bfs_shapes(fx, k)
    build = make_frontier_sharded_sparse_bfs_kernel(
        mesh, "parts", sh, fx.steps, fx.etypes, cap, cap_x=cap_x,
        cap_e=cap_e, stop_when_found=True)
    kern = build(fx.qmax)
    pair = fx.aval((k, cap), np.int32)
    avals = ((pair, pair, pair, pair,
              fx.aval(sh.starts_s.shape, np.int32),
              fx.aval(sh.ecnt_s.shape, np.int32),
              fx.aval(sh.e0_s.shape, np.int32))
             + _sharded_table_avals(fx, sh.tables_s))
    return [(("mesh_sparse_bfs", fx.ell.shape_sig(), fx.etypes,
              fx.steps, cap, k, cap_x, cap_e, fx.qmax, True), kern,
             avals)]


def _mesh_sparse_bfs_buckets(fx):
    return _mesh_sparse_bfs_mesh_buckets(fx, fx.mesh())


def _mesh_sparse_ici(fx, k):
    """all_to_all budget: per hop the candidate router ships two
    [k, cap_x] int32 planes and the hub router two [k, cap_e] planes
    (each device keeps 1/k, so (k-1)/k of it crosses ICI); the psum'd
    overflow/early-exit scalars are noise under the 4 KiB pad."""
    _c0, _caps, cap_x, cap_e = _mesh_sparse_shapes(fx, k)
    return 2 * 4 * k * (cap_x + cap_e) + 4096


register_kernel(KernelSpec(
    "mesh_sparse_go", make_frontier_sharded_sparse_go_kernel,
    phase_kind="mesh_sparse_go",
    # one retrace per sparse c0 rung per mesh size (the runtime keys
    # caps/k/cap_x/cap_e into the kernel cache)
    budget=2, instantiate=_mesh_sparse_go_buckets, dispatch=(0, 1),
    mesh_instantiate=_mesh_sparse_go_mesh_buckets,
    collective=(("all_to_all", ("parts",)), ("psum", ("parts",))),
    # the hop loop is Python-unrolled: steps-1 candidate exchanges
    # plus the pre-loop hub exchange
    ici_bytes=lambda fx, k: _mesh_sparse_ici(fx, k) * fx.steps,
    shard_args=lambda fx: tuple(
        range(5 + 4 * len(fx.ell.bucket_nbr))),
    shard_outs=(0,)))
def _mesh_sparse_bfs_ici(fx, k):
    """Per BFS level (the while body traces once): the candidate
    router ships two [k, cap_x] int32 planes, the hub router two
    [k, cap_e] — at the runtime's REAL tpu_sparse_cap-derived caps."""
    _cap, cap_x, cap_e = _mesh_sparse_bfs_shapes(fx, k)
    return 2 * 4 * k * (cap_x + cap_e) + 4096


register_kernel(KernelSpec(
    "mesh_sparse_bfs", make_frontier_sharded_sparse_bfs_kernel,
    phase_kind="mesh_sparse_bfs",
    budget=2, instantiate=_mesh_sparse_bfs_buckets,
    dispatch=(0, 1, 2, 3),
    mesh_instantiate=_mesh_sparse_bfs_mesh_buckets,
    collective=(("all_to_all", ("parts",)), ("psum", ("parts",))),
    ici_bytes=_mesh_sparse_bfs_ici,
    shard_args=lambda fx: tuple(
        range(7 + 4 * len(fx.ell.bucket_nbr))),
    shard_outs=(0, 1)))
