"""TpuQueryRuntime — the device-side storage backend behind graphd's
executor seam (BASELINE.json north star).

The reference runs a multi-hop GO as one storaged RPC fan-out per hop
plus graphd-side set dedup, and an extra RPC wave for $$-props
(GoExecutor.cpp:334-431, 531-569).  This runtime answers the same
executor calls from HBM-resident ELL tables instead: the full hop loop
and the frontier dedup run as jitted XLA programs; the WHERE filter
(including $$ refs — no second wave) and the YIELD meet the final
frontier's candidate edges on the host, in numpy over the mirror's
columns at the CPU executor's precision.

Serving architecture:

* Concurrent GO queries coalesce in the batch dispatcher
  (graph/batch_dispatch.py) and the WHOLE query — frontier advance,
  final-hop candidate assembly, WHERE filter, YIELD materialization —
  executes batch-at-a-time: one device dispatch plus one vectorized
  numpy pass for the entire batch, with per-query error isolation.
* Kernels take the ELL tables as jit ARGUMENTS (ell.py), so the
  compiled program depends only on table SHAPES: mirror rebuilds reuse
  cached executables, and the persistent compilation cache
  (jax_setup.py) removes first-compile cost across processes.
* Batch widths ride a small pinned ladder (`go_batch_widths`), so
  steady-state serving never sees a new program shape.
* Small frontiers run the sparse pair-list kernel
  (ell.make_batched_sparse_go_kernel): device work scales with the live
  frontier and the transfer is a compact pair list.  Overflow or hub
  contact falls back to the dense bitmap kernel, whose frontier is
  bit-packed on the device and across the link (ell.py owns the
  layout).
* Multi-hop GO dispatch is CONTINUOUS by default (round 15,
  ``go_dispatch_mode``): queries join and leave an in-flight lane
  batch at hop boundaries over a resident packed frontier pair
  (_ContinuousGoSession + graph/batch_dispatch.py's seat-map ledger),
  so the device never idles between windows; the windowed pipeline
  stays as the bit-exact parity oracle and the rollback path.

Fallback contract: ``can_run_go``/``can_run_path`` decline anything the
device can't reproduce bit-for-bit (per-root $-/$var inputs, expressions
the compiler rejects) — graphd's CPU path then executes the query,
exactly like the reference's CPU-storaged path.  One flagship rule:
whatever both paths can run must return identical result sets
(tests/test_tpu_backend.py asserts this).
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..common import deadline as deadlines
from ..common import flight as _flight
from ..common import hostclock
from ..common import mc_hooks
from ..common import protocol
from ..common import tracing
from ..common.deadline import DeadlineExceeded
from ..common.flags import flags
from ..common.stats import stats as _stats
from ..common.status import ErrorCode
from ..filter.expressions import ExprContext, ExprError, Expression
from ..graph.interim import InterimResult
from ..storage.device import DeviceExecError
from .csr import CsrMirror, build_mirror, edge_column
from .expr_compile import (CompileError, CVal, Env, ExprCompiler, K_BOOL,
                           K_FLOAT, K_STR, K_STRCODE, K_VIDRANK)
from .jax_setup import device_info, ensure_jax_configured
from . import kernels
from .ell import (LANE_JOIN_RUNGS, EllIndex, lane_bitmap_rows,
                  lane_extract_rung, lane_extract_rungs, lane_join_rung,
                  sides_read)


class MeshUnavailable(DeviceExecError):
    """``tpu_mesh_devices`` asks for more devices than jax sees.  A
    query ERROR on both seams (in-process executor and storaged RPC),
    never a decline: the CPU loop answering under a mesh flag would
    hide that the deployment is not the one configured."""


class _GoPlan:
    """Prepared per-query state handed from can_run_go to run_go."""

    __slots__ = ("mirror", "alias_to_etype", "filter_cval", "filter_used",
                 "pushed_mode", "compiler", "expr_str", "sc_or")

    def __init__(self, mirror, alias_to_etype, filter_cval, filter_used,
                 pushed_mode, compiler, expr_str, sc_or=False):
        self.mirror = mirror
        self.alias_to_etype = alias_to_etype
        self.filter_cval = filter_cval
        self.filter_used = filter_used      # dict key -> descriptor
        self.pushed_mode = pushed_mode      # True: skip-invalid (storage
        self.compiler = compiler            # semantics); False: raise
        self.expr_str = expr_str            # canonical WHERE text (cache key)
        # WHERE contains a disjunction: `x || missing` short-circuits
        # on the CPU path (row kept without touching the prop), which
        # the vectorized validity mask cannot reproduce — rows with
        # invalid used props must decline to the CPU loop then
        # (pure-conjunction masks match skip-on-error exactly)
        self.sc_or = sc_or


def _aliases_of(etype_to_alias: Dict[int, str]) -> Dict[str, Tuple]:
    """alias -> its signed etypes in ascending order, from the map the
    executor ships: one type a name, or under BIDIRECT both signs."""
    out: Dict[str, Tuple] = {}
    for et in sorted(etype_to_alias):
        out[etype_to_alias[et]] = out.get(etype_to_alias[et], ()) + (et,)
    return out


def _filter_has_or(expr) -> bool:
    """True when the predicate can short-circuit PAST a prop read in a
    way the validity AND-mask cannot reproduce (see _GoPlan.sc_or).

    A pure conjunction is mask-safe: `false && missing` skips the row
    either way, `true && missing` raises-and-skips = masked.  Anything
    that can turn a skipped operand into a KEPT row is not: any
    disjunction, and any `!` (or other non-logical operator) APPLIED
    OVER a logical subtree — `!(false && missing)` keeps the row on
    the CPU path without touching the prop."""
    from ..filter.expressions import LogicalExpr
    if expr is None:
        return False

    def scan(nd, under_non_logical: bool) -> bool:
        if isinstance(nd, LogicalExpr):
            if nd.op != "&&" or under_non_logical:
                return True
            return any(scan(c, False) for c in nd.children())
        # every non-logical node (unary !, arithmetic, comparisons,
        # function calls) makes a logical op underneath order-sensitive
        return any(scan(c, True) for c in nd.children())

    return scan(expr, False)


class _GoQuery:
    """One query riding a go_batch_execute dispatch."""

    __slots__ = ("start_vids", "plan", "yield_cols", "distinct",
                 "where_expr", "etype_to_alias", "exc_type", "deadline")

    def __init__(self, start_vids, plan, yield_cols, distinct, where_expr,
                 etype_to_alias, exc_type, deadline=None):
        self.start_vids = start_vids
        self.plan = plan
        self.yield_cols = yield_cols
        self.distinct = distinct
        self.where_expr = where_expr
        self.etype_to_alias = etype_to_alias
        self.exc_type = exc_type
        # whole-request budget (common/deadline.py): checked again
        # right before the device launch — the dispatcher's snapshot
        # check can predate a slow mirror build
        self.deadline = deadline


class _Pending:
    """Two-phase dispatcher contract: the leader launched device work
    (async); ``finish()`` blocks on the transfer and completes the host
    half.  While one batch finishes, the next batch's leader may
    launch — host assembly overlaps device compute
    (graph/batch_dispatch.py)."""

    __slots__ = ("finish",)

    def __init__(self, finish):
        self.finish = finish


class _DeviceCounts:
    """Marker wrapper a count-reduced launch resolver returns instead
    of per-query frontier vertex lists: the device already collapsed
    the result to per-query candidate-edge counts (int64[nq]), so the
    fetch was B words and assembly is skipped entirely."""

    __slots__ = ("arr",)

    def __init__(self, arr: np.ndarray):
        self.arr = arr


class _EdgeRuns:
    """Candidate edges held as runs of consecutive mirror rows:
    ``lo[i] : lo[i] + cnt[i]``, back to back in that order.  The edge
    arrays are in (src, etype, rank, dst) order, so a frontier vertex's
    edges of one OVER set are one run (_over_ranges), and a WHERE that
    keeps a hundredth of its candidates never needs a row index for
    each of them: ``take`` copies the runs of an edge-aligned array
    (one memcpy a run through the native library; without it, numpy's
    gather through the index), ``rows`` gives the mirror rows of the
    few positions that were kept."""

    __slots__ = ("lo", "cnt", "ends", "total", "_index")

    def __init__(self, lo: np.ndarray, cnt: np.ndarray):
        self.lo = np.ascontiguousarray(lo, np.int64)
        self.cnt = np.ascontiguousarray(cnt, np.int64)
        self.ends = np.cumsum(self.cnt)     # candidates up to each run's end
        self.total = int(self.ends[-1]) if len(self.ends) else 0
        self._index = None

    def __len__(self) -> int:
        return self.total

    def index(self) -> np.ndarray:
        """The mirror row of every candidate (int64[total])."""
        if self._index is None:
            # multi-range arange: global position -> within-range
            # offset + range start, fully vectorized
            idx = np.repeat(self.lo - (self.ends - self.cnt), self.cnt)
            idx += np.arange(self.total, dtype=np.int64)
            self._index = idx
        return self._index

    def take(self, arr: np.ndarray) -> np.ndarray:
        """``arr[self.index()]`` for an edge-aligned array."""
        from ..native import lib
        L = lib()
        if L is None or not hasattr(L, "neb_gather_runs"):
            _say_once("[tpu] native run gather missing: a WHERE's "
                      "candidates are gathered through an index for "
                      "each edge (several times slower)")
            return arr[self.index()]
        if arr.ndim != 1 or not arr.flags.c_contiguous \
                or not len(self.lo):
            return arr[self.index()]
        if int(self.lo.min()) < 0 or int(self.cnt.min()) < 0 \
                or int((self.lo + self.cnt).max()) > len(arr):
            raise IndexError("edge run outside the array")
        out = np.empty(self.total, arr.dtype)
        L.neb_gather_runs(arr.ctypes.data, arr.itemsize,
                          self.lo.ctypes.data, self.cnt.ctypes.data,
                          len(self.lo), out.ctypes.data)
        return out

    _OPS = {"<": 0, "<=": 1, ">": 2, ">=": 3, "==": 4, "!=": 5}

    def keep_f64(self, values: np.ndarray, valid: np.ndarray, op: str,
                 c: float) -> np.ndarray:
        """Positions (ascending, over the runs back to back) of the
        candidates whose ``valid`` is set and whose float64 ``values``
        satisfy ``value op c``: one native pass that reads a candidate
        once and writes only what is kept.  The caller has checked
        that it can run (TpuQueryRuntime._native_filter)."""
        from ..native import lib
        if not len(self.lo):
            return np.zeros(0, np.int64)
        if int(self.lo.min()) < 0 or int(self.cnt.min()) < 0 \
                or int((self.lo + self.cnt).max()) > len(values):
            raise IndexError("edge run outside the array")
        out = np.empty(self.total, np.int64)
        n = lib().neb_filter_runs_f64(
            values.ctypes.data, valid.ctypes.data, self.lo.ctypes.data,
            self.cnt.ctypes.data, len(self.lo), self._OPS[op], float(c),
            out.ctypes.data)
        return out[:n]

    def pieces(self, limit: int):
        """The runs in order, cut after the run that brings a piece to
        ``limit`` candidates (a longer run is a piece of its own)."""
        cuts = np.searchsorted(self.ends,
                               np.arange(limit, self.total, limit))
        bounds = np.unique(np.concatenate(
            ([0], cuts + 1, [len(self.cnt)])))
        for a, b in zip(bounds[:-1].tolist(), bounds[1:].tolist()):
            yield _EdgeRuns(self.lo[a:b], self.cnt[a:b])

    def rows(self, at: np.ndarray) -> np.ndarray:
        """Mirror rows of the candidates at positions ``at``."""
        run = np.searchsorted(self.ends, at, side="right")
        return self.lo[run] + (at - (self.ends[run] - self.cnt[run]))


# one lock for the per-mirror host tables below: reentrant, because
# one table's fill asks for another (_over_ranges)
_MIRROR_TABLE_LOCK = threading.RLock()
_NO_TABLE = object()


def _mirror_table(m: CsrMirror, attr: str, key, fill):
    """The O(m) host table ``fill()`` builds, once per (mirror, key),
    kept in the dict ``m.<attr>`` (at most 8 keys: each entry is O(m)).
    Riders assemble on their own threads, so after a generation change
    a whole leave cohort asks for the same table in the same instant:
    the first fills it under the lock and the others wait for that one
    pass instead of each running its own."""
    cache = getattr(m, attr, None)
    got = _NO_TABLE if cache is None else cache.get(key, _NO_TABLE)
    if got is not _NO_TABLE:
        return got
    with _MIRROR_TABLE_LOCK:
        cache = getattr(m, attr, None)
        if cache is None:
            cache = {}
            setattr(m, attr, cache)
        got = cache.get(key, _NO_TABLE)
        if got is _NO_TABLE:
            if len(cache) >= 8:
                cache.clear()
            got = cache[key] = fill()
    return got


# candidates a WHERE evaluates at a time where it has them as runs
# (_filter_runs): 2 MB of doubles, so the copied columns, the mask
# and the compiled predicate's temporaries stay in the cache and out
# of the allocator's way, whatever a cohort's millions of candidates
# (the native pass of a column against a constant copies nothing, and
# takes the same pieces: its kept positions then fit 2 MB too)
WHERE_PIECE_EDGES = 1 << 18


_said: set = set()


def _say_once(what: str) -> None:
    """A degraded mode named once a process on stderr, where the
    runtime's other start-up lines go."""
    if what not in _said:
        _said.add(what)
        print(what, file=sys.stderr, flush=True)


def _take(arr: np.ndarray, idx) -> np.ndarray:
    """``arr[idx]`` where ``idx`` is a row index array or _EdgeRuns."""
    return idx.take(arr) if isinstance(idx, _EdgeRuns) else arr[idx]


# nebulint: disable=flag-registry
flags.define(
    "tpu_filter_mode", "auto",
    "read by nothing; defined and managed only because benchmark/"
    "configs/graph500-s20-where.json sets it in set-up (ROADMAP S7 g)")
flags.define(
    "tpu_device_timing_every", 16,
    "sample every Nth dense/sparse device dispatch with a "
    "block_until_ready timestamp around the kernel — the device-"
    "compute-vs-link split (tpu.device_compute.latency_us histogram, "
    "achieved-GB/s gauge, BASELINE.md roofline columns) AND the "
    "flight-recorder kernel-timing rows that feed the live-vs-"
    "declared HBM drift fold (common/flight.py, docs/observability.md "
    "'The device timeline').  Windowed dispatch only: the continuous "
    "stream is never blocked by this probe, its device wait is the "
    "tick record's fetch_wait_us, read on every leaving tick.  0 "
    "disables sampling (no serialization of the dispatch pipeline at "
    "all, and no timing rows)")
flags.define(
    "tpu_sparse_go", True,
    "batched GO prefers the sparse pair-list kernel "
    "(ell.make_batched_sparse_go_kernel) when the batch's total start "
    "count fits tpu_sparse_c0: device work scales with the live "
    "frontier instead of the whole ELL table, and the device->host "
    "transfer is the pair list instead of a bitmap. Overflow/hub "
    "contact re-runs the batch on the dense kernel (exactness is "
    "kernel-checked)")
flags.define("tpu_sparse_c0s", "256,2048",
             "pinned start-pair capacities (comma ladder, ascending) of "
             "the sparse batched GO kernel; a batch rides the smallest "
             "width holding its start count — per-hop caps (and sort "
             "sizes) scale from it")
flags.define("tpu_sparse_cap", 1 << 17,
             "final-frontier pair capacity of the sparse batched GO "
             "kernel; a hop whose deduped (query, vertex) pairs exceed "
             "its cap falls back to the dense kernel")
flags.define("tpu_sparse_growth", 8,
             "geometric growth of intermediate sparse-hop caps "
             "(~expected out-degree); tighter = cheaper sorts, more "
             "dense fallbacks (ell.sparse_caps)")
flags.define(
    "go_batch_widths", "128,1024",
    "pinned dense-kernel batch widths (comma list, ascending): every "
    "dense dispatch pads its query count to one of these so steady "
    "state never compiles a new program shape")
flags.define(
    "tpu_mesh_devices", 0,
    "shard the ELL tables over this many devices (a 1-D 'parts' Mesh). "
    "0 = single-device. The TPU analogue of the reference's "
    "multi-storaged partition spread (SURVEY.md §2.12)")
flags.define(
    "tpu_mesh_mode", "sparse",
    "multi-chip GO strategy: 'sparse' (frontier partitioned by vertex "
    "range per chip, candidate pairs exchanged via all_to_all over ICI "
    "— per-chip memory is graph/k + frontier/k, so chips ADD servable "
    "scale; ell.make_frontier_sharded_sparse_go_kernel) or 'dense' "
    "(tables sharded, frontier replicated + re-replicated per hop — "
    "the round-4 design, kept as the overflow fallback and the BFS "
    "path)")
flags.define(
    "tpu_prewarm_kernels", True,
    "after a query family's first kernel builds, background-compile "
    "the family's OTHER pinned batch shapes (sparse c0 ladder, dense "
    "widths) so fresh clusters don't pay first-compile seconds as p99 "
    "spikes when concurrency shifts the batch shape")
flags.define(
    "mirror_delta_max", 4096,
    "max committed edge events one absorption window folds into the "
    "resident tables; a burst past this pays the full CSR/ELL rebuild "
    "instead (counted as tpu.mirror.delta_overflow and journaled — "
    "the write-while-serve soak asserts absorptions keep it at zero)")
flags.define(
    "mirror_absorb", True,
    "fold committed write deltas into the resident ELL/CSR tables "
    "device-side as immutable mirror GENERATIONS (ell_absorb kernels, "
    "docs/durability.md): a sustained write stream costs O(delta) per "
    "absorption instead of O(m) per rebuild.  Off restores "
    "rebuild-per-write — the absorb-vs-rebuild parity differential's "
    "oracle (tests/test_absorb.py)")
flags.define(
    "tpu_ell_cap", 512,
    "ELL slot-table width cap (ell.EllIndex.build): vertices above it "
    "spill into hub extra rows. Smaller halves the sparse kernel's "
    "per-hop candidate/sort width (d_max) at the price of more hub "
    "rows — worth tuning down on heavy-tailed graphs")
flags.define(
    "tpu_ell_growth_slack", 8,
    "SPARE all-sentinel rows provisioned per ELL build in the widest "
    "bucket (ell.EllIndex.build growth_slack): an absorb window whose "
    "degree growth overflows an existing vertex's resident slot row "
    "claims one IN PLACE instead of paying the slot-overflow "
    "re-bucketing rebuild (narrow scope: non-hub existing vertices; "
    "new-vertex ingest still rebuilds).  ~tpu_ell_cap*10 bytes of HBM "
    "per spare; 0 disables growth (docs/durability.md decision table)")
flags.define(
    "mirror_refresh_mode", "sync",
    "CSR-mirror refresh on space mutation: 'sync' rebuilds before the "
    "next device query (always fresh — the test/parity default); "
    "'async' keeps serving the stale mirror while a background thread "
    "rebuilds (bounded staleness — the reference's own consistency "
    "model: graphd/storaged caches refresh every "
    "load_data_interval_secs=120s, MetaClient.cpp:13-14)")


# ====================================================================
# Declared device-dispatch phase structure — the runtime's side of the
# contract tools/lint/jaxaudit.py audits every registered kernel
# against (tpu/kernels.py KERNEL_REGISTRY).  Per kernel kind:
#   phases  the nebulatrace spans (SPAN_NAMES literals) a dispatch of
#           this kind passes through (PR 3 phase attribution)
#   h2d     host->device argument-leaf uploads paid PER DISPATCH
#           (mirror-resident tables excluded — they upload per build)
#   d2h     device->host fetches the resolver performs per dispatch
# Drift in either direction fails tier-1: a kernel growing an output
# (an extra fetch) or a new per-dispatch upload must update this table
# — the declaration is the review surface, exactly like the
# reference's Thrift IDL.
# ====================================================================
# ====================================================================
# Declared per-device HBM budget — the arithmetic behind the published
# ~639M-edge/chip ceiling (BASELINE.md "Scale", docs/tpu_backend.md),
# now a LINT-ENFORCED declaration instead of a prose claim: the jaxpr
# auditor's HBM pass (tools/lint/jaxaudit.py, docs/static_analysis.md
# "HBM budget table") proves on every registered kernel's abstract
# avals that each ladder rung's peak resident bytes (mirror tables +
# per-dispatch frontier uploads + outputs, donation-adjusted) fit the
# PHYSICAL device_hbm_bytes, and that edge_ceiling *
# table_bytes_per_edge fits table_budget_bytes (the mirror-table
# slice; its gap to device_hbm_bytes is the headroom rungs may use
# for frontiers/outputs/scratch) — growing either side without
# updating the other fails tier-1.
#   device_hbm_bytes     physical HBM of the serving chip (v5e: 16 GB)
#   table_budget_bytes   the slice the mirror publisher may fill with
#                        ELL tables (the rest covers XLA scratch,
#                        frontier uploads and result buffers)
#   table_bytes_per_edge device table bytes per DECLARED edge — both
#                        directions + ELL padding + hub spill rows
#                        (2.14 GiB / 105M power-law edges at
#                        tpu_ell_cap=256, computed from the host table
#                        shapes; chip_smoke.py prints the live figure)
#   edge_ceiling         the serving claim the budget must cover
# ====================================================================
# Published per-chip peaks keyed by jax's ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
# The ONE table anything that divides by a peak reads: the declared
# models below are the v5e row, and live folds look the serving device
# up here and do nothing for a kind that is not listed — a CPU run
# must never be priced against a TPU's roofline.
DEVICE_PEAKS = {
    "TPU v5 lite": {"hbm_bytes": 16 * 1000**3, "hbm_gbps": 819.0,
                    "ici_gbps": 1600.0 / 8},
}
_V5E = DEVICE_PEAKS["TPU v5 lite"]

HBM_MODEL = {
    "device_hbm_bytes": _V5E["hbm_bytes"],
    "table_budget_bytes": 14 * 1000**3,
    "table_bytes_per_edge": 21.9,
    "edge_ceiling": 639_000_000,
}

# ====================================================================
# MESH_MODEL — the multi-chip counterpart of HBM_MODEL, enforced by
# meshaudit (tools/lint/meshaudit.py, nebulint v4).  The auditor
# proves, per audited mesh size k:
#   * capacity_edges[k] * table_bytes_per_edge <= k * table_budget —
#     the published multi-chip capacity table (max edges vs #chips,
#     docs/static_analysis.md + BASELINE.md) is ARITHMETIC over the
#     declarations, so growing one side without the other fails tier-1;
#   * every sharded kernel rung's per-shard residency (tables/k +
#     replicated frontier + collective exchange buffers) fits
#     device_hbm_bytes;
#   * the per-dispatch ICI exchange bytes derived from the traced
#     collective operand avals fit each kernel's declared ici_bytes
#     bound (the static link-traffic model; ici_gbps prices it into
#     the link-vs-compute table beside docs/roofline.md).
#   ici_gbps   per-chip aggregate ICI bandwidth, GB/s (DEVICE_PEAKS)
#   hbm_gbps   PUBLISHED peak HBM bandwidth, GB/s (DEVICE_PEAKS) — a
#              ceiling, not a measurement
#   capacity_edges  the serving claim per mesh size — k chips hold
#              k x the per-chip table budget (the frontier-sharded
#              design adds no replicated state that scales with the
#              graph; the replicated-frontier design's [n_rows+1, W]
#              matrix is audited against the rung residency gate)
# ====================================================================
MESH_MODEL = {
    "mesh_sizes": (1, 2, 4, 8),
    "ici_gbps": _V5E["ici_gbps"],
    "hbm_gbps": _V5E["hbm_gbps"],
    "capacity_edges": {1: 639_000_000, 2: 1_278_000_000,
                       4: 2_556_000_000, 8: 5_112_000_000},
}

# ====================================================================
# MESH_CARVEOUTS — the closed registry of reasons a sharded-space
# query may decline to the CPU loop.  Every ``raise TpuDecline`` and
# every ``return False`` inside a can_run_* gate in THIS module must
# carry a ``# nebulint: carveout=<reason>`` tag naming one of these
# keys (tools/lint/meshaudit.py carveout-inventory); untagged decline
# sites and dead registry entries fail lint.  This makes ROADMAP-5's
# "shrink the mesh carve-outs" an enumerable, baselined list: deleting
# a carve-out means deleting its sites AND its row here.
# ====================================================================
MESH_CARVEOUTS = {
    "cpu-backend": "storage_backend=cpu pins the space to the CPU "
                   "loop by configuration",
    "piped-input": "GO ... | GO feeds per-row inputs the batch "
                   "planner cannot see statically",
    "breaker-open": "device circuit breaker open — a known-broken "
                    "device must not be re-probed per query "
                    "(docs/durability.md)",
    "upto-mesh": "GO UPTO needs the union accumulator the mesh "
                 "kernels do not carry yet (ROADMAP-5)",
    "schema-miss": "OVER names an edge type the schema manager "
                   "cannot resolve",
    "plan-decline": "the GO planner cannot reproduce the query's "
                    "semantics on the device path",
    "expr-undecodable": "a shipped WHERE/YIELD expression tree "
                        "failed to decode on the serving side",
    "device-failure": "classified device runtime failure — the "
                      "breaker records it and the CPU loop serves",
    # PR 11 deleted the two overlay-serving carve-outs
    # (overlay-uncompilable, overlay-div-guard): committed deltas now
    # ABSORB into the resident tables as new mirror generations
    # (docs/durability.md), so no query is ever assembled against a
    # live overlay — the decline sites are gone with the overlay path.
    "invalid-prop-shortcircuit": "missing-prop disjunction needs the "
                                 "CPU path's short-circuit evaluation "
                                 "order",
    "mirror-build-failed": "mirror build/transfer failed for the "
                           "space — nothing resident to serve from",
}

DEVICE_PHASES = {
    "ell_go": {"phases": ("tpu.launch", "tpu.kernel", "tpu.fetch",
                          "tpu.assemble"), "h2d": 1, "d2h": 1},
    "ell_go_count": {"phases": ("tpu.launch", "tpu.kernel", "tpu.fetch",
                                "tpu.assemble"), "h2d": 1, "d2h": 1},
    "sparse_go": {"phases": ("tpu.launch", "tpu.kernel", "tpu.fetch",
                             "tpu.assemble"), "h2d": 2, "d2h": 1},
    # delta absorption: per-dispatch uploads are the O(delta)
    # replacement-row triples of each direction's table; the four
    # "fetches" are the next generation's tables (index and etype
    # columns of the in- and the out-table), which STAY resident (they
    # become the published generation's device arrays — nothing
    # crosses the link back)
    "ell_absorb": {"phases": ("tpu.absorb",), "h2d": 6, "d2h": 4},
    # the second fetch is the loop's int32[3] info vector: levels run,
    # levels that pushed, slots the pushed levels visited (12 bytes)
    "ell_bfs": {"phases": ("tpu.kernel", "tpu.fetch"), "h2d": 2,
                "d2h": 2},
    # continuous hop-boundary batching (graph/batch_dispatch.py,
    # docs/admission.md "Continuous dispatch"): the resident frontier
    # pair never crosses the link — hop/join/clear "fetches" are the
    # next resident (fp, accp) generation (donated in, stays on
    # device); only the leave-extract's lane bitmaps actually move d2h.
    # The hop's third output is its 12-byte info vector (which branch
    # ran, live slot rows, slots visited): read once it is ready,
    # never waited for (_ContinuousGoSession.hop_reads)
    "ell_go_hop": {"phases": ("tpu.kernel",), "h2d": 0, "d2h": 3},
    "ell_lane_join": {"phases": ("tpu.kernel",), "h2d": 3, "d2h": 2},
    "ell_lane_clear": {"phases": ("tpu.kernel",), "h2d": 1, "d2h": 2},
    "ell_lane_extract": {"phases": ("tpu.kernel", "tpu.fetch"),
                         "h2d": 1, "d2h": 1},
    # the k-hop count's leave: reads the resident frontier in place,
    # one int32 a lane comes back (_LaneCount)
    "ell_lane_count": {"phases": ("tpu.kernel", "tpu.count"),
                       "h2d": 0, "d2h": 1},
    "ell_go_sharded": {"phases": ("tpu.launch", "tpu.kernel",
                                  "tpu.fetch", "tpu.assemble"),
                       "h2d": 1, "d2h": 1},
    "ell_bfs_sharded": {"phases": ("tpu.kernel", "tpu.fetch"),
                        "h2d": 2, "d2h": 2},
    "mesh_sparse_go": {"phases": ("tpu.launch", "tpu.kernel",
                                  "tpu.fetch", "tpu.assemble"),
                       "h2d": 2, "d2h": 1},
    "mesh_sparse_bfs": {"phases": ("tpu.kernel", "tpu.fetch"),
                        "h2d": 4, "d2h": 2},
}


class TpuQueryRuntime:
    def __init__(self, storage_nodes, schema_man, remote_provider=None,
                 role: str = "device"):
        # storage_nodes: objects with .kv (NebulaStore); the runtime is the
        # in-process equivalent of a TpuStorageServiceHandler fleet.
        # remote_provider(space_id) -> extra store-shaped views of PEER
        # storageds' led parts (storage/device.RemoteStoreView) — the
        # multi-host mirror fold (VERDICT round-2 missing #1).
        # ``role`` labels this runtime's gauge series: a storaged holds
        # TWO runtimes (the deviceGo-serving one and the bulk-read
        # backend's local-only one, storage/service.py) whose scrape
        # collectors would otherwise overwrite each other's series —
        # the write-while-serve soak reads these gauges, so the
        # collision silently zeroed the serving runtime's absorb/build
        # counters whenever the backend runtime registered second.
        ensure_jax_configured()
        self._role = role
        # what this runtime actually landed on — logged here and
        # published (storaged /status, tpu.device.count gauge) so a
        # harness learns the platform from the process that holds the
        # device, never from its own jax
        self.device_info = device_info()
        self._peaks = DEVICE_PEAKS.get(self.device_info["device_kind"])
        sys.stderr.write(
            "[tpu] {} runtime on platform={platform} "
            "device_kind={device_kind!r} devices={device_count}\n"
            .format(role, **self.device_info))
        self.stores = [n.kv for n in storage_nodes]
        self.remote_provider = remote_provider
        self.sm = schema_man
        self.mirrors: Dict[int, CsrMirror] = {}
        self._plans: Dict[int, _GoPlan] = {}
        self._kernels: Dict[Tuple, object] = {}
        # (table shapes, width rung) whose extract has run at every
        # rung of leavers (_ContinuousGoSession._extract_kernel)
        self.extract_rungs_run: set = set()
        # the same for the join's scatter-pad rungs (_join_kernel)
        self.join_rungs_run: set = set()
        self._lock = threading.Lock()
        self._build_locks: Dict[int, threading.Lock] = {}
        self._rebuilding: set = set()           # spaces rebuilding now
        self._dispatcher = None   # lazy GoBatchDispatcher
        # observability (tests assert the device path actually ran;
        # webservice /get_stats exports these)
        self.stats = {"go_device": 0, "path_device": 0,
                      # FIND PATH: BFS levels the device loops ran,
                      # rows answered, statements cut at find_path_max_paths,
                      # predecessor orders built (one a mirror
                      # generation and signed OVER set, at its first
                      # path statement) and the microseconds they took
                      "path_levels": 0, "path_levels_push": 0,
                      "path_rows": 0, "path_capped": 0,
                      "path_index_builds": 0, "path_index_us": 0,
                      # filtered GO served through the dispatcher:
                      # statements, candidate edges their predicates
                      # were evaluated over, rows kept, and how many
                      # of the statements the native pass filtered
                      # (_assemble_group, _native_filter)
                      "go_where": 0, "where_candidates": 0,
                      "where_rows": 0, "where_native": 0,
                      "mirror_builds": 0,
                      "mirror_deltas": 0, "mirror_absorbs": 0,
                      "mirror_absorb_failed": 0,
                      "mirror_delta_overflow": 0,
                      # streamed peer-delta absorption (multi-host
                      # mirrors fold peer writes at O(delta) —
                      # storage/device.py RemoteStoreView.delta_since)
                      "peer_absorbs": 0, "peer_absorb_events": 0,
                      "peer_absorb_failed": 0,
                      # in-place ELL slot growth (cap-bucket spare-row
                      # claims that absorbed what used to be a
                      # slot-overflow rebuild — ell.plan_ell_absorb)
                      "mirror_slot_grows": 0,
                      "go_sparse": 0, "go_dense": 0,
                      "sparse_overflows": 0,
                      # continuous hops by the branch the program took
                      # on the device (ell.make_continuous_hop_kernel):
                      # push out of the live slot rows / pull over
                      # every slot of the table(s) read; and the hops
                      # (BFS levels too) that read one direction's
                      # table only: all of them unless an OVER set has
                      # both signs; and the ELL slots hops and BFS
                      # levels gathered (a pull's are ell.swept_slots)
                      "hop_sparse": 0, "hop_dense": 0,
                      "hop_onesided": 0,
                      "hop_swept_slots": 0,
                      # continuous joiners, and those of them whose
                      # seat took their first hop
                      # (_ContinuousGoSession.join)
                      "seat_joins": 0, "seat_hops": 0,
                      # continuous leavers whose bitmap the native
                      # pass unpacked (_unpack_lanes): all that fetch
                      # one, or none where the library lacks the entry
                      "unpack_native": 0,
                      "prewarm_compiled": 0, "prewarm_hits": 0,
                      "prewarm_misses": 0, "prewarm_failed": 0,
                      "t_launch_s": 0.0, "t_fetch_s": 0.0,
                      "t_assemble_s": 0.0,
                      # roofline accounting (docs/roofline.md): sampled
                      # block_until_ready device-compute time, the HBM
                      # traffic the sampled dispatches moved under the
                      # ell.dense_hop_bytes model, and the bytes every
                      # fetch pulled over the link
                      "t_device_s": 0.0, "device_bytes_moved": 0,
                      "device_timed_dispatches": 0,
                      "fetch_bytes": 0, "go_reduced": 0,
                      # k-hop neighbourhood counts (GO k STEPS ... YIELD
                      # DISTINCT e._dst | YIELD COUNT(*)) answered by k
                      # hops and a count of the k-th frontier, either
                      # tier: statements, the hops they rode, the sum
                      # of their answers (count_distinct_results)
                      "go_count_distinct": 0, "count_distinct_hops": 0,
                      "count_distinct_vertices": 0,
                      # k-hop neighbourhoods (the same GO with no pipe
                      # behind it) answered by k hops and the k-th
                      # frontier itself, either tier: statements, hops
                      # ridden, rows returned (distinct_results)
                      "go_distinct": 0, "distinct_hops": 0,
                      "distinct_vertices": 0,
                      # statements planned over a two-signed OVER set
                      # (GO ... BIDIRECT): every hop of theirs reads
                      # both direction tables
                      "go_bidirect": 0}
        self._timing_seq = 0
        # shapes the AOT pre-warm compiled / shapes live dispatch used
        # (prewarm_hits/misses make the pre-warm's p99 effect auditable:
        # a miss = a live query paid a first compile the warm should
        # have absorbed)
        self._prewarmed_shapes: set = set()
        # background threads (kernel prewarm, async mirror rebuild)
        # are daemons, but XLA work in flight at interpreter exit
        # tears down C++ state under the running thread ("pure virtual
        # method called" aborts) — shutdown() flags them off and joins
        # what's in flight
        self._bg_stop = threading.Event()
        self._bg_threads: List[threading.Thread] = []
        self._live_shapes: set = set()
        # device circuit breaker per (space, kernel-class): classified
        # runtime failures (XlaRuntimeError / RESOURCE_EXHAUSTED /
        # transfer — storage/device.py classify_device_failure) open it,
        # open declines go straight to the CPU path as degraded
        # TpuDeclines, half-open probes re-admit (docs/durability.md)
        from ..storage.device import DeviceCircuitBreaker
        self.breaker = DeviceCircuitBreaker()
        # device telemetry for the cluster metrics plane: the counters
        # above export as gauges at scrape time (weak bound method — a
        # discarded runtime unregisters itself), and every batched GO
        # dispatch lands one latency observation keyed by its dense
        # batch-width rung
        _stats.register_histogram("tpu.dispatch.latency_us")
        # device-compute time distinct from link RTT: one observation
        # per SAMPLED dispatch (tpu_device_timing_every), measured by a
        # block_until_ready timestamp around the kernel
        _stats.register_histogram("tpu.device_compute.latency_us")
        # absorption wall time per published generation (host plan +
        # CSR splice + device scatter dispatch — docs/roofline.md
        # "The absorb cost model")
        _stats.register_histogram("tpu.absorb.latency_us")
        _stats.register_collector(self._collect_metrics)

    @staticmethod
    def _mirror_nbytes(m: CsrMirror) -> int:
        """Approximate HBM residency of one space's mirror: the core
        CSR arrays plus every finalized column/tag bitmap (the device
        copies mirror these host arrays 1:1, modulo int64->int32/f32
        narrowing — good enough for capacity dashboards)."""
        total = (m.vids.nbytes + m.edge_src.nbytes + m.edge_dst.nbytes
                 + m.edge_etype.nbytes + m.edge_rank.nbytes
                 + m.row_ptr.nbytes)
        for col in list(m.edge_cols.values()) \
                + list(m.vertex_cols.values()):
            vals = getattr(col, "values", None)
            if vals is not None and hasattr(vals, "nbytes"):
                total += vals.nbytes
        for bm in m.has_tag.values():
            total += bm.nbytes
        return int(total)

    def _collect_metrics(self) -> None:
        """Scrape-time gauge refresh (stats.register_collector).  Every
        series carries this runtime's ``runtime`` role label: the
        device-serving and bulk-read-backend runtimes coexist in one
        storaged and cleared-per-scrape gauges from two collectors
        would otherwise shadow each other (whichever registered last
        won — the soak's absorb counters read as zero)."""
        role = self._role
        with self._lock:
            mirrors = dict(self.mirrors)
            n_kernels = len(self._kernels)
            snap = dict(self.stats)
        for space_id, m in mirrors.items():
            _stats.set_gauge("tpu.mirror.hbm_bytes",
                             self._mirror_nbytes(m), space=space_id,
                             runtime=role)
            # generation lifecycle: absorptions and rebuilds both
            # publish a NEW generation; readers admitted after a
            # publish see it (read-your-writes, docs/durability.md)
            _stats.set_gauge("tpu.mirror.generation",
                             getattr(m, "generation", 0),
                             space=space_id, runtime=role)
        _stats.set_gauge("tpu.absorb.count",
                         snap.get("mirror_absorbs", 0), runtime=role)
        _stats.set_gauge("tpu.absorb.failed",
                         snap.get("mirror_absorb_failed", 0),
                         runtime=role)
        _stats.set_gauge("tpu.mirror.delta_overflow",
                         snap.get("mirror_delta_overflow", 0),
                         runtime=role)
        # streamed peer-delta absorption (the multi-host soak's gates:
        # peer_absorb.count grows, remote rebuilds stay flat)
        _stats.set_gauge("tpu.peer_absorb.count",
                         snap.get("peer_absorbs", 0), runtime=role)
        _stats.set_gauge("tpu.peer_absorb.events",
                         snap.get("peer_absorb_events", 0), runtime=role)
        _stats.set_gauge("tpu.peer_absorb.failed",
                         snap.get("peer_absorb_failed", 0), runtime=role)
        _stats.set_gauge("tpu.absorb.slot_grows",
                         snap.get("mirror_slot_grows", 0), runtime=role)
        _stats.set_gauge("tpu.device.count",
                         self.device_info["device_count"],
                         platform=self.device_info["platform"],
                         device_kind=self.device_info["device_kind"],
                         runtime=role)
        _stats.set_gauge("tpu.jit_cache.size", n_kernels, runtime=role)
        _stats.set_gauge("tpu.compile.count",
                         snap.get("kernel_compiles", 0), runtime=role)
        _stats.set_gauge("tpu.mirror.builds",
                         snap.get("mirror_builds", 0), runtime=role)
        _stats.set_gauge("tpu.prewarm.hits", snap.get("prewarm_hits", 0),
                         runtime=role)
        _stats.set_gauge("tpu.prewarm.misses",
                         snap.get("prewarm_misses", 0), runtime=role)
        _stats.set_gauge("tpu.prewarm.failed",
                         snap.get("prewarm_failed", 0), runtime=role)
        # roofline position: sampled-dispatch achieved HBM bandwidth
        # under the dense_hop_bytes model, plus cumulative fetch bytes
        # (the reduction pushdown's ≥4x drop shows here first)
        t_dev = float(snap.get("t_device_s", 0.0))
        if t_dev > 0:
            _stats.set_gauge(
                "tpu.roofline.achieved_gbps",
                round(snap.get("device_bytes_moved", 0) / t_dev / 1e9,
                      3), runtime=role)
        _stats.set_gauge("tpu.fetch.bytes", snap.get("fetch_bytes", 0),
                         runtime=role)
        _stats.set_gauge("tpu.hop.sparse", snap.get("hop_sparse", 0),
                         runtime=role)
        _stats.set_gauge("tpu.hop.dense", snap.get("hop_dense", 0),
                         runtime=role)
        for key, state, _reason in self.breaker.cells_snapshot():
            _stats.set_gauge("tpu.breaker.state",
                             {"closed": 0.0, "half_open": 0.5,
                              "open": 1.0}.get(state, 1.0),
                             space=key[0], kernel_class=key[1],
                             runtime=role)

    def _bump(self, key: str, n=1) -> None:
        """Thread-safe stats counter bump — dispatch leaders run
        concurrently, and a bare ``stats[k] += 1`` read-modify-write
        loses updates between them (guard-inference audit, round 10)."""
        with self._lock:
            self.stats[key] = self.stats.get(key, 0) + n

    def _tick(self, key: str, t0: float) -> float:
        """Accumulate wall time into a stats bucket; returns now."""
        import time
        now = time.perf_counter()
        with self._lock:
            self.stats[key] = self.stats.get(key, 0.0) + (now - t0)
        return now

    @property
    def dispatcher(self):
        """Coalesces concurrent GO queries into one device dispatch
        (graph/batch_dispatch.py)."""
        if self._dispatcher is None:
            from ..graph.batch_dispatch import GoBatchDispatcher
            self._dispatcher = GoBatchDispatcher(self)
        return self._dispatcher

    # ================================================== mirror lifecycle
    def _stores_for(self, space_id: int) -> List:
        """Local stores plus (for multi-host spaces) remote peer views —
        the store list every mirror operation for the space must use
        consistently."""
        if self.remote_provider is None:
            return self.stores
        return self.stores + list(self.remote_provider(space_id))

    def _store_versions(self, space_id: int, stores) -> List[int]:
        return [s.mutation_version(space_id) for s in stores]

    def _space_version(self, space_id: int, stores=None,
                       vers: Optional[List[int]] = None) -> int:
        if stores is None:
            stores = self._stores_for(space_id)
        if vers is None:
            vers = self._store_versions(space_id, stores)
        v = 0
        for s, sv in zip(stores, vers):
            v += sv
            v += 7919 * len(s.part_ids(space_id))
        return v

    def mirror(self, space_id: int) -> Optional[CsrMirror]:
        self._mesh_devices()    # raises MeshUnavailable: no mirror is
        # built (or served) for a mesh the process cannot form
        stores = self._stores_for(space_id)
        # versions captured BEFORE any scan: a write landing during the
        # build makes the published version stale, so the next query
        # rebuilds (or absorbs) — capturing them after the build
        # would mark a mirror missing that write as fresh forever
        vers = self._store_versions(space_id, stores)
        ver = self._space_version(space_id, stores, vers)
        # scheduling point for nebulamc's mirror-swap scenario: a
        # publish may land between the version capture above and the
        # generation capture below — the explorer proves an in-flight
        # dispatch keeps a coherent (older) generation either way
        mc_hooks.mc_yield("runtime.mirror.capture", self)
        with self._lock:
            m = self.mirrors.get(space_id)
            if m is not None \
                    and getattr(m, "_fresh_version", m.build_version) == ver \
                    and not m.expired_now():
                return m
            stale = m if (m is not None and not m.expired_now()) else None
        if stale is not None:
            # absorb, don't rebuild: fold the committed delta into the
            # resident tables as the NEXT mirror generation (in-flight
            # dispatches finish on the one they captured).  Runs under
            # the per-space build lock, NOT the global runtime lock —
            # other spaces keep dispatching through an absorption.
            a = self._try_absorb(space_id, ver)
            if a is not None:
                return a
            if flags.get("mirror_refresh_mode") == "async":
                # absorb declined: serve the stale mirror and degrade
                # to the BACKGROUND rebuild (bounded staleness, like
                # the reference's 120s cache refresh).  At most ONE
                # rebuild per space is in flight; a version bump during
                # the rebuild re-triggers on the next query because the
                # published build_version won't match _space_version
                with self._lock:
                    cur = self.mirrors.get(space_id)
                    spawn = cur is not None \
                        and space_id not in self._rebuilding \
                        and not self._bg_stop.is_set()
                    if spawn:
                        # the marker outlives this call by design: the
                        # background _rebuild_async's finally discards
                        # it when the rebuild lands (or dies)
                        # nebulint: obligation=handed-off/discarded-by-rebuild-async
                        self._rebuilding.add(space_id)
                if cur is not None:
                    if spawn:
                        # tracked spawn OUTSIDE the global lock
                        # (_spawn_bg takes it) so shutdown() can join
                        # an in-flight rebuild's XLA work too
                        self._spawn_bg(
                            lambda: self._rebuild_async(space_id, ver,
                                                        cur),
                            f"mirror-rebuild-{space_id}")
                    return cur
        # sync build OUTSIDE the global lock: a multi-host space streams
        # full remote part scans over RPC here, and holding the runtime
        # lock across that stalled every other space's dispatches (and a
        # hung peer wedged the whole runtime).  The per-space build lock
        # keeps concurrent first-queries from paying duplicate builds.
        with self._build_lock(space_id):
            # re-capture versions: they may have advanced while we
            # waited for the previous builder, and publishing a build
            # made for an older version over a newer mirror would
            # regress freshness
            stores = self._stores_for(space_id)
            vers = self._store_versions(space_id, stores)
            ver = self._space_version(space_id, stores, vers)
            with self._lock:
                m = self.mirrors.get(space_id)
                if m is not None \
                        and getattr(m, "_fresh_version",
                                    m.build_version) == ver \
                        and not m.expired_now():
                    return m     # another thread built while we waited
            with tracing.span("tpu.mirror.build", space=space_id) as bs:
                built = build_mirror(space_id, stores, self.sm)
                if bs is not None:
                    bs.tag(edges=built.m, vertices=built.n)
            with self._lock:
                return self._publish(space_id, built, ver, stores, vers)

    def _build_lock(self, space_id: int) -> threading.Lock:
        with self._lock:
            lk = self._build_locks.get(space_id)
            if lk is None:
                # seam-constructed (common/mc_hooks.py): nebulamc's
                # mirror-swap scenario substitutes an instrumented lock
                lk = self._build_locks[space_id] = \
                    mc_hooks.Lock("tpu.build")
            return lk

    def _publish(self, space_id: int, m: CsrMirror, ver: int,
                 stores=None, vers: Optional[List[int]] = None,
                 cursors: Optional[Dict[int, int]] = None,
                 absorbed_from: Optional[CsrMirror] = None
                 ) -> CsrMirror:
        """Install a mirror GENERATION (caller holds the lock): either
        a full build or an absorbed next generation.  ``vers`` are the
        per-store versions captured BEFORE the build scan — they
        become the delta cursors, so a write racing the scan is either
        re-delivered by delta_since (where a same-identity put
        supersedes the already-scanned base row via base_dead + an
        overlay override — build_delta_mirror) or surfaces as a
        version mismatch; it can never be silently skipped.  Absorbed
        publishes pass the post-absorption ``cursors`` instead.

        Generations are immutable-once-published: in-flight dispatches
        keep the object (and device tables) they captured; a write
        acked at generation g is visible to every query admitted after
        g publishes (read-your-writes — docs/durability.md)."""
        if stores is None:
            stores = self._stores_for(space_id)
        if vers is None:
            vers = self._store_versions(space_id, stores)
        m.build_version = ver
        m._fresh_version = ver       # advanced by vertex-only absorbs
        m._delta_cursors = cursors if cursors is not None \
            else {i: v for i, v in enumerate(vers)}
        m._part_sig = tuple(len(s.part_ids(space_id))
                            for s in stores)
        prev = absorbed_from if absorbed_from is not None \
            else self.mirrors.get(space_id)
        m.generation = getattr(prev, "generation", 0) + 1
        if absorbed_from is not None:
            self.stats["mirror_absorbs"] += 1
            self.stats["mirror_deltas"] += 1
        else:
            self.stats["mirror_builds"] += 1
        self.mirrors[space_id] = m
        # a freshly published generation is a new device state: an
        # OPEN breaker half-opens so the next query probes against the
        # new state instead of waiting out the clock (the PR 4
        # _upto_declined generation-check stance, docs/durability.md)
        self.breaker.reset_space(space_id)
        # NOTE: cached kernels are keyed by TABLE SHAPES and take the
        # tables as arguments (ell.py), so they survive rebuilds AND
        # absorptions (shape_sig moves with a generation only where a
        # spare is claimed or a pull's reach crosses its step).
        return m

    # ============================================== delta absorption
    def _try_absorb(self, space_id: int,
                    caller_ver: int) -> Optional[CsrMirror]:
        """Fold committed write deltas into the resident tables as the
        NEXT immutable mirror generation — O(delta) per absorption
        instead of the O(m)-scan rebuild (docs/durability.md "The
        generation state machine").  None means this window can't
        absorb (vertex-plan change, slot overflow past the hub budget,
        delta-budget overflow, opaque events, part moves): the caller
        takes the rebuild path, and the failure is counted + journaled
        ONCE per declined version, not per query — a space that can't
        absorb at version v (e.g. remote-backed: delta_since is always
        opaque) short-circuits here until a new write moves the
        version, so stale-serving traffic neither re-pays the
        whole-fleet version poll under the build lock nor floods the
        bounded event journal.  ``caller_ver`` is the space version
        mirror() already captured — the cheap checks run against it
        before any RPC is re-issued."""
        if not flags.get("mirror_absorb", True):
            return None
        import time
        with self._build_lock(space_id):
            with self._lock:
                m = self.mirrors.get(space_id)
                if m is None or m.expired_now():
                    return None
                if getattr(m, "_fresh_version",
                           m.build_version) == caller_ver:
                    return m     # absorbed/rebuilt while we waited
                if getattr(m, "_delta_cursors", None) is None:
                    return None
                if getattr(m, "_absorb_declined_ver",
                           None) == caller_ver:
                    return None  # already declined at this version
            # re-capture ONCE under the build lock: absorb up to the
            # LATEST committed state (writes may have landed while we
            # waited), and publish with matching cursors
            stores = self._stores_for(space_id)
            vers = self._store_versions(space_id, stores)
            ver = self._space_version(space_id, stores, vers)
            with self._lock:
                if getattr(m, "_fresh_version", m.build_version) == ver:
                    return m
            t0 = time.perf_counter()
            with tracing.span("tpu.absorb", space=space_id) as sp:
                out, reason, n_events = self._absorb_once(
                    space_id, m, ver, stores, vers)
                if sp is not None:
                    sp.tag(ok=out is not None, reason=reason,
                           events=n_events)
            if out is None:
                with self._lock:
                    # negative-cache per version: the next query only
                    # re-attempts after a new write moves the version
                    # (the rebuild that follows publishes a fresh
                    # mirror and drops this marker anyway)
                    m._absorb_declined_ver = ver
                self._note_absorb_failure(space_id, reason, n_events)
                return None
            wall_us = (time.perf_counter() - t0) * 1e6
            _stats.observe("tpu.absorb.latency_us", wall_us)
            # mirror maintenance on the device timeline: absorb
            # windows interleave with query dispatches, and "why was
            # this tick slow" is often "an absorb ran" (flight.py)
            _flight.recorder.note_dispatch(
                "ell_absorb", space=space_id, events=n_events,
                wall_us=int(wall_us),
                generation=int(getattr(out, "generation", -1)))
            return out

    def _note_absorb_failure(self, space_id: int, reason: str,
                             n_events: int) -> None:
        """Satellite observability: an absorb decline is a REBUILD
        about to happen — count it (delta-budget overflows get their
        own counter, the soak asserts it stays zero) and journal it."""
        from ..common.events import journal
        with self._lock:
            self.stats["mirror_absorb_failed"] += 1
            if reason == protocol.ABSORB_DELTA_OVERFLOW:
                self.stats["mirror_delta_overflow"] += 1
        journal.record("mirror.absorb_failed",
                       detail=f"space {space_id}: {reason} "
                              f"({n_events} events)",
                       space=space_id, reason=reason, events=n_events)

    def _absorb_once(self, space_id: int, m: CsrMirror, ver: int,
                     stores, vers):
        """One absorption attempt against the published mirror ``m``
        (caller holds the per-space build lock).  Returns
        (mirror | None, reason, event count): the published next
        generation (or ``m`` itself for vertex-only windows, whose
        in-place commit IS the absorb), or None with the decline
        reason."""
        sig = tuple(len(s.part_ids(space_id)) for s in stores)
        if getattr(m, "_part_sig", None) != sig:
            return None, protocol.ABSORB_PART_MOVED, 0
        if len(stores) != len(m._delta_cursors):
            return None, protocol.ABSORB_PEER_SET_CHANGED, 0
        new_events = []
        cursors = dict(m._delta_cursors)
        n_peer_events = 0
        for i, s in enumerate(stores):
            now_v = vers[i]
            if now_v == cursors[i]:
                continue
            evs = s.delta_since(space_id, cursors[i])
            if evs is None:
                # a remote view types its stream break (peer-restarted,
                # peer-leader-changed, peer-cursor-truncated, ...) —
                # the journaled reason then names WHY the rebuild is
                # about to be paid instead of a generic opaque-events
                reason = getattr(s, "last_delta_decline", None) \
                    or protocol.ABSORB_OPAQUE_EVENTS
                if getattr(s, "is_remote", False):
                    with self._lock:
                        self.stats["peer_absorb_failed"] = \
                            self.stats.get("peer_absorb_failed", 0) + 1
                return None, reason, 0
            if getattr(s, "is_remote", False):
                n_peer_events += len(evs)
            new_events.extend(evs)
            cursors[i] = now_v
        n_events = len(new_events)
        edge_events = [e for e in new_events if e[0] != "vput"]
        if len(edge_events) > int(flags.get("mirror_delta_max") or 4096):
            return None, protocol.ABSORB_DELTA_OVERFLOW, n_events
        from .csr import (build_delta_mirror, commit_vertex_plan,
                          plan_vertex_events)
        # ORDER MATTERS for commit atomicity: plan the vertex writes
        # (no mutation), build everything declinable (overlay, slot
        # plan, merged CSR, device scatter), and only when NOTHING can
        # decline anymore commit the in-place vertex plan + publish —
        # a decline after mutating would expose half of a commit batch
        # (the device-side analogue of the torn-scan guard)
        vplan = plan_vertex_events(m, new_events, self.sm, space_id)
        if vplan is None:
            return None, protocol.ABSORB_VERTEX_UNABSORBABLE, n_events

        def commit_in_place():
            with self._lock:
                commit_vertex_plan(m, vplan)
                m._delta_cursors = cursors
                m._fresh_version = ver
                self.stats["mirror_deltas"] += 1
            self._note_peer_absorbed(space_id, n_peer_events, m)
            return m

        if not edge_events:
            # vertex-only window: numeric single-element stores commit
            # in place (csr.commit_vertex_plan's values-first/valid-
            # last stance) — no table content moves, no new generation
            return commit_in_place(), protocol.ABSORB_VERTEX_IN_PLACE, \
                n_events
        d = build_delta_mirror(m, edge_events, self.sm, space_id)
        if d is None:
            return None, protocol.ABSORB_OVERLAY_UNBUILDABLE, n_events
        if len(d.extra_vids):
            return None, protocol.ABSORB_VERTEX_PLAN_CHANGE, n_events
        if d.m == 0 and not len(d.base_dead):
            # the window's edge events collapsed to nothing (e.g. a
            # put+delete of the same fresh edge): cursors still advance
            return commit_in_place(), protocol.ABSORB_NO_OP, n_events
        new_m = self._absorb_build(space_id, m, d)
        if new_m is None:
            return None, protocol.ABSORB_SLOT_OVERFLOW, n_events
        with self._lock:
            commit_vertex_plan(m, vplan)
            self._publish(space_id, new_m, ver, stores, vers,
                          cursors=cursors, absorbed_from=m)
        from ..common.events import journal
        journal.record("mirror.absorbed",
                       detail=f"space {space_id}: {int(d.m)} edge rows "
                              f"in, {int(len(d.base_dead))} tombstones "
                              f"-> generation {new_m.generation}",
                       space=space_id,
                       generation=int(new_m.generation),
                       edges=int(d.m), deletes=int(len(d.base_dead)),
                       claims=int(getattr(new_m, "_slot_claims", 0)))
        self._note_peer_absorbed(space_id, n_peer_events, new_m)
        return new_m, "absorbed", n_events

    def _note_peer_absorbed(self, space_id: int, n_peer_events: int,
                            m: CsrMirror) -> None:
        """Peer-delta accounting: an absorption window that folded ≥1
        event STREAMED from a remote peer (deviceScanDelta) counts as
        a peer absorb — the multi-host soak's proof that peer writes
        ride ell_absorb at O(delta) instead of the O(m) remote mirror
        rebuild (docs/durability.md)."""
        if n_peer_events <= 0:
            return
        with self._lock:
            self.stats["peer_absorbs"] = \
                self.stats.get("peer_absorbs", 0) + 1
            self.stats["peer_absorb_events"] = \
                self.stats.get("peer_absorb_events", 0) + n_peer_events
        from ..common.events import journal
        journal.record("mirror.peer_absorbed",
                       detail=f"space {space_id}: {n_peer_events} peer "
                              f"events -> generation "
                              f"{getattr(m, 'generation', 0)}",
                       space=space_id, events=n_peer_events,
                       generation=int(getattr(m, "generation", 0)))

    def _absorb_build(self, space_id: int, m: CsrMirror,
                      d) -> Optional[CsrMirror]:
        """The CSR + ELL halves of one absorption: merged host CSR
        (new mirror sharing the vertex side), replacement-row slot
        plan, copy-on-write host ELL, and the device scatter that
        derives the next generation's tables FROM the resident ones —
        the h2d upload is the O(delta) replacement rows, never the
        O(table) re-upload a rebuild pays.  None = slot overflow.
        Caller holds the per-space build lock."""
        import jax.numpy as jnp
        from .csr import absorb_overlay
        from .ell import (absorb_update_arrays, apply_ell_absorb_host,
                          make_ell_absorb_kernel,
                          make_sharded_ell_absorb_kernel,
                          plan_ell_absorb)
        ix = self.ell(m)
        dead = np.asarray(getattr(d, "base_dead", ()), dtype=np.int64)
        # the ELL keys rows by DST (slots hold srcs) — overlay rows
        # and tombstoned base rows feed the plan in that orientation.
        # claims collect in-place slot GROWTH (an overflowing vertex
        # takes unclaimed spare rows instead of forcing the rebuild)
        claims: List = []
        plan = plan_ell_absorb(
            ix, d.edge_dst, d.edge_src, d.edge_etype,
            m.edge_dst[dead], m.edge_src[dead], m.edge_etype[dead],
            claims_out=claims)
        if plan is None:
            return None
        new_m = absorb_overlay(m, d)
        if new_m is None:
            return None
        ix2 = apply_ell_absorb_host(ix, plan, new_m.m, claims=claims)
        counts, upd = absorb_update_arrays(ix, plan)
        rows_a = [jnp.asarray(u[0]) for u in upd]
        nn_a = [jnp.asarray(u[1]) for u in upd]
        ne_a = [jnp.asarray(u[2]) for u in upd]
        nb = len(ix.bucket_nbr)
        if ix._device is not None:
            # scatter the replacement rows into the RESIDENT device
            # tables; the outputs seed the next generation's device
            # arrays (the old generation's buffers are not donated —
            # in-flight dispatches still read them)
            owner_dev, *tables = ix.kernel_args()
            kern = self._kernel(
                ("ell_absorb", ix.shape_sig(), counts),
                lambda: make_ell_absorb_kernel(ix, counts))
            outs = kern(*rows_a, *nn_a, *ne_a, *tables)
            if claims:
                # a claimed spare changed extra_owner content: the
                # next generation's owner scatter needs the NEW array
                # on device (a few bytes — never the table re-upload)
                owner_dev = jnp.asarray(ix2.extra_owner)
            ix2._device = tuple(list(outs[g * nb:(g + 1) * nb])
                                for g in range(4)) + (owner_dev,)
        cached = getattr(m, "_mesh_tables_cache", None)
        if cached is not None and cached[1] is not None:
            # per-shard absorption of the resident replicated-frontier
            # mesh tables: each chip applies only the rows it owns —
            # zero collectives, zero ICI (meshaudit-declared)
            k, (mesh, tables, reals) = cached
            padded = [int(a.shape[0]) for a in tables[:nb]]
            skern = self._kernel(
                ("ell_absorb_sharded", ix.shape_sig(), counts, k),
                lambda: make_sharded_ell_absorb_kernel(
                    mesh, "parts", ix, padded, counts))
            souts = skern(*rows_a, *nn_a, *ne_a, *tables)
            new_m._mesh_tables_cache = (k, (mesh, tuple(souts), reals))
        # the frontier-sharded (ShardedEll) per-chunk tables rebuild
        # lazily from the UPDATED host arrays on the next mesh-sparse
        # query — a device_put, never a store re-scan
        new_m._ell = ix2
        # carry what stays valid across generations: the warm ledger
        # (kernels are shape-keyed) and the structural hub metadata —
        # UNLESS a growth claim just changed extra_owner, which is
        # exactly what those caches derive from (expansion runs, merge
        # slots): a grown generation re-derives them
        if hasattr(m, "_prewarm_done"):
            new_m._prewarm_done = m._prewarm_done
        if not claims:
            for cache_attr in ("_hub_exp_cache", "_hub_merge_cache"):
                val = getattr(m, cache_attr, None)
                if val is not None:
                    setattr(new_m, cache_attr, val)
        else:
            self._bump("mirror_slot_grows", len(claims))
            # the publish-time mirror.absorbed record (one per window,
            # _absorb_once) carries the claim count — a second journal
            # entry here would double-count absorptions on /events
            new_m._slot_claims = len(claims)
        return new_m

    def mirror_full(self, space_id: int) -> Optional[CsrMirror]:
        """Alias of mirror(): every published generation is already
        overlay-free (committed deltas ABSORB into the tables before
        publishing — docs/durability.md), so the raw-base-array
        consumers (BFS / FIND PATH, the sharded paths, the storage
        bulk-read backend) read the same generation every other path
        serves.  Kept as a seam so those callers document their
        raw-array dependency."""
        return self.mirror(space_id)

    def _rebuild_async(self, space_id: int, ver: int,
                       stale: CsrMirror) -> None:
        try:
            if self._bg_stop.is_set():
                return             # shutting down; finally clears the
                                   # in-flight marker
            stores = self._stores_for(space_id)
            vers = self._store_versions(space_id, stores)  # pre-build
            m = build_mirror(space_id, stores, self.sm)
            with self._lock:
                # publish only if the mirror we set out to replace is
                # still the installed one — anything else means a sync
                # install (possibly newer) won the race; don't regress
                if self.mirrors.get(space_id) is stale:
                    self._publish(space_id, m, ver, stores, vers)
        except Exception:      # noqa: BLE001 — a failed refresh keeps
            pass               # serving the stale mirror; next query retries
        finally:
            with self._lock:
                self._rebuilding.discard(space_id)

    # ================================================== GO planning
    def _plan_go(self, space_id: int, alias_to_etype: Dict[str, Tuple],
                 where_expr: Optional[Expression],
                 pushed_mode: bool) -> Optional[_GoPlan]:
        """Compile a GO plan against the space's current mirror, or None
        when the device can't reproduce CPU semantics bit-for-bit.
        Shared by the in-process executor gate (can_run_go) and the
        cross-process RPC entry (serve_go)."""
        try:
            m = self.mirror(space_id)
        except MeshUnavailable:
            raise
        except Exception as e:      # noqa: BLE001 — build/transfer failed
            # a classified device failure here (HBM OOM during the
            # mirror upload, transfer error) feeds the breaker so
            # repeated failing builds open it instead of every query
            # re-paying a doomed build
            from ..storage.device import classify_device_failure
            reason = classify_device_failure(e)
            if reason is not None:
                self.breaker.record_failure((space_id, "go"), reason)
            return None
        filter_cval = None
        filter_used: Dict[str, Tuple] = {}
        compiler = ExprCompiler(m, space_id, self.sm, alias_to_etype)
        if where_expr is not None:
            try:
                filter_cval = compiler.compile(where_expr)
            except CompileError:
                return None
            filter_used = dict(compiler.used)
            if compiler.div_guards and not pushed_mode:
                # graphd-side WHERE raises ExprError on a real x/0; a
                # vectorized mask can't raise for one row — let the
                # CPU path run it
                return None
        return _GoPlan(
            m, alias_to_etype, filter_cval, filter_used,
            pushed_mode=pushed_mode, compiler=compiler,
            expr_str=(str(where_expr) if where_expr is not None else None),
            sc_or=_filter_has_or(where_expr))

    def can_run_go(self, space_id: int, etypes: List[int], sentence,
                   pushed: Optional[bytes], remnant: Optional[Expression],
                   src_refs, dst_refs, has_input: bool) -> bool:
        if flags.get("storage_backend") == "cpu":
            return False        # nebulint: carveout=cpu-backend
        if has_input:
            return False        # nebulint: carveout=piped-input
        if self.breaker.is_open((space_id, "go")):
            # route to CPU without paying a plan/mirror attempt against
            # a known-broken device (non-mutating peek: the half-open
            # probe token is consumed at dispatch, not here)
            return False        # nebulint: carveout=breaker-open
        if getattr(sentence.step, "upto", False) \
                and sentence.step.steps > 1 \
                and int(flags.get("tpu_mesh_devices") or 0) > 1:
            # UPTO runs on the cumulative-frontier kernel variants
            # (single-device sparse + dense); the frontier-sharded
            # mesh kernels have no union accumulator — CPU loop there
            return False        # nebulint: carveout=upto-mesh
        # alias map (the resolution GoExecutor did)
        s = sentence
        try:
            alias_to_etype = s.over.resolve(self.sm, space_id)
        except KeyError:
            return False        # nebulint: carveout=schema-miss

        where_expr = s.where.filter if s.where else None
        plan = self._plan_go(space_id, alias_to_etype, where_expr,
                             pushed_mode=(pushed is not None))
        if plan is None:
            return False        # nebulint: carveout=plan-decline
        self._plans[id(sentence)] = plan
        return True

    # ================================================== GO execution
    def run_go(self, executor, space_id: int, start_vids: List[int],
               etypes: List[int], steps: int, etype_to_alias: Dict[int, str],
               yield_cols, distinct: bool, where_expr,
               edge_props, vertex_props,
               upto: bool = False, reduce=None) -> InterimResult:
        from ..graph.executors.base import ExecError

        s = executor.sentence
        plan = self._plans.pop(id(s), None)
        if plan is None:   # defensive: re-prepare
            raise ExecError("TPU plan missing (can_run_go not called)")
        columns, rows = self._go_via_dispatcher(
            space_id, plan, start_vids, etypes, steps, etype_to_alias,
            yield_cols, distinct, where_expr, ExecError, upto=upto,
            reduce=reduce)
        out = InterimResult(columns, rows)
        if reduce is not None:
            # marker for the fused-pipe helper (traverse.py): the
            # device DID apply the reduction (a CPU fallback never
            # sets it, so the helper re-derives from full rows there)
            out.reduced = tuple(reduce)
        return out

    def serve_go(self, space_id: int, start_vids: List[int],
                 etypes: List[int], steps: int,
                 etype_to_alias: Dict[int, str], yield_specs,
                 distinct: bool, where_blob: Optional[bytes],
                 pushed_mode: bool, upto: bool = False, reduce=None):
        """storaged-side RPC half of the cross-process device path
        (storage/service.py rpc_deviceGo → here): decode the shipped
        WHERE/YIELD expression trees, plan against the local mirror and
        execute.  Returns (columns, rows); raises TpuDecline when the
        CPU path must take over, DeviceExecError for real query errors
        (both defined jax-free in storage/device.py)."""
        from types import SimpleNamespace
        from ..filter.expressions import decode_expr
        from ..storage.device import DeviceExecError, TpuDecline

        try:
            where_expr = (decode_expr(where_blob)
                          if where_blob else None)
            yield_cols = [SimpleNamespace(expr=decode_expr(blob),
                                          alias=alias)
                          for blob, alias in yield_specs]
        except Exception as e:      # noqa: BLE001 — undecodable tree
            # nebulint: carveout=expr-undecodable
            raise TpuDecline(f"undecodable expression: {e}")
        alias_to_etype = _aliases_of(etype_to_alias)
        if upto and int(flags.get("tpu_mesh_devices") or 0) > 1:
            # the frontier-sharded mesh kernels have no UPTO union
            # accumulator; the graphd side can't see this flag, so the
            # decline happens here — BEFORE the plan build, and the
            # client caches it per space so repeat UPTO queries don't
            # re-pay the RPC round trip (storage/device.py)
            # nebulint: carveout=upto-mesh
            raise TpuDecline("UPTO on a mesh-sharded space")
        plan = self._plan_go(space_id, alias_to_etype, where_expr,
                             pushed_mode)
        if plan is None:
            # nebulint: carveout=plan-decline
            raise TpuDecline("device cannot reproduce this query")
        return self._go_via_dispatcher(
            space_id, plan, start_vids, etypes, steps, etype_to_alias,
            yield_cols, distinct, where_expr, DeviceExecError, upto=upto,
            reduce=reduce)

    def _go_via_dispatcher(self, space_id: int, plan: _GoPlan,
                           start_vids: List[int], etypes: List[int],
                           steps: int, etype_to_alias: Dict[int, str],
                           yield_cols, distinct: bool, where_expr,
                           ExcType, upto: bool = False, reduce=None):
        """Submit one GO onto the coalescing dispatcher; the batch
        leader (or the continuous pump) runs the whole device + host
        pipeline for every rider (go_batch_execute), a WHERE included:
        its hops ride beside unfiltered statements of the same key and
        the predicate meets the final frontier's candidate edges at
        assembly (_assemble_group)."""
        from ..storage.device import TpuDecline, classify_device_failure
        bkey = (space_id, "go")
        why = self.breaker.admit(bkey)
        if why is not None:
            # closed-breaker admit is a dict probe + compare
            # (micro_bench recovery_path); an OPEN one declines here —
            # degraded, so the CPU fallback surfaces the state
            tracing.annotate("tpu.breaker", state="open", space=space_id,
                             kernel_class="go")
            # nebulint: carveout=breaker-open
            raise TpuDecline(why, degraded=True)
        et_tuple = tuple(sorted(set(etypes)))
        self._bump("go_device")
        if sides_read(et_tuple) == 2:
            self._bump("go_bidirect")
        # what _plan_go declined went to the CPU executor before we
        # ever got here
        try:
            q = _GoQuery(start_vids, plan, yield_cols, distinct,
                         where_expr, etype_to_alias, ExcType,
                         deadline=deadlines.current())
            result, _m = self.dispatcher.submit_batched(
                ("go_batch_execute", space_id, et_tuple, steps, upto,
                 tuple(reduce) if reduce is not None else None),
                q)
        except Exception as e:      # noqa: BLE001 — classify, then rethrow
            reason = classify_device_failure(e)
            if reason is None:
                # query/control errors (exec errors, deadline) pass
                # through — they prove nothing about device health, so
                # only hand a half-open probe token back (the next
                # query re-probes); never close the cell on them
                self.breaker.release_probe(bkey)
                raise
            self.breaker.record_failure(bkey, reason)
            tracing.annotate("tpu.breaker", state="failure",
                             space=space_id, kernel_class="go",
                             reason=reason)
            # nebulint: carveout=device-failure
            raise TpuDecline(f"device runtime failure ({reason}): {e}",
                             degraded=True) from e
        self.breaker.record_success(bkey)
        return result

    # ------------------------------------------------ batch entry point
    def go_batch_execute(self, space_id: int, queries: List[_GoQuery],
                         et_tuple: Tuple[int, ...], steps: int,
                         upto: bool = False, reduce=None):
        """Dispatcher leader entry: run a whole batch of GO queries —
        one device launch for the frontier advance, then one vectorized
        host pass per (WHERE, YIELD) signature group.

        Returns a _Pending whose finish() yields
        (results, mirror): results[i] is (columns, rows) or an
        Exception instance for per-query failures (the dispatcher maps
        those back to their own waiters only — VERDICT round-2 weak #5:
        a poisoned query must not fail its batch)."""
        import time
        t0 = time.perf_counter()
        # final pre-launch deadline gate (docs/admission.md): the
        # dispatcher filtered at snapshot time, but a slow mirror
        # build / leadership handoff can age a batch — an entry whose
        # budget ran out here is dropped from the launch and its
        # waiter woken with DEADLINE_EXCEEDED via the per-query
        # exception slots, exactly like a poisoned query
        expired: Dict[int, Exception] = {}
        live = queries
        if any(q.deadline is not None and q.deadline.expired()
               for q in queries):
            live = []
            for i, q in enumerate(queries):
                if q.deadline is not None and q.deadline.expired():
                    expired[i] = DeadlineExceeded(
                        "go: budget exhausted before device launch")
                else:
                    live.append(q)
        if not live:
            return [expired[i] for i in range(len(queries))], None
        starts = [q.start_vids for q in live]
        # a k-hop neighbourhood count is the size of the k-th frontier:
        # one advance more than a GO's rows need, and nothing reduced
        # on the way (the dense count program counts EDGES of the last
        # hop and must not answer it) — the launch is a plain
        # (steps + 1)-step GO's, on whichever program serves that
        count_distinct = reduce is not None \
            and reduce[0] == "count_distinct"
        # ... and the k-hop neighbourhood itself is that frontier
        distinct = reduce is not None and reduce[0] == "distinct"
        with tracing.span("tpu.launch", queries=len(live),
                          steps=steps):
            if count_distinct or distinct:
                launch = self._launch_frontiers(space_id, starts,
                                                et_tuple, steps + 1)
            else:
                launch = self._launch_frontiers(space_id, starts,
                                                et_tuple, steps,
                                                upto=upto, reduce=reduce)
        self._tick("t_launch_s", t0)
        # finish() may run on a different thread (the dispatcher
        # pipelines batches) — carry the leader's trace context across
        tctx = tracing.capture()

        def finish():
            t1 = time.perf_counter()
            with tracing.attach_captured(tctx):
                with tracing.span("tpu.fetch"):
                    vs_lists, m = launch()
                t1 = self._tick("t_fetch_s", t1)
                if count_distinct:
                    # the frontier arrays are ascending and without
                    # repeats by construction: their lengths are the
                    # answers (a mirror with no edge advances nothing
                    # and hands the starts back: nobody is reached)
                    results = self.count_distinct_results(
                        [len(vs) if m.m else 0 for vs in vs_lists],
                        [steps] * len(live))
                elif distinct:
                    results = self.distinct_results(
                        m, live, vs_lists, [steps] * len(live))
                elif reduce is not None and reduce[0] == "count":
                    # COUNT(*) pushdown: no candidate assembly, no row
                    # materialization — the result per query is one
                    # number (device-counted on the dense path, a
                    # vectorized degree sum over the fetched frontier
                    # everywhere else)
                    results = self._count_results(m, vs_lists,
                                                  len(live), et_tuple)
                    with self._lock:
                        self.stats["go_reduced"] += len(live)
                else:
                    if reduce is not None:
                        with self._lock:
                            self.stats["go_reduced"] += len(live)
                    with tracing.span("tpu.assemble",
                                      queries=len(live)):
                        results = self._assemble_results(
                            space_id, m, live, vs_lists, et_tuple)
            self._tick("t_assemble_s", t1)
            # whole-dispatch latency (launch -> fetch -> assemble),
            # bucketed by the dense batch-width rung this query count
            # rides — one histogram update per BATCH, not per query
            _stats.observe("tpu.dispatch.latency_us",
                           (time.perf_counter() - t0) * 1e6,
                           width=self._batch_width(len(live)))
            if not expired:
                return results, m
            it = iter(results)
            return [expired[i] if i in expired else next(it)
                    for i in range(len(queries))], m

        return _Pending(finish)

    def _count_results(self, m: CsrMirror, vs_lists, nq: int,
                       et_tuple: Tuple[int, ...]):
        """Per-query COUNT(*) results from a reduced launch: either the
        device already counted (_DeviceCounts) or the fetched frontier
        lists fold through the cached per-vertex degree vector — never
        row materialization."""
        if isinstance(vs_lists, _DeviceCounts):
            counts = vs_lists.arr
        else:
            deg = self._deg_host(m, et_tuple)
            counts = [int(deg[np.asarray(vs, np.int64)].sum())
                      if len(vs) else 0 for vs in vs_lists]
        return [(["__count__"], [[int(c)]]) for c in counts[:nq]]

    def count_distinct_results(self, counts, hops):
        """Results of k-hop neighbourhood counts from the sizes of
        their k-th frontiers, in the form _count_results gives (the
        fused pipe reads the number off the one row), and the counters
        that say such statements were answered this way: statements,
        hops ridden, vertices counted."""
        with self._lock:
            self.stats["go_count_distinct"] += len(counts)
            self.stats["count_distinct_hops"] += int(sum(hops))
            self.stats["count_distinct_vertices"] += int(sum(counts))
            self.stats["go_reduced"] += len(counts)
        return [(["__count__"], [[int(c)]]) for c in counts]

    def distinct_results(self, m: CsrMirror, queries: List[_GoQuery],
                         vs_lists, hops):
        """Results of k-hop neighbourhoods (GO k STEPS ... YIELD
        DISTINCT e._dst, reduce "distinct") from their k-th frontiers:
        a frontier array is ascending and without repeats by
        construction, so its vertices' ids are the one column, each
        once, and there is no row where nobody is reached (a mirror
        with no edge advances nothing and hands the starts back).  No
        candidate edge, no gather, no sort.  The span is the assembly's
        own name with tags ``reduce`` and ``vertices`` (a windowed
        leader's, or a continuous rider's on its own thread); counters:
        statements, hops ridden, rows returned."""
        from ..graph.interim import ColumnarRows
        results = []
        with tracing.span("tpu.assemble", queries=len(queries),
                          reduce="distinct") as sp:
            for q, vs in zip(queries, vs_lists):
                ids = m.vids[np.asarray(vs if m.m else (), np.int64)]
                results.append((
                    [c.alias or _default_col_name(c.expr)
                     for c in q.yield_cols],
                    ColumnarRows([ids], len(ids)) if len(ids) else []))
            vertices = sum(len(rows) for _c, rows in results)
            if sp is not None:
                sp.tag(vertices=vertices)
        with self._lock:
            self.stats["go_distinct"] += len(results)
            self.stats["distinct_hops"] += int(sum(hops))
            self.stats["distinct_vertices"] += vertices
            self.stats["go_reduced"] += len(results)
        return results

    # ------------------------------------- continuous dispatch seam
    def continuous_session(self, space_id: int,
                           et_tuple: Tuple[int, ...],
                           min_lanes: int = 1,
                           seat_rows: Optional[int] = None):
        """Anchor one continuous-dispatch device session for a
        (space, OVER set) stream (graph/batch_dispatch.py
        ContinuousGoScheduler): the resident packed frontier pair plus
        the hop/join/clear/extract kernels over the CURRENT mirror
        generation.  Returns None when the space cannot ride the
        seat-map path — mesh-sharded tables (the replicated-frontier
        mesh kernels have no resident-pair protocol yet) or an
        empty/unbuildable mirror — and the caller falls back to the
        windowed pipeline.  ``seat_rows`` is a test's own row budget
        for the seat's first hop (_ContinuousGoSession.join)."""
        # flag check, not _mesh_only(): the mesh cache is request-path
        # state and the pump must not warm it from its own thread
        if int(flags.get("tpu_mesh_devices") or 0) > 1:
            return None
        m = self.mirror(space_id)
        if m is None or m.m == 0:
            return None
        ix = self.ell(m)
        # smallest batch-width rung covering the caller's demand
        # (``min_lanes`` = arrival backlog at anchor time): the stream
        # re-anchors one rung wider when the seat map saturates, so
        # lane capacity rides the SAME pinned ladder the windowed
        # kernels use — never a new program shape
        ladder = sorted(int(w) for w in
                        str(flags.get("go_batch_widths") or
                            "128,1024").split(",") if w.strip()) \
            or [128]
        B = ladder[-1]
        for w in ladder:
            if min_lanes <= w:
                B = w
                break
        return _ContinuousGoSession(self, space_id, m, ix, et_tuple, B,
                                    seat_rows=seat_rows)

    def continuous_results(self, space_id: int, m: CsrMirror,
                           queries: List[_GoQuery], reduces,
                           vs_lists, et_tuple: Tuple[int, ...]):
        """Post-frontier half for continuous leavers: COUNT riders
        fold the cached degree vector over their extracted frontier
        (route-independent — identical to the windowed non-device
        count fold), everything else (full fetch, LIMIT riders whose
        pipe slices, UPTO unions, a WHERE) runs the same grouped
        assembly the windowed leader uses.  The pump calls it over the
        leavers of a cohort it answers itself (COUNT riders, a WHERE
        that filters in numpy: rider_assembles), every other leaver
        over its own statement on its own thread
        (graph/batch_dispatch.py _finish, _assemble_own).  results[i] is (columns, rows) or an Exception
        for per-query failures."""
        results: List[object] = [None] * len(queries)
        other_idx = []
        count_idx = []
        for i, red in enumerate(reduces):
            if red is not None and red[0] == "count":
                count_idx.append(i)
            else:
                other_idx.append(i)
        if count_idx:
            folded = self._count_results(
                m, [vs_lists[i] for i in count_idx], len(count_idx),
                et_tuple)
            for j, i in enumerate(count_idx):
                results[i] = folded[j]
            with self._lock:
                self.stats["go_reduced"] += len(count_idx)
        if other_idx:
            with tracing.span("tpu.assemble", queries=len(other_idx)):
                sub = self._assemble_results(
                    space_id, m, [queries[i] for i in other_idx],
                    [vs_lists[i] for i in other_idx], et_tuple)
            n_lim = 0
            for j, i in enumerate(other_idx):
                results[i] = sub[j]
                if reduces[i] is not None:
                    n_lim += 1
            if n_lim:
                with self._lock:
                    self.stats["go_reduced"] += n_lim
        return results

    # ------------------------------------------------ frontier launch
    def _launch_frontiers(self, space_id: int, starts_per_query,
                          et_tuple: Tuple[int, ...], steps: int,
                          upto: bool = False, reduce=None):
        """Start the device work for ``steps - 1`` frontier advances of
        B queries; returns a zero-arg resolver -> (per-query ascending
        dense-id frontier arrays, mirror).  Selection order: host-only
        (steps==1) → sparse → sparse split → dense bit-packed, with
        sparse overflow re-running dense.  ``upto`` selects the
        cumulative-frontier kernel variants (the returned per-query
        arrays are the UNION of depths 0..steps-1).

        The start sets ride ONE flat (dense_id, query) pair vector,
        deduped with a single lexsort — per-query Python loops here ran
        on the batch leader and each GIL re-acquisition cost up to a
        thread switch interval under a hundred request threads."""
        # every published generation is overlay-free (deltas absorb
        # before publishing), so the reduced (COUNT/LIMIT) degree
        # folds, multi-hop advances over deletes, and fresh-vertex
        # starts all read ONE consistent table set — the PR 8 "live
        # delta forces mirror_full" gates are gone with the overlay
        m = self.mirror(space_id)
        nq = len(starts_per_query)
        if steps < 1:
            empty = [np.zeros(0, np.int64)] * nq
            return lambda: (empty, m)

        lens = [len(s) for s in starts_per_query]
        flat: List[int] = []
        for s in starts_per_query:
            flat.extend(int(v) for v in s)
        flat_arr = np.asarray(flat, dtype=np.int64)
        d_all = m.to_dense(flat_arr)
        q_all = np.repeat(np.arange(nq, dtype=np.int64),
                          np.asarray(lens, np.int64))
        keep = d_all >= 0
        d_all, q_all = d_all[keep].astype(np.int64), q_all[keep]
        order = np.lexsort((d_all, q_all))
        d_all, q_all = d_all[order], q_all[order]
        if len(d_all):
            first = np.ones(len(d_all), dtype=bool)
            first[1:] = (q_all[1:] != q_all[:-1]) | (d_all[1:] != d_all[:-1])
            d_all, q_all = d_all[first], q_all[first]
        qbounds = np.searchsorted(q_all, np.arange(nq + 1))

        if steps == 1 or m.m == 0:
            # frontier before the final hop IS the start set
            starts_v = [d_all[qbounds[q]:qbounds[q + 1]]
                        for q in range(nq)]
            return lambda: (starts_v, m)

        ix = self.ell(m)
        c0 = self._sparse_c0(len(d_all))
        mesh = self._mesh_only()
        if mesh is not None and c0 is not None \
                and not upto \
                and flags.get("tpu_mesh_mode") == "sparse":
            # the dense replicated-frontier tables are NOT built here —
            # uploading both designs' tables would double per-chip HBM;
            # the dense fallback builds them lazily on overflow only
            launched = self._launch_mesh_sparse(
                space_id, m, ix, d_all, q_all, nq, et_tuple, steps, c0,
                mesh)
            if launched is not None:
                return launched
            # start placement outgrew the per-device cap: dense fallback
        mesh_mt = self._mesh_tables(m, ix) if mesh is not None else None

        if flags.get("tpu_sparse_go") \
                and mesh_mt is None and c0 is not None:
            return self._launch_sparse(space_id, m, ix, d_all, q_all, nq,
                                       et_tuple, steps, c0, upto=upto,
                                       reduce=reduce)

        if flags.get("tpu_sparse_go") \
                and mesh_mt is None and c0 is None and nq > 1:
            # total starts outgrew the sparse ladder (a wide batch of
            # multi-start queries): split at query boundaries into
            # ladder-sized sparse sub-launches instead of the dense
            # pull — at 10^8-edge scale a dense [n_rows+1, B] frontier
            # is GBs of upload and a whole-table pull per hop
            launched = self._launch_sparse_split(
                space_id, m, ix, d_all, q_all, nq, et_tuple, steps,
                qbounds, upto=upto, reduce=reduce)
            if launched is not None:
                return launched

        return self._launch_dense(space_id, m, ix, d_all, q_all, nq,
                                  et_tuple, steps, mesh_mt,
                                  upto=upto, reduce=reduce)

    def _launch_sparse_split(self, space_id: int, m: CsrMirror,
                             ix: EllIndex, d_all: np.ndarray,
                             q_all: np.ndarray, nq: int,
                             et_tuple: Tuple[int, ...], steps: int,
                             qbounds: np.ndarray, upto: bool = False,
                             reduce=None):
        """Greedy query-boundary split of an over-wide batch into
        sparse sub-launches (each within the c0 ladder).  All sub
        kernels dispatch async back-to-back, so the launches pipeline
        on the device; the resolver stitches per-query results back in
        submission order.  None when any SINGLE query outgrows the
        ladder (only the dense pull can hold it)."""
        cap_max = max(self._sparse_ladder())
        groups: List[Tuple[int, int]] = []
        lo = 0
        while lo < nq:
            hi = lo + 1
            while hi < nq and \
                    qbounds[hi + 1] - qbounds[lo] <= cap_max:
                hi += 1
            if qbounds[hi] - qbounds[lo] > cap_max:
                return None          # one query alone outgrows the ladder
            groups.append((lo, hi))
            lo = hi
        parts = []
        for g_lo, g_hi in groups:
            seg = slice(int(qbounds[g_lo]), int(qbounds[g_hi]))
            d_seg = d_all[seg]
            q_seg = q_all[seg] - g_lo
            c0g = self._sparse_c0(len(d_seg))
            if c0g is None:          # empty group (queries w/o starts)
                parts.append((g_lo, g_hi, None))
                continue
            parts.append((g_lo, g_hi, self._launch_sparse(
                space_id, m, ix, d_seg, q_seg, g_hi - g_lo, et_tuple,
                steps, c0g, upto=upto, reduce=reduce)))
        self._bump("go_sparse_split")

        def resolve():
            if reduce is not None and reduce[0] == "count":
                # count sub-launches resolve to _DeviceCounts (device
                # or dense-fallback counted) — stitch the per-query
                # numbers, never slice-assign them as vertex lists
                counts = np.zeros(nq, np.int64)
                mm = m
                for g_lo, g_hi, r in parts:
                    if r is None:
                        continue        # start-less queries count 0
                    vals, mm = r()
                    if isinstance(vals, _DeviceCounts):
                        counts[g_lo:g_hi] = vals.arr
                    else:               # defensive: vertex lists
                        deg = self._deg_host(mm, et_tuple)
                        counts[g_lo:g_hi] = [
                            int(deg[np.asarray(v, np.int64)].sum())
                            if len(v) else 0 for v in vals]
                return _DeviceCounts(counts), mm
            out: List[np.ndarray] = [np.zeros(0, np.int64)] * nq
            mm = m
            for g_lo, g_hi, r in parts:
                if r is None:
                    continue
                vs, mm = r()
                out[g_lo:g_hi] = vs
            return out, mm

        return resolve

    @staticmethod
    def _sparse_ladder() -> List[int]:
        """The pinned sparse start-capacity ladder (ascending) — the
        ONE parse of tpu_sparse_c0s, shared by the capacity lookup and
        the batch splitter so their notions of 'fits' cannot drift."""
        return sorted(int(x) for x in
                      str(flags.get("tpu_sparse_c0s") or
                          "256,2048").split(",") if x.strip())

    @classmethod
    def _sparse_c0(cls, total_starts: int) -> Optional[int]:
        """Smallest pinned sparse start-capacity holding the batch, or
        None when the batch is empty / outgrows the ladder (dense
        path)."""
        if total_starts <= 0:
            return None
        for w in cls._sparse_ladder():
            if total_starts <= w:
                return w
        return None

    def _note_live_shape(self, shape_key: Tuple,
                         first_of_family: bool = False) -> None:
        """First live dispatch of a pinned kernel shape: was it
        pre-warmed?  The FAMILY-TRIGGERING shape (the very first query
        of an (OVER, steps) family — the one whose arrival STARTS the
        background warm) is registered uncounted: nothing could have
        warmed it, so neither hit nor miss is meaningful for it."""
        # double-checked: re-verified under the lock just below
        # nebulint: disable=guard-inference
        if shape_key in self._live_shapes:
            return
        with self._lock:
            if shape_key in self._live_shapes:
                return
            self._live_shapes.add(shape_key)
            if first_of_family:
                return
            if shape_key in self._prewarmed_shapes:
                self.stats["prewarm_hits"] += 1
            else:
                self.stats["prewarm_misses"] += 1

    def _launch_sparse(self, space_id: int, m: CsrMirror, ix: EllIndex,
                       d_all: np.ndarray, q_all: np.ndarray, nq: int,
                       et_tuple: Tuple[int, ...], steps: int, c0: int,
                       upto: bool = False, reduce=None):
        from .ell import make_batched_sparse_go_kernel, sparse_caps
        import jax.numpy as jnp
        d_max = max(ix.bucket_D) if ix.bucket_D else 1
        cap = int(flags.get("tpu_sparse_cap") or (1 << 17))
        caps = sparse_caps(c0, d_max, steps, cap,
                           growth=int(flags.get("tpu_sparse_growth") or 8))
        qmax = max(int(flags.get("go_batch_max") or 1024), nq)
        # the LIMIT-n pushdown: the kernel cuts the final pair list on
        # device so the fetch carries ~limit pairs per live query
        # instead of the full caps[-1] tail (ROADMAP item 2 ≥4x ask)
        limit = int(reduce[1]) if reduce is not None \
            and reduce[0] == "limit" else None
        count_mode = reduce is not None and reduce[0] == "count"
        if limit is not None:
            kern = self._kernel(
                ("sparse_go_limit", ix.shape_sig(), et_tuple, steps,
                 caps, qmax, limit),
                lambda: make_batched_sparse_go_kernel(
                    ix, steps, et_tuple, caps, qmax=qmax, limit=limit))
        elif count_mode:
            kern = self._kernel(
                ("sparse_go_count", ix.shape_sig(), et_tuple, steps,
                 caps, qmax),
                lambda: make_batched_sparse_go_kernel(
                    ix, steps, et_tuple, caps, qmax=qmax, count=True))
        else:
            kern = self._kernel(
                ("sparse_go", ix.shape_sig(), et_tuple, steps, caps,
                 qmax, upto),
                lambda: make_batched_sparse_go_kernel(
                    ix, steps, et_tuple, caps, qmax=qmax, upto=upto))
        first = (et_tuple, steps) not in getattr(m, "_prewarm_done",
                                                 set())
        # an UPTO query compiled only the UPTO variant — every exact
        # rung still needs the warm
        # reduced/upto dispatches compile their OWN kernel keys, so the
        # warm must still cover the plain rung at this c0
        self._prewarm_family(m, ix, et_tuple, steps,
                             skip_c0=None
                             if (upto or limit is not None or count_mode)
                             else c0)
        S = len(d_all)
        ids = np.full(c0, ix.n_rows, np.int32)
        qid = np.zeros(c0, np.int32)
        new = ix.perm[d_all]
        order = np.lexsort((new, q_all))     # per-query ascending new-ids
        ids[:S] = new[order]
        qid[:S] = q_all[order]
        ecnt, e0 = self._hub_expansion_dev(m, ix)
        # upto/limit shapes are outside the warm's scope (it compiles
        # the exact-depth unreduced variants only) — register
        # uncounted, like the family-triggering shape
        self._note_live_shape(("sparse_go", ix.shape_sig(), et_tuple,
                               steps, c0),
                              first_of_family=first or upto
                              or limit is not None)
        extra = (self._deg_dev(m, ix, et_tuple),) \
            if (limit is not None or count_mode) else ()
        with tracing.span("tpu.kernel", kind="sparse_go", starts=S):
            out_dev = kern(jnp.asarray(ids), jnp.asarray(qid), ecnt, e0,
                           *extra, *ix.kernel_args()[1:])
        self._bump("go_sparse")
        _flight.recorder.note_dispatch(
            "sparse_go", rung=c0, steps=steps,
            sides=sides_read(et_tuple),
            h2d_bytes=int(ids.nbytes + qid.nbytes))
        self._maybe_time_device(
            out_dev, sum(c * (d_max + 12) * 4 for c in caps[1:]),
            kind="sparse_go")

        if count_mode:
            def resolve_counts():
                out_host = np.asarray(out_dev)
                self._note_fetch(out_host)
                if bool(out_host[1]):            # hop overflow: dense
                    self._bump("sparse_overflows")
                    return self._launch_dense(
                        space_id, m, ix, d_all, q_all, nq, et_tuple,
                        steps, self._mesh_tables(m, ix),
                        upto=upto, reduce=reduce)()
                return _DeviceCounts(
                    out_host[2:2 + nq].astype(np.int64)), m
            return resolve_counts

        def resolve():
            from .ell import sparse_go_pairs
            out_host = np.asarray(out_dev)
            self._note_fetch(out_host)
            _cnt, overflow, qids, vids_new = sparse_go_pairs(
                kern, out_host)
            if overflow:
                self._bump("sparse_overflows")
                return self._launch_dense(space_id, m, ix, d_all, q_all,
                                          nq, et_tuple, steps,
                                          self._mesh_tables(m, ix),
                                          upto=upto, reduce=reduce)()
            vs_old = ix.inv[vids_new]
            # sorted by (query, old dense id): deterministic row order
            # identical to the dense path's ascending nonzero scan
            order2 = np.lexsort((vs_old, qids))
            qids, vs_old = qids[order2], vs_old[order2]
            bounds = np.searchsorted(qids, np.arange(nq + 1))
            return [vs_old[bounds[q]:bounds[q + 1]]
                    for q in range(nq)], m

        return resolve

    @staticmethod
    def _sharded_ell(m: CsrMirror, ix: EllIndex, k: int):
        """Per-mirror cache of the k-way sharded ELL view — the ONE
        cache both mesh entry points (GO and FIND PATH) read, so the
        two paths can never serve from differently-built tables."""
        from .ell import build_sharded_ell
        cached = getattr(m, "_sharded_ell_cache", None)
        if cached is None or cached[0] != k:
            sh = build_sharded_ell(ix, k)
            m._sharded_ell_cache = (k, sh)
        else:
            sh = cached[1]
        return sh

    def _launch_mesh_sparse(self, space_id: int, m: CsrMirror,
                            ix: EllIndex, d_all: np.ndarray,
                            q_all: np.ndarray, nq: int,
                            et_tuple: Tuple[int, ...], steps: int,
                            c0: int, mesh):
        """Frontier-sharded multi-chip GO: per-device pair lists +
        all_to_all candidate exchange (ell.py design 2) — chips add
        servable graph AND frontier capacity.  Returns None when the
        start placement outgrows the per-device cap (caller falls back
        to the replicated-frontier dense path); overflow inside the
        kernel reruns dense."""
        from .ell import (make_frontier_sharded_sparse_go_kernel,
                          sharded_device_args, sharded_sparse_pairs,
                          split_start_pairs_by_owner, sparse_caps)
        import jax.numpy as jnp
        k = mesh.shape["parts"]
        sh = self._sharded_ell(m, ix, k)
        new = ix.perm[d_all].astype(np.int32)
        placed = split_start_pairs_by_owner(sh, new,
                                            q_all.astype(np.int32), c0)
        if placed is None:
            return None
        d_max = max(ix.bucket_D) if ix.bucket_D else 1
        cap = int(flags.get("tpu_sparse_cap") or (1 << 17))
        caps = sparse_caps(c0, d_max, steps, cap,
                           growth=int(flags.get("tpu_sparse_growth") or 8))
        cap_x = max(256, caps[-1] // max(k // 2, 1))
        cap_e = max(64, c0)
        kern = self._kernel(
            ("mesh_sparse_go", ix.shape_sig(), et_tuple, steps, caps,
             k, cap_x, cap_e),
            lambda: make_frontier_sharded_sparse_go_kernel(
                mesh, "parts", sh, steps, et_tuple, caps,
                cap_x=cap_x, cap_e=cap_e))
        args = sharded_device_args(mesh, "parts", sh)
        with tracing.span("tpu.kernel", kind="mesh_sparse_go"):
            out_dev = kern(jnp.asarray(placed[0]), jnp.asarray(placed[1]),
                           args[0], args[1], args[2], *args[3])
        self._bump("go_mesh_sparse")
        # live ICI accounting: per hop the candidate router ships two
        # [k, cap_x] int32 planes, the hub router two [k, cap_e], and
        # the overflow/early-exit scalars ride a psum — folded against
        # the spec's fx.steps-scaled bound at the SAME live caps
        self._note_sharded_ici(
            "mesh_sparse_go", k,
            [("all_to_all", 2 * 4 * k * (cap_x + cap_e) * steps),
             ("psum", 4 * k * steps)],
            ell=ix, c0s=(c0,), steps=steps, sparse_cap=cap,
            sparse_growth=int(flags.get("tpu_sparse_growth") or 8),
            fields={"rung": c0, "steps": steps})

        def resolve():
            overflow, qids, vids_new = sharded_sparse_pairs(
                np.asarray(out_dev))
            if overflow:
                self._bump("sparse_overflows")
                return self._launch_dense(
                    space_id, m, ix, d_all, q_all, nq, et_tuple, steps,
                    self._mesh_tables(m, ix))()
            vs_old = ix.inv[vids_new]
            order2 = np.lexsort((vs_old, qids))
            q2, v2 = qids[order2], vs_old[order2]
            bounds = np.searchsorted(q2, np.arange(nq + 1))
            return [v2[bounds[q]:bounds[q + 1]]
                    for q in range(nq)], m

        return resolve

    def _launch_dense(self, space_id: int, m: CsrMirror, ix: EllIndex,
                      d_all: np.ndarray, q_all: np.ndarray, nq: int,
                      et_tuple: Tuple[int, ...], steps: int,
                      mesh_mt, upto: bool = False,
                      reduce=None):
        from .ell import (dense_hop_bytes, lanes_width,
                          make_batched_go_lanes_kernel,
                          make_sharded_batched_go_kernel,
                          unpack_lanes_host)
        # callers guarantee: upto never reaches the sharded variants
        # (the mesh gate declines); a count reduction only rides the
        # single-chip kernel
        assert not (upto and mesh_mt is not None)
        B = self._batch_width(nq)
        count_mode = reduce is not None and reduce[0] == "count" \
            and mesh_mt is None
        args = ix.kernel_args()
        f0_dev = self._upload_frontier_packed(
            ix, ix.perm[d_all], q_all.astype(np.int32), B)
        eslot, hrows = self._hub_merge_dev(m, ix)
        hop_bytes = dense_hop_bytes(ix, et_tuple, lanes_width(B), steps)
        if mesh_mt is not None:
            mesh, tables, reals = mesh_mt
            kern = self._kernel(
                ("ell_go_sharded", ix.shape_sig(), et_tuple, steps,
                 mesh.shape["parts"]),
                # donate=True: f0p is fresh per dispatch, same as the
                # single-chip packed kernel
                lambda: make_sharded_batched_go_kernel(
                    mesh, "parts", ix, steps, et_tuple, reals,
                    donate=True))
            with tracing.span("tpu.kernel", kind="ell_go_sharded",
                              width=B, packed=True):
                out_dev = kern(f0_dev, eslot, hrows, *tables)
            # live ICI accounting: steps-1 frontier re-replications,
            # (k-1)/k of the packed [n_rows+1, W] matrix each
            fbytes = (ix.n_rows + 1) * lanes_width(B)
            self._note_sharded_ici(
                "ell_go_sharded", mesh.shape["parts"],
                [("sharding_constraint",
                  fbytes * max(steps - 1, 1))],
                ell=ix, widths=(B,), steps=steps,
                fields={"rung": B, "steps": steps,
                        "h2d_bytes": fbytes})
        elif count_mode:
            deg = self._deg_dev(m, ix, et_tuple)
            kern = self._kernel(
                ("ell_go_count", ix.shape_sig(), et_tuple, steps),
                lambda: make_batched_go_lanes_kernel(
                    ix, steps, et_tuple, count=True, donate=True))
            with tracing.span("tpu.kernel", kind="ell_go_count",
                              width=B):
                out_dev = kern(f0_dev, eslot, hrows, deg, *args[1:])
        else:
            # family registration BEFORE the first/_note check (like
            # the sparse path): same-family queries racing the first
            # compile must still be counted against the warm
            first = (et_tuple, steps) not in getattr(m, "_prewarm_done",
                                                     set())
            self._prewarm_family(m, ix, et_tuple, steps)
            kern = self._kernel(
                ("ell_go_packed", ix.shape_sig(), et_tuple, steps, upto),
                # donate=True: f0p is built fresh per dispatch right
                # above — single-use by construction
                lambda: make_batched_go_lanes_kernel(
                    ix, steps, et_tuple, upto=upto, donate=True))
            self._note_live_shape(
                ("ell_go_packed", ix.shape_sig(), et_tuple, steps, B),
                first_of_family=first or upto)
            with tracing.span("tpu.kernel", kind="ell_go", width=B,
                              packed=True):
                out_dev = kern(f0_dev, eslot, hrows, *args[1:])
        self._bump("go_dense")
        if mesh_mt is None:
            # sharded dispatches already logged a (richer) row above
            _flight.recorder.note_dispatch(
                "ell_go_count" if count_mode else "ell_go",
                rung=B, steps=steps, sides=sides_read(et_tuple),
                hop_bytes=int(hop_bytes))
        self._maybe_time_device(out_dev, hop_bytes, kind="ell_go")

        if count_mode:
            def resolve_counts():
                counts = np.asarray(out_dev)      # [B] int32
                self._note_fetch(counts)
                return _DeviceCounts(counts[:nq].astype(np.int64)), m
            return resolve_counts

        def resolve():
            # slice to the live query columns ON DEVICE before the
            # fetch — transferring all B padded columns at small nq
            # re-pays the cost the bit-packing exists to remove
            nwp = min(lanes_width(B), max(1, -(-nq // 8)))
            lanes = np.asarray(out_dev[:, :nwp])      # [R1, nwp] uint8
            self._note_fetch(lanes)
            bits = unpack_lanes_host(lanes, nq)
            old = bits[ix.perm]                   # [n, nq] old dense ids
            qs, vs = np.nonzero(old.T)
            bounds = np.searchsorted(qs, np.arange(nq + 1))
            return [vs[bounds[q]:bounds[q + 1]] for q in range(nq)], m

        return resolve

    def _prewarm_family(self, m: CsrMirror, ix: EllIndex,
                        et_tuple: Tuple[int, ...], steps: int,
                        skip_c0: Optional[int] = None) -> None:
        """Background-compile the OTHER pinned batch shapes of a query
        family (same OVER set + steps): the sparse c0 ladder rungs and
        the dense batch widths the first live query didn't hit.  A new
        shape's first XLA compile costs seconds and lands as a p99
        spike on fresh clusters.

        AOT-only: each shape is ``lower(...).compile()``d on shape
        specs — NO device execution and no transfers (an earlier
        version EXECUTED the warm shapes, and the dense pulls stole
        whole seconds of device time from live batches mid-burst).
        The compiled binary lands in the persistent XLA cache
        (jax_setup), so the live first call of the shape deserializes
        instead of compiling.  One shot per (mirror, family)."""
        if not flags.get("tpu_prewarm_kernels"):
            return
        key = (et_tuple, steps)
        warmed = getattr(m, "_prewarm_done", None)
        if warmed is None:
            warmed = m._prewarm_done = set()
        if key in warmed:
            return
        warmed.add(key)

        def run():
            try:
                import jax
                from .ell import (lanes_width,
                                  make_batched_go_lanes_kernel,
                                  make_batched_sparse_go_kernel,
                                  sparse_caps)
                d_max = max(ix.bucket_D) if ix.bucket_D else 1
                cap = int(flags.get("tpu_sparse_cap") or (1 << 17))
                growth = int(flags.get("tpu_sparse_growth") or 8)
                qmax = int(flags.get("go_batch_max") or 1024)
                ecnt, e0 = self._hub_expansion_dev(m, ix)
                args = ix.kernel_args()
                i32 = jax.ShapeDtypeStruct
                for c0 in self._sparse_ladder():
                    if self._bg_stop.is_set():
                        return
                    if steps <= 1:
                        continue
                    shape_key = ("sparse_go", ix.shape_sig(), et_tuple,
                                 steps, c0)
                    if c0 == skip_c0:
                        continue   # the triggering live query compiled
                    caps = sparse_caps(c0, d_max, steps, cap,
                                       growth=growth)
                    # upto=False in the key: prewarm covers the
                    # exact-depth variants (the common shapes); UPTO
                    # kernels compile on first use
                    kern = self._kernel(
                        ("sparse_go", ix.shape_sig(), et_tuple, steps,
                         caps, qmax, False),
                        lambda: make_batched_sparse_go_kernel(
                            ix, steps, et_tuple, caps, qmax=qmax))
                    kern.lower(i32((c0,), np.int32), i32((c0,), np.int32),
                               ecnt, e0, *args[1:]).compile()
                    with self._lock:
                        self._prewarmed_shapes.add(shape_key)
                        self.stats["prewarm_compiled"] += 1
                eslot, hrows = self._hub_merge_dev(m, ix)
                for B in sorted(int(w) for w in
                                str(flags.get("go_batch_widths") or
                                    "128,1024").split(",") if w.strip()):
                    if self._bg_stop.is_set():
                        return
                    if steps <= 1:
                        continue
                    kern = self._kernel(
                        ("ell_go_packed", ix.shape_sig(), et_tuple,
                         steps, False),
                        lambda: make_batched_go_lanes_kernel(
                            ix, steps, et_tuple,
                            donate=True))       # must match live dispatch
                    kern.lower(
                        i32((ix.n_rows + 1, lanes_width(B)), np.uint8),
                        eslot, hrows, *args[1:]).compile()
                    shape_key = ("ell_go_packed", ix.shape_sig(),
                                 et_tuple, steps, B)
                    with self._lock:
                        self._prewarmed_shapes.add(shape_key)
                        self.stats["prewarm_compiled"] += 1
            except Exception as e:   # noqa: BLE001 — pre-warm must
                # never disturb serving, but a shape the compiler
                # refuses here is refused on the live path too: count
                # it and say which family (chip_smoke.py asserts zero)
                with self._lock:
                    self.stats["prewarm_failed"] += 1
                sys.stderr.write(
                    f"[tpu] kernel prewarm failed for space "
                    f"{m.space_id} {et_tuple}/{steps} steps: "
                    f"{type(e).__name__}: {e}\n")

        self._spawn_bg(run, f"kernel-prewarm-{m.space_id}")

    def _spawn_bg(self, target, name: str) -> None:
        """Start a tracked daemon thread (prewarm compile, async mirror
        rebuild) that shutdown() can flag off and join — an untracked
        daemon inside XLA work at process exit crashes the C++
        teardown.  No-op once shutdown has begun."""
        if self._bg_stop.is_set():
            return
        t = threading.Thread(target=target, daemon=True, name=name)
        with self._lock:
            self._bg_threads = [w for w in self._bg_threads
                                if w.is_alive()]
            self._bg_threads.append(t)
        t.start()

    def shutdown(self, timeout_s: float = 30.0) -> None:
        """Stop background work (prewarm compiles, async mirror
        rebuilds) and wait for what's in flight: a daemon thread inside
        an XLA compile or device transfer when the process exits races
        the C++ runtime's teardown (observed as "pure virtual method
        called" aborts).  The stop flag bounds the wait to the work
        already running; serving paths are untouched (a runtime keeps
        answering queries after shutdown(), it just stops background
        warming/refreshing).  Idempotent; called by StorageService
        .shutdown() and LocalCluster.stop()."""
        import time
        self._bg_stop.set()
        d = self._dispatcher
        if d is not None and getattr(d, "continuous", None) is not None:
            # continuous-dispatch pump threads sit in the same XLA
            # trap: a pump mid-hop at interpreter exit crashes the
            # C++ teardown — drain the seat maps and join the pumps
            d.continuous.shutdown(timeout_s=timeout_s / 2)
        with self._lock:
            threads = list(self._bg_threads)
        deadline = time.monotonic() + timeout_s
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))

    def _hub_expansion_dev(self, m: CsrMirror, ix: EllIndex):
        """(ecnt, e0) device arrays for the sparse kernel's exact hub
        push (ell.EllIndex.hub_expansion), cached per mirror."""
        import jax.numpy as jnp
        cached = getattr(m, "_hub_exp_cache", None)
        if cached is None:
            ecnt, e0 = ix.hub_expansion()
            cached = m._hub_exp_cache = (jnp.asarray(ecnt),
                                         jnp.asarray(e0))
        return cached

    def _hub_merge_dev(self, m: CsrMirror, ix: EllIndex):
        """(eslot, hrows) device arrays for the packed kernels' OR-
        merge (ell.EllIndex.hub_merge), cached per mirror."""
        import jax.numpy as jnp
        cached = getattr(m, "_hub_merge_cache", None)
        if cached is None:
            eslot, hrows = ix.hub_merge()
            cached = m._hub_merge_cache = (jnp.asarray(eslot),
                                           jnp.asarray(hrows))
        return cached

    def _deg_host(self, m: CsrMirror, et_tuple: Tuple[int, ...]
                  ) -> np.ndarray:
        """int64[n]: per-vertex final-hop candidate-edge count over the
        OVER set — the COUNT(*)/LIMIT pushdown's degree vector, cached
        per (mirror, OVER) beside _etype_edge_mask."""
        def fill():
            mask = self._etype_edge_mask(m, et_tuple)
            return np.bincount(m.edge_src[mask], minlength=m.n) \
                .astype(np.int64)
        return _mirror_table(m, "_deg_cache", et_tuple, fill)

    def _deg_dev(self, m: CsrMirror, ix: EllIndex,
                 et_tuple: Tuple[int, ...]):
        """int32[n_rows+1] NEW-id-space device copy of _deg_host (zero
        for hub extra rows and the pad row, so junk extras never
        count), cached per (mirror, OVER)."""
        import jax.numpy as jnp
        cache = getattr(m, "_deg_dev_cache", None)
        if cache is None:
            cache = m._deg_dev_cache = {}
        dev = cache.get(et_tuple)
        if dev is None:
            if len(cache) >= 8:
                cache.clear()
            deg = np.zeros(ix.n_rows + 1, np.int32)
            deg[ix.perm] = np.minimum(self._deg_host(m, et_tuple),
                                      2**31 - 1).astype(np.int32)
            dev = cache[et_tuple] = jnp.asarray(deg)
        return dev

    def _note_fetch(self, arr: np.ndarray) -> None:
        """Account the bytes one resolver pulled over the link."""
        with self._lock:
            self.stats["fetch_bytes"] += int(arr.nbytes)

    def _maybe_time_device(self, out_dev, bytes_moved: int,
                           kind: str) -> None:
        """Every Nth dispatch (tpu_device_timing_every): block on the
        just-launched kernel and record device-compute time distinct
        from link RTT — the roofline's compute-vs-link attribution.
        Dispatch is async, so the wait measured here is (queue +)
        device compute; the sampled dispatch serializes the pipeline,
        which is why this is a sample, not every dispatch."""
        n = int(flags.get("tpu_device_timing_every") or 0)
        if n <= 0:
            return
        with self._lock:
            self._timing_seq += 1
            if self._timing_seq % n:
                return
        import time
        import jax
        t0 = time.perf_counter()
        jax.block_until_ready(out_dev)
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats["t_device_s"] += dt
            self.stats["device_bytes_moved"] += int(bytes_moved)
            self.stats["device_timed_dispatches"] += 1
        _stats.observe("tpu.device_compute.latency_us", dt * 1e6,
                       kind=kind)
        gbps = (bytes_moved / dt / 1e9) if dt > 0 else 0.0
        _flight.recorder.note_timing(kind, dt * 1e6, int(bytes_moved),
                                     gbps)
        if gbps > 0 and self._peaks is not None:
            # live-vs-declared HBM fold: achieved streaming rate above
            # the serving device's published peak means the byte model
            # is stale — tpu.model_drift fires typed (common/flight.py).
            # A device_kind outside DEVICE_PEAKS (CPU jax) has no peak
            # to fold against
            _flight.recorder.fold("hbm", kind, gbps,
                                  float(self._peaks["hbm_gbps"]))

    def _note_sharded_ici(self, kernel_name: str, k: int, ops,
                          trips: int = 1,
                          fields: Optional[dict] = None,
                          **shape) -> None:
        """Fold one sharded dispatch's live per-collective ICI bytes
        against the registry-declared ``KernelSpec.ici_bytes`` bound
        evaluated at the LIVE shapes — ``shape`` becomes the ``fx``
        the spec's bound function reads, ``trips`` multiplies a
        per-level bound (BFS declares per level; the live side ships
        one exchange per level too, so both sides scale together).
        This is the meshaudit invariant checked on the RUNNING system
        instead of a traced fixture; the recorder fires
        ``tpu.model_drift`` on live > declared (common/flight.py)."""
        spec = kernels.KERNEL_REGISTRY.get(kernel_name)
        if spec is None or spec.ici_bytes is None:
            return
        from types import SimpleNamespace
        try:
            declared = int(spec.ici_bytes(SimpleNamespace(**shape),
                                          k)) * max(int(trips), 1)
        except Exception:   # noqa: BLE001 — accounting never fails a dispatch
            return
        _flight.recorder.note_sharded_dispatch(
            kernel_name, k, ops, declared, **(fields or {}))

    # ------------------------------------------------ host assembly
    def _assemble_results(self, space_id: int, m: CsrMirror,
                          queries: List[_GoQuery], vs_lists,
                          et_tuple: Tuple[int, ...]):
        """Vectorized final hop for a whole batch: group queries by
        (WHERE, YIELD, mode) signature, then per group do ONE candidate
        assembly + filter + materialization over the concatenated
        frontier, splitting rows back per query.  Per-query failures
        become Exception entries."""
        results: List[object] = [None] * len(queries)
        groups: Dict[Tuple, List[int]] = {}
        for i, q in enumerate(queries):
            sig = (q.plan.expr_str, q.plan.pushed_mode,
                   tuple(sorted(q.plan.alias_to_etype.items())),
                   tuple((str(c.expr), c.alias) for c in q.yield_cols),
                   q.distinct)
            groups.setdefault(sig, []).append(i)
        for sig, idxs in groups.items():
            try:
                self._assemble_group(
                    space_id, m, queries, idxs, vs_lists, et_tuple,
                    results)
            except Exception as ex:     # noqa: BLE001 — group-level
                for i in idxs:          # failure hits only its riders
                    if results[i] is None:
                        results[i] = ex
        return results

    def _assemble_group(self, space_id: int, m: CsrMirror,
                        queries: List[_GoQuery], idxs: List[int],
                        vs_lists, et_tuple: Tuple[int, ...],
                        results: List[object]) -> None:
        """One signature group's candidates, filter and rows into
        ``results``; what its WHERE met (statements filtered,
        candidate edges, rows kept) goes to the tpu.where span and the
        go_where / where_candidates / where_rows / where_native
        counters."""
        rep = queries[idxs[0]]
        plan = rep.plan
        columns = [c.alias or _default_col_name(c.expr)
                   for c in rep.yield_cols]
        # recompile against the dispatch's mirror when planning raced a
        # version bump: compiled cvals bake mirror-specific constants
        # (dictionary codes, vid ranks)
        if plan.mirror is not m and plan.filter_cval is not None:
            compiler = ExprCompiler(m, space_id, self.sm,
                                    plan.alias_to_etype)
            try:
                cval = compiler.compile(rep.where_expr)
            except CompileError:
                for i in idxs:
                    results[i] = queries[i].exc_type(
                        "schema changed while the query ran")
                return
            plan = _GoPlan(m, plan.alias_to_etype, cval,
                           dict(compiler.used), plan.pushed_mode,
                           compiler, plan.expr_str, sc_or=plan.sc_or)

        # concatenated final-hop candidates across the group.  A WHERE
        # keeps few of them, so it takes them as runs of mirror rows
        # and reads a kept candidate's query off the bounds; a row
        # index and a query segment for each candidate are made only
        # where validity has to be told apart query by query
        filtered = plan.filter_cval is not None
        by_query = filtered and (not plan.pushed_mode or plan.sc_or)
        vs_concat = [vs_lists[i] for i in idxs]
        cand, qseg, qbounds = self._frontier_edges_multi(
            m, vs_concat, et_tuple, as_runs=filtered and not by_query)

        # WHERE validity: the compiled filter evaluates EVERY operand
        # over vectorized columns, but the CPU executor SHORT-CIRCUITS
        # (`x || $$.t.p > k` never touches the missing prop when x is
        # truthy, and a missing prop only errors the query when the
        # evaluation order actually reaches it).  A mask can't
        # reproduce order-dependent semantics, so any query whose
        # candidates carry an invalid used prop DECLINES to the CPU
        # loop — which then short-circuits or raises exactly.  The
        # all-valid common case (the generative differential's
        # baseline) stays vectorized.
        from ..storage.device import TpuDecline
        bad = np.zeros(len(idxs), dtype=bool)
        if by_query:
            # pure-conjunction pushed filters keep the mask: skip-on-
            # invalid == AND-with-validity.  Everything else declines
            # the AFFECTED queries only (their batch neighbours keep
            # their vectorized results)
            invalid = self._invalid_candidates(m, plan.filter_used, cand)
            if invalid is not None and invalid.any():
                hit = np.unique(qseg[invalid])
                bad[hit] = True
                for g in hit:
                    i = idxs[int(g)]
                    results[i] = TpuDecline(
                        "WHERE reads a prop invalid on candidate rows; "
                        "CPU short-circuit semantics decide")
                # drop the declined queries' rows BEFORE the group
                # mask: _host_filter re-raises on the same invalid
                # bits, and a group-level raise would decline every
                # healthy neighbour too
                keep_rows = ~bad[qseg]
                cand = cand[keep_rows]
                qbounds = np.searchsorted(qseg[keep_rows],
                                          np.arange(len(idxs) + 1))

        if filtered:
            # which pass filters: the span and where_native say, so a
            # library without the native pass shows in a trace (where
            # it can run, candidates that are not runs are none at all)
            native = self._native_filter(m, plan, et_tuple)
            with tracing.span("tpu.where", queries=len(idxs),
                              site="assembly",
                              native=0 if native is None else len(idxs)
                              ) as sp:
                # the span's wall may be shared with other threads
                # under the interpreter lock; cpu_us is this pass's own
                # run time, runq_us its wait for a core
                h0 = hostclock.stamp() if sp is not None else None
                if isinstance(cand, _EdgeRuns):
                    kept = self._filter_runs(m, plan, cand, native)
                    cand2 = cand.rows(kept)
                else:
                    kept = np.flatnonzero(
                        self._host_filter(m, plan, cand))
                    cand2 = cand[kept]
                qb2 = np.searchsorted(kept, qbounds)
                qseg2 = np.repeat(np.arange(len(idxs), dtype=np.int64),
                                  np.diff(qb2))
                met = (len(idxs) - int(bad.sum()), len(cand), len(cand2),
                       0 if native is None else len(idxs))
                if sp is not None:
                    sp.tag(candidates=met[1], kept=met[2],
                           **hostclock.span_fields(
                               "", h0, hostclock.stamp()))
            with self._lock:
                for key, n in zip(("go_where", "where_candidates",
                                   "where_rows", "where_native"), met):
                    self.stats[key] += n
        else:
            cand2, qseg2, qb2 = cand, qseg, qbounds

        rows_per_query = self._materialize_group(
            m, space_id, plan.alias_to_etype, rep.etype_to_alias,
            rep.yield_cols, cand2, qseg2, qb2, len(idxs),
            [queries[i].exc_type for i in idxs])

        for g, i in enumerate(idxs):
            if bad[g] or isinstance(rows_per_query[g], Exception):
                if results[i] is None:
                    results[i] = rows_per_query[g] if \
                        isinstance(rows_per_query[g], Exception) else \
                        queries[i].exc_type("prop unavailable in WHERE")
                continue
            rows = rows_per_query[g]
            if queries[i].distinct:
                rows = _distinct_rows(rows)
            results[i] = (columns, rows)

    def _invalid_candidates(self, m: CsrMirror, used: Dict[str, Tuple],
                            cand: np.ndarray) -> Optional[np.ndarray]:
        """bool[cand] — candidate edge references an invalid used prop
        (graphd WHERE raises per query), or None when nothing is used."""
        if not used or len(cand) == 0:
            return None
        inv = np.zeros(len(cand), dtype=bool)
        for k, desc in used.items():
            if desc[0] == "edge":
                col = edge_column(m, desc[1], desc[2])
                inv |= ~col.valid[cand]
            elif desc[0] == "vertex":
                col = m.vertex_cols[(desc[1], desc[2])]
                gather = m.edge_src[cand] if desc[3] == "src" \
                    else m.edge_dst[cand]
                inv |= ~col.valid[gather]
        return inv

    # -------------------------------------------------- host columns
    def _gather_cols(self, m: CsrMirror, alias_to_etype: Dict[str, Tuple],
                     used: Dict[str, Tuple],
                     idx: np.ndarray) -> Dict[str, np.ndarray]:
        """numpy columns for compiled-expression eval over edge rows
        ``idx`` (a row index array, or _EdgeRuns) — the one
        descriptor->array mapping shared by the host WHERE filter and
        YIELD materialization."""
        cols: Dict[str, np.ndarray] = {}
        for k, desc in used.items():
            if desc[0] == "edge":
                cols[k] = _take(
                    edge_column(m, desc[1], desc[2]).values, idx)
            elif desc[0] == "vertex":
                col = m.vertex_cols[(desc[1], desc[2])]
                gather = _take(m.edge_src if desc[3] == "src"
                               else m.edge_dst, idx)
                cols[k] = col.values[gather]
            elif desc[0] == "rank":
                cols["rank"] = _take(m.edge_rank, idx)
            elif desc[0] == "src_idx":
                cols["src_idx"] = _take(m.edge_src, idx)
            elif desc[0] == "dst_idx":
                cols["dst_idx"] = _take(m.edge_dst, idx)
            elif desc[0] == "etype_alias":
                cols["etype_alias"] = _take(
                    self._etype_alias_codes(m, alias_to_etype), idx)
        return cols

    # -------------------------------------------------- host filter
    def _native_filter(self, m: CsrMirror, plan: _GoPlan,
                       et_tuple: Tuple[int, ...]):
        """(column, op, constant) where the plan's WHERE is ONE native
        pass over its candidate runs (_EdgeRuns.keep_f64), else None:
        the compiled value is one float64 edge column against a
        constant (``cmp``), a pushed pure conjunction with no division
        guard, the OVER set's edges are one run a vertex, and the
        library has the pass.  Every other WHERE gathers its columns
        and evaluates in numpy, which holds the allocator and, between
        its calls, the interpreter: rider_assembles() keeps that one
        on the pump."""
        from ..native import lib
        cmp_ = plan.filter_cval.cmp if plan.filter_cval is not None \
            else None
        if cmp_ is None or not plan.pushed_mode or plan.sc_or \
                or plan.compiler.div_guards \
                or self._over_ranges(m, et_tuple) is None:
            return None
        if not hasattr(lib(), "neb_filter_runs_f64"):
            _say_once("[tpu] native run filter missing: a WHERE of a "
                      "column against a constant filters in numpy, on "
                      "the pump's thread in the continuous tier")
            return None
        key, op, c = cmp_
        col = edge_column(m, *plan.filter_used[key][1:])
        if col is None or col.values.dtype != np.float64 \
                or col.valid.dtype != np.bool_ or col.values.ndim != 1 \
                or col.valid.shape != col.values.shape \
                or not col.values.flags.c_contiguous \
                or not col.valid.flags.c_contiguous:
            return None
        return col, op, c

    def rider_assembles(self, m: CsrMirror, q: _GoQuery,
                        et_tuple: Tuple[int, ...]) -> bool:
        """Whether a continuous leaver's own thread runs its
        post-frontier half (graph/batch_dispatch.py _finish): yes
        unless its WHERE filters in numpy.  Sixteen such passes beside
        each other and the pump cost the claimed cell a fifth on the
        one-chip machine (PERF.md section 6, PR 32); one thread
        running them in turn, the pump, is what the parent does."""
        return q.plan.filter_cval is None \
            or self._native_filter(m, q.plan, et_tuple) is not None

    def _filter_runs(self, m: CsrMirror, plan: _GoPlan, cand: _EdgeRuns,
                     native) -> np.ndarray:
        """Positions of the candidates (runs: a pushed, conjunctive
        WHERE — _assemble_group) the predicate keeps, ascending, a
        piece of WHERE_PIECE_EDGES at a time: ``native``
        (_native_filter's) is one native pass over a piece's runs;
        without it a piece's columns are gathered and the compiled
        predicate evaluates in numpy.  Both compare the stored double
        in float64 and AND the column's validity."""
        kept, seen = [np.zeros(0, np.int64)], 0
        for piece in cand.pieces(WHERE_PIECE_EDGES):
            if native is not None:
                col, op, c = native
                got = piece.keep_f64(col.values, col.valid, op, c)
            else:
                got = np.flatnonzero(self._host_filter(m, plan, piece))
            kept.append(seen + got)
            seen += len(piece)
        return np.concatenate(kept)

    def _host_filter(self, m: CsrMirror, plan: _GoPlan,
                     idx) -> np.ndarray:
        """Evaluate the compiled WHERE over candidate edges ``idx`` (a
        row index array, or _EdgeRuns) in numpy at the CPU executor's
        precision, with pushed-mode validity/div-guard semantics."""
        if len(idx) == 0:
            return np.zeros(0, dtype=bool)
        # pushed-mode validity is snapshotted BEFORE the value gather:
        # commit_vertex_plan absorbs in place values-first/valid-last,
        # so a reader must never hold a valid bit fresher than the
        # value it gates (stale-valid over fresh-value only hides a
        # just-committed row — the same bounded staleness a racing scan
        # has; fresh-valid over stale-value would serve garbage)
        valid_snap: Dict[str, np.ndarray] = {}
        if plan.pushed_mode:
            for k, desc in plan.filter_used.items():
                if desc[0] == "edge":
                    valid_snap[k] = _take(
                        edge_column(m, desc[1], desc[2]).valid, idx)
                elif desc[0] == "vertex":
                    gather = _take(m.edge_src if desc[3] == "src"
                                   else m.edge_dst, idx)
                    valid_snap[k] = \
                        m.vertex_cols[(desc[1], desc[2])].valid[gather]
            if plan.sc_or and valid_snap \
                    and not all(v.all() for v in valid_snap.values()):
                # `x || missing` short-circuits on the per-row path
                # (row kept without touching the prop); ANDing validity
                # into the mask can't reproduce that — decline so the
                # per-row evaluator decides (the generative WHERE
                # differential's missing-column x disjunction cell)
                from ..storage.device import TpuDecline
                # nebulint: carveout=invalid-prop-shortcircuit
                raise TpuDecline(
                    "pushed WHERE with || over a partially-valid "
                    "prop; per-row short-circuit semantics decide")
        env = Env(self._gather_cols(m, plan.alias_to_etype,
                                        plan.filter_used, idx))
        with np.errstate(divide="ignore", invalid="ignore"):
            mask = np.broadcast_to(np.asarray(plan.filter_cval.fn(env)),
                                   (len(idx),))
            if mask.dtype != np.bool_:
                # numeric WHERE: CPU-path truthiness (nonzero = keep) —
                # and callers fancy-index with this mask, so it MUST be
                # bool, never int/float
                mask = mask != 0
            else:
                mask = mask.copy()
            for g in plan.compiler.div_guards:
                # a real x/0 drops the row in pushed mode (can_run_go
                # declines div guards in graphd/remnant mode)
                mask &= ~np.broadcast_to(np.asarray(g(env)),
                                        (len(idx),))
        if plan.pushed_mode:
            for k in valid_snap:
                mask &= valid_snap[k]
        return mask

    @staticmethod
    def _etype_alias_codes(m: CsrMirror,
                           alias_to_etype: Dict[str, Tuple]) -> np.ndarray:
        """int32[m]: per-edge code into the sorted alias dictionary
        (cached per mirror+alias map — O(m) to build, reused across
        queries)."""
        def fill():
            alias_pos = {a: i
                         for i, a in enumerate(sorted(alias_to_etype))}
            codes = np.zeros(m.m, dtype=np.int32)
            for a, ets in alias_to_etype.items():
                codes[np.isin(m.edge_etype, ets)] = alias_pos[a]
            return codes
        return _mirror_table(m, "_alias_code_cache",
                             tuple(sorted(alias_to_etype.items())), fill)

    # -------------------------------------------------- final-hop edges
    @staticmethod
    def _etype_edge_mask(m: CsrMirror,
                         et_tuple: Tuple[int, ...]) -> np.ndarray:
        """bool[m]: edge etype in the OVER set — cached per mirror so
        the O(m) isin pass is paid once per (mirror, OVER), not per
        query."""
        return _mirror_table(
            m, "_etype_mask_cache", et_tuple,
            lambda: np.isin(m.edge_etype,
                            np.asarray(et_tuple, dtype=np.int32)))

    def _over_ranges(self, m: CsrMirror, et_tuple: Tuple[int, ...]):
        """(lo, cnt) int64[n]: vertex v's edges of the OVER set lie at
        mirror rows ``lo[v] : lo[v] + cnt[v]`` — the edge arrays are in
        (src, etype, rank, dst) order, so one edge type (or types
        adjacent in that order) is one run a vertex.  None where some
        vertex's are not one run (another type sorts between them):
        the caller then walks whole rows and masks.  O(m) once per
        (mirror, OVER), cached beside _etype_edge_mask."""
        def fill():
            mask = self._etype_edge_mask(m, et_tuple)
            cnt = self._deg_host(m, et_tuple)
            # a run starts where an edge of the set follows an edge
            # outside it, or another vertex's
            head = mask.copy()
            head[1:] &= ~(mask[:-1] & (m.edge_src[1:] == m.edge_src[:-1]))
            first = np.flatnonzero(head)
            if len(first) != int(np.count_nonzero(cnt)):
                return None
            lo = np.zeros(m.n, np.int64)
            lo[m.edge_src[first]] = first
            return lo, cnt
        return _mirror_table(m, "_over_range_cache", et_tuple, fill)

    def _frontier_edges_multi(self, m: CsrMirror, vs_lists,
                              et_tuple: Tuple[int, ...],
                              as_runs: bool = False):
        """Batched candidate assembly: per-query frontier vertex lists
        -> (edge idx concat, per-edge query segment, per-query bounds).
        One vectorized pass for the whole batch — the round-3 answer to
        per-query Python loops dominating the serving profile.  A
        caller that keeps few of the candidates (a WHERE) passes
        ``as_runs``: where the OVER set's edges are one run a vertex it
        gets them as _EdgeRuns and no segment array (a kept
        candidate's query is read off the bounds), else arrays as
        ever."""
        nq = len(vs_lists)
        vq_counts = np.fromiter((len(v) for v in vs_lists), np.int64,
                                count=nq)
        none = (np.zeros(0, np.int64), np.zeros(0, np.int64),
                np.zeros(nq + 1, np.int64))
        if vq_counts.sum() == 0:
            return none
        vs = np.concatenate([np.asarray(v, np.int64) for v in vs_lists])
        ranges = self._over_ranges(m, et_tuple)
        if ranges is not None:
            starts, counts = ranges[0][vs], ranges[1][vs]
        else:
            starts = m.row_ptr[vs].astype(np.int64)
            counts = (m.row_ptr[vs + 1].astype(np.int64) - starts)
        total = int(counts.sum())
        if total == 0:
            return none
        if ranges is None and nq == 1 and total * 5 >= m.m:
            # saturated single frontier over whole rows: one flat bool
            # gather over all m edges beats per-row index assembly and
            # its mask (measured break-even ~20% density); the OVER
            # set's own runs are cheaper than the flat pass at any size
            frontier = np.zeros(m.n, dtype=bool)
            frontier[vs] = True
            idx = np.nonzero(frontier[m.edge_src]
                             & self._etype_edge_mask(m, et_tuple))[0]
            return (idx, np.zeros(len(idx), np.int64),
                    np.asarray([0, len(idx)], np.int64))
        # a query's candidates end where its last vertex's do
        ends = np.concatenate(([0], np.cumsum(counts)))
        per_q = ends[np.concatenate(([0], np.cumsum(vq_counts)))]
        nz = counts > 0
        runs = _EdgeRuns(starts[nz], counts[nz])
        if ranges is not None and as_runs:
            return runs, None, per_q
        idx = runs.index()
        qseg = np.repeat(np.arange(nq, dtype=np.int64), np.diff(per_q))
        if ranges is not None:
            return idx, qseg, per_q
        keep = self._etype_edge_mask(m, et_tuple)[idx]
        idx, qseg = idx[keep], qseg[keep]
        # no dead-row exclusion pass: deletes fold into the published
        # generation at absorb/rebuild time, so the edge arrays here
        # never contain tombstoned rows
        return idx, qseg, np.searchsorted(qseg, np.arange(nq + 1))

    # -------------------------------------------------- materialization
    def _materialize_group(self, m: CsrMirror, space_id: int,
                           alias_to_etype: Dict[str, Tuple],
                           etype_to_alias: Dict[int, str], yield_cols,
                           idx: np.ndarray, qseg: np.ndarray,
                           qbounds: np.ndarray, nq: int,
                           exc_types) -> List[object]:
        """Vectorized YIELD for a whole signature group: ONE compile +
        ONE column evaluation over the concatenated edge selection,
        then per-query row splits.  Queries whose rows need per-row
        semantics (invalid props, live div guards, uncompilable
        expressions) fall back individually to the per-row evaluator —
        their result (or error) never disturbs the rest of the group.
        Returns per-query: list-of-rows or an Exception instance."""
        def slice_q(g):
            return idx[qbounds[g]:qbounds[g + 1]]

        def per_query_fallback():
            out = []
            for g in range(nq):
                try:
                    out.append(self._materialize(
                        m, space_id, alias_to_etype, etype_to_alias,
                        yield_cols, slice_q(g), exc_types[g]))
                except Exception as ex:     # noqa: BLE001
                    out.append(ex)
            return out

        if len(idx) == 0:
            return [[] for _ in range(nq)]
        compiler = ExprCompiler(m, space_id, self.sm, alias_to_etype)
        try:
            cvals = [compiler.compile(c.expr) for c in yield_cols]
        except CompileError:
            return per_query_fallback()

        # validity / div-guard irregularities -> per-query fallback for
        # ONLY the affected queries
        irregular = np.zeros(nq, dtype=bool)
        inv = self._invalid_candidates(m, compiler.used, idx)
        if inv is not None and inv.any():
            irregular[np.unique(qseg[inv])] = True
        clean = ~irregular
        if not clean.any():
            return per_query_fallback()

        env = Env(self._gather_cols(m, alias_to_etype, compiler.used,
                                        idx))
        if compiler.div_guards:
            g_any = np.zeros(len(idx), dtype=bool)
            for g in compiler.div_guards:
                g_any |= np.broadcast_to(np.asarray(g(env)), idx.shape)
            if g_any.any():
                irregular[np.unique(qseg[g_any])] = True

        out_cols: List[List[object]] = []
        k_edges = len(idx)
        for cv, yc in zip(cvals, yield_cols):
            arr = cv.fn(env)
            out_cols.append(self._decode_col(m, cv, yc, arr, idx, k_edges,
                                             etype_to_alias))
        from ..graph.interim import ColumnarRows
        results: List[object] = [None] * nq
        for g in range(nq):
            if irregular[g]:
                try:
                    results[g] = self._materialize(
                        m, space_id, alias_to_etype, etype_to_alias,
                        yield_cols, slice_q(g), exc_types[g])
                except Exception as ex:     # noqa: BLE001
                    results[g] = ex
                continue
            lo, hi = int(qbounds[g]), int(qbounds[g + 1])
            # columnar + lazy: building hi-lo row lists per query here
            # was the assembly hot spot AND fed the cyclic GC millions
            # of row objects per dispatch
            results[g] = ColumnarRows([c[lo:hi] for c in out_cols],
                                      hi - lo)
        return results

    def _materialize(self, m: CsrMirror, space_id: int,
                     alias_to_etype: Dict[str, Tuple],
                     etype_to_alias: Dict[int, str], yield_cols,
                     idx: np.ndarray, exc_type) -> List[List[object]]:
        """Evaluate YIELD columns for the selected edges.

        Vectorized numpy (full int64/float64 precision) when the compiler
        supports every column; falls back to per-row eval — which
        reproduces _RowCtx error semantics exactly — otherwise.
        """
        if len(idx) == 0:
            return []
        compiler = ExprCompiler(m, space_id, self.sm, alias_to_etype)
        try:
            cvals = [compiler.compile(c.expr) for c in yield_cols]
        except CompileError:
            return self._materialize_per_row(
                m, space_id, alias_to_etype, etype_to_alias, yield_cols,
                idx, exc_type)

        # validity → per-row fallback raises the right error
        inv = self._invalid_candidates(m, compiler.used, idx)
        if inv is not None and inv.any():
            return self._materialize_per_row(
                m, space_id, alias_to_etype, etype_to_alias,
                yield_cols, idx, exc_type)

        env = Env(self._gather_cols(m, alias_to_etype, compiler.used,
                                        idx))

        # a real x/0 in a YIELD raises on the CPU path — per-row eval
        # reproduces the exact error
        for g in compiler.div_guards:
            if np.any(g(env)):
                return self._materialize_per_row(
                    m, space_id, alias_to_etype, etype_to_alias,
                    yield_cols, idx, exc_type)

        from ..graph.interim import _col_tolist
        out_cols: List[List[object]] = []
        k_edges = len(idx)
        for cv, yc in zip(cvals, yield_cols):
            arr = cv.fn(env)
            out_cols.append(_col_tolist(
                self._decode_col(m, cv, yc, arr, idx, k_edges,
                                 etype_to_alias)))
        if len(out_cols) == 1:
            return [[v] for v in out_cols[0]]
        return [list(t) for t in zip(*out_cols)]

    def _decode_col(self, m: CsrMirror, cv: CVal, yc, arr, idx: np.ndarray,
                    k: int, etype_to_alias: Dict[int, str]):
        """One YIELD column -> a flat column container (numpy array /
        ConstCol / DictCol) — rows materialize only at the edge, and
        the wire carries typed buffers (graph/interim.py)."""
        from ..graph.interim import ConstCol, DictCol
        if cv.kind == K_VIDRANK:
            return m.vids[np.asarray(arr)]
        if cv.kind == K_STR:
            return ConstCol(cv.const, k)
        if cv.kind == K_STRCODE:
            return DictCol(np.asarray(arr),
                           [str(v) for v in cv.dictionary])
        a = np.broadcast_to(np.asarray(arr), (k,))
        if cv.kind == K_BOOL:
            return a.astype(bool)
        if cv.kind == K_FLOAT:
            return a.astype(np.float64)
        return a.astype(np.int64)

    def _materialize_per_row(self, m: CsrMirror, space_id: int,
                             alias_to_etype: Dict[str, Tuple],
                             etype_to_alias: Dict[int, str], yield_cols,
                             idx: np.ndarray, exc_type) -> List[List[object]]:
        """Row-at-a-time eval with _RowCtx-equivalent getter semantics —
        the universal fallback (strings ops, functions, missing props)."""
        tag_ids = {}   # tag name -> id, resolved lazily

        def tag_id(tag: str) -> Optional[int]:
            if tag not in tag_ids:
                r = self.sm.to_tag_id(space_id, tag)
                tag_ids[tag] = r.value() if r.ok() else None
            return tag_ids[tag]

        rows = []
        for e in idx.tolist():
            src_i, dst_i = int(m.edge_src[e]), int(m.edge_dst[e])
            et = int(m.edge_etype[e])
            ctx = ExprContext()

            def vget(which_i, tag, prop, _e=e):
                t = tag_id(tag)
                col = m.vertex_cols.get((t, prop)) if t is not None else None
                if col is None or not col.valid[which_i]:
                    raise ExprError(f"{tag}.{prop} unavailable")
                return col.host_value(which_i)

            ctx.get_src_tag_prop = lambda tag, prop, _i=src_i: \
                vget(_i, tag, prop)
            ctx.get_dst_tag_prop = lambda tag, prop, _i=dst_i: \
                vget(_i, tag, prop)

            def eget(alias, prop, _e=e, _et=et):
                col = m.edge_cols.get((_et, prop))
                if col is None or not col.valid[_e]:
                    raise ExprError(f"{alias}.{prop} unavailable")
                return col.host_value(_e)

            ctx.get_alias_prop = eget
            ctx.get_edge_dst_id = lambda a, _i=dst_i: int(m.vids[_i])
            ctx.get_edge_src_id = lambda a, _i=src_i: int(m.vids[_i])
            ctx.get_edge_rank = lambda a, _e=e: int(m.edge_rank[_e])
            ctx.get_edge_type = lambda a, _et=et: \
                etype_to_alias.get(_et, str(_et))
            try:
                rows.append([c.expr.eval(ctx) for c in yield_cols])
            except ExprError as ex:
                raise exc_type(str(ex))
        return rows

    # ================================================== batched GO/BFS
    # The throughput path: B concurrent queries share one [rows, B]
    # frontier so the per-row-access cost (the TPU's serial
    # gather floor) is amortised across the whole batch — see
    # ell.py's module docstring.  graphd-level batching (many client
    # sessions, one device dispatch) and the perf tool drive these.
    @staticmethod
    def ell(m: CsrMirror) -> EllIndex:
        """EllIndex for an already-fetched mirror (cached on it — a
        single fetch keeps perm and dense-id space consistent even if
        the space version moves concurrently)."""
        ix = getattr(m, "_ell", None)
        if ix is None:
            ix = EllIndex.build(m.edge_src, m.edge_dst, m.edge_etype,
                                m.n,
                                cap=int(flags.get("tpu_ell_cap") or 512),
                                growth_slack=int(
                                    flags.get("tpu_ell_growth_slack")
                                    or 0))
            m._ell = ix
        return ix

    @staticmethod
    def _mesh_devices() -> int:
        """``tpu_mesh_devices`` (0/1 = single-device).  Asking for more
        devices than jax sees raises: serving single-device under a
        mesh flag would report a k-chip deployment that never left the
        first chip.  Checked at mirror build and wherever a mesh is
        resolved (the flag is mutable)."""
        k = int(flags.get("tpu_mesh_devices") or 0)
        if k <= 1:
            return 0
        import jax
        n = len(jax.devices())
        if n < k:
            raise MeshUnavailable(
                f"tpu_mesh_devices={k} but jax sees {n} device(s)")
        return k

    def _mesh_only(self):
        """The configured 1-D Mesh (or None) WITHOUT building any
        sharded tables — the sparse mesh path builds its own per-chunk
        tables and must not pay for (or hold) the dense design's."""
        k = self._mesh_devices()
        if not k:
            return None
        cached = getattr(self, "_mesh_cache", None)
        if cached is not None and cached[0] == k:
            return cached[1]
        import jax
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:k]), ("parts",))
        self._mesh_cache = (k, mesh)
        return mesh

    def _mesh_tables(self, m: CsrMirror, ix: EllIndex):
        """(mesh, tables, real_rows) — ell.shard_ell's — when
        tpu_mesh_devices > 1, else None.  Sharded tables are cached on
        the mirror alongside the ELL so they follow its lifecycle."""
        mesh = self._mesh_only()
        if mesh is None:
            return None
        k = mesh.devices.size
        cached = getattr(m, "_mesh_tables_cache", None)
        if cached is not None and cached[0] == k:
            return cached[1]
        from .ell import shard_ell
        tables = (mesh,) + shard_ell(mesh, "parts", ix)
        m._mesh_tables_cache = (k, tables)
        return tables

    @staticmethod
    def _batch_width(nq: int) -> int:
        """Pad the query count to a PINNED ladder width
        (`go_batch_widths`) so the dense kernels see a tiny fixed set
        of program shapes — a new width is a fresh XLA compile
        (measured 8-60 s), so steady-state serving must never ramp
        through widths."""
        ladder = sorted(int(w) for w in
                        str(flags.get("go_batch_widths") or
                            "128,1024").split(",") if w.strip())
        for w in ladder:
            if nq <= w:
                return w
        return max(ladder[-1] if ladder else 128,
                   1 << (nq - 1).bit_length())

    def _kernel(self, key: Tuple, builder):
        with self._lock:
            kern = self._kernels.get(key)
            if kern is None:
                # a cache miss is a jit (re)trace event — the p99 spike
                # source PROFILE must be able to name
                self.stats["kernel_compiles"] = \
                    self.stats.get("kernel_compiles", 0) + 1
                with tracing.span("tpu.jit.compile", kernel=str(key[0])):
                    kern = self._kernels[key] = builder()
        return kern

    @staticmethod
    def _upload_frontier_packed(ix: EllIndex, new_ids: np.ndarray,
                                qcols: np.ndarray, B: int):
        """Device [rows+1, B/8] uint8 start frontier built ON the device
        from flat (new-id row, query col) coordinates — the host→device
        transfer is the start list (bytes), not the dense mostly-zero
        matrix (MBs at million-vertex scale).  Padded to a power of two
        for stable shapes.  (row, query) pairs are deduped HERE, so two
        bits never
        collide in one scatter cell and scatter-ADD of distinct powers
        of two is exact (a scatter-max would lose bits; see
        ell._scatter_or_rows)."""
        import jax.numpy as jnp
        from .ell import lanes_width
        if len(new_ids):
            key = np.asarray(new_ids, np.int64) * max(B, 1) \
                + np.asarray(qcols, np.int64)
            _, first = np.unique(key, return_index=True)
            new_ids = np.asarray(new_ids)[first]
            qcols = np.asarray(qcols)[first]
        S = len(new_ids)
        Sp = max(8, 1 << (max(S, 1) - 1).bit_length())
        pad_row = ix.n_rows
        rows_p = np.full(Sp, pad_row, np.int32)
        word_p = np.zeros(Sp, np.int32)
        vals_p = np.zeros(Sp, np.uint8)
        rows_p[:S] = new_ids
        word_p[:S] = qcols >> 3
        vals_p[:S] = np.uint8(1) << (qcols & 7).astype(np.uint8)
        f0 = jnp.zeros((ix.n_rows + 1, lanes_width(B)), jnp.uint8)
        f0 = f0.at[jnp.asarray(rows_p), jnp.asarray(word_p)].add(
            jnp.asarray(vals_p))
        # the pad row collected the Sp-S padding scatters (value 1<<0);
        # it must stay all-zero — it is every sentinel slot's gather
        # source
        return f0.at[pad_row, :].set(0)

    def _go_batch_frontiers(self, space_id: int, starts_per_query,
                            et_tuple: Tuple[int, ...], kernel_steps: int):
        """Batched-GO core for the tool/bench surface: run
        ``kernel_steps - 1`` frontier advances for B queries; returns
        (bool [B, n] frontiers in the mirror's dense-id space, mirror)."""
        resolver = self._launch_frontiers(space_id, starts_per_query,
                                          et_tuple, kernel_steps)
        vs_lists, m = resolver()
        out = np.zeros((len(starts_per_query), m.n), dtype=bool)
        for q, vs in enumerate(vs_lists):
            out[q, vs] = True
        return out, m

    def go_batch(self, space_id: int, starts_per_query, etypes: List[int],
                 steps: int) -> np.ndarray:
        """Run B concurrent multi-hop GOs; returns bool [B, n] final
        frontiers (the final-hop *destinations*, i.e. ``steps``
        advances — the kernel's steps counts like kernels._go_body, so
        pass steps + 1) in the mirror's dense-id space.  Oversized
        batches run in go_batch_max chunks so the frontier matrix stays
        memory-bounded."""
        et_tuple = tuple(sorted(set(etypes)))
        self._bump("go_device", len(starts_per_query))
        if not starts_per_query:
            m = self.mirror(space_id)
            return np.zeros((0, m.n), dtype=bool)
        max_b = int(flags.get("go_batch_max") or 1024)
        outs = []
        for lo in range(0, len(starts_per_query), max_b):
            out, _ = self._go_batch_frontiers(
                space_id, starts_per_query[lo:lo + max_b], et_tuple,
                steps + 1)
            outs.append(out)
        return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    def _bfs_depths(self, space_id: int, m: CsrMirror, starts_per_query,
                    targets_per_query, et_tuple: Tuple[int, ...],
                    max_steps: int, shortest: bool) -> np.ndarray:
        """Batched BFS core against an already-fetched mirror: int16
        [B, n] depths (INT16_INF = unreached).  The dispatch record
        carries the levels the device loop ran, how many of them pushed
        out of their live rows, the slots they visited in all, and the
        lanes used."""
        from .ell import (BFS_INFO_LEVELS, BFS_INFO_PUSHED, INT16_INF,
                          bfs_slots, bfs_swept, dense_hop_bytes, lanes_width,
                          make_batched_bfs_lanes_kernel,
                          make_sharded_batched_bfs_kernel)
        import time
        stamps = [time.perf_counter()]
        ix = self.ell(m)
        nq = len(starts_per_query)
        B = self._batch_width(nq)
        mesh = self._mesh_only()
        if mesh is not None and flags.get("tpu_mesh_mode") == "sparse":
            d = self._mesh_sparse_bfs(space_id, m, ix, starts_per_query,
                                      targets_per_query, et_tuple,
                                      max_steps, shortest, B, mesh)
            if d is not None:
                return d
            # placement/overflow: replicated-frontier fallback below
        args = ix.kernel_args()
        mt = self._mesh_tables(m, ix)
        eslot, hrows = self._hub_merge_dev(m, ix)
        f0_dev = self._upload_frontier_packed(
            ix, *self._flat_coords(m, ix, starts_per_query, nq), B)
        t0_dev = self._upload_frontier_packed(
            ix, *self._flat_coords(m, ix, targets_per_query, nq), B)
        if mt is not None:
            mesh, tables, reals = mt
            kern = self._kernel(
                ("ell_bfs_sharded", ix.shape_sig(), et_tuple, max_steps,
                 shortest, mesh.shape["parts"]),
                # donate=True: f0p/t0p are built fresh per dispatch
                lambda: make_sharded_batched_bfs_kernel(
                    mesh, "parts", ix, max_steps, et_tuple, reals,
                    stop_when_found=shortest, donate=True))
            call_args = (f0_dev, t0_dev, eslot, hrows, *tables)
        else:
            kern = self._kernel(
                ("ell_bfs_packed", ix.shape_sig(), et_tuple, max_steps,
                 shortest),
                # donate=True: f0p/t0p are built fresh per dispatch
                lambda: make_batched_bfs_lanes_kernel(
                    ix, max_steps, et_tuple, stop_when_found=shortest,
                    donate=True))
            call_args = (f0_dev, t0_dev, eslot, hrows, *args[1:])
        self._bump("path_device", nq)
        stamps.append(time.perf_counter())
        with tracing.span("tpu.kernel",
                          kind="ell_bfs" if mt is None
                          else "ell_bfs_sharded", queries=nq):
            d_dev, info_dev = kern(*call_args)
        self._maybe_time_device(
            d_dev, dense_hop_bytes(ix, et_tuple, lanes_width(B),
                                   max_steps + 1),
            kind="ell_bfs")
        stamps.append(time.perf_counter())
        nqp = min(B, max(8, -(-nq // 8) * 8))
        with tracing.span("tpu.fetch"):
            host = np.asarray(d_dev[:, :nqp])[:, :nq]   # device slice
            # the lanes program's int32[3] (ell.BFS_INFO_*); the sharded
            # one's level count alone
            info = np.asarray(info_dev).reshape(-1)
            self._note_fetch(host)
        stamps.append(time.perf_counter())
        levels = int(info[BFS_INFO_LEVELS])
        self._bump("path_levels", levels)
        # the record is written once the level count is on the host,
        # so its time is the dispatch's end; its stages (tables +
        # kernel lookup + frontier upload, the asynchronous launch, the
        # wait for the device + the copy) tile the dispatch, traced or
        # not, so a dispatch that stood still says where
        stages = {k: int((b - a) * 1e6) for k, a, b in zip(
            ("upload_us", "enqueue_us", "fetch_us"), stamps, stamps[1:])}
        if mt is not None:
            # live ICI accounting: the spec declares the frontier
            # re-replication PER LEVEL; trips scales both sides by the
            # level count so the fold compares like with like
            fbytes = (ix.n_rows + 1) * lanes_width(B)
            self._note_sharded_ici(
                "ell_bfs_sharded", mesh.shape["parts"],
                [("sharding_constraint", fbytes * max_steps)],
                trips=max_steps, ell=ix, widths=(B,),
                fields={"rung": B, "steps": max_steps,
                        "h2d_bytes": 2 * fbytes, "levels": levels,
                        "queries": nq, **stages})
        else:
            levels_push = int(info[BFS_INFO_PUSHED])
            self._bump("path_levels_push", levels_push)
            # the levels that read one direction's table only: all of
            # them, or (a mixed-sign OVER set) none
            onesided = levels if sides_read(et_tuple) == 1 else 0
            self._bump("hop_onesided", onesided)
            swept = bfs_swept(ix, et_tuple, info)
            self._bump("hop_swept_slots", swept)
            _flight.recorder.note_dispatch(
                "ell_bfs", rung=B, steps=max_steps, levels=levels,
                levels_push=levels_push, hop_onesided=onesided,
                sides=sides_read(et_tuple),
                slots=bfs_slots(ix, et_tuple, info), swept=swept,
                queries=nq, **stages)
        if host.dtype == np.int8:        # in-kernel compression (-1=INF)
            d = np.where(host < 0, INT16_INF, host).astype(np.int16)
        else:
            d = host
        return ix.to_old(d).T

    @staticmethod
    def _flat_coords(m: CsrMirror, ix: EllIndex, per_query, nq: int):
        """Per-query vid lists -> flat (new-id rows, query ids) with
        unknown vids dropped — the ONE coordinate-flattening used by
        both the replicated and frontier-sharded BFS paths (their
        results are bit-matched fallbacks of each other, so start
        placement must never diverge)."""
        lens = [len(s) for s in per_query]
        flat: List[int] = []
        for s in per_query:
            flat.extend(int(v) for v in s)
        d = m.to_dense(flat)
        q = np.repeat(np.arange(nq, dtype=np.int32),
                      np.asarray(lens, np.int64))
        keep = d >= 0
        return ix.perm[d[keep]], q[keep]

    def _mesh_sparse_bfs(self, space_id: int, m: CsrMirror,
                         ix: EllIndex, starts_per_query,
                         targets_per_query, et_tuple: Tuple[int, ...],
                         max_steps: int, shortest: bool, B: int, mesh):
        """Frontier-sharded BFS depths (per-chip memory graph/k +
        depth/k — ell.make_frontier_sharded_sparse_bfs_kernel), or None
        when pair placement outgrows the per-device cap / the kernel
        overflows (caller runs the replicated-frontier design)."""
        from .ell import (INT16_INF,
                          make_frontier_sharded_sparse_bfs_kernel,
                          sharded_device_args,
                          split_start_pairs_by_owner)
        import jax.numpy as jnp
        k = mesh.shape["parts"]
        sh = self._sharded_ell(m, ix, k)
        nq = len(starts_per_query)
        cap = int(flags.get("tpu_sparse_cap") or (1 << 17))
        cap_x = max(256, cap // max(k // 2, 1))
        cap_e = max(64, cap // 8)

        def place(per_query):
            rows, q = self._flat_coords(m, ix, per_query, nq)
            return split_start_pairs_by_owner(
                sh, rows.astype(np.int32), q, cap)

        ps = place(starts_per_query)
        pt = place(targets_per_query)
        if ps is None or pt is None:
            return None
        builder = self._kernel(
            ("mesh_sparse_bfs", ix.shape_sig(), et_tuple, max_steps,
             shortest, k, cap, cap_x, cap_e),
            lambda: make_frontier_sharded_sparse_bfs_kernel(
                mesh, "parts", sh, max_steps, et_tuple,
                cap, cap_x, cap_e, stop_when_found=shortest))
        kern = self._kernel(
            ("mesh_sparse_bfs_b", ix.shape_sig(), et_tuple, max_steps,
             shortest, k, cap, cap_x, cap_e, B),
            lambda: builder(B))
        args = sharded_device_args(mesh, "parts", sh)
        with tracing.span("tpu.kernel", kind="mesh_sparse_bfs"):
            dep_dev, ovf_dev = kern(
                jnp.asarray(ps[0]), jnp.asarray(ps[1]),
                jnp.asarray(pt[0]), jnp.asarray(pt[1]),
                args[0], args[1], args[2], *args[3])
        if np.asarray(ovf_dev).any():
            self._bump("sparse_overflows")
            return None
        self._bump("path_device", nq)
        self._bump("bfs_mesh_sparse")
        # live ICI accounting: per level, two [k, cap_x] candidate
        # planes + two [k, cap_e] hub planes + the psum'd scalars —
        # the spec's per-level bound rides trips like the levels do
        self._note_sharded_ici(
            "mesh_sparse_bfs", k,
            [("all_to_all", 2 * 4 * k * (cap_x + cap_e) * max_steps),
             ("psum", 4 * k * max_steps)],
            trips=max_steps, sparse_cap=cap,
            fields={"rung": cap, "steps": max_steps})
        # device-side column slice before the fetch, like the
        # replicated path — B-nq padded columns are pure link waste
        nqp = min(B, max(8, -(-nq // 8) * 8))
        dep = np.asarray(dep_dev[:, :, :nqp]) \
            .reshape(k * sh.chunk, nqp)[:, :nq]
        d16 = np.vstack([dep[:ix.n_rows + 1],
                         np.full((max(0, ix.n_rows + 1 - len(dep)), nq),
                                 INT16_INF, np.int16)]) \
            if len(dep) < ix.n_rows + 1 else dep[:ix.n_rows + 1]
        return ix.to_old(d16.astype(np.int16)).T

    def bfs_batch(self, space_id: int, starts_per_query, targets_per_query,
                  etypes: List[int], max_steps: int,
                  shortest: bool = True) -> np.ndarray:
        """Batched BFS depths: int16 [B, n] (INT16_INF = unreached)."""
        if len(starts_per_query) != len(targets_per_query):
            raise ValueError(
                f"bfs_batch: {len(starts_per_query)} start lists vs "
                f"{len(targets_per_query)} target lists")
        rows, _ = self.bfs_batch_dispatch(
            space_id, list(zip(starts_per_query, targets_per_query)),
            tuple(sorted(set(etypes))), max_steps, shortest)
        return np.asarray(rows)

    def bfs_batch_dispatch(self, space_id: int, pairs,
                           et_tuple: Tuple[int, ...], max_steps: int,
                           shortest: bool):
        """Dispatcher entry (graph/batch_dispatch.py submit_batched):
        ``pairs`` is [(srcs, dsts), ...]; returns (depth rows, mirror).
        BFS reads raw base arrays — mirror_full documents that
        dependency (published generations are always overlay-free)."""
        m = self.mirror_full(space_id)
        d = self._bfs_depths(space_id, m, [p[0] for p in pairs],
                             [p[1] for p in pairs], et_tuple, max_steps,
                             shortest)
        return list(d), m

    # ================================================== FIND PATH
    def can_run_path(self, space_id: int, etypes: List[int]) -> bool:
        if flags.get("storage_backend") == "cpu":
            return False        # nebulint: carveout=cpu-backend
        if self.breaker.is_open((space_id, "path")):
            return False        # nebulint: carveout=breaker-open
        try:
            self.mirror(space_id)
        except MeshUnavailable:
            raise
        except Exception as e:      # noqa: BLE001 — build/transfer failed
            from ..storage.device import classify_device_failure
            reason = classify_device_failure(e)
            if reason is not None:
                self.breaker.record_failure((space_id, "path"), reason)
            return False        # nebulint: carveout=mirror-build-failed
        return True

    def run_find_path(self, executor, space_id: int, srcs: List[int],
                      dsts: List[int], etypes: List[int], max_steps: int,
                      shortest: bool, etype_names: Dict[int, str]
                      ) -> InterimResult:
        from ..storage.device import TpuDecline, classify_device_failure
        if not srcs or not dsts:
            return InterimResult(["path"])
        bkey = (space_id, "path")
        why = self.breaker.admit(bkey)
        if why is not None:
            tracing.annotate("tpu.breaker", state="open", space=space_id,
                             kernel_class="path")
            # nebulint: carveout=breaker-open
            raise TpuDecline(why, degraded=True)
        et_tuple = tuple(sorted(set(etypes)))

        # --- device half: batched ELL BFS depths, coalesced with any
        # concurrent same-shaped FIND PATHs (same dispatcher the GO
        # path uses).  The dispatch's mirror is the single source of
        # truth — evaluating emptiness against a separately fetched
        # mirror could disagree with the one the BFS actually used.
        try:
            d16, m = self.dispatcher.submit_batched(
                ("bfs_batch_dispatch", space_id, et_tuple, max_steps,
                 shortest), (srcs, dsts))
        except Exception as e:      # noqa: BLE001 — classify, rethrow
            reason = classify_device_failure(e)
            if reason is None:
                self.breaker.release_probe(bkey)    # neutral: re-probe
                raise
            self.breaker.record_failure(bkey, reason)
            tracing.annotate("tpu.breaker", state="failure",
                             space=space_id, kernel_class="path",
                             reason=reason)
            # nebulint: carveout=device-failure
            raise TpuDecline(f"device runtime failure ({reason}): {e}",
                             degraded=True) from e
        self.breaker.record_success(bkey)
        if m.m == 0:
            return InterimResult(["path"])

        # --- host half: parent-DAG reconstruction over the in-edge
        # order this mirror generation keeps -------------------------
        index = self._path_index(m, et_tuple)
        with tracing.span("tpu.path_reconstruct") as sp:
            # the span's wall is shared with every other walk of the
            # batch under the interpreter lock; cpu_us is this one's own
            # run time, runq_us its wait for a core
            h0 = hostclock.stamp() if sp is not None else None
            paths, found = _reconstruct_paths(
                m, index, d16, srcs, dsts, max_steps, shortest,
                etype_names)
            if sp is not None:
                sp.tag(paths=len(paths), **found,
                       **hostclock.span_fields("", h0, hostclock.stamp()))
        self._bump("path_rows", len(paths))
        if found["capped"]:
            self._bump("path_capped")
        return InterimResult(["path"], [[p] for p in sorted(paths)])

    def _path_index(self, m: CsrMirror, et_tuple: Tuple[int, ...]):
        """The predecessor order of one mirror generation for one
        SIGNED OVER set: (ptr int64 [n+1], edge ids) with the mirror
        rows (u, etype, rank, v) of those types that end in vertex v at
        ``[ptr[v]:ptr[v+1]]`` by ascending (u's vid, signed etype,
        rank) — the order _reconstruct_paths cuts by.  A row of +t is a
        stored edge u -> v, a row of -t the reverse key of a stored
        v -> u: a forward set indexes v's in-edges, REVERSELY its
        out-edges, BIDIRECT both, -t before +t.  Built at the
        generation's first path statement over that set and kept on
        the mirror (host memory only: 4 bytes a row of those types),
        so it goes when the generation does; ``path_index_us`` counts
        what the builds took, traced or not."""
        import time
        with m._path_index_lock:
            if et_tuple not in m._path_index:
                t0 = time.perf_counter()
                with tracing.span("tpu.path_index", edges=int(m.m)):
                    of_type = np.nonzero(np.isin(
                        m.edge_etype, np.asarray(et_tuple, np.int32)))[0]
                    dst = m.edge_dst[of_type]
                    # the edge arrays are in (src, etype, rank, dst)
                    # order and dense ids ascend with vids, so a stable
                    # sort by destination leaves each vertex's in-edges
                    # in that order
                    edge = of_type[np.argsort(dst, kind="stable")]
                    ptr = np.zeros(m.n + 1, np.int64)
                    np.cumsum(np.bincount(dst, minlength=m.n),
                              out=ptr[1:])
                    if m.m < 2 ** 31:
                        edge = edge.astype(np.int32)
                m._path_index[et_tuple] = (ptr, edge)
                self._bump("path_index_builds")
                self._bump("path_index_us",
                           int((time.perf_counter() - t0) * 1e6))
            return m._path_index[et_tuple]

    def serve_find_path(self, space_id: int, srcs: List[int],
                        dsts: List[int], etypes: List[int], max_steps: int,
                        shortest: bool, etype_names: Dict[int, str]):
        """storaged-side RPC half of cross-process FIND PATH
        (storage/service.py rpc_deviceFindPath).  Returns
        (columns, rows); raises TpuDecline when the device can't serve
        the space."""
        from ..storage.device import TpuDecline
        if not self.can_run_path(space_id, etypes):
            # nebulint: carveout=plan-decline
            raise TpuDecline("device path unavailable for space")
        interim = self.run_find_path(None, space_id, srcs, dsts, etypes,
                                     max_steps, shortest, etype_names)
        return interim.columns, interim.rows


# ================================================ continuous dispatch
class _ContinuousGoSession:
    """Resident device state of ONE continuous-dispatch stream: the
    packed frontier pair (exact-depth frontier + UPTO union
    accumulator) for a (space, OVER set) lane batch, advanced one hop
    per tick (docs/admission.md "Continuous dispatch").  A joiner's
    FIRST hop is the seat's wherever it can be: the host holds a
    start's neighbours, so ``join`` scatters the first frontier and
    the lane rides one sweep fewer.

    Owned by the stream's single pump thread (graph/batch_dispatch.py
    _ContinuousStream) — every method here runs on that one thread, so
    the session carries no lock by design; the seat bookkeeping that
    IS shared (the lane ledger, the rider queue) lives stream-side
    under its condition.  The device ops are all async under JAX: the
    pump enqueues join -> hop -> extract -> clear for tick k, then
    np.asarray-forces tick k-1's extract buffer while hop k computes —
    that forced fetch is the only point the host ever waits on the
    device (the double-buffer overlap tpu.device_idle_frac measures).

    Donation discipline: hop/join/clear consume the resident pair and
    return its next generation (the old buffers are dead the moment
    the op is enqueued — nothing else holds them); extract does NOT
    donate, its output is a fresh fetch-sized buffer."""

    def __init__(self, rt, space_id: int, m: CsrMirror, ix: EllIndex,
                 et_tuple: Tuple[int, ...], B: int,
                 seat_rows: Optional[int] = None):
        import jax.numpy as jnp
        from .ell import HOP_PUSH_ROWS, lanes_width, swept_slots
        self.rt = rt
        self.space_id = space_id
        self.m = m
        self.ix = ix
        self.et_tuple = et_tuple
        # the direction tables every hop of this stream reads: one, or
        # both for a two-signed OVER set (GO ... BIDIRECT)
        self.sides = sides_read(et_tuple)
        # the slots a pull of this stream gathers (a pull REPORTS the
        # table's; the index's reach lets its loops skip padding)
        self._pull_swept = swept_slots(ix, et_tuple)
        self.B = B                          # lane count (width rung)
        self.W = lanes_width(B)
        self._tables = ix.kernel_args()[1:]  # mirror-resident buckets
        self.eslot, self.hrows = rt._hub_merge_dev(m, ix)
        # the seat takes a joiner's first hop (join) out of the
        # mirror's per-(generation, OVER set) host tables, O(edges) at
        # their first use: filled here, where a generation's other
        # first-use costs land, never under a seated lane
        rt._over_ranges(m, et_tuple)
        # the most distinct rows a first frontier may have for the
        # seat to take the hop: the budget the device's own branch
        # uses (HOP_PUSH_ROWS unless a test passes its own; negative:
        # no seat takes a hop), and the first-hop edges one cohort's
        # seat expands, eight programs of the join ladder's top rung
        self.seat_rows = HOP_PUSH_ROWS if seat_rows is None \
            else seat_rows
        self.seat_cohort_edges = SEAT_COHORT_EDGES
        fp = jnp.zeros((ix.n_rows + 1, self.W), jnp.uint8)
        # .copy(): the pair is donated together every hop — two
        # argument slots must never alias one device buffer
        self.fp, self.accp = fp, fp.copy()
        self.hops = 0
        # info vectors of hops whose branch is not read yet, oldest
        # first (hop_reads); bounded, so a caller that never reads
        # them forgets the oldest
        self._hop_info: collections.deque = collections.deque(maxlen=64)
        self._hop_read = [0, 0, 0, 0, 0]  # read, not yet in a tick record
        # perf_counter marks of the last join: its map's end and its
        # pack's end, and the rows it scattered (join)
        self.join_marks = None
        self.join_rows = 0

    def _join_kernel(self):
        """The join program.  The first join over tables of these
        shapes at this width runs it once at every rung
        (ell.LANE_JOIN_RUNGS) with all-pad rows and zero values, a
        no-op on the carriers, so that no later cohort meets a shape
        for the first time: which rung a tick needs is the rows its
        joiners' first frontiers number, and no warm-up can promise to
        have met them all (_extract_kernel's rule; a session
        re-anchored over tables of the same shapes finds them run,
        rt.join_rungs_run)."""
        from .ell import make_lane_join_kernel
        sig = self.ix.shape_sig()
        kern = self.rt._kernel(
            ("ell_lane_join", sig),
            lambda: make_lane_join_kernel(self.ix, donate=True))
        if (sig, self.B) not in self.rt.join_rungs_run:
            for Sp in LANE_JOIN_RUNGS:
                self.fp, self.accp = kern(
                    self.fp, self.accp,
                    np.full(Sp, self.ix.n_rows, np.int32),
                    np.zeros(Sp, np.int32), np.zeros(Sp, np.uint8))
            self.rt.join_rungs_run.add((sig, self.B))
        return kern

    def join(self, joiners) -> List[bool]:
        """Seat the arrivals: ``joiners`` is [(lane, start_vids,
        may_advance)], the answer per joiner whether THE SEAT TOOK ITS
        FIRST HOP: its lane then holds its first frontier, the far ends
        of its starts' edges of the OVER set, read from the mirror
        generation the session is anchored on by the pass that answers
        every GO's last hop (rt._frontier_edges_multi: signs,
        REVERSELY and BIDIRECT are the mirror's rows, no case here),
        and the stream counts it one hop fewer to ride.  A walk's
        first step IS N(start), so every later frontier, count and row
        is what it was, one sweep sooner.

        A joiner advances iff the stream allows it (``may_advance``:
        it keeps a hop on the lanes afterwards and its lane is no UPTO
        one, whose accumulator needs depth 0 apart), its first
        frontier has at most ``seat_rows`` distinct rows (HOP_PUSH_ROWS,
        the device's own push budget) and the first-hop edges of the
        cohort's joiners, taken cheapest first, up to and including its
        own stay within ``seat_cohort_edges`` (a joiner of more edges
        than that alone is not expanded and stands in nobody's way).  Every other joiner is seated with its
        start rows, as ever, in the same scatter.  A start with no edge
        of the OVER set advances to an empty lane.  Unmappable vids
        drop exactly like the windowed upload.

        ONE pass over the cohort, whatever its size: one to_dense, one
        expansion, one np.unique over (joiner, row) keys, so that the
        scatter-add lands on zero bits only (the clear contract;
        multi-edges, several starts a statement, a neighbour reached
        twice), ``perm`` for the rows, one pack.  The table goes in as
        one program of the least rung that holds it
        (ell.lane_join_rung), or several of the top rung."""
        import time
        n, nj = self.m.n, len(joiners)
        lanes = np.fromiter((j[0] for j in joiners), np.int64, count=nj)
        lens = np.fromiter((len(j[1]) for j in joiners), np.int64,
                           count=nj)
        d = self.m.to_dense(np.fromiter(
            itertools.chain.from_iterable(j[1] for j in joiners),
            np.int64, count=int(lens.sum()))).astype(np.int64)
        who = np.repeat(np.arange(nj), lens)     # a start's joiner
        d, who = d[d >= 0], who[d >= 0]
        # keys (2 * joiner + kind) * n + row: kind 0 a start, kind 1 a
        # first-frontier row
        keys = 2 * who * n + d
        may = np.fromiter((j[2] and self.seat_rows >= 0 for j in joiners),
                          np.bool_, count=nj)
        if may.any():
            # who is expanded: read off the degrees, before any edge is
            edges = np.bincount(
                who, self.rt._deg_host(self.m, self.et_tuple)[d],
                minlength=nj).astype(np.int64)
            may &= edges <= self.seat_cohort_edges
            # cheapest first: a heavy joiner, which may yet fail the
            # row budget, takes the cohort's budget from nobody lighter
            order = np.argsort(np.where(may, edges, 0), kind="stable")
            may[order] &= np.cumsum(np.where(may, edges, 0)[order]) \
                <= self.seat_cohort_edges
            qs = np.flatnonzero(may)
            sel = may[who]
            idx, qseg, _ = self.rt._frontier_edges_multi(
                self.m, np.split(d[sel], np.searchsorted(
                    who[sel], qs[1:])), self.et_tuple)
            keys = np.concatenate(
                [keys, (2 * qs[qseg] + 1) * n + self.m.edge_dst[idx]])
        keys = np.unique(keys)
        kind, who = (keys // n) & 1, keys // (2 * n)
        adv = may & (np.bincount(who[kind == 1], minlength=nj)
                     <= self.seat_rows)
        # an advancing joiner's first frontier, everyone else's starts
        take = adv[who] == (kind == 1)
        keys, who = keys[take], who[take]
        S = len(keys)
        rows = self.ix.perm[keys % n]
        t_map = time.perf_counter()
        # where the pump splits join_us (graph/batch_dispatch.py
        # _tick): the joiners mapped, the arrays packed, and what is
        # left, the enqueue
        self.join_marks, self.join_rows = (t_map, t_map), S
        self.rt._bump("seat_joins", nj)
        self.rt._bump("seat_hops", int(adv.sum()))
        if S == 0:
            return adv.tolist()
        # full programs of the top rung, then the rest at its rung
        top = LANE_JOIN_RUNGS[-1]
        Sp = S - S % top + (lane_join_rung(S % top) if S % top else 0)
        rows_p = np.full(Sp, self.ix.n_rows, np.int32)   # pad row
        words_p = np.zeros(Sp, np.int32)
        vals_p = np.zeros(Sp, np.uint8)          # zero add: no-op
        rows_p[:S] = rows
        words_p[:S] = lanes[who] >> 3
        vals_p[:S] = np.uint8(1) << (lanes[who] & 7).astype(np.uint8)
        self.join_marks = (t_map, time.perf_counter())
        kern = self._join_kernel()
        with tracing.span("tpu.kernel", kind="ell_lane_join",
                          width=self.B):
            for lo in range(0, Sp, top):
                self.fp, self.accp = kern(
                    self.fp, self.accp, rows_p[lo:lo + top],
                    words_p[lo:lo + top], vals_p[lo:lo + top])
        return adv.tolist()

    def hop(self) -> None:
        """Advance every seated lane one hop; the UPTO accumulator
        unions the new frontier (exact-depth lanes never read it)."""
        from .ell import make_continuous_hop_kernel
        kern = self.rt._kernel(
            ("ell_go_hop", self.ix.shape_sig(), self.et_tuple),
            lambda: make_continuous_hop_kernel(self.ix, self.et_tuple,
                                               donate=True))
        with tracing.span("tpu.kernel", kind="ell_go_hop",
                          width=self.B, packed=True, sides=self.sides):
            self.fp, self.accp, info = kern(self.fp, self.accp,
                                            self.eslot, self.hrows,
                                            *self._tables)
        # its 12 bytes start for the host when the hop ends: a fetch
        # has a floor of half a millisecond on the v5e's host whatever
        # it carries, and read_hop_info should not pay it on the pump
        info.copy_to_host_async()
        self._hop_info.append(info)
        self.hops += 1
        # no tpu_device_timing_every probe here: blocking on the hop
        # before the pending cohort is assembled gives up the overlap
        # the stream exists for.  The stream's device wait is read
        # where the host blocks anyway — _LaneFetch.t_wait, the tick
        # record's fetch_wait_us (graph/batch_dispatch.py)

    def read_hop_info(self) -> None:
        """Take in the info vectors of the hops the device has finished
        (12 bytes each); a hop still in flight stays for a later call.
        Never waits.  _LaneFetch calls this where the pump has just
        waited on an extract queued behind those hops."""
        from .ell import HOP_INFO_SLOTS, HOP_INFO_SPARSE
        reads = sparse = swept = 0
        while self._hop_info and self._hop_info[0].is_ready():
            info = np.asarray(self._hop_info.popleft())
            reads += 1
            pushed, slots = int(info[HOP_INFO_SPARSE]), \
                int(info[HOP_INFO_SLOTS])
            sparse += pushed
            self._hop_read[2] += slots
            # a push gathered the slots it reports, a pull its reach
            swept += slots if pushed else self._pull_swept
        if reads:
            onesided = reads if self.sides == 1 else 0
            self._hop_read[0] += reads
            self._hop_read[1] += sparse
            self._hop_read[3] += onesided
            self._hop_read[4] += swept
            self.rt._bump("hop_sparse", sparse)
            self.rt._bump("hop_dense", reads - sparse)
            self.rt._bump("hop_onesided", onesided)
            self.rt._bump("hop_swept_slots", swept)

    def hop_reads(self) -> Tuple[int, int, int, int, int]:
        """(hops read, of them pushed, ELL slots they visited, hops
        that read one direction's table only, ELL slots they gathered)
        since the last call — the tick record's hop_reads / hop_sparse
        / hop_slots / hop_onesided / hop_swept."""
        self.read_hop_info()
        out = tuple(self._hop_read)
        self._hop_read = [0, 0, 0, 0, 0]
        return out

    def _extract_kernel(self):
        """The extract program.  The first fetching cohort over tables
        of these shapes at this width runs it once at every rung
        (ell.lane_extract_rungs), so that no later cohort meets a
        shape for the first time: which rung a tick needs is its
        leavers' number, and no warm-up can promise to have met them
        all.  A stream whose leavers only count never comes here and
        loads none of them; a session re-anchored over tables of the
        same shapes finds them run (rt.extract_rungs_run)."""
        from .ell import make_lane_extract_kernel
        sig = self.ix.shape_sig()
        kern = self.rt._kernel(("ell_lane_extract", sig),
                               lambda: make_lane_extract_kernel(self.ix))
        if (sig, self.B) not in self.rt.extract_rungs_run:
            for L in lane_extract_rungs(self.B):
                kern(self.fp, self.accp, np.zeros((3, L), np.int32))
            self.rt.extract_rungs_run.add((sig, self.B))
        return kern

    def extract(self, leavers):
        """Pack each leaving lane down the vertex rows (an UPTO lane
        out of the accumulator) and return a zero-arg resolver
        (_LaneFetch) -> per-leaver ascending old-dense-id frontier
        arrays.  The cohort is padded to its rung
        (ell.lane_extract_rung) with leavers of word 0, bit 0, which
        nothing reads.  The resolver is where the d2h fetch forces —
        call it AFTER enqueueing the next hop
        so the host assembly overlaps the device compute."""
        lanes = np.zeros((3, lane_extract_rung(len(leavers), self.B)),
                         np.int32)
        for i, (lane, upto) in enumerate(leavers):
            lanes[:, i] = (lane >> 3, lane & 7, 1 if upto else 0)
        kern = self._extract_kernel()
        with tracing.span("tpu.kernel", kind="ell_lane_extract",
                          width=self.B):
            out_dev = kern(self.fp, self.accp, lanes)
        # the copy starts when the pack ends, a tick before the
        # resolver asks for it
        out_dev.copy_to_host_async()
        return _LaneFetch(self, out_dev, len(leavers))

    def count(self, lanes):
        """Count the set bits of every lane of the resident frontier
        over the real vertex rows (ell.make_lane_count_kernel) and
        return a zero-arg resolver (_LaneCount) -> the counts of
        ``lanes``, the leavers whose statement is a k-hop
        neighbourhood count: they rode k hops and the k-th frontier is
        their distinct destinations.  Enqueued behind the hop that
        made that frontier and before the clear that drops it; one
        int32 a lane crosses the link, no column does."""
        from .ell import make_lane_count_kernel
        kern = self.rt._kernel(
            ("ell_lane_count", self.ix.shape_sig()),
            lambda: make_lane_count_kernel(self.ix))
        with tracing.span("tpu.kernel", kind="ell_lane_count",
                          width=self.B):
            out_dev = kern(self.fp)
        return _LaneCount(self, out_dev, list(lanes))

    def clear(self, lanes) -> None:
        """Zero the freed lanes' bits in both carriers — the seat-map
        half of a leave/evict; the ledger hands the lanes out again
        only after this op is enqueued (device program order makes the
        next join's scatter exact)."""
        from .ell import make_lane_clear_kernel
        keep = np.full(self.W, 0xFF, np.uint8)
        for lane in lanes:
            keep[lane >> 3] &= np.uint8(0xFF ^ (1 << (lane & 7)))
        kern = self.rt._kernel(
            ("ell_lane_clear", self.ix.shape_sig()),
            lambda: make_lane_clear_kernel(donate=True))
        with tracing.span("tpu.kernel", kind="ell_lane_clear",
                          width=self.B):
            self.fp, self.accp = kern(self.fp, self.accp, keep)


# The first-hop edges one cohort's seat expands on the host (join): its
# joiners are expanded cheapest first while their edges up to and
# including a joiner's own stay within this.  Eight programs of the join ladder's top rung:
# a lone joiner at the row budget (HOP_PUSH_ROWS = 2,048 distinct rows)
# fits with room for its multi-edges, and the pump's worst tick maps
# and scatters 4,096 rows (on the cells' graph a cohort of four
# joiners numbers 37 rows at the median and 969 at the 99th percentile,
# ISSUE 43).  A bound on the pump's work, not on the answer.
SEAT_COHORT_EDGES = 8 * LANE_JOIN_RUNGS[-1]


# A leaver whose set rows are at most this share of the table's vertex
# rows counts as ``unpack_live`` (the tick record's field; the traffic's
# share of sparse leavers).  The native pass (_unpack_lanes) takes every
# leaver one way and the share chooses nothing there.  In the numpy form
# it is the turn between two routes: a leaver over it takes its whole
# bitmap through ``perm``; under it, the bits of its non-zero bytes
# through ``inv`` and a sort.  On the v5e's host at 646,081 rows
# (PERF.md section 6, PR 37) a leaver costs 0.03 ms out of 30 set rows,
# 0.06 out of 1,000, 0.5 out of 18,000, 2.1 out of 100,000, 2.6 out of
# 130,000 and 3.9 out of 200,000 that way, and 1.6-1.9 ms at the low
# counts, 2.5 at 130,000 and 2.9 at 200,000 through ``perm``: the two
# cross at 0.19-0.20 of the table, where the column block's routes
# crossed too (PR 28).  A speed choice only: both ways give the same
# array.
LANE_UNPACK_LIVE_SHARE = 0.2


def _unpack_lanes(packed: np.ndarray, n: int, perm: np.ndarray,
                  inv: np.ndarray, n_leavers: int):
    """The leave cohort's frontiers out of its fetched buffer.

    ``packed`` is the extract's result (ell.make_lane_extract_kernel):
    uint8 [L, nb], nb a multiple of eight; row i < ``n_leavers`` is
    leaver i's bitmap, bit k of byte j vertex row k * nb + j
    (ell.lane_bitmap_rows), the bits of rows from n on zero; the rows
    past the leavers are the rung's padding, never read.  Returns (per
    leaver the ascending old dense ids of its set rows, int64: element
    for element what ``np.nonzero(column_bit[perm])[0]`` gives over the
    lane's word column; how many leavers' set rows were at most
    LANE_UNPACK_LIVE_SHARE of the table; the set rows found, summed
    over the leavers; how many leavers the native pass unpacked: all of
    them or none).

    One call into the native library (native/unpack.cc
    neb_unpack_lanes, made through ctypes: the interpreter lock is the
    riders' for its whole length) reads each leaver's own row of the
    buffer where it lies, marks ``inv`` of every set row in a bitmap of
    n id bits and reads the marks off in order, so the ids are
    ascending without a sort and a leaver of few set rows goes the way
    one of many does.  The ids of all leavers land in one array sized
    by their counted bits (neb_count_lanes: a pass over the words, made
    WITH the lock: it costs less than getting the lock back would) and
    leave as slices of it.  A library that lacks the entry leaves the
    numpy form (_unpack_lanes_numpy), said once on stderr."""
    from ..native import lib
    L = lib()
    if L is None or not hasattr(L, "neb_unpack_lanes"):
        _say_once("[tpu] native lane unpack missing: a leave cohort's "
                  "bitmaps are unpacked in numpy (three to four times "
                  "slower, and the lock changes hands at every call)")
        return _unpack_lanes_numpy(packed, n, perm, inv, n_leavers) + (0,)
    nb = packed.shape[1]
    own = packed[:n_leavers]
    if own.strides[1] != 1:
        # however the device hands the buffer over, a leaver's row is
        # one run of bytes before it is read (on the TPU and on CPU
        # jax it already is)
        own = np.ascontiguousarray(own)
    inv = np.ascontiguousarray(inv, np.int32)
    if len(inv) < n:
        raise IndexError("vertex row outside the index")
    found = np.empty(n_leavers, np.int64)
    ids = np.empty(L.neb_count_lanes(
        own.ctypes.data, own.strides[0], nb, n_leavers,
        found.ctypes.data), np.int64)
    L.neb_unpack_lanes(own.ctypes.data, own.strides[0], nb, n_leavers, n,
                       inv.ctypes.data, found.ctypes.data, ids.ctypes.data)
    ends = np.cumsum(found).tolist()
    outs = [ids[a:b] for a, b in zip([0] + ends, ends)]
    return (outs, int((found <= LANE_UNPACK_LIVE_SHARE * n).sum()),
            int(found.sum()), n_leavers)


def _unpack_lanes_numpy(packed: np.ndarray, n: int, perm: np.ndarray,
                        inv: np.ndarray, n_leavers: int):
    """_unpack_lanes without the native library: the same arrays and
    the same two counts.

    The set bits are counted first, a leaver at a time in one pass
    over the 64-bit words, and each leaver's route follows from its
    own count (LANE_UNPACK_LIVE_SHARE): a cohort's routes can differ.
    The leavers of few set rows share every later pass, so a cohort
    costs the interpreter what one leaver does: the non-zero bytes of
    all their bitmaps are found and unpacked together, a set bit's
    place gives its leaver and its row, ``inv`` its old id, and one
    sort by (leaver, id) puts every leaver's ids in order; they leave
    as slices of that one array.  A leaver of many set rows has its
    whole bitmap unpacked, put in row order and read in old-id order
    through ``perm``, which needs no sort."""
    nb = packed.shape[1]
    own = np.ascontiguousarray(packed[:n_leavers])
    found = np.bitwise_count(own.view(np.uint64)).sum(
        axis=1, dtype=np.int64)
    few = found <= LANE_UNPACK_LIVE_SHARE * n
    outs: List[np.ndarray] = [None] * n_leavers
    for i in np.flatnonzero(~few):
        bits = np.unpackbits(own[i], bitorder="little").view(np.bool_)
        rows = bits.reshape(nb, 8).T.ravel()[:n]
        outs[i] = np.flatnonzero(rows[perm]).astype(np.int64, copy=False)
    who = np.flatnonzero(few)
    if len(who):
        flat = (own if len(who) == n_leavers else own[who]).ravel()
        at = np.flatnonzero(flat != 0)
        pos = np.flatnonzero(np.unpackbits(
            flat[at], bitorder="little").view(np.bool_))
        # the leaver of every set bit: the bytes lie leaver by leaver
        of = np.repeat(np.arange(len(who)), found[who])
        rows = lane_bitmap_rows(at[pos >> 3] - of * nb, pos & 7, n)
        ids = np.sort(inv[rows] + of * n)
        ids -= of * n
        for i, own_ids in zip(who, np.split(
                ids, np.cumsum(found[who])[:-1])):
            outs[i] = own_ids
    return outs, len(who), int(found.sum())


class _LaneFetch:
    """Zero-arg resolver of one leave cohort's lane extraction ->
    per-leaver ascending old-dense-id frontier arrays.  It stamps the
    calling thread's three clocks (common/hostclock.py) where the
    device wait ends (``t_wait``) and where the
    copy ends (``t_d2h``) — the pump reads both into the tick record
    and its trace (graph/batch_dispatch.py _finish); ``tpu.fetch``
    still wraps wait + copy, as the windowed resolvers' does.  What
    the unpack met is left beside them for the same reader:
    ``unpack_leavers``, of them ``unpack_live`` of few set rows and
    ``unpack_native`` through the native pass, ``unpack_rows`` the set
    rows of all of them (_unpack_lanes)."""

    __slots__ = ("session", "out_dev", "n_leavers", "t_wait", "t_d2h",
                 "unpack_leavers", "unpack_live", "unpack_rows",
                 "unpack_native")

    def __init__(self, session, out_dev, n_leavers):
        self.session = session
        self.out_dev = out_dev
        self.n_leavers = n_leavers
        self.t_wait = self.t_d2h = None
        self.unpack_leavers = self.unpack_live = 0
        self.unpack_rows = self.unpack_native = 0

    def __call__(self):
        with tracing.span("tpu.fetch"):
            # the only point the pump waits on the device
            self.out_dev.block_until_ready()
            self.t_wait = hostclock.stamp()
            # every hop queued before this extract is done: their info
            # vectors are read here, before the results are handed
            # over, so the tick's record is not held up after it
            self.session.read_hop_info()
            packed = np.asarray(self.out_dev)       # [L, n / 8] uint8
            self.t_d2h = hostclock.stamp()
        # the buffer that crossed the link, the rung's padding too
        self.session.rt._note_fetch(packed)
        ix = self.session.ix
        outs, self.unpack_live, self.unpack_rows, self.unpack_native = \
            _unpack_lanes(packed, ix.n, ix.perm, ix.inv, self.n_leavers)
        self.unpack_leavers = self.n_leavers
        self.session.rt._bump("unpack_native", self.unpack_native)
        return outs


class _LaneCount:
    """Zero-arg resolver of one leave cohort's per-lane count -> the
    counting leavers' numbers, in the order of ``lanes``.  The wait for
    the count program (it sits behind the hop that made the frontier)
    and the read of its B int32 are one ``tpu.count`` span; ``t_done``
    is the stamp the pump splits its fetch wait at
    (graph/batch_dispatch.py _finish: count_us)."""

    __slots__ = ("session", "out_dev", "lanes", "t_done")

    def __init__(self, session, out_dev, lanes):
        self.session = session
        self.out_dev = out_dev
        self.lanes = lanes
        self.t_done = None

    def __call__(self):
        with tracing.span("tpu.count", leavers=len(self.lanes),
                          bytes=4 * self.session.B):
            self.out_dev.block_until_ready()
            # every hop queued before this count is done (_LaneFetch)
            self.session.read_hop_info()
            counts = np.asarray(self.out_dev)       # [B] int32
        self.session.rt._note_fetch(counts)
        self.t_done = hostclock.stamp()
        return [int(counts[lane]) for lane in self.lanes]


# ================================================== path reconstruction
# A FIND PATH answers at most ``find_path_max_paths`` rows (the flag,
# common/flags.py), chosen by a rule over vertex ids (docs/STATUS.md
# "FIND PATH"): a path is read from its target backwards, one step an
# edge (the vertex before, then the SIGNED edge type, then rank), the
# smaller step first and a path before its extensions; the first that
# many under that order are the answer.  The signed type is how the
# step crosses its stored edge: +t along it (u -> v stored, walked
# u to v), -t against it (v -> u stored, walked u to v: the mirror
# holds that as the reverse key (u, -t, rank, v)), so -t comes before
# +t.  A forward OVER set has +t alone, REVERSELY -t alone, BIDIRECT
# both: a path is a sequence of EDGES, so where u -> v and v -> u are
# both stored the step between them is two steps and gives two paths.
# FindPathExecutor's CPU walk (graph/executors/traverse.py) cuts by
# the same order.


def _reconstruct_paths(m: CsrMirror, index, depth: np.ndarray, srcs, dsts,
                       max_steps: int, shortest: bool,
                       etype_names: Dict[int, str]):
    """Host half of FIND PATH: the parent walk of FindPathExecutor
    (traverse.py) over the mirror generation's in-edge order (``index``,
    TpuRuntime._path_index) instead of RPC responses.  ``depth`` is the
    BFS's row for this statement (INT16_INF = unreached).  The work is
    the in-edges of the vertices on answered paths, never the edge
    table.  Returns (path strings, the span's tags: ``capped`` whether
    more than the cap existed, ``on_path_vertices`` the distinct
    vertices visited, ``depth`` the longest answered path's edges,
    ``steps`` the edges of the answered paths and ``rev_steps`` those
    of them crossed against their direction, a -t step)."""
    from .ell import INT16_INF
    targets = np.unique(m.to_dense(dsts))
    targets = targets[targets >= 0]
    src_set = {int(i) for i in m.to_dense(srcs) if i >= 0}
    paths: List[str] = []
    max_paths = int(flags.get("find_path_max_paths"))
    found = {"capped": False, "on_path_vertices": 0, "depth": 0,
             "steps": 0, "rev_steps": 0}

    def rows(verts: np.ndarray, eids: np.ndarray) -> List[str]:
        """Path strings of chains of one length: ``verts`` [k, D+1]
        dense ids target first, ``eids`` [k, D] the edges between."""
        vids = m.vids[verts[:, ::-1]].tolist()
        signed = m.edge_etype[eids[:, ::-1]]
        found["steps"] += signed.size
        found["rev_steps"] += int((signed < 0).sum())
        ets = signed.tolist()
        ranks = m.edge_rank[eids[:, ::-1]].tolist()
        return [" ".join([str(vs[0])] + [
            f"<{etype_names.get(et, et)},{rank}> {v}"
            for et, rank, v in zip(es, rs, vs[1:])])
            for vs, es, rs in zip(vids, ets, ranks)]

    for t in targets.tolist():
        if shortest and not 0 < depth[t] < INT16_INF:
            continue
        budget = max_paths - len(paths)
        if shortest and not budget:     # a further target with paths
            found["capped"] = True
            break
        if shortest:
            verts, eids, more, seen = _shortest_chains(
                m, index, depth, t, budget)
            paths += rows(verts, eids)
            longest = eids.shape[1]
        else:
            chains, more, seen = _all_chains(
                m, index, depth, t, src_set, max_steps, budget)
            for v, e in chains:
                paths += rows(np.asarray([v]), np.asarray([e]))
            longest = max((len(e) for _, e in chains), default=0)
        found["capped"] |= more
        found["on_path_vertices"] += seen
        found["depth"] = max(found["depth"], longest)
    return paths, found


def _shortest_chains(m: CsrMirror, index, depth: np.ndarray, t: int,
                     budget: int):
    """The first ``budget`` least-length paths into ``t`` in the cap's
    order, level by level from the target: every partial
    path is extended by its last vertex's in-edges whose source lies
    one level nearer a start.  Each such partial has a completion, so
    the first ``budget`` partials of a level hold the first ``budget``
    paths and the rest are dropped there.  Returns (vertices [k, D+1]
    target first, edge ids [k, D], whether paths were dropped, distinct
    vertices visited)."""
    ptr, edge = index
    verts = np.asarray([[t]], np.int64)
    eids = np.zeros((1, 0), np.int64)
    dropped, seen = False, 0
    for d in range(int(depth[t]), 0, -1):
        uniq, inv = np.unique(verts[:, -1], return_inverse=True)
        inv = inv.reshape(-1)
        seen += len(uniq)
        # the in-edges of the level's distinct vertices, kept where
        # the source's depth is d - 1, as a CSR over ``uniq``
        lo, cnt = ptr[uniq], ptr[uniq + 1] - ptr[uniq]
        cand = edge[np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
                    + np.arange(int(cnt.sum()))]
        keep = depth[m.edge_src[cand]] == d - 1
        cand = cand[keep]
        pcnt = np.bincount(np.repeat(np.arange(len(uniq)), cnt)[keep],
                           minlength=len(uniq))
        pstart = np.cumsum(pcnt) - pcnt
        # partial i takes its vertex's parents, in order, until the
        # budget is spent
        take = pcnt[inv]
        before = np.cumsum(take) - take
        dropped |= int(take.sum()) > budget
        take = np.minimum(take, np.maximum(budget - before, 0))
        before = np.cumsum(take) - take
        rows = np.repeat(np.arange(len(verts)), take)
        chosen = cand[np.repeat(pstart[inv] - before, take)
                      + np.arange(int(take.sum()))]
        verts = np.concatenate([verts[rows], m.edge_src[chosen][:, None]],
                               axis=1)
        eids = np.concatenate([eids[rows], chosen[:, None]], axis=1)
    return verts, eids, dropped, seen + len(np.unique(verts[:, -1]))


def _all_chains(m: CsrMirror, index, depth: np.ndarray, t: int,
                src_set, max_steps: int, budget: int):
    """FIND ALL PATH into ``t``: the first ``budget`` simple paths of at
    most ``max_steps`` edges from a start, in the cap's order
    (depth first, a vertex's in-edges in index order).  Every
    edge whose source was discovered within max_steps - 1 levels is a
    parent edge, as FindPathExecutor records them.  Returns
    ([(vertices target first, edge ids)], whether paths were dropped,
    distinct vertices visited)."""
    ptr, edge = index

    def parents(v: int):
        into = edge[ptr[v]:ptr[v + 1]]
        return iter(into[depth[m.edge_src[into]] <= max_steps - 1].tolist())

    chains, seen = [], {t}
    verts, eids, stack = [t], [], [parents(t)]
    while stack:
        e = next(stack[-1], None)
        if e is None:
            stack.pop()
            verts.pop()
            if eids:
                eids.pop()
            continue
        u = int(m.edge_src[e])
        if u in verts:
            continue
        seen.add(u)
        if u in src_set:
            if len(chains) >= budget:
                return chains, True, len(seen)
            chains.append((verts + [u], eids + [e]))
        if len(eids) + 1 < max_steps:
            verts.append(u)
            eids.append(e)
            stack.append(parents(u))
    return chains, False, len(seen)


# ================================================== small helpers
def _distinct_rows(rows):
    """YIELD DISTINCT: the first occurrence of every row, in order.
    Rows still held as integer columns are de-duplicated in one
    vectorised pass (the first index of each distinct row, ascending;
    a single column through numpy's plain 1-D unique, which sorts the
    integers themselves: ``axis=0`` goes through a structured view and
    takes eight times as long, so only several columns take it);
    anything else (rows already materialised, a float, string or
    dictionary column) keeps the row-by-row loop.  Same rows, same
    order, either way."""
    from ..graph.interim import ColumnarRows
    cols = rows._cols if isinstance(rows, ColumnarRows) else None
    if cols and all(
            isinstance(c, np.ndarray) and c.ndim == 1
            and c.dtype.kind in "iu" and c.dtype == cols[0].dtype
            for c in cols):
        if len(cols) == 1:
            first = np.unique(cols[0], return_index=True)[1]
        else:
            first = np.unique(np.stack(cols, axis=1), axis=0,
                              return_index=True)[1]
        first.sort()
        return ColumnarRows([c[first] for c in cols], len(first))
    seen = set()
    out = []
    for r in rows:
        key = tuple(r)
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out


def _default_col_name(expr) -> str:
    from ..graph.executors.traverse import default_col_name
    return default_col_name(expr)
