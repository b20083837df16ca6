"""Jitted traversal kernels — edge-parallel BFS over the CSR mirror.

Replaces the reference's per-hop RPC round trip + host-side set dedup
(GoExecutor.cpp:377-431 → StorageClient fan-out → storaged prefix scans).
Here a hop is three fused XLA ops over static shapes:

    active  = frontier[edge_src] & etype_ok          # gather  (HBM-bound)
    next    = zeros(n).at[edge_dst].max(active)      # scatter-max
    visited |= next

No data-dependent shapes: the frontier is a dense bool bitmap over the
n dense vertices and every hop touches all m edges.  That trades FLOPs
for compiler-friendliness — on TPU the scan is a pure HBM-bandwidth
stream (~9 bytes/edge/hop), which at v5e bandwidth (~800 GB/s) is ~10^10
edges/s, versus the reference's per-hop network RTT + RocksDB seeks.

Multi-chip: edges are sharded across a 1-D `parts` mesh axis
(jax.sharding.Mesh); each device expands its edge shard and the partial
frontier bitmaps merge with a `psum` over ICI — the TPU-native analogue
of the reference's scatter-gather + graphd-side dedup (SURVEY.md §5.7).

All kernels are cached per (mirror, query-shape) by the runtime; jit
recompiles only when static shapes/etypes/filter change.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


INT32_INF = np.int32(2**31 - 1)


# ====================================================================
# Kernel registry — the auditable surface of the device path.
#
# Every kernel factory (here, tpu/ell.py, and the expr_compile filter
# entry) registers a KernelSpec describing the ABSTRACT signatures the
# runtime really dispatches: its shape buckets (the pinned flag
# ladders), the runtime cache key per bucket, the declared donated
# buffers, the per-dispatch transfer arity, and a retrace budget.  The
# jaxpr device-path auditor (tools/lint/jaxaudit.py) traces each spec
# with jax.make_jaxpr across its buckets and proves, on the traced IR:
# no host callbacks in loop bodies, no 64-bit promotion of indices or
# frontier bitmaps, donation where claimed, a bounded recompile-key
# space, and transfer counts matching runtime.DEVICE_PHASES.
# ====================================================================
class KernelSpec:
    """One auditable kernel family.

    name        registry key (also the audit report symbol)
    factory     the factory callable — anchors violations (and inline
                ``# nebulint: disable=`` suppressions) to its def line
    phase_kind  key into tpu.runtime.DEVICE_PHASES (declared phases +
                transfer arity for this kernel's dispatch path)
    budget      max distinct (cache key, abstract signature) pairs —
                i.e. jit retraces — across the buckets, PER steps value
    instantiate fn(fixture) -> list of (cache_key, jitted_fn,
                abstract_args) buckets; fns with equal cache_key must
                be the same object (the runtime memoizes by that key)
    donate      declared donated argument indices (large single-use
                buffers: the batched frontier uploads)
    dispatch    argument indices uploaded PER DISPATCH (the rest are
                mirror-resident device arrays); len() must equal the
                declared h2d count in DEVICE_PHASES
    frontier    argument indices that are frontier bitmaps — their
                avals must stay <= 8-bit (int8/uint8/bool)
    packed      frontier indices that must be BIT-PACKED uint8 lanes
                (ell.pack_lanes_host layout) — a regression to the
                int8-per-lane layout (8x the hop's gather traffic,
                docs/roofline.md) fails lint on the aval dtype
    d2h_bytes_max  for reduction kernels (COUNT / LIMIT pushdown): a
                callable(fixture) -> max bytes any bucket's device->
                host fetch may total — the static proof that the
                reduced wire shape actually shrank

    meshaudit (nebulint v4) fields — sharded families only:

    mesh_instantiate  fn(fixture, mesh) -> buckets like ``instantiate``
                but built against a REAL multi-device mesh; meshaudit
                traces them at every audited mesh size (2/4/8-way on
                the forced-host-device CPU mesh) and proves the
                COLLECTIVE_MODEL on the IR
    collective  the declared COLLECTIVE_MODEL: a tuple of
                (primitive_name, axes_tuple) pairs — the EXACT
                collective inventory the traced jaxpr may contain
                (psum/all_gather/all_to_all/ppermute, plus
                'sharding_constraint' for the replicated designs'
                re-replication points).  Any undeclared collective —
                including an implicit resharding/all-gather introduced
                by closure capture — fails lint, as does a declared
                one that vanished
    ici_bytes   callable(fixture, k) -> upper bound on the per-device
                cross-shard exchange bytes of ONE traced dispatch at
                mesh size k.  meshaudit derives the actual bytes from
                the collective operand avals (the static ICI traffic
                model, docs/static_analysis.md): eqns inside scan/fori
                bodies multiply by their static trip counts; a data-
                dependent while body counts ONCE, so for level-loop
                kernels the bound is per level
    shard_args  argument indices whose leading dim shards over the
                mesh axis (per-shard residency = bytes / k); all
                other arguments are replicated per chip.  A callable
                (fixture) -> indices for families whose table count
                is fixture-dependent
    shard_outs  output indices sharded the same way (the rest are
                replicated, e.g. the re-replicated frontier)
    """

    __slots__ = ("name", "factory", "phase_kind", "budget", "instantiate",
                 "donate", "dispatch", "frontier", "packed",
                 "d2h_bytes_max", "mesh_instantiate", "collective",
                 "ici_bytes", "shard_args", "shard_outs")

    def __init__(self, name: str, factory, phase_kind: str, budget: int,
                 instantiate, donate: Tuple[int, ...] = (),
                 dispatch: Tuple[int, ...] = (),
                 frontier: Tuple[int, ...] = (),
                 packed: Tuple[int, ...] = (),
                 d2h_bytes_max=None,
                 mesh_instantiate=None,
                 collective: Optional[Tuple] = None,
                 ici_bytes=None,
                 shard_args: Tuple[int, ...] = (),
                 shard_outs: Tuple[int, ...] = ()):
        self.name = name
        self.factory = factory
        self.phase_kind = phase_kind
        self.budget = budget
        self.instantiate = instantiate
        self.donate = tuple(donate)
        self.dispatch = tuple(dispatch)
        self.frontier = tuple(frontier)
        self.packed = tuple(packed)
        self.d2h_bytes_max = d2h_bytes_max
        self.mesh_instantiate = mesh_instantiate
        self.collective = (tuple(tuple(c) for c in collective)
                          if collective is not None else None)
        self.ici_bytes = ici_bytes
        self.shard_args = (shard_args if callable(shard_args)
                           else tuple(shard_args))
        self.shard_outs = tuple(shard_outs)


KERNEL_REGISTRY: Dict[str, KernelSpec] = {}


def register_kernel(spec: KernelSpec) -> KernelSpec:
    KERNEL_REGISTRY[spec.name] = spec
    return spec


def kernel_registry() -> Dict[str, KernelSpec]:
    """The full registry, with the ell/expr_compile entry points
    loaded (they register on import)."""
    from . import ell as _ell                     # noqa: F401
    from . import expr_compile as _ec             # noqa: F401
    return dict(KERNEL_REGISTRY)


class AuditFixture:
    """Deterministic shape context the auditor traces against: a small
    synthetic ELL index (with a hub, so spill paths trace) plus the
    runtime's REAL pinned shape ladders read from the flag registry —
    the same ladders live dispatch buckets shapes into."""

    def __init__(self):
        from ..common.flags import flags
        rng = np.random.default_rng(7)
        self.n = 48
        self.m = 256
        self.etypes = (1, 2)
        src = rng.integers(0, self.n, self.m).astype(np.int32)
        dst = rng.integers(0, self.n, self.m).astype(np.int32)
        # one hub: concentrate edges on vertex 0 so cap=8 spills into
        # extra rows and the hub-expansion paths appear in the IR
        dst[: self.m // 4] = 0
        et = rng.integers(1, 3, self.m).astype(np.int32)
        et = np.concatenate([et, -et]).astype(np.int32)
        src2 = np.concatenate([src, dst]).astype(np.int32)
        dst2 = np.concatenate([dst, src]).astype(np.int32)
        self.edge_src, self.edge_dst, self.edge_etype = src2, dst2, et
        self.m = len(src2)
        from .ell import EllIndex
        self.ell = EllIndex.build(src2, dst2, et, self.n, cap=8,
                                  use_native=False)
        # the runtime's pinned ladders (one parse each, from the same
        # flags the dispatch paths read)
        self.widths = sorted(int(w) for w in
                             str(flags.get("go_batch_widths") or
                                 "128,1024").split(",") if w.strip())
        self.c0s = sorted(int(x) for x in
                          str(flags.get("tpu_sparse_c0s") or
                              "256,2048").split(",") if x.strip())
        self.sparse_cap = int(flags.get("tpu_sparse_cap") or (1 << 17))
        self.sparse_growth = int(flags.get("tpu_sparse_growth") or 8)
        self.qmax = int(flags.get("go_batch_max") or 1024)
        self.steps = 3                 # representative multi-hop depth
        self.limit = 10                # representative LIMIT pushdown

    # ---- abstract-signature helpers ---------------------------------
    @staticmethod
    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

    def table_avals(self) -> Tuple:
        """(owner, *bucket_nbr, *bucket_et, *out_nbr, *out_et) avals —
        mirror-resident (EllIndex.kernel_args order)."""
        ix = self.ell
        return ((self.aval((len(ix.extra_owner),), np.int32),)
                + tuple(self.aval(a.shape, a.dtype)
                        for group in (ix.bucket_nbr, ix.bucket_et,
                                      ix.out_nbr, ix.out_et)
                        for a in group))

    def edge_avals(self) -> Tuple:
        i32 = np.int32
        return (self.aval((self.m,), i32), self.aval((self.m,), i32),
                self.aval((self.m,), i32))

    def mesh(self, k: int = 1):
        """A k-device 1-D mesh over the visible devices (tier-1 forces
        an 8-way virtual CPU host platform, tests/conftest.py; the lint
        CLI forces the same before jax initializes).  jaxaudit's base
        pass traces k=1; meshaudit re-traces every sharded family at
        the REAL audited sizes because collective inventory, exchange
        avals and per-shard residency all depend on the axis size."""
        devs = jax.devices()
        if len(devs) < k:
            raise ValueError(f"mesh({k}) needs {k} devices, "
                             f"have {len(devs)}")
        return Mesh(np.array(devs[:k]), ("parts",))

    def mesh_sizes(self) -> Tuple[int, ...]:
        """The audited mesh-shape ladder, clamped to visible devices
        (8 under the tier-1 forced host platform)."""
        have = len(jax.devices())
        return tuple(k for k in (1, 2, 4, 8) if k <= have)


# ---------------------------------------------------------------- helpers
def etype_mask(edge_etype: jnp.ndarray, etypes: Tuple[int, ...]) -> jnp.ndarray:
    """bool[m]: edge participates in this OVER set (static etype tuple)."""
    ok = jnp.zeros(edge_etype.shape, dtype=bool)
    for et in etypes:
        ok = ok | (edge_etype == et)
    return ok


def bitmap_from_idx(idx: jnp.ndarray, n: int) -> jnp.ndarray:
    """Dense frontier bitmap from (possibly -1-padded) dense indices."""
    valid = idx >= 0
    safe = jnp.where(valid, idx, 0)
    return jnp.zeros((n,), dtype=bool).at[safe].max(valid)


# ---------------------------------------------------------------- GO
def _go_body(n: int, steps: int, etypes: Tuple[int, ...],
             edge_src, edge_dst, edge_etype, start_idx, filter_mask):
    """Shared GO trace: hops 1..steps-1 move the frontier bitmap (the CPU
    path's per-hop `seen` dedup — GoExecutor.cpp:407-431); the final hop
    emits the edge mask, post-filter."""
    ok = etype_mask(edge_etype, etypes)
    frontier = bitmap_from_idx(start_idx, n)

    def hop(_, f):
        active = f[edge_src] & ok
        return jnp.zeros((n,), dtype=bool).at[edge_dst].max(active)

    if steps > 1:
        frontier = jax.lax.fori_loop(0, steps - 1, hop, frontier)
    final = frontier[edge_src] & ok
    if filter_mask is not None:
        final = final & filter_mask
    return final, frontier


def make_go_kernel(n: int, steps: int, etypes: Tuple[int, ...]):
    """fn(edge_src, edge_dst, edge_etype, start_idx)
    -> (final_edge_mask bool[m], final_frontier bool[n])."""

    @jax.jit
    def go(edge_src, edge_dst, edge_etype, start_idx):
        return _go_body(n, steps, etypes, edge_src, edge_dst, edge_etype,
                        start_idx, None)

    return go


def make_go_filtered_kernel(n: int, steps: int, etypes: Tuple[int, ...],
                            filter_fn: Callable):
    """GO with the WHERE mask fused into the same XLA program.

    ``filter_fn(edge_src, edge_dst, env_cols) -> bool[m]`` is the compiled
    expression (expr_compile.py); env_cols is a flat dict of device arrays
    (edge-aligned prop columns, n-length vertex columns gathered inside).
    """

    @jax.jit
    def go(edge_src, edge_dst, edge_etype, start_idx, env_cols):
        fmask = filter_fn(edge_src, edge_dst, env_cols)
        return _go_body(n, steps, etypes, edge_src, edge_dst, edge_etype,
                        start_idx, fmask)

    return go


# ---------------------------------------------------------------- BFS depth
def make_bfs_kernel(n: int, max_steps: int, etypes: Tuple[int, ...],
                    stop_when_found: bool = True):
    """Level-synchronous BFS depths (FIND PATH device half).

    fn(edge_src, edge_dst, edge_etype, start_idx, target_idx) -> depth
    int32[n] (INT32_INF = unreachable within max_steps).

    ``stop_when_found`` mirrors the CPU path's shortest-mode `unfound`
    early exit (traverse.py FindPathExecutor); ALL-paths mode must keep
    expanding to max_steps because every discovered edge is a parent.
    """

    @jax.jit
    def bfs(edge_src, edge_dst, edge_etype, start_idx, target_idx):
        ok = etype_mask(edge_etype, etypes)
        start = bitmap_from_idx(start_idx, n)
        targets = bitmap_from_idx(target_idx, n)
        depth0 = jnp.where(start, 0, INT32_INF).astype(jnp.int32)

        def cond(state):
            d, frontier, step = state
            go_on = (step < max_steps) & frontier.any()
            if stop_when_found:
                go_on = go_on & (targets & (d == INT32_INF)).any()
            return go_on

        def body(state):
            d, frontier, step = state
            active = frontier[edge_src] & ok
            reached = jnp.zeros((n,), dtype=bool).at[edge_dst].max(active)
            newly = reached & (d == INT32_INF)
            d = jnp.where(newly, step + 1, d)
            return d, newly, step + 1

        d, _, _ = jax.lax.while_loop(
            cond, body, (depth0, start, jnp.int32(0)))
        return d

    return bfs


# ---------------------------------------------------------------- sharded GO
def pad_to(arr: np.ndarray, size: int, fill) -> np.ndarray:
    if len(arr) >= size:
        return arr
    pad = np.full(size - len(arr), fill, dtype=arr.dtype)
    return np.concatenate([arr, pad])


def make_sharded_go_kernel(mesh: Mesh, axis: str, n: int, steps: int,
                           etypes: Tuple[int, ...]):
    """Multi-chip GO: edge arrays sharded over ``axis``, frontier bitmap
    replicated; each hop psum-merges per-shard partial bitmaps over ICI.

    This is the TPU equivalent of the reference's partitioned storaged
    fan-out (§2.12): the edge shard plays the part, the psum plays the
    graphd-side dedup/merge.  fn maps sharded (edge_src, edge_dst,
    edge_etype) + replicated start bitmap -> (final_mask sharded bool[m],
    frontier bool[n]).
    """
    from jax import shard_map

    def per_shard(edge_src, edge_dst, edge_etype, frontier0):
        ok = etype_mask(edge_etype, etypes)

        def hop(_, f):
            active = f[edge_src] & ok
            partial = jnp.zeros((n,), dtype=jnp.int32) \
                .at[edge_dst].max(active.astype(jnp.int32))
            merged = jax.lax.psum(partial, axis)     # ICI all-reduce
            return merged > 0

        frontier = jax.lax.fori_loop(0, steps - 1, hop, frontier0) \
            if steps > 1 else frontier0
        final = frontier[edge_src] & ok
        return final, frontier

    sharded = shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis), P()),
        out_specs=(P(axis), P()),
        check_vma=False)
    return jax.jit(sharded)


def _go_buckets(fx: "AuditFixture"):
    """make_go_kernel dispatches on (steps, padded start count): the
    start pad rides _pad_pow2's pow-2 ladder, so the key space per
    steps value is log2-bounded.  Two representative rungs trace the
    ladder's shape law."""
    out = []
    for S in (8, 64):
        # audit-time instantiation: traced, never dispatched
        kern = make_go_kernel(  # nebulint: disable=jax-hotpath
            fx.n, fx.steps, fx.etypes)
        out.append((("fused_go", fx.steps, S), kern,
                    fx.edge_avals() + (fx.aval((S,), np.int32),)))
    return out


def _go_filtered_buckets(fx: "AuditFixture"):
    def filter_fn(edge_src, edge_dst, env_cols):
        # representative compiled-WHERE shape: an edge float column
        # compare fused with a src-gathered vertex column compare —
        # the same column-gather pattern runtime._run_go_kernel's
        # filter closures emit
        return (env_cols["ew"] > 0) & (env_cols["vw"][edge_src] > 0)

    env = {"ew": fx.aval((fx.m,), np.float32),
           "vw": fx.aval((fx.n,), np.float32)}
    kern = make_go_filtered_kernel(fx.n, fx.steps, fx.etypes, filter_fn)
    return [(("fused_go_filtered", fx.steps, 8), kern,
             fx.edge_avals() + (fx.aval((8,), np.int32), env))]


def _bfs_buckets(fx: "AuditFixture"):
    out = []
    for stop in (True, False):
        kern = make_bfs_kernel(  # nebulint: disable=jax-hotpath
            fx.n, fx.steps, fx.etypes,
                               stop_when_found=stop)
        out.append((("fused_bfs", fx.steps, stop, 8), kern,
                    fx.edge_avals() + (fx.aval((8,), np.int32),
                                       fx.aval((8,), np.int32))))
    return out


def _sharded_go_mesh_buckets(fx: "AuditFixture", mesh: Mesh):
    """One bucket per mesh size; fx.m is a multiple of 8, so the edge
    avals shard evenly at every audited axis size."""
    k = mesh.shape["parts"]
    kern = make_sharded_go_kernel(mesh, "parts", fx.n, fx.steps,
                                  fx.etypes)
    return [(("sharded_go", fx.steps, k), kern,
             fx.edge_avals() + (fx.aval((fx.n,), np.bool_),))]


def _sharded_go_buckets(fx: "AuditFixture"):
    return _sharded_go_mesh_buckets(fx, fx.mesh())


register_kernel(KernelSpec(
    "go", make_go_kernel, phase_kind="go_fused",
    # per steps value: one retrace per pow-2 start-pad rung; 24 rungs
    # bound every int32-indexable start count
    budget=24, instantiate=_go_buckets, dispatch=(3,)))
register_kernel(KernelSpec(
    "go_filtered", make_go_filtered_kernel, phase_kind="go_filtered",
    # fused-filter kernels are per (space, build, expr) by design —
    # ONE shape bucket each (the runtime keys them that way)
    budget=1, instantiate=_go_filtered_buckets, dispatch=(3, 4)))
register_kernel(KernelSpec(
    "bfs", make_bfs_kernel, phase_kind="bfs_fused",
    budget=2, instantiate=_bfs_buckets, dispatch=(3, 4)))
register_kernel(KernelSpec(
    "sharded_go", make_sharded_go_kernel, phase_kind="go_sharded",
    budget=1, instantiate=_sharded_go_buckets, dispatch=(3,),
    frontier=(3,),
    # COLLECTIVE_MODEL: one explicit psum per hop merges the per-shard
    # partial bitmaps over ICI — nothing else may move between chips
    mesh_instantiate=_sharded_go_mesh_buckets,
    collective=(("psum", ("parts",)),),
    # ring all-reduce of the int32 [n] partial bitmap per hop:
    # 2*(k-1)/k * 4n bytes per device, bounded by 8n, times the
    # steps-1 hop scan
    ici_bytes=lambda fx, k: 8 * fx.n * max(fx.steps - 1, 1),
    shard_args=(0, 1, 2), shard_outs=(0,)))


def shard_edges(mesh: Mesh, axis: str, edge_src: np.ndarray,
                edge_dst: np.ndarray, edge_etype: np.ndarray):
    """Pad edge arrays to a multiple of the mesh axis size and place them
    sharded; padding uses etype=0 (never a real etype — SURVEY §2.1: etype
    ids start at 1), so padded lanes are masked out by etype_ok."""
    k = mesh.shape[axis]
    m = len(edge_src)
    size = ((m + k - 1) // k) * k if m else k
    es = pad_to(edge_src, size, 0)
    ed = pad_to(edge_dst, size, 0)
    ee = pad_to(edge_etype, size, 0)
    sharding = NamedSharding(mesh, P(axis))
    return (jax.device_put(es, sharding), jax.device_put(ed, sharding),
            jax.device_put(ee, sharding), size)
