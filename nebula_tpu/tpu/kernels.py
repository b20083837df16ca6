"""The kernel registry the device-path lint audits, and the plain
edge-list GO the ELL programs are held to.

``make_go_kernel`` is the first-generation hop — three XLA ops over
static shapes, every hop touching all m edges:

    active  = frontier[edge_src] & etype_ok          # gather
    next    = zeros(n).at[edge_dst].max(active)      # scatter-max

Nothing in the package dispatches it: the served programs are the ELL
families of tpu/ell.py, and tests/test_ell.py,
tests/test_tpu_backend.py::TestKernels and __graft_entry__.py compare
them against this one as the independent reference.  So it carries no
KernelSpec and no DEVICE_PHASES row.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh


# ====================================================================
# Kernel registry — the auditable surface of the device path.
#
# Every kernel factory the runtime dispatches (tpu/ell.py) registers
# a KernelSpec describing the ABSTRACT signatures the runtime really
# dispatches: its shape buckets (the pinned flag ladders), the runtime
# cache key per bucket, the declared donated buffers, the
# per-dispatch transfer arity, and a retrace budget.  The
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
    """The full registry, with ell's entry points loaded (they
    register on import)."""
    from . import ell as _ell                     # noqa: F401
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
        m = 256
        self.etypes = (1, 2)
        src = rng.integers(0, self.n, m).astype(np.int32)
        dst = rng.integers(0, self.n, m).astype(np.int32)
        # one hub: concentrate edges on vertex 0 so cap=8 spills into
        # extra rows and the hub-expansion paths appear in the IR
        dst[: m // 4] = 0
        et = rng.integers(1, 3, m).astype(np.int32)
        et = np.concatenate([et, -et]).astype(np.int32)
        src2 = np.concatenate([src, dst]).astype(np.int32)
        dst2 = np.concatenate([dst, src]).astype(np.int32)
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
             edge_src, edge_dst, edge_etype, start_idx):
    """The GO trace: hops 1..steps-1 move the frontier bitmap (the CPU
    path's per-hop `seen` dedup — GoExecutor.cpp:407-431); the final hop
    emits the edge mask."""
    ok = etype_mask(edge_etype, etypes)
    frontier = bitmap_from_idx(start_idx, n)

    def hop(_, f):
        active = f[edge_src] & ok
        return jnp.zeros((n,), dtype=bool).at[edge_dst].max(active)

    if steps > 1:
        frontier = jax.lax.fori_loop(0, steps - 1, hop, frontier)
    return frontier[edge_src] & ok, frontier


def make_go_kernel(n: int, steps: int, etypes: Tuple[int, ...]):
    """fn(edge_src, edge_dst, edge_etype, start_idx)
    -> (final_edge_mask bool[m], final_frontier bool[n])."""

    @jax.jit
    def go(edge_src, edge_dst, edge_etype, start_idx):
        return _go_body(n, steps, etypes, edge_src, edge_dst, edge_etype,
                        start_idx)

    return go
