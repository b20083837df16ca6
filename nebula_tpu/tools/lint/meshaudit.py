"""meshaudit — SPMD collective, ICI-traffic and multi-chip capacity
auditor (nebulint v4).

jaxpr-audit (v3) proves the single-chip device path on the IR; this
pass proves the MULTI-CHIP story the same way, before the runtime mesh
work that will depend on it ships (ROADMAP-5).  Every sharded kernel
family registers ``mesh_instantiate`` buckets (tpu/kernels.py
KernelSpec v4) and the auditor re-traces them under REAL multi-device
meshes — 2/4/8-way on the forced-host-device CPU platform tier-1
already uses (tests/conftest.py) — asserting on the traced jaxpr:

  * **collective inventory**: the trace's collective primitives
    (psum / all_gather / all_to_all / ppermute / reduce_scatter, plus
    ``sharding_constraint`` re-replication points) must EXACTLY match
    the spec's declared COLLECTIVE_MODEL, axes included.  An implicit
    reshard or a full-table all-gather smuggled in by a refactor is an
    undeclared collective and fails lint (the communication-bottleneck
    stance of the on-chip-communication paper, PAPERS.md arxiv
    2108.11521);
  * **no closure-captured device buffers**: a constvar bigger than
    ``CONST_BYTES_MAX`` means a table was closed over instead of
    passed as an argument — the partitioner replicates it to every
    chip and the kernel cache pins it for the mirror's lifetime;
  * **static ICI traffic**: per-dispatch cross-shard exchange bytes
    derived from the collective operand avals (scan bodies multiply by
    their static trip counts, a data-dependent while body counts once,
    i.e. per level) must fit the spec's declared ``ici_bytes`` bound at
    every audited mesh size — the link half of the link-vs-compute
    table published beside docs/roofline.md;
  * **mesh-parameterized HBM residency**: per-shard tables (sharded
    args / k) + replicated frontier + outputs + exchange buffers must
    fit ``device_hbm_bytes`` at every audited mesh size (the PR 9
    per-rung gate, mesh-parameterized);
  * **layout + donation + width**: bit-packed uint8 frontiers across
    shard boundaries (an int8 regression fails on the aval dtype),
    donation surviving shard_map (donated_invars on the traced pjit),
    and no 64-bit promotion of sharded avals — all re-asserted per
    mesh size because each size is a distinct trace;
  * **capacity arithmetic**: runtime.MESH_MODEL's published multi-chip
    capacity table (max edges vs #chips, docs/static_analysis.md +
    BASELINE.md) must follow from HBM_MODEL — capacity_edges[k] x
    table_bytes_per_edge <= k x table_budget_bytes, monotone in k,
    with the k=1 row equal to HBM_MODEL's edge_ceiling.

The second check in this module, **carveout-inventory**, is the AST
half of ROADMAP-5's "shrink the mesh carve-outs": every CPU-decline
site in tpu/runtime.py (``raise TpuDecline`` and ``return False``
inside a ``can_run_*`` gate) must carry a ``# nebulint:
carveout=<reason>`` tag naming an entry of the closed MESH_CARVEOUTS
registry; untagged sites, unknown reasons and dead registry entries
are violations — the carve-out list becomes enumerable and baselined
instead of folklore.

Violations anchor to the factory's ``def`` line (mesh-audit) or the
decline site (carveout-inventory), so the usual ``# nebulint:
disable=`` machinery applies.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .core import Module, PackageContext, Violation, qualname_map

CHECK = "mesh-audit"
CARVEOUT_CHECK = "carveout-inventory"

# collective primitive -> per-device byte factor model, as a fraction
# of the operand bytes at mesh size k (documented in
# docs/static_analysis.md "The static ICI traffic model"):
#   psum            ring all-reduce: 2*(k-1)/k
#   all_gather      (k-1) x the per-shard operand
#   all_to_all      (k-1)/k of the [k, ...] per-device buffer moves
#   reduce_scatter  (k-1)/k
#   ppermute        one hop: the whole operand
#   sharding_constraint  re-replication of a sharded global: (k-1)/k
COLLECTIVE_PRIMS = ("psum", "all_gather", "all_gather_invariant",
                    "all_to_all", "ppermute", "pbroadcast",
                    "reduce_scatter", "psum_scatter",
                    "sharding_constraint")

# a closure-captured concrete array bigger than this is a smuggled
# device buffer (tables must ride as ARGUMENTS — tpu/ell.py's kernel
# cache contract); the audit fixture's whole table set is ~100 KB so
# real captures clear this by orders of magnitude
CONST_BYTES_MAX = 1 << 16


# ------------------------------------------------------------ jaxpr walk
def _sub_jaxprs(eqn) -> Iterable:
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for s in vs:
            inner = getattr(s, "jaxpr", None)
            if inner is not None:
                yield inner
            elif hasattr(s, "eqns"):
                yield s


def _walk_trips(jaxpr, trip: int):
    """Yield (eqn, trip) over the nested jaxpr, where ``trip`` is the
    product of enclosing static scan lengths (fori lowers to scan);
    while bodies — data-dependent — multiply by 1, so their costs are
    PER ITERATION (per BFS level)."""
    for eqn in jaxpr.eqns:
        yield eqn, trip
        name = eqn.primitive.name
        factor = 1
        if name == "scan":
            factor = int(eqn.params.get("length") or 1)
        for sub in _sub_jaxprs(eqn):
            yield from _walk_trips(sub, trip * factor)


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    return int(np.prod(aval.shape, dtype=np.int64)) \
        * np.dtype(aval.dtype).itemsize


def _collective_axes(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axes")
    if ax is None:
        ax = eqn.params.get("axis_name")
    if ax is None:
        return ()
    if isinstance(ax, (tuple, list)):
        return tuple(str(a) for a in ax)
    return (str(ax),)


def _exchange_bytes(name: str, operand_bytes: int, k: int) -> int:
    """The per-device ICI byte model above, evaluated."""
    if k <= 1:
        return 0
    if name == "psum":
        return (2 * (k - 1) * operand_bytes) // k
    if name in ("all_gather", "all_gather_invariant"):
        return (k - 1) * operand_bytes
    if name in ("all_to_all", "reduce_scatter", "psum_scatter",
                "sharding_constraint"):
        return ((k - 1) * operand_bytes) // k
    return operand_bytes          # ppermute / pbroadcast: one hop


def collect_collectives(closed, k: int):
    """(inventory, total_bytes, per_const_bytes): the set of
    (primitive, axes) pairs in the trace, the summed per-device
    exchange bytes (trip-multiplied), and the closure-captured
    constvar sizes."""
    inventory = set()
    total = 0
    consts = [_aval_bytes(v) for v in closed.jaxpr.constvars]
    for eqn, trip in _walk_trips(closed.jaxpr, 1):
        name = eqn.primitive.name
        for sub in _sub_jaxprs(eqn):
            # closure consts hoist into the nested pjit/shard_map
            # jaxprs' constvars, not the outer trace's
            consts.extend(_aval_bytes(v)
                          for v in getattr(sub, "constvars", ()))
        if name not in COLLECTIVE_PRIMS:
            continue
        inventory.add((name, _collective_axes(eqn)))
        op_bytes = sum(_aval_bytes(v) for v in eqn.invars)
        total += _exchange_bytes(name, op_bytes, k) * trip
    return inventory, total, consts


# ------------------------------------------------------------ residency
def _leaf_avals(args) -> List:
    import jax
    leaves, _ = jax.tree_util.tree_flatten(args)
    return leaves


def _arg_bytes(arg) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64))
               * np.dtype(a.dtype).itemsize for a in _leaf_avals(arg))


def _resolved_shard_args(spec, fx) -> set:
    sa = spec.shard_args
    return set(sa(fx) if callable(sa) else sa)


def mesh_residency(spec, fx, closed, avals, k: int,
                   exchange_bytes: int) -> int:
    """Per-SHARD peak resident bytes of one traced bucket at mesh size
    k: sharded args divide by k, replicated args (the packed frontier,
    hub merge vectors) are paid per chip, outputs likewise (donation
    reuses the donated frontier's buffer), plus the collective
    exchange buffers — the mesh-parameterized form of
    jaxaudit.hbm_residency behind the multi-chip capacity table."""
    shard_idx = _resolved_shard_args(spec, fx)
    args_b = 0
    donated_b = 0
    for idx, arg in enumerate(avals):
        b = _arg_bytes(arg)
        per = -(-b // k) if idx in shard_idx else b
        args_b += per
        if idx in spec.donate:
            donated_b += per
    out_b = 0
    for i, a in enumerate(closed.out_avals):
        b = int(np.prod(a.shape, dtype=np.int64)) \
            * np.dtype(a.dtype).itemsize
        out_b += -(-b // k) if i in spec.shard_outs else b
    return args_b + max(0, out_b - donated_b) + exchange_bytes


# ------------------------------------------------------------ audit core
def mesh_audit_specs(specs, fx, anchor, hbm: Optional[dict] = None,
                     sizes: Optional[Tuple[int, ...]] = None
                     ) -> List[Violation]:
    """Pure audit core (fixture-testable like jaxaudit.audit_specs):
    trace every spec's ``mesh_instantiate`` buckets at each mesh size
    and run the five IR checks.  ``anchor(spec) -> (rel, line)`` places
    violations; ``hbm`` (runtime.HBM_MODEL) arms the residency gate."""
    import jax
    from . import jaxaudit

    out: List[Violation] = []

    def emitter(spec):
        rel, line = anchor(spec)

        def emit(msg: str) -> None:
            out.append(Violation(CHECK, rel, line, spec.name, msg))
        return emit

    # the audited ladder lives on the fixture (AuditFixture.mesh_sizes
    # — ONE clamp site), so adding a rung there widens the audit too
    sizes = sizes or tuple(fx.mesh_sizes())
    budget = int((hbm or {}).get("device_hbm_bytes") or 0)
    for spec in specs:
        emit = emitter(spec)
        mesh_inst = getattr(spec, "mesh_instantiate", None)
        declared = getattr(spec, "collective", None)
        if mesh_inst is None:
            if declared is not None:
                emit(f"kernel '{spec.name}': declares a COLLECTIVE_"
                     f"MODEL but registers no mesh_instantiate buckets "
                     f"— the declaration is unprovable")
            continue
        if declared is None:
            emit(f"kernel '{spec.name}': sharded family without a "
                 f"declared COLLECTIVE_MODEL — its cross-chip traffic "
                 f"is unaudited")
            continue
        declared_set = {(name, tuple(axes)) for name, axes in declared}
        for k in sizes:
            try:
                mesh = fx.mesh(k)
                buckets = mesh_inst(fx, mesh)
            except Exception as e:  # noqa: BLE001 — can't build = finding
                emit(f"kernel '{spec.name}': mesh instantiation failed "
                     f"at k={k}: {type(e).__name__}: {e}")
                continue
            for key, fn, avals in buckets:
                try:
                    with jax.enable_x64(True):
                        closed = jax.make_jaxpr(fn)(*avals)
                except Exception as e:  # noqa: BLE001
                    emit(f"kernel '{spec.name}': mesh trace failed for "
                         f"bucket {key!r} at k={k}: "
                         f"{type(e).__name__}: {e}")
                    continue
                inventory, ici_total, consts = collect_collectives(
                    closed, k)
                # ---- exact collective inventory --------------------
                for extra in sorted(inventory - declared_set):
                    emit(f"kernel '{spec.name}': UNDECLARED collective "
                         f"{extra[0]}{list(extra[1])} in the k={k} "
                         f"trace — an implicit reshard/all-gather "
                         f"ships undeclared ICI traffic per dispatch")
                if k > 1:       # a 1-way mesh may fold collectives away
                    for missing in sorted(declared_set - inventory):
                        emit(f"kernel '{spec.name}': declared "
                             f"collective {missing[0]}{list(missing[1])}"
                             f" absent from the k={k} trace — the "
                             f"COLLECTIVE_MODEL is stale")
                # ---- closure-captured buffers ----------------------
                big = [b for b in consts if b > CONST_BYTES_MAX]
                if big:
                    emit(f"kernel '{spec.name}': k={k} trace closes "
                         f"over {len(big)} concrete buffer(s) of "
                         f"{max(big)} bytes — tables must ride as "
                         f"arguments or every chip pins a replica for "
                         f"the kernel cache's lifetime")
                # ---- static ICI bound ------------------------------
                bound_fn = getattr(spec, "ici_bytes", None)
                if inventory and k > 1:
                    if bound_fn is None:
                        emit(f"kernel '{spec.name}': collectives "
                             f"traced but no ici_bytes bound declared "
                             f"— the link cost is unmodeled")
                    elif ici_total > int(bound_fn(fx, k)):
                        emit(f"kernel '{spec.name}': k={k} bucket "
                             f"{key!r} exchanges {ici_total} bytes/"
                             f"device/dispatch over ICI, above the "
                             f"declared ici_bytes bound "
                             f"{int(bound_fn(fx, k))}")
                # ---- layout / width / donation ---------------------
                jaxaudit._audit_inputs(spec, avals, emit)
                jaxaudit._audit_one_trace(spec, closed, emit)
                jaxaudit._audit_donation(spec, closed, avals, emit)
                # ---- per-shard residency ---------------------------
                if budget > 0:
                    peak = mesh_residency(spec, fx, closed, avals, k,
                                          ici_total)
                    if peak > budget:
                        emit(f"kernel '{spec.name}': k={k} bucket "
                             f"{key!r} holds {peak} bytes resident "
                             f"per shard (tables/k + replicated "
                             f"frontier + outputs + exchange), over "
                             f"device_hbm_bytes {budget} — this mesh "
                             f"rung cannot serve")
    return out


def mesh_capacity_findings(hbm: Optional[dict],
                           mesh_model: Optional[dict]) -> List[str]:
    """The published multi-chip capacity table, proven on the
    declarations (the mesh form of jaxaudit.hbm_ceiling_findings):
    max-edges-at-k-chips must fit k per-chip table budgets, grow
    monotonically, and agree with the single-chip ceiling."""
    out: List[str] = []
    if not hbm or not mesh_model:
        return out
    sizes = tuple(mesh_model.get("mesh_sizes") or ())
    caps = dict(mesh_model.get("capacity_edges") or {})
    edge_bytes = float(hbm.get("table_bytes_per_edge") or 0.0)
    table_budget = int(hbm.get("table_budget_bytes") or 0)
    if set(caps) != set(sizes):
        out.append(
            f"MESH_MODEL: capacity_edges keys {sorted(caps)} do not "
            f"match mesh_sizes {sorted(sizes)} — every audited mesh "
            f"size needs a published capacity row")
        return out
    prev = 0
    for k in sorted(sizes):
        need = int(caps[k] * edge_bytes)
        have = k * table_budget
        if need > have:
            out.append(
                f"MESH_MODEL: capacity_edges[{k}] ({caps[k]:,} edges "
                f"x {edge_bytes} B/edge = {need:,} bytes) exceeds "
                f"{k} x table_budget_bytes = {have:,} — the published "
                f"multi-chip capacity table no longer holds")
        if caps[k] < prev:
            out.append(
                f"MESH_MODEL: capacity_edges[{k}] ({caps[k]:,}) is "
                f"below the previous rung ({prev:,}) — adding chips "
                f"must never shrink servable scale")
        prev = caps[k]
    ceiling = int(hbm.get("edge_ceiling") or 0)
    if 1 in caps and caps[1] != ceiling:
        out.append(
            f"MESH_MODEL: capacity_edges[1] ({caps[1]:,}) disagrees "
            f"with HBM_MODEL.edge_ceiling ({ceiling:,}) — one "
            f"single-chip claim, two numbers")
    return out


def mesh_traffic_table(fx, registry, mesh_model: dict,
                       spec_name: str = "ell_go_sharded") -> List[dict]:
    """Link-vs-compute rows per mesh shape for the replicated-frontier
    flagship (published beside docs/roofline.md): per-hop ICI exchange
    vs per-chip HBM hop traffic, timed at the declared ici_gbps /
    hbm_gbps.  Informational — the lint assertions above are the
    gate."""
    import jax
    from .jaxaudit import hbm_residency  # noqa: F401 (doc cross-ref)
    from ...tpu.ell import dense_hop_bytes, lanes_width
    spec = registry[spec_name]
    rows = []
    for k in (s for s in mesh_model["mesh_sizes"]
              if s <= len(jax.devices())):
        mesh = fx.mesh(k)
        buckets = spec.mesh_instantiate(fx, mesh)
        _key, fn, avals = buckets[-1]
        closed = jax.make_jaxpr(fn)(*avals)
        _inv, total, _c = collect_collectives(closed, k)
        hops = max(fx.steps - 1, 1)
        per_hop = total // hops
        compute = dense_hop_bytes(
            fx.ell, fx.etypes, lanes_width(max(fx.widths)), fx.steps) \
            // hops // k
        link_s = per_hop / (mesh_model["ici_gbps"] * 1e9)
        comp_s = compute / (mesh_model["hbm_gbps"] * 1e9)
        rows.append({
            "k": k, "exchange_bytes_per_hop": per_hop,
            "compute_bytes_per_hop_per_chip": compute,
            "bound": "link" if link_s > comp_s else "compute",
        })
    return rows


# ------------------------------------------------------------ package
def check_mesh_audit(ctx: PackageContext) -> List[Violation]:
    # fixture roots carry no kernel registry (same gate as jaxaudit)
    host = None
    for m in ctx.modules:
        if m.rel.endswith("tpu/kernels.py") and "KERNEL_REGISTRY" in m.source:
            host = m
            break
    if host is None:
        return []

    from ...tpu import runtime as rt
    from ...tpu.kernels import AuditFixture, kernel_registry

    registry = kernel_registry()
    pkg_dir = os.path.dirname(os.path.dirname(os.path.abspath(host.path)))
    rel_prefix = os.path.dirname(os.path.dirname(host.rel))

    def anchor(spec):
        code = getattr(spec.factory, "__code__", None)
        if code is None:
            return host.rel, 1
        rel = os.path.relpath(code.co_filename, pkg_dir).replace(
            os.sep, "/")
        rel = (rel_prefix + "/" + rel) if rel_prefix else rel
        return rel, code.co_firstlineno

    fx = AuditFixture()
    hbm = getattr(rt, "HBM_MODEL", None)
    out = mesh_audit_specs(registry.values(), fx, anchor, hbm=hbm)

    rt_mod = next((m for m in ctx.modules
                   if m.rel.endswith("tpu/runtime.py")), None)

    def _rt_anchor(symbol: str):
        line = 1
        if rt_mod is not None:
            for i, txt in enumerate(rt_mod.lines, start=1):
                if txt.startswith(symbol):
                    line = i
                    break
        return (rt_mod.rel if rt_mod is not None else host.rel), line

    mesh_model = getattr(rt, "MESH_MODEL", None)
    if mesh_model is None:
        rel, line = _rt_anchor("MESH_MODEL")
        out.append(Violation(
            CHECK, rel, line, "MESH_MODEL",
            "tpu/runtime.py declares no MESH_MODEL — the multi-chip "
            "capacity table is unpublished and unenforceable"))
    else:
        for msg in mesh_capacity_findings(hbm, mesh_model):
            rel, line = _rt_anchor("MESH_MODEL")
            out.append(Violation(CHECK, rel, line, "MESH_MODEL", msg))
    return out


# ==================================================================
# carveout-inventory — the AST half ("shrink the mesh carve-outs")
# ==================================================================
_CARVEOUT_TAG = re.compile(r"#\s*nebulint:\s*carveout\s*=\s*([\w\-]+)")
_CARVEOUT_FILE = "tpu/runtime.py"
_REGISTRY_NAME = "MESH_CARVEOUTS"


def _carveout_registry(mod: Module):
    """(name -> dict-key line) from the module's MESH_CARVEOUTS
    literal, or None when absent; malformed entries reported inline."""
    for node in mod.tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == _REGISTRY_NAME
                   for t in node.targets):
            continue
        if not isinstance(node.value, ast.Dict):
            return None, [Violation(
                CARVEOUT_CHECK, mod.rel, node.lineno, _REGISTRY_NAME,
                f"{_REGISTRY_NAME} must be a dict literal of "
                f"reason -> justification")]
        reg: Dict[str, int] = {}
        bad: List[Violation] = []
        for kn, vn in zip(node.value.keys, node.value.values):
            if not (isinstance(kn, ast.Constant)
                    and isinstance(kn.value, str)):
                bad.append(Violation(
                    CARVEOUT_CHECK, mod.rel, node.lineno, _REGISTRY_NAME,
                    "carve-out registry keys must be string literals"))
                continue
            just = ""
            if isinstance(vn, ast.Constant) and isinstance(vn.value, str):
                just = vn.value     # implicit concat folds to one Constant
            elif isinstance(vn, ast.JoinedStr):
                just = "x"          # f-strings count as non-empty
            if not just.strip():
                bad.append(Violation(
                    CARVEOUT_CHECK, mod.rel, kn.lineno, _REGISTRY_NAME,
                    f"carve-out '{kn.value}' carries no justification "
                    f"— every accepted decline needs a reason"))
            reg[kn.value] = kn.lineno
        return reg, bad
    return None, []


def _decline_sites(mod: Module) -> List[Tuple[int, str]]:
    """(line, symbol) of every ``raise TpuDecline(...)`` plus every
    ``return False`` inside a ``can_run_*`` function."""
    qmap = qualname_map(mod.tree)
    sites: List[Tuple[int, str]] = []

    def walk(node, stack):
        for child in ast.iter_child_nodes(node):
            sym = qmap.get(child)
            nstack = stack + [sym] if sym else stack
            if isinstance(child, ast.Raise):
                exc = child.exc
                fn = exc.func if isinstance(exc, ast.Call) else None
                name = None
                if isinstance(fn, ast.Name):
                    name = fn.id
                elif isinstance(fn, ast.Attribute):
                    name = fn.attr
                if name == "TpuDecline":
                    sites.append((child.lineno,
                                  nstack[-1] if nstack else "<module>"))
            elif isinstance(child, ast.Return):
                enclosing = next(
                    (s for s in reversed(nstack)
                     if s.split(".")[-1].startswith("can_run_")), None)
                if enclosing is not None \
                        and isinstance(child.value, ast.Constant) \
                        and child.value.value is False:
                    sites.append((child.lineno, enclosing))
            walk(child, nstack)

    walk(mod.tree, [])
    return sites


def _tag_at(mod: Module, line: int) -> Optional[str]:
    for ln in (line, line - 1):
        if 1 <= ln <= len(mod.lines):
            m = _CARVEOUT_TAG.search(mod.lines[ln - 1])
            if m:
                return m.group(1)
    return None


def check_carveout_inventory(ctx: PackageContext) -> List[Violation]:
    out: List[Violation] = []
    for mod in ctx.modules:
        if not mod.rel.endswith(_CARVEOUT_FILE):
            continue
        sites = _decline_sites(mod)
        reg, bad = _carveout_registry(mod)
        out.extend(bad)
        if reg is None:
            if sites:
                out.append(Violation(
                    CARVEOUT_CHECK, mod.rel, 1, "<module>",
                    f"{len(sites)} CPU-decline site(s) but no "
                    f"{_REGISTRY_NAME} registry — carve-outs must be "
                    f"an enumerable, justified list"))
            continue
        used = set()
        for line, symbol in sites:
            tag = _tag_at(mod, line)
            if tag is None:
                out.append(Violation(
                    CARVEOUT_CHECK, mod.rel, line, symbol,
                    "untagged carve-out: this CPU-decline site needs "
                    "a '# nebulint: carveout=<reason>' naming a "
                    f"{_REGISTRY_NAME} entry"))
            elif tag not in reg:
                out.append(Violation(
                    CARVEOUT_CHECK, mod.rel, line, symbol,
                    f"unknown carve-out reason '{tag}' — not in the "
                    f"{_REGISTRY_NAME} registry"))
            else:
                used.add(tag)
        for name in sorted(set(reg) - used):
            out.append(Violation(
                CARVEOUT_CHECK, mod.rel, reg[name], _REGISTRY_NAME,
                f"dead carve-out registry entry '{name}' — no decline "
                f"site cites it; delete the row (the carve-out was "
                f"shrunk, record the win)"))
    return out
