"""jaxpr-audit — semantic device-path analysis on the traced IR.

PR 2's jax-hotpath check reads SOURCE (jit-in-loop, ``_dev``-suffix
host syncs); this pass reads the IR the compiler actually sees.  Every
kernel factory registers a KernelSpec (tpu/kernels.py KERNEL_REGISTRY
— the GO/BFS/sharded families, the ELL table kernels, the expr_compile
filter entry), and the auditor traces each one with ``jax.make_jaxpr``
across the runtime's REAL shape buckets (the pinned go_batch_widths /
tpu_sparse_c0s ladders), proving on the jaxpr:

  * no host callbacks (``pure_callback``/``io_callback``/
    ``debug_callback``) inside ``while``/``scan`` loop bodies — a
    callback per hop re-serializes the frontier loop on the host
    (IntersectX, arxiv 2012.10848: accelerator traversal wins evaporate
    on host round trips);
  * no 64-bit promotion of persistent buffers: kernel inputs, outputs
    and loop carries must stay <= 32-bit (traced under enable_x64 so a
    silent promotion cannot hide behind dtype canonicalization), and
    declared frontier bitmaps must stay <= 8-bit (the hop loop is an
    HBM-bandwidth stream — doubling the element width halves hop rate);
  * donation where the runtime claims it: args declared donated
    (single-use frontier uploads) must carry ``donated_invars`` in the
    traced pjit — and nothing else may;
  * a bounded recompile-key space: distinct (runtime cache key,
    abstract signature) pairs across the buckets — i.e. jit retraces —
    must fit the spec's budget (the static form of
    tests/test_tpu_backend.py::TestRetraceBudget), and two buckets
    sharing a runtime cache key must share ONE compiled callable;
  * transfer accounting: per-dispatch h2d argument leaves and d2h
    output fetches must match tpu/runtime.py's declared DEVICE_PHASES
    row for the kernel's kind, whose span names must be SPAN_NAMES
    literals (PR 3 phase attribution).

Violations anchor to the factory's ``def`` line, so
``# nebulint: disable=jaxpr-audit`` on that line suppresses a justified
finding like any other check.

v4: this module is also the shared audit core for the mesh layer —
meshaudit.py re-traces every sharded family's ``mesh_instantiate``
buckets at real 2/4/8-way meshes and reuses ``_audit_inputs`` (packed
frontier layout), ``_audit_one_trace`` (loop callbacks, 64-bit
promotion) and ``_audit_donation`` (donation through shard_map) per
mesh size, adding the COLLECTIVE_MODEL inventory, the static ICI
traffic model, per-shard residency and the MESH_MODEL capacity
arithmetic on top.
"""
from __future__ import annotations

import os
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from .core import PackageContext, Violation

CHECK = "jaxpr-audit"

FORBIDDEN_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "outside_call", "host_callback"}
LOOP_PRIMS = {"while", "scan"}
WIDE_DTYPES = {"int64", "uint64", "float64", "complex128"}
FRONTIER_DTYPES = {"int8", "uint8", "bool"}


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


def _walk_eqns(jaxpr, in_loop: bool):
    """Yield (eqn, in_loop) over the whole nested jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        deeper = in_loop or eqn.primitive.name in LOOP_PRIMS
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub, deeper)


def _leaf_avals(args) -> List:
    import jax
    leaves, _ = jax.tree_util.tree_flatten(args)
    return leaves


def _sig_of(avals) -> Tuple:
    return tuple((tuple(a.shape), str(a.dtype))
                 for a in _leaf_avals(avals))


def _find_pjit(jaxpr):
    """The traced jit call (the equation that carries
    ``donated_invars``) — jax 0.9.0 names the primitive ``jit``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "jit":
            return eqn
    return None


# ------------------------------------------------------------ per spec
def _audit_one_trace(spec, closed, emit) -> None:
    """IR checks over one traced bucket."""
    jaxpr = closed.jaxpr
    seen_forbidden = set()
    wide_carries = set()
    for eqn, in_loop in _walk_eqns(jaxpr, False):
        name = eqn.primitive.name
        if name in FORBIDDEN_PRIMS and in_loop \
                and name not in seen_forbidden:
            seen_forbidden.add(name)
            emit(f"kernel '{spec.name}': host callback primitive "
                 f"'{name}' inside a traced loop body — one host "
                 f"round trip PER HOP")
        if name in LOOP_PRIMS:
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                dt = getattr(aval, "dtype", None)
                # rank-0 carries (the fori counter) are register
                # state, not HBM traffic — only ARRAY carries count
                if dt is not None and str(dt) in WIDE_DTYPES \
                        and getattr(aval, "shape", ()) != () \
                        and str(dt) not in wide_carries:
                    wide_carries.add(str(dt))
                    emit(f"kernel '{spec.name}': loop carry promoted "
                         f"to {dt} — persistent 64-bit state in the "
                         f"frontier loop doubles HBM traffic")
    for i, av in enumerate(closed.out_avals):
        if av.shape != () and str(av.dtype) in WIDE_DTYPES:
            emit(f"kernel '{spec.name}': output {i} is {av.dtype} — "
                 f"64-bit result transfer (indices and bitmaps must "
                 f"stay <= 32-bit)")


def _audit_inputs(spec, avals, emit) -> None:
    packed = getattr(spec, "packed", ())
    for idx, arg in enumerate(avals):
        for leaf in _leaf_avals(arg):
            dt = str(leaf.dtype)
            if dt in WIDE_DTYPES:
                emit(f"kernel '{spec.name}': argument {idx} is {dt} — "
                     f"the runtime would upload 64-bit data per "
                     f"dispatch")
            if idx in spec.frontier and dt not in FRONTIER_DTYPES:
                emit(f"kernel '{spec.name}': frontier argument {idx} "
                     f"is {dt}, not an int8/uint8/bool bitmap")
            if idx in packed and dt != "uint8":
                # the roofline arc's layout gate: a packed frontier
                # regressing to int8-per-lane octuples the hop's
                # gather traffic (docs/roofline.md)
                emit(f"kernel '{spec.name}': frontier argument {idx} "
                     f"is {dt}, not a bit-packed uint8 lane matrix — "
                     f"8x the frontier HBM traffic per hop")


def _leaf_bytes(avals) -> int:
    return sum(int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
               for a in _leaf_avals(avals))


def hbm_residency(spec, closed, avals):
    """Static peak-resident-bytes accounting for one traced bucket:
    mirror-resident inputs (everything not uploaded per dispatch) +
    per-dispatch uploads + outputs, minus what donation reuses (a
    donated single-use frontier's buffer becomes the output's).
    Returns (mirror, dispatch, out, peak) in bytes — the rows behind
    docs/static_analysis.md's HBM budget table."""
    mirror_b = dispatch_b = donated_b = 0
    for idx, arg in enumerate(avals):
        b = _leaf_bytes(arg)
        if idx in spec.dispatch:
            dispatch_b += b
        else:
            mirror_b += b
        if idx in spec.donate:
            donated_b += b
    out_b = sum(int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
                for a in closed.out_avals)
    peak = mirror_b + dispatch_b + max(0, out_b - donated_b)
    return mirror_b, dispatch_b, out_b, peak


def _audit_hbm(spec, closed, avals, key, hbm, emit) -> None:
    """Per-rung budget gate: the bucket's peak resident bytes must fit
    the declared per-device budget (runtime.HBM_MODEL) — the static
    form of 'this ladder rung serves without an HBM OOM'."""
    if not hbm:
        return
    budget = int(hbm.get("device_hbm_bytes") or 0)
    if budget <= 0:
        return
    _m, _d, _o, peak = hbm_residency(spec, closed, avals)
    if peak > budget:
        emit(f"kernel '{spec.name}': bucket {key!r} holds {peak} "
             f"bytes resident at dispatch (tables + frontier + "
             f"outputs, donation-adjusted), over the declared "
             f"per-device HBM budget {budget} — this ladder rung "
             f"cannot serve")


def hbm_ceiling_findings(hbm) -> List[str]:
    """The published-capacity arithmetic, proven on the declaration:
    edge_ceiling * table_bytes_per_edge must fit table_budget_bytes,
    which must fit the physical device_hbm_bytes.  Returns messages
    (empty = consistent) — the static proof behind the ~639M-edge
    claim (BASELINE.md 'Scale')."""
    out: List[str] = []
    if not hbm:
        return out
    edge_bytes = float(hbm.get("table_bytes_per_edge") or 0.0)
    ceiling = int(hbm.get("edge_ceiling") or 0)
    table_budget = int(hbm.get("table_budget_bytes") or 0)
    device = int(hbm.get("device_hbm_bytes") or 0)
    need = int(ceiling * edge_bytes)
    if need > table_budget:
        out.append(
            f"HBM_MODEL: the declared edge ceiling ({ceiling:,} edges "
            f"x {edge_bytes} B/edge = {need:,} bytes of device tables) "
            f"exceeds table_budget_bytes ({table_budget:,}) — the "
            f"published per-chip capacity claim no longer holds")
    if table_budget > device:
        out.append(
            f"HBM_MODEL: table_budget_bytes ({table_budget:,}) exceeds "
            f"device_hbm_bytes ({device:,}) — no headroom for XLA "
            f"scratch, frontier uploads or result buffers")
    return out


def _audit_d2h_bytes(spec, fx, closed, key, emit) -> None:
    """Reduction kernels (COUNT / LIMIT pushdown) declare a per-
    dispatch fetch byte bound; the traced output avals must fit it."""
    bound_fn = getattr(spec, "d2h_bytes_max", None)
    if bound_fn is None:
        return
    bound = int(bound_fn(fx)) if callable(bound_fn) else int(bound_fn)
    total = sum(int(np.prod(a.shape, dtype=np.int64)) * a.dtype.itemsize
                for a in closed.out_avals)
    if total > bound:
        emit(f"kernel '{spec.name}': bucket {key!r} fetches {total} "
             f"bytes per dispatch, over the declared reduction bound "
             f"{bound} — the reduced wire shape regressed")


def _audit_donation(spec, closed, avals, emit) -> None:
    eqn = _find_pjit(closed.jaxpr)
    if eqn is None:
        if spec.donate:
            emit(f"kernel '{spec.name}': declared donation "
                 f"{spec.donate} but the trace has no pjit call to "
                 f"carry it")
        return
    donated = tuple(eqn.params.get("donated_invars") or ())
    # arg index -> its leaf span in the flattened invars
    want = []
    for idx, arg in enumerate(avals):
        want.extend([idx in spec.donate] * len(_leaf_avals(arg)))
    if len(donated) < len(want):
        emit(f"kernel '{spec.name}': donation unauditable — traced "
             f"pjit has {len(donated)} invars for {len(want)} "
             f"argument leaves")
        return
    # closure consts prepend to the pjit invars and are never donated:
    # the declared args are the TRAILING leaves
    head, tail = donated[:-len(want)] if want else donated, \
        donated[-len(want):] if want else ()
    if any(head):
        emit(f"kernel '{spec.name}': donation drift — a closure "
             f"const is marked donated")
    if tuple(want) != tuple(tail):
        got = tuple(i for i, d in enumerate(tail) if d)
        emit(f"kernel '{spec.name}': donation drift — declared arg "
             f"indices {spec.donate}, traced donated leaves {got} "
             f"(single-use frontier buffers must be donated, cached "
             f"buffers must NOT be)")


def audit_specs(specs, fx, phases_table: Dict[str, dict],
                span_names: Tuple[str, ...],
                anchor, hbm: Optional[dict] = None
                ) -> Tuple[List[Violation], set]:
    """Pure audit core (fixture-testable): run every check over
    ``specs`` against the declared ``phases_table``; returns
    (violations, phase kinds actually used).  ``anchor(spec)`` ->
    (rel_path, line) places each violation.  ``hbm`` (the runtime's
    HBM_MODEL) arms the per-rung resident-bytes budget gate."""
    import jax

    out: List[Violation] = []

    def emitter(spec):
        rel, line = anchor(spec)

        def emit(msg: str) -> None:
            out.append(Violation(CHECK, rel, line, spec.name, msg))
        return emit

    used_kinds = set()
    for spec in specs:
        emit = emitter(spec)
        try:
            buckets = spec.instantiate(fx)
        except Exception as e:      # noqa: BLE001 — a factory that
            emit(f"kernel '{spec.name}': instantiation failed: "
                 f"{type(e).__name__}: {e}")
            continue                # can't build can't be audited
        # --- recompile-key space -----------------------------------
        key_to_fn: Dict = {}
        retraces = set()
        for key, fn, avals in buckets:
            retraces.add((key, _sig_of(avals)))
            prev = key_to_fn.setdefault(key, fn)
            if prev is not fn:
                emit(f"kernel '{spec.name}': two distinct compiled "
                     f"callables share runtime cache key {key!r} — "
                     f"the memo would serve the wrong program")
        if len(retraces) > spec.budget:
            emit(f"kernel '{spec.name}': {len(retraces)} distinct "
                 f"(cache key, signature) pairs across the shape "
                 f"buckets exceed the retrace budget {spec.budget} — "
                 f"unbounded recompile-key space")
        # --- per-bucket IR checks ----------------------------------
        traced = set()
        for key, fn, avals in buckets:
            tkey = (id(fn), _sig_of(avals))
            if tkey in traced:
                continue
            traced.add(tkey)
            try:
                with jax.enable_x64(True):
                    closed = jax.make_jaxpr(fn)(*avals)
            except Exception as e:  # noqa: BLE001 — untraceable =
                emit(f"kernel '{spec.name}': trace failed for bucket "
                     f"{key!r}: {type(e).__name__}: {e}")
                continue            # unauditable, and that's a finding
            _audit_inputs(spec, avals, emit)
            _audit_one_trace(spec, closed, emit)
            _audit_donation(spec, closed, avals, emit)
            _audit_d2h_bytes(spec, fx, closed, key, emit)
            _audit_hbm(spec, closed, avals, key, hbm, emit)
            # --- transfer accounting -------------------------------
            row = phases_table.get(spec.phase_kind)
            if row is None:
                emit(f"kernel '{spec.name}': phase kind "
                     f"'{spec.phase_kind}' missing from "
                     f"runtime.DEVICE_PHASES — dispatches of this "
                     f"kernel are unattributable")
                continue
            used_kinds.add(spec.phase_kind)
            h2d = sum(len(_leaf_avals(avals[i])) for i in spec.dispatch
                      if i < len(avals))
            if h2d != row["h2d"]:
                emit(f"kernel '{spec.name}': {h2d} per-dispatch "
                     f"h2d argument leaves, DEVICE_PHASES declares "
                     f"{row['h2d']}")
            d2h = len(closed.out_avals)
            if d2h != row["d2h"]:
                emit(f"kernel '{spec.name}': {d2h} device->host "
                     f"output fetches, DEVICE_PHASES declares "
                     f"{row['d2h']}")
            for ph in row["phases"]:
                if ph not in span_names:
                    emit(f"kernel '{spec.name}': DEVICE_PHASES names "
                         f"span '{ph}' which is not a SPAN_NAMES "
                         f"literal")
    return out, used_kinds


# ------------------------------------------------------------ package
def check_jaxpr_audit(ctx: PackageContext) -> List[Violation]:
    # only the real package carries the registry — fixture roots (the
    # lint self-tests) have no device path to audit
    host = None
    for m in ctx.modules:
        if m.rel.endswith("tpu/kernels.py") and "KERNEL_REGISTRY" in m.source:
            host = m
            break
    if host is None:
        return []

    from ...common.tracing import SPAN_NAMES
    from ...tpu import runtime as rt
    from ...tpu.kernels import AuditFixture, kernel_registry

    registry = kernel_registry()
    pkg_dir = os.path.dirname(os.path.dirname(
        os.path.abspath(host.path)))          # .../nebula_tpu
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
    out, used_kinds = audit_specs(registry.values(), fx,
                                  rt.DEVICE_PHASES, SPAN_NAMES, anchor,
                                  hbm=hbm)

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

    # dead declaration rows: a DEVICE_PHASES kind no registered kernel
    # dispatches under is drift in the other direction
    dead = sorted(set(rt.DEVICE_PHASES) - used_kinds)
    if dead:
        rel, line = _rt_anchor("DEVICE_PHASES")
        for kind in dead:
            out.append(Violation(
                CHECK, rel, line, "DEVICE_PHASES",
                f"declared phase kind '{kind}' has no registered "
                f"kernel — stale declaration"))
    # the published-capacity arithmetic, proven on the declaration
    for msg in hbm_ceiling_findings(hbm):
        rel, line = _rt_anchor("HBM_MODEL")
        out.append(Violation(CHECK, rel, line, "HBM_MODEL", msg))
    return out
