"""Traverse executors — GO / FETCH / YIELD / ORDER BY / LIMIT / GROUP BY /
set ops / pipes / variables / FIND [SHORTEST|ALL] PATH.

Capability parity with /root/reference/src/graph/ (SURVEY.md §2.2):
GoExecutor.cpp (step loop :334-399, dst back-tracking :407-431, second
prop wave :531-569, final eval :669-782), FetchVerticesExecutor,
FetchEdgesExecutor, YieldExecutor, OrderByExecutor, SetExecutor,
PipeExecutor, AssignmentExecutor. FIND/MATCH are principled stubs in the
reference (FindExecutor.cpp:19-21); here FIND SHORTEST/ALL PATH is fully
implemented (BASELINE.md config 3) and basic MATCH lowers onto the GO
planner (MatchExecutor below).

When ``ectx.tpu_runtime`` serves the current space, GO and FIND PATH
delegate the whole multi-hop loop to the device (tpu/runtime.py): frontier
expansion, filtering and dedup happen in one jitted program over the CSR
mirror instead of per-hop RPC fan-outs — same result sets.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from ...codec.rows import RowReader, RowSetReader
import time

from ...common.flags import flags
from ...common.status import ErrorCode
from ...filter.expressions import (AliasPropExpr, DestPropExpr,
                                   EdgeDstIdExpr, EdgeRankExpr, EdgeSrcIdExpr,
                                   EdgeTypeExpr, ExprContext, ExprError,
                                   Expression, FunctionCallExpr,
                                   InputPropExpr, PrimaryExpr,
                                   SourcePropExpr, VariablePropExpr,
                                   encode_expr)
from ...interface.common import schema_from_wire
from ...storage.device import TpuDecline
from ..interim import InterimResult
from ..parser import ast
from .base import ExecError, Executor

_AGG_FNS = {"count", "sum", "avg", "max", "min", "collect"}


# ---------------------------------------------------------------- helpers
flags.define(
    "flat_bound_mode", True,
    "GO final hops whose YIELD maps onto flat columns request the "
    "columnar getBound response (typed buffers, one batch decode) "
    "instead of per-vertex rowsets; off = always per-vertex (the "
    "per-row reference shape, kept as the universal fallback)")


def walk_expr(expr: Expression):
    yield expr
    for c in expr.children():
        yield from walk_expr(c)


def collect_prop_refs(exprs: List[Expression]):
    """-> (src {(tag,prop)}, edge {(alias,prop)}, dst {(tag,prop)},
          has_input, has_var)"""
    src: Set[Tuple[str, str]] = set()
    edge: Set[Tuple[str, str]] = set()
    dst: Set[Tuple[str, str]] = set()
    has_input = False
    has_var = False
    for e in exprs:
        for node in walk_expr(e):
            if isinstance(node, SourcePropExpr):
                src.add((node.tag, node.prop))
            elif isinstance(node, AliasPropExpr):
                edge.add((node.alias, node.prop))
            elif isinstance(node, DestPropExpr):
                dst.add((node.tag, node.prop))
            elif isinstance(node, InputPropExpr):
                has_input = True
            elif isinstance(node, VariablePropExpr):
                has_var = True
    return src, edge, dst, has_input, has_var


def default_col_name(expr: Expression) -> str:
    return str(expr)


def _flat_yield_specs(yield_cols, over_aliases: Dict[str, Tuple],
                      etypes: List[int]):
    """Map each YIELD column onto a flat-response column, or None when
    any column needs per-row evaluation (composite expressions, and
    alias props under a multi-edge OVER — those raise per-row on rows
    of the other edge types, which a column mapping can't reproduce)."""
    specs = []
    for c in yield_cols:
        e = c.expr
        if isinstance(e, EdgeDstIdExpr) and e.alias in over_aliases:
            specs.append(("dst",))
        elif isinstance(e, EdgeSrcIdExpr) and e.alias in over_aliases:
            specs.append(("src",))
        elif isinstance(e, EdgeRankExpr) and e.alias in over_aliases:
            specs.append(("rank",))
        elif isinstance(e, EdgeTypeExpr) and e.alias in over_aliases:
            specs.append(("type",))
        elif isinstance(e, AliasPropExpr) and e.alias in over_aliases \
                and len(etypes) == 1:
            specs.append(("prop", e.prop))
        else:
            return None
    return specs


def _flat_assemble(responses, specs, etype_to_alias: Dict[int, str],
                   distinct: bool):
    """Build the GO result columns straight from flat-response chunks
    (storage/processors.py _process_flat) — one numpy concatenate per
    column for the whole result set."""
    import numpy as np
    from ..interim import ColumnarRows, ConstCol, _col_tolist

    per_col: List[list] = [[] for _ in specs]
    total = 0
    for r in responses:
        for ch in r.get("flat", ()):
            n = int(ch["n"])
            if n == 0:
                continue
            total += n
            alias = etype_to_alias.get(int(ch["etype"]),
                                       str(ch["etype"]))
            for i, spec in enumerate(specs):
                if spec[0] in ("dst", "src", "rank"):
                    col = np.frombuffer(ch[spec[0]], "<i8")
                elif spec[0] == "type":
                    col = ConstCol(alias, n)
                else:
                    ps = ch["props"][spec[1]]
                    col = (np.frombuffer(ps["b"], ps["d"])
                           if "b" in ps else list(ps["l"]))
                per_col[i].append(col)

    cols: List[object] = []
    for chunks in per_col:
        if not chunks:
            cols.append([])
        elif len(chunks) == 1:
            cols.append(chunks[0])
        elif all(isinstance(c, np.ndarray) for c in chunks):
            cols.append(np.concatenate(chunks))
        else:
            merged: list = []
            for c in chunks:
                merged.extend(_col_tolist(c))
            cols.append(merged)
    rows = ColumnarRows(cols, total)
    if distinct:
        out, seen = [], set()
        for row in rows:
            key = tuple(row)
            if key not in seen:
                seen.add(key)
                out.append(row)
        return out
    return rows


class _RowCtx(ExprContext):
    """Mutable per-row binding used by GO final eval."""
    __slots__ = ("src_vals", "edge_vals", "dst_vals", "input_row",
                 "edge_meta")

    def __init__(self):
        super().__init__()
        self.src_vals: Dict[Tuple[str, str], object] = {}
        self.edge_vals: Dict[str, object] = {}
        self.dst_vals: Dict[Tuple[str, str], object] = {}
        self.input_row: Dict[str, object] = {}
        self.edge_meta: Dict[str, object] = {}

        def src_get(tag, prop):
            try:
                return self.src_vals[(tag, prop)]
            except KeyError:
                raise ExprError(f"$^.{tag}.{prop} unavailable")

        def alias_get(alias, prop):
            try:
                return self.edge_vals[prop]
            except KeyError:
                raise ExprError(f"{alias}.{prop} unavailable")

        def dst_get(tag, prop):
            try:
                return self.dst_vals[(tag, prop)]
            except KeyError:
                raise ExprError(f"$$.{tag}.{prop} unavailable")

        def input_get(prop):
            try:
                return self.input_row[prop]
            except KeyError:
                raise ExprError(f"$-.{prop} unavailable")

        self.get_src_tag_prop = src_get
        self.get_alias_prop = alias_get
        self.get_dst_tag_prop = dst_get
        self.get_input_prop = input_get
        self.get_variable_prop = lambda var, prop: input_get(prop)
        self.get_edge_dst_id = lambda a: self.edge_meta.get("dst")
        self.get_edge_src_id = lambda a: self.edge_meta.get("src")
        self.get_edge_rank = lambda a: self.edge_meta.get("rank")
        self.get_edge_type = lambda a: self.edge_meta.get("type_name")


# ================================================================== GO
class GoExecutor(Executor):
    NAME = "GoExecutor"
    # set by a pipe whose right side is a LIMIT: the cut takes the rows
    # as they come, so this GO keeps the order _distinct_rows gives
    cut_behind = False

    def execute(self) -> InterimResult:
        self.check_space_chosen()
        s: ast.GoSentence = self.sentence
        space = self.ectx.space_id()
        sm = self.ectx.schema_man

        start_vids = self.resolve_vids(s.from_)
        steps = s.step.steps

        # ---- OVER resolution ----------------------------------------
        # alias/name -> its signed etypes: one, or under BIDIRECT both
        try:
            over_aliases = s.over.resolve(sm, space)
        except KeyError as e:
            raise ExecError(f"unknown edge `{e.args[0]}'")
        etypes = sorted({et for ets in over_aliases.values() for et in ets})
        etype_to_alias = {et: a for a, ets in over_aliases.items()
                          for et in ets}

        # ---- YIELD defaults -----------------------------------------
        if s.yield_ is not None:
            yield_cols = s.yield_.columns
            distinct = s.yield_.distinct
        else:
            yield_cols = [ast.YieldColumn(expr=EdgeDstIdExpr(a),
                                          alias=f"{a}._dst")
                          for a in over_aliases]
            distinct = False

        exprs = [c.expr for c in yield_cols]
        where_expr = s.where.filter if s.where else None
        all_exprs = exprs + ([where_expr] if where_expr is not None else [])
        src_refs, edge_refs, dst_refs, has_input, has_var = \
            collect_prop_refs(all_exprs)

        # validate edge aliases
        for alias, prop in edge_refs:
            if alias not in over_aliases:
                raise ExecError(f"unknown edge alias `{alias}'")

        # ---- prop requests ------------------------------------------
        vertex_props: List[List] = []
        for tag, prop in sorted(src_refs):
            tr = sm.to_tag_id(space, tag)
            if not tr.ok():
                raise ExecError(f"unknown tag `{tag}'")
            vertex_props.append([tr.value(), prop])

        edge_props: Dict[int, List[str]] = {}
        for alias, prop in sorted(edge_refs):
            for et in over_aliases[alias]:
                edge_props.setdefault(et, []).append(prop)

        # ---- filter pushdown decision -------------------------------
        pushed: Optional[bytes] = None
        remnant: Optional[Expression] = None
        if where_expr is not None:
            w_src, w_edge, w_dst, w_inp, w_var = collect_prop_refs([where_expr])
            if not w_dst and not w_inp and not w_var:
                pushed = encode_expr(where_expr)
            else:
                remnant = where_expr

        # ---- TPU fast path ------------------------------------------
        rt = self.ectx.tpu_runtime
        router = self.ectx.router if flags.get("go_backend_router") \
            else None
        # upto is part of the family key: it runs different kernels
        # (cumulative-frontier) and costs differently than exact-depth
        # GO, so sharing a key would pollute the EWMA that routes the
        # exact queries
        upto = bool(s.step.upto and steps > 1)
        route_key = (space, tuple(sorted(set(etypes))), steps, upto)
        prefer_device = True
        if rt is not None and router is not None:
            prefer_device = router.choose(route_key) == "device"
        # pipe-reduction hint (PipeExecutor._try_reduced_pipe): only
        # meaningful on the device path — the CPU loop below ignores it
        # and serves full rows, which the fused pipe handles identically
        reduce = self.ectx.go_reduce
        if reduce is None and not self.cut_behind \
                and s.from_.ref is None and _go_distinct_dst(s):
            # the k-hop neighbourhood itself: the distinct destinations
            # of hop k are frontier k, so the device rides one hop more
            # and the frontier is the answer; the CPU loop below
            # de-duplicates the last hop's rows to the same set
            reduce = ("distinct",)
        if rt is not None and prefer_device \
                and rt.can_run_go(space, etypes, s, pushed, remnant,
                                  src_refs, dst_refs,
                                  has_input or has_var):
            t0 = time.perf_counter()
            try:
                out = rt.run_go(self, space, start_vids, etypes, steps,
                                etype_to_alias, yield_cols, distinct,
                                where_expr, edge_props, vertex_props,
                                upto=upto, reduce=reduce)
                if router is not None:
                    router.record(route_key, "device",
                                  time.perf_counter() - t0)
                return out
            except TpuDecline as d:
                # CPU loop below answers; a DEGRADED decline (device
                # runtime failure / open circuit breaker) additionally
                # surfaces on the response — completeness < 100 + a
                # warning — so clients see the cluster is serving in a
                # degraded mode, not silently (docs/durability.md)
                if getattr(d, "degraded", False):
                    self.ectx.completeness = min(self.ectx.completeness,
                                                 99)
                    self.ectx.warnings.append(
                        f"device path degraded, served by CPU fallback: "
                        f"{d}")
        t_cpu0 = time.perf_counter()

        # ---- input mapping (pipe/$var semantics) --------------------
        input_map: Dict[int, Dict[str, object]] = {}
        if has_input or has_var:
            src_interim = self.ectx.input
            if has_var:
                # FROM $var: the variable's interim is the input
                from ...filter.expressions import VariablePropExpr as _V
                if s.from_.ref is not None and isinstance(s.from_.ref, _V):
                    src_interim = self.ectx.variables.get(s.from_.ref.var)
            if src_interim is not None:
                key_col = None
                if s.from_.ref is not None and hasattr(s.from_.ref, "prop"):
                    key_col = s.from_.ref.prop
                    if key_col == "id" and src_interim.col_index("id") < 0:
                        key_col = src_interim.columns[0]
                else:
                    key_col = src_interim.columns[0]
                ki = src_interim.col_index(key_col)
                for row in src_interim.rows:
                    vid = row[ki]
                    if isinstance(vid, int) and vid not in input_map:
                        input_map[vid] = dict(zip(src_interim.columns, row))

        # ---- flat final hop eligibility -----------------------------
        # columnar end-to-end: the final hop's edges cross as typed
        # buffers and YIELD columns map straight onto them — no
        # per-vertex rowsets, no per-row decode/eval.  Any shape the
        # mapping can't reproduce bit-for-bit keeps the per-row path.
        flat_specs = None
        if flags.get("flat_bound_mode") \
                and pushed is None and remnant is None \
                and not vertex_props \
                and not dst_refs and not (has_input or has_var):
            flat_specs = _flat_yield_specs(yield_cols, over_aliases,
                                           etypes)

        # ---- step loop (stepOut / onStepOutResponse) ----------------
        # UPTO N STEPS: the final hop materializes edges out of the
        # UNION of the frontiers at depths 0..N-1 — "every neighbor
        # within N hops", each edge once.  (The reference parses UPTO
        # but refuses it — GoExecutor.cpp:121-123 `UPTO not supported
        # yet` — so this is defined capability beyond parity, not a
        # ported semantic.  `upto` was computed before the device fast
        # path above, which serves the same union via the
        # cumulative-frontier kernels.)
        union_ids: List[int] = []
        union_bt: Dict[int, int] = {}
        cur = start_vids
        backtracker: Dict[int, int] = {v: v for v in cur}
        final_resp = None
        for step in range(steps):
            if upto:
                for v in cur:
                    if v not in union_bt:
                        union_bt[v] = backtracker.get(v, v)
                        union_ids.append(v)
            is_final = step == steps - 1
            if upto and not is_final and not cur:
                is_final = True      # frontier exhausted early: the
                                     # union is complete, materialize
            if is_final and upto:
                cur = union_ids
                backtracker = union_bt
            if not cur:
                break
            resp = self.ectx.storage.get_neighbors(
                space, cur, etypes,
                filter_bytes=pushed if is_final else None,
                vertex_props=vertex_props if is_final else [],
                edge_props=edge_props if is_final else {},
                dst_only=not is_final,
                flat=is_final and flat_specs is not None)
            self.check_storage_resp(resp)
            if is_final:
                final_resp = resp
                break        # may have been promoted early under UPTO
            else:
                nxt: List[int] = []
                seen: Set[int] = set()
                new_bt: Dict[int, int] = {}
                import numpy as _np
                from ...native.batch import decode_rowset_column
                for r in resp.responses:
                    schemas = {int(k): schema_from_wire(v)
                               for k, v in r.get("edge_schemas",
                                                 {}).items()}
                    for v in r["vertices"]:
                        root = backtracker.get(v["id"], v["id"])
                        if "dsts" in v:
                            # lean dst_only response: one packed int64
                            # array per vertex (already deduped by
                            # (rank, dst) and TTL-checked server-side)
                            per_et = [_np.frombuffer(
                                v["dsts"], "<i8").tolist()]
                        else:
                            per_et = []
                            for et_s, blob in v["edges"].items():
                                schema = schemas[int(et_s)]
                                # one C call per rowset instead of a
                                # Python RowReader per row (reference
                                # decodes per row too:
                                # GoExecutor::getDstIdsFromResp:407-431)
                                col = decode_rowset_column(blob, schema,
                                                           "_dst")
                                per_et.append(
                                    col.tolist() if col is not None else
                                    [RowReader(raw, schema).get("_dst")
                                     for raw in RowSetReader(blob)])
                        for dsts in per_et:
                            for dst in dsts:
                                if dst not in seen:
                                    seen.add(dst)
                                    nxt.append(dst)
                                if dst not in new_bt:
                                    new_bt[dst] = root
                cur = nxt
                backtracker = new_bt

        def _rec(result: InterimResult) -> InterimResult:
            if router is not None:
                router.record(route_key, "cpu",
                              time.perf_counter() - t_cpu0)
            return result

        columns = [c.alias or default_col_name(c.expr) for c in yield_cols]
        if final_resp is None:
            return _rec(InterimResult(columns))

        # ---- flat final eval: columns straight from typed buffers ---
        flat_rows = None
        if flat_specs is not None \
                and any("flat" in r for r in final_resp.responses):
            flat_rows = _flat_assemble(
                [r for r in final_resp.responses if "flat" in r],
                flat_specs, etype_to_alias, distinct)
            if all("flat" in r for r in final_resp.responses):
                return _rec(InterimResult(columns, flat_rows))
            # mixed cluster (a host without the native lib answered
            # per-vertex): the flat hosts' rows must combine with the
            # per-row loop's — falling through with them dropped would
            # be silent wrong results

        # ---- second wave: dst props ---------------------------------
        dst_prop_map: Dict[int, Dict[Tuple[str, str], object]] = {}
        if dst_refs:
            from ...native.batch import decode_rowset_column
            dst_ids: Set[int] = set()
            for r in final_resp.responses:
                schemas = {int(k): schema_from_wire(v)
                           for k, v in r["edge_schemas"].items()}
                for v in r["vertices"]:
                    for et_s, blob in v["edges"].items():
                        schema = schemas[int(et_s)]
                        col = decode_rowset_column(blob, schema, "_dst")
                        if col is not None:
                            dst_ids.update(col.tolist())
                            continue
                        for raw in RowSetReader(blob):
                            dst_ids.add(RowReader(raw, schema).get("_dst"))
            dst_vp: List[List] = []
            for tag, prop in sorted(dst_refs):
                tr = sm.to_tag_id(space, tag)
                if not tr.ok():
                    raise ExecError(f"unknown tag `{tag}'")
                dst_vp.append([tr.value(), prop])
            presp = self.ectx.storage.get_props(space, sorted(dst_ids), dst_vp)
            names = [t for t, _ in sorted(dst_refs)]
            props = [p for _, p in sorted(dst_refs)]
            for r in presp.responses:
                if not r.get("vertex_schema"):
                    continue
                schema = schema_from_wire(r["vertex_schema"])
                for v in r["vertices"]:
                    reader = RowReader(v["vdata"], schema)
                    vals = {}
                    for (tag, prop) in sorted(dst_refs):
                        try:
                            vals[(tag, prop)] = reader.get(prop)
                        except KeyError:
                            pass
                    dst_prop_map[v["id"]] = vals

        # ---- final eval (processFinalResult) ------------------------
        from ...native.batch import decode_rowset_rows, \
            decode_rowsets_grouped
        ctx = _RowCtx()
        rows: List[List[object]] = []
        seen_rows: Set[Tuple] = set()
        if flat_rows is not None:         # mixed flat/per-vertex cluster
            rows = [list(r) for r in flat_rows]
            if distinct:
                seen_rows = {tuple(r) for r in rows}
        for r in final_resp.responses:
            vschema = (schema_from_wire(r["vertex_schema"])
                       if r.get("vertex_schema") else None)
            eschemas = {int(k): schema_from_wire(v)
                        for k, v in r["edge_schemas"].items()}
            # response-wide batch decode: per-vertex rowsets are tiny,
            # so the C calls amortize across the whole response
            grouped: Dict[int, Dict[int, List[dict]]] = {}
            for et in eschemas:
                vixs = [i for i, v in enumerate(r["vertices"])
                        if str(et) in v["edges"] or et in v["edges"]]
                blobs = [v["edges"].get(str(et), v["edges"].get(et))
                         for v in r["vertices"]
                         if str(et) in v["edges"] or et in v["edges"]]
                dec = decode_rowsets_grouped(blobs, eschemas[et])
                if dec is not None:
                    grouped[et] = dict(zip(vixs, dec))
            for vi, v in enumerate(r["vertices"]):
                src_vid = v["id"]
                ctx.src_vals = {}
                if vschema is not None and v["vdata"]:
                    reader = RowReader(v["vdata"], vschema)
                    for (tag, prop) in sorted(src_refs):
                        try:
                            ctx.src_vals[(tag, prop)] = reader.get(prop)
                        except KeyError:
                            pass
                root = backtracker.get(src_vid, src_vid)
                ctx.input_row = input_map.get(root, {})
                for et_s, blob in v["edges"].items():
                    et = int(et_s)
                    schema = eschemas[et]
                    alias = etype_to_alias.get(et, str(et))
                    # response-wide batch decode, then per-blob batch,
                    # then the per-row reader as semantic fallback
                    row_dicts = grouped.get(et, {}).get(vi)
                    if row_dicts is None:
                        row_dicts = decode_rowset_rows(blob, schema)
                    if row_dicts is None:
                        row_dicts = (RowReader(raw, schema).to_dict()
                                     for raw in RowSetReader(blob))
                    for edge_vals in row_dicts:
                        ctx.edge_vals = edge_vals
                        dst = ctx.edge_vals.get("_dst")
                        ctx.edge_meta = {"dst": dst, "src": src_vid,
                                         "rank": ctx.edge_vals.get("_rank", 0),
                                         "type_name": alias}
                        ctx.dst_vals = dst_prop_map.get(dst, {})
                        try:
                            if remnant is not None and not remnant.eval(ctx):
                                continue
                            row = [c.expr.eval(ctx) for c in yield_cols]
                        except ExprError as e:
                            raise ExecError(str(e))
                        if distinct:
                            key = tuple(row)
                            if key in seen_rows:
                                continue
                            seen_rows.add(key)
                        rows.append(row)
        return _rec(InterimResult(columns, rows))


# ================================================================== FETCH
class FetchVerticesExecutor(Executor):
    NAME = "FetchVerticesExecutor"

    def execute(self) -> InterimResult:
        self.check_space_chosen()
        s: ast.FetchVerticesSentence = self.sentence
        space = self.ectx.space_id()
        sm = self.ectx.schema_man
        vids = self.resolve_vids(s.from_)

        vertex_props: List[List] = []
        if s.tag != "*":
            tr = sm.to_tag_id(space, s.tag)
            if not tr.ok():
                raise ExecError(f"unknown tag `{s.tag}'")
            tag_id = tr.value()
            schema = sm.get_tag_schema(space, tag_id)
            if s.yield_ is not None:
                # request only referenced props
                refs, _, _, _, _ = collect_prop_refs(
                    [c.expr for c in s.yield_.columns])
                props = sorted({p for t, p in refs if t == s.tag})
                vertex_props = [[tag_id, p] for p in props]
            else:
                vertex_props = [[tag_id, p] for p in schema.names()]

        resp = self.ectx.storage.get_props(space, vids, vertex_props)
        self.check_storage_resp(resp)

        if s.yield_ is not None:
            yield_cols = s.yield_.columns
        else:
            if s.tag == "*":
                # columns discovered from response schema
                yield_cols = None
            else:
                schema = sm.get_tag_schema(space, sm.to_tag_id(space, s.tag).value())
                yield_cols = [
                    ast.YieldColumn(expr=AliasPropExpr(s.tag, p),
                                    alias=f"{s.tag}.{p}")
                    for p in schema.names()]

        rows: List[List[object]] = []
        if yield_cols is None:
            columns = ["VertexID"]
            col_set: List[str] = []
            decoded = []
            for r in resp.responses:
                if not r.get("vertex_schema"):
                    continue
                schema = schema_from_wire(r["vertex_schema"])
                for v in r["vertices"]:
                    d = RowReader(v["vdata"], schema).to_dict()
                    decoded.append((v["id"], d))
                    for k in d:
                        if k not in col_set:
                            col_set.append(k)
            columns += col_set
            for vid, d in decoded:
                rows.append([vid] + [d.get(c) for c in col_set])
            return InterimResult(columns, rows)

        columns = ["VertexID"] + [c.alias or default_col_name(c.expr)
                                  for c in yield_cols]
        ctx = _RowCtx()
        for r in resp.responses:
            if not r.get("vertex_schema"):
                continue
            schema = schema_from_wire(r["vertex_schema"])
            for v in r["vertices"]:
                reader = RowReader(v["vdata"], schema)
                vals = reader.to_dict()
                # expose as alias (tag.prop), $^ and plain
                ctx.edge_vals = vals
                ctx.src_vals = {(s.tag, k): val for k, val in vals.items()}
                ctx.input_row = vals
                try:
                    row = [v["id"]] + [c.expr.eval(ctx) for c in yield_cols]
                except ExprError as e:
                    raise ExecError(str(e))
                rows.append(row)
        return InterimResult(columns, rows)


class FetchEdgesExecutor(Executor):
    NAME = "FetchEdgesExecutor"

    def execute(self) -> InterimResult:
        self.check_space_chosen()
        s: ast.FetchEdgesSentence = self.sentence
        space = self.ectx.space_id()
        sm = self.ectx.schema_man
        er = sm.to_edge_type(space, s.edge)
        if not er.ok():
            raise ExecError(f"unknown edge `{s.edge}'")
        etype = er.value()
        schema = sm.get_edge_schema(space, etype)

        keys: List[Tuple[int, int, int, int]] = []
        if s.ref is not None:
            src_ref, dst_ref = s.ref
            src_col = getattr(src_ref, "prop", None)
            dst_col = getattr(dst_ref, "prop", None)
            inp = self.ectx.input
            if isinstance(src_ref, VariablePropExpr):
                inp = self.ectx.variables.get(src_ref.var)
            if inp is not None:
                si, di = inp.col_index(src_col), inp.col_index(dst_col)
                if si < 0 or di < 0:
                    raise ExecError(f"no such input columns "
                                    f"`{src_col}'/`{dst_col}'")
                for row in inp.rows:
                    keys.append((row[si], etype, 0, row[di]))
        else:
            for k in s.keys:
                keys.append((self.eval_const(k.src), etype, k.rank,
                             self.eval_const(k.dst)))

        props = None
        if s.yield_ is not None:
            _, edge_refs, _, _, _ = collect_prop_refs(
                [c.expr for c in s.yield_.columns])
            props = sorted({p for _a, p in edge_refs})
        resp = self.ectx.storage.get_edge_props(space, keys, props)
        self.check_storage_resp(resp)

        if s.yield_ is not None:
            yield_cols = s.yield_.columns
        else:
            yield_cols = [ast.YieldColumn(expr=AliasPropExpr(s.edge, p),
                                          alias=f"{s.edge}.{p}")
                          for p in schema.names()]
        columns = ([f"{s.edge}._src", f"{s.edge}._dst", f"{s.edge}._rank"] +
                   [c.alias or default_col_name(c.expr) for c in yield_cols])
        ctx = _RowCtx()
        rows = []
        for r in resp.responses:
            for et_s, blob in r.get("edges", {}).items():
                rschema = schema_from_wire(r["edge_schemas"][int(et_s)])
                for raw in RowSetReader(blob):
                    vals = RowReader(raw, rschema).to_dict()
                    ctx.edge_vals = vals
                    src = vals.get("_src")
                    ctx.edge_meta = {"dst": vals.get("_dst"), "src": src,
                                     "rank": vals.get("_rank", 0),
                                     "type_name": s.edge}
                    try:
                        row = ([src, vals.get("_dst"), vals.get("_rank", 0)] +
                               [c.expr.eval(ctx) for c in yield_cols])
                    except ExprError as e:
                        raise ExecError(str(e))
                    rows.append(row)
        return InterimResult(columns, rows)


# ================================================================== YIELD
class YieldExecutor(Executor):
    NAME = "YieldExecutor"

    def execute(self) -> InterimResult:
        s: ast.YieldSentence = self.sentence
        yield_cols = s.yield_.columns
        columns = [c.alias or default_col_name(c.expr) for c in yield_cols]
        exprs = [c.expr for c in yield_cols]
        _, _, _, has_input, has_var = collect_prop_refs(
            exprs + ([s.where.filter] if s.where else []))

        ctx = _RowCtx()
        rows: List[List[object]] = []
        inp = self.ectx.input
        has_agg = any(isinstance(e, FunctionCallExpr) and
                      e.name.lower() in _AGG_FNS for e in exprs)
        if has_agg and inp is not None:
            return _aggregate_rows(self, inp, yield_cols, s.where)
        if (has_input or has_var) and inp is not None:
            for i in range(len(inp)):
                ctx.input_row = inp.row_dict(i)
                try:
                    if s.where is not None and not s.where.filter.eval(ctx):
                        continue
                    rows.append([e.eval(ctx) for e in exprs])
                except ExprError as e:
                    raise ExecError(str(e))
        else:
            try:
                if s.where is None or s.where.filter.eval(ctx):
                    rows.append([self.eval_const(e) for e in exprs])
            except ExprError as e:
                raise ExecError(str(e))
        result = InterimResult(columns, rows)
        if s.yield_.distinct:
            return _distinct(result)
        return result


def _distinct(r: InterimResult) -> InterimResult:
    seen = set()
    rows = []
    for row in r.rows:
        k = tuple(row)
        if k not in seen:
            seen.add(k)
            rows.append(row)
    return InterimResult(r.columns, rows)


def _aggregate_rows(ex: Executor, inp: InterimResult,
                    yield_cols: List[ast.YieldColumn],
                    where: Optional[ast.WhereClause],
                    group_exprs: Optional[List[Expression]] = None) -> InterimResult:
    """Shared GROUP BY / aggregate-YIELD engine."""
    ctx = _RowCtx()
    groups: Dict[Tuple, List[int]] = {}
    order: List[Tuple] = []
    for i in range(len(inp)):
        ctx.input_row = inp.row_dict(i)
        try:
            if where is not None and not where.filter.eval(ctx):
                continue
            if group_exprs:
                key = tuple(g.eval(ctx) for g in group_exprs)
            else:
                key = ()
        except ExprError as e:
            raise ExecError(str(e))
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(i)

    columns = [c.alias or default_col_name(c.expr) for c in yield_cols]
    rows = []
    for key in order:
        idxs = groups[key]
        row = []
        for c in yield_cols:
            e = c.expr
            if isinstance(e, FunctionCallExpr) and e.name.lower() in _AGG_FNS:
                fname = e.name.lower()
                vals = []
                for i in idxs:
                    ctx.input_row = inp.row_dict(i)
                    if not e.args:
                        vals.append(1)
                    else:
                        try:
                            vals.append(e.args[0].eval(ctx))
                        except ExprError as ee:
                            raise ExecError(str(ee))
                if fname == "count":
                    row.append(len(vals))
                elif fname == "sum":
                    row.append(sum(vals) if vals else 0)
                elif fname == "avg":
                    row.append(sum(vals) / len(vals) if vals else 0.0)
                elif fname == "max":
                    row.append(max(vals) if vals else None)
                elif fname == "min":
                    row.append(min(vals) if vals else None)
                elif fname == "collect":
                    row.append(vals)
            else:
                ctx.input_row = inp.row_dict(idxs[0])
                try:
                    row.append(e.eval(ctx))
                except ExprError as ee:
                    raise ExecError(str(ee))
        rows.append(row)
    return InterimResult(columns, rows)


class GroupByExecutor(Executor):
    NAME = "GroupByExecutor"

    def execute(self) -> InterimResult:
        s: ast.GroupBySentence = self.sentence
        inp = self.ectx.input
        if inp is None:
            raise ExecError("GROUP BY must follow a pipe")
        if s.yield_ is None:
            raise ExecError("GROUP BY requires YIELD")
        return _aggregate_rows(self, inp, s.yield_.columns, None,
                               [c.expr for c in s.group_cols])


# ================================================================== ORDER/LIMIT
class OrderByExecutor(Executor):
    NAME = "OrderByExecutor"

    def execute(self) -> InterimResult:
        s: ast.OrderBySentence = self.sentence
        inp = self.ectx.input
        if inp is None:
            raise ExecError("ORDER BY must follow a pipe")
        ctx = _RowCtx()

        def sort_key_for(i: int):
            ctx.input_row = inp.row_dict(i)
            key = []
            for f in s.factors:
                try:
                    v = f.expr.eval(ctx)
                except ExprError as e:
                    raise ExecError(str(e))
                key.append(v)
            return key

        idxs = list(range(len(inp)))
        # stable multi-factor sort honoring per-factor direction
        for fi in range(len(s.factors) - 1, -1, -1):
            f = s.factors[fi]

            def one_key(i, fi=fi):
                ctx.input_row = inp.row_dict(i)
                try:
                    v = s.factors[fi].expr.eval(ctx)
                except ExprError as e:
                    raise ExecError(str(e))
                # mixed types: sort by (type rank, value)
                tr = 0 if isinstance(v, bool) else \
                    1 if isinstance(v, (int, float)) else 2
                return (tr, v)

            idxs.sort(key=one_key, reverse=not f.ascending)
        return InterimResult(inp.columns, [inp.rows[i] for i in idxs])


class LimitExecutor(Executor):
    NAME = "LimitExecutor"

    def execute(self) -> InterimResult:
        s: ast.LimitSentence = self.sentence
        inp = self.ectx.input
        if inp is None:
            raise ExecError("LIMIT must follow a pipe")
        lo = s.offset
        hi = len(inp.rows) if s.count < 0 else lo + s.count
        return InterimResult(inp.columns, inp.rows[lo:hi])


# ================================================================== SET/PIPE
class SetExecutor(Executor):
    NAME = "SetExecutor"

    def execute(self) -> InterimResult:
        from . import make_executor, traced_execute
        s: ast.SetSentence = self.sentence
        left = traced_execute(make_executor(s.left, self.ectx),
                              self.ectx)
        right = traced_execute(make_executor(s.right, self.ectx),
                               self.ectx)
        left = left or InterimResult([])
        right = right or InterimResult([])
        if left.columns and right.columns and \
                len(left.columns) != len(right.columns):
            raise ExecError("set operand column counts differ: "
                            f"{left.columns} vs {right.columns}")
        columns = left.columns or right.columns
        if s.op == ast.SetOpKind.UNION:
            rows = left.rows + right.rows
            result = InterimResult(columns, rows)
            return _distinct(result) if s.distinct else result
        lset = {tuple(r) for r in left.rows}
        rset = {tuple(r) for r in right.rows}
        if s.op == ast.SetOpKind.INTERSECT:
            keep = lset & rset
            return InterimResult(columns,
                                 [r for r in left.rows if tuple(r) in keep])
        keep = lset - rset
        return InterimResult(columns,
                             [r for r in left.rows if tuple(r) in keep])


def _go_plain(go) -> bool:
    """A GO that cannot raise per-row errors: meta-only YIELD columns
    (_dst/_src/_rank/_type never error), no WHERE, no UPTO."""
    if go.where is not None:
        return False
    if getattr(go.step, "upto", False) and go.step.steps > 1:
        return False
    return go.yield_ is None or all(
        isinstance(c.expr, (EdgeDstIdExpr, EdgeSrcIdExpr, EdgeRankExpr,
                            EdgeTypeExpr))
        for c in go.yield_.columns)


def _go_distinct_dst(go) -> bool:
    """The one DISTINCT the device answers: ``YIELD DISTINCT <e>._dst``
    as a plain GO's only column, over one edge name: forwards,
    REVERSELY or BIDIRECT, the k-th frontier is the k-th frontier
    whatever tables the hops read.  Its rows are the next frontier:
    on their own the k-hop neighbourhood
    (GoExecutor: reduce "distinct"), piped into a bare COUNT(*) its
    size (_go_reduce_shape: "count_distinct")."""
    return go.yield_ is not None and go.yield_.distinct \
        and _go_plain(go) \
        and len(go.yield_.columns) == 1 \
        and isinstance(go.yield_.columns[0].expr, EdgeDstIdExpr) \
        and not go.over.is_all and len(go.over.edges) == 1


def _go_reduce_shape(left, right):
    """-> ("limit", cap) | ("count", col_name) |
    ("count_distinct", col_name) | None: the GO|LIMIT and
    GO|YIELD COUNT(*) pipe shapes whose result the device can REDUCE
    before the fetch (ROADMAP item 2 pushdown).  The gate is
    conservative: the left GO must be unable to raise per-row errors
    (_go_plain), because a truncated/counted result would skip
    rows whose evaluation the CPU path would have failed on.  A
    DISTINCT is reduced in one shape only (_go_distinct_dst), piped
    into the bare COUNT(*) — the k-hop neighbourhood count, whose
    distinct destinations are the next frontier, so the device rides
    one hop more and counts it.  Every other DISTINCT stays
    unreduced."""
    if not isinstance(left, ast.GoSentence) or not _go_plain(left):
        return None
    distinct = left.yield_ is not None and left.yield_.distinct
    if distinct and not _go_distinct_dst(left):
        return None
    if isinstance(right, ast.LimitSentence):
        if distinct or right.count < 0 or right.offset < 0:
            return None
        return ("limit", right.offset + right.count)
    if isinstance(right, ast.YieldSentence):
        if right.where is not None or right.yield_.distinct:
            return None
        cols = right.yield_.columns
        if len(cols) != 1:
            return None
        e = cols[0].expr
        if isinstance(e, FunctionCallExpr) and e.name.lower() == "count" \
                and not e.args:
            return ("count_distinct" if distinct else "count",
                    cols[0].alias or default_col_name(e))
    return None


class PipeExecutor(Executor):
    NAME = "PipeExecutor"

    def execute(self) -> Optional[InterimResult]:
        # both halves run via traced_execute so a PROFILE of a piped
        # statement shows each side as its own span with the real
        # rows_in it consumed (the left half may itself be fed by an
        # enclosing pipe's input)
        from . import make_executor, traced_execute
        s: ast.PipedSentence = self.sentence
        fused = self._try_reduced_pipe(s)
        if fused is not None:
            return fused
        left_ex = make_executor(s.left, self.ectx)
        if isinstance(s.right, ast.LimitSentence):
            left_ex.cut_behind = True
        left = traced_execute(left_ex, self.ectx)
        saved = self.ectx.input
        self.ectx.input = left if left is not None else InterimResult([])
        try:
            return traced_execute(make_executor(s.right, self.ectx),
                                  self.ectx)
        finally:
            self.ectx.input = saved

    def _try_reduced_pipe(self, s) -> Optional[InterimResult]:
        """GO|LIMIT / GO|YIELD COUNT(*) fusion: run the left GO with a
        reduction hint so the device fetch carries only the
        surviving/reduced rows, then finish the pipe inline (a k-hop
        neighbourhood count, YIELD DISTINCT <e>._dst | YIELD COUNT(*),
        comes back as one number counted on the device, like a COUNT;
        from the CPU path its de-duplicated rows arrive and their
        number is the same).  When the
        GO served on the CPU path instead (decline, has_input, router)
        the hint was ignored and the FULL rows arrive — the same
        slice/count below is then plain pipe semantics.  Live writes
        no longer gate the hint: committed deltas ABSORB into the
        mirror generation before dispatch (tpu/runtime.py,
        docs/durability.md), so the device-side reduction always
        folds a write-fresh table — the PR 8 "live delta forces
        mirror_full" escape is gone.  COUNT values
        are route-independent; a device-cut LIMIT may pick a DIFFERENT
        (deterministic) subset than the CPU path's first rows — the
        unordered cut LIMIT-without-ORDER-BY permits (row count and
        membership in the full result always hold; docs/roofline.md)."""
        from . import make_executor, traced_execute
        shape = _go_reduce_shape(s.left, s.right)
        if shape is None or self.ectx.tpu_runtime is None:
            return None
        kind = shape[0]
        saved_hint = self.ectx.go_reduce
        self.ectx.go_reduce = ("limit", int(shape[1])) \
            if kind == "limit" else (kind,)
        try:
            left = traced_execute(make_executor(s.left, self.ectx),
                                  self.ectx)
        finally:
            self.ectx.go_reduce = saved_hint
        left = left if left is not None else InterimResult([])
        if kind == "limit":
            lo = s.right.offset
            hi = lo + s.right.count
            return InterimResult(left.columns, left.rows[lo:hi])
        if getattr(left, "reduced", None) == (kind,):
            total = int(left.rows[0][0]) if left.rows else 0
        else:
            total = len(left.rows)
        # CPU-path parity: YIELD COUNT(*) over ZERO input rows yields
        # zero groups, hence zero rows (_aggregate_rows)
        return InterimResult([shape[1]], [[total]] if total else [])


class AssignmentExecutor(Executor):
    NAME = "AssignmentExecutor"

    def execute(self) -> None:
        from . import make_executor, traced_execute
        s: ast.AssignmentSentence = self.sentence
        result = traced_execute(make_executor(s.sentence, self.ectx),
                                self.ectx)
        self.ectx.variables.add(s.var, result or InterimResult([]))
        return None


# ================================================================== PATH
class FindPathExecutor(Executor):
    """FIND SHORTEST|ALL PATH — layered BFS with parent tracking over the
    getNeighbors seam (CPU path; the TPU runtime runs the same search as a
    jitted batched BFS over the ELL tables).  At most
    ``find_path_max_paths`` rows (the flag), the first under the order
    tpu/runtime.py states above its path walk: targets by ascending
    id, each vertex's parent edges by ascending (the vertex before,
    SIGNED edge type, rank), depth first.  The OVER set is walked by
    its signs: forwards (+t), REVERSELY (-t: every stored edge from its
    far end) or BIDIRECT (both, -t before +t), the same signed types
    GO asks getNeighbors for."""

    NAME = "FindPathExecutor"

    def execute(self) -> InterimResult:
        self.check_space_chosen()
        s: ast.FindPathSentence = self.sentence
        space = self.ectx.space_id()
        sm = self.ectx.schema_man
        srcs = self.resolve_vids(s.from_)
        dsts = self.resolve_vids(s.to)
        # the OVER set's SIGNED edge types, as GO resolves them: +t
        # walks a stored edge along it, -t (REVERSELY) against it,
        # BIDIRECT names both; a step crossed against its direction is
        # printed under the signed name (`<-knows,0>`)
        try:
            over = s.over.resolve(sm, space)
        except KeyError as e:
            raise ExecError(f"unknown edge `{e.args[0]}'")
        etypes = sorted({et for ets in over.values() for et in ets})
        max_steps = s.upto.steps if s.upto else 5
        etype_names = {
            et: ("-" if et < 0 else "")
            + (sm.edge_name(space, abs(et)) or str(abs(et)))
            for et in etypes}

        rt = self.ectx.tpu_runtime
        if rt is not None and rt.can_run_path(space, etypes):
            try:
                return rt.run_find_path(self, space, srcs, dsts, etypes,
                                        max_steps, s.shortest, etype_names)
            except TpuDecline as d:
                # CPU BFS below answers; degraded declines surface
                # (same contract as the GO executor above)
                if getattr(d, "degraded", False):
                    self.ectx.completeness = min(self.ectx.completeness,
                                                 99)
                    self.ectx.warnings.append(
                        f"device path degraded, served by CPU fallback: "
                        f"{d}")

        # BFS recording predecessor edges. SHORTEST keeps only edges that
        # advance depth (depth-layered DAG); ALL keeps every discovered
        # edge and reconstructs with cycle-avoiding DFS.
        src_set = set(srcs)
        parents: Dict[int, List[Tuple[int, int, int]]] = {}
        depth_of: Dict[int, int] = {v: 0 for v in srcs}
        frontier = list(srcs)
        target_set = set(dsts)
        unfound = set(dsts) - src_set
        for depth in range(1, max_steps + 1):
            if not frontier:
                break
            if not unfound and s.shortest:
                break  # every target reached at its shortest depth
            resp = self.ectx.storage.get_neighbors(space, frontier, etypes)
            self.check_storage_resp(resp)
            from ...native.batch import decode_rowset_column
            nxt: List[int] = []
            for r in resp.responses:
                schemas = {int(k): schema_from_wire(v)
                           for k, v in r["edge_schemas"].items()}
                for v in r["vertices"]:
                    src = v["id"]
                    for et_s, blob in v["edges"].items():
                        et = int(et_s)
                        schema = schemas[et]
                        dcol = decode_rowset_column(blob, schema, "_dst")
                        rcol = (decode_rowset_column(blob, schema,
                                                     "_rank")
                                if dcol is not None else None)
                        if dcol is not None and rcol is not None:
                            pairs = zip(dcol.tolist(), rcol.tolist())
                        else:
                            pairs = ((row.get("_dst"),
                                      row.get("_rank", 0))
                                     for row in
                                     (RowReader(raw, schema)
                                      for raw in RowSetReader(blob)))
                        for dst, rank in pairs:
                            if dst not in depth_of:
                                depth_of[dst] = depth
                                nxt.append(dst)
                            if s.shortest:
                                if depth_of[dst] == depth:
                                    parents.setdefault(dst, []).append(
                                        (src, et, rank))
                            else:
                                parents.setdefault(dst, []).append(
                                    (src, et, rank))
                            if dst in target_set:
                                unfound.discard(dst)
            frontier = nxt

        # the cut at find_path_max_paths is a rule over ids, not over the order
        # the responses came in
        for edges_in in parents.values():
            edges_in.sort()
        paths: List[str] = []
        max_paths = int(flags.get("find_path_max_paths"))

        def fmt(chain: List, start: int) -> str:
            parts = [str(start)]
            for (etype, rank, node) in chain:
                parts.append(f"<{etype_names.get(etype, etype)},{rank}>")
                parts.append(str(node))
            return " ".join(parts)

        def build_shortest(v: int, acc: List, depth: int):
            if len(paths) >= max_paths:
                return
            if depth == 0:
                if v in src_set:
                    paths.append(fmt(acc, v))
                return
            for (prev, et, rank) in parents.get(v, []):
                if depth_of.get(prev, -1) == depth - 1:
                    build_shortest(prev, [(et, rank, v)] + acc, depth - 1)

        def build_all(v: int, acc: List, visited: Set[int]):
            if len(paths) >= max_paths or len(acc) > max_steps:
                return
            if v in src_set and acc:
                paths.append(fmt(acc, v))
                # keep exploring: longer paths through v may also exist
            for (prev, et, rank) in parents.get(v, []):
                if prev not in visited:
                    build_all(prev, [(et, rank, v)] + acc, visited | {prev})

        for d in sorted(target_set):
            if s.shortest:
                if d in depth_of and depth_of[d] > 0:
                    build_shortest(d, [], depth_of[d])
            else:
                build_all(d, [], {d})
        return InterimResult(["path"], [[p] for p in sorted(paths)])


class FindExecutor(Executor):
    """Reference parity: FIND is parsed but unsupported
    (FindExecutor.cpp:19-21)."""

    NAME = "FindExecutor"

    def execute(self):
        raise ExecError("FIND is not supported yet; use FIND SHORTEST PATH",
                        ErrorCode.E_UNSUPPORTED)


class MatchExecutor(Executor):
    """Basic MATCH, lowered onto the GO planner — strictly beyond the
    reference, whose MatchExecutor rejects everything
    (MatchExecutor.cpp:19-21).

    Supported shapes: ``MATCH (a[:tag])-[e:etype]->(b[:tag])
    WHERE id(a) == <vid> [AND <preds>] RETURN <exprs>`` plus the
    reverse pattern ``(a)<-[e:etype]-(b)``, anchored on EITHER pattern
    vertex — pattern variables rewrite into GO's property spaces
    (``id(<start var>)``/``id(<other>)`` → ``etype._src``/
    ``etype._dst``, ``e.p`` → ``etype.p``, ``<start>.p`` →
    ``$^.tag.p``, ``<other>.p`` → ``$$.tag.p``), the ``id(...)``
    anchor conjuncts become the FROM list, and the lowered GoSentence
    runs through GoExecutor — batching, the device backend, and result
    semantics all ride along.  Anchoring the edge's HEAD vertex lowers
    onto ``OVER e REVERSELY`` (the engine's ``_src``/``$^`` are
    traversal-relative, so one rewrite rule serves both directions).
    Labels resolve property namespaces only (tag-presence is not an
    implicit filter); everything outside the shape errors
    E_UNSUPPORTED with the raw text preserved."""

    NAME = "MatchExecutor"

    def execute(self):
        from ..parser.parser import _Parser, ParseError
        from ..parser.lexer import LexError, tokenize

        s = self.sentence
        if s.a_var is None:
            raise ExecError(
                "MATCH supports the basic (a)-[e:etype]->(b) / "
                "(a)<-[e:etype]-(b) pattern with an id() anchor; "
                "got: " + s.raw,
                ErrorCode.E_UNSUPPORTED)
        if not s.e_label:
            raise ExecError(
                "MATCH needs a typed edge pattern [e:etype]",
                ErrorCode.E_UNSUPPORTED)
        alias = s.e_label

        # variable-length bounds: [e:t*N] = exact N hops, [e:t*1..N] =
        # UPTO N (union of depths 1..N — GO UPTO semantics); other
        # lower bounds have no GO lowering.  Results use GO's WALK
        # semantics (reachable by an N-edge walk; edges may repeat on
        # cycles, frontier dedup collapses path multiplicity) — nGQL's
        # established meaning, NOT Cypher's edge-distinct trails
        # (docs/STATUS.md states this scope)
        hop_min, hop_max = s.hop_min, s.hop_max
        if hop_min < 1 or hop_max < hop_min:
            raise ExecError(
                f"bad hop range *{hop_min}..{hop_max}",
                ErrorCode.E_UNSUPPORTED)
        if hop_min not in (1, hop_max):
            raise ExecError(
                f"*{hop_min}..{hop_max}: only *N (exact) and *1..N "
                f"(up to) variable-length patterns lower onto the GO "
                f"planner", ErrorCode.E_UNSUPPORTED)
        steps = hop_max
        upto = hop_min == 1 and hop_max > 1

        pat_vars = {s.a_var, s.b_var, s.e_var}
        labels = {s.a_var: s.a_label, s.b_var: s.b_label}

        def rewrite(text: str, what: str, start_var: str) -> str:
            """Token-level pattern-variable substitution — operating on
            TOKENS (not raw text) so string literals that happen to
            spell a variable name are never touched."""
            try:
                toks = tokenize(text)
            except LexError as e:
                raise ExecError(f"MATCH {what}: {e}")
            out: List[str] = []
            i = 0

            def lexeme(j: int) -> str:
                end = toks[j + 1].pos if j + 1 < len(toks) else len(text)
                return text[toks[j].pos:end]

            def is_id(j: int, val: Optional[str] = None) -> bool:
                t = toks[j]
                return t.type == "ID" and (val is None or t.value == val)

            def sym(j: int, v: str) -> bool:
                t = toks[j]
                return t.type == "SYM" and t.value == v

            while toks[i].type != "EOF":
                # id(<var>) — case-insensitive like every nGQL keyword
                if is_id(i) and toks[i].value.lower() == "id" \
                        and sym(i + 1, "(") \
                        and is_id(i + 2) and sym(i + 3, ")") \
                        and toks[i + 2].value in pat_vars:
                    v = toks[i + 2].value
                    if v == s.e_var:
                        raise ExecError(
                            f"id({v}): {v} is the edge variable; edges "
                            f"have no vertex id")
                    out.append(f"{alias}._src " if v == start_var
                               else f"{alias}._dst ")
                    i += 4
                    continue
                # <var>.<prop>
                if is_id(i) and toks[i].value in pat_vars \
                        and sym(i + 1, ".") and is_id(i + 2):
                    v, prop = toks[i].value, toks[i + 2].value
                    if v == s.e_var:
                        if steps > 1:
                            # the lowered GO binds the alias to the
                            # FINAL hop's edge; a Cypher-style reader
                            # expects e to bind the whole edge list —
                            # reject rather than silently serve one
                            # edge's value
                            raise ExecError(
                                f"{v}.{prop}: edge properties across "
                                f"a variable-length pattern are "
                                f"unsupported (the lowered GO binds "
                                f"{v} to the final hop's edge only)",
                                ErrorCode.E_UNSUPPORTED)
                        out.append(f"{alias}.{prop} ")
                    else:
                        if not labels.get(v):
                            raise ExecError(
                                f"({v}) needs a :tag label to read "
                                f"{v}.{prop}")
                        if v == start_var and steps > 1:
                            # multi-hop GO's $^ is the FINAL hop's
                            # source, not the anchor — serving the
                            # anchor's props would be silently wrong
                            raise ExecError(
                                f"{v}.{prop}: anchor-vertex properties "
                                f"across a variable-length pattern are "
                                f"unsupported (the lowered GO reads "
                                f"the final hop's source)",
                                ErrorCode.E_UNSUPPORTED)
                        space = "$^" if v == start_var else "$$"
                        out.append(f"{space}.{labels[v]}.{prop} ")
                    i += 3
                    continue
                # bare <var>
                if is_id(i) and toks[i].value in pat_vars:
                    v = toks[i].value
                    if v == s.e_var:
                        raise ExecError(
                            f"bare edge variable {v} in {what}; return "
                            f"its properties ({v}.<prop>) instead")
                    out.append(f"{alias}._src " if v == start_var
                               else f"{alias}._dst ")
                    i += 1
                    continue
                out.append(lexeme(i))
                i += 1
            return "".join(out)

        def parse_with(fn_name: str, text: str):
            try:
                p = _Parser(tokenize(text), text)
                out = getattr(p, fn_name)()
                if p.peek().type != "EOF":
                    p.fail("unexpected trailing input in MATCH clause")
                return out
            except (ParseError, LexError) as e:
                raise ExecError(f"MATCH clause: {e}")

        # WHERE: split the anchor conjuncts (id(<start>) == vid) off
        # the predicate tree; the rest travels as the GO filter.  The
        # traversal START is whichever pattern vertex the anchor
        # names: the edge's tail lowers onto a forward GO, its head
        # onto OVER ... REVERSELY (tried tail-first, so a query
        # anchoring BOTH vertices runs forward with the head anchor
        # kept as an equality filter)
        from ...filter.expressions import (EdgeSrcIdExpr, LogicalExpr,
                                           PrimaryExpr, RelationalExpr,
                                           UnaryExpr)

        def int_literal(e) -> Optional[int]:
            # vids are signed: -5 parses as UnaryExpr('-', Primary(5))
            if isinstance(e, UnaryExpr) and e.op == "-":
                inner = int_literal(e.operand)
                return None if inner is None else -inner
            if isinstance(e, PrimaryExpr) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                return int(e.value)
            return None

        def split_anchors(tree):
            """(vids, remnant): id(start) == <lit> conjuncts vs the
            rest of the predicate."""
            vids: List[int] = []
            remnant = [None]

            def split(e):
                if isinstance(e, LogicalExpr) and e.op == "&&":
                    split(e.left)
                    split(e.right)
                    return
                if isinstance(e, RelationalExpr) and e.op == "==":
                    l, r = e.left, e.right
                    if isinstance(r, EdgeSrcIdExpr):
                        l, r = r, l
                    if isinstance(l, EdgeSrcIdExpr):
                        lit = int_literal(r)
                        if lit is not None:
                            vids.append(lit)
                            return
                remnant[0] = e if remnant[0] is None else \
                    LogicalExpr("&&", remnant[0], e)

            split(tree)
            return vids, remnant[0]

        # pattern normalization: the edge runs tail -> head
        if s.reverse:
            tail, head = s.b_var, s.a_var
        else:
            tail, head = s.a_var, s.b_var
        chosen = None
        rewrite_err = None
        rewrote_clean = False
        for start_var, reversely in ((tail, False), (head, True)):
            if not s.where_text:
                break
            try:
                tree = parse_with(
                    "p_expression",
                    rewrite(s.where_text, "WHERE", start_var))
            except ExecError as e:
                # a direction can fail to rewrite on its own (e.g. the
                # would-be $^/$$ vertex reads a prop without a label);
                # the other direction may still carry the anchor
                rewrite_err = rewrite_err or e
                continue
            rewrote_clean = True
            vids, remnant = split_anchors(tree)
            if vids:
                chosen = (start_var, reversely, vids, remnant)
                break
        if chosen is None:
            # when a direction rewrote cleanly but carried no anchor,
            # the real problem is the missing id() anchor — the OTHER
            # direction's rewrite error is incidental (its $^/$$ shape
            # would never have been used) and would only mislead
            if rewrite_err is not None and not rewrote_clean:
                raise rewrite_err
            raise ExecError(
                "MATCH needs an id(<pattern vertex>) == <vid> anchor "
                "in WHERE to choose start vertices",
                ErrorCode.E_UNSUPPORTED)
        start_var, reversely, vids, remnant = chosen

        yc = parse_with(
            "p_yield_clause",
            "yield " + rewrite(s.return_text, "RETURN", start_var))

        if steps > 1:
            # any id(<start>) that did NOT become the anchor (a
            # non-== use in WHERE, or a RETURN column) would read the
            # FINAL hop's source under the lowered multi-hop GO, not
            # the pattern anchor — reject instead of serving the
            # wrong vertex
            for e in ([remnant] if remnant is not None else []) + \
                    [c.expr for c in yc.columns]:
                for node in walk_expr(e):
                    if isinstance(node, EdgeSrcIdExpr):
                        raise ExecError(
                            f"id({start_var}) across a "
                            f"variable-length pattern is only usable "
                            f"as the == anchor (the lowered GO's _src "
                            f"is the final hop's source)",
                            ErrorCode.E_UNSUPPORTED)

        if len(set(vids)) > 1:
            # two DIFFERENT id(start) == … conjuncts can't both hold:
            # the predicate is unsatisfiable, the result set is empty
            cols = [c.alias or default_col_name(c.expr)
                    for c in yc.columns]
            return InterimResult(cols, [])
        vids = vids[:1]

        go = ast.GoSentence(
            step=ast.StepClause(steps=steps, upto=upto),
            from_=ast.FromClause(vids=[PrimaryExpr(v) for v in vids]),
            over=ast.OverClause(edges=[ast.OverEdge(edge=s.e_label)],
                                reversely=reversely),
            where=(ast.WhereClause(filter=remnant)
                   if remnant is not None else None),
            yield_=yc)
        return GoExecutor(go, self.ectx).execute()
