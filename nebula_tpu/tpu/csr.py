"""CSR mirror — fold a space's edge/vertex KV partitions into host columns.

The storage key encoding is order-preserving (common/keys.py), so a plain
range scan over each partition already yields edges in
(src, etype, rank, dst, version) order.  Building CSR is therefore one
merge pass with multi-version "first wins" dedup (the reference dedups the
same way while scanning RocksDB — QueryBaseProcessor.inl:352-361).

Ids and strings are re-encoded into **order-preserving dense
spaces**, so the device tables (tpu/ell.py, built from these edge
arrays) index vertices in int32 and every comparison is numeric:

  * vertex ids  → dense indices into the sorted ``vids`` array.  Sorted
    order means dense-index comparisons equal vid comparisons, so filter
    literals translate via searchsorted.
  * strings     → codes into a sorted per-column dictionary; the sort makes
    codes order-preserving too, so ==/!=/</> all compile.
  * numbers     → int64 / float64 as stored, the CPU executor's precision.

The columns stay on the host: the device advances frontiers, and a
WHERE or a YIELD meets the candidate edges here in numpy (no per-row
Python in the hot path).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

import threading
import time

from ..codec.rows import RowReader
from ..common.flags import flags
from ..common.keys import KeyUtils
from ..interface.common import Schema, SupportedType

flags.define(
    "mirror_bulk_build", True,
    "CSR mirror builds use the vectorized bulk path (csr_bulk.py: "
    "packed engine scans + native batch codec) when the native library "
    "is available; off = always the per-row reference builder")


def _now_s() -> float:
    from ..common.clock import now_s
    return now_s()


_NO_ABSORB = object()   # Column.absorb_form: "this write needs a rebuild"


def _ttl_expiry(reader: RowReader):
    """Absolute expiry time (seconds) of a row under its schema's TTL, or
    None when the schema has no TTL / the column is unusable (same
    semantics as processors._ttl_expired, which mirrors the reference's
    compaction-filter + read-skip TTL handling)."""
    prop = reader.schema.schema_prop
    if not prop.ttl_col or not prop.ttl_duration:
        return None
    try:
        base = reader.get(prop.ttl_col)
    except KeyError:
        return None
    if not isinstance(base, (int, float)) or isinstance(base, bool):
        return None
    return base + prop.ttl_duration


class Column:
    """One columnar property: numeric array or dictionary-encoded strings.

    ``values`` is aligned to the edge array (edge props) or to the dense
    vertex array (tag props).  ``valid`` marks rows that actually carry the
    column (a vertex may lack the tag; an edge row written under an older
    schema version may miss appended columns).
    """

    __slots__ = ("name", "stype", "values", "valid", "dictionary", "raw")

    def __init__(self, name: str, stype: SupportedType, size: int):
        self.name = name
        self.stype = stype
        self.valid = np.zeros(size, dtype=bool)
        self.dictionary: Optional[np.ndarray] = None  # sorted unique strings
        self.raw: Optional[list] = None
        if stype == SupportedType.STRING:
            self.raw = [""] * size          # filled then dict-encoded
            self.values = None
        elif stype in (SupportedType.FLOAT, SupportedType.DOUBLE):
            self.values = np.zeros(size, dtype=np.float64)
        elif stype == SupportedType.BOOL:
            self.values = np.zeros(size, dtype=bool)
        else:  # INT / VID / TIMESTAMP
            self.values = np.zeros(size, dtype=np.int64)

    def finalize(self) -> None:
        """Dictionary-encode strings."""
        if self.stype == SupportedType.STRING:
            arr = np.asarray(self.raw, dtype=object)
            self.dictionary, codes = np.unique(
                arr.astype(str), return_inverse=True)
            self.values = codes.astype(np.int32)
            self.raw = arr

    def absorb_form(self, v):
        """The storable form of an in-place write of ``v`` to this
        column, or _NO_ABSORB when the write needs a rebuild.  A
        number absorbs as it is: the host column holds any int64 or
        float64 the store does.  A string absorbs only when it is
        already in the dictionary (growing it re-encodes every row's
        code, torn for racing readers) — returns (raw, code)."""
        if self.stype != SupportedType.STRING:
            return v
        if self.dictionary is None:
            return _NO_ABSORB
        s = v if isinstance(v, str) else str(v)
        pos = int(np.searchsorted(self.dictionary, s))
        if pos >= len(self.dictionary) \
                or str(self.dictionary[pos]) != s:
            return _NO_ABSORB       # new string: dictionary grows
        return (s, pos)

    def host_value(self, i: int):
        """Python value at row i (for result rows)."""
        if self.stype == SupportedType.STRING:
            return str(self.raw[i])
        v = self.values[i]
        if self.stype == SupportedType.BOOL:
            return bool(v)
        if self.values.dtype == np.float64:
            return float(v)
        return int(v)


def edge_column(m: "CsrMirror", et, prop: str) -> Optional[Column]:
    """The edge column ``(et, prop)``.  ``et`` is one signed edge type,
    or the tuple of both signs that one name stands on under ``GO ...
    BIDIRECT`` (ExprCompiler._edge_col hands a lone sign over as the
    int): the columns of +t and of -t are valid on disjoint rows
    (a row is one sign's), so the merged column holds each row's own
    value, built once per mirror generation (edge events publish a new
    generation; only vertex writes commit in place).  None where either
    column is missing or holds strings (two dictionaries, two codes for
    one string): the per-row evaluator answers those."""
    if not isinstance(et, tuple):
        return m.edge_cols.get((et, prop))
    cache = m.__dict__.setdefault("_merged_edge_cols", {})
    got = cache.get((et, prop))
    if got is None:
        parts = [m.edge_cols.get((e, prop)) for e in et]
        if any(c is None or c.values is None or c.raw is not None
               or c.stype != parts[0].stype for c in parts):
            return None
        got = Column(prop, parts[0].stype, 0)
        got.values = parts[0].values.copy()
        got.valid = parts[0].valid.copy()
        for c in parts[1:]:
            got.values[c.valid] = c.values[c.valid]
            got.valid |= c.valid
        cache[(et, prop)] = got
    return got


class CsrMirror:
    """Per-space CSR + columnar property store.

    Edge arrays are sorted by (src_dense, etype, rank, dst) — the KV scan
    order — and carry BOTH directions (the mutate executors write the
    reverse edge under -etype, mirroring the reference), so
    ``GO ... REVERSELY`` is just an etype-sign flip.
    """

    def __init__(self, space_id: int):
        self.space_id = space_id
        # dense vertex space
        self.vids = np.zeros(0, dtype=np.int64)       # sorted unique
        self.n = 0
        # edge arrays (length m)
        self.m = 0
        self.edge_src = np.zeros(0, dtype=np.int32)   # dense idx
        self.edge_dst = np.zeros(0, dtype=np.int32)   # dense idx
        self.edge_etype = np.zeros(0, dtype=np.int32) # signed etype
        self.edge_rank = np.zeros(0, dtype=np.int64)
        self.row_ptr = np.zeros(1, dtype=np.int32)
        # (etype, prop) -> Column aligned to edge arrays
        self.edge_cols: Dict[Tuple[int, str], Column] = {}
        # (tag_id, prop) -> Column aligned to dense vertex array
        self.vertex_cols: Dict[Tuple[int, str], Column] = {}
        # tag presence: tag_id -> bool[n]
        self.has_tag: Dict[int, np.ndarray] = {}
        self.build_version = -1
        # FIND PATH's in-edge order by OVER set, built by the runtime
        # at this generation's first path statement under this lock
        # (runtime._path_index); host memory, goes with the generation
        self._path_index: Dict[Tuple[int, ...], tuple] = {}
        self._path_index_lock = threading.Lock()
        # earliest future TTL expiry among mirrored rows (seconds), or
        # None; the runtime rebuilds once this passes so aging rows drop
        # out in lockstep with the CPU read path
        self.expires_at_s = None

    def note_expiry(self, exp_s: float) -> None:
        if self.expires_at_s is None or exp_s < self.expires_at_s:
            self.expires_at_s = exp_s

    def expired_now(self) -> bool:
        return self.expires_at_s is not None and _now_s() >= self.expires_at_s

    # ---- lookups -----------------------------------------------------
    def to_dense(self, vids) -> np.ndarray:
        """vid values -> dense indices (-1 when absent)."""
        a = np.asarray(vids, dtype=np.int64)
        pos = np.searchsorted(self.vids, a)
        pos = np.clip(pos, 0, max(self.n - 1, 0))
        ok = (self.n > 0) & (self.vids[pos] == a) if self.n else \
            np.zeros(len(a), dtype=bool)
        return np.where(ok, pos, -1).astype(np.int32)

    def vid_rank(self, vid: int) -> int:
        """searchsorted position — order-preserving literal translation."""
        return int(np.searchsorted(self.vids, np.int64(vid)))

    def has_vid(self, vid: int) -> bool:
        p = self.vid_rank(vid)
        return p < self.n and int(self.vids[p]) == vid


def iter_leader_parts(space_id: int, stores):
    """Yield (store, part_id) for every part this scan must fold: parts
    sorted per store, leaders only, first claiming store wins (a stale
    leadership claim mid-transfer must not fold a part twice).  The
    SINGLE source of the part-selection rule shared by the per-row and
    bulk mirror builders — their bit-identical contract depends on
    scanning the same part set."""
    folded: set = set()
    for store in stores:
        for part in sorted(store.part_ids(space_id)):
            if part in folded:
                continue
            p = store.part(space_id, part)
            if p is None or not p.is_leader():
                continue
            folded.add(part)
            yield store, part


def _scatter_bool(src: np.ndarray, remap: np.ndarray,
                  n: int) -> np.ndarray:
    out = np.zeros(n, dtype=bool)
    out[remap] = src
    return out


def _base_edge_index(base: CsrMirror, src_d: int, et: int, rank: int,
                     dst_d: int) -> int:
    """Base edge row for one identity, or -1.  Row slices are tiny
    (one vertex's out-edges), so the linear probe is fine at delta
    scale."""
    lo, hi = int(base.row_ptr[src_d]), int(base.row_ptr[src_d + 1])
    for e in range(lo, hi):
        if int(base.edge_etype[e]) == et \
                and int(base.edge_rank[e]) == rank \
                and int(base.edge_dst[e]) == dst_d:
            return e
    return -1


def build_delta_mirror(base: CsrMirror, events, schema_man,
                       space_id: int) -> Optional[CsrMirror]:
    """Fold committed edge mutation EVENTS into a small overlay mirror
    over ``base`` (SURVEY §7 hard part (a): mutations without the O(m)
    rebuild).  Events are the store's typed delta stream
    (kvstore/store.py delta_since): ("put", key, value) inserts AND
    in-place updates, ("del", identity32) whole-edge deletes.

    The overlay carries, beyond its own appended rows:
      * ``base_dead``   — sorted base edge rows superseded by an update
                          or killed by a delete (candidate assembly
                          excludes them);
      * ``extra_vids``  — endpoint vids the base doesn't know (the
                          overlay's dense space grows to the sorted
                          union; ``remap_from_base`` translates base
                          dense ids);
      * ``has_deletes`` — a base edge died with no same-identity
                          replacement, which changes reachability: the
                          runtime must not run multi-hop frontier
                          advances over the base ELL then (it forces
                          the rebuild for those queries only).

    Returns None — full rebuild — for TTL'd rows and unresolvable
    schemas.  Same-identity overwrite ordering assumes the forward
    wall clock that inverted-timestamp versioning itself relies on.
    """
    sm = schema_man
    # collapse in commit order: the last event per edge identity wins
    # (vertex events are applied in place by plan_vertex_events + commit_vertex_plan, not
    # through the edge overlay)
    final: Dict[Tuple[int, int, int, int], Optional[bytes]] = {}
    for ev in events:
        if ev[0] == "vput":
            continue
        if ev[0] == "put":
            _part, src, et, rank, dst, _ver = KeyUtils.parse_edge(ev[1])
            final[(src, et, rank, dst)] = ev[2]
        else:       # ("del", identity32): all versions of one edge
            _part, src, et, rank, dst, _ = KeyUtils.parse_edge(
                ev[1] + b"\x00" * 8)     # pad the absent version field
            final[(src, et, rank, dst)] = None

    puts = {k: v for k, v in final.items() if v is not None}
    dels = [k for k, v in final.items() if v is None]

    # ---- extended dense vid space (new endpoint vids) ----------------
    put_idents = list(puts.keys())
    src_vids = np.asarray([i[0] for i in put_idents], dtype=np.int64)
    dst_vids = np.asarray([i[3] for i in put_idents], dtype=np.int64)
    known_src = base.to_dense(src_vids)
    known_dst = base.to_dense(dst_vids)
    extra = np.unique(np.concatenate([
        src_vids[known_src < 0] if len(put_idents) else
        np.zeros(0, np.int64),
        dst_vids[known_dst < 0] if len(put_idents) else
        np.zeros(0, np.int64)]))

    d = CsrMirror(space_id)
    d.base_dead = np.zeros(0, dtype=np.int64)
    d.extra_vids = extra
    d.remap_from_base = None
    d.has_deletes = False
    if len(extra) == 0:
        d.vids = base.vids             # shared dense-id space
        d.n = base.n
        d.vertex_cols = base.vertex_cols   # vertex side unchanged by
        d.has_tag = base.has_tag           # edge mutations
    else:
        # re-seat the shared vertex side in the grown dense space.
        # Vectorized scatters only (no per-row Python, no re-encode:
        # dictionaries carry over — added rows are invalid, never
        # read), and cached on the base keyed by the
        # extra set: absorptions repeat over the accumulated event
        # list, and this runs under the runtime lock
        ext_key = extra.tobytes()
        cached = getattr(base, "_ext_vertex_cache", None)
        if cached is not None and cached[0] == ext_key:
            d.vids, d.n, d.remap_from_base, d.vertex_cols, d.has_tag = \
                cached[1:]
        else:
            d.vids = np.unique(np.concatenate([base.vids, extra]))
            d.n = len(d.vids)
            remap = np.searchsorted(d.vids, base.vids).astype(np.int32)
            d.remap_from_base = remap
            d.vertex_cols = {}
            for key, c in base.vertex_cols.items():
                nc = Column(c.name, c.stype, d.n)
                nc.valid[remap] = c.valid
                if c.raw is not None:
                    raw = np.empty(d.n, dtype=object)
                    raw[:] = ""
                    raw[remap] = np.asarray(c.raw, dtype=object)
                    nc.raw = raw
                    nc.dictionary = c.dictionary
                    codes = np.zeros(d.n, dtype=np.int32)
                    codes[remap] = c.values
                    nc.values = codes
                else:
                    nc.values[remap] = c.values
                d.vertex_cols[key] = nc
            d.has_tag = {t: _scatter_bool(flags_arr, remap, d.n)
                         for t, flags_arr in base.has_tag.items()}
            base._ext_vertex_cache = (ext_key, d.vids, d.n,
                                      d.remap_from_base, d.vertex_cols,
                                      d.has_tag)

    # ---- base rows superseded / deleted ------------------------------
    # (vectorized endpoint translation — known_src/known_dst already
    # cover the puts; one batch covers the dels.  The per-identity row
    # probe stays Python but walks only one vertex's slice each.)
    dead: List[int] = []
    for i, (src, et, rank, dst) in enumerate(put_idents):
        sd, dd = int(known_src[i]), int(known_dst[i])
        if sd < 0 or dd < 0:
            continue                    # brand-new edge: nothing to kill
        e = _base_edge_index(base, sd, et, rank, dd)
        if e >= 0:
            dead.append(e)              # in-place update: override
    if dels:
        del_sd = base.to_dense(
            np.asarray([k[0] for k in dels], dtype=np.int64))
        del_dd = base.to_dense(
            np.asarray([k[3] for k in dels], dtype=np.int64))
        for i, (src, et, rank, dst) in enumerate(dels):
            sd, dd = int(del_sd[i]), int(del_dd[i])
            if sd < 0 or dd < 0:
                continue                # deleting an unknown edge: no-op
            e = _base_edge_index(base, sd, et, rank, dd)
            if e >= 0:
                dead.append(e)
                d.has_deletes = True    # reachability changed
    d.base_dead = np.unique(np.asarray(dead, dtype=np.int64))

    m = len(put_idents)
    d.m = m
    if m == 0:
        d.row_ptr = np.zeros(d.n + 1, dtype=np.int32)
        return d
    src_d = d.to_dense(src_vids)
    dst_d = d.to_dense(dst_vids)
    etype_a = np.asarray([i[1] for i in put_idents], dtype=np.int32)
    rank_a = np.asarray([i[2] for i in put_idents], dtype=np.int64)
    order = np.lexsort((dst_d, rank_a, etype_a, src_d))
    d.edge_src = src_d[order].astype(np.int32)
    d.edge_dst = dst_d[order].astype(np.int32)
    d.edge_etype = etype_a[order]
    d.edge_rank = rank_a[order]

    cols: Dict[Tuple[int, str], Column] = {}
    for et in np.unique(d.edge_etype).tolist():
        schema = sm.get_edge_schema(space_id, abs(et), -1)
        if schema is None:
            return None
        for col in schema.columns:
            cols[(et, col.name)] = Column(col.name, col.type, m)
    vals = [puts[put_idents[j]] for j in order]
    for i, blob in enumerate(vals):
        if not blob:
            continue
        et = int(d.edge_etype[i])
        try:
            reader = RowReader.from_resolver(
                blob, lambda ver, _et=abs(et): sm.get_edge_schema(
                    space_id, _et, ver))
        except KeyError:
            return None
        if _ttl_expiry(reader) is not None:
            return None                # TTL rows need the rebuild path
        for cname in reader.schema.names():
            c = cols.get((et, cname))
            if c is None:
                continue
            try:
                v = reader.get(cname)
            except KeyError:
                continue
            if c.raw is not None:
                c.raw[i] = v if isinstance(v, str) else str(v)
            else:
                c.values[i] = v
            c.valid[i] = True
    for c in cols.values():
        c.finalize()
    d.edge_cols = cols
    counts = np.bincount(d.edge_src, minlength=d.n)
    d.row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    return d


def _merge_edge_cols(base: CsrMirror, d: CsrMirror, keep: np.ndarray,
                     order: np.ndarray,
                     m_new: int) -> Dict[Tuple[int, str], Column]:
    """Columnar half of absorb_overlay: splice overlay columns into
    the kept base rows and restore canonical order.  Dictionary-coded
    strings re-encode through the sorted UNION dictionary when the
    sides' dictionaries differ (codes stay order-preserving, so
    compiled comparisons keep translating); rows a side doesn't carry
    stay invalid."""
    kept = int(keep.sum())
    cols: Dict[Tuple[int, str], Column] = {}
    for key in set(base.edge_cols) | set(d.edge_cols):
        b = base.edge_cols.get(key)
        o = d.edge_cols.get(key)
        ref = b if b is not None else o
        c = Column.__new__(Column)
        c.name, c.stype = ref.name, ref.stype
        c.dictionary = None
        c.raw = None
        valid = np.zeros(m_new, dtype=bool)
        if b is not None:
            valid[:kept] = b.valid[keep]
        if o is not None:
            valid[kept:] = o.valid
        c.valid = valid[order]
        if ref.stype == SupportedType.STRING:
            raw = np.empty(m_new, dtype=object)
            raw[:] = ""
            if b is not None and b.raw is not None:
                raw[:kept] = np.asarray(b.raw, dtype=object)[keep]
            if o is not None and o.raw is not None:
                raw[kept:] = np.asarray(o.raw, dtype=object)
            c.raw = raw[order]
            dicts = [x.dictionary for x in (b, o)
                     if x is not None and x.dictionary is not None]
            same = len(dicts) == 2 and np.array_equal(dicts[0], dicts[1])
            codes = np.zeros(m_new, np.int32)
            if len(dicts) <= 1 or same:
                c.dictionary = dicts[0] if dicts else \
                    np.zeros(0, dtype=object)
                if b is not None:
                    codes[:kept] = b.values[keep]
                if o is not None:
                    codes[kept:] = o.values
            else:
                union = np.unique(np.concatenate(dicts))
                c.dictionary = union
                remap_b = np.searchsorted(union, b.dictionary)
                codes[:kept] = remap_b[b.values[keep]]
                remap_o = np.searchsorted(union, o.dictionary)
                codes[kept:] = remap_o[o.values]
            c.values = codes[order].astype(np.int32)
        else:
            vals = np.zeros(m_new, dtype=ref.values.dtype)
            if b is not None:
                vals[:kept] = b.values[keep]
            if o is not None:
                vals[kept:] = o.values
            c.values = vals[order]
        cols[key] = c
    return cols


def absorb_overlay(base: CsrMirror, d: CsrMirror) -> Optional[CsrMirror]:
    """Fold an edge overlay (build_delta_mirror) into a NEW CsrMirror
    — the host-CSR half of incremental delta absorption (the device
    half is ell.make_ell_absorb_kernel; docs/durability.md "The
    generation state machine").

    The vertex side (vids / vertex_cols / has_tag) is SHARED with the
    base: vertex writes commit in place (commit_vertex_plan) under the
    documented values-first/valid-last bounded-staleness stance.  The
    edge side is a vectorized splice — base rows minus the overlay's
    tombstones (base_dead), plus the overlay rows, restored to the
    canonical (src, etype, rank, dst) scan order every other builder
    produces — O(m) host memcpy, never a store re-scan.

    Returns None when the overlay grew the dense-id space
    (extra_vids: a vertex-plan change only the rebuild can serve)."""
    if len(getattr(d, "extra_vids", ())):
        return None
    keep = np.ones(base.m, dtype=bool)
    dead = getattr(d, "base_dead", None)
    if dead is not None and len(dead):
        keep[np.asarray(dead, dtype=np.int64)] = False
    out = CsrMirror(base.space_id)
    out.vids, out.n = base.vids, base.n
    out.vertex_cols = base.vertex_cols
    out.has_tag = base.has_tag
    out.expires_at_s = base.expires_at_s
    src = np.concatenate([base.edge_src[keep], d.edge_src])
    dst = np.concatenate([base.edge_dst[keep], d.edge_dst])
    et = np.concatenate([base.edge_etype[keep], d.edge_etype])
    rank = np.concatenate([base.edge_rank[keep], d.edge_rank])
    order = np.lexsort((dst, rank, et, src))
    out.edge_src = src[order].astype(np.int32)
    out.edge_dst = dst[order].astype(np.int32)
    out.edge_etype = et[order].astype(np.int32)
    out.edge_rank = rank[order]
    out.m = len(out.edge_src)
    out.edge_cols = _merge_edge_cols(base, d, keep, order, out.m)
    counts = np.bincount(out.edge_src, minlength=out.n)
    out.row_ptr = np.concatenate([[0], np.cumsum(counts)]) \
        .astype(np.int32)
    return out


def plan_vertex_events(base: CsrMirror, events, schema_man,
                       space_id: int):
    """Validate committed vertex-row writes ("vput" events) against the
    base mirror and return an apply plan for commit_vertex_plan — the
    vertex-side half of incremental maintenance.  NOTHING is mutated
    here: the caller commits the plan only after every other
    absorption step has succeeded, so no decline path can expose half
    of a commit batch.  Returns None ("do the full rebuild") for any
    write the in-place path can't reproduce exactly:

      * a vid or tag the base doesn't know (dense space / column set
        would change);
      * string values NOT already in the column's dictionary (growing
        or re-sorting a dictionary re-encodes every row's code, which
        a concurrently evaluating plan would read torn; writing an
        EXISTING value is a single-element code store, safe like the
        numeric case — this covers the common re-insert-row-to-update-
        one-field pattern);
      * TTL'd schemas (need expiry tracking).

    Numeric single-element stores are effectively atomic on the host;
    queries racing an absorption see either the old or the new value —
    the same bounded-staleness window every mirror refresh already has.
    """
    sm = schema_man
    # newest write per (vid, tag) wins (commit order)
    newest: Dict[Tuple[int, int], bytes] = {}
    for ev in events:
        if ev[0] != "vput":
            continue
        _part, vid, tag, _ver = KeyUtils.parse_vertex(ev[1])
        newest[(vid, tag)] = ev[2]
    plan = []        # (dense, tag, tag_cols, present | None)
    for (vid, tag), blob in newest.items():
        dense = int(base.to_dense([vid])[0])
        if dense < 0 or tag not in base.has_tag:
            return None
        tag_cols = {cname: c for (t, cname), c in base.vertex_cols.items()
                    if t == tag}
        if not blob:
            plan.append((dense, tag, tag_cols, None))
            continue
        try:
            reader = RowReader.from_resolver(
                blob, lambda ver, _t=tag: sm.get_tag_schema(space_id, _t,
                                                            ver))
        except KeyError:
            return None
        if _ttl_expiry(reader) is not None:
            return None
        present: Dict[str, object] = {}
        for cname in reader.schema.names():
            c = tag_cols.get(cname)
            if c is None:
                return None             # schema drift: rebuild
            try:
                present[cname] = reader.get(cname)
            except KeyError:
                pass
        for cname, v in list(present.items()):
            absorbed = tag_cols[cname].absorb_form(v)
            if absorbed is _NO_ABSORB:
                return None
            present[cname] = absorbed
        plan.append((dense, tag, tag_cols, present))
    return plan


def commit_vertex_plan(base: CsrMirror, plan) -> None:
    """Apply a plan_vertex_events plan IN PLACE.  Values first,
    validity flags LAST: a reader racing the absorption then sees each
    column as either its old state (stale valid bit) or its new state
    (fresh value + fresh bit) — never valid=True over a not-yet-written
    value."""
    for dense, tag, tag_cols, present in plan:
        if present is None:
            # the newest committed row is empty: it REPLACES the old
            # one, so no column survives (rebuild semantics —
            # build_mirror's first-wins dedup never reads older rows)
            for c in tag_cols.values():
                c.valid[dense] = False
        else:
            for cname, v in present.items():
                c = tag_cols[cname]
                if c.stype == SupportedType.STRING:
                    s, code = v
                    c.raw[dense] = s
                    c.values[dense] = code
                else:
                    c.values[dense] = v
            for cname, c in tag_cols.items():
                c.valid[dense] = cname in present
        base.has_tag[tag][dense] = True
    # grown-space vertex copies (extras cache) are now stale
    if plan and getattr(base, "_ext_vertex_cache", None) is not None:
        base._ext_vertex_cache = None


def build_mirror(space_id: int, stores, schema_man) -> CsrMirror:
    """Scan every part of ``space_id`` across the given NebulaStores and
    fold the KV ranges into a CsrMirror.

    ``stores`` — list of kvstore.store.NebulaStore (one per storage node;
    in-process the runtime sees them all — this is the storaged-side
    "CSR mirror fold" of SURVEY.md §7 step 5 run centrally).

    Dispatch: the vectorized bulk builder (csr_bulk.py — packed engine
    scans + native batch codec; the 10^8-row scale path) runs first and
    must produce a bit-identical mirror; anything it can't take
    verbatim falls through to the per-row reference flow below (which
    doubles as the differential-test oracle, tests/test_csr_bulk.py).
    """
    if flags.get("mirror_bulk_build"):
        # scan/RPC failures propagate from here unchanged (the
        # decline-to-CPU contract); a None return means "shape the bulk
        # path doesn't take" and falls through to the per-row builder
        from .csr_bulk import build_mirror_bulk
        m = build_mirror_bulk(space_id, stores, schema_man)
        if m is not None:
            return m
    return _build_mirror_slow(space_id, stores, schema_man)


def _build_mirror_slow(space_id: int, stores, schema_man) -> CsrMirror:
    """The per-row reference builder (see build_mirror)."""
    sm = schema_man
    edge_schema_cache: Dict[Tuple[int, int], Optional[Schema]] = {}
    tag_schema_cache: Dict[Tuple[int, int], Optional[Schema]] = {}

    def edge_schema(etype: int, ver: int) -> Optional[Schema]:
        key = (etype, ver)
        if key not in edge_schema_cache:
            edge_schema_cache[key] = sm.get_edge_schema(
                space_id, abs(etype), ver)
        return edge_schema_cache[key]

    def tag_schema(tag_id: int, ver: int) -> Optional[Schema]:
        key = (tag_id, ver)
        if key not in tag_schema_cache:
            tag_schema_cache[key] = sm.get_tag_schema(space_id, tag_id, ver)
        return tag_schema_cache[key]

    # ---- pass 1: scan KV, dedup multi-version, collect raw tuples ----
    # keys sort latest-version-first within (rank, dst) / (vid, tag), so
    # dedup is "first wins" in scan order.
    edges: List[Tuple[int, int, int, int, bytes]] = []  # src,etype,rank,dst,val
    verts: List[Tuple[int, int, bytes]] = []            # vid,tag,val
    seen_edge_prev: Optional[Tuple[int, int, int, int]] = None
    seen_vert_prev: Optional[Tuple[int, int]] = None
    for store, part in iter_leader_parts(space_id, stores):
        seen_edge_prev = seen_vert_prev = None
        for key, val in store.prefix(space_id, part,
                                     KeyUtils.part_prefix(part)):
            if KeyUtils.is_edge(key):
                _, src, et, rank, dst, _ = KeyUtils.parse_edge(key)
                ident = (src, et, rank, dst)
                if ident == seen_edge_prev:
                    continue          # older version of same edge
                seen_edge_prev = ident
                edges.append((src, et, rank, dst, val))
            elif KeyUtils.is_vertex(key):
                _, vid, tag, _ = KeyUtils.parse_vertex(key)
                ident = (vid, tag)
                if ident == seen_vert_prev:
                    continue
                seen_vert_prev = ident
                verts.append((vid, tag, val))

    mirror = CsrMirror(space_id)

    # ---- dense vertex space ------------------------------------------
    vid_parts = [np.asarray([v for v, _, _ in verts], dtype=np.int64)]
    if edges:
        e_src = np.asarray([e[0] for e in edges], dtype=np.int64)
        e_dst = np.asarray([e[3] for e in edges], dtype=np.int64)
        vid_parts += [e_src, e_dst]
    all_vids = np.concatenate(vid_parts) if vid_parts else \
        np.zeros(0, dtype=np.int64)
    mirror.vids = np.unique(all_vids)
    mirror.n = len(mirror.vids)
    n = mirror.n

    # ---- edge arrays (sort by (src_dense, etype, rank, dst)) ---------
    m = len(edges)
    mirror.m = m
    if m:
        src_d = np.searchsorted(mirror.vids, e_src).astype(np.int32)
        dst_d = np.searchsorted(mirror.vids, e_dst).astype(np.int32)
        etype_a = np.asarray([e[1] for e in edges], dtype=np.int32)
        rank_a = np.asarray([e[2] for e in edges], dtype=np.int64)
        order = np.lexsort((dst_d, rank_a, etype_a, src_d))
        mirror.edge_src = src_d[order]
        mirror.edge_dst = dst_d[order]
        mirror.edge_etype = etype_a[order]
        mirror.edge_rank = rank_a[order]

        # ---- edge prop columns ---------------------------------------
        etypes_present = np.unique(mirror.edge_etype)
        cols: Dict[Tuple[int, str], Column] = {}
        for et in etypes_present.tolist():
            schema = edge_schema(et, -1)
            if schema is None:
                continue
            for col in schema.columns:
                cols[(et, col.name)] = Column(col.name, col.type, m)
        vals_in_order = [edges[i][4] for i in order]
        et_in_order = mirror.edge_etype
        keep = np.ones(m, dtype=bool)
        for i, blob in enumerate(vals_in_order):
            et = int(et_in_order[i])
            if not blob:
                continue
            try:
                reader = RowReader.from_resolver(
                    blob, lambda ver, _et=et: edge_schema(_et, ver))
            except KeyError:
                continue
            # TTL parity: the CPU read path skips expired rows
            # (processors._ttl_expired); expired edges must not traverse
            exp = _ttl_expiry(reader)
            if exp is not None:
                if exp < _now_s():
                    keep[i] = False
                    continue
                mirror.note_expiry(exp)
            for cname in reader.schema.names():
                c = cols.get((et, cname))
                if c is None:
                    continue
                try:
                    v = reader.get(cname)
                except KeyError:
                    continue
                if c.raw is not None:
                    c.raw[i] = v if isinstance(v, str) else str(v)
                else:
                    c.values[i] = v
                c.valid[i] = True
        if not keep.all():
            mirror.edge_src = mirror.edge_src[keep]
            mirror.edge_dst = mirror.edge_dst[keep]
            mirror.edge_etype = mirror.edge_etype[keep]
            mirror.edge_rank = mirror.edge_rank[keep]
            kept_idx = np.nonzero(keep)[0]
            for c in cols.values():
                c.valid = c.valid[keep]
                if c.raw is not None:
                    c.raw = [c.raw[j] for j in kept_idx]
                else:
                    c.values = c.values[keep]
            m = len(mirror.edge_src)
            mirror.m = m
        for c in cols.values():
            c.finalize()
        mirror.edge_cols = cols
        counts = np.bincount(mirror.edge_src, minlength=n)
        mirror.row_ptr = np.concatenate(
            [[0], np.cumsum(counts)]).astype(np.int32)
    else:
        mirror.row_ptr = np.zeros(n + 1, dtype=np.int32)

    # ---- vertex (tag) prop columns -----------------------------------
    vcols: Dict[Tuple[int, str], Column] = {}
    tag_ids = sorted({t for _, t, _ in verts})
    for t in tag_ids:
        schema = tag_schema(t, -1)
        if schema is None:
            continue
        for col in schema.columns:
            vcols[(t, col.name)] = Column(col.name, col.type, n)
        mirror.has_tag[t] = np.zeros(n, dtype=bool)
    for vid, t, blob in verts:
        di = int(np.searchsorted(mirror.vids, np.int64(vid)))
        if not blob:
            if t in mirror.has_tag:
                mirror.has_tag[t][di] = True
            continue
        try:
            reader = RowReader.from_resolver(
                blob, lambda ver, _t=t: tag_schema(_t, ver))
        except KeyError:
            continue
        exp = _ttl_expiry(reader)
        if exp is not None:
            if exp < _now_s():
                continue    # expired tag row: CPU path treats it as absent
            mirror.note_expiry(exp)
        if t in mirror.has_tag:
            mirror.has_tag[t][di] = True
        for cname in reader.schema.names():
            c = vcols.get((t, cname))
            if c is None:
                continue
            try:
                v = reader.get(cname)
            except KeyError:
                continue
            if c.raw is not None:
                c.raw[di] = v if isinstance(v, str) else str(v)
            else:
                c.values[di] = v
            c.valid[di] = True
    for c in vcols.values():
        c.finalize()
    mirror.vertex_cols = vcols
    return mirror
