"""nebulatrace — process-wide span tracer with cross-RPC propagation.

The reference has aggregate StatsManager counters but nothing that
attributes ONE slow query to parse vs RPC fan-out vs device kernels
(SURVEY.md §5.5 scaffolds the counters and stops there).  This module
is the Dapper-shaped half: a query (or any root operation) opens a
trace; every instrumented seam underneath — RPC client/server hops
(interface/rpc.py frame envelope), executor runs (graph/service.py),
storage/meta retry passes, TPU runtime phases (tpu/runtime.py) — adds
child spans that share the trace id across thread and process
boundaries.

Design constraints, in order:

1. **Disabled must be free.**  With ``trace_sample_rate=0`` and no
   PROFILE in flight the hot path is one thread-local read returning
   ``None`` — no allocation, no branch into this module's classes
   (tests/test_tracing.py pins this with tracemalloc on
   ``RpcChannel.call``).
2. **Propagation is explicit.**  Context rides a thread-local; crossing
   a thread pool uses ``capture()``/``attach_captured()`` and crossing
   a process uses the RPC frame envelope ``[method, payload,
   [trace_id, span_id]]`` with finished spans returned piggybacked on
   the response — the client absorbs them, so graphd assembles the
   whole tree without a second collection RPC.
3. **Names are a closed set.**  Every span name is a literal dotted
   string from ``SPAN_NAMES`` below; ``nebula_tpu/tools/lint``'s
   span-registry check enforces it (same contract as the flag
   registry), so dashboards and tests can rely on exact names.

Timing: spans use clock.Duration (monotonic) plus the fake-clock test
offset (clock.advance_for_tests), so tracing tests are deterministic
without sleeping.  ``emit`` records a span that was timed elsewhere
(the continuous pump stamps perf_counter and reports after the tick),
on the same now_micros() clock.
"""
from __future__ import annotations

import random
import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

from .clock import Duration, now_micros, test_offset_micros
from .flags import flags
from .ordered_lock import OrderedLock
from .stats import stats

flags.define("trace_sample_rate", 0.0,
             "fraction of root operations (queries) traced when not "
             "explicitly PROFILEd; 0 disables background sampling")
flags.define("trace_buffer_size", 256,
             "recent traces kept in the in-process ring buffer served "
             "by the /traces web endpoint")
flags.define("slow_query_threshold_ms", 0,
             "statements slower than this land in the slow-query log "
             "(/traces?slow=1) and journal a query.slow event, with "
             "their trace id when sampled; entries carry the dispatch "
             "seat markers of the continuous tier (lane, joined_tick, "
             "hop count, typed ending) when the statement rode a lane "
             "batch; 0 disables")

# The single span-name registry (lint: span-registry).  Add here FIRST,
# then use the literal at the call site.
SPAN_NAMES = (
    "graph.query",            # root: one statement through the engine
    "graph.parse",            # GQLParser.parse
    "graph.executor",         # one executor run (tags: executor, rows)
    "rpc.client",             # outbound RPC (tags: method, peer)
    "rpc.server",             # inbound RPC dispatch (tags: method)
    "storage.collect.pass",   # one scatter-gather retry pass
    "meta.call.pass",         # one meta whole-peer-set retry pass
    "tpu.mirror.build",       # full CSR/ELL mirror rebuild
    "tpu.absorb",             # incremental delta absorption: fold the
                              # committed write delta into the resident
                              # tables as the next mirror generation
                              # (tpu/runtime.py, docs/durability.md)
    "tpu.peer_absorb",        # one peer-delta stream window: the
                              # deviceScanDelta fetch + cursor checks
                              # that feed a remote store's events into
                              # the absorption above (storage/device.py
                              # RemoteStoreView.delta_since)
    "tpu.jit.compile",        # kernel cache miss → XLA build/compile
    "tpu.kernel",             # device kernel dispatch (async launch)
    "tpu.launch",             # batch leader: frontier launch half
    "tpu.fetch",              # device→host result gather
    "tpu.count",              # a continuous leave cohort's per-lane
                              # count: the wait for the count program
                              # and the read of its B int32, on the
                              # first counting leaver's trace (tags:
                              # leavers, bytes — tpu/runtime.py
                              # _LaneCount)
    "tpu.assemble",           # host row materialization
    "tpu.path_index",         # FIND PATH: the in-edge order of one
                              # mirror generation and OVER set, built at
                              # its first path statement (tag: edges)
    "tpu.path_reconstruct",   # FIND PATH host half: the parent walk
                              # from the BFS depths to path rows (tags:
                              # depth, paths, on_path_vertices, capped,
                              # cpu_us: the walk's own thread time)
    "tpu.where",              # a GO's WHERE over the final frontier's
                              # candidate edges: one a signature group
                              # of a windowed batch or of what a
                              # continuous pump kept, one a continuous
                              # leaver on its own trace (tags:
                              # queries, candidates, kept, site:
                              # assembly, native: the statements the
                              # one native pass filtered, 0 where
                              # numpy did, cpu_us: the pass's own
                              # thread time — tpu/runtime.py
                              # _assemble_group)
    "rpc.fault",              # zero-duration marker: injected fault
    "graph.admission",        # zero-duration marker: admission decision
                              # (shed / deadline drop — batch_dispatch)
    "graph.continuous",       # zero-duration marker: a query's seat
                              # trajectory through the continuous lane
                              # batch (lane, join tick, midflight —
                              # batch_dispatch _ContinuousStream)
    "graph.batched",          # zero-duration marker: a rider's time
                              # in the windowed tier (submit_batched):
                              # tags method, riders, pool_wait_us (its
                              # batch starts), run_us (its batch ends),
                              # wake_us (its thread runs again)
    "tpu.breaker",            # zero-duration marker: device breaker
                              # decline / classified runtime failure
                              # (tpu/runtime.py, docs/durability.md)
    "graph.timeline.export",  # stitching one Chrome-trace export out
                              # of the span tree + flight-recorder
                              # rows (PROFILE FORMAT=trace / the
                              # /timeline endpoint — common/flight.py
                              # chrome_trace, docs/observability.md
                              # "The device timeline")
    # the continuous pump's own trace, emitted post hoc from one set
    # of perf_counter stamps per tick (batch_dispatch _emit_pump_trace,
    # docs/observability.md "The pump trace"): a root per traced tick,
    # children that tile it in pump order, and the idle stretch since
    # the previous tick ended
    "pump.tick",              # root: one traced tick (tags: stream,
                              # tick, seats, joins, leaves, riders)
    "pump.hold",              # the door held open behind a busy
                              # device (batch_dispatch _hold; tag
                              # joins: riders seated that arrived
                              # meanwhile); only on a tick that held
    "pump.seat",              # anchor + seat-map bookkeeping
    "pump.enqueue",           # join + hop + extract + clear enqueues
    "pump.count",             # host blocked on the leave cohort's
                              # per-lane count and reading it (tag
                              # counted: the leavers it answers); the
                              # head of the tick record's fetch_wait_us
    "pump.fetch_wait",        # host blocked on the leave cohort's
                              # extract buffer (the device, seen from
                              # the pump)
    "pump.d2h",               # the copy after that wait
    "pump.unpack",            # the cohort's bitmaps to its leavers'
                              # id arrays: one native call (tag
                              # native: the leavers it took)
    "pump.rows",              # what the pump answers itself: the
                              # cohort's COUNT fold, a WHERE that
                              # filters in numpy (tag handed: the
                              # leavers whose frontier went to their
                              # own thread, which makes the rows)
    "pump.handover",          # frontiers and counts published,
                              # waiters notified
    "pump.idle",              # root: no tick in flight (tag: why)
)

# the waits a continuous rider's time in submit() is made of, in
# order (batch_dispatch _ContinuousStream._waits): tags of its
# graph.continuous marker, keys of its seat markers and slow-log entry
RIDER_WAITS = ("seat_wait_us", "ride_us", "result_wait_us", "wake_us",
               "assemble_us")

_tls = threading.local()          # .ctx = (trace_id, span_id, True)
_rng = random.Random()            # ids; independent of seeded test RNGs


class _Noop:
    """Shared disabled-path context manager: ``with span(...) as s``
    yields None and allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def current_context() -> Optional[Tuple[int, int, bool]]:
    """(trace_id, span_id, sampled) of the calling thread, or None.
    Presence implies sampled — unsampled operations never set context."""
    return getattr(_tls, "ctx", None)


class Span:
    """One timed operation.  Context-manager protocol; while entered it
    becomes the thread's current context so nested spans parent to it."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "tags",
                 "start_us", "duration_us", "_dur", "_off0", "_prev")

    def __init__(self, name: str, trace_id: int, parent_id: Optional[int],
                 tags: Dict[str, Any]):
        self.name = name
        self.trace_id = trace_id
        self.parent_id = parent_id
        self.span_id = _rng.getrandbits(63)
        self.tags = tags
        self.start_us = 0
        self.duration_us = 0

    def tag(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def __enter__(self) -> "Span":
        self._prev = getattr(_tls, "ctx", None)
        _tls.ctx = (self.trace_id, self.span_id, True)
        if self.parent_id is None:
            trace_store.pin(self.trace_id)
        self._off0 = test_offset_micros()
        self.start_us = now_micros()
        self._dur = Duration()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        # fake-clock aware: advance_for_tests() moves the duration too,
        # so tracing tests assert exact-ish timings without sleeping
        self.duration_us = self._dur.elapsed_in_usec() + \
            (test_offset_micros() - self._off0)
        _tls.ctx = self._prev
        if et is not None:
            self.tags["error"] = f"{et.__name__}: {ev}"
        _record(self.to_wire())
        if self.parent_id is None:
            # root closed: the trace is complete and becomes evictable
            trace_store.unpin(self.trace_id)
        return False

    def to_wire(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "start_us": self.start_us,
                "duration_us": self.duration_us, "tags": self.tags}


def span(name: str, **tags):
    """Child span under the current context, or the shared no-op when
    the thread isn't tracing.  ``name`` must be a SPAN_NAMES literal."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return _NOOP
    return Span(name, ctx[0], ctx[1], tags)


def start_trace(name: str, forced: bool = False, **tags):
    """Root span: samples per trace_sample_rate unless ``forced``
    (PROFILE).  Returns the root Span or the no-op."""
    if not forced:
        rate = flags.get("trace_sample_rate", 0.0)
        if not rate or _rng.random() >= float(rate):
            return _NOOP
    return Span(name, new_trace_id(), None, tags)


class _Attach:
    """Install a (context, sink) pair on the calling thread for a
    with-block — the cross-thread / server-side adoption primitive."""

    __slots__ = ("_ctx", "_sink", "_prev")

    def __init__(self, ctx, sink=None):
        self._ctx = ctx
        self._sink = sink

    def __enter__(self):
        self._prev = (getattr(_tls, "ctx", None),
                      getattr(_tls, "sink", None))
        _tls.ctx = self._ctx
        _tls.sink = self._sink
        return self

    def __exit__(self, *exc):
        _tls.ctx, _tls.sink = self._prev
        return False


def attach(ctx, sink=None):
    """Adopt a propagated context (server dispatch, pool worker)."""
    return _Attach(ctx, sink)


def capture():
    """Snapshot the calling thread's trace state for handoff into a
    worker thread; None when not tracing (then attach_captured is the
    free no-op)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return None
    return (ctx, getattr(_tls, "sink", None))


def attach_captured(cap):
    if cap is None:
        return _NOOP
    return _Attach(cap[0], cap[1])


# ------------------------------------------------------------ storage
class TraceStore:
    """Ring buffer of recent traces (trace_buffer_size), each a flat
    span list deduped by span id; /traces serves it as JSON."""

    def __init__(self):
        self._lock = OrderedLock("tracing.store")
        self._traces: "OrderedDict[int, List[dict]]" = OrderedDict()
        self._seen: Dict[int, set] = {}
        self._pinned: set = set()   # in-flight rooted traces: no evict

    def pin(self, trace_id: int) -> None:
        """Shield an in-flight trace from ring eviction (the root Span
        pins on enter, unpins on exit): a slow PROFILE under ring
        pressure must not come back gutted of its early spans."""
        with self._lock:
            self._pinned.add(trace_id)

    def unpin(self, trace_id: int) -> None:
        with self._lock:
            self._pinned.discard(trace_id)

    def record(self, wire: Dict[str, Any]) -> None:
        cap = int(flags.get("trace_buffer_size", 256) or 256)
        tid = wire["trace_id"]
        with self._lock:
            spans = self._traces.get(tid)
            if spans is None:
                spans = self._traces[tid] = []
                self._seen[tid] = set()
                while len(self._traces) > cap:
                    # oldest UNPINNED trace goes — never the entry just
                    # created for THIS span (evicting it would KeyError
                    # below); pinned (in-flight) traces may transiently
                    # push the ring over cap, bounded by the number of
                    # concurrent roots
                    victim = next((t for t in self._traces
                                   if t not in self._pinned
                                   and t != tid), None)
                    if victim is None:
                        break
                    del self._traces[victim]
                    self._seen.pop(victim, None)
            if wire["span_id"] in self._seen[tid]:
                return           # envelope echo of a span already local
            self._seen[tid].add(wire["span_id"])
            spans.append(wire)

    def absorb(self, spans: List[dict]) -> None:
        """Fold spans returned in an RPC response envelope into the
        local store (they carry their own trace/span ids)."""
        for s in spans:
            if isinstance(s, dict) and "trace_id" in s \
                    and "span_id" in s:
                self.record(s)

    def spans(self, trace_id: int) -> List[dict]:
        with self._lock:
            return list(self._traces.get(trace_id, ()))

    def summaries(self) -> List[dict]:
        """Newest-first trace summaries for the /traces listing."""
        with self._lock:
            items = list(self._traces.items())
        out = []
        for tid, spans in reversed(items):
            if not spans:
                continue
            roots = [s for s in spans if s.get("parent_id") is None]
            head = roots[0] if roots else \
                min(spans, key=lambda s: s.get("start_us", 0))
            out.append({"id": f"{tid:016x}", "name": head["name"],
                        "start_us": head.get("start_us", 0),
                        "duration_us": head.get("duration_us", 0),
                        "spans": len(spans)})
        return out

    def tree(self, trace_id: int) -> Optional[dict]:
        """Nested span tree {id, name, duration_us, tags, children}.
        Spans whose parent is missing (other process, evicted) hang off
        the synthetic root list."""
        spans = self.spans(trace_id)
        if not spans:
            return None
        nodes = {}
        for s in spans:
            nodes[s["span_id"]] = {
                "span_id": f"{s['span_id']:016x}", "name": s["name"],
                "start_us": s.get("start_us", 0),
                "duration_us": s.get("duration_us", 0),
                "tags": s.get("tags") or {}, "children": []}
        orphans = []
        for s in spans:
            node = nodes[s["span_id"]]
            parent = s.get("parent_id")
            if parent is not None and parent in nodes:
                nodes[parent]["children"].append(node)
            else:
                orphans.append(node)
        for n in nodes.values():
            n["children"].sort(key=lambda c: c["start_us"])
        orphans.sort(key=lambda c: c["start_us"])
        return {"trace_id": f"{trace_id:016x}", "roots": orphans}

    def discard(self, trace_id: int) -> None:
        """Drop one trace (a force-started trace whose statement turned
        out not to be a PROFILE — it would only evict real traces)."""
        with self._lock:
            self._traces.pop(trace_id, None)
            self._seen.pop(trace_id, None)
            self._pinned.discard(trace_id)

    def clear_for_tests(self) -> None:
        with self._lock:
            self._traces.clear()
            self._seen.clear()
            self._pinned.clear()


class SlowQueryLog:
    """Bounded ring of statements over slow_query_threshold_ms."""

    _CAP = 128
    # credential-bearing statements (CREATE USER ... WITH PASSWORD "x",
    # CHANGE PASSWORD u FROM "old" TO "new") must not leak plaintext to
    # the unauthenticated /traces?slow=1 endpoint — any statement
    # mentioning PASSWORD gets EVERY string literal masked (the
    # literals sit after WITH/FROM/TO, so masking only the one adjacent
    # to the keyword would miss them; reference DBs mask slow logs the
    # same way)
    _PASSWORD_KW = re.compile(r"(?i)\bpassword\b")
    _STRING_RE = re.compile(r"\"(?:\\.|[^\"\\])*\"|'(?:\\.|[^'\\])*'")

    def __init__(self):
        self._lock = OrderedLock("tracing.slowlog")
        self._entries: List[dict] = []

    _MAX_STMT = 4096

    def record(self, stmt: str, latency_us: int,
               trace_id: Optional[int],
               seat: Optional[dict] = None) -> None:
        """``seat`` carries the continuous-dispatch markers of a slow
        statement that rode a lane batch — lane, joined_tick,
        left_tick, hops, the typed ``ending`` (common/protocol.py
        continuous-ending vocabulary), the five waits its submit() was
        made of (RIDER_WAITS: which wait was slow) and the
        ``timeline`` anchor (first/last flight-recorder tick ids for
        the rider's stream, common/flight.py) —
        so the slow log attributes a slow rider to its seat trajectory
        and its `/timeline` window, not just its wall time (windowed
        statements pass None and keep the PR 3 entry shape)."""
        if self._PASSWORD_KW.search(stmt):
            stmt = self._STRING_RE.sub('"***"', stmt)
        if len(stmt) > self._MAX_STMT:
            # slow statements are often huge INSERT bodies — the ring
            # bounds entry COUNT; this bounds entry SIZE (reference DBs
            # truncate slow-log statements the same way)
            stmt = stmt[:self._MAX_STMT] + f"... [{len(stmt)} chars]"
        entry = {"stmt": stmt, "latency_us": int(latency_us),
                 "time_us": now_micros(),
                 "trace_id": (f"{trace_id:016x}"
                              if trace_id is not None else None)}
        if seat:
            for k in ("lane", "joined_tick", "left_tick", "hops",
                      "ending", "timeline") + RIDER_WAITS:
                if seat.get(k) is not None:
                    entry[k] = seat[k]
        with self._lock:
            self._entries.append(entry)
            if len(self._entries) > self._CAP:
                del self._entries[:len(self._entries) - self._CAP]

    def dump(self) -> List[dict]:
        with self._lock:
            return list(reversed(self._entries))

    def clear_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()


trace_store = TraceStore()
slow_log = SlowQueryLog()


def _record(wire: Dict[str, Any]) -> None:
    trace_store.record(wire)
    sink = getattr(_tls, "sink", None)
    if sink is not None:
        sink.append(wire)


def new_trace_id() -> int:
    """A fresh trace id for a trace that has no live root Span (the
    pump's post-hoc trees)."""
    return _rng.getrandbits(63)


def emit(name: str, trace_id: int, parent_id: Optional[int],
         start_us: int, duration_us: int, **tags) -> int:
    """Record a FINISHED span with explicit timing into ``trace_id``
    and return its span id (so the caller can parent children to it).
    For code that stamps its own clock and reports afterwards — the
    continuous pump — and the primitive under ``annotate``.
    ``start_us`` is on the now_micros() clock, so a caller converting
    from perf_counter takes ONE ``now_micros() - perf_counter`` offset
    per batch of spans and ``advance_for_tests`` ages them like any
    other span.  ``name`` must be a SPAN_NAMES literal (lint:
    span-registry)."""
    s = Span(name, trace_id, parent_id, tags)
    s.start_us = int(start_us)
    s.duration_us = int(duration_us)
    _record(s.to_wire())
    return s.span_id


def annotate(name: str, **tags) -> None:
    """Best-effort tag drop on the thread's ACTIVE span context — used
    by layers that don't own a span object (fault injection).  The tags
    land on a zero-duration marker child so the enclosing span's tree
    shows them without mutating a span owned by another frame.
    ``name`` must be a SPAN_NAMES literal (lint: span-registry)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        return
    emit(name, ctx[0], ctx[1], now_micros(), 0, **tags)


# ------------------------------------------- critical-path analyzer
# Per-phase decomposition of a finished span tree: where did this
# query's wall time actually go?  Device phases map by span name; a
# carrier span's SELF time (its duration minus the stretch its
# children cover) is attributed to "queue" — for a dispatched GO that
# is exactly the stretch the statement sat blocked waiting for a
# window to close or a lane seat to launch, the time no child span
# owns.  Unmapped leaves (parse, markers) fold into "other".
PHASE_QUEUE = "queue"
PHASE_MIRROR = "mirror"
PHASE_KERNEL = "hop-kernel"
PHASE_FETCH = "fetch"
PHASE_ASSEMBLE = "assemble"
PHASE_OTHER = "other"

CRITICAL_PHASES = (PHASE_QUEUE, PHASE_MIRROR, PHASE_KERNEL,
                   PHASE_FETCH, PHASE_ASSEMBLE, PHASE_OTHER)

# leaf-span phase map; names absent here are carriers (self time →
# queue) when they have children, "other" otherwise
_PHASE_OF = {
    "tpu.mirror.build": PHASE_MIRROR,
    "tpu.absorb": PHASE_MIRROR,
    "tpu.peer_absorb": PHASE_MIRROR,
    "tpu.jit.compile": PHASE_KERNEL,
    "tpu.launch": PHASE_KERNEL,
    "tpu.kernel": PHASE_KERNEL,
    "tpu.fetch": PHASE_FETCH,
    "tpu.count": PHASE_FETCH,
    "tpu.assemble": PHASE_ASSEMBLE,
    "tpu.where": PHASE_ASSEMBLE,
}

stats.register_histogram("graph.query.phase_us")


def _covered_us(node: dict) -> int:
    """Wall stretch of ``node`` covered by its children, interval-
    merged and clipped to the node's own window."""
    lo = node.get("start_us", 0)
    hi = lo + node.get("duration_us", 0)
    ivs = []
    for ch in node.get("children", ()):
        s = ch.get("start_us", 0)
        e = s + ch.get("duration_us", 0)
        s, e = max(s, lo), min(e, hi)
        if e > s:
            ivs.append((s, e))
    ivs.sort()
    total, cur_s, cur_e = 0, None, None
    for s, e in ivs:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def critical_path(tree: Optional[dict]) -> Optional[Dict[str, int]]:
    """Fold a TraceStore.tree() span tree into per-phase micros.

    Each span's self time (duration minus merged child coverage) is
    charged to its phase; parallel siblings each charge their own time
    (a scatter-gather's branches are all real work), so the phase sum
    can exceed wall clock on fanned-out queries — the decomposition
    answers "what would shortening this phase buy", not "what is the
    wall total"."""
    if not tree or not tree.get("roots"):
        return None
    phases = dict.fromkeys(CRITICAL_PHASES, 0)

    def walk(node):
        self_us = max(node.get("duration_us", 0) - _covered_us(node), 0)
        phase = _PHASE_OF.get(node.get("name"))
        if phase is None:
            phase = PHASE_QUEUE if node.get("children") else PHASE_OTHER
        phases[phase] += self_us
        for ch in node.get("children", ()):
            walk(ch)

    for root in tree["roots"]:
        walk(root)
    return phases


def critical_path_summary(phases: Dict[str, int]) -> str:
    """The one-line PROFILE footer."""
    parts = [f"{p} {phases.get(p, 0)}us" for p in CRITICAL_PHASES
             if phases.get(p, 0) > 0]
    total = sum(phases.values())
    return ("critical path: " + " | ".join(parts or ["idle"])
            + f" (total {total}us)")


def observe_phases(phases: Optional[Dict[str, int]]) -> None:
    """Feed the per-phase histogram family — one labeled observation
    per non-zero phase of a finished traced query."""
    if not phases:
        return
    for p, us in phases.items():
        if us > 0:
            stats.observe("graph.query.phase_us", float(us), phase=p)
