"""nebulaprof — the flight recorder (docs/observability.md
"The device timeline").

The metrics plane's fourth leg: counters/gauges say HOW MUCH, traces
say WHERE in one statement, events say WHAT happened — the flight
recorder says WHEN on the pump that feeds the device.  Every number
in it is a HOST clock reading: a lock-cheap ring buffer holds one
structured record per continuous-pump tick (seat churn, per-phase
micros of the pump thread, idle gap, mirror generation —
graph/batch_dispatch.py; ``fetch_wait_us`` is where that thread
blocked on the device, the device as the pump sees it), one per
windowed/mesh kernel dispatch (kernel class, shape rung,
per-collective ICI bytes — tpu/runtime.py), and one per sampled
device-timing probe (the ``tpu_device_timing_every`` gate: windowed
dispatch only, the continuous stream is never blocked by it).  Records
are stamped with clock.now_micros() so ``clock.advance_for_tests``
ages the timeline deterministically, exactly like the event journal.

Two consumers sit on top:

* **live-vs-model drift accounting** — every sharded dispatch folds
  its live per-collective ICI bytes against the ``KernelSpec.ici_bytes``
  bound the kernel DECLARED (evaluated at the live shapes), and every
  sampled device timing folds its achieved GB/s against
  ``MESH_MODEL["hbm_gbps"]``.  A fold that exceeds its bound flips the
  cell "over": the transition records a typed ``tpu.model_drift``
  event, and the scrape-time collector publishes the overshoot
  fraction as the ``tpu.model_drift.<axis>`` gauge family (zero while
  in-bound; the gauge table is cleared each scrape, so a cell that
  returns in-bound clears on the next scrape).  The static models stop
  being unfalsifiable arithmetic: meshaudit proves the declared bound
  on the traced jaxpr, the recorder re-proves it on live dispatches.

* **Perfetto/Chrome-trace export** — ``chrome_trace`` stitches a span
  tree (common/tracing.py TraceStore.tree), a rider's seat markers and
  the recorder's device rows into one chrome://tracing-openable JSON
  object.  It is a PURE function of its inputs (no clock, no flags) so
  tests pin a byte-stable golden (tests/golden_timeline.json).

The per-collective byte model below deliberately DUPLICATES
tools/lint/meshaudit._exchange_bytes (production code must not import
the lint package): the factors are the documented static ICI traffic
model (docs/static_analysis.md), and every factor is <= 1x the
operand bytes except all_gather/psum — which no declared bound here
relies on being under-estimated — so a healthy dispatch measured with
the same model meshaudit proved the bound against stays in-bound.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from .clock import now_micros
from .events import journal
from .flags import flags
from .ordered_lock import OrderedLock
from .stats import stats

flags.define("flight_recorder_size", 1024,
             "flight-recorder records kept in the in-process ring "
             "(ticks + dispatches + timing probes) served by the "
             "/timeline web endpoint and SHOW TIMELINE")
flags.define("timeline_export_max_ticks", 256,
             "cap on recorder records one /timeline response or "
             "PROFILE FORMAT=trace export stitches — bounds response "
             "size the way event_journal_size bounds /events")


# ---------------------------------------------------------------- ICI
# collective primitive -> per-device exchange-byte factor at mesh size
# k, as a fraction of the operand bytes (the meshaudit static model,
# re-stated for the live path):
#   psum 2(k-1)/k | all_gather (k-1) | all_to_all / reduce_scatter /
#   psum_scatter / sharding_constraint (k-1)/k | ppermute / pbroadcast 1
def ici_exchange_bytes(op: str, operand_bytes: int, k: int) -> int:
    if k <= 1:
        return 0
    operand_bytes = int(operand_bytes)
    if op == "psum":
        return (2 * (k - 1) * operand_bytes) // k
    if op in ("all_gather", "all_gather_invariant"):
        return (k - 1) * operand_bytes
    if op in ("all_to_all", "reduce_scatter", "psum_scatter",
              "sharding_constraint"):
        return ((k - 1) * operand_bytes) // k
    return operand_bytes          # ppermute / pbroadcast: one hop


def collective_rows(ops: Iterable[Tuple[str, int]], k: int
                    ) -> List[dict]:
    """Per-collective live byte rows for one dispatch: ``ops`` is the
    (primitive, operand_bytes) list the dispatch site knows it moved
    (already trip-multiplied for multi-step kernels)."""
    return [{"op": op,
             "bytes": ici_exchange_bytes(op, nbytes, k)}
            for op, nbytes in ops]


class FlightRecorder:
    """Bounded ring of timeline records plus the drift-cell table.

    One leaf lock guards both; every public entry point is one lock
    acquisition, one dict build and one list append — cheap enough for
    the continuous pump's tick path and the dispatch hot path."""

    def __init__(self):
        self._lock = OrderedLock("flight.recorder")
        self._entries: List[dict] = []
        self._seq = 0
        # (axis, key) -> {"live", "declared", "over"}; axes are a small
        # closed set ("ici" per kernel class, "hbm" per timing kind)
        self._drift: Dict[Tuple[str, str], dict] = {}

    # ----------------------------------------------------- recording
    def _note(self, rec: dict) -> int:
        rec["time_us"] = now_micros()
        cap = int(flags.get("flight_recorder_size") or 1024)
        with self._lock:
            self._seq += 1
            rec["id"] = self._seq
            self._entries.append(rec)
            if len(self._entries) > cap:
                del self._entries[:len(self._entries) - cap]
            return self._seq

    def note_tick(self, stream: int, **fields) -> int:
        """One continuous-pump tick of the per-(space, OVER set)
        stream keyed ``stream``: seat churn counts (hold_joins: of the
        joins, the riders that arrived while the tick held its door;
        seat_hops: of the joins, the riders whose seat took their first
        hop, and join_rows, the rows the join scattered —
        tpu/runtime.py _ContinuousGoSession.join),
        per-phase micros
        in pump order (hold: the door held open while the device was
        busy with the hop in flight, 0 on a tick that did not wait —
        graph/batch_dispatch.py _hold; seat, then the
        join/hop/extract/clear ENQUEUES,
        then the leave cohort's fetch_wait/d2h/unpack/rows/handover,
        whose sum is assemble_us; rows is what the pump answers
        itself: the COUNT riders' fold, a WHERE that filters in
        numpy), what unpack_us met (unpack_leavers unpacked in the tick's cohorts,
        of them unpack_live whose set rows were at most
        LANE_UNPACK_LIVE_SHARE of the table, unpack_rows the set rows
        of all of them, unpack_native the leavers that went through
        the native pass — one call for the cohort, native/unpack.cc:
        all of unpack_leavers, or 0 where the library lacks the entry
        and numpy unpacks — summed over the cohorts — tpu/runtime.py
        _unpack_lanes), handed
        (the leavers whose frontier went to their own thread, which
        filters and makes the rows: every leaver but a COUNT rider and
        a WHERE the native pass cannot take — graph/batch_dispatch.py
        _finish), counted and count_us (the leavers answered by the
        device's per-lane count, k-hop neighbourhood counts, and the
        pump's wait for and read of it: the head of fetch_wait_us),
        distinct (the fetching leavers whose frontier IS their answer:
        k-hop neighbourhoods, reduce "distinct"),
        the hops whose branch
        the tick learned (hop_reads; of them hop_sparse pushed out of
        the live slot rows; hop_slots the ELL slots they visited, a
        pull the whole of the table it read; hop_swept the slots they
        gathered, a pull what its reach leaves of the table:
        ell.swept_slots; hop_onesided those that read one direction's
        table only: a stream is one OVER set, so all of hop_reads, or 0
        for the two-signed set of a GO ... BIDIRECT, whose pulls read
        both tables),
        idle gap since the previous tick, mirror generation, tick wall
        micros."""
        rec = {"kind": "tick", "stream": int(stream)}
        rec.update(fields)
        return self._note(rec)

    def note_beat(self, **fields) -> int:
        """One second of the host's two beats (common/hostclock.py):
        ``clock`` (schedstat, or thread_time where the machine has no
        run-queue clock and every ``*_runq_us`` is left off), the
        Python beat's ``n``, ``py_late_sum_us``, ``py_late_max_us``
        and, where the native beat runs, its ``nat_n``,
        ``nat_late_sum_us``, ``nat_late_max_us``."""
        rec = {"kind": "beat"}
        rec.update(fields)
        return self._note(rec)

    def note_dispatch(self, kernel: str, **fields) -> int:
        """One windowed/mesh kernel dispatch: kernel class, shape
        rung, h2d/d2h bytes, per-collective ICI rows when sharded."""
        rec = {"kind": "dispatch", "kernel": str(kernel)}
        rec.update(fields)
        return self._note(rec)

    def note_timing(self, op: str, wall_us: float, nbytes: int,
                    gbps: float) -> int:
        """One sampled device-timing probe — the rows the
        ``tpu_device_timing_every`` flag gates (tpu/runtime.py
        _maybe_time_device; windowed dispatch sites only)."""
        return self._note({"kind": "timing", "op": str(op),
                           "wall_us": round(float(wall_us), 1),
                           "bytes": int(nbytes),
                           "gbps": round(float(gbps), 3)})

    def note_sharded_dispatch(self, kernel: str, k: int,
                              ops: Iterable[Tuple[str, int]],
                              declared_bytes: int, **fields) -> int:
        """Dispatch record for a sharded kernel: derives the
        per-collective live ICI rows from ``ops`` via the byte model
        above and folds the total against the ``KernelSpec.ici_bytes``
        bound the dispatch site evaluated at its live shapes."""
        rows = collective_rows(ops, k)
        live = sum(r["bytes"] for r in rows)
        rec = self.note_dispatch(kernel, k=int(k), ici=rows,
                                 ici_bytes=live,
                                 ici_declared=int(declared_bytes),
                                 **fields)
        self.fold("ici", kernel, live, declared_bytes)
        return rec

    # --------------------------------------------------------- drift
    def fold(self, axis: str, key: str, live: float,
             declared: float) -> bool:
        """Fold one live measurement against its declared bound.
        Returns True when this fold TRANSITIONED the (axis, key) cell
        to over-bound — that edge records the typed event; staying
        over does not re-fire, returning in-bound re-arms."""
        live = float(live)
        declared = float(declared)
        over = declared > 0 and live > declared
        with self._lock:
            cell = self._drift.get((axis, key))
            if cell is None:
                cell = self._drift[(axis, key)] = {
                    "live": 0.0, "declared": 0.0, "over": False}
            fired = over and not cell["over"]
            cell["live"] = live
            cell["declared"] = declared
            cell["over"] = over
        if fired:
            journal.record(
                "tpu.model_drift",
                f"live {axis} traffic for {key} exceeds the declared "
                f"model bound",
                axis=axis, key=key, live=round(live, 3),
                declared=round(declared, 3))
        return fired

    def drift_cells(self) -> Dict[str, dict]:
        """``"axis/key" -> cell`` snapshot (tests, SHOW TIMELINE)."""
        with self._lock:
            return {f"{a}/{key}": dict(c)
                    for (a, key), c in self._drift.items()}

    # --------------------------------------------------------- reads
    def dump(self, limit: int = 64) -> List[dict]:
        """Newest-first snapshot for /timeline and SHOW TIMELINE
        (the events.dump ordering)."""
        with self._lock:
            out = list(reversed(self._entries[-max(int(limit), 0):]))
        return [dict(e) for e in out]

    def export(self, limit: Optional[int] = None) -> List[dict]:
        """Oldest-first tail for trace stitching, clamped by
        ``timeline_export_max_ticks``."""
        cap = int(flags.get("timeline_export_max_ticks") or 256)
        n = cap if limit is None else max(0, min(int(limit), cap))
        with self._lock:
            out = self._entries[-n:] if n else []
            return [dict(e) for e in out]

    # ------------------------------------------------ gauge collector
    def _collect(self) -> None:
        """Scrape-time collector: recorder occupancy plus one
        ``tpu.model_drift.<axis>`` series per drift cell carrying the
        overshoot FRACTION (0.0 while live <= declared).  The gauge
        table is cleared before collectors run, so cells publish their
        current verdict every scrape — fire-and-clear for free."""
        with self._lock:
            n = len(self._entries)
            cells = [(a, key, c["live"], c["declared"])
                     for (a, key), c in self._drift.items()]
        stats.set_gauge("tpu.flight.records", n)
        for axis, key, live, declared in cells:
            over = max(0.0, live / declared - 1.0) if declared > 0 \
                else 0.0
            stats.set_gauge(f"tpu.model_drift.{axis}", round(over, 6),
                            key=key)

    def clear_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()
            self._drift.clear()
            self._seq = 0


recorder = FlightRecorder()
stats.register_collector(recorder._collect)


# ------------------------------------------------------- trace export
_HOST_PID = 1          # the span-tree rows
_PUMP_PID = 2          # the flight-recorder rows (host clock too)
_DISPATCH_TID = 1
_TIMING_TID = 2
_STREAM_TID_BASE = 10  # continuous stream S renders as tid 10+S


def _span_events(node: dict, tid: int, out: List[dict]) -> None:
    out.append({"ph": "X", "pid": _HOST_PID, "tid": tid, "cat": "host",
                "name": str(node.get("name", "?")),
                "ts": int(node.get("start_us", 0)),
                "dur": int(node.get("duration_us", 0)),
                "args": {str(k): v for k, v in
                         sorted((node.get("tags") or {}).items())}})
    for child in node.get("children") or ():
        _span_events(child, tid, out)


# per-tick phases, in pump execution order — rendered as nested
# slices inside the tick so the "where do the busy-ms go" question is
# answered visually (batch_dispatch._tick records the micros; the first
# is the door held open behind a busy device, the last five are the
# parts of assemble_us)
_TICK_PHASES = ("hold_us", "seat_us", "join_us", "hop_us", "extract_us",
                "clear_us", "fetch_wait_us", "d2h_us", "unpack_us",
                "rows_us", "handover_us")


def chrome_trace(tree: Optional[dict] = None,
                 ticks: Iterable[dict] = (),
                 seat: Optional[dict] = None) -> dict:
    """Stitch a span tree, seat markers and recorder rows into one
    Chrome-trace/Perfetto JSON object ({"traceEvents": [...]}).  Pure
    function of its inputs: same tree + same ticks -> byte-identical
    output (the golden-timeline pin relies on this)."""
    ev: List[dict] = [
        {"ph": "M", "pid": _HOST_PID, "tid": 0, "name": "process_name",
         "args": {"name": "host spans"}},
        {"ph": "M", "pid": _PUMP_PID, "tid": 0,
         "name": "process_name",
         "args": {"name": "pump flight recorder (host)"}},
        {"ph": "M", "pid": _PUMP_PID, "tid": _DISPATCH_TID,
         "name": "thread_name", "args": {"name": "dispatch"}},
        {"ph": "M", "pid": _PUMP_PID, "tid": _TIMING_TID,
         "name": "thread_name", "args": {"name": "device timing"}},
    ]
    if tree:
        for root in tree.get("roots") or ():
            _span_events(root, 1, ev)
        if seat:
            roots = tree.get("roots") or [{}]
            ev.append({"ph": "i", "s": "t", "pid": _HOST_PID, "tid": 1,
                       "name": "seat",
                       "ts": int(roots[0].get("start_us", 0)),
                       "args": {str(k): v for k, v in
                                sorted(seat.items())}})
    streams_named = set()
    for rec in ticks:
        kind = rec.get("kind")
        ts = int(rec.get("time_us", 0))
        if kind == "tick":
            tid = _STREAM_TID_BASE + int(rec.get("stream", 0))
            if tid not in streams_named:
                streams_named.add(tid)
                ev.append({"ph": "M", "pid": _PUMP_PID, "tid": tid,
                           "name": "thread_name",
                           "args": {"name":
                                    f"stream {rec.get('stream', 0)}"}})
            dur = int(rec.get("dur_us", 0))
            start = ts - dur
            args = {k: v for k, v in sorted(rec.items())
                    if k not in ("kind", "time_us")}
            ev.append({"ph": "X", "pid": _PUMP_PID, "tid": tid,
                       "cat": "tick", "name": "tick", "ts": start,
                       "dur": dur, "args": args})
            cursor = start
            for phase in _TICK_PHASES:
                us = int(rec.get(phase) or 0)
                if us <= 0:
                    continue
                ev.append({"ph": "X", "pid": _PUMP_PID, "tid": tid,
                           "cat": "phase", "name": phase[:-3],
                           "ts": cursor, "dur": us, "args": {}})
                cursor += us
        elif kind == "beat":
            continue               # the host's beats are no device row
        elif kind == "timing":
            dur = int(rec.get("wall_us") or 0)
            ev.append({"ph": "X", "pid": _PUMP_PID,
                       "tid": _TIMING_TID, "cat": "timing",
                       "name": str(rec.get("op", "?")),
                       "ts": ts - dur, "dur": dur,
                       "args": {"bytes": rec.get("bytes", 0),
                                "gbps": rec.get("gbps", 0.0)}})
        else:                      # dispatch rows render as markers
            args = {k: v for k, v in sorted(rec.items())
                    if k not in ("kind", "time_us")}
            ev.append({"ph": "i", "s": "p", "pid": _PUMP_PID,
                       "tid": _DISPATCH_TID,
                       "name": str(rec.get("kernel", "dispatch")),
                       "ts": ts, "args": args})
    return {"displayTimeUnit": "ms", "traceEvents": ev}
