"""Live query registry — the serving control plane's eyes on what is
running RIGHT NOW (docs/observability.md "The live query plane").

Counters say how many queries ran; traces say what one sampled query
did; nothing between PR 15's continuous seat map and an operator says
*which statements are seated in a lane batch at this instant* — or
lets the operator end one.  This module closes that gap: every
admitted statement registers a process-unique query id carrying its
session, statement text, query class, space, dispatch mode, current
phase/hop, lane seat, elapsed time, and deadline remaining.  Surfaces:

  * ``SHOW QUERIES`` — graphd → metad ``showQueries`` fan-out across
    every heartbeating graphd replica (the SHOW STATS shape);
  * ``GET /queries`` — every daemon's webservice, local registry only;
  * ``KILL QUERY <id>`` — marks the entry killed; the statement ends
    TYPED (``ErrorCode.E_KILLED``) through the machinery it is already
    inside: a seated continuous rider evicts at the next hop boundary
    (``protocol.END_KILLED``), a queued/windowed waiter wakes through
    the per-query exception path, and the engine checks between
    sentences (graph/batch_dispatch.py, graph/service.py).

The registry is a process singleton like TraceStore and the event
journal: one OrderedLock-guarded dict capped at
``query_registry_size`` (statements past the cap still run — they are
just not visible/killable, and ``graph.query_registry.overflow``
counts them).  The ambient query id travels the same way deadlines do
(``bind``/``current`` thread-local), so dispatch riders capture it at
construction without new plumbing through every call signature.
"""
from __future__ import annotations

import random
import threading
from typing import Dict, List, Optional

from ..common import deadline as deadlines
from ..common.clock import now_micros
from ..common.flags import flags
from ..common.ordered_lock import OrderedLock
from ..common.stats import stats

flags.define("query_registry_size", 1024,
             "live statements tracked by the query registry (SHOW "
             "QUERIES / /queries / KILL QUERY); statements admitted "
             "past the cap still execute but are not visible or "
             "killable")

stats.register_stats("graph.query_registry.registered")
stats.register_stats("graph.query_registry.finished")
stats.register_stats("graph.query_registry.killed")
stats.register_stats("graph.query_registry.overflow")


class KilledError(RuntimeError):
    """The statement was ended by ``KILL QUERY <id>``.  Mapped to
    ``ErrorCode.E_KILLED`` at the engine boundary — deliberately NOT a
    DeadlineExceeded subclass so kill and budget-exhaustion stay
    distinguishable in every counter and client response."""


# process-unique id space: a random 16-bit process tag above a local
# sequence — two graphd replicas can never mint the same id, so the
# metad killQuery fan-out cannot end the wrong replica's query.
# Private Random: independent of seeded test RNGs (the event-id
# stance, common/events.py).
_PROC_TAG = random.Random().getrandbits(16) << 40

_tls = threading.local()          # .qid = int | None


def bind(qid: Optional[int]):
    """Context manager binding the ambient query id for this thread
    (the deadlines.bind shape) — dispatch riders capture it via
    ``current()`` at construction."""
    return _Bind(qid)


class _Bind:
    __slots__ = ("qid", "_prev")

    def __init__(self, qid: Optional[int]):
        self.qid = qid

    def __enter__(self):
        self._prev = getattr(_tls, "qid", None)
        _tls.qid = self.qid
        return self.qid

    def __exit__(self, *exc):
        _tls.qid = self._prev
        return False


def current() -> Optional[int]:
    """The executing thread's ambient query id, if any."""
    return getattr(_tls, "qid", None)


class _Entry:
    __slots__ = ("qid", "session", "user", "stmt", "cls", "space",
                 "mode", "phase", "hop", "lane", "joined_tick",
                 "left_tick", "waits", "ending", "tl_first", "tl_last",
                 "start_us", "deadline", "kill_flag")

    def __init__(self, qid, session, user, stmt, cls, space, mode,
                 dl):
        self.qid = qid
        self.session = session
        self.user = user
        self.stmt = stmt
        self.cls = cls
        self.space = space
        self.mode = mode
        self.phase = "admitted"
        self.hop = -1
        self.lane = -1
        self.joined_tick = -1
        self.left_tick = -1
        self.waits = None         # seat_wait/ride/result_wait/wake us
        self.ending = None        # protocol continuous-ending, once done
        self.tl_first = -1        # first/last flight-recorder tick id
        self.tl_last = -1         # for the rider's stream (flight.py)
        self.start_us = now_micros()
        self.deadline = dl
        self.kill_flag = False

    def row(self) -> dict:
        dl_left = (round(self.deadline.remaining_ms(), 1)
                   if self.deadline is not None else None)
        return {"id": self.qid, "session": self.session,
                "user": self.user, "stmt": self.stmt,
                "class": self.cls, "space": self.space,
                "mode": self.mode, "phase": self.phase,
                "hop": self.hop, "lane": self.lane,
                "joined_tick": self.joined_tick,
                "elapsed_us": now_micros() - self.start_us,
                "deadline_left_ms": dl_left,
                "killed": self.kill_flag}


class QueryRegistry:
    """Process-global registry of in-flight statements."""

    def __init__(self):
        self._lock = OrderedLock("graph.query_registry")
        self._entries: Dict[int, _Entry] = {}
        self._seq = 0
        stats.register_collector(self._collect_gauges)

    # ------------------------------------------------------ lifecycle
    def register(self, stmt: str, session: int = -1, user: str = "",
                 cls: str = "", space: str = "",
                 mode: str = "windowed") -> Optional[int]:
        """Admit one statement; returns its query id, or None when the
        registry is at ``query_registry_size`` (the statement still
        runs, untracked)."""
        cap = int(flags.get("query_registry_size") or 1024)
        dl = deadlines.current()
        with self._lock:
            if len(self._entries) >= cap:
                stats.add_value("graph.query_registry.overflow")
                return None
            self._seq += 1
            qid = _PROC_TAG | self._seq
            self._entries[qid] = _Entry(qid, session, user, stmt, cls,
                                        space, mode, dl)
        stats.add_value("graph.query_registry.registered")
        return qid

    def unregister(self, qid: Optional[int]) -> None:
        if qid is None:
            return
        with self._lock:
            self._entries.pop(qid, None)
        stats.add_value("graph.query_registry.finished")

    # ----------------------------------------------------- updates
    # phase/seat/hop notes are fire-and-forget lock-free fast paths:
    # entries are only ever removed (never mutated back in), dict get
    # is atomic, and an entry evicted by a concurrent unregister just
    # drops the note
    def note_phase(self, qid: Optional[int], phase: str) -> None:
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            e.phase = phase

    def note_seat(self, qid: Optional[int], lane: int,
                  joined_tick: int) -> None:
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            e.lane = lane
            e.joined_tick = joined_tick
            e.phase = "seated"

    def note_hop(self, qid: Optional[int], hop: int) -> None:
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            e.hop = hop

    def note_ending(self, qid: Optional[int], ending: str) -> None:
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            e.ending = ending

    def note_waits(self, qid: Optional[int], left_tick: int,
                   waits: dict) -> None:
        """The waits a rider's submit() was made of (batch_dispatch
        _ContinuousStream._waits) and the tick it left on."""
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            e.left_tick = left_tick
            e.waits = waits

    def note_timeline(self, qid: Optional[int], rec_id: int) -> None:
        """Anchor the rider's stream to a flight-recorder tick id
        (common/flight.py): the first note pins tl_first, every note
        advances tl_last — the pump calls this once per tick per
        seated rider."""
        e = self._entries.get(qid) if qid is not None else None
        if e is not None:
            if e.tl_first < 0:
                e.tl_first = rec_id
            e.tl_last = rec_id

    def seat_markers(self, qid: Optional[int]) -> Optional[dict]:
        """The continuous-tier seat trajectory of a still-registered
        statement — lane, joined_tick, left_tick, hop count, typed
        ending, the five waits (tracing.RIDER_WAITS) and the [first,
        last] recorder tick-id window — or None when it never rode a
        lane batch.  The engine folds this into
        slow-query-log entries before unregistering."""
        e = self._entries.get(qid) if qid is not None else None
        if e is None or (e.lane < 0 and e.ending is None):
            return None
        out = {"lane": e.lane, "joined_tick": e.joined_tick,
               "hops": e.hop, "ending": e.ending}
        if e.waits is not None:     # submit() returned: note_waits
            out["left_tick"] = e.left_tick
            out.update(e.waits)
        if e.tl_first >= 0:
            out["timeline"] = [e.tl_first, e.tl_last]
        return out

    # ------------------------------------------------------- kill
    def kill(self, qid: int) -> bool:
        """Mark ``qid`` killed.  Returns whether the id was live here —
        the metad fan-out ORs the per-replica answers."""
        with self._lock:
            e = self._entries.get(qid)
            if e is None:
                return False
            e.kill_flag = True
        stats.add_value("graph.query_registry.killed")
        return True

    def is_killed(self, qid: Optional[int]) -> bool:
        """Lock-free hot-path probe (per hop boundary / per window) —
        one atomic dict get plus an attribute read."""
        if qid is None:
            return False
        e = self._entries.get(qid)
        return e is not None and e.kill_flag

    def check_killed(self, qid: Optional[int]) -> None:
        """Raise KilledError when ``qid`` was killed — the engine's
        between-sentences checkpoint."""
        if self.is_killed(qid):
            raise KilledError("query killed by KILL QUERY")

    # ------------------------------------------------------ surfaces
    def snapshot(self) -> List[dict]:
        """Live entries as plain dicts, oldest first — /queries and
        the showQueries RPC serve this verbatim."""
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: e.start_us)
        return [e.row() for e in entries]

    def size(self) -> int:
        with self._lock:
            return len(self._entries)

    def _collect_gauges(self) -> None:
        stats.set_gauge("graph.query_registry.size", self.size())

    def clear_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()


registry = QueryRegistry()
