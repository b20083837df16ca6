"""Event journal — clock-stamped ring buffer of notable cluster events.

The metrics plane's third leg (beside counters/gauges and traces): a
bounded, process-wide journal of the DISCRETE things an operator asks
"what just happened?" about — leader elections and step-downs,
membership and balancer moves, meta catalog writes, injected faults,
and slow queries.  Served raw at every daemon's ``/events`` endpoint,
piggybacked on storaged heartbeats to metad (meta/client.py),
aggregated cluster-wide there (meta/service.py rpc_listEvents), and
surfaced in nGQL as ``SHOW EVENTS`` (docs/observability.md).

Shape: the journal mirrors TraceStore — an OrderedLock-guarded ring
(``event_journal_size``), entries stamped with clock.now_micros() so
``clock.advance_for_tests`` ages them deterministically.  Each entry
carries a process-unique 63-bit ``id``: the cluster aggregation dedups
on it, so an event that reaches metad twice (heartbeat piggyback AND
the shared in-process journal of a LocalCluster) lands once.

Kinds are a closed set (``EVENT_KINDS``) so dashboards and tests can
match exactly — ``record`` refuses unknown kinds at runtime, the cheap
analogue of the span/metric registry lint contracts.
"""
from __future__ import annotations

import random
import threading
from typing import Dict, List, Optional, Tuple

from .clock import now_micros
from .flags import flags
from .ordered_lock import OrderedLock
from .stats import stats

flags.define("event_journal_size", 512,
             "events kept in the in-process ring buffer served by the "
             "/events web endpoint and heartbeat-forwarded to metad")

EVENT_KINDS = (
    "raft.leader_elected",   # a part won an election (space/part/term)
    "raft.step_down",        # a LEADER reverted to follower
    "raft.membership",       # learner/peer add/remove took effect
    "balancer.move",         # one BalanceTask moved a part replica
    "meta.catalog_write",    # a DDL/config write landed in the catalog
    "fault.injected",        # the wire-level fault injector fired
    "query.slow",            # a statement crossed slow_query_threshold_ms
    "query.shed",            # admission control rejected a query
                             # (queue full / budget provably unmeetable
                             # — graph/batch_dispatch.py)
    "query.joined_midflight",  # a query's start frontier OR-merged
                             # into an ALREADY-RUNNING continuous lane
                             # batch at a hop boundary
                             # (graph/batch_dispatch.py
                             # _ContinuousStream, docs/admission.md)
    "wal.truncated",         # recovery cut unverifiable frames off a
                             # WAL segment (kvstore/wal.py CRC check —
                             # docs/durability.md)
    "tpu.breaker_open",      # the device circuit breaker opened for a
                             # (space, kernel-class): queries decline to
                             # the CPU path until a half-open probe
                             # re-admits the device (tpu/runtime.py)
    "node.recovered",        # a daemon booted over existing durable
                             # state and recovered its parts' commit
                             # watermarks (cluster.py / daemons)
    "mirror.absorbed",       # a committed write delta folded into the
                             # resident device tables as a new mirror
                             # generation (tpu/runtime.py absorb path,
                             # docs/durability.md)
    "mirror.absorb_failed",  # an absorption declined — a full rebuild
                             # is about to be paid instead.  The
                             # ``reason`` payload is CLOSED the same
                             # way this tuple is: it must be one of
                             # common/protocol.py's "absorb-decline" /
                             # "peer-delta" constants (the
                             # protocol-registry lint pass proves the
                             # producers only emit those)
    "mirror.peer_absorbed",  # a PEER's committed writes streamed over
                             # deviceScanDelta and folded into the
                             # resident device tables at O(delta) —
                             # the multi-host absorb path
                             # (storage/device.py RemoteStoreView,
                             # docs/durability.md)
    "net.partitioned",       # a directional link cut was installed
                             # (FaultInjector.partition — this
                             # process's outbound calls to the named
                             # host now blackhole;
                             # docs/fault_injection.md)
    "net.healed",            # directional link cuts matching a host
                             # pattern were removed (FaultInjector.heal)
    "query.killed",          # KILL QUERY <id> ended a statement —
                             # seated continuous riders evict at the
                             # next hop boundary, windowed/queued
                             # waiters wake typed E_KILLED
                             # (graph/query_registry.py,
                             # docs/observability.md)
    "slo.burn_alert",        # a declared SLO's burn rate crossed its
                             # threshold on BOTH windows of a pair
                             # (fast or slow) — or recovered; the
                             # ``state`` field says which
                             # (common/slo.py, docs/observability.md
                             # "SLO burn rates")
    "tpu.model_drift",       # a live measurement crossed its DECLARED
                             # static-model bound: per-collective ICI
                             # bytes over KernelSpec.ici_bytes, or
                             # achieved GB/s over MESH_MODEL's
                             # hbm_gbps (common/flight.py fold — fires
                             # on the in-bound -> over transition,
                             # re-arms when the cell returns in-bound;
                             # docs/observability.md "The device
                             # timeline")
    "host.stall",            # the host stood still: a beat of
                             # common/hostclock.py was over 100 ms
                             # late, or the pump waited that long for
                             # the device with both beats on time.
                             # ``who``: host (the beat that needs no
                             # interpreter was late: the guest was
                             # paused or had no core), interpreter
                             # (the Python beat alone: something held
                             # the lock), device (docs/observability.md
                             # "The device timeline")
)

_rng = random.Random()       # event ids; independent of seeded test RNGs

stats.register_stats("events.recorded")


class EventJournal:
    """Bounded ring of event dicts, oldest evicted first."""

    def __init__(self):
        # seam-constructed (common/mc_hooks.py): the real OrderedLock
        # in production; nebulamc's journal-cursor scenario swaps in an
        # instrumented shim to interleave record() against since()
        from . import mc_hooks
        self._lock = mc_hooks.OrderedLock("events.journal")
        self._entries: List[dict] = []
        self._seq = 0

    def record(self, kind: str, detail: str = "", **fields) -> dict:
        """Append one event.  ``fields`` are structured extras (space,
        part, term, host, ...) merged into the entry.  Cheap enough to
        call from consensus paths — one lock, one dict, no I/O."""
        if kind not in EVENT_KINDS:
            raise ValueError(f"unknown event kind {kind!r} "
                             f"(register it in EVENT_KINDS first)")
        entry = {"id": _rng.getrandbits(63), "kind": kind,
                 "time_us": now_micros(), "detail": str(detail)}
        for k, v in fields.items():
            if v is not None:
                entry[k] = v
        cap = int(flags.get("event_journal_size") or 512)
        with self._lock:
            self._seq += 1
            entry["seq"] = self._seq
            self._entries.append(entry)
            if len(self._entries) > cap:
                del self._entries[:len(self._entries) - cap]
        stats.add_value("events.recorded")
        return entry

    def since(self, seq: int, limit: int = 64) -> Tuple[List[dict], int]:
        """Events with seq > ``seq``, OLDEST first and capped at
        ``limit``, plus the seq of the last event RETURNED — the
        heartbeat piggyback cursor.  Capping keeps the oldest and the
        cursor tracks what was actually handed out, so a burst larger
        than one beat's budget drains over several beats instead of
        silently dropping its head."""
        with self._lock:
            out = [e for e in self._entries if e["seq"] > seq]
        if len(out) > limit:
            out = out[:limit]
        last = out[-1]["seq"] if out else seq
        return [dict(e) for e in out], last

    def dump(self, limit: int = 100) -> List[dict]:
        """Newest-first snapshot for /events and SHOW EVENTS."""
        with self._lock:
            out = list(reversed(self._entries[-max(int(limit), 0):]))
        return [dict(e) for e in out]

    def clear_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()


journal = EventJournal()


def merge_events(*sources: List[dict], limit: int = 200) -> List[dict]:
    """Dedup-by-id merge of event lists, newest first, capped — THE
    ordering every surface shares (metad rpc_listEvents, graphd's
    SHOW EVENTS executor).  Earlier sources win on id collisions."""
    out: Dict[int, dict] = {}
    for events in sources:
        for e in events:
            if isinstance(e, dict) and "id" in e:
                out.setdefault(e["id"], e)
    rows = sorted(out.values(),
                  key=lambda e: (e.get("time_us", 0), e.get("id", 0)),
                  reverse=True)
    return rows[:max(int(limit), 0)]


class ClusterEventStore:
    """Metad-side aggregation of events reported over heartbeats,
    deduped by event id and bounded like the local journal.  Kept
    separate from EventJournal because absorbed entries arrive with
    their own ids/stamps and a reporting ``host``."""

    def __init__(self):
        self._lock = OrderedLock("events.cluster")
        self._by_id: "Dict[int, dict]" = {}
        self._order: List[int] = []

    def absorb(self, host: Optional[str], events) -> None:
        if not events:
            return
        cap = int(flags.get("event_journal_size") or 512)
        with self._lock:
            for e in events:
                if not isinstance(e, dict) or "id" not in e \
                        or e.get("kind") not in EVENT_KINDS:
                    continue
                eid = e["id"]
                if eid in self._by_id:
                    continue
                e = dict(e)
                if host and "host" not in e:
                    e["host"] = host
                self._by_id[eid] = e
                self._order.append(eid)
            while len(self._order) > cap:
                self._by_id.pop(self._order.pop(0), None)

    def merged(self, local: List[dict], limit: int = 200) -> List[dict]:
        """Cluster view: absorbed events + the caller's local snapshot,
        deduped by id, newest first (merge_events ordering)."""
        with self._lock:
            absorbed = list(self._by_id.values())
        return merge_events(absorbed, local, limit=limit)

    def clear_for_tests(self) -> None:
        with self._lock:
            self._by_id.clear()
            self._order.clear()
