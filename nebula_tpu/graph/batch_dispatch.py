"""GoBatchDispatcher — coalesce concurrent device queries into one
dispatch (GO executions and FIND PATH BFS depths share the seam), with
deadline-aware admission control in front (docs/admission.md).

The batched ELL engine (tpu/ell.py) amortises the TPU's per-row-access
floor across the whole batch, so the serving layer must feed it
batches.  graphd's RPC server runs each query on its own thread
(interface/rpc.py ThreadingTCPServer — the analogue of the reference's
IOThreadPool + worker pools, StorageServer.cpp:92-96); this dispatcher
is the seam where those threads merge: requests with the same
(space, OVER set, steps) shape queue up, one waiter at a time becomes
the dispatching leader, and everyone blocks until their own result is
filled in.

Pipelining (round 3): a batch runs in two phases.  The leader LAUNCHES
the device work (async under JAX), then immediately releases
leadership so the next batch's leader can launch while this batch's
transfer + host assembly (`finish`) complete — device compute and
host post-processing overlap instead of serializing.  In-flight
batches are bounded by ``go_batch_inflight``; under admission control
the slots hand out in PRIORITY order (cheap 1-hop GO ahead of deep
FIND PATH BFS — the per-query-class ladder).

Failure isolation (round 3): the runtime returns per-query results in
which individual entries may be Exception instances; only their own
waiters see them.  A batch-level failure (device error, infra) still
wakes everyone with the error — but a poisoned query no longer fails
its 1023 innocent neighbours (the reference's semantics are per-request
partial failure, StorageClient.h:22-72).

Admission control (round 6): the old dispatcher admitted everything —
at 64 workers FIND PATH p50 tripled because every thread piled onto
the queue behind a static 25 ms window.  Now each key's queue is
BOUNDED (``admission_queue_max``), a query whose remaining deadline
budget (common/deadline.py) provably cannot cover the queue ahead of
it is REJECTED at admission (fast failure — an AdmissionShed surfaces
as DEADLINE_EXCEEDED with the partial-result completeness/warning
machinery, never a hang), entries whose budget ran out while queued
are dropped from the batch BEFORE launch and their waiters woken with
DEADLINE_EXCEEDED through the per-query-exception machinery, and the
static window cap is replaced by a closed-loop controller
(_WindowController) that tracks queue depth and dispatch latency:
deep queues already pool, so the artificial wait collapses to zero
exactly when it would only add latency.

Continuous dispatch (round 15, docs/admission.md "Continuous
dispatch"): the windowed pipeline above still serves DISCRETE batches
— every window pays a pooling wait, a full h2d/compute/d2h round trip,
and a device-idle gap before the next window forms.  With
``go_dispatch_mode=continuous`` (the default) multi-hop GO queries
instead join and leave ONE in-flight lane batch per (space, OVER set)
at hop boundaries, LLM-serving style: the lane dimension of the
resident frontier (one bit a query; tpu/ell.py owns the layout) is
the seat map (_LaneLedger), a
finishing query's lanes clear at its last hop, and a queued arrival's
start frontier is scatter-merged into the freed lanes before the next
hop dispatches (tpu/runtime.py _ContinuousGoSession).  No recompile
moves: the lane width stays on the go_batch_widths rung ladder — only
lane OCCUPANCY changes, and occupancy is data.  The pump pipeline is
double-buffered: while hop k computes, the host assembles hop k-1's
leavers and uploads the next joiners (tpu.device_idle_frac proves the
overlap).  The windowed path is kept verbatim as the bit-exact parity
oracle and rollback (``go_dispatch_mode=windowed``); BFS, mesh-sharded
spaces and single-hop queries stay on their existing paths.

The reference has no cross-query batching (each GO is its own RPC
fan-out); this is TPU-native serving the same way the reference's
per-request vertex bucketing (QueryBaseProcessor.inl:433-460) is
CPU-native parallelism.
"""
from __future__ import annotations

import heapq
import math
import threading
import time
from typing import Dict, List, Tuple

from ..common import deadline as deadlines
from ..common import flight
from ..common import hostclock
from ..common import mc_hooks
from ..common import protocol
from ..common import tracing
from ..common.clock import now_micros
from ..common.deadline import DeadlineExceeded
from ..common.events import journal
from ..common.flags import flags
from ..common.stats import stats
from .query_registry import (KilledError, current as current_qid,
                             registry as query_registry)

flags.define("go_batch_window_ms", -1,
             "WINDOWED-mode batch-leader wait before dispatching "
             "coalesced device queries — GO and FIND PATH both "
             "(continuous-mode GO never sleeps: arrivals merge at the "
             "next hop boundary instead).  -1 (default): ADAPTIVE — "
             "the wait tracks go_batch_window_frac of the key's "
             "recent batch round-trip, capped by the closed-loop "
             "controller (_WindowController: the go_batch_window_max_ms "
             "ceiling scales DOWN with queue depth), so a slow batch "
             "round-trip pools wide batches while a loaded or fast "
             "dispatcher pays ~nothing.  0: dispatch immediately; >0: "
             "fixed wait in ms (bypasses the controller entirely)")
flags.define("go_batch_window_frac", 0.12,
             "adaptive window as a fraction of the EMA batch "
             "round-trip (launch -> results ready), capped by the "
             "closed-loop controller (go_batch_window_max_ms scaled "
             "down as queue depth grows).  The sparse kernel's result "
             "transfer is FIXED-SIZE per batch (the final pair-list "
             "cap), so fewer/fuller batches cut total fetched bytes "
             "directly.  The value was tuned on hardware that no "
             "longer exists (ROADMAP D4)")
flags.define("go_batch_window_max_ms", 25,
             "upper bound of the adaptive batch window when the "
             "dispatcher is otherwise idle (tuned on hardware that no "
             "longer exists, ROADMAP D4).  Under load "
             "the effective cap is this value scaled DOWN by the "
             "closed-loop controller: queue depth already pools "
             "arrivals, so sleeping on top of it only adds latency "
             "(admission_window_depth_ref)")
flags.define("go_batch_max", 1024,
             "max coalesced queries (GO or FIND PATH) per device dispatch")
flags.define("go_batch_inflight", 3,
             "max device batches in flight across the two-phase "
             "dispatch pipeline (launch overlaps the previous batch's "
             "transfer + host assembly).  The result transfer is "
             "fixed-size per batch, so a deeper pipeline means more, "
             "smaller batches moving more total bytes; 3 was tuned on "
             "hardware that no longer exists (ROADMAP D4)")

# ---- admission control (docs/admission.md) --------------------------
flags.define("admission_control", True,
             "deadline-aware admission in the batch dispatcher: "
             "bounded per-(space, shape) queues, load shedding when a "
             "query provably cannot meet its remaining deadline "
             "budget, pre-launch expiry drops, and priority-ordered "
             "pipeline slots.  Off restores the round-3 admit-"
             "everything behavior (the window controller and stats "
             "stay live either way)")
flags.define("admission_queue_max", 256,
             "per-(space, shape-key) queue bound: a submit finding "
             "this many requests already queued on its key is shed "
             "immediately (fast DEADLINE_EXCEEDED failure) instead of "
             "joining a queue that only grows the tail")
flags.define("admission_window_depth_ref", 8,
             "closed-loop window controller reference depth: the "
             "effective pooling-window cap is go_batch_window_max_ms "
             "/ (1 + depth_ema / ref) — at the reference depth the "
             "cap halves, and a saturated queue drives it toward 0 "
             "because arrivals already pool behind in-flight batches. "
             "Also the autoscale signal's reference: "
             "graph.autoscale.recommended_replicas grows as depth_ema "
             "passes multiples of this depth (docs/admission.md)")

# ---- continuous dispatch (docs/admission.md "Continuous dispatch") --
flags.define("go_dispatch_mode", "continuous",
             "multi-hop GO dispatch pipeline: 'continuous' (default) "
             "keeps one in-flight lane batch per (space, OVER set) — "
             "queries join/leave at hop boundaries over a resident "
             "packed frontier, the device never idles between windows "
             "— 'windowed' restores the discrete coalescing pipeline "
             "(the bit-exact parity oracle and rollback).  BFS, "
             "single-hop GO and mesh-sharded dispatch "
             "always use the windowed pipeline.  Managed: UPDATE "
             "CONFIGS graph:go_dispatch_mode=...")
flags.define("autoscale_max_replicas", 8,
             "ceiling of the graph.autoscale.recommended_replicas "
             "gauge — the window controller's depth EMA plus the "
             "recent shed rate, expressed as a graphd replica count "
             "for an external autoscaler (proc_cluster boots them; "
             "docs/admission.md)")


# registered at import (not per-dispatcher) so SHOW STATS always has
# the admission rows, zero until the first shed (docs/admission.md)
stats.register_stats("graph.admission.shed")
stats.register_stats("graph.admission.deadline_exceeded")
stats.register_histogram("graph.admission.wait_us")
# continuous-dispatch lifecycle (zero in windowed mode): every seat
# grant is a join, every completed extraction a leave, every
# deadline/drain removal an eviction; occupancy is observed once per
# hop tick (seat-count buckets, not latency buckets)
stats.register_stats("graph.continuous.joins")
stats.register_stats("graph.continuous.leaves")
stats.register_stats("graph.continuous.evictions")
# of the joins, the riders whose seat took their first hop: the session
# scattered their first frontier (_ContinuousGoSession.join)
stats.register_stats("graph.continuous.seat_hops")
# the pump's hold (_ContinuousStream._hold): the micros it held the
# door of a tick open behind a busy device, and the riders it seated
# that arrived meanwhile
stats.register_stats("graph.continuous.hold_us")
stats.register_stats("graph.continuous.held_joins")
stats.register_histogram("graph.continuous.lane_occupancy",
                         buckets=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0,
                                  64.0, 128.0, 256.0, 512.0, 1024.0))


class AdmissionShed(DeadlineExceeded):
    """Rejected at admission — the queue is full or the remaining
    deadline budget provably cannot cover the work ahead.  A shed is a
    DEADLINE_EXCEEDED to every upper layer (fast typed failure with
    completeness < 100, docs/admission.md), with the shed reason kept
    for stats/journal."""

    def __init__(self, msg: str, reason: str):
        super().__init__(msg)
        self.reason = reason


class _Request:
    __slots__ = ("payload", "done", "result", "mirror", "error",
                 "deadline", "enq_t", "qid", "run_t", "done_t", "riders")

    def __init__(self, payload, deadline=None):
        self.payload = payload   # per-query input, method-defined (GO:
        self.done = False        # _GoQuery; BFS: (srcs, dsts)); the
                                 # leader maps ids against ONE mirror
        self.result = None               # per-query result of the batch
        self.mirror = None
        self.error = None
        self.deadline = deadline         # common/deadline.py Deadline|None
        self.enq_t = time.perf_counter()
        # set by the leader that took this request into its batch
        # (_run): when the batch started and ended, how many rode it
        self.run_t = self.done_t = None
        self.riders = 0
        # live-query-registry id (KILL QUERY's handle on this waiter),
        # captured thread-locally like the deadline budget
        self.qid = current_qid()


class _KeyState:
    __slots__ = ("cond", "queue", "dispatching", "rt_ema_s")

    def __init__(self):
        # constructed through the mc seam: a plain threading.Condition
        # in production, an instrumented shim while a nebulamc scenario
        # explores this key's leader election (docs/static_analysis.md)
        self.cond = mc_hooks.Condition("dispatch.key")
        self.queue: List[_Request] = []
        self.dispatching = False
        # EMA of this key's batch round-trip (leader entering _run ->
        # results materialized); feeds the adaptive batch window AND
        # the admission estimate of whether a deadline is meetable.
        # 0.0 until the first batch completes, so a fresh key never
        # sleeps (or sheds) on a guess.
        self.rt_ema_s = 0.0


class _PrioritySlots:
    """Counted pipeline slots whose waiters are served in priority
    order (lower value first; FIFO within a class): when a slot frees
    under contention, a cheap interactive GO leader takes it ahead of
    a deep FIND PATH BFS leader — the per-query-class ladder.  With no
    contention this degenerates to the plain semaphore it replaced."""

    def __init__(self, n: int):
        self._cond = mc_hooks.Condition("dispatch.slots")
        self._free = max(1, int(n))
        self._seq = 0
        self._waiters: List[Tuple[int, int]] = []   # heap (prio, seq)

    def acquire(self, priority: int = 1) -> None:
        with self._cond:
            self._seq += 1
            me = (int(priority), self._seq)
            heapq.heappush(self._waiters, me)
            try:
                while self._free <= 0 or self._waiters[0] != me:
                    self._cond.wait()
            except BaseException:
                # interrupted waiter must not wedge the queue head
                self._waiters = [w for w in self._waiters if w != me]
                heapq.heapify(self._waiters)
                self._cond.notify_all()
                raise
            heapq.heappop(self._waiters)
            self._free -= 1
            if self._free > 0 and self._waiters:
                # two release()s can land while the old head is inside
                # one wait(): popping ourselves makes a NEW head that
                # nobody will notify again — hand the spare slot on, or
                # it idles a full batch round-trip under contention
                self._cond.notify_all()

    def release(self) -> None:
        with self._cond:
            self._free += 1
            self._cond.notify_all()


class _WindowController:
    """Closed-loop cap on the pooling window: tracks the queue depth
    leaders observe (the PR 5 queue-depth gauge's signal) and the
    dispatch latency (the tpu.dispatch.latency_us histogram's signal)
    and scales ``go_batch_window_max_ms`` down as depth grows —
    cap = max_ms / (1 + depth_ema / depth_ref).  Idle dispatchers keep
    the full pooling window (wide batches when round-trips are slow); a
    saturated queue drives the artificial wait toward zero because
    arrivals already pool behind the in-flight batches (self-clocking),
    so sleeping on top of the backlog is pure added latency."""

    def __init__(self):
        self._lock = threading.Lock()
        self.depth_ema = 0.0
        self.lat_ema_s = 0.0

    def observe_depth(self, depth: int) -> None:
        with self._lock:
            self.depth_ema = 0.8 * self.depth_ema + 0.2 * float(depth)

    def observe_latency(self, seconds: float) -> None:
        with self._lock:
            self.lat_ema_s = (seconds if self.lat_ema_s == 0.0
                              else 0.7 * self.lat_ema_s + 0.3 * seconds)

    def depth(self) -> float:
        """Current queue-depth EMA — the autoscale signal's input."""
        with self._lock:
            return self.depth_ema

    def cap_s(self) -> float:
        cap_raw = flags.get("go_batch_window_max_ms")
        cap_s = (25.0 if cap_raw is None else float(cap_raw)) / 1000.0
        ref_raw = flags.get("admission_window_depth_ref")
        ref = 8.0 if ref_raw is None else float(ref_raw)
        if ref <= 0:
            return cap_s
        with self._lock:
            depth = self.depth_ema
        return cap_s / (1.0 + depth / ref)


class _DeviceBusyMeter:
    """Wall-clock device-utilization proxy shared by both dispatch
    modes: accumulates time during which at least one device dispatch
    is in flight (windowed: a pipeline slot is held; continuous: a
    stream has seated lanes) versus time the device sits idle.  The
    scrape-time ``tpu.device_idle_frac`` gauge is the idle share since
    the previous scrape — the number the continuous pipeline exists to
    drive down (docs/admission.md "Continuous dispatch")."""
    # nebulint: mc=caller-synced/every access runs under self._lock;
    # the busy-meter obligation is modeled by the dispatch-admission
    # scenario through begin/end rather than a shimmed internal lock

    def __init__(self):
        self._lock = threading.Lock()
        self._active = 0
        self._mark = time.perf_counter()
        self.busy_s = 0.0
        self.idle_s = 0.0

    def _roll(self, now: float) -> None:
        """caller holds self._lock"""
        span = now - self._mark
        if span > 0:
            if self._active > 0:
                self.busy_s += span
            else:
                self.idle_s += span
        self._mark = now

    def begin(self) -> None:
        with self._lock:
            self._roll(time.perf_counter())
            self._active += 1

    def end(self) -> None:
        with self._lock:
            self._roll(time.perf_counter())
            self._active = max(0, self._active - 1)

    def snapshot(self) -> Tuple[float, float]:
        """(busy_s, idle_s) cumulative, rolled to now."""
        with self._lock:
            self._roll(time.perf_counter())
            return self.busy_s, self.idle_s


class _LaneLedger:
    """The continuous batch's seat map: which of the resident
    frontier's B lanes are occupied.  Lanes hand out lowest-index-first
    so a lightly loaded stream's occupancy clusters into few of the
    device's lane words (the leave-extract fetch is per word,
    docs/admission.md).  Pure bookkeeping — the caller (the
    stream, under its condition) sequences it against the device-side
    clear: a lane re-enters the free heap only after its bits were
    cleared from the resident pair, which is what makes the join
    kernel's scatter-add exact.  Double-seating any lane raises."""
    # nebulint: mc=caller-synced/the stream cond sequences every access;
    # the lane-churn scenario models it under an instrumented condition

    __slots__ = ("width", "_free", "_seated")

    def __init__(self, width: int):
        self.width = int(width)
        self._free = list(range(self.width))
        heapq.heapify(self._free)
        self._seated: set = set()

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("lane ledger exhausted")
        lane = heapq.heappop(self._free)
        if lane in self._seated:        # pragma: no cover — invariant
            raise RuntimeError(f"lane {lane} double-seated")
        self._seated.add(lane)
        return lane

    def release(self, lane: int) -> None:
        if lane not in self._seated:
            raise RuntimeError(f"lane {lane} released but not seated")
        self._seated.discard(lane)
        heapq.heappush(self._free, lane)

    def free_count(self) -> int:
        return len(self._free)

    def seated_count(self) -> int:
        return len(self._seated)


# an idle continuous stream releases its resident device frontier
# pair (two [n_rows+1, lanes] buffers + table references) after this
# long with no riders — the next arrival re-anchors against the
# then-current mirror generation, which the drain path already
# supports.  Keeps per-(space, OVER set) HBM from accumulating on
# servers that touch many spaces.
CONTINUOUS_IDLE_RELEASE_S = 30.0

# the pump's own trace (docs/observability.md "The pump trace"): a
# stretch between two ticks shorter than this is loop overhead, not an
# idle gap, and gets no pump.idle span; a pump.tick names at most this
# many of the trace ids it touched
PUMP_IDLE_SPAN_MIN_US = 1000
PUMP_TICK_RIDER_TAGS = 8

# the phases of a tick that have a ``<p>_us`` in its flight record, in
# pump order (common/flight.py note_tick): the hold, the seating block,
# the four enqueues, and the five parts of a leave cohort's assembly.
# With ``other_us`` they tile ``dur_us``
_ENQUEUE_PARTS = ("join", "hop", "extract", "clear")
_ASSEMBLE_PARTS = ("fetch_wait", "d2h", "unpack", "rows", "handover")
PUMP_PHASES = ("hold", "seat") + _ENQUEUE_PARTS + _ASSEMBLE_PARTS

# The hold (_ContinuousStream._hold, docs/admission.md "Continuous
# dispatch"): before a tick takes its joiners the pump waits, for a
# share of the time the hop in flight is expected to keep the device
# busy beyond what the pump needs to enqueue the next hop behind it.
# A caller handed its answer at the end of the tick before needs its
# wake, its client's turn-around and its parse to be back in the queue
# (3-12 ms on the v5e's host), and one that misses the door sits out a
# whole pull.  HOLD_SHARE is the share of that slack the pump spends
# waiting and HOLD_FLOOR_S the slack under which it does not wait at
# all: PERF.md sections 5-6, PR 41 have the readings (a pull of 54 ms
# holds 26 ms, and from a share of 0.5 to 0.9 the cell's qps is flat;
# a push of 1-5 ms, which is every hop of the host-paced cells, never
# passes the floor).  HOLD_POLL_S bounds one sleep of the hold,
# so that a KILL QUERY, which sets a flag and wakes nobody, ends it
# within that long; HOLD_BLOCKED_S is the least wait of a fetch that
# says the device, and not the link's floor of 0.5 ms, was waited for,
# and HOLD_SEEN_SHARE the least share of the time since its hop began
# (a pump in step with the device waits 0.42-0.49 of it behind a hold
# and 0.9 without one; one that waits for the interpreter 0.04-0.08).
# HOP_EMA_KEEP is the weight of the old estimate in the two device
# estimates, as in hop_ema_s.
HOLD_SHARE = 0.5
HOLD_FLOOR_S = 0.010
HOLD_POLL_S = 0.005
HOLD_BLOCKED_S = 0.001
HOLD_SEEN_SHARE = 0.25
HOP_EMA_KEEP = 0.7


class _PhaseClocks:
    """One tick's phases on the pump thread's three clocks: per phase
    the wall, run and runnable micros between the stamps that bound it
    (common/hostclock.py), summed over the tick's cohorts.  Where the
    tick's first stamp has no run-queue reading no phase has one."""

    __slots__ = ("_us",)

    def __init__(self, t0):
        runq = None if t0[2] is None else 0
        self._us = {p: [0, 0, runq] for p in PUMP_PHASES}

    def add(self, phase: str, a, b) -> None:
        wall, cpu, runq = hostclock.split(a, b)
        acc = self._us[phase]
        acc[0] += wall
        acc[1] += cpu
        acc[2] = None if runq is None or acc[2] is None \
            else acc[2] + runq

    def wall(self, phase: str) -> int:
        return self._us[phase][0]

    def fields(self) -> Dict[str, int]:
        """``<p>_us``, ``<p>_cpu_us`` and, where the machine has the
        clock, ``<p>_runq_us`` of every phase: the tick record's."""
        out: Dict[str, int] = {}
        for p, (wall, cpu, runq) in self._us.items():
            out[p + "_us"] = wall
            out.update(hostclock.host_fields(p + "_", cpu, runq))
        return out


def _ema(old: float, new: float) -> float:
    return new if old == 0.0 else HOP_EMA_KEEP * old \
        + (1.0 - HOP_EMA_KEEP) * new


class _HopInFlight:
    """What the pump knows of the hop the device is running while the
    next tick starts, from stamps it takes anyway (pump thread only):

      * ``began``: when that hop began on the device — the later of
        its enqueue and the moment the fetch of the cohort before it
        came back from a wait that blocked (the device finishing the
        hop before it); 0 where no hop is in flight.  ``seen`` says
        the start was observed and not assumed (a hop enqueued behind
        one the pump never waited for began later than its enqueue:
        the estimate then ends early, and the hold is short);
      * ``hop_s``: how long a hop has taken from an observed start to
        the blocked fetch behind it, an EMA apart for the hops that
        pushed and those that pulled (``pushed``: the branch of the
        last hops read, None where they differed or none was read);
      * ``turn_s``: an EMA of the pump's own way from the door to the
        next hop enqueued (seat + join + enqueue).

    ``slack`` is what the hold may spend a share of."""

    __slots__ = ("began", "seen", "before", "hop_s", "pushed", "turn_s")

    def __init__(self):
        self.began = 0.0
        self.seen = False
        self.before = (0.0, False)      # (began, seen) of the hop before
        self.hop_s = {False: 0.0, True: 0.0}
        self.pushed = None
        self.turn_s = 0.0

    def slack(self, now: float, carried: bool) -> float:
        """Seconds the device is expected to stay busy with the hop in
        flight after the pump, starting now, has enqueued the next one
        behind it.  A pull is followed by a pull while any of its
        riders ride on (``carried``); a hop of first hops alone is
        taken for a push."""
        if not self.began or self.pushed is None:
            return 0.0
        est = self.hop_s[self.pushed or not carried]
        if est <= 0.0:
            return 0.0
        return self.began + est - now - self.turn_s

    def enqueued(self, t_door: float, t_enq: float) -> None:
        """A hop was enqueued at ``t_enq``; the pump had shut the door
        at ``t_door``.  The whole stretch is the pump's turn-around,
        the session's call too: in every untraced window on the chip
        it returns in half a millisecond, and where it took 22-152 ms
        (a traced window while the profiler wrote its trace) the join
        beside it took 30-84: the interpreter, not the device's queue,
        and a pump that slow has nothing to hold for."""
        self.turn_s = _ema(self.turn_s, t_enq - t_door)
        self.before = (self.began, self.seen)
        # with nothing in flight the hop starts where it is enqueued
        self.seen = not self.began
        self.began = t_enq

    def read(self, reads: int, sparse: int) -> None:
        """The branches of the hops whose report arrived this tick."""
        if reads:
            self.pushed = True if sparse == reads \
                else False if sparse == 0 else None

    def fetched(self, t_asked: float, t_wait: float, enqueued: bool,
                known: bool) -> None:
        """The cohort of the hop before the newest was fetched: asked
        for at ``t_asked``, there at ``t_wait``; by then that hop had
        ended.  The fetch **blocked on the device** where the pump
        waited HOLD_BLOCKED_S or more and HOLD_SEEN_SHARE or more of
        the time since the hop began: then the hop ended where the
        wait did, which is a sample if its start was observed (one
        that a stall of the device or the runtime stretched counts for
        twice the estimate at most: a fetch of 1.4 s behind a pull of
        0.12 once made the next holds outlast their hops) and the
        start of the hop behind it.  A pump that came late and waited
        a sliver saw its own lateness (a traced window's fetches wait
        10-20 ms for the interpreter while the profiler writes, and
        the time since the hop began is then the pump's period, not
        the hop): no sample; the hop took no longer than to the wait's
        end, and an estimate above that comes down to it.  That bound
        is what ends a hold that outlasts its hop, behind which no
        fetch blocks and no sample comes.  ``enqueued``: this tick put
        a hop behind it.  ``known``: its branch was read this tick."""
        began, seen = self.before if enqueued else (self.began, self.seen)
        took = t_wait - began
        waited = t_wait - t_asked
        blocked = waited >= HOLD_BLOCKED_S and bool(began) \
            and waited >= HOLD_SEEN_SHARE * took
        if began and known and self.pushed is not None:
            est = self.hop_s[self.pushed]
            if blocked and seen:
                self.hop_s[self.pushed] = _ema(
                    est, min(took, 2.0 * est) if est else took)
            elif not blocked and est:
                self.hop_s[self.pushed] = min(est, took)
        if not enqueued:
            self.idle()
        elif blocked:
            self.began, self.seen = max(self.began, t_wait), True

    def idle(self) -> None:
        """Everything enqueued was waited for."""
        self.began, self.seen = 0.0, False


class ContinuousUnavailable(Exception):
    """The stream could not anchor a device session for this space
    (empty mirror, mesh-sharded tables): the submit falls back to the
    windowed pipeline.  Internal control flow —
    never surfaces to a caller of submit_batched.

    ``reason`` is a protocol.PROTOCOL_REASONS "continuous-bounce"
    constant: the fallback counter and the graph.continuous trace
    marker's ``ending`` classification key on it."""

    def __init__(self, msg: str, reason: str):
        super().__init__(msg)
        self.reason = reason


class _Rider:
    """One query riding the continuous batch: queued until a lane
    frees, seated for ``hops`` hops, extracted at its last hop (or
    evicted at its deadline).  The seat itself takes the first of them
    where it can (``seat_hops`` 1: the session scattered the rider's
    first frontier, its starts' neighbours, which the host holds —
    _ContinuousGoSession.join), and the rider then rides ``hops`` - 1
    hop ticks; ``may_advance`` is the part of that rule the stream
    sees: it keeps a hop on the lanes afterwards, and its lane is no
    UPTO one.  ``hops`` is steps-1 — the last step's
    edges are the host's to assemble from the frontier — but for a
    k-hop neighbourhood count (reduce "count_distinct": ``counts``),
    which rides all of its steps and leaves with the size of its last
    frontier, counted on the device, and no extraction, and for the
    k-hop neighbourhood itself (reduce "distinct": ``distinct``),
    which rides all of its steps too and is a fetching leaver like any
    other: its last frontier, extracted and unpacked, IS its answer
    (_assemble_own turns the vertex rows into ids).  Fields are
    written by the stream pump under the stream condition; the
    submitting thread reads them after ``done`` flips: its
    ``frontier``, which its own thread assembles (submit()), or the
    ``result`` of a leaver the pump answered itself (a counting
    leaver's number, a COUNT rider, a WHERE that filters in numpy:
    _finish)."""

    __slots__ = ("payload", "steps", "upto", "reduce", "counts",
                 "distinct", "hops", "may_advance", "seat_hops",
                 "deadline",
                 "tctx", "enq", "enq_t", "seated_t", "left_t", "done_t",
                 "lane", "remaining", "joined_tick", "left_tick",
                 "midflight", "done", "result", "frontier", "mirror",
                 "error", "qid")

    def __init__(self, payload, steps: int, upto: bool, reduce,
                 deadline):
        self.payload = payload
        self.steps = int(steps)
        self.upto = bool(upto)
        self.reduce = tuple(reduce) if reduce is not None else None
        self.counts = self.reduce is not None \
            and self.reduce[0] == "count_distinct"
        self.distinct = self.reduce is not None \
            and self.reduce[0] == "distinct"
        self.hops = self.steps if self.counts or self.distinct \
            else self.steps - 1
        self.may_advance = self.hops >= 2 and not self.upto
        self.seat_hops = 0
        self.deadline = deadline
        # the submitter's trace snapshot: the pump attaches it around
        # the device phases this rider participates in, so a PROFILE
        # still shows mirror/launch/kernel/fetch/assemble exactly like
        # a windowed batch leader's would
        self.tctx = tracing.capture()
        # the submitter's own three clocks at the enqueue
        # (common/hostclock.py): submit() splits its wait against them
        self.enq = hostclock.stamp()
        self.enq_t = self.enq[0]
        # perf_counter stamps the PUMP writes as the rider moves on:
        # seated, left the seat map, frontier (or count, or error)
        # handed over.  submit() turns them into the marker's waits
        self.seated_t = self.left_t = self.done_t = 0.0
        self.lane = -1
        self.remaining = 0
        self.joined_tick = -1
        self.left_tick = -1
        self.midflight = False
        self.done = False
        self.result = None
        self.frontier = None
        self.mirror = None
        self.error = None
        # live-query-registry id — the pump reports this rider's seat /
        # hop progress through it, and KILL QUERY evicts by it
        self.qid = current_qid()


class _ContinuousStream:
    """One (space, OVER set) continuous lane batch: a single pump
    thread owns the device session (tpu/runtime.py
    _ContinuousGoSession) and runs the hop-tick loop —

        hold the door while the device is busy with hop k-1 (_hold:
        arrivals queue up; not at all when it is not) ->
        seat joiners -> scatter-merge their FIRST frontiers (a joiner
        whose seat cannot take its first hop: its starts) ->
        dispatch hop k -> mark leavers/evictions -> enqueue their
        lane extraction (a counting leaver's per-lane count) + clear ->
        fetch + unpack hop k-1's leavers while hop k computes -> hand
        each its frontier (or its number) and wake it

    so the device always has the next hop enqueued while the host
    does per-query work (the double-buffer overlap), and a leaver's
    filter and rows are its own thread's work (submit()), not the
    pump's, wherever that pass leaves the interpreter and the
    allocator alone (_finish): the next tick does not wait for the
    slowest answer.  A hop costs the device the same for one lane as
    for all of them, so where the device is the pace the pump spends
    half of the time it would wait for the fetch anyway at the door
    instead: a caller handed its answer rides the next hop enqueued,
    and hop k is still enqueued before hop k-1 ends.  Mirror
    generation changes drain the stream: seated riders finish on the
    generation they captured (the published-generation contract,
    docs/durability.md), new arrivals wait for the re-anchor —
    read-your-writes holds because a query admitted after generation
    g publishes is seated on a session anchored at >= g."""

    def __init__(self, sched: "ContinuousGoScheduler", space_id: int,
                 et_tuple: Tuple):
        self.sched = sched
        self.space_id = space_id
        self.et_tuple = et_tuple
        self.cond = threading.Condition()
        self.queue: List[_Rider] = []
        self.seated: Dict[int, _Rider] = {}
        self.ledger = None              # _LaneLedger once anchored
        self.hop_ema_s = 0.0            # EMA of one tick's wall time
        self.tick_no = 0
        self.draining = False           # generation change: no seats
        self.stopping = False
        self.retired = False            # scheduler replaces the stream
        # pump-thread-only device state: the session is created,
        # advanced and discarded exclusively on the pump thread — the
        # condition above guards the SEAT bookkeeping, not this
        self.session = None             # nebulint: guarded-by=none
        # pump-only: the seat map saturated with a backlog — drain and
        # re-anchor one batch-width rung wider (at least _widen_min
        # lanes, so the re-anchor provably moves UP the ladder)
        self._widen = False             # nebulint: guarded-by=none
        self._widen_min = 0             # nebulint: guarded-by=none
        # test hook: sleep this long before each tick so differential
        # tests can force arrivals to land mid-flight deterministically
        self.tick_delay_s = 0.0         # nebulint: guarded-by=none
        self._meter_open = False        # nebulint: guarded-by=none
        # pump-only: perf_counter stamp of the previous tick's end —
        # the flight recorder's idle-gap column (common/flight.py)
        self._last_tick_end = 0.0       # nebulint: guarded-by=none
        # pump-only: the pump slept for want of work since the last
        # tick — the ``why`` of that tick's pump.idle span
        self._saw_no_work = False       # nebulint: guarded-by=none
        # pump-only: the hop the device is running and how long such
        # hops take — what the hold's length is read off (_hold)
        self._flight = _HopInFlight()   # nebulint: guarded-by=none
        self._pump_thread = threading.Thread(
            target=self._pump, daemon=True,
            name=f"continuous-go-{space_id}")
        self._pump_thread.start()

    # --------------------------------------------------------- pump
    def _pump(self) -> None:
        pending = None
        idle_since = None
        while True:
            with self.cond:
                idle = (not self.queue and not self.seated
                        and not self.stopping and pending is None)
                stopping = self.stopping
            if stopping:
                break
            if idle:
                # end the busy interval OUTSIDE the condition — the
                # device sync must not block submitters — then
                # re-check under it before sleeping
                self._meter_close()
                now = time.perf_counter()
                if idle_since is None:
                    idle_since = now
                elif self.session is not None and \
                        now - idle_since > CONTINUOUS_IDLE_RELEASE_S:
                    self._release_idle_session()
                elif self.session is None and \
                        now - idle_since > 3 * CONTINUOUS_IDLE_RELEASE_S:
                    # long-dead stream: retire the pump thread too —
                    # the scheduler replaces a retired stream on the
                    # next submit, so per-(space, OVER set) threads
                    # don't accumulate forever on long-lived servers
                    with self.cond:
                        if not self.queue and not self.seated:
                            self.retired = True
                            self.stopping = True
                    continue
                # pump-thread-only state (see __init__)
                self._saw_no_work = True  # nebulint: disable=lock-discipline
                with self.cond:
                    if not self.queue and not self.seated \
                            and not self.stopping:
                        self.cond.wait(0.25)
                continue
            idle_since = None
            delay = self.tick_delay_s
            if delay > 0:
                time.sleep(delay)
            try:
                pending = self._tick(pending)
            except BaseException as ex:  # noqa: BLE001 — pump must
                # survive: a dead pump wedges every future submit on
                # this stream.  Fail everyone currently riding —
                # INCLUDING the extracted-but-unassembled previous
                # cohort, whose riders already left the seat map —
                # drop the session (its donated buffers may be dead),
                # and keep serving
                err = (ex if isinstance(ex, Exception)
                       else RuntimeError(f"pump interrupted: {ex!r}"))
                self._fail_all(err)
                if pending is not None:
                    self._fail_cohort(pending, err)
                    pending = None
                if not isinstance(ex, Exception):
                    raise
        self._fail_all(RuntimeError("continuous dispatcher stopped"))
        if pending is not None:
            self._finish(pending)
        self._meter_close()

    def _release_idle_session(self) -> None:
        """Drop the resident device pair after a sustained idle window
        (CONTINUOUS_IDLE_RELEASE_S): the buffers free, the next
        arrival re-anchors on the current mirror generation.  Pump
        thread only."""
        with self.cond:
            if self.queue or self.seated:
                return                  # woke up meanwhile
            self.ledger = None
        # pump-thread-only state (see __init__)
        self.session = None  # nebulint: disable=lock-discipline

    def _meter_close(self) -> None:
        """Idle transition: force the in-flight device work to
        completion so the busy interval ends honestly, then flip the
        meter.  Pump thread only."""
        if not self._meter_open:
            return
        sess = self.session
        if sess is not None:
            try:
                sess.fp.block_until_ready()
            except Exception:       # noqa: BLE001 — a dead session
                pass                # still ends the busy interval
        self._flight.idle()
        self.sched.meter.end()
        # pump-thread-only state, like self.session
        self._meter_open = False  # nebulint: disable=lock-discipline

    def _fail_cohort(self, pending, ex: Exception) -> None:
        """Wake an extracted-but-unassembled leave cohort with ``ex``
        — its riders already left the seat map, so _fail_all cannot
        reach them."""
        leavers = pending[1]
        with self.cond:
            for r in leavers:
                if r.error is None and r.result is None:
                    r.error = ex
                r.done = True
            self.cond.notify_all()

    def _fail_all(self, ex: Exception) -> None:
        """Batch-level failure: wake every queued and seated rider
        with ``ex`` (their submitters classify it against the device
        breaker exactly like a windowed batch failure) and reset the
        seat map."""
        # pump-thread-only state (see __init__)
        self.session = None  # nebulint: disable=lock-discipline
        self._flight.idle()
        with self.cond:
            riders = list(self.queue) + list(self.seated.values())
            self.queue.clear()
            self.seated.clear()
            self.ledger = None
            self.draining = False
            for r in riders:
                if r.error is None and r.result is None:
                    r.error = ex
                r.done = True
            self.cond.notify_all()

    def _anchor(self) -> None:
        """Ensure a device session over the CURRENT mirror generation
        (pump thread, outside the condition — mirror() may build or
        absorb for seconds).  A generation change while lanes are
        seated flips ``draining`` instead: the seated riders finish on
        what they captured, the stream re-anchors once empty."""
        rt = self.sched.runtime
        # a mirror build/absorb on the pump belongs to the FIRST
        # queued rider's trace (windowed equivalence: the batch leader
        # pays and shows it)
        with self.cond:
            tctx = self.queue[0].tctx if self.queue else None
        with tracing.attach_captured(tctx):
            self._anchor_traced(rt)

    def _anchor_traced(self, rt) -> None:
        sess = self.session
        if sess is not None:
            m = rt.mirror(self.space_id)
            if m is not sess.m or self._widen:
                with self.cond:
                    if self.seated:
                        self.draining = True
                        return
                # pump-thread-only state (see __init__)
                self.session = None  # nebulint: disable=lock-discipline
                self._widen = False  # nebulint: disable=lock-discipline
                sess = None
            else:
                with self.cond:
                    self.draining = False
                return
        with self.cond:
            backlog = len(self.queue)
        new_sess = rt.continuous_session(
            self.space_id, self.et_tuple,
            min_lanes=max(backlog, self._widen_min))
        self._widen_min = 0  # nebulint: disable=lock-discipline
        if new_sess is None:
            raise ContinuousUnavailable(
                f"space {self.space_id} cannot ride continuous "
                f"dispatch", protocol.BOUNCE_NO_SESSION)
        # pump-thread-only state (see __init__)
        self.session = new_sess  # nebulint: disable=lock-discipline
        with self.cond:
            self.draining = False
            self.ledger = _LaneLedger(new_sess.B)

    def _hold(self, t0, pending):
        """Hold the door: wait on the stream's condition, collecting
        arrivals, for as long as the device is expected to have more
        of the hop in flight left than the pump needs to get the next
        hop enqueued behind it — HOLD_SHARE of that slack
        (_HopInFlight.slack), nothing under HOLD_FLOOR_S — and return
        the stamp the wait ended at, or ``t0`` itself where it did not
        wait.  A pull costs the same for one lane as for 128, so a
        caller handed its answer when the tick before ended, and back
        in the queue a few milliseconds after this one began, rides
        the hop this tick enqueues instead of sitting out a whole one.

        No wait where no session is anchored, no hop is in flight,
        the stream is draining, stopping or about to widen, nobody
        stays seated (the next hop would carry the joiners alone) or
        every free lane has a taker; the wait ends where one of those
        comes true, where a rider was killed (its lane leaves at this
        tick's boundary), where the runtime has published a mirror
        that is not the session's (this tick's generation check will
        drain), or where the time is up.  ``pending`` is the cohort of
        the hop in flight: with the seated riders it says whether the
        hop before it carried on into this one.  Pump thread only."""
        sess, flight_now = self.session, self._flight
        if sess is None or self._widen or not flight_now.began:
            return t0
        # nothing to hold for whoever rides: leave the condition alone
        # (a tick of a stream the host paces is what it was: one more
        # turn at the condition is one more chance to hand the
        # interpreter to sixty-four callers, 2.5 ms a tick in closed64)
        now = time.perf_counter()
        if max(flight_now.slack(now, True),
               flight_now.slack(now, False)) < HOLD_FLOOR_S:
            return t0
        riding = pending[1] if pending is not None else ()
        end = None
        with self.cond:
            while not self._door_shut(sess):
                now = time.perf_counter()
                if end is None:
                    # a rider of the hop in flight that rode the one
                    # before it too: joined two ticks ago or earlier
                    carried = any(
                        r.joined_tick <= self.tick_no - 2
                        for r in (*self.seated.values(), *riding))
                    slack = flight_now.slack(now, carried)
                    if slack < HOLD_FLOOR_S:
                        break
                    end = now + HOLD_SHARE * slack
                if now >= end:
                    break
                self.cond.wait(min(end - now, HOLD_POLL_S))
        return t0 if end is None else hostclock.stamp()

    def _door_shut(self, sess) -> bool:
        """Whether a hold has nothing (more) to wait for, whatever the
        time: see _hold.  Caller holds the lock (the stream
        condition)."""
        if self.stopping or self.draining or not self.seated \
                or self.ledger is None \
                or len(self.queue) >= self.ledger.free_count():
            return True
        # published, not built: the dict the runtime serves from
        # (a build or an absorb is the generation check's to pay)
        mirrors = getattr(self.sched.runtime, "mirrors", None) or {}
        if mirrors.get(self.space_id, sess.m) is not sess.m:
            return True
        return any(query_registry.is_killed(r.qid)
                   for r in (*self.seated.values(), *self.queue))

    def _tick(self, pending):
        """One hop tick; returns the next tick's pending leave cohort
        (or None).  ``pending`` is the PREVIOUS tick's cohort — its
        fetch+assembly runs here, after this tick's hop is enqueued,
        which is the overlap the idle-frac gauge measures.  The tick
        begins by holding its door while the device is busy with the
        hop in flight (_hold), then seats who is queued."""
        # every stamp that bounds a phase of the tick is the pump
        # thread's three clocks (common/hostclock.py): wall, run time,
        # time runnable without a core
        t0 = hostclock.stamp()
        # idle gap since the previous tick ended (0 on the first tick)
        # — one column of the flight-recorder tick record
        idle_us = (t0[0] - self._last_tick_end) * 1e6 \
            if self._last_tick_end else 0.0
        saw_no_work = self._saw_no_work
        # pump-thread-only state (see __init__)
        self._saw_no_work = False  # nebulint: disable=lock-discipline
        # the door stays open while the device is busy with the hop in
        # flight (_hold).  BEFORE the generation check below: whoever
        # arrives meanwhile is in the queue when it is counted, so the
        # check still follows the last arrival this tick seats
        t_door = self._hold(t0, pending)
        held = t_door is not t0
        host = _PhaseClocks(t0)
        if held:
            host.add("hold", t0, t_door)
            stats.add_value("graph.continuous.hold_us", host.wall("hold"))
        with self.cond:
            was_draining = self.draining
            # riders present BEFORE this tick's generation check are
            # seatable this tick; later arrivals wait for the next
            # tick's _anchor so a query admitted after generation g
            # publishes can never seat on a < g session
            # (read-your-writes — the windowed leader's mirror()-at-
            # launch gives the same guarantee)
            n_eligible = len(self.queue)
            want_seats = n_eligible > 0 and not self.stopping
        if want_seats:
            try:
                self._anchor()
            except ContinuousUnavailable as ex:
                # typed fallback: ONLY the queued riders bounce to the
                # windowed pipeline; seated riders (an anchored session
                # that went away is a _fail_all case, not this) ride on
                with self.cond:
                    waiting = list(self.queue)
                    self.queue.clear()
                    for r in waiting:
                        r.error = ex
                        r.done = True
                    self.cond.notify_all()

        sess = self.session
        joiners: List[_Rider] = []
        evicted: List[_Rider] = []
        with self.cond:
            # feed the closed-loop controller the continuous queue
            # depth too — the autoscale recommendation must see the
            # DEFAULT path's backlog, not just windowed leaders'
            qdepth = len(self.queue)
            if sess is not None and not self.draining \
                    and not self.stopping:
                # mid-flight means hops are ALREADY dispatched for
                # previously seated riders — co-arrivals pooling into
                # a fresh batch this same tick are the windowed case
                was_running = bool(self.seated)
                t_seated = time.perf_counter()
                while self.queue and n_eligible > 0 \
                        and self.ledger.free_count() > 0:
                    n_eligible -= 1
                    r = self.queue.pop(0)
                    if r.deadline is not None and r.deadline.expired():
                        r.error = DeadlineExceeded(
                            "go: budget exhausted in the continuous "
                            "admission queue")
                        r.done = True
                        self.sched.dispatcher._note_deadline_drop(
                            ("go_batch_execute", self.space_id,
                             self.et_tuple))
                        continue
                    # the seat outlives this call by design: it is
                    # released when its rider leaves or is evicted on
                    # a LATER tick, and a pump death retires the whole
                    # seat map via _fail_all
                    # nebulint: obligation=handed-off/seat-map-retired-by-fail-all
                    r.lane = self.ledger.alloc()
                    r.remaining = r.hops
                    r.joined_tick = self.tick_no
                    r.seated_t = t_seated
                    r.midflight = was_running
                    self.seated[r.lane] = r
                    joiners.append(r)
                    query_registry.note_seat(r.qid, r.lane,
                                             r.joined_tick)
            # deadline evictions and KILL QUERY both leave their seat
            # this tick — their lanes clear alongside the leavers' and
            # free next tick (the "within one hop boundary" contract)
            for lane, r in list(self.seated.items()):
                if (r.deadline is not None and r.deadline.expired()) \
                        or query_registry.is_killed(r.qid):
                    del self.seated[lane]
                    r.left_t = time.perf_counter()
                    r.left_tick = self.tick_no
                    evicted.append(r)
            # a KILLed rider still waiting for a lane must not sit out
            # a full seat map it will never use — end it this tick too
            still = []
            for r in self.queue:
                if not query_registry.is_killed(r.qid):
                    still.append(r)
                    continue
                r.error = KilledError(
                    "go: ended by KILL QUERY in the continuous "
                    "admission queue")
                r.done = True
            self.queue[:] = still
            seated_now = bool(self.seated)
            backlog = len(self.queue)
            lanes_full = (self.ledger is not None
                          and self.ledger.free_count() == 0)
            width = self.ledger.width if self.ledger is not None else 0
            self.cond.notify_all()      # wake shed/expired waiters
        self.sched.dispatcher.window.observe_depth(qdepth)
        if sess is not None and backlog and lanes_full \
                and not self._widen:
            # seat map saturated with a waiting backlog: drain and
            # re-anchor one batch-width rung wider (the ladder the
            # windowed kernels already compile for — never a new
            # program shape)
            ladder = sorted(int(w) for w in
                            str(flags.get("go_batch_widths") or
                                "128,1024").split(",") if w.strip())
            if ladder and width < ladder[-1]:
                # pump-thread-only state (see __init__)
                self._widen = True  # nebulint: disable=lock-discipline
                self._widen_min = width + 1  # nebulint: disable=lock-discipline
        # end of the seating block (anchor + seat-map bookkeeping):
        # the tick record's seat_us, the trace's pump.seat
        t_seat = hostclock.stamp()
        host.add("seat", t_door, t_seat)

        new_pending = None
        leavers: List[_Rider] = []
        occupancy = 0
        join_map_us = join_pack_us = hold_joins = 0
        seat_hops = join_rows = 0
        hop_enqueued = False
        busy = sess is not None and bool(joiners or evicted
                                         or seated_now)
        if busy:
            if not self._meter_open:
                # one busy interval spans MANY ticks: _meter_close
                # ends it at idle / drain / pump retirement
                # nebulint: obligation=handed-off/meter-closed-at-idle
                self.sched.meter.begin()
                # pump-thread-only state (see __init__)
                self._meter_open = True  # nebulint: disable=lock-discipline
            if joiners:
                # admission wait of the oldest rider seated this tick
                # — the windowed leader's per-batch observation
                stats.observe(
                    "graph.admission.wait_us",
                    (time.perf_counter()
                     - min(r.enq_t for r in joiners)) * 1e6)
            # device phase spans land on the FIRST joiner's trace —
            # the windowed equivalence (the leader thread's PROFILE
            # shows launch/kernel; riders see the seat markers)
            jctx = joiners[0].tctx if joiners else None
            resolver = counter = None
            try:
                with tracing.attach_captured(jctx):
                    with tracing.span("tpu.launch",
                                      joiners=len(joiners), steps=1):
                        if joiners:
                            tj = hostclock.stamp()
                            took = sess.join(
                                [(r.lane, r.payload.start_vids,
                                  r.may_advance) for r in joiners])
                            # the stream counts what the session did:
                            # a rider whose seat took its first hop
                            # has one fewer to ride
                            with self.cond:
                                for r, hop_taken in zip(joiners, took):
                                    if hop_taken:
                                        r.seat_hops = 1
                                        r.remaining -= 1
                            seat_hops = sum(took)
                            join_rows = getattr(sess, "join_rows", 0)
                            host.add("join", tj, hostclock.stamp())
                            # where the session's own marks split it
                            # (tpu/runtime.py join): the joiners
                            # mapped, the arrays packed; the enqueue
                            # is what is left of join_us
                            t_map, t_pack = getattr(
                                sess, "join_marks", None) or (tj[0], tj[0])
                            join_map_us = int((t_map - tj[0]) * 1e6)
                            join_pack_us = int((t_pack - t_map) * 1e6)
                        with self.cond:
                            has_work = bool(self.seated)
                        if has_work:
                            th = hostclock.stamp()
                            sess.hop()
                            t_hop = hostclock.stamp()
                            host.add("hop", th, t_hop)
                            t_left = t_hop[0]
                            hop_enqueued = True
                            self._flight.enqueued(t_door[0], t_left)
                            with self.cond:
                                self.tick_no += 1
                                for lane, r in \
                                        list(self.seated.items()):
                                    r.remaining -= 1
                                    query_registry.note_hop(
                                        r.qid,
                                        r.hops - r.remaining)
                                    if r.remaining <= 0:
                                        del self.seated[lane]
                                        r.left_t = t_left
                                        r.left_tick = self.tick_no
                                        leavers.append(r)
                    if leavers:
                        tx = hostclock.stamp()
                        # a cohort is its fetching leavers, then its
                        # counting ones: the first take their lanes'
                        # columns off the device, the second one
                        # number each out of ONE count over the
                        # resident frontier (_finish)
                        fetching = [r for r in leavers if not r.counts]
                        counting = [r for r in leavers if r.counts]
                        leavers[:] = fetching + counting
                        if counting:
                            # its kernel span on the first counting
                            # leaver's trace, where its tpu.count
                            # lands too (a leave tick may seat nobody)
                            with tracing.attach_captured(
                                    counting[0].tctx):
                                counter = sess.count(
                                    [r.lane for r in counting])
                        if fetching:
                            resolver = sess.extract([(r.lane, r.upto)
                                                     for r in fetching])
                        host.add("extract", tx, hostclock.stamp())
                    if leavers or evicted:
                        tc = hostclock.stamp()
                        sess.clear([r.lane for r in leavers]
                                   + [r.lane for r in evicted
                                      if r.lane >= 0])
                        host.add("clear", tc, hostclock.stamp())
            except BaseException as ex:
                # leavers/evicted already left the seat map — the
                # pump-level _fail_all can no longer reach them, so
                # they must be woken HERE or their waiters hang
                if isinstance(ex, Exception):
                    with self.cond:
                        for r in leavers + evicted:
                            if r.error is None and r.result is None:
                                r.error = ex
                            r.done = True
                        self.cond.notify_all()
                raise
            if joiners:
                stats.add_value("graph.continuous.joins",
                                len(joiners))
                stats.add_value("graph.continuous.seat_hops", seat_hops)
                if held:
                    # riders this tick seated that came while it held
                    # the door: a tick without the hold had left them
                    # to the next
                    hold_joins = sum(r.enq_t > t0[0] for r in joiners)
                    stats.add_value("graph.continuous.held_joins",
                                    hold_joins)
                for r in joiners:
                    if r.midflight:
                        journal.record(
                            "query.joined_midflight",
                            detail=f"lane={r.lane} hops={r.hops} "
                                   f"seat_hops={r.seat_hops} "
                                   f"tick={r.joined_tick}",
                            space=self.space_id)
            if leavers or evicted:
                with self.cond:
                    for r in leavers:
                        self.ledger.release(r.lane)
                    for r in evicted:
                        if r.lane >= 0:
                            self.ledger.release(r.lane)
            with self.cond:
                occupancy = len(self.seated)
            stats.observe("graph.continuous.lane_occupancy",
                          float(occupancy))
            if leavers:
                new_pending = (resolver, leavers, sess.m, counter)
        if evicted:
            stats.add_value("graph.continuous.evictions",
                            len(evicted))
            with self.cond:
                t_done = time.perf_counter()
                for r in evicted:
                    if query_registry.is_killed(r.qid):
                        r.error = KilledError(
                            "go: ended by KILL QUERY (evicted at a "
                            "hop boundary)")
                    else:
                        r.error = DeadlineExceeded(
                            "go: deadline expired mid-flight (evicted "
                            "at a hop boundary)")
                    r.done_t = t_done
                    r.done = True
                self.cond.notify_all()

        # hop k's work is on the device; fetch and hand over hop k-1's
        # leavers NOW — host post-processing overlaps device compute.
        # Each _finish returns its stamps, the leavers it handed their
        # frontier and what its unpack met; the tick record's
        # assemble_us is the sum of the stamps' parts
        finishes = []
        pending_leavers = pending[1] if pending is not None else []
        if pending is not None:
            finishes.append(self._finish(pending))
        # nothing left in flight: the cohort just produced has no hop
        # to hide behind — flush it immediately rather than letting it
        # age one idle-poll interval
        flushed = False
        if new_pending is not None:
            with self.cond:
                empty = not self.seated and not self.queue
            if empty:
                finishes.append(self._finish(new_pending))
                new_pending = None
                flushed = True
        t_end = hostclock.stamp()
        dur = t_end[0] - t0[0]
        # a handover runs to where the pump stamps next (the flush's
        # start, or the tick's end): the parts then tile the tick's
        # tail, and a pump that lost the interpreter between two stamps
        # is charged to a part instead of to nothing
        hand_ends = [f[0][0] for f in finishes[1:]] + [t_end]
        finishes = [(stamps + (t_hand,), n, met)
                    for (stamps, n, met), t_hand
                    in zip(finishes, hand_ends)]
        with self.cond:
            self.hop_ema_s = _ema(self.hop_ema_s, dur)
            tick_done = self.tick_no
            seated_riders = list(self.seated.values())
        # pump-thread-only state (see __init__)
        self._last_tick_end = time.perf_counter()  # nebulint: disable=lock-discipline
        # the branch the device took, for the hops whose info the
        # session has read since the last record: the fetch reads
        # it where it has just waited (tpu/runtime.py _LaneFetch),
        # this call takes in what else is ready.  Never a wait
        hop_reads, hop_sparse, hop_slots, hop_onesided, hop_swept = \
            sess.hop_reads() if busy else (0, 0, 0, 0, 0)
        # what the next tick's hold reads (_hold): which branch ran,
        # and where the fetch of the cohort before this tick's hop
        # came back from the device
        flight_now = self._flight
        flight_now.read(hop_reads, hop_sparse)
        if pending is not None:
            ta, _t_count, t_wait = finishes[0][0][:3]
            flight_now.fetched(ta[0], t_wait[0], hop_enqueued,
                               hop_reads > 0)
        if flushed:
            flight_now.idle()
        if busy:
            # per cohort: start, (count end,) fetch_wait end, d2h end,
            # unpack end, rows end, handover end
            for (ta, _t_count, *ends), _n, _met in finishes:
                for name, a, b in zip(_ASSEMBLE_PARTS, [ta] + ends, ends):
                    host.add(name, a, b)
            dur_us = int(dur * 1e6)
            _w, tick_cpu_us, tick_runq_us = hostclock.split(t0, t_end)
            # what the cohorts' unpacks met, under the record's own
            # field names (_finish)
            met = {name: sum(f[2][name] for f in finishes)
                   for name in ("unpack_leavers", "unpack_live",
                                "unpack_rows", "unpack_native",
                                "counted", "count_us", "distinct")}
            rec_id = flight.recorder.note_tick(
                stream=self.space_id, tick=tick_done,
                seats=occupancy, joins=len(joiners),
                hold_joins=hold_joins, seat_hops=seat_hops,
                join_rows=join_rows,
                leaves=len(leavers), evictions=len(evicted),
                **host.fields(),
                join_map_us=join_map_us, join_pack_us=join_pack_us,
                join_enqueue_us=host.wall("join") - join_map_us
                - join_pack_us,
                assemble_us=sum(host.wall(p) for p in _ASSEMBLE_PARTS),
                # the bookkeeping between the stamps (ledger release,
                # stats.observe, the journal): with it the eleven
                # parts tile dur_us
                other_us=dur_us - sum(host.wall(p)
                                      for p in PUMP_PHASES),
                **hostclock.host_fields("", tick_cpu_us, tick_runq_us),
                **met,
                handed=sum(f[1] for f in finishes),
                hop_reads=hop_reads, hop_sparse=hop_sparse,
                hop_slots=hop_slots, hop_onesided=hop_onesided,
                hop_swept=hop_swept,
                idle_us=int(idle_us),
                dur_us=dur_us,
                generation=int(getattr(getattr(sess, "m", None),
                                       "generation", -1)))
            # advance every touched rider's slow-log timeline anchor
            # (first note pins the window start —
            # query_registry.note_timeline), and collect the trace ids
            # of those that are traced: the tick is traced iff it
            # touched one
            touched = joiners + leavers + evicted + seated_riders
            for r in touched:
                query_registry.note_timeline(r.qid, rec_id)
            riders = list(dict.fromkeys(
                r.tctx[0][0] for r in touched + pending_leavers
                if r.tctx is not None))
            if riders:
                why = ("no_work" if saw_no_work else
                       "drain" if was_draining else
                       "tick_delay" if self.tick_delay_s > 0 else
                       "loop")
                self._emit_pump_trace(
                    riders, (t0, t_door, t_seat, t_end), finishes,
                    idle_us, why,
                    {p + "_us": host.wall(p) for p in _ENQUEUE_PARTS},
                    hold_joins,
                    tick=tick_done, rec=rec_id, seats=occupancy,
                    joins=len(joiners), leaves=len(leavers))
        return new_pending

    def _emit_pump_trace(self, riders: List[int], tick_stamps,
                         finishes, idle_us: float, why: str,
                         enqueues: Dict[str, int], hold_joins: int,
                         **tags) -> None:
        """The tick just ended, as a trace of its own, post hoc from
        the stamps the tick took anyway: root pump.tick,
        children that tile it in pump order (they lie inside it and do
        not overlap, by construction: consecutive stamps on one
        clock), and a root pump.idle over the stretch since the
        previous tick ended when that is >= PUMP_IDLE_SPAN_MIN_US
        (``why``: no_work — the pump slept for want of riders; drain /
        tick_delay — a generation change / the test hook held it; loop
        — it was between two ticks: recording, the condition, the
        interpreter lock).  Every child carries what the pump thread
        did in its stretch beside the wall: ``cpu_us`` it ran,
        ``runq_us`` it was runnable without a core (left off where the
        machine has no such clock); pump.enqueue also the walls of the
        four enqueues it is made of (``enqueues``); pump.hold, which
        heads a tick that held its door (_hold) and no other, the
        riders seated that arrived meanwhile (``hold_joins``).
        ``finishes``
        holds, per finished cohort, _finish's stamps plus the one its
        handover ran to, the leavers handed their frontier and what
        its unpack met.  Only called for a tick that touched a traced
        rider."""
        t0, t_door, t_seat, t_end = tick_stamps
        # ONE wall-minus-perf offset for the whole tick: the spans land
        # on the now_micros() clock every other span uses
        off = now_micros() - time.perf_counter() * 1e6

        def us(t) -> int:
            return int(t[0] * 1e6 + off)

        tid = tracing.new_trace_id()
        root = tracing.emit(
            "pump.tick", tid, None, us(t0), us(t_end) - us(t0),
            stream=self.space_id,
            riders=[f"{r:016x}" for r in riders[:PUMP_TICK_RIDER_TAGS]],
            **tags)

        def at(a, b) -> Tuple:
            """Where a child of the root lies: emit's placing."""
            return tid, root, us(a), us(b) - us(a)

        def ran(a, b) -> Dict[str, int]:
            return hostclock.span_fields("", a, b)

        if t_door is not t0:
            tracing.emit("pump.hold", *at(t0, t_door), **ran(t0, t_door),
                         joins=hold_joins)
        tracing.emit("pump.seat", *at(t_door, t_seat),
                     **ran(t_door, t_seat))
        t_enq = finishes[0][0][0] if finishes else t_end   # first ta
        tracing.emit("pump.enqueue", *at(t_seat, t_enq),
                     **ran(t_seat, t_enq), **enqueues)
        for (ta, t_count, t_wait, t_d2h, t_unpack, t_rows,
             t_hand), n, met in finishes:
            # the count's wait and read head the fetch wait
            if met["counted"]:
                tracing.emit("pump.count", *at(ta, t_count),
                             **ran(ta, t_count), counted=met["counted"])
            tracing.emit("pump.fetch_wait", *at(t_count, t_wait),
                         **ran(t_count, t_wait))
            tracing.emit("pump.d2h", *at(t_wait, t_d2h),
                         **ran(t_wait, t_d2h))
            tracing.emit("pump.unpack", *at(t_d2h, t_unpack),
                         **ran(t_d2h, t_unpack),
                         leavers=met["unpack_leavers"],
                         live=met["unpack_live"],
                         rows=met["unpack_rows"],
                         native=met["unpack_native"])
            tracing.emit("pump.rows", *at(t_unpack, t_rows),
                         **ran(t_unpack, t_rows), handed=n)
            tracing.emit("pump.handover", *at(t_rows, t_hand),
                         **ran(t_rows, t_hand))
        if idle_us >= PUMP_IDLE_SPAN_MIN_US:
            tracing.emit("pump.idle", tid, None, us(t0) - int(idle_us),
                         int(idle_us), stream=self.space_id, why=why)

    def _finish(self, pending) -> Tuple:
        """Force the leave cohort's fetches and hand every leaver what
        its own thread goes on from (submit()).  A counting leaver (a
        k-hop neighbourhood count) gets its number: the cohort's ONE
        per-lane count is waited for and read first — B int32, no
        column, no unpack — and is all such a leaver needs.  A
        fetching leaver gets its frontier with the generation it was
        extracted under — its filter and rows are then its thread's
        work, not the pump's — or, for the leavers the pump answers
        itself, its result: a COUNT rider's number (one vectorised
        degree fold over the cohort's COUNT riders) and the rows of a
        WHERE that filters in numpy (rt.rider_assembles: one thread
        running those passes in turn is faster than the riders'
        threads running them at once).  A cohort-level failure (a
        resolver's, the fold's) wakes every cohort member with it.

        Returns (the stamps that split this stretch of the pump's
        time, each the pump thread's three clocks (common/
        hostclock.py): start, count end, fetch_wait end, d2h end,
        unpack end, rows end —
        "rows" being what the pump answered itself; the leavers handed
        their frontier; what the fetches met, under the tick record's
        field names: unpack_leavers, unpack_live, unpack_rows,
        unpack_native —
        tpu/runtime.py _unpack_lanes — and counted, count_us: the
        counting leavers and the wait for and read of their counts,
        which is the head of the fetch wait — and distinct: the
        fetching leavers whose frontier is their answer, k-hop
        neighbourhoods).  The handover ends where the caller stamps
        next."""
        resolver, leavers, m, counter = pending
        rt = self.sched.runtime
        ta = hostclock.stamp()
        # the cohort is its fetching leavers, then its counting ones
        n_fetch = sum(not r.counts for r in leavers)
        fetching, counting = leavers[:n_fetch], leavers[n_fetch:]
        t_count = t_unpack = None
        # the fetching leavers the pump answers itself: COUNT riders
        # (one fold over the cohort's) and a WHERE that filters in
        # numpy, which sixteen threads at once run slower than one in
        # turn (rt.rider_assembles).  Every other takes its frontier
        own_idx = [i for i, r in enumerate(fetching)
                   if (r.reduce is not None and r.reduce[0] == "count")
                   or not rt.rider_assembles(m, r.payload,
                                             self.et_tuple)]
        # per leaver: the pump's result, the cohort's failure, or None
        # — the leaver takes its frontier
        outs: List[object] = [None] * len(leavers)
        vs_lists = None
        try:
            if counting:
                # the count's span lands on the first counting
                # leaver's trace
                with tracing.attach_captured(counting[0].tctx):
                    counts = counter()
                t_count = counter.t_done
                outs[n_fetch:] = rt.count_distinct_results(
                    counts, [r.hops for r in counting])
            if fetching:
                # fetch spans land on the first fetching leaver's trace
                with tracing.attach_captured(fetching[0].tctx):
                    vs_lists = resolver()
                    t_unpack = hostclock.stamp()
                    if own_idx:
                        own = rt.continuous_results(
                            self.space_id, m,
                            [fetching[i].payload for i in own_idx],
                            [fetching[i].reduce for i in own_idx],
                            [vs_lists[i] for i in own_idx],
                            self.et_tuple)
                        for i, out in zip(own_idx, own):
                            outs[i] = out
        except Exception as ex:         # noqa: BLE001 — cohort-level
            outs = [ex] * len(leavers)
        t_rows = hostclock.stamp()
        # a resolver that failed (or a test's stand-in) has no stamps:
        # its whole stretch reads as the part it died in, and it
        # unpacked nothing.  A cohort of counting leavers alone has no
        # extract: its fetch wait is its count
        t_count = t_count or ta
        t_unpack = t_unpack or (t_rows if fetching else t_count)
        t_wait = getattr(resolver, "t_wait", None) or t_unpack
        t_d2h = getattr(resolver, "t_d2h", None) or t_unpack
        # a wait this long with both beats on time is the device's
        hostclock.note_wait("fetch_wait", ta, t_wait)
        met = {name: int(getattr(resolver, name, 0)) for name in
               ("unpack_leavers", "unpack_live", "unpack_rows",
                "unpack_native")}
        met["counted"] = len(counting)
        met["count_us"] = hostclock.split(ta, t_count)[0]
        met["distinct"] = sum(r.distinct for r in fetching)
        stats.add_value("graph.continuous.leaves", len(leavers))
        handed = 0
        with self.cond:
            t_done = time.perf_counter()
            for i, (r, out) in enumerate(zip(leavers, outs)):
                if isinstance(out, Exception):
                    r.error = out
                else:
                    r.mirror = m
                    if out is not None:
                        r.result = out
                    else:
                        r.frontier = vs_lists[i]
                        handed += 1
                r.done_t = t_done
                r.done = True
            self.cond.notify_all()
        return (ta, t_count, t_wait, t_d2h, t_unpack, t_rows), handed, met

    # ------------------------------------------------------- submit
    def submit(self, key: Tuple, payload, steps: int, upto: bool,
               reduce):
        """Queue one rider and block until its leave (or typed
        failure).  Admission happens here, under the stream condition:
        bounded queue + free-lane deadline feasibility — the estimate
        counts SEATS (a lane frees at a hop boundary), not whole
        windows (docs/admission.md)."""
        dl = deadlines.current()
        rider = _Rider(payload, steps, upto, reduce, dl)
        disp = self.sched.dispatcher
        with self.cond:
            if flags.get("admission_control", True):
                depth = len(self.queue)
                qraw = flags.get("admission_queue_max")
                qmax = 256 if qraw is None else int(qraw)
                if depth >= qmax:
                    disp._shed(key, protocol.SHED_QUEUE_FULL, depth)
                if dl is not None:
                    rem = dl.remaining_s()
                    if rem <= 0:
                        disp._deadline_reject(
                            key, protocol.REJECT_EXPIRED, depth)
                    elif self.hop_ema_s > 0.0:
                        # seats free at hop boundaries: if every free
                        # lane seats someone ahead of us we wait >= 1
                        # tick for churn, then ride our hops (one
                        # fewer where the seat may take the first) — a
                        # conservative LOWER bound, so a shed is
                        # provably unmeetable
                        free = self.ledger.free_count() \
                            if self.ledger is not None else None
                        wait_ticks = 0 if (free is None
                                           or free > depth) else 1
                        est_s = self.hop_ema_s * (
                            wait_ticks
                            + max(1, rider.hops - rider.may_advance))
                        if rem < est_s:
                            if depth > 0:
                                disp._shed(
                                    key,
                                    protocol.SHED_DEADLINE_UNMEETABLE,
                                    depth)
                            disp._deadline_reject(
                                key,
                                protocol.REJECT_BUDGET_BELOW_ROUND_TRIP,
                                depth)
            if self.stopping:
                raise ContinuousUnavailable(
                    "stream stopping", protocol.BOUNCE_STREAM_STOPPING)
            self.queue.append(rider)
            self.cond.notify_all()
            while not rider.done:
                if dl is not None and dl.expired():
                    if rider in self.queue:
                        try:
                            # plain list.remove, not a package Status
                            self.queue.remove(rider)  # nebulint: disable=status-discard
                        except ValueError:
                            pass        # pump seated it meanwhile
                        rider.error = DeadlineExceeded(
                            f"go: deadline expired after "
                            f"{(time.perf_counter() - rider.enq_t) * 1e3:.0f}"
                            f" ms in the continuous queue")
                        disp._note_deadline_drop(key)
                        break
                    # seated: the pump evicts at the next hop
                    # boundary; bound the wait to the deadline so the
                    # WAITER never blocks past it either way
                if dl is None:
                    self.cond.wait()
                else:
                    self.cond.wait(max(0.01, dl.remaining_s()))
                    if not rider.done and dl.expired() \
                            and rider not in self.queue:
                        rider.error = DeadlineExceeded(
                            "go: deadline expired mid-flight")
                        disp._note_deadline_drop(key)
                        break
        t_wake = hostclock.stamp()
        if rider.error is None and rider.frontier is not None:
            self._assemble_own(key, rider)
        t_end = hostclock.stamp()
        # the seat trajectory lands on the WAITER's own trace: a
        # PROFILE of the query shows its lane, join and leave tick,
        # whether it merged into an already-running batch, the waits
        # its time here was made of, and HOW its wait ended — one of
        # protocol's closed "continuous-ending" kinds, the vocabulary
        # the eviction dashboards key on
        waits = self._waits(rider, t_wake[0], t_end[0])
        query_registry.note_waits(rider.qid, rider.left_tick, waits)
        # what this thread did meanwhile, on its own clocks: from the
        # enqueue to its wake it slept in cond.wait, and every
        # notify_all of the stream woke it to test rider.done and
        # sleep again: wait_cpu_us / wait_runq_us are what that herd
        # cost this thread in run time and in waiting for a core.
        # assemble_cpu_us / assemble_runq_us split assemble_us.  On
        # the marker only: the registry and the slow log keep the waits
        host = {**hostclock.span_fields("wait_", rider.enq, t_wake),
                **hostclock.span_fields("assemble_", t_wake, t_end)}
        if rider.error is not None:
            if isinstance(rider.error, ContinuousUnavailable):
                ending = protocol.END_BOUNCED
            elif isinstance(rider.error, KilledError):
                ending = protocol.END_KILLED
            elif isinstance(rider.error, DeadlineExceeded):
                ending = (protocol.END_EVICTED if rider.lane >= 0
                          else protocol.END_EXPIRED_QUEUED)
            else:
                ending = protocol.END_STREAM_FAILED
            query_registry.note_ending(rider.qid, ending)
            tracing.annotate("graph.continuous", lane=rider.lane,
                             joined_tick=rider.joined_tick,
                             left_tick=rider.left_tick,
                             ending=ending, **waits, **host)
            raise rider.error
        query_registry.note_ending(rider.qid, protocol.END_LEFT)
        tracing.annotate("graph.continuous", lane=rider.lane,
                         joined_tick=rider.joined_tick,
                         left_tick=rider.left_tick,
                         hops=rider.hops, seat_hops=rider.seat_hops,
                         reduce=rider.reduce[0] if rider.reduce else "",
                         midflight=rider.midflight,
                         ending=protocol.END_LEFT, **waits, **host)
        with self.sched.dispatcher._lock:
            self.sched.dispatcher.stats["continuous_queries"] = \
                self.sched.dispatcher.stats.get("continuous_queries",
                                                0) + 1
        return rider.result, rider.mirror

    def _assemble_own(self, key: Tuple, rider: _Rider) -> None:
        """The post-frontier half of a leaver the pump handed its
        frontier, on the rider's own thread and outside the stream
        condition: the same continuous_results the pump answers its
        own leavers through, over this one statement, against the
        generation the pump extracted under — candidate runs, the
        WHERE in float64, the rows; a k-hop neighbourhood's frontier
        is its rows already (rt.distinct_results).  Its tpu.assemble /
        tpu.where spans land on the rider's own trace.  A per-query
        failure (an Exception entry) becomes this rider's error; a
        rider killed or out of budget since the handover skips the
        pass."""
        if query_registry.is_killed(rider.qid):
            rider.error = KilledError(
                "go: ended by KILL QUERY before its rows were "
                "assembled")
            return
        if rider.deadline is not None and rider.deadline.expired():
            rider.error = DeadlineExceeded(
                "go: deadline expired mid-flight")
            self.sched.dispatcher._note_deadline_drop(key)
            return
        rt = self.sched.runtime
        try:
            if rider.distinct:
                # what the pump handed is final: the vertex rows'
                # ids are the one column, no candidate edge assembled
                out = rt.distinct_results(
                    rider.mirror, [rider.payload], [rider.frontier],
                    [rider.hops])[0]
            else:
                out = rt.continuous_results(
                    self.space_id, rider.mirror, [rider.payload],
                    [rider.reduce], [rider.frontier], self.et_tuple)[0]
        except Exception as ex:         # noqa: BLE001 — this rider's
            out = ex
        if isinstance(out, Exception):
            rider.error = out
        else:
            rider.result = out

    @staticmethod
    def _waits(rider: _Rider, t_wake: float,
               t_end: float) -> Dict[str, int]:
        """The rider's time in submit(), split where the pump and then
        its own thread stamped it: queued until seated, riding until
        it left the seat map, waiting for its cohort's fetch and
        handover, from the handover to this thread running again, and
        assembling its own rows (the fifth of a leaver the pump
        answered is what it took to get here).  A rider that left normally has all five and
        they sum to enq_t -> t_end; one that ended early has the waits
        it got as far as."""
        out: Dict[str, int] = {}
        t = rider.enq_t
        for key, stamp in zip(tracing.RIDER_WAITS,
                              (rider.seated_t, rider.left_t,
                               rider.done_t, t_wake, t_end)):
            if not stamp:
                break
            out[key] = int((stamp - t) * 1e6)
            t = stamp
        return out

    # ------------------------------------------------------ control
    def stop(self, timeout_s: float = 10.0) -> None:
        with self.cond:
            self.stopping = True
            self.cond.notify_all()
        self._pump_thread.join(timeout=timeout_s)


class ContinuousGoScheduler:
    """The continuous-dispatch tier: one _ContinuousStream per
    (space, OVER set), routed to from submit_batched when
    ``go_dispatch_mode=continuous`` and the key is eligible (multi-hop
    GO; BFS/mesh stay windowed).  Scrape-time gauges expose the
    live seat maps — the chaos suite's lane-leak assertion reads
    graph.continuous.seated from /metrics."""

    def __init__(self, runtime, dispatcher: "GoBatchDispatcher"):
        self.runtime = runtime
        self.dispatcher = dispatcher
        self.meter = dispatcher.meter
        self._lock = threading.Lock()
        self._streams: Dict[Tuple, _ContinuousStream] = {}

    @staticmethod
    def route_eligible(key: Tuple) -> bool:
        """Static routing decision from the shape key alone:
        ('go_batch_execute', space, et_tuple, steps, upto, reduce).
        Session-level eligibility (empty mirror, mesh tables) is the
        pump's ContinuousUnavailable fallback."""
        if flags.get("go_dispatch_mode") != "continuous":
            return False
        if int(flags.get("tpu_mesh_devices") or 0) > 1:
            return False
        if key[0] != "go_batch_execute" or len(key) < 6:
            return False
        try:
            steps = int(key[3])
        except (TypeError, ValueError):
            return False
        reduce = key[5]
        if reduce is not None \
                and reduce[0] in ("count_distinct", "distinct"):
            # a k-hop neighbourhood (and its count) rides every one of
            # its steps and leaves with its last frontier (a number):
            # one step is a hop to ride too
            return steps >= 1
        if reduce is not None and reduce[0] not in ("count", "limit"):
            return False
        return steps >= 2

    def submit(self, key: Tuple, payload):
        st = self._stream(key[1], key[2])
        return st.submit(key, payload, int(key[3]), bool(key[4]),
                         key[5])

    def _stream(self, space_id: int, et_tuple: Tuple
                ) -> _ContinuousStream:
        with self._lock:
            st = self._streams.get((space_id, et_tuple))
            # a long-idle stream retires its pump thread; the next
            # submit replaces it (plain bool read — the retired flag
            # only ever flips False -> True)
            if st is None or st.retired:
                st = self._streams[(space_id, et_tuple)] = \
                    _ContinuousStream(self, space_id, et_tuple)
            return st

    def streams(self) -> List[_ContinuousStream]:
        with self._lock:
            return list(self._streams.values())

    def seat_counts(self) -> Tuple[int, int]:
        """(seated, queued) across every stream — the /metrics lane-
        leak surface."""
        seated = queued = 0
        for st in self.streams():
            with st.cond:
                seated += len(st.seated)
                queued += len(st.queue)
        return seated, queued

    def shutdown(self, timeout_s: float = 10.0) -> None:
        for st in self.streams():
            st.stop(timeout_s=timeout_s)


class GoBatchDispatcher:
    def __init__(self, runtime):
        self.runtime = runtime
        self._lock = threading.Lock()
        self._keys: Dict[Tuple, _KeyState] = {}
        self._inflight = _PrioritySlots(
            max(1, int(flags.get("go_batch_inflight") or 3)))
        self.window = _WindowController()
        self.stats = {"batches": 0, "batched_queries": 0, "max_batch": 0,
                      "query_errors": 0, "sheds": 0, "deadline_drops": 0,
                      "continuous_queries": 0}
        # device-utilization proxy shared by both dispatch modes
        # (tpu.device_idle_frac) + the continuous seat-map tier; a
        # runtime without continuous_session (the micro-bench fakes)
        # keeps the windowed pipeline only
        self.meter = _DeviceBusyMeter()
        # the host's two beats, one pair a process however many
        # runtimes it holds (common/hostclock.py)
        hostclock.ensure_started()
        self.continuous = (ContinuousGoScheduler(runtime, self)
                           if hasattr(runtime, "continuous_session")
                           else None)
        self._idle_mark = (0.0, 0.0)    # (busy_s, idle_s) last scrape
        self._load_mark = (0.0, 0.0)    # same meter, load-brief cadence
        # scrape-time gauges: live per-key queue depths + the current
        # closed-loop window cap (weak bound method — a discarded
        # dispatcher unregisters itself)
        stats.register_collector(self._collect_gauges)

    def _state(self, key: Tuple) -> _KeyState:
        with self._lock:
            st = self._keys.get(key)
            if st is None:
                st = self._keys[key] = _KeyState()
            return st

    # ------------------------------------------------------ admission
    @staticmethod
    def _priority_for_key(key: Tuple) -> int:
        """Per-query-class priority (lower = sooner): cheap 1-hop GO
        ahead of multi-hop GO ahead of FIND PATH BFS — interactive
        short reads keep their latency while deep traversals absorb
        the queueing (docs/admission.md).

        GO keys are (method, space, OVER set, steps, upto, reduce):
        the REDUCE descriptor — ("limit", n) / ("count",) / None, the
        LIMIT/COUNT pushdown's per-query result cap — rides the shape
        key so queries sharing a reduction batch into ONE reduced
        device dispatch and never mix with full-fetch traffic whose
        wire shape (and kernel) differs (docs/roofline.md).  A reduced
        query ranks with the 1-hop class: its fetch is a few hundred
        bytes, so it clears the pipeline fastest.  Under a live write
        stream the batch leader's mirror() call may absorb the
        committed delta into the next generation before launching
        (docs/durability.md) — riders coalesced into that dispatch
        read the write-fresh tables, which is what makes the reduce
        descriptor safe to batch at write traffic (the old overlay
        path forced reduced queries onto a full rebuild instead)."""
        method = key[0]
        if method == "go_batch_execute":
            steps = key[3] if len(key) > 3 else 1
            if len(key) > 5 and key[5] is not None:
                return 0             # reduced fetch: interactive class
            try:
                return 0 if int(steps) <= 1 else 1
            except (TypeError, ValueError):
                return 1
        if method == "bfs_batch_dispatch":
            return 2
        return 1

    def _admit(self, key: Tuple, st: _KeyState, dl) -> None:
        """Admission decision for one submit (st.cond held): bounded
        queue + deadline-feasibility check.  Raises AdmissionShed —
        the fast typed failure — instead of letting a query join a
        queue it cannot survive."""
        if not flags.get("admission_control", True):
            return
        depth = len(st.queue)
        qraw = flags.get("admission_queue_max")
        # explicit 0 means "shed everything" (an operator draining a
        # graphd) — no falsy-`or` default here
        qmax = 256 if qraw is None else int(qraw)
        if depth >= qmax:
            self._shed(key, protocol.SHED_QUEUE_FULL, depth)
        if dl is not None:
            rem = dl.remaining_s()
            if rem <= 0:
                # already expired on arrival: the CLIENT's budget
                # failed, not this daemon — typed fast failure without
                # the shed/overload counters (a tight TIMEOUT on an
                # idle graphd must never flip /healthz)
                self._deadline_reject(key, protocol.REJECT_EXPIRED,
                                      depth)
            elif st.rt_ema_s > 0.0:
                # batches ahead of us (the backlog dispatches in
                # ceil(depth/max_b) batches) plus our own — each costs
                # ~one measured round trip.  A conservative LOWER
                # bound: if even that exceeds the remaining budget,
                # the query cannot finish in time and queuing it only
                # steals batch width from queries that can
                max_b = max(1, int(flags.get("go_batch_max") or 1024))
                est_s = st.rt_ema_s * (depth // max_b + 1)
                if rem < est_s:
                    if depth > 0:
                        # a BACKLOG makes the budget unmeetable —
                        # that is overload: shed
                        self._shed(key,
                                   protocol.SHED_DEADLINE_UNMEETABLE,
                                   depth)
                    # empty queue: the budget is simply smaller than
                    # one batch round trip — client-chosen, not load
                    self._deadline_reject(
                        key, protocol.REJECT_BUDGET_BELOW_ROUND_TRIP,
                        depth)

    def _shed(self, key: Tuple, reason: str, depth: int) -> None:
        stats.add_value("graph.admission.shed")
        if reason != protocol.SHED_QUEUE_FULL:
            stats.add_value("graph.admission.deadline_exceeded")
        with self._lock:
            self.stats["sheds"] += 1
        journal.record("query.shed",
                       detail=f"{reason} {key[0]} depth={depth}",
                       space=key[1])
        tracing.annotate("graph.admission",
                         decision=protocol.DECISION_SHED,
                         reason=reason, depth=depth, method=key[0])
        raise AdmissionShed(
            f"query shed at admission ({reason}): {key[0]} queue depth "
            f"{depth}", reason)

    def _deadline_reject(self, key: Tuple, reason: str,
                         depth: int) -> None:
        """Client-budget fast failure at admission: typed
        DEADLINE_EXCEEDED, deadline counters, trace marker — but NOT a
        shed (no overload counters, no query.shed journal entry, no
        /healthz degradation: the budget was the caller's choice)."""
        self._note_deadline_drop(key)
        tracing.annotate("graph.admission", decision=reason,
                         depth=depth, method=key[0])
        raise DeadlineExceeded(
            f"{key[0]}: remaining budget cannot cover one dispatch "
            f"({reason})")

    def _note_deadline_drop(self, key: Tuple) -> None:
        stats.add_value("graph.admission.deadline_exceeded")
        with self._lock:
            self.stats["deadline_drops"] += 1

    def queue_depths(self) -> Dict[Tuple, int]:
        """Live queue depth per key — the shared source for the
        scrape-time gauges and SHOW STATS' live admission row."""
        with self._lock:
            keys = list(self._keys.items())
        out: Dict[Tuple, int] = {}
        for key, st in keys:
            with st.cond:
                out[key] = len(st.queue)
        return out

    # nebulint: mc=caller-synced/_load_mark is written solely from the
    # single metrics scrape thread (heartbeat loop); no scenario thread
    # ever enters this read-side brief
    def load_brief(self) -> dict:
        """One rankable serving-load struct per graphd replica
        (docs/observability.md): live queue depth summed across keys,
        continuous lane occupancy, device busy fraction since the
        last brief, and the 5 s shed rate.  Rides the role=graph
        heartbeat into metad's ``listDeviceBriefs`` — an external
        balancer ranks replicas on it — and is republished verbatim
        as the graph.load.* gauges so the ranking input is always
        inspectable on /metrics."""
        seated = queued = 0
        if self.continuous is not None:
            seated, queued = self.continuous.seat_counts()
        busy, idle = self.meter.snapshot()
        d_busy = busy - self._load_mark[0]
        d_idle = idle - self._load_mark[1]
        self._load_mark = (busy, idle)
        total = d_busy + d_idle
        return {
            "queue_depth": int(sum(self.queue_depths().values())),
            "lane_seated": int(seated),
            "lane_queued": int(queued),
            "busy_frac": round(d_busy / total, 4) if total > 0 else 0.0,
            "shed_rate_5s":
                stats.read_stats("graph.admission.shed.count.5") or 0.0,
        }

    # nebulint: mc=caller-synced/_idle_mark is written solely from the
    # single metrics scrape thread registered with stats.add_collector
    def _collect_gauges(self) -> None:
        brief = self.load_brief()
        for k, v in brief.items():
            stats.set_gauge(f"graph.load.{k}", float(v))
        for key, depth in self.queue_depths().items():
            stats.set_gauge("graph.admission.queue_depth", depth,
                            method=str(key[0]), space=str(key[1]))
        stats.set_gauge("graph.admission.window_ms",
                        round(self.window.cap_s() * 1000.0, 3))
        # device idle share since the previous scrape — the continuous
        # pipeline's headline gauge (1.0 = the device did nothing)
        busy, idle = self.meter.snapshot()
        d_busy = busy - self._idle_mark[0]
        d_idle = idle - self._idle_mark[1]
        self._idle_mark = (busy, idle)
        if d_busy + d_idle > 0:
            stats.set_gauge("tpu.device_idle_frac",
                            round(d_idle / (d_busy + d_idle), 4))
        if self.continuous is not None:
            seated, queued = self.continuous.seat_counts()
            stats.set_gauge("graph.continuous.seated", seated)
            stats.set_gauge("graph.continuous.queued", queued)
            if d_busy + d_idle > 0:
                # deliberately the SAME measurement as
                # tpu.device_idle_frac, exported under the serving-
                # tier family name too: one _DeviceBusyMeter covers
                # both dispatch modes (dashboards keyed on either
                # name read identical values by design)
                stats.set_gauge("graph.continuous.idle_frac",
                                round(d_idle / (d_busy + d_idle), 4))
        # the window controller's depth EMA + the recent shed rate as
        # a replica-count recommendation (docs/admission.md): depth at
        # the reference means the fleet needs ~2x the capacity; active
        # shedding always asks for one more
        depth_ema = self.window.depth()
        ref_raw = flags.get("admission_window_depth_ref")
        ref = 8.0 if ref_raw is None else float(ref_raw)
        shed5 = stats.read_stats("graph.admission.shed.count.5") or 0.0
        reco = math.ceil(1.0 + (depth_ema / ref if ref > 0 else 0.0))
        if shed5 > 0:
            reco += 1
        cap = int(flags.get("autoscale_max_replicas") or 8)
        stats.set_gauge("graph.autoscale.recommended_replicas",
                        min(max(1, reco), cap))

    # ---------------------------------------------------------- submit
    def submit_batched(self, key: Tuple, payload):
        """Coalesce any batched runtime entry point: ``key[0]`` names a
        runtime method with signature ``fn(space_id, payloads, *key[2:])
        -> (per-query results, mirror)`` — or a two-phase ``_Pending``
        (an object with ``.finish()``) whose launch half has already
        run.  Requests sharing the key ride one device dispatch.  A
        per-query result that is an Exception instance is raised only
        for its own submitter.

        The calling thread's deadline budget (common/deadline.py) is
        captured at admission: an unmeetable budget sheds here, an
        expired one wakes the waiter with DEADLINE_EXCEEDED even while
        its batch is still in flight — no waiter ever blocks past its
        deadline.

        Continuous routing (docs/admission.md "Continuous dispatch"):
        an eligible multi-hop GO key rides the seat-map tier instead
        of the windowed pipeline below; a stream that cannot anchor a
        device session (empty mirror, mesh tables) bounces the rider
        back here typed, so the windowed path stays the universal
        fallback."""
        if self.continuous is not None \
                and ContinuousGoScheduler.route_eligible(key):
            try:
                return self.continuous.submit(key, payload)
            except ContinuousUnavailable:
                pass                    # windowed fallback below
        st = self._state(key)
        dl = deadlines.current()
        req = _Request(payload, dl)
        st.cond.acquire()
        try:
            self._admit(key, st, dl)         # may raise AdmissionShed
            st.queue.append(req)
            while not req.done:
                if dl is not None and dl.expired():
                    # budget gone while waiting: leave the queue (or
                    # abandon the in-flight batch's result) and fail
                    # fast — the leader setting fields on an abandoned
                    # request is harmless
                    try:
                        st.queue.remove(req)
                    except ValueError:
                        pass                 # already snapshotted
                    req.error = DeadlineExceeded(
                        f"{key[0]}: deadline expired after "
                        f"{(time.perf_counter() - req.enq_t) * 1e3:.0f} ms "
                        f"in the admission queue")
                    self._note_deadline_drop(key)
                    break
                if st.dispatching or not st.queue:
                    if dl is None:
                        st.cond.wait()
                    else:
                        st.cond.wait(max(0.0, dl.remaining_s()))
                    continue
                # become the leader for the next batch.  ANY failure
                # between taking leadership and entering _run (whose
                # finally hands it back) must reset `dispatching`, or
                # every future request on this key waits forever
                st.dispatching = True
                sem_held = False
                # a lone request on an idle key skips the pooling wait
                # entirely — there is nothing to pool with, and taxing
                # solo interactive queries a window is a pure latency
                # regression (arrivals during its round trip still pool
                # behind it via self-clocking).  A queue already at
                # go_batch_max skips it too: the batch is full, the
                # wait could pool nothing
                qlen = len(st.queue)
                self.window.observe_depth(qlen)
                no_wait = qlen <= 1 or \
                    qlen >= int(flags.get("go_batch_max") or 1024)
                # snapshot the round-trip EMA while st.cond is still
                # held: _window_s runs after the release below, and a
                # concurrent leader's EMA update would race the bare
                # read (guard-inference audit, round 10)
                rt_ema_s = st.rt_ema_s
                try:
                    # take the pipeline slot BEFORE snapshotting the
                    # batch: while go_batch_inflight batches are already
                    # on the device, arrivals pool in the queue and the
                    # next leader takes them ALL — batching self-clocks
                    # to the device's cadence with no timer and no idle
                    # latency penalty
                    st.cond.release()
                    try:
                        # any configured window runs BEFORE taking the
                        # slot — sleeping while holding it would park
                        # pipeline capacity the device could be using.
                        # (_window_s always evaluates so corrupt flag
                        # values fail fast even for lone requests)
                        window = self._window_s(rt_ema_s)
                        if no_wait:
                            window = 0.0
                        if window > 0:
                            time.sleep(window)
                        self._inflight.acquire(self._priority_for_key(key))
                        sem_held = True
                        self.meter.begin()
                    finally:
                        st.cond.acquire()
                    max_b = int(flags.get("go_batch_max") or 1024)
                    batch = st.queue[:max_b]
                    del st.queue[:max_b]
                except BaseException:       # cond is held here
                    if sem_held:
                        self._inflight.release()
                        self.meter.end()
                    st.dispatching = False
                    st.cond.notify_all()
                    raise
                st.cond.release()
                released = [False]

                def release_leadership():
                    # device work for this batch is queued; the next
                    # leader may launch while we finish the transfer +
                    # host assembly
                    with st.cond:
                        st.dispatching = False
                        st.cond.notify_all()
                    released[0] = True

                try:
                    self._run(key, batch, release_leadership)
                finally:
                    st.cond.acquire()
                    if not released[0]:
                        st.dispatching = False
                    st.cond.notify_all()
        finally:
            st.cond.release()
        if req.done_t is not None:
            # the rider's time in here, in order, on its OWN trace:
            # pooled behind the batch in flight and the window, its
            # batch on the device and through the fetch, until its
            # thread runs again
            woke = time.perf_counter()
            tracing.annotate(
                "graph.batched", method=key[0], riders=req.riders,
                pool_wait_us=int((req.run_t - req.enq_t) * 1e6),
                run_us=int((req.done_t - req.run_t) * 1e6),
                wake_us=int((woke - req.done_t) * 1e6))
        if req.error is not None:
            if isinstance(req.error, DeadlineExceeded) \
                    and not isinstance(req.error, AdmissionShed):
                # the admission decision lands on the WAITER's own
                # trace (the leader thread can't reach it): a PROFILE
                # of the failed query shows why it never launched
                tracing.annotate("graph.admission",
                                 decision=protocol.DECISION_DEADLINE_DROP,
                                 method=key[0])
            raise req.error
        return req.result, req.mirror

    # ------------------------------------------------------------------
    def _window_s(self, rt_ema_s: float) -> float:
        """Pooling wait (seconds) the next leader observes before it
        takes a pipeline slot, from a round-trip EMA the caller
        SNAPSHOTTED under the key's condition (this runs after the
        leader released it).  Adaptive mode scales with the key's
        measured batch round-trip: slow round-trips pool arrivals into
        wider batches (the per-batch fetch cost is flat in batch
        width), fast ones collapse the wait to ~nothing —
        the same no-tuning philosophy as the backend router.  The cap
        is the CLOSED-LOOP controller's (queue depth scales the
        go_batch_window_max_ms flag down), replacing the static cap."""
        raw = flags.get("go_batch_window_ms")
        window_ms = float(raw if raw is not None else -1)
        if window_ms >= 0:
            return window_ms / 1000.0
        # explicit 0 must mean 0 (an operator disabling the wait), so
        # no falsy-`or` fallbacks here
        frac_raw = flags.get("go_batch_window_frac")
        frac = 0.12 if frac_raw is None else float(frac_raw)
        return min(rt_ema_s * frac, self.window.cap_s())

    # ------------------------------------------------------------------
    def _run(self, key: Tuple, batch: List[_Request],
             release_leadership) -> None:
        method, space_id = key[0], key[1]
        st_key = self._state(key)
        t_run0 = time.perf_counter()
        n_errors = 0
        live = batch
        for r in batch:
            r.run_t, r.riders = t_run0, len(batch)
        try:
            if flags.get("admission_control", True):
                # pre-launch expiry drop: entries whose budget ran out
                # while queued never reach the device — their waiters
                # wake with DEADLINE_EXCEEDED via the same per-query
                # exception machinery a poisoned query uses
                live = []
                for r in batch:
                    if r.deadline is not None and r.deadline.expired():
                        r.error = DeadlineExceeded(
                            f"{method}: budget exhausted in the "
                            f"admission queue (dropped pre-launch)")
                        self._note_deadline_drop(key)
                    elif query_registry.is_killed(r.qid):
                        # KILL QUERY of a windowed waiter rides the
                        # same per-query exception machinery as a
                        # pre-launch expiry: the batch launches
                        # without it, the waiter wakes typed
                        r.error = KilledError(
                            f"{method}: ended by KILL QUERY (dropped "
                            f"pre-launch)")
                    else:
                        live.append(r)
            if live:
                # admission wait of the OLDEST rider — one histogram
                # observation per batch, the tail-relevant sample
                stats.observe(
                    "graph.admission.wait_us",
                    (time.perf_counter()
                     - min(r.enq_t for r in live)) * 1e6)
            # the leader already holds an in-flight slot (acquired
            # before the batch snapshot in submit_batched)
            try:
                if live:
                    fn = getattr(self.runtime, method)
                    res = fn(space_id, [r.payload for r in live],
                             *key[2:])
                    if hasattr(res, "finish"):   # two-phase _Pending
                        release_leadership()
                        results, mirror = res.finish()
                    else:
                        results, mirror = res
                    # round-trip sample for the adaptive window
                    # (results are materialized here; waiters wake just
                    # after).  EMA weight 0.3: a regime change (link
                    # congestion, kernel shape shift) re-centers within
                    # a few batches without single-outlier jitter
                    dur = time.perf_counter() - t_run0
                    with st_key.cond:
                        st_key.rt_ema_s = dur if st_key.rt_ema_s == 0.0 \
                            else 0.7 * st_key.rt_ema_s + 0.3 * dur
                    self.window.observe_latency(dur)
                else:
                    results, mirror = [], None
            finally:
                self._inflight.release()
                self.meter.end()
            for i, r in enumerate(live):
                out = results[i]
                if isinstance(out, Exception):
                    r.error = out                # only this waiter fails
                    n_errors += 1
                else:
                    r.result = out
                    r.mirror = mirror
        except BaseException as ex:        # noqa: BLE001 — batch-level
            for r in batch:                # failure wakes every waiter
                if r.error is None and r.result is None:
                    r.error = ex
            if not isinstance(ex, Exception):
                raise                      # KeyboardInterrupt etc.
        finally:
            with self._lock:   # leaders for different keys run concurrently
                self.stats["batches"] += 1
                self.stats["batched_queries"] += len(batch)
                self.stats["query_errors"] += n_errors
                self.stats["max_batch"] = max(self.stats["max_batch"],
                                              len(batch))
            t_done = time.perf_counter()
            for r in batch:
                r.done_t = t_done
                r.done = True
