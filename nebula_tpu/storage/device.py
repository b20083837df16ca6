"""Cross-process device serving — the graphd half.

The reference's seam for swapping storage backends is the StorageService
RPC surface (StorageServiceHandler.cpp:1-119).  This module is graphd's
client for the device-backed half of that surface
(``rpc_deviceGo`` / ``rpc_deviceFindPath``, storage/service.py): the
standalone graphd daemon ships a WHOLE multi-hop GO (or FIND PATH) —
encoded start vids, OVER set, WHERE and YIELD expression trees — to the
storaged that leads every part of the space, where the HBM-resident CSR
mirror answers it in one device dispatch (tpu/runtime.py serve_go).
That replaces the reference's per-hop getNeighbors RPC fan-out
(GoExecutor.cpp:334-431) with ONE round trip per query.

Fallback contract: when the storaged declines (device disabled,
non-leader, uncompilable filter, schema drift) the proxy raises
``TpuDecline`` and the executor falls back to the per-hop CPU loop —
the same "backend can't serve → CPU storaged path" behavior the
reference's architecture implies (SURVEY.md §7 step 5).

This module must stay jax-free: it is imported by the stateless graphd
daemon, which never touches the device.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from ..common import mc_hooks, protocol
from ..common.deadline import DeadlineExceeded
from ..common.flags import flags
from ..common.stats import stats
from ..common.status import ErrorCode, Status
from ..filter.expressions import encode_expr
from ..graph.interim import InterimResult
from ..interface.common import HostAddr
from ..interface.rpc import RpcError


class TpuDecline(Exception):
    """The device path cannot serve this query — fall back to the CPU
    executor loop.  Raised by both the remote proxy (this module) and
    the storaged-side runtime (tpu/runtime.py serve_go).

    ``degraded=True`` marks declines caused by a device RUNTIME failure
    or an open circuit breaker (not a semantic can't-serve): the CPU
    fallback still answers, but executors surface a warning +
    completeness < 100 so operators see the degradation on the query
    surface, not only on /metrics (docs/durability.md)."""

    def __init__(self, msg: str = "", degraded: bool = False,
                 retriable: bool = False):
        super().__init__(msg)
        self.degraded = degraded
        # the replica that raised this decline (tagged by the failover
        # ladder) — negative caches blame it, not the preferred rung
        self.host = None
        # ``retriable=True`` marks declines another REPLICA of the same
        # parts might serve (transport failure, degraded runtime, open
        # breaker) — the failover ladder retries those on the next
        # healthy replica before falling back to the CPU loop
        # (docs/durability.md "The failover ladder").  Semantic
        # declines (can't-serve-this-query) repeat identically on
        # every replica and go straight to the CPU path.
        self.retriable = retriable


class DeviceExecError(Exception):
    """A real query error on the storaged-side device path (schema
    drift mid-query, per-row missing props under graphd WHERE
    semantics) — maps to ExecutionResponse error, NOT a CPU fallback."""


# ------------------------------------------------------- failover ladder
flags.define("device_failover_replicas", 3,
             "replicas of the SAME parts graphd tries per device query "
             "before falling back to the CPU loop: on a degraded "
             "decline (device-runtime failure / open breaker) or a "
             "transport failure, the next-freshest healthy replica "
             "retries the query; 1 disables the ladder "
             "(docs/durability.md \"The failover ladder\")")
flags.define("device_decline_ttl_s", 15.0,
             "seconds a replica that answered degraded (or was "
             "unreachable) is deprioritized in the failover ladder "
             "before graphd probes it again — the UPTO-style TTL'd "
             "per-(host, space) decline cache")

stats.register_stats("graph.device_failover.retries")
stats.register_stats("graph.device_failover.served")
stats.register_stats("graph.device_failover.exhausted")
stats.register_stats("graph.device_failover.decline_skips")


# ---------------------------------------------------------------- breaker
flags.define("tpu_breaker_failures", 3,
             "consecutive classified device-runtime failures of one "
             "(space, kernel-class) before its circuit breaker OPENS "
             "and queries decline straight to the CPU path; 0 disables "
             "the breaker (docs/durability.md)")
flags.define("tpu_breaker_open_s", 30.0,
             "seconds an OPEN device breaker declines before it half-"
             "opens and lets ONE probe query try the device again")


def classify_device_failure(exc: BaseException) -> Optional[str]:
    """Classify an exception as a device RUNTIME failure, or None.

    tpu/runtime.py historically caught only CompileError; everything the
    accelerator throws at dispatch/transfer time (jaxlib's
    XlaRuntimeError, RESOURCE_EXHAUSTED / HBM OOM, transfer failures)
    escaped as generic exceptions.  This classifier is what feeds the
    circuit breaker — typed by NAME and message, not by import, so the
    jax-free graphd daemon can classify a peer's reported failure too.
    Typed query/control errors (declines, exec errors, deadline/shed)
    are never device failures."""
    if isinstance(exc, (TpuDecline, DeviceExecError, DeadlineExceeded)):
        return None
    low = str(exc).lower()
    if protocol.DEVFAIL_RESOURCE_EXHAUSTED in low \
            or "resource exhausted" in low \
            or "out of memory" in low or "hbm" in low:
        return protocol.DEVFAIL_RESOURCE_EXHAUSTED
    if (protocol.DEVFAIL_TRANSFER in low or "copy" in low) \
            and ("fail" in low or "error" in low or "abort" in low):
        return protocol.DEVFAIL_TRANSFER
    for klass in type(exc).__mro__:
        if klass.__name__ == "XlaRuntimeError":
            return protocol.DEVFAIL_XLA_RUNTIME
    return None


class _BreakerCell:
    __slots__ = ("state", "fails", "opened_at", "probing", "last_reason")

    def __init__(self):
        self.state = "closed"
        self.fails = 0
        self.opened_at = 0.0
        self.probing = False
        self.last_reason = ""


class DeviceCircuitBreaker:
    """Circuit breaker per (space_id, kernel-class) over the device
    dispatch path (docs/durability.md state machine):

      CLOSED     serving; ``tpu_breaker_failures`` consecutive
                 classified runtime failures -> OPEN (journal
                 ``tpu.breaker_open``)
      OPEN       every admit declines instantly (callers raise
                 ``TpuDecline(degraded=True)`` -> CPU fallback with the
                 degradation surfaced); after ``tpu_breaker_open_s``
                 the next admit half-opens
      HALF_OPEN  exactly one probe query runs on the device; success
                 -> CLOSED (``tpu.breaker.reclosed``), failure -> OPEN
                 with a fresh clock

    The CLOSED check is one dict probe + one attribute compare with no
    lock (micro_bench recovery_path pins it ≲1 µs/op) — the breaker is
    off the hot path until something actually fails.  A mirror rebuild
    (``reset_space``, called from the runtime's publish — the
    generation-checked seam, like PR 4's ``_upto_declined``) half-opens
    an OPEN breaker immediately: fresh state deserves a fresh probe."""

    def __init__(self):
        # seam-constructed: the real OrderedLock in production, an
        # instrumented shim while nebulamc explores the half-open
        # probe races (tools/mc/scenarios.py breaker-probe)
        self._lock = mc_hooks.OrderedLock("tpu.breaker")
        # nebulint: guarded-by=_lock (state transitions; the CLOSED
        # probes below are the documented lock-free exceptions)
        self._cells: Dict[Tuple[int, str], _BreakerCell] = {}

    # ------------------------------------------------------- hot path
    def admit(self, key: Tuple[int, str]) -> Optional[str]:
        """None = run on the device (possibly as the half-open probe);
        a string = decline reason (breaker open)."""
        # lock-free fast path; anything non-closed re-reads under the
        # lock below.  The mc_yield marks the bare read as a scheduling
        # point so the explorer can interleave a state transition
        # between it and the locked re-read — the exact window this
        # fast path is designed to tolerate
        mc_hooks.mc_yield("breaker.admit.fast", self)
        # nebulint: disable=guard-inference
        cell = self._cells.get(key)
        if cell is None or cell.state == "closed":
            return None
        from ..common.stats import stats
        with self._lock:
            cell = self._cells.get(key)
            if cell is None or cell.state == "closed":
                return None
            if cell.state == "open":
                open_s = float(flags.get("tpu_breaker_open_s") or 30.0)
                if time.monotonic() - cell.opened_at >= open_s:
                    cell.state = "half_open"
                    cell.probing = False
            if cell.state == "half_open" and not cell.probing:
                cell.probing = True
                stats.add_value("tpu.breaker.probes")
                return None                  # this caller IS the probe
            stats.add_value("tpu.breaker.fast_fail")
            return (f"device breaker open for {key[1]} on space "
                    f"{key[0]} ({cell.last_reason})")

    def is_open(self, key: Tuple[int, str]) -> bool:
        """Non-mutating peek (no probe token consumed): used by the
        in-process can_run_* gates to route to CPU without paying a
        plan/mirror attempt against a known-broken device."""
        # deliberately lock-free: a stale peek routes one query to the
        # wrong path once, never corrupts breaker state
        # nebulint: disable=guard-inference
        cell = self._cells.get(key)
        if cell is None or cell.state == "closed":
            return False
        if cell.state == "open":
            open_s = float(flags.get("tpu_breaker_open_s") or 30.0)
            return time.monotonic() - cell.opened_at < open_s
        return False                         # half-open: let it probe

    # ------------------------------------------------------ accounting
    def release_probe(self, key: Tuple[int, str]) -> None:
        """A half-open probe ended WITHOUT exercising the device (a
        deadline fired first, a semantic decline, a plain query error):
        hand the token back so the NEXT query probes — but do NOT
        close the cell (only a real device success proves health) and
        do NOT clear the consecutive-failure count on closed cells (an
        unclassified error is neutral, not a device success)."""
        # lock-free empty probe; the mutation re-reads under the lock
        mc_hooks.mc_yield("breaker.release_probe.fast", self)
        # nebulint: disable=guard-inference
        cell = self._cells.get(key)
        if cell is None:
            return
        with self._lock:
            cell = self._cells.get(key)
            if cell is not None and cell.state == "half_open":
                cell.probing = False

    def record_success(self, key: Tuple[int, str]) -> None:
        # hot path: nothing tracked for a healthy cell; any real
        # transition re-reads under the lock below
        mc_hooks.mc_yield("breaker.record_success.fast", self)
        # nebulint: disable=guard-inference
        cell = self._cells.get(key)
        if cell is None or (cell.state == "closed" and cell.fails == 0):
            return
        from ..common.stats import stats
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                return
            reclosed = cell.state != "closed"
            cell.state = "closed"
            cell.fails = 0
            cell.probing = False
        if reclosed:
            stats.add_value("tpu.breaker.reclosed")

    def record_failure(self, key: Tuple[int, str], reason: str) -> None:
        from ..common.events import journal
        from ..common.stats import stats
        threshold = int(flags.get("tpu_breaker_failures") or 0)
        if threshold <= 0:
            return                           # breaker disabled
        with self._lock:
            cell = self._cells.get(key)
            if cell is None:
                cell = self._cells[key] = _BreakerCell()
            cell.fails += 1
            cell.last_reason = reason
            opened = False
            if cell.state == "half_open" \
                    or (cell.state == "closed" and cell.fails >= threshold):
                cell.state = "open"
                cell.opened_at = time.monotonic()
                cell.probing = False
                opened = True
        stats.add_value("tpu.breaker.failures")
        if opened:
            stats.add_value("tpu.breaker.opened")
            # journaled OUTSIDE the breaker lock (events takes its own
            # leaf lock)
            journal.record("tpu.breaker_open",
                           detail=f"{key[1]} on space {key[0]}: {reason}",
                           space=key[0], kernel_class=key[1],
                           reason=reason)

    def reset_space(self, space_id: int) -> None:
        """Generation change (mirror rebuilt over fresh store state —
        e.g. after a storaged restart re-heartbeats and the runtime
        republishes): an OPEN breaker half-opens immediately so the
        next query probes the device against the NEW mirror instead of
        waiting out the clock; accumulated failure counts clear."""
        with self._lock:
            for k, cell in self._cells.items():
                if k[0] != space_id:
                    continue
                if cell.state == "open":
                    cell.opened_at = 0.0     # next admit half-opens
                cell.fails = 0

    def cells_snapshot(self) -> List[Tuple[Tuple[int, str], str, str]]:
        """[(key, state, last_reason)] for /healthz + the metrics
        collector (tpu.breaker.state gauges)."""
        with self._lock:
            return [(k, c.state, c.last_reason)
                    for k, c in self._cells.items()]


class _LedPartStub:
    """Minimal Part facade for parts a REMOTE peer reports leading —
    build_mirror only asks is_leader() (csr.py); the peer re-verifies
    leadership on every scan chunk."""

    __slots__ = ()

    def is_leader(self) -> bool:
        return True


# ---------------------------------------------------------- peer deltas
# Fused peer-version encoding (docs/durability.md "The peer-delta
# cursor protocol"): a RemoteStoreView reports (boot epoch, led-set
# generation, mutation version) fused into ONE integer, so the
# runtime's per-store delta cursors — plain ints captured at publish —
# carry the peer's whole stream identity.  A restart or a leadership
# move changes the fused value (staleness detected even when the
# replayed version counter lands on the same number), and delta_since
# decodes the anchor back out to type the decline exactly.
_LED_MOD = 1 << 14
_VER_MOD = 1 << 34


def fuse_peer_version(epoch: int, led_gen: int, version: int) -> int:
    return ((int(epoch) * _LED_MOD + int(led_gen) % _LED_MOD)
            * _VER_MOD + int(version) % _VER_MOD)


def split_peer_version(fused: int):
    """(epoch, led_gen, version) back out of a fused cursor."""
    return (int(fused) // (_LED_MOD * _VER_MOD),
            (int(fused) // _VER_MOD) % _LED_MOD,
            int(fused) % _VER_MOD)


class RemoteStoreView:
    """Store-shaped READ view of one peer storaged's led parts, backing
    the multi-host CSR mirror fold (VERDICT round-2 missing #1): the
    device-serving storaged composes its local NebulaStore with one
    view per peer, so build_mirror scans the WHOLE space — remote parts
    stream over the `deviceScan` RPC in chunks, and `deviceVersion`
    polls the peer's mutation counter + led-part set for the staleness
    check.  This is the reference's scatter-gather
    (StorageClient.h:176-196) moved from query time to MIRROR BUILD
    time, which is what lets the whole multi-hop loop stay in one
    device dispatch.

    Consistency contract: a peer's committed writes STREAM over the
    ``deviceScanDelta`` RPC as monotonically-sequenced typed events
    (ROADMAP item 5 landed): ``delta_since`` fetches exactly the
    ``(cursor, polled-version]`` window, so the runtime folds peer
    writes through ``ell_absorb`` at O(delta) the same way locally-led
    writes absorb.  Any break in the stream — peer restart (epoch),
    leadership move (led_gen), trimmed log, opaque window, cursor gap
    — is detected from the fused cursor + the peer's typed verdict and
    surfaces as a ``mirror.absorb_failed`` reason (peer-*) that
    degrades to the existing background rebuild; the rebuild's publish
    re-anchors the cursor at the scan snapshot and absorption resumes
    (re-subscribe is implicit: the next delta window continues from
    the fresh anchor)."""

    POLL_REUSE_S = 0.02
    RPC_TIMEOUT_S = 10.0    # a hung peer fails the build fast instead of
                            # stalling the rebuilding space for 30 s/call
    is_remote = True        # the absorb path labels peer windows with
                            # this (tpu.peer_absorb.* accounting)

    def __init__(self, host: HostAddr, space_id: int, client_manager):
        self.host = host
        self.space_id = space_id
        self.cm = client_manager
        self._led: List[int] = []
        self._version = -1          # raw peer mutation version
        self._epoch = 0
        self._led_gen = 0
        self._polled_at = 0.0
        # delta-stream health for the /healthz peer_mirror check
        # (storage/web.py): when the subscribed cursor last advanced
        # to the peer's published version, and since when it has been
        # wedged (typed declines / unreachable peer) while the peer's
        # version sat ahead of it
        self.last_delta_decline: Optional[str] = None
        self._stalled_since = 0.0

    def refresh(self) -> bool:
        """Poll version + led parts; False when the peer is down."""
        import time
        try:
            resp = self.cm.call(self.host, "deviceVersion",
                                {"space_id": self.space_id},
                                timeout=self.RPC_TIMEOUT_S)
        except RpcError:
            self._led = []
            self._polled_at = 0.0
            return False
        self._led = [int(p) for p in resp.get("led_parts", [])]
        self._version = int(resp.get("version", 0))
        self._epoch = int(resp.get("epoch") or 0)
        self._led_gen = int(resp.get("led_gen") or 0)
        self._polled_at = time.monotonic()
        if self.last_delta_decline == protocol.PEER_UNREACHABLE:
            # the peer is back; an unreachable-stall must not outlive
            # the outage (typed STREAM breaks instead clear when the
            # rebuild's full scan completes — prefix() below)
            self._note_advanced()
        return True

    # ---- store-shaped surface (what build_mirror + runtime touch) ----
    def part_ids(self, space_id: int) -> List[int]:
        return sorted(self._led)

    def part(self, space_id: int, part_id: int):
        return _LedPartStub() if part_id in self._led else None

    def mutation_version(self, space_id: int) -> int:
        import time
        # the serving gate refreshes unconditionally right before the
        # runtime's version check — reuse that poll instead of paying a
        # second identical round-trip per query.  Any poll taken after
        # a committed write sees it, so reuse never hides one
        if time.monotonic() - self._polled_at <= self.POLL_REUSE_S:
            return fuse_peer_version(self._epoch, self._led_gen,
                                     self._version)
        if not self.refresh():
            # an unreachable peer must FAIL the version check / mirror
            # build (callers decline to the CPU path) — quietly
            # reporting an empty led set would let build_mirror publish
            # a partial mirror and serve incomplete rows as success
            self._note_stalled(protocol.PEER_UNREACHABLE)
            raise RpcError(Status(
                ErrorCode.E_FAIL_TO_CONNECT,
                f"peer {self.host} unreachable for device mirror"))
        return fuse_peer_version(self._epoch, self._led_gen,
                                 self._version)

    def _note_stalled(self, reason: str) -> None:
        self.last_delta_decline = reason
        if self._stalled_since == 0.0:
            self._stalled_since = time.monotonic()

    def _note_advanced(self) -> None:
        self.last_delta_decline = None
        self._stalled_since = 0.0

    def stalled_for_s(self) -> float:
        """Seconds the subscribed delta cursor has been wedged behind
        the peer's published version (0.0 = healthy / idle) — the
        /healthz peer_mirror probe's signal (storage/web.py)."""
        if self._stalled_since == 0.0:
            return 0.0
        return time.monotonic() - self._stalled_since

    def delta_since(self, space_id: int, from_version: int):
        """Streamed peer-delta window: typed events covering
        ``(anchor, polled-version]`` over the ``deviceScanDelta`` RPC,
        or None with ``last_delta_decline`` typed (peer-restarted /
        peer-leader-changed / peer-cursor-truncated /
        peer-opaque-events / peer-cursor-gap / peer-unreachable /
        peer-unsupported) — the absorb path journals the reason and
        degrades to the background rebuild, which re-anchors the
        cursor at its scan snapshot."""
        from ..common import tracing
        epoch_c, led_gen_c, ver_c = split_peer_version(from_version)
        # SNAPSHOT the polled identity once: the view is shared across
        # query threads and a concurrent refresh() (serving gate /
        # another absorb) may re-poll mid-window — comparing against
        # moving fields would fabricate gap declines
        epoch_now, led_gen_now = self._epoch, self._led_gen
        upto = self._version
        # compare the ANCHOR identity against the freshly polled one:
        # any mismatch means events after ver_c belong to a different
        # history (reboot) or part membership (leadership move) and
        # can never be contiguous with the anchor
        if epoch_c != epoch_now:
            self._note_stalled(protocol.PEER_RESTARTED)
            return None
        # the cursor carries led_gen modulo _LED_MOD — compare in the
        # same ring, or a peer whose led set changed 2^14+ times would
        # mismatch forever (every window paying the rebuild)
        if led_gen_c != led_gen_now % _LED_MOD:
            self._note_stalled(protocol.PEER_LEADER_CHANGED)
            return None
        with tracing.span("tpu.peer_absorb", space=space_id,
                          peer=str(self.host)) as sp:
            try:
                resp = self.cm.call(self.host, "deviceScanDelta", {
                    "space_id": space_id, "cursor": ver_c,
                    "upto": upto, "epoch": epoch_c,
                    "led_gen": led_gen_c}, timeout=self.RPC_TIMEOUT_S)
            except RpcError as e:
                reason = (protocol.PEER_UNSUPPORTED
                          if e.status.code == ErrorCode.E_UNSUPPORTED
                          else protocol.PEER_UNREACHABLE)
                self._note_stalled(reason)
                stats.add_value("tpu.peer_absorb.stream_errors")
                if sp is not None:
                    sp.tag(ok=False, reason=reason)
                return None
            if not resp.get("ok"):
                reason = str(resp.get("reason")
                             or protocol.PEER_OPAQUE_EVENTS)
                self._note_stalled(reason)
                stats.add_value("tpu.peer_absorb.declines")
                if sp is not None:
                    sp.tag(ok=False, reason=reason)
                return None
            if int(resp.get("version", -1)) != upto:
                # the peer served a different window than requested
                # (its version regressed below the poll — a history
                # break the epoch check should normally catch first):
                # events and cursor would disagree — typed gap, the
                # rebuild re-anchors
                self._note_stalled(protocol.PEER_CURSOR_GAP)
                if sp is not None:
                    sp.tag(ok=False, reason=protocol.PEER_CURSOR_GAP)
                return None
            events = [tuple(e) for e in resp.get("events", [])]
            self._note_advanced()
            stats.add_value("tpu.peer_absorb.windows")
            if sp is not None:
                sp.tag(ok=True, events=len(events))
            return events

    def prefix(self, space_id: int, part_id: int, prefix: bytes):
        """Chunk-streamed remote scan; raises RpcError on peer failure
        (mirror build then fails → the query declines to CPU).

        Torn-scan guard: each chunk echoes the peer's space mutation
        version (sampled before its rows were read); a write landing
        BETWEEN chunks would hand the mirror a torn view of a multi-key
        commit, so a mid-scan version bump fails the scan — the build
        fails, the query declines to the CPU path, and the next query's
        rebuild retries.  Rows stream through chunk-at-a-time (no
        whole-part buffering); a single-chunk scan is single-pass on
        the peer, same window as a local build."""
        cursor = None
        scan_ver = None
        while True:
            resp = self.cm.call(self.host, "deviceScan", {
                "space_id": space_id, "part": part_id,
                "prefix": prefix, "cursor": cursor,
                "limit": 16384}, timeout=self.RPC_TIMEOUT_S)
            if not resp.get("ok"):
                raise RpcError(Status(
                    ErrorCode.E_LEADER_CHANGED,
                    f"deviceScan declined: {resp.get('reason')}"))
            ver = resp.get("version")
            if scan_ver is None:
                scan_ver = ver
            elif ver is not None and ver != scan_ver:
                raise RpcError(Status(
                    ErrorCode.E_RPC_FAILURE,
                    f"deviceScan of part {part_id} raced a write"))
            for k, v in resp["rows"]:
                yield k, v
            if resp.get("done"):
                # a completed full scan is the rebuild re-anchoring the
                # delta cursor at this snapshot: whatever wedged the
                # stream (truncation, leadership move, restart) is
                # reconciled once the build publishes — clear the
                # /healthz peer_mirror stall (re-subscribe is implicit)
                self._note_advanced()
                return
            cursor = resp.get("cursor")


class RemoteDeviceRuntime:
    """Duck-type of TpuQueryRuntime's executor-facing surface
    (can_run_go/run_go/can_run_path/run_find_path) that delegates over
    the StorageService RPC boundary instead of in-process stores."""

    def __init__(self, meta_client, schema_man, client_manager):
        self.meta = meta_client
        self.sm = schema_man
        self.cm = client_manager
        # id(sentence) -> (pushed_mode, (host, parts)) stashed by
        # can_run_go for the immediately following run_go
        self._stash: Dict[int, Tuple] = {}
        # spaces whose storaged declined UPTO (mesh-sharded there, or
        # an older build that can't serve it): remembered so repeat
        # UPTO queries skip the ~RTT-costly decline round trip.
        # Negative-cache entries carry (expiry, device host, meta
        # generation): they lapse after upto_decline_ttl_s, drop
        # immediately when a placement refresh moves the device host,
        # AND drop whenever the meta cache refreshes at all
        # (meta/client.py data_generation) — a storaged restarting
        # WITHOUT mesh sharding re-heartbeats, metad's catalog clock
        # moves, graphd's next load_data bumps the generation, and the
        # space probes UPTO again without waiting out the TTL or
        # restarting graphd
        self._upto_declined: Dict[int, Tuple[float, str, int]] = {}
        # failover-ladder decline cache, the UPTO style made per
        # (space, host): a replica that answered degraded (or was
        # unreachable) is deprioritized until its TTL lapses, so every
        # query in the window rides a healthy replica WITHOUT paying
        # the sick one's round trip first (docs/durability.md
        # "The failover ladder")
        self._dev_declined: Dict[Tuple[int, str], float] = {}

    # ------------------------------------------------------------ placement
    def _dev_decline_active(self, space_id: int, host: str) -> bool:
        exp = self._dev_declined.get((space_id, host))
        if exp is None:
            return False
        if time.monotonic() >= exp:
            self._dev_declined.pop((space_id, host), None)
            return False
        return True

    def _note_dev_declined(self, space_id: int, host: str) -> None:
        ttl = float(flags.get("device_decline_ttl_s") or 15.0)
        self._dev_declined[(space_id, host)] = time.monotonic() + ttl

    def _device_hosts(self, space_id: int
                      ) -> List[Tuple[HostAddr, List[int]]]:
        """The replica failover ladder: every storaged holding parts
        of the space can device-serve it (each composes the peers' led
        parts through RemoteStoreView), ordered by preference —
        healthy before breaker-open, freshest device generation first
        (both from the heartbeat device briefs metad folds into the
        host table), most locally-held parts next (fewest remote-part
        streams for its mirror fold).  Hosts inside an active decline
        window sort LAST, not out: when every replica is sick the
        primary still gets one probe before the CPU loop answers."""
        alloc = self.meta.parts_alloc(space_id)
        if not alloc:
            return []
        counts: Dict[str, int] = {}
        for peers in alloc.values():
            for h in peers:
                counts[h] = counts.get(h, 0) + 1
        if not counts:
            return []
        briefs = {}
        briefs_fn = getattr(self.meta, "device_briefs", None)
        if briefs_fn is not None:
            try:
                briefs = briefs_fn() or {}
            except Exception:   # noqa: BLE001 — briefs are advisory;
                briefs = {}     # placement still works without them
        parts = sorted(alloc.keys())

        def rank(h: str):
            b = (briefs.get(h) or {}).get(str(space_id)) \
                or (briefs.get(h) or {}).get(space_id) or {}
            return (self._dev_decline_active(space_id, h),  # healthy 1st
                    bool(b.get("breaker_open")),    # closed breakers
                    -int(b.get("generation") or 0),  # freshest mirror
                    -counts[h],                     # most local parts
                    h)                              # deterministic tie
        return [(HostAddr.parse(h), parts) for h in
                sorted(counts, key=rank)]

    # ------------------------------------------------- UPTO negative cache
    def _upto_decline_active(self, space_id: int, host) -> bool:
        """True while a remembered UPTO decline still binds: unexpired,
        the device host unchanged, AND the meta cache not refreshed
        since the decline.  TTL lapse, a placement refresh that moved
        the device host, or ANY completed meta refresh drops the
        entry, so the next UPTO query probes again."""
        ent = self._upto_declined.get(space_id)
        if ent is None:
            return False
        expiry, decline_host, gen = ent
        if time.monotonic() >= expiry or decline_host != str(host) \
                or gen != getattr(self.meta, "data_generation", gen):
            self._upto_declined.pop(space_id, None)
            return False
        return True

    def _note_upto_declined(self, space_id: int, host) -> None:
        ttl = float(flags.get("upto_decline_ttl_s", 300))
        self._upto_declined[space_id] = (
            time.monotonic() + ttl, str(host),
            getattr(self.meta, "data_generation", 0))

    # ------------------------------------------------------------ rpc
    def _call(self, host: HostAddr, method: str, req: dict,
              ExecError) -> dict:
        """One deviceGo/deviceFindPath round trip with the shared
        decline/error contract: transport failure or an explicit
        decline → TpuDecline (CPU fallback); a served-side query error
        → ExecError."""
        try:
            resp = self.cm.call(host, method, req)
        except RpcError as e:
            if e.status.code == ErrorCode.E_DEADLINE_EXCEEDED:
                # the budget is gone — falling back to the CPU loop
                # would spend MORE time the query no longer has
                raise DeadlineExceeded(e.status.msg) from e
            # storaged down / partitioned away / old build without the
            # method — retriable: another replica of the same parts
            # may still serve on the device (the failover ladder)
            raise TpuDecline(f"{method} rpc failed: {e.status.msg}",
                             retriable=True)
        if not resp.get("ok"):
            if resp.get("code") == int(ErrorCode.E_DEADLINE_EXCEEDED):
                # storaged-side admission shed / expiry: typed fast
                # failure, never a decline (docs/admission.md).  A
                # marked SHED keeps its class across the wire so graphd
                # counts it as overload, not as a client timeout
                if resp.get("shed"):
                    from ..graph.batch_dispatch import AdmissionShed
                    raise AdmissionShed(
                        resp.get("error", "query shed"),
                        protocol.SHED_REMOTE)
                raise DeadlineExceeded(resp.get("error",
                                                "deadline exceeded"))
            if resp.get("error"):
                raise ExecError(resp["error"])
            # a degraded decline (device runtime failure / open breaker
            # on the storaged) keeps its class across the wire so the
            # executor's CPU fallback surfaces the degradation — and is
            # retriable: a healthy replica of the same parts can serve
            raise TpuDecline(resp.get("reason", "declined"),
                             degraded=bool(resp.get("degraded")),
                             retriable=bool(resp.get("degraded")
                                            or resp.get("retriable")))
        return resp

    def _ladder_call(self, space_id: int, ladder, method: str,
                     req: dict, ExecError) -> dict:
        """One device query down the replica failover ladder
        (docs/durability.md): try each replica in preference order;
        a RETRIABLE decline (transport failure, degraded runtime, open
        breaker) notes the replica in the TTL'd decline cache and
        moves to the next rung; anything else — semantic declines,
        query errors, deadline/shed — propagates immediately (tagged
        with the declining host so callers' negative caches blame the
        right replica).  The FIRST rung is always probed; later rungs
        inside an active decline window are skipped — a fleet-wide
        outage costs one failed RPC per query for the TTL, not one
        per rung.  Only when every live rung declined does the
        (degraded) decline reach the executor's CPU fallback."""
        max_r = max(1, int(flags.get("device_failover_replicas") or 1))
        last: Optional[TpuDecline] = None
        for i, (host, _parts) in enumerate(ladder[:max_r]):
            if i > 0 and self._dev_decline_active(space_id, str(host)):
                stats.add_value("graph.device_failover.decline_skips")
                continue
            if i > 0:
                stats.add_value("graph.device_failover.retries")
            try:
                resp = self._call(host, method, req, ExecError)
            except TpuDecline as d:
                d.host = host
                if not d.retriable:
                    raise
                self._note_dev_declined(space_id, str(host))
                last = d
                continue
            if i > 0:
                # a replica served what the preferred host could not —
                # the ladder paid for itself (the soak's proof counter)
                stats.add_value("graph.device_failover.served")
            return resp, host
        stats.add_value("graph.device_failover.exhausted")
        raise last if last is not None else TpuDecline(
            "space has no device placement")

    # ------------------------------------------------------------ GO
    def can_run_go(self, space_id: int, etypes, sentence, pushed,
                   remnant, src_refs, dst_refs, has_input: bool) -> bool:
        if flags.get("storage_backend") == "cpu":
            return False
        if has_input:      # per-root $-/$var inputs never run on device
            return False
        ladder = self._device_hosts(space_id)
        if not ladder:
            return False
        # UPTO rides the cumulative-frontier kernels; the remote
        # runtime declines if ITS mesh config or build can't serve it
        # (this side can't see the storaged's flags) — cached with a
        # TTL + the declining host, so the decline round trip is paid
        # once per space, not per query, without pinning a restarted
        # or re-placed storaged out of UPTO traffic forever
        if getattr(sentence.step, "upto", False) \
                and sentence.step.steps > 1 \
                and self._upto_decline_active(space_id, ladder[0][0]):
            return False
        self._stash[id(sentence)] = (pushed is not None, ladder)
        return True

    def run_go(self, executor, space_id: int, start_vids: List[int],
               etypes: List[int], steps: int,
               etype_to_alias: Dict[int, str], yield_cols, distinct: bool,
               where_expr, edge_props, vertex_props,
               upto: bool = False, reduce=None) -> InterimResult:
        from ..graph.executors.base import ExecError

        pushed_mode, ladder = self._stash.pop(
            id(executor.sentence), (False, None))
        if ladder is None:
            ladder = self._device_hosts(space_id)
        if not ladder:
            raise TpuDecline("space has no device placement")
        parts = ladder[0][1]
        try:
            yspecs = [[encode_expr(c.expr), c.alias] for c in yield_cols]
            wblob = (encode_expr(where_expr)
                     if where_expr is not None else None)
        except Exception as e:      # noqa: BLE001 — unencodable AST node
            raise TpuDecline(f"unencodable expression: {e}")
        req = {
            "space_id": space_id,
            "parts": parts,
            "start_vids": list(start_vids),
            "etypes": list(etypes),
            "steps": steps,
            "etype_to_alias": {int(k): v for k, v in etype_to_alias.items()},
            "yield": yspecs,
            "distinct": bool(distinct),
            "where": wblob,
            "pushed_mode": pushed_mode,
            "upto": bool(upto),
        }
        if reduce is not None:
            # LIMIT/COUNT pushdown: the storaged's device runtime cuts
            # the result BEFORE the fetch and the response carries only
            # surviving/reduced rows; an older build ignores the field
            # and serves full rows — correct either way (the fused pipe
            # slices/counts full rows identically), so no echo gate is
            # needed for LIMIT.  COUNT changes the result SHAPE, so its
            # application is proven by the "reduce" echo below
            req["reduce"] = list(reduce)
        try:
            resp, host = self._ladder_call(space_id, ladder, "deviceGo",
                                           req, ExecError)
        except TpuDecline as d:
            if upto:
                # mesh-sharded there / older build: don't re-pay this
                # round trip for the space's next UPTO query.  The
                # decline is blamed on the replica that RAISED it
                # (_ladder_call tags it), not on the preferred rung —
                # a healthy primary must not inherit a stale replica's
                # UPTO incapability
                self._note_upto_declined(
                    space_id, getattr(d, "host", ladder[0][0]))
            raise
        if upto and resp.get("upto") is not True:
            # version skew: an older storaged ignores the upto field
            # and serves EXACT depth — silently wrong rows.  The echo
            # proves the server understood the request; absence means
            # decline to the CPU loop (and stop asking)
            self._note_upto_declined(space_id, host)
            raise TpuDecline("storaged build predates UPTO serving")
        from ..graph.interim import rows_from_wire
        out = InterimResult(list(resp["columns"]),
                            rows_from_wire(resp["rows"]))
        if reduce is not None and resp.get("reduce") is True:
            # capability echo (like upto): only a storaged that READ
            # the reduce field may have changed the result shape —
            # without it the rows are full and the pipe reduces them
            # itself
            out.reduced = tuple(reduce)
        elif reduce is not None and reduce[0] == "count":
            # older build served full GO rows for a COUNT pushdown:
            # fold them here so the caller still sees a count result
            out = InterimResult(["__count__"], [[len(out.rows)]])
            out.reduced = tuple(reduce)
        return out

    # ------------------------------------------------------------ FIND PATH
    def can_run_path(self, space_id: int, etypes: List[int]) -> bool:
        if flags.get("storage_backend") == "cpu":
            return False
        # placement existence only — run_find_path builds the (brief-
        # ranked) ladder once; building it here too would double the
        # rank sort + briefs copies on every FIND PATH
        return bool(self.meta.parts_alloc(space_id))

    def run_find_path(self, executor, space_id: int, srcs: List[int],
                      dsts: List[int], etypes: List[int], max_steps: int,
                      shortest: bool, etype_names: Dict[int, str]
                      ) -> InterimResult:
        from ..graph.executors.base import ExecError

        ladder = self._device_hosts(space_id)
        if not ladder:
            raise TpuDecline("space has no device placement")
        req = {
            "space_id": space_id,
            "parts": ladder[0][1],
            "srcs": list(srcs),
            "dsts": list(dsts),
            "etypes": list(etypes),
            "max_steps": max_steps,
            "shortest": bool(shortest),
            "etype_names": {int(k): v for k, v in etype_names.items()},
        }
        resp, _host = self._ladder_call(space_id, ladder,
                                        "deviceFindPath", req, ExecError)
        return InterimResult(list(resp["columns"]),
                             [list(r) for r in resp["rows"]])
