"""StorageService — the storaged RPC handler.

Capability parity with /root/reference/src/storage/StorageServiceHandler.cpp
(one processor per request) plus the leader-redirect contract: every
part-addressed request checks local ownership and leadership first and
returns E_LEADER_CHANGED with a leader hint (storage.thrift:57-62) so
clients can chase leaders.

The ``backend`` seam: when a TpuStorageBackend is attached (tpu/backend.py)
and the space has a device CSR mirror, getBound/stats are answered from
HBM-resident device arrays instead of KV prefix scans — same wire contract,
same results (BASELINE.json north star).
"""
from __future__ import annotations

import concurrent.futures
import threading
from typing import Dict, List, Optional

from ..common import flight, protocol
from ..common.clock import Duration
from ..common.deadline import DeadlineExceeded
from ..common.flags import flags
from ..common.ordered_lock import OrderedLock
from ..common.stats import PROC_TOKEN, stats
from ..common.status import ErrorCode, Status
from ..interface.rpc import RpcError
from ..kvstore.store import NebulaStore
from ..meta.schema_manager import SchemaManager
from .processors import (AddEdgesProcessor, AddVerticesProcessor,
                         DeleteProcessor, QueryBoundProcessor,
                         QueryEdgePropsProcessor, QueryStatsProcessor,
                         QueryVertexPropsProcessor)


def _prefix_stop(prefix: bytes) -> Optional[bytes]:
    """Smallest key > every key with this prefix (None = unbounded)."""
    p = bytearray(prefix)
    while p and p[-1] == 0xFF:
        p.pop()
    if not p:
        return None
    p[-1] += 1
    return bytes(p)


class StorageService:
    def __init__(self, kv: NebulaStore, schema_man: SchemaManager,
                 local_host: Optional[str] = None,
                 num_workers: int = 4, meta_client=None,
                 client_manager=None):
        self.kv = kv
        self.schema_man = schema_man
        self.local_host = local_host
        # meta client + RPC client manager enable MULTI-HOST device
        # serving: this storaged folds peer-led parts into its CSR
        # mirror through RemoteStoreView scans (storage/device.py)
        self.meta_client = meta_client
        self.client_manager = client_manager
        self.pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=num_workers, thread_name_prefix="storage-worker")
        self.backend = None  # TpuStorageBackend when attached
        self._device_rt = None      # lazy TpuQueryRuntime (device serving)
        self._backend_rt = None     # local-only runtime for the backend
        self._backend_broken = False
        self._device_rt_lock = OrderedLock("storage.device_rt")
        self._remote_views: Dict = {}   # (space_id, host_str) -> view
        self._device_fail_log: Dict = {}  # (method, exc type) -> last log
        # per-space led-part-set generation: peers fuse it into their
        # delta cursors (storage/device.py) so a leadership change
        # between two delta windows surfaces as a TYPED decline
        # (peer-leader-changed) instead of silently-wrong events
        self._led_gens: Dict[int, tuple] = {}  # space -> (led tuple, gen)
        stats.register_histogram("storage.get_bound.latency_us")
        stats.register_histogram("storage.add.latency_us")
        stats.register_stats("storage.qps")
        stats.register_stats("storage.device_go.qps")
        stats.register_stats("storage.device_path.qps")
        stats.register_stats("storage.device_decline.qps")
        stats.register_stats("storage.backend_bound.qps")
        stats.register_stats("storage.backend_stats.qps")
        # raft replication gauges for every part this node hosts —
        # refreshed only when /metrics or SHOW STATS scrapes (the
        # collector is a weak bound method: dropped with the service)
        stats.register_collector(self._collect_metrics)

    def _collect_metrics(self) -> None:
        from ..kvstore.store import collect_raft_gauges
        collect_raft_gauges(self.kv, self.local_host or "local")

    # ---- ownership / leadership gate --------------------------------
    def _check_parts(self, space_id: int, part_ids) -> None:
        """Whole-request leadership check (single-part RPCs)."""
        for part_id in part_ids:
            part = self.kv.part(space_id, int(part_id))
            if part is None:
                raise RpcError(Status(ErrorCode.E_PART_NOT_FOUND,
                                      f"part {part_id} not on this host"))
            if not part.is_leader():
                leader = part.leader()
                raise RpcError(Status(
                    ErrorCode.E_LEADER_CHANGED,
                    str(leader) if leader else ""))

    def _split_req(self, req: dict):
        """Per-part leadership routing for bulk RPCs (the reference
        returns a per-part ResultCode with a leader hint rather than
        failing the whole request — storage.thrift:57-62): parts this
        host leads stay in the request; the rest come back as
        ``failed {part: {"code", "leader"}}``.  Failing the whole bulk
        request on the first bad part would make the client poison its
        leader cache for the GOOD parts with that one hint and
        ping-pong between hosts."""
        space = req["space_id"]
        led, failed = {}, {}
        for part_id, items in req["parts"].items():
            part = self.kv.part(space, int(part_id))
            if part is None:
                failed[str(part_id)] = {
                    "code": int(ErrorCode.E_PART_NOT_FOUND), "leader": ""}
            elif not part.is_leader():
                leader = part.leader()
                failed[str(part_id)] = {
                    "code": int(ErrorCode.E_LEADER_CHANGED),
                    "leader": str(leader) if leader else ""}
            else:
                led[part_id] = items
        if failed:
            req = dict(req)
            req["parts"] = led
        return req, failed

    def _bulk(self, req: dict, process):
        """Split -> process led parts -> attach per-part failures.
        Skips the processor entirely when this host leads none of the
        addressed parts (common right after an election or a balancer
        move)."""
        req, failed = self._split_req(req)
        if failed and not req["parts"]:
            return {"failed_parts": failed, "latency_us": 0}
        resp = process(req)
        if failed:
            resp["failed_parts"] = failed
        return resp

    # ---- reads ------------------------------------------------------
    def rpc_getBound(self, req: dict) -> dict:
        stats.add_value("storage.qps")

        def run(r):
            proc = QueryBoundProcessor(self.kv, self.schema_man,
                                       self.pool)
            if r.get("flat") and not r.get("filter") \
                    and not r.get("vertex_props") \
                    and proc.flat_coverable(int(r["space_id"]),
                                            r.get("edge_types") or []):
                # columnar final hop beats both the per-vertex backend
                # response and the per-vertex processor.  The cheap
                # coverage probe keeps non-coverable shapes (TTL'd
                # schemas, missing native lib) on the backend path
                # below instead of regressing them to per-vertex CPU
                return proc.process(r)
            b = self._ensure_backend()
            if b is not None and b.serves(int(r["space_id"])):
                from ..tpu.backend import BackendDecline
                try:
                    resp = (b.get_bound_dst_only(r)
                            if r.get("dst_only") else b.get_bound(r))
                    stats.add_value("storage.backend_bound.qps")
                    return resp
                except BackendDecline:
                    pass          # mirror can't reproduce — CPU answers
            return proc.process(r)

        resp = self._bulk(req, run)
        stats.add_value("storage.get_bound.latency_us",
                        resp.get("latency_us", 0))
        return resp

    def _ensure_backend(self):
        """Lazily attach the mirror-backed bulk-read backend
        (tpu/backend.py).  Stays None on CPU-only deployments or when
        jax is unavailable — the processors answer everything then."""
        if self.backend is None and not self._backend_broken:
            if flags.get("storage_backend") == "cpu":
                return None
            try:
                import types
                from ..tpu.backend import TpuStorageBackend
                from ..tpu.runtime import TpuQueryRuntime
                # LOCAL-ONLY runtime: getBound/boundStats requests are
                # already split to locally-led parts (_split_req), so
                # the backend's mirror never needs peer parts — using
                # the remote-aware deviceGo runtime here would make
                # every storaged mirror the whole space and pay peer
                # version polls on the bulk-read hot path.
                # Construction is locked end to end: an unlocked
                # check-then-set let two concurrent first RPCs build
                # two backends (split stats, duplicate mirror builds)
                with self._device_rt_lock:
                    if self._backend_rt is None:
                        # role="backend" keeps its gauge series apart
                        # from the deviceGo runtime's (one cleared-per-
                        # scrape table, two collectors — unlabeled they
                        # shadow each other and the absorb/build
                        # counters read zero)
                        self._backend_rt = TpuQueryRuntime(
                            [types.SimpleNamespace(kv=self.kv)],
                            self.schema_man, role="backend")
                    if self.backend is None:
                        self.backend = TpuStorageBackend(
                            self._backend_rt, self.schema_man)
            except Exception as e:  # noqa: BLE001 — no jax / broken dev
                # loud, once: a silently-disabled backend is otherwise
                # indistinguishable from a CPU-only deployment (same
                # rationale as _log_device_failure)
                import sys
                sys.stderr.write(
                    "[storage] mirror read backend unavailable — bulk "
                    f"reads stay on the CPU processors: "
                    f"{type(e).__name__}: {e}\n")
                with self._device_rt_lock:
                    self._backend_broken = True
        return self.backend

    # reference-IDL spellings (storage.thrift:207-228): direction is a
    # sign on the request's edge types for us, so In/Out collapse onto
    # the same processors
    def rpc_getOutBound(self, req: dict) -> dict:
        return self.rpc_getBound(req)

    def rpc_getInBound(self, req: dict) -> dict:
        neg = dict(req)
        neg["edge_types"] = [-abs(int(t)) for t in req.get("edge_types", [])]
        neg["reverse"] = True        # all-edge-types default negates too
        return self.rpc_getBound(neg)

    def rpc_outBoundStats(self, req: dict) -> dict:
        return self.rpc_boundStats(req)

    def rpc_inBoundStats(self, req: dict) -> dict:
        neg = dict(req)
        neg["edge_types"] = [-abs(int(t)) for t in req.get("edge_types", [])]
        neg["reverse"] = True
        # aggregate targets match signed etypes exactly — flip them too
        neg["stat_props"] = {a: [-abs(int(et)), prop] for a, (et, prop)
                             in req.get("stat_props", {}).items()}
        return self.rpc_boundStats(neg)

    def rpc_getProps(self, req: dict) -> dict:
        stats.add_value("storage.qps")
        return self._bulk(req, QueryVertexPropsProcessor(
            self.kv, self.schema_man, self.pool).process)

    def rpc_getEdgeProps(self, req: dict) -> dict:
        stats.add_value("storage.qps")
        return self._bulk(req, QueryEdgePropsProcessor(
            self.kv, self.schema_man).process)

    def rpc_boundStats(self, req: dict) -> dict:
        stats.add_value("storage.qps")

        def run(r):
            b = self._ensure_backend()
            if b is not None and b.serves(int(r["space_id"])):
                from ..tpu.backend import BackendDecline
                try:
                    resp = b.bound_stats(r)
                    stats.add_value("storage.backend_stats.qps")
                    return resp
                except BackendDecline:
                    pass
            return QueryStatsProcessor(self.kv, self.schema_man).process(r)

        return self._bulk(req, run)

    # ---- device-backed whole-query serving ---------------------------
    # The cross-process TpuStorageServiceHandler seam (SURVEY.md §7 step
    # 5; reference seam StorageServiceHandler.cpp:1-119): graphd ships a
    # whole GO / FIND PATH here (storage/device.py RemoteDeviceRuntime)
    # and the HBM-resident CSR mirror answers it in one dispatch instead
    # of one getBound fan-out per hop.
    def _device_runtime(self):
        with self._device_rt_lock:
            if self._device_rt is None:
                import types
                from ..tpu.runtime import TpuQueryRuntime
                self._device_rt = TpuQueryRuntime(
                    [types.SimpleNamespace(kv=self.kv)], self.schema_man,
                    remote_provider=self._peer_views)
            return self._device_rt

    def _peer_views(self, space_id: int):
        """RemoteStoreViews for every OTHER host holding parts of the
        space (per the meta part allocation) — the runtime composes
        them with the local store so its mirror covers the whole space
        (multi-host device serving, VERDICT round-2 missing #1)."""
        if self.meta_client is None or self.client_manager is None:
            return []
        from ..interface.common import HostAddr
        from .device import RemoteStoreView
        alloc = self.meta_client.parts_alloc(space_id) or {}
        hosts = sorted({h for peers in alloc.values() for h in peers}
                       - {self.local_host})
        # the view cache is shared across query threads (this runs
        # outside the runtime's locks) — mutate it under one lock
        with self._device_rt_lock:
            # evict views whose host left the space's allocation (or
            # whose space was dropped — empty alloc): stale entries
            # otherwise leak forever and keep getting refreshed by
            # _device_gate
            live = {(space_id, h) for h in hosts}
            for key in [k for k in list(self._remote_views)
                        if k[0] == space_id and k not in live]:
                self._remote_views.pop(key, None)
            views = []
            for h in hosts:
                key = (space_id, h)
                v = self._remote_views.get(key)
                if v is None:
                    v = self._remote_views[key] = RemoteStoreView(
                        HostAddr.parse(h), space_id, self.client_manager)
                views.append(v)
        return views

    def _device_gate(self, space_id: int, parts) -> Optional[str]:
        """Reason this host can't device-serve the space, or None.  The
        mirror folds locally-led parts plus peer-led parts streamed
        through RemoteStoreView — serving is correct when every part in
        the client's meta view is led by a REACHABLE host."""
        if flags.get("storage_backend") == "cpu":
            return "storage_backend=cpu"
        covered = set()
        for part_id in self.kv.part_ids(space_id):
            part = self.kv.part(space_id, int(part_id))
            if part is not None and part.is_leader():
                covered.add(int(part_id))
        missing = [int(p) for p in parts if int(p) not in covered]
        if missing:
            for v in self._peer_views(space_id):
                if v.refresh():
                    covered.update(v.part_ids(space_id))
            missing = [int(p) for p in parts if int(p) not in covered]
        if missing:
            return f"parts {missing} not led by reachable hosts"
        return None

    def _log_device_failure(self, method: str, exc: Exception) -> None:
        """Rate-limited stderr log for unexpected device failures (one
        line per distinct failure type per minute — enough signal to
        diagnose a silently-CPU-only cluster without log flood)."""
        import sys
        import time as _time
        key = (method, type(exc).__name__)
        now = _time.time()
        with self._device_rt_lock:
            should_log = now - self._device_fail_log.get(key, 0) >= 60
            if should_log:
                self._device_fail_log[key] = now
        if should_log:
            sys.stderr.write(
                f"[storage] {method} device failure — queries fall back "
                f"to the CPU path: {type(exc).__name__}: {exc}\n")

    def _led_snapshot(self, space_id: int):
        """(led part ids, led-set generation): the generation bumps
        whenever the set of parts this host leads for the space
        changes, and peers fuse it into their delta cursors — a
        leadership move between two delta windows types the next
        absorb decline as peer-leader-changed (docs/durability.md
        "The peer-delta cursor protocol")."""
        led = []
        for pid in self.kv.part_ids(space_id):
            p = self.kv.part(space_id, pid)
            if p is not None and p.is_leader():
                led.append(int(pid))
        key = tuple(sorted(led))
        with self._device_rt_lock:
            cur = self._led_gens.get(space_id)
            if cur is None:
                cur = self._led_gens[space_id] = (key, 1)
            elif cur[0] != key:
                cur = self._led_gens[space_id] = (key, cur[1] + 1)
        return led, cur[1]

    def rpc_deviceVersion(self, req: dict) -> dict:
        """Peer poll for multi-host mirror staleness: this host's
        mutation counter for the space plus the parts it currently
        leads (RemoteStoreView.refresh).  ``epoch`` (per boot) and
        ``led_gen`` (per led-set change) ride along so the peer's
        fused cursor detects restarts and leadership moves between
        delta windows."""
        space_id = int(req["space_id"])
        led, led_gen = self._led_snapshot(space_id)
        return {"version": self.kv.mutation_version(space_id),
                "led_parts": led,
                "epoch": getattr(self.kv, "boot_epoch", 1),
                "led_gen": led_gen}

    def rpc_deviceScanDelta(self, req: dict) -> dict:
        """Peer-delta stream: the typed committed-mutation window
        ``(cursor, upto]`` of this host's delta log, so a peer's
        RemoteStoreView-backed mirror folds this host's writes through
        ell_absorb at O(delta) instead of re-scanning every led part
        at O(m) (ROADMAP item 5; docs/durability.md "The peer-delta
        cursor protocol").  The peer's cursor names (epoch, led_gen,
        version); any mismatch with this host's current identity is a
        TYPED decline the peer turns into a mirror.absorb_failed
        reason and a background rebuild:

          peer-restarted       epoch moved (this process rebooted —
                               its version counter is a new history)
          peer-leader-changed  the led-part set changed (events alone
                               cannot fix part membership)
          peer-cursor-truncated / peer-opaque-events / peer-cursor-gap
                               the store's own window verdicts
        """
        space_id = int(req["space_id"])
        epoch = getattr(self.kv, "boot_epoch", 1)
        if int(req.get("epoch") or 0) != epoch:
            return {"ok": False, "reason": protocol.PEER_RESTARTED}
        _led, led_gen = self._led_snapshot(space_id)
        # peers carry led_gen modulo the fused-cursor ring
        # (storage/device.py _LED_MOD) — compare in that ring
        from .device import _LED_MOD
        if int(req.get("led_gen") or 0) != led_gen % _LED_MOD:
            return {"ok": False,
                    "reason": protocol.PEER_LEADER_CHANGED}
        events, reason, ver = self.kv.delta_window(
            space_id, int(req["cursor"]), upto=req.get("upto"))
        if events is None:
            wire_reason = {"truncated": protocol.PEER_CURSOR_TRUNCATED,
                           "opaque": protocol.PEER_OPAQUE_EVENTS,
                           "ahead": protocol.PEER_CURSOR_GAP}.get(
                               reason, protocol.PEER_OPAQUE_EVENTS)
            return {"ok": False, "reason": wire_reason}
        stats.add_value("tpu.peer_absorb.windows_served")
        # the served window lands on THIS host's device timeline too:
        # peer absorb traffic competes with local dispatches for the
        # link, so "why was this tick slow" needs it (common/flight.py)
        flight.recorder.note_dispatch(
            "peer_delta_serve", space=space_id, events=len(events))
        return {"ok": True, "events": [list(e) for e in events],
                "version": ver}

    def rpc_deviceScan(self, req: dict) -> dict:
        """Chunked raw KV scan of one locally-led part — the transport
        under a peer's mirror fold (RemoteStoreView.prefix).  Leadership
        is re-verified per chunk; a mid-scan leader change fails the
        peer's build, which declines that query to the CPU path."""
        space_id, part_id = int(req["space_id"]), int(req["part"])
        p = self.kv.part(space_id, part_id)
        if p is None or not p.is_leader():
            return {"ok": False, "reason": f"not leader for {part_id}"}
        # version echo sampled BEFORE the rows are read: a write landing
        # after the read but before a post-iteration sample would stamp
        # the pre-write rows with the post-write version and hide the
        # very race the peer's torn-scan guard checks for
        scan_version = self.kv.mutation_version(space_id)
        prefix = req["prefix"]
        cursor = req.get("cursor")
        limit = int(req.get("limit") or 16384)
        rows = []
        if cursor is None:
            it = self.kv.prefix(space_id, part_id, prefix)
        else:
            stop = _prefix_stop(prefix)
            it = self.kv.range(space_id, part_id, cursor + b"\x00",
                               stop if stop is not None else b"\xff" * 64)
        last = cursor
        for k, v in it:
            rows.append((k, v))
            last = k
            if len(rows) >= limit:
                break
        # version echo: the peer fails a scan whose chunks straddle a
        # write (RemoteStoreView.prefix torn-scan guard)
        return {"ok": True, "rows": rows, "cursor": last,
                "done": len(rows) < limit,
                "version": scan_version}

    def rpc_deviceGo(self, req: dict) -> dict:
        from .device import DeviceExecError, TpuDecline
        reason = self._device_gate(req["space_id"], req.get("parts", []))
        if reason is not None:
            # coverage gaps are RETRIABLE: this host can't reach every
            # part, but another replica one RPC away may (asymmetric
            # partitions — the failover ladder's gray-failure case)
            return {"ok": False, "reason": reason, "retriable": True}
        try:
            columns, rows = self._device_runtime().serve_go(
                space_id=int(req["space_id"]),
                start_vids=req["start_vids"],
                etypes=req["etypes"],
                steps=int(req["steps"]),
                etype_to_alias={int(k): v
                                for k, v in req["etype_to_alias"].items()},
                yield_specs=req["yield"],
                distinct=bool(req["distinct"]),
                where_blob=req.get("where"),
                pushed_mode=bool(req["pushed_mode"]),
                upto=bool(req.get("upto", False)),
                reduce=(tuple(req["reduce"])
                        if req.get("reduce") else None))
        except TpuDecline as d:
            stats.add_value("storage.device_decline.qps")
            resp = {"ok": False, "reason": str(d)}
            if getattr(d, "degraded", False):
                # breaker-open / runtime-failure declines keep their
                # class across the wire (storage/device.py _call) so
                # graphd's CPU fallback surfaces the degradation
                resp["degraded"] = True
            return resp
        except DeviceExecError as e:
            return {"ok": False, "error": str(e)}
        except DeadlineExceeded as e:
            # admission shed / budget exhausted: a TYPED fast failure —
            # NOT a decline, or graphd's CPU fallback would re-run the
            # very work the overload protection just rejected.  A true
            # SHED (admission decision, not mere expiry) is marked so
            # graphd's overload signals count it (docs/admission.md)
            from ..graph.batch_dispatch import AdmissionShed
            resp = {"ok": False, "error": str(e),
                    "code": int(ErrorCode.E_DEADLINE_EXCEEDED)}
            if isinstance(e, AdmissionShed):
                resp["shed"] = True
            return resp
        except Exception as e:      # noqa: BLE001 — device-infra failure
            # (jax missing/broken, HBM OOM, unreachable peer, ...):
            # decline so graphd's CPU per-hop loop still answers the
            # query — but loudly, or a permanently broken device path
            # would be invisible
            from .device import classify_device_failure
            self._log_device_failure("deviceGo", e)
            stats.add_value("storage.device_decline.qps")
            resp = {"ok": False,
                    "reason": f"device failure: {type(e).__name__}: {e}"}
            if classify_device_failure(e) is not None:
                resp["degraded"] = True
            if isinstance(e, RpcError):
                # a peer this host can't reach mid-build/poll: another
                # replica with a healthy link may serve the same parts
                resp["retriable"] = True
            return resp
        stats.add_value("storage.device_go.qps")
        resp = {"ok": True, "columns": columns, "rows": rows}
        if req.get("upto"):
            # capability echo: proves this build READ the upto field
            # (an older build would silently serve exact depth; the
            # client treats a missing echo as a decline)
            resp["upto"] = True
        if req.get("reduce"):
            # reduction echo (same contract as upto): the result shape
            # above is already reduced — COUNT rows or a LIMIT-cut
            # subset — and the client must not re-derive from it as if
            # it were the full row set
            resp["reduce"] = True
        # capability echo: this build routes eligible multi-hop GO
        # through the continuous seat-map tier (docs/admission.md).
        # Advisory — result semantics are dispatch-mode-invariant (the
        # windowed path is the bit-exact oracle), but the bench/chaos
        # harnesses use the echo to prove which pipeline served
        resp["continuous"] = flags.get("go_dispatch_mode") == \
            "continuous"
        return resp

    def rpc_deviceFindPath(self, req: dict) -> dict:
        from .device import DeviceExecError, TpuDecline
        reason = self._device_gate(req["space_id"], req.get("parts", []))
        if reason is not None:
            # retriable, as in rpc_deviceGo: another replica may cover
            return {"ok": False, "reason": reason, "retriable": True}
        try:
            columns, rows = self._device_runtime().serve_find_path(
                space_id=int(req["space_id"]),
                srcs=req["srcs"], dsts=req["dsts"],
                etypes=req["etypes"], max_steps=int(req["max_steps"]),
                shortest=bool(req["shortest"]),
                etype_names={int(k): v
                             for k, v in req["etype_names"].items()})
        except TpuDecline as d:
            stats.add_value("storage.device_decline.qps")
            resp = {"ok": False, "reason": str(d)}
            if getattr(d, "degraded", False):
                resp["degraded"] = True
            return resp
        except DeviceExecError as e:
            return {"ok": False, "error": str(e)}
        except DeadlineExceeded as e:
            # typed fast failure (see rpc_deviceGo): never a decline
            from ..graph.batch_dispatch import AdmissionShed
            resp = {"ok": False, "error": str(e),
                    "code": int(ErrorCode.E_DEADLINE_EXCEEDED)}
            if isinstance(e, AdmissionShed):
                resp["shed"] = True
            return resp
        except Exception as e:      # noqa: BLE001 — device-infra failure
            from .device import classify_device_failure
            self._log_device_failure("deviceFindPath", e)
            stats.add_value("storage.device_decline.qps")
            resp = {"ok": False,
                    "reason": f"device failure: {type(e).__name__}: {e}"}
            if classify_device_failure(e) is not None:
                resp["degraded"] = True
            if isinstance(e, RpcError):
                resp["retriable"] = True
            return resp
        stats.add_value("storage.device_path.qps")
        return {"ok": True, "columns": columns, "rows": rows}

    # ---- writes -----------------------------------------------------
    def rpc_addVertices(self, req: dict) -> dict:
        stats.add_value("storage.qps")
        dur = Duration()
        resp = self._bulk(req, AddVerticesProcessor(
            self.kv, self.schema_man).process)
        stats.add_value("storage.add.latency_us", dur.elapsed_in_usec())
        return resp

    def rpc_addEdges(self, req: dict) -> dict:
        stats.add_value("storage.qps")
        dur = Duration()
        resp = self._bulk(req, AddEdgesProcessor(
            self.kv, self.schema_man).process)
        stats.add_value("storage.add.latency_us", dur.elapsed_in_usec())
        return resp

    def rpc_deleteVertex(self, req: dict) -> dict:
        self._check_parts(req["space_id"], [req["part"]])
        return DeleteProcessor(self.kv, self.schema_man).delete_vertex(req)

    def rpc_deleteEdges(self, req: dict) -> dict:
        return self._bulk(req, DeleteProcessor(
            self.kv, self.schema_man).delete_edges)

    # ---- admin (raft membership — driven by meta's balancer) --------
    def _raft(self, req: dict):
        part = self.kv.part(int(req["space_id"]), int(req["part_id"]))
        if part is None:
            raise RpcError(Status(ErrorCode.E_PART_NOT_FOUND, ""))
        return part

    def rpc_transLeader(self, req: dict) -> dict:
        part = self._raft(req)
        if part.raft is not None:
            # Deliberately fire-and-forget (the reference's (void) cast
            # case): the OP_TRANS_LEADER batch is often aborted by the
            # very election it triggers — the target's higher-term vote
            # deposes the sender mid-append — so a non-OK append status
            # does NOT mean the transfer failed. Callers poll the
            # leadership instead (balancer catch-up loop).
            # nebulint: disable=status-discard
            part.raft.transfer_leadership(req["new_leader"])
        return {}

    def rpc_addPart(self, req: dict) -> dict:
        self.kv.add_part(int(req["space_id"]), int(req["part_id"]),
                         req.get("peers"),
                         as_learner=bool(req.get("as_learner")))
        return {}

    def rpc_raftPartStatus(self, req: dict) -> dict:
        """Raft role/term per hosted part (AdminClient leader discovery +
        webservice /status)."""
        out = []
        for sid in list(self.kv.spaces):
            for pid in self.kv.part_ids(sid):
                part = self.kv.part(sid, pid)
                if part is None:
                    continue
                if part.raft is not None:
                    out.append(part.raft.status())
                else:
                    out.append({"space": sid, "part": pid, "role": "LEADER",
                                "term": 0, "leader": self.local_host,
                                "committed": 0, "last_log_id": 0,
                                "peers": {}})
        return {"parts": out}

    def rpc_daemonStats(self, req: dict) -> dict:
        """One daemon's 60 s stats snapshot for metad's SHOW STATS
        fan-out (the nGQL analogue of scraping /get_stats)."""
        return {"host": self.local_host or "storaged",
                "stats": stats.dump(), "proc": PROC_TOKEN}

    def part_status_brief(self) -> Dict[str, dict]:
        """Per-part replication brief piggybacked on heartbeats
        (meta/client.py hb_parts_provider): metad folds it into the
        host table so SHOW PARTS can show term/commit/log positions
        without scraping every storaged."""
        out: Dict[str, dict] = {}
        for sid in list(self.kv.spaces):
            for pid in self.kv.part_ids(sid):
                part = self.kv.part(sid, pid)
                if part is None or part.raft is None:
                    continue
                st = part.raft.status()
                out[f"{sid}/{pid}"] = {
                    "role": st["role"], "term": st["term"],
                    "committed": st["committed"],
                    "last_log_id": st["last_log_id"]}
        return out

    def device_status_brief(self) -> Dict[str, dict]:
        """Per-space device-serving brief piggybacked on heartbeats
        (meta/client.py hb_device_provider): the serving runtime's
        mirror generation (freshness) and whether any breaker cell for
        the space is OPEN.  metad folds it into the host table and
        graphd's failover ladder reads it back (listDeviceBriefs) to
        prefer the freshest HEALTHY replica (docs/durability.md
        "The failover ladder")."""
        with self._device_rt_lock:
            rt = self._device_rt
        out: Dict[str, dict] = {}
        if rt is not None:
            with rt._lock:
                mirrors = {sid: getattr(m, "generation", 0)
                           for sid, m in rt.mirrors.items()}
            for sid, gen in mirrors.items():
                out[str(sid)] = {"generation": int(gen),
                                 "breaker_open": False}
        for key, state, _reason in self.breaker_snapshot():
            if state != "open":
                continue
            ent = out.setdefault(str(key[0]),
                                 {"generation": 0, "breaker_open": False})
            ent["breaker_open"] = True
        # serving-load extension (docs/observability.md): the same
        # rankable fields the graphd brief carries — a remote-device
        # storaged IS the serving tier for its spaces, and a balancer
        # reading listDeviceBriefs ranks on freshness AND load from
        # one struct.  Extra keys are invisible to the failover
        # ladder's rank() (it reads generation/breaker_open only).
        disp = getattr(rt, "_dispatcher", None) if rt is not None else None
        if disp is not None and out:
            load = disp.load_brief()
            for ent in out.values():
                ent.update(load)
        return out

    def peer_mirror_stalls(self):
        """[(space_id, peer host, stalled seconds, typed reason)] for
        every subscribed peer-delta stream currently wedged — the
        /healthz peer_mirror probe's source (storage/web.py)."""
        with self._device_rt_lock:
            views = list(self._remote_views.items())
        out = []
        for (space_id, host), v in views:
            s = v.stalled_for_s()
            if s > 0.0:
                out.append((space_id, host, s,
                            v.last_delta_decline
                            or protocol.PEER_STALLED))
        return out

    def breaker_snapshot(self):
        """[(key, state, last_reason)] across the attached device
        runtimes — the /healthz device_breaker check and tests read
        breaker state through this one seam (docs/durability.md)."""
        with self._device_rt_lock:
            rts = [rt for rt in (self._device_rt, self._backend_rt)
                   if rt is not None]
        out = []
        for rt in rts:
            b = getattr(rt, "breaker", None)
            if b is not None:
                out.extend(b.cells_snapshot())
        return out

    def device_info(self) -> Optional[dict]:
        """{platform, device_kind, device_count} of the runtime that
        serves deviceGo here (the bulk-read backend's when that is the
        only one built), or None before the first device request —
        what /status publishes so a harness learns WHERE storaged's
        kernels ran from storaged itself."""
        with self._device_rt_lock:
            rt = self._device_rt or self._backend_rt
        return dict(rt.device_info) if rt is not None else None

    def device_ready(self):
        """Healthz probe -> (ok, detail): the device runtime either
        isn't wanted (storage_backend=cpu) or jax imports.  The detail
        names the platform once a runtime exists — ready says nothing
        about WHICH device, /status does."""
        if flags.get("storage_backend") == "cpu":
            return True, "storage_backend=cpu"
        info = self.device_info()
        if info is not None:
            return True, ("device runtime on platform={platform} "
                          "device_kind={device_kind!r} "
                          "devices={device_count}".format(**info))
        try:
            import jax  # noqa: F401 — importable is all boot can prove
        except ImportError as e:
            return False, f"jax unavailable: {e}"
        return True, "jax importable; no device runtime built yet"

    def rpc_addLearner(self, req: dict) -> dict:
        part = self._raft(req)
        if part.raft is not None:
            # replicated COMMAND log so every replica learns the learner
            st = part.raft.add_learner_async(req["learner"])
            if not st.ok():
                raise RpcError(st)
        return {}

    def rpc_waitingForCatchUpData(self, req: dict) -> dict:
        part = self._raft(req)
        caught_up = True
        if part.raft is not None:
            caught_up = part.raft.learner_caught_up(req.get("target"))
        return {"caught_up": caught_up}

    def rpc_memberChange(self, req: dict) -> dict:
        part = self._raft(req)
        if part.raft is not None:
            if req.get("add"):
                st = part.raft.add_peer_async(req["peer"])
            else:
                st = part.raft.remove_peer_async(req["peer"])
            if not st.ok():
                raise RpcError(st)
        return {}

    def rpc_removePart(self, req: dict) -> dict:
        self.kv.remove_part(int(req["space_id"]), int(req["part_id"]))
        return {}

    def shutdown(self) -> None:
        stats.unregister_collector(self._collect_metrics)
        self.pool.shutdown(wait=False)
        with self._device_rt_lock:
            rts = [rt for rt in (self._device_rt, self._backend_rt)
                   if rt is not None]
        for rt in rts:
            # stop background prewarm compiles — a daemon thread inside
            # an XLA compile at process exit crashes the C++ teardown
            rt.shutdown()
