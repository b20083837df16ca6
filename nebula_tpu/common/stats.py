"""StatsManager — registered counters with sliding time-window histograms,
labeled gauges, explicit-bucket histograms, and Prometheus exposition.

Capability parity with the reference (src/common/stats/StatsManager.h:24-96):
  * register a counter or histogram once, add values from any thread,
  * read back with the string syntax
        "<name>.{sum|count|avg|rate|pNN}.{5|60|600|3600}"
    where the trailing number selects the sliding window in seconds.

On top of that windowed core (kept — /get_stats and the p95/p99
reservoirs are unchanged) the cluster metrics plane adds:

  * cumulative totals per stat (sum/count/min/max since process start),
  * explicit-bucket histograms (``register_histogram`` + ``observe``,
    optionally labeled — e.g. kernel-dispatch latency keyed by the
    go_batch_widths ladder) rendered as native Prometheus histograms,
  * labeled gauges: ``set_gauge(name, v, **labels)`` plus scrape-time
    collectors (``register_collector``) that re-set the gauge table on
    every scrape — series for vanished parts/spaces disappear instead
    of going stale.  Collectors are held via weakrefs for bound
    methods, so a dropped service/runtime unregisters itself,
  * ``prometheus_text()`` — the text exposition `/metrics` serves.

Metric names are a closed set: every literal name used with
``add_value``/``observe``/``set_gauge``/``register_*`` must appear in
``METRIC_NAMES`` below (entries ending in ``.*`` license a dynamic
f-string family such as per-statement-kind latencies).  nebulint's
``metric-registry`` check enforces this package-wide, mirroring the
span-registry contract.

Design: per-stat ring of one-second buckets (3600 of them) holding
(sum, count, min, max) plus a bounded per-bucket sample reservoir for
percentiles — no global locks on the read path, one small lock per stat
on write.  The cumulative histogram shares the stat's lock, so a
histogram ``add`` costs a bisect and a few float ops over the plain
counter path.
"""
from __future__ import annotations

import random
import re
import time
import weakref
from bisect import bisect_left
from typing import Callable, Dict, List, Optional, Tuple

from .ordered_lock import OrderedLock

_WINDOWS = (5, 60, 600, 3600)
_RING = 3600
_MAX_SAMPLES_PER_BUCKET = 256

# The single metric-name registry (lint: metric-registry).  Add here
# FIRST, then use the literal at the call site.  Entries ending in
# ``.*`` license a dynamic family: an f-string whose literal head
# matches the prefix (``f"graph.stmt.{kind}.latency_us"``).
METRIC_NAMES = (
    # graphd
    "graph.qps",
    "graph.latency_us",
    "graph.error.qps",
    "graph.partial_result.qps",
    "graph.slow_query.qps",
    "graph.stmt.*",                  # per-statement-kind latency family
    "graph.router.device.qps",
    "graph.router.cpu.qps",
    # replica failover ladder (storage/device.py RemoteDeviceRuntime,
    # docs/durability.md "The failover ladder"): retries onto another
    # replica, queries a replica actually served after the primary
    # degraded, ladders exhausted to the CPU loop, decline-cache skips
    "graph.device_failover.*",
    # admission control / load shedding (graph/batch_dispatch.py,
    # docs/admission.md): queue depth observations + gauges, shed and
    # deadline-exceeded counters, admission wait histogram, the
    # closed-loop batch-window gauge
    "graph.admission.*",
    # continuous hop-boundary dispatch (graph/batch_dispatch.py
    # ContinuousGoScheduler, docs/admission.md "Continuous dispatch"):
    # join/leave/eviction counters, the per-tick lane-occupancy
    # histogram, live seated/queued gauges (the chaos lane-leak
    # assertion's surface) and the idle-fraction share
    "graph.continuous.*",
    # the window controller's depth/shed signals as a replica-count
    # recommendation for an external autoscaler (docs/admission.md)
    "graph.autoscale.recommended_replicas",
    # live query registry (graph/query_registry.py, SHOW QUERIES /
    # /queries / KILL QUERY — docs/observability.md "The live query
    # plane"): admitted/finished/killed counters + live-size gauge
    "graph.query_registry.*",
    # per-phase critical-path micros folded out of a finished span
    # tree (common/tracing.py critical_path — labeled phase=queue/
    # mirror/hop-kernel/fetch/assemble/other)
    "graph.query.phase_us",
    # SLO burn-rate engine (common/slo.py, docs/observability.md "SLO
    # burn rates"): per-objective burn-rate gauges, breach counters,
    # and the alert state gauge the healthz check reads
    "graph.slo.*",
    # per-replica serving load brief (the same struct the graphd
    # heartbeat ships to metad listDeviceBriefs — queue depth, lane
    # occupancy, busy fraction, 5s shed rate) as scrape-time gauges
    "graph.load.*",
    # rpc / fault injection
    "rpc.fault.injected",
    "rpc.fault_injected.*",          # per-method fault counters
    # meta client/server
    "meta.client.retry_attempts",
    "meta.client.backoff_ms",
    "meta.client.retry_exhausted",
    "meta.client.hint_chases",
    "meta.client.heartbeat_failed",
    "meta.client.deadline_exceeded",
    "meta.heartbeat.latency_us",
    # storage client/server
    "storage.client.retry_attempts",
    "storage.client.backoff_ms",
    "storage.client.retry_exhausted",
    "storage.client.deadline_exceeded",
    "storage.qps",
    "storage.get_bound.latency_us",
    "storage.add.latency_us",
    "storage.device_go.qps",
    "storage.device_path.qps",
    "storage.device_decline.qps",
    "storage.backend_bound.qps",
    "storage.backend_stats.qps",
    # raft replication gauges (set per scrape by collect_raft_gauges)
    "raft.is_leader",
    "raft.term",
    "raft.commit_lag",
    "raft.wal_depth",
    "raft.elections",
    "raft.snapshot_sending",
    "raft.snapshot_receiving",
    # TPU device telemetry (tpu/runtime.py collector)
    "tpu.mirror.hbm_bytes",
    "tpu.mirror.builds",
    # mirror generations + incremental absorption (tpu/runtime.py
    # absorb path, docs/durability.md): per-space generation gauge,
    # delta-budget overflows (each one is a rebuild the write stream
    # forced — the write-while-serve soak asserts zero), and the
    # tpu.absorb.* family (absorb/decline counts + wall-time
    # histogram, docs/roofline.md absorb cost model)
    "tpu.mirror.generation",
    "tpu.mirror.delta_overflow",
    "tpu.absorb.*",
    # streamed peer-delta absorption (storage/device.py RemoteStoreView
    # + rpc_deviceScanDelta, docs/durability.md "The peer-delta cursor
    # protocol"): absorbed windows / typed declines / events folded on
    # the mirror side, windows served on the leading side
    "tpu.peer_absorb.*",
    "tpu.jit_cache.size",
    "tpu.compile.count",
    "tpu.prewarm.hits",
    "tpu.prewarm.misses",
    "tpu.prewarm.failed",            # a prewarm compile the compiler refused
    # where the runtime landed: device count labelled platform /
    # device_kind (what storaged's /status also publishes)
    "tpu.device.count",
    "tpu.dispatch.latency_us",
    # roofline accounting (tpu/runtime.py collector, docs/roofline.md):
    # sampled device-compute latency distinct from link RTT, achieved
    # HBM GB/s under the dense_hop_bytes model, cumulative fetch bytes
    "tpu.device_compute.latency_us",
    "tpu.roofline.achieved_gbps",
    "tpu.fetch.bytes",
    # continuous hops by the branch the hop program took on the device
    # (tpu/ell.py make_continuous_hop_kernel): push out of the live
    # slot rows / pull over every slot of the table
    "tpu.hop.sparse",
    "tpu.hop.dense",
    # device idle share since the previous scrape, both dispatch modes
    # (graph/batch_dispatch.py _DeviceBusyMeter): windowed mode idles
    # between windows, the continuous pipeline's double-buffered hop
    # loop exists to drive this toward zero (docs/admission.md)
    "tpu.device_idle_frac",
    # device circuit breaker (tpu/runtime.py + storage/device.py,
    # docs/durability.md): opened/reclosed transitions, classified
    # runtime failures, fast-path declines while open, half-open
    # probes, and the per-(space, class) state gauge
    "tpu.breaker.*",
    # flight recorder (common/flight.py, docs/observability.md "The
    # device timeline"): ring occupancy plus the live-vs-declared
    # drift family — per-axis (ici/hbm) overshoot-fraction gauges
    # labeled by kernel class / timing kind, zero while every live
    # measurement sits inside its declared model bound
    "tpu.flight.records",
    "tpu.model_drift.*",
    # crash-recovery counters (kvstore/wal.py, cluster.py,
    # docs/durability.md): WAL truncations/dropped bytes on replay,
    # flush failures that dropped an un-persisted tail, nodes that
    # booted over recovered durable state
    "recovery.*",
    # event journal
    "events.recorded",
)

# default explicit bucket ladder for *latency_us histograms (microseconds)
LATENCY_BUCKETS_US = (100.0, 500.0, 1000.0, 5000.0, 10000.0, 50000.0,
                      100000.0, 500000.0, 1000000.0, 5000000.0)


def _percentile_sorted(vals: List[float], q: float) -> float:
    """Linear-interpolated quantile over an already-sorted sample list
    (shared by read_stats pNN and dump's p95/p99 columns)."""
    pos = q * (len(vals) - 1)
    i = int(pos)
    frac = pos - i
    if i + 1 < len(vals):
        return vals[i] * (1 - frac) + vals[i + 1] * frac
    return vals[i]


class _HistCell:
    """Cumulative explicit-bucket histogram cell (one labelset).
    Guarded by the owning _Stat's lock."""

    __slots__ = ("counts", "sum", "count", "min", "max")

    def __init__(self, n_bounds: int):
        self.counts = [0] * n_bounds      # per-bound (non-cumulative)
        self.sum = 0.0
        self.count = 0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def add(self, value: float, bounds: Tuple[float, ...]) -> None:
        i = bisect_left(bounds, value)
        if i < len(self.counts):
            self.counts[i] += 1
        self.sum += value
        self.count += 1
        if self.min is None or value < self.min:
            self.min = value
        if self.max is None or value > self.max:
            self.max = value


class _Stat:
    __slots__ = ("lock", "sums", "counts", "samples", "stamps", "mins",
                 "maxs", "cum_sum", "cum_count", "cum_min", "cum_max",
                 "bounds", "cells")

    def __init__(self, bounds: Optional[Tuple[float, ...]] = None):
        self.lock = OrderedLock("stats.stat")
        self.sums = [0.0] * _RING
        self.counts = [0] * _RING
        self.samples: List[List[float]] = [[] for _ in range(_RING)]
        self.stamps = [0] * _RING  # epoch second each bucket last belonged to
        self.mins = [0.0] * _RING
        self.maxs = [0.0] * _RING
        self.cum_sum = 0.0
        self.cum_count = 0
        self.cum_min: Optional[float] = None
        self.cum_max: Optional[float] = None
        # explicit-bucket histogram state (None for plain counters):
        # cells keyed by the labelset tuple — () is the unlabeled series
        self.bounds = tuple(sorted(bounds)) if bounds else None
        self.cells: Dict[Tuple, _HistCell] = {}

    def add(self, value: float, now: Optional[float] = None,
            labels: Tuple = ()) -> None:
        sec = int(now if now is not None else time.time())
        idx = sec % _RING
        with self.lock:
            if self.stamps[idx] != sec:
                self.stamps[idx] = sec
                self.sums[idx] = 0.0
                self.counts[idx] = 0
                self.samples[idx] = []
                self.mins[idx] = value
                self.maxs[idx] = value
            self.sums[idx] += value
            self.counts[idx] += 1
            if value < self.mins[idx]:
                self.mins[idx] = value
            if value > self.maxs[idx]:
                self.maxs[idx] = value
            bucket = self.samples[idx]
            if len(bucket) < _MAX_SAMPLES_PER_BUCKET:
                bucket.append(value)
            self.cum_sum += value
            self.cum_count += 1
            if self.cum_min is None or value < self.cum_min:
                self.cum_min = value
            if self.cum_max is None or value > self.cum_max:
                self.cum_max = value
            if self.bounds is not None:
                cell = self.cells.get(labels)
                if cell is None:
                    cell = self.cells[labels] = _HistCell(len(self.bounds))
                cell.add(value, self.bounds)

    def window(self, seconds: int, now: Optional[float] = None
               ) -> Tuple[float, int, List[float]]:
        sec = int(now if now is not None else time.time())
        total, count, vals = 0.0, 0, []
        with self.lock:
            for off in range(min(seconds, _RING)):
                idx = (sec - off) % _RING
                if self.stamps[idx] == sec - off:
                    total += self.sums[idx]
                    count += self.counts[idx]
                    vals.extend(self.samples[idx])
        return total, count, vals

    def window_full(self, seconds: int, now: Optional[float] = None
                    ) -> Tuple[float, int, List[float],
                               Optional[float], Optional[float]]:
        """window() plus exact min/max, in ONE locked bucket pass —
        dump() scrapes every stat, so it must not walk the ring (and
        contend the write-path lock) twice.  min/max come from the
        per-bucket columns, so (unlike the sample reservoir) extremes
        past the 256-sample cap are still seen."""
        sec = int(now if now is not None else time.time())
        total, count, vals = 0.0, 0, []
        mn: Optional[float] = None
        mx: Optional[float] = None
        with self.lock:
            for off in range(min(seconds, _RING)):
                idx = (sec - off) % _RING
                if self.stamps[idx] == sec - off:
                    total += self.sums[idx]
                    count += self.counts[idx]
                    vals.extend(self.samples[idx])
                    if self.counts[idx]:
                        if mn is None or self.mins[idx] < mn:
                            mn = self.mins[idx]
                        if mx is None or self.maxs[idx] > mx:
                            mx = self.maxs[idx]
        return total, count, vals, mn, mx


def _san(name: str) -> str:
    """Dotted stat name -> Prometheus metric family name."""
    return "nebula_" + re.sub(r"[^a-zA-Z0-9_]", "_", name)


def _fmt_value(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


_LABEL_ESC = {"\\": "\\\\", '"': '\\"', "\n": "\\n"}


def _fmt_labels(labels: Tuple) -> str:
    if not labels:
        return ""
    parts = []
    for k, v in labels:
        sv = str(v)
        for ch, rep in _LABEL_ESC.items():
            sv = sv.replace(ch, rep)
        parts.append(f'{k}="{sv}"')
    return "{" + ",".join(parts) + "}"


def _label_tuple(labels: Dict) -> Tuple:
    return tuple(sorted((str(k), v) for k, v in labels.items()))


class StatsManager:
    """Process-global registry. Use the module-level singleton ``stats``."""

    def __init__(self):
        self._stats: Dict[str, _Stat] = {}
        self._lock = OrderedLock("stats.manager")
        self._gauges: Dict[Tuple[str, Tuple], float] = {}
        self._collectors: List[Callable] = []   # weak/strong refs
        # serializes whole scrapes: clear -> collectors -> snapshot is
        # not atomic under _lock alone, and two overlapping /metrics
        # fetches (webservice is threaded) would otherwise race one
        # scrape's clear() against the other's collector writes,
        # returning an exposition with series missing
        self._scrape_lock = OrderedLock("stats.scrape")

    def register_stats(self, name: str) -> str:
        with self._lock:
            if name not in self._stats:
                self._stats[name] = _Stat()
        return name

    def register_histogram(self, name: str,
                           buckets: Tuple[float, ...] = LATENCY_BUCKETS_US
                           ) -> str:
        """Declare ``name`` as an explicit-bucket histogram: every
        add_value/observe also lands in cumulative Prometheus buckets.
        Re-registering an existing plain stat upgrades it in place (its
        windowed history is kept; buckets start from now)."""
        with self._lock:
            st = self._stats.get(name)
            if st is None:
                self._stats[name] = _Stat(bounds=buckets)
            elif st.bounds is None:
                st.bounds = tuple(sorted(buckets))
        return name

    def add_value(self, name: str, value: float = 1.0) -> None:
        # lock-free fast path for registered stats; the auto-register
        # slow path mutates the dict and must hold the registry lock
        # (counters are bumped from every daemon/RPC thread)
        # registered-stat fast path: entries are never removed and
        # dict get is atomic  # nebulint: disable=guard-inference
        stat = self._stats.get(name)
        if stat is None:
            with self._lock:
                stat = self._stats.setdefault(name, _Stat())
        stat.add(value)

    def observe(self, name: str, value: float, **labels) -> None:
        """Histogram observation with an optional labelset (e.g.
        ``observe("tpu.dispatch.latency_us", us, width=256)``).  The
        windowed reservoir always aggregates across labels; the
        cumulative buckets are kept per labelset."""
        # registered-stat fast path: entries are never removed and
        # dict get is atomic  # nebulint: disable=guard-inference
        stat = self._stats.get(name)
        if stat is None:
            with self._lock:
                stat = self._stats.setdefault(
                    name, _Stat(bounds=LATENCY_BUCKETS_US))
        stat.add(value, labels=_label_tuple(labels) if labels else ())

    # --------------------------------------------------------- gauges
    def set_gauge(self, name: str, value: float, **labels) -> None:
        key = (name, _label_tuple(labels))
        with self._lock:
            self._gauges[key] = float(value)

    def register_collector(self, fn: Callable[[], None]) -> None:
        """Register a scrape-time callback that ``set_gauge``s the
        current values.  Bound methods are held via WeakMethod so a
        dropped owner (a stopped service, a discarded runtime)
        unregisters itself."""
        try:
            ref = weakref.WeakMethod(fn)
        except TypeError:
            ref = (lambda f=fn: f)
        with self._lock:
            self._collectors.append(ref)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            self._collectors = [r for r in self._collectors
                                if r() is not None and r() != fn]

    def _run_collectors(self) -> None:
        """Clear the gauge table and let every live collector re-set
        it — stale series (removed parts, dropped spaces) vanish."""
        with self._lock:
            self._gauges.clear()
            refs = list(self._collectors)
        dead = []
        for r in refs:
            fn = r()
            if fn is None:
                dead.append(r)
                continue
            try:
                fn()
            except Exception:   # noqa: BLE001 — one sick collector must
                pass            # not take down the whole scrape
        if dead:
            with self._lock:
                self._collectors = [r for r in self._collectors
                                    if r not in dead]

    def gauges(self) -> List[Tuple[str, Tuple, float]]:
        """Scrape-time gauge snapshot: runs collectors, returns
        (name, labels_tuple, value) sorted for stable exposition.
        One scrape at a time (scrape lock)."""
        with self._scrape_lock:
            self._run_collectors()
            with self._lock:
                return sorted((n, lt, v)
                              for (n, lt), v in self._gauges.items())

    # ------------------------------------------------------- reads
    def read_stats(self, expr: str, now: Optional[float] = None) -> Optional[float]:
        """Evaluate "name.method.window" (StatsManager.h:67-96)."""
        parts = expr.rsplit(".", 2)
        if len(parts) != 3:
            return None
        name, method, window_s = parts
        try:
            window = int(window_s)
        except ValueError:
            return None
        # read-only window lookup: entries are never removed and
        # dict get is atomic  # nebulint: disable=guard-inference
        stat = self._stats.get(name)
        if stat is None or window not in _WINDOWS:
            return None
        total, count, vals = stat.window(window, now)
        if method == "sum":
            return total
        if method == "count":
            return float(count)
        if method == "avg":
            return total / count if count else 0.0
        if method == "rate":
            return total / window
        if method.startswith("p") and method[1:].isdigit():
            if not vals:
                return 0.0
            vals.sort()
            return _percentile_sorted(vals,
                                      min(int(method[1:]), 100) / 100.0)
        return None

    def dump(self, now: Optional[float] = None) -> Dict[str, Dict[str, float]]:
        """All stats over the 60 s window — feeds /get_stats (webservice)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            snapshot = dict(self._stats)
        for name, stat in snapshot.items():
            total, count, vals, mn, mx = stat.window_full(60, now)
            vals.sort()
            out[name] = {
                "sum.60": total,
                "count.60": float(count),
                "avg.60": total / count if count else 0.0,
                "rate.60": total / 60.0,
                # exact window extremes from the per-bucket min/max
                # columns (the reservoir caps at 256 samples/bucket and
                # would miss outliers)
                "min.60": mn if mn is not None else 0.0,
                "max.60": mx if mx is not None else 0.0,
                # tail latency from the per-bucket sample reservoirs —
                # the avg alone hid p99 regressions on /get_stats
                "p95.60": _percentile_sorted(vals, 0.95) if vals else 0.0,
                "p99.60": _percentile_sorted(vals, 0.99) if vals else 0.0,
            }
        return out

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._stats)

    # ------------------------------------------------- Prometheus text
    def prometheus_text(self) -> str:
        """Text exposition (format 0.0.4) of the whole registry:
        counters (cumulative sum since start as ``_total``), explicit
        histograms (``_bucket``/``_sum``/``_count`` per labelset) and
        gauges (the collector-refreshed table)."""
        lines: List[str] = []
        with self._lock:
            snapshot = sorted(self._stats.items())
        for name, stat in snapshot:
            fam = _san(name)
            if stat.bounds is None:
                lines.append(f"# TYPE {fam} counter")
                with stat.lock:
                    lines.append(f"{fam}_total {_fmt_value(stat.cum_sum)}")
                continue
            lines.append(f"# TYPE {fam} histogram")
            with stat.lock:
                cells = sorted(stat.cells.items())
                bounds = stat.bounds
                for labels, cell in cells:
                    cum = 0
                    for bound, c in zip(bounds, cell.counts):
                        cum += c
                        lt = _fmt_labels(labels + (("le",
                                                    _fmt_value(bound)),))
                        lines.append(f"{fam}_bucket{lt} {cum}")
                    lt = _fmt_labels(labels + (("le", "+Inf"),))
                    lines.append(f"{fam}_bucket{lt} {cell.count}")
                    ls = _fmt_labels(labels)
                    lines.append(f"{fam}_sum{ls} {_fmt_value(cell.sum)}")
                    lines.append(f"{fam}_count{ls} {cell.count}")
        last_fam = None
        for name, labels, value in self.gauges():
            fam = _san(name)
            if fam != last_fam:
                lines.append(f"# TYPE {fam} gauge")
                last_fam = fam
            lines.append(f"{fam}{_fmt_labels(labels)} {_fmt_value(value)}")
        return "\n".join(lines) + "\n"


stats = StatsManager()

# Process identity for cluster-wide stats aggregation (SHOW STATS):
# daemons sharing one process (LocalCluster) share this registry, so
# their sections carry the same token and the rollup counts them once
# (graph/executors/admin.py _show_stats) instead of double-summing.
# Private Random: independent of seeded test RNGs (same stance as the
# event-id RNG in common/events.py) — two daemons whose GLOBAL RNG
# state matches at import must still mint distinct tokens.
PROC_TOKEN = random.Random().getrandbits(63)
