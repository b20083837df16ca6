"""WebService — HTTP ops endpoint embedded in every daemon.

Capability parity with the reference's proxygen webservice
(/root/reference/src/webservice/WebService.h:26-50, GetStatsHandler.h:
17-40, GetFlagsHandler.cpp, SetFlagsHandler.cpp): each daemon runs one
HTTP server exposing

  GET /status                       liveness + daemon role (+ any
                                    register_status_field extras)
  GET /flags[?names=a,b]            runtime gflag read (JSON)
  PUT /flags?name=<n>&value=<v>     runtime gflag write (MUTABLE only)
  GET /get_stats[?stats=expr,...]   StatsManager counters; expr syntax
                                    "counter.{sum|count|avg|rate|pXX}.
                                    {5|60|600|3600}" (StatsManager.h:24-40)
  GET /get_stats?format=text        plain-text k=v dump
  GET /traces[?id=<hex>|slow=1]     nebulatrace ring buffer: recent
                                    trace summaries, one span tree, or
                                    the slow-query log
                                    (docs/observability.md)
  GET /metrics                      Prometheus text exposition of the
                                    whole StatsManager registry
                                    (counters, gauges, histograms)
  GET /healthz                      readiness: 200 when every registered
                                    health check passes, else 503
  GET /events[?limit=N]             event journal, newest first
                                    (common/events.py)
  GET /timeline[?limit=N]           flight-recorder device timeline,
                                    newest first; ?format=trace (plus
                                    optional ?trace=<hex>) exports
                                    Chrome-trace JSON (common/flight.py,
                                    docs/observability.md)

plus ``register_handler(path, fn)`` for daemon-specific paths (storage's
/download /ingest /admin, meta's /*-dispatch — SURVEY.md §2.10) and
``register_health_check(name, fn)`` for daemon-specific readiness
probes (meta reachable, partitions serving, device runtime up).
"""
from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional
from urllib.parse import parse_qs, urlparse

from ..common.flags import flags
from ..common.stats import stats


class WebService:
    def __init__(self, daemon_name: str = "daemon", host: str = "127.0.0.1",
                 port: int = 0):
        self.daemon_name = daemon_name
        # path -> fn(query_dict, body: bytes) -> (code, obj-or-str)
        self._handlers: Dict[str, Callable] = {}
        # name -> fn() -> (ok: bool, detail: str); all must pass for 200
        self._health_checks: Dict[str, Callable] = {}
        # name -> fn() -> JSON-able; extra daemon-specific /status fields
        self._status_fields: Dict[str, Callable] = {}
        self.register_handler("/status", self._status)
        self.register_handler("/flags", self._flags)
        self.register_handler("/faults", self._faults)
        self.register_handler("/get_stats", self._get_stats)
        self.register_handler("/traces", self._traces)
        self.register_handler("/metrics", self._metrics)
        self.register_handler("/healthz", self._healthz)
        self.register_handler("/events", self._events)
        self.register_handler("/queries", self._queries)
        self.register_handler("/timeline", self._timeline)
        outer = self

        class _Req(BaseHTTPRequestHandler):
            def log_message(self, *a):      # quiet
                pass

            def _serve(self, body: bytes):
                url = urlparse(self.path)
                fn = outer._handlers.get(url.path)
                if fn is None:
                    self.send_response(404)
                    self.end_headers()
                    self.wfile.write(b"not found")
                    return
                q = {k: v[-1] for k, v in parse_qs(url.query).items()}
                q["__method__"] = self.command
                try:
                    code, obj = fn(q, body)
                except Exception as e:       # noqa: BLE001
                    code, obj = 500, {"error": f"{type(e).__name__}: {e}"}
                payload = obj if isinstance(obj, (bytes, str)) \
                    else json.dumps(obj, indent=2)
                if isinstance(payload, str):
                    payload = payload.encode()
                self.send_response(code)
                ctype = "application/json" if not isinstance(obj, (bytes, str)) \
                    else "text/plain"
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def do_GET(self):
                self._serve(b"")

            def do_PUT(self):
                ln = int(self.headers.get("Content-Length", 0) or 0)
                self._serve(self.rfile.read(ln) if ln else b"")

            do_POST = do_PUT

        self._server = ThreadingHTTPServer((host, port), _Req)
        self._server.daemon_threads = True
        self.host = host
        self.port = self._server.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------- lifecycle
    def start(self) -> "WebService":
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name=f"ws-{self.port}", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()

    def register_handler(self, path: str, fn: Callable) -> None:
        self._handlers[path] = fn

    def register_health_check(self, name: str, fn: Callable) -> None:
        """``fn() -> (ok, detail)``; /healthz is 200 only when every
        registered check passes.  A check that raises counts as
        failed (its exception becomes the detail)."""
        self._health_checks[name] = fn

    def register_status_field(self, name: str, fn: Callable) -> None:
        """``fn() -> JSON-able`` evaluated per /status request and
        published under ``name`` (storaged: the device runtime's
        platform)."""
        self._status_fields[name] = fn

    # ------------------------------------------------------- built-ins
    def _status(self, q: dict, body: bytes):
        return 200, {"status": "running", "name": self.daemon_name,
                     "git_info_sha": "nebula-tpu",
                     **{k: fn() for k, fn in self._status_fields.items()}}

    def _flags(self, q: dict, body: bytes):
        if q.get("__method__") in ("PUT", "POST"):
            name, value = q.get("name"), q.get("value")
            if name is None and body:
                try:
                    parsed = json.loads(body)
                    (name, value), = parsed.items()
                except Exception:    # noqa: BLE001
                    return 400, {"error": "bad body"}
            if name is None:
                return 400, {"error": "name required"}
            if not flags.set(name, value):
                return 400, {"error": f"flag {name} immutable or unknown"}
            return 200, {name: flags.get(name)}
        names = q.get("names")
        if names:
            return 200, {n: flags.get(n) for n in names.split(",")}
        return 200, flags.dump() if hasattr(flags, "dump") else \
            {n: flags.get(n) for n in flags.names()}

    def _faults(self, q: dict, body: bytes):
        """Runtime fault-injection control (docs/fault_injection.md):
        GET returns {seed, rules:[... with hits/fired]}; PUT with a JSON
        body {"seed": N, "rules": [...]} (or a bare rule list) replaces
        the table atomically — {"rules": []} turns injection off.
        Directional-partition ops APPEND/REMOVE tagged rules without
        disturbing the rest of the table (and journal net.partitioned
        / net.healed inside THIS daemon): {"partition": {"host": H
        [, "method": M]}} cuts this process's outbound link to H;
        {"heal": {"host": H}} (or {"heal": {}}) removes matching cuts
        (tools/proc_cluster.py drives these across subprocesses)."""
        from ..interface.faults import default_injector
        if q.get("__method__") in ("PUT", "POST"):
            try:
                spec = json.loads(body) if body else {"rules": []}
            except json.JSONDecodeError as e:
                return 400, {"error": f"bad JSON body: {e}"}
            if isinstance(spec, list):
                spec = {"rules": spec}
            if not isinstance(spec, dict):
                return 400, {"error": "body must be a rule list or "
                                      "{seed, rules}"}
            try:
                if "partition" in spec:
                    part = dict(spec["partition"] or {})
                    default_injector.partition(
                        str(part.get("host", "*")),
                        method=str(part.get("method", "*")))
                elif "heal" in spec:
                    default_injector.heal(
                        str((spec["heal"] or {}).get("host", "*")))
                else:
                    default_injector.configure(spec.get("rules", []),
                                               seed=spec.get("seed"))
            except (TypeError, ValueError) as e:
                return 400, {"error": str(e)}
        return 200, default_injector.dump()

    def _traces(self, q: dict, body: bytes):
        """nebulatrace ring buffer (docs/observability.md):
        GET /traces             recent trace summaries (newest first)
        GET /traces?id=<hex>    one trace as a nested span tree
        GET /traces?slow=1      the slow-query log
        (common/tracing.py; traces appear when trace_sample_rate > 0 or
        a statement ran under PROFILE)."""
        from ..common.tracing import slow_log, trace_store
        tid = q.get("id")
        if tid:
            try:
                tree = trace_store.tree(int(tid, 16))
            except ValueError:
                return 400, {"error": f"bad trace id {tid!r}"}
            if tree is None:
                return 404, {"error": f"trace {tid} not found "
                                      "(evicted or never sampled)"}
            return 200, tree
        if q.get("slow"):
            return 200, {"slow_queries": slow_log.dump()}
        return 200, {"traces": trace_store.summaries()}

    def _metrics(self, q: dict, body: bytes):
        """Prometheus text exposition (docs/observability.md): the
        whole StatsManager registry — cumulative counters, native
        explicit-bucket histograms, and collector-refreshed gauges
        (raft replication per (space, part), TPU device telemetry)."""
        return 200, stats.prometheus_text()

    def _healthz(self, q: dict, body: bytes):
        """Readiness probe: every check registered via
        register_health_check must pass.  A daemon with no checks is
        trivially ready (bare liveness, like /status)."""
        checks = {}
        healthy = True
        for name, fn in sorted(self._health_checks.items()):
            try:
                ok, detail = fn()
            except Exception as e:         # noqa: BLE001
                ok, detail = False, f"{type(e).__name__}: {e}"
            checks[name] = {"ok": bool(ok), "detail": str(detail)}
            healthy = healthy and bool(ok)
        return (200 if healthy else 503), {"healthy": healthy,
                                           "checks": checks}

    def _events(self, q: dict, body: bytes):
        """Local event journal, newest first (common/events.py).  On
        metad the daemon overrides this path with the cluster-wide
        aggregation (daemons/metad.py)."""
        from ..common.events import journal
        try:
            limit = int(q.get("limit", 100))
        except ValueError:
            return 400, {"error": f"bad limit {q.get('limit')!r}"}
        return 200, {"events": journal.dump(limit=limit)}

    def _timeline(self, q: dict, body: bytes):
        """The device flight recorder, THIS process only
        (common/flight.py; cluster-wide is SHOW TIMELINE's metad
        fan-out).
        GET /timeline[?limit=N]       recorder records, newest first
        GET /timeline?format=trace    Chrome-trace JSON of the last
                                      records (timeline_export_max_ticks
                                      caps the stitch), optionally
                                      joined with one span tree via
                                      ?trace=<hex> — open the payload
                                      in chrome://tracing / Perfetto."""
        from ..common import flight
        from ..common.tracing import trace_store
        raw = q.get("limit")
        try:
            limit = int(raw) if raw is not None else None
        except ValueError:
            return 400, {"error": f"bad limit {raw!r}"}
        if q.get("format") == "trace":
            tree = None
            tid = q.get("trace")
            if tid:
                try:
                    tree = trace_store.tree(int(tid, 16))
                except ValueError:
                    return 400, {"error": f"bad trace id {tid!r}"}
                if tree is None:
                    return 404, {"error": f"trace {tid} not found "
                                          "(evicted or never sampled)"}
            trace = flight.chrome_trace(
                tree=tree, ticks=flight.recorder.export(limit))
            return 200, trace
        return 200, {"ticks": flight.recorder.dump(
            limit=64 if limit is None else limit)}

    def _queries(self, q: dict, body: bytes):
        """The live query registry, THIS process only
        (graph/query_registry.py; cluster-wide is SHOW QUERIES' metad
        fan-out).  Oldest first — the statement most worth killing
        reads first."""
        from ..graph.query_registry import registry
        return 200, {"queries": registry.snapshot()}

    def _get_stats(self, q: dict, body: bytes):
        exprs = q.get("stats")
        if exprs:
            out = {e: stats.read_stats(e) for e in exprs.split(",")}
        else:
            out = stats.dump()
        if q.get("format") == "text":
            lines = []
            for k, v in sorted(out.items()):
                if isinstance(v, dict):
                    for kk, vv in sorted(v.items()):
                        lines.append(f"{k}.{kk}={vv}")
                else:
                    lines.append(f"{k}={v}")
            return 200, "\n".join(lines) + "\n"
        return 200, out
