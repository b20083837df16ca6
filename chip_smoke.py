#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served GO / FIND PATH
path still starts, and answers correctly, on the TPU.

Runs the system's main path once through the entry points a user calls:

  phase A  embedded deployment at real size — LocalCluster(tpu_backend)
           with the shipped conf defaults, the repo's seeded power-law
           generator + bulk ingest, then a handful of nGQL statements
           (1..4-hop GO, 32-start GO, WHERE, COUNT / LIMIT
           pushdown, UPTO, FIND SHORTEST PATH, a 64-thread burst, the
           multi-hop set again under go_dispatch_mode=windowed, INSERT
           + read-back), each compared with the same statement under
           storage_backend=cpu — the plain reference.  With >= 4
           devices it continues under tpu_mesh_devices=4.
  phase B  the daemons — metad + storaged + graphd as real subprocesses
           over TCP, storaged the only one that touches jax; a small
           INSERTed graph; GO and FIND PATH through graphd ->
           rpc_deviceGo, compared with a storage_backend=cpu graphd.

A statement that returns ok() proves nothing here: a classified device
failure degrades to the CPU loop BY DESIGN (safety code).  So every
check also reads the response's warnings and completeness, the
runtime's device counters, the circuit breaker and the prewarm failure
count, and phase B learns the platform from storaged's /status, never
from its own jax.

One process per chip: this parent never imports jax.  Phase A is a
child process that owns the chip alone; in phase B storaged owns it.
Every printed line is one JSON object stamped with the device as the
process that held it reported it.  Nothing here is a benchmark result:
times are observations from one run.

Without a TPU the script exits non-zero and prints no result.
``--rehearse-cpu`` is the explicit CPU rehearsal (tests, tiny sizes):
it forces CPU jax and every line says ``"platform": "cpu"``.

Last stdout line on success:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import resource
import shutil
import signal
import statistics
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

# The contract's time limit is 1200 s, compilation included.
BUDGET_S = 1140.0
PHASE_B_RESERVE_S = 240.0
# LDBC SNB SF1 has roughly 17 M edges (figure from memory of the spec,
# not checked against it here — no network): 2^24 edges over 2^20
# vertices is that order, ~0.37 GB of device tables at the declared
# 21.9 B/edge.  Degree shape as tools/scale_bench: Zipf alpha 2.2,
# capped at 20,000, topped up uniformly to the edge count.
REAL = {"vertices": 1 << 20, "edges": 1 << 24, "alpha": 2.2,
        "max_deg": 20_000, "parts": 8}
BURST_THREADS, BURST_PER_THREAD = 64, 4
MESH_DEVICES = 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


_REPORT = None      # the parent's <out>/report.jsonl, once opened


def publish(line: str) -> None:
    """One result line to stdout (and to the parent's report file)."""
    print(line, flush=True)
    if _REPORT is not None:
        _REPORT.write(line + "\n")
        _REPORT.flush()


def emit(rec: dict) -> None:
    publish(json.dumps(rec))


# ====================================================================
# shared helpers (jax-free)
# ====================================================================
def shipped_defaults() -> Dict[str, str]:
    """name -> raw value of every flag the shipped graphd and storaged
    conf files set (etc/*.conf.default)."""
    out: Dict[str, str] = {}
    for daemon in ("graphd", "storaged"):
        path = os.path.join(HERE, "etc", f"nebula-{daemon}.conf.default")
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line and not line.startswith("#") and "=" in line:
                    k, v = line.split("=", 1)
                    out[k] = v
    return out


@contextlib.contextmanager
def flags_set(values: Dict[str, object]):
    """Set already-defined flags for the duration of a phase and put
    the old values back (flags are process-wide; tier-1 drives the
    phases in-process)."""
    from nebula_tpu.common.flags import flags
    old: Dict[str, object] = {}
    try:
        for k, v in values.items():
            if flags.info(k) is None:
                continue        # no reader in this process
            old[k] = flags.get(k)
            flags.set(k, v, force=True)
        yield
    finally:
        for k, v in old.items():
            flags.set(k, v, force=True)


def contract_device(info: Optional[dict]) -> Optional[dict]:
    """The repo's device stamp (tpu/jax_setup.device_info, storaged's
    /status ``device``) in the key names the smoke's contract fixes."""
    if not info:
        return None
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": info["device_count"]}


def rows_of(resp) -> List[tuple]:
    return sorted(map(tuple, resp.rows or []))


def response_problems(resp) -> List[str]:
    """Why a response must not count as device-served even though it
    may be ok(): the degraded-decline ladder answers from the CPU loop
    with completeness < 100 and a warning."""
    out = []
    if not resp.ok():
        out.append(f"error: {resp.error_msg}")
    if resp.warnings:
        out.append(f"warnings: {resp.warnings}")
    if resp.completeness != 100:
        out.append(f"completeness {resp.completeness}")
    return out


def limit_problems(got: List[tuple], full: List[tuple], n: int
                   ) -> List[str]:
    """``| LIMIT n`` is an unordered prefix cut on both paths: the
    repo's own contract (tests/test_packed_frontier.py) is the row
    COUNT plus membership in the full reference result."""
    out = []
    if len(got) != min(n, len(full)):
        out.append(f"LIMIT returned {len(got)} rows, expected "
                   f"{min(n, len(full))}")
    pool = set(full)
    if any(r not in pool for r in got):
        out.append("LIMIT returned a row the reference does not have")
    return out


# ====================================================================
# phase A — embedded deployment (runs in a child that owns the chip)
# ====================================================================
class CompileMeter:
    """XLA compile accounting from jax.monitoring's public events:
    backend compiles (count + wall; on a persistent-cache hit the wall
    is the retrieval) and persistent-cache hits / misses."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring
        self._lock = threading.Lock()
        self._c = {"backend_compiles": 0, "backend_compile_s": 0.0,
                   "persistent_cache_hits": 0,
                   "persistent_cache_misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self._c["backend_compiles"] += 1
                self._c["backend_compile_s"] += float(secs)

    def _event(self, event: str, **_kw) -> None:
        key = {self._HIT: "persistent_cache_hits",
               self._MISS: "persistent_cache_misses"}.get(event)
        if key:
            with self._lock:
                self._c[key] += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._event)

    def snap(self) -> dict:
        with self._lock:
            out = dict(self._c)
        out["backend_compile_s"] = round(out["backend_compile_s"], 3)
        return out

    @staticmethod
    def delta(a: dict, b: dict) -> dict:
        return {k: round(b[k] - a[k], 3) for k in b}


_SERVE_COUNTERS = ("go_device", "path_device", "go_sparse", "go_dense",
                   "go_sparse_split", "go_reduced",
                   "go_mesh_sparse", "bfs_mesh_sparse",
                   "sparse_overflows", "kernel_compiles",
                   "mirror_builds", "mirror_absorbs")


class PhaseA:
    """One loaded embedded cluster and the checks run against it.
    ``failures`` collects every failed check so one run reports all of
    them; ``emit(rec)`` prints an observation line."""

    def __init__(self, cfg: dict, device: dict,
                 emit_fn: Callable[[dict], None]):
        import jax
        self.jax = jax
        self.cfg = cfg
        self.device = device
        self._emit = emit_fn
        self.failures: List[str] = []
        self.meter = CompileMeter()
        self._refs: Dict[str, List[tuple]] = {}
        self._flight_seen = 0
        self._timings: List[dict] = []
        self.cluster = self.client = self.rt = None

    # ---------------------------------------------------------- output
    def emit(self, event: str, **fields) -> None:
        self._emit({"smoke": "phase_a", "event": event, **fields,
                    "device": self.device})

    def fail(self, what: str) -> None:
        log(f"FAIL {what}")
        self.failures.append(what)

    # ---------------------------------------------------------- set-up
    def load(self) -> None:
        """Generate, bulk-ingest, fold, build ELL, upload — each stage
        timed (set-up time, not a result)."""
        import numpy as np
        from nebula_tpu.cluster import LocalCluster
        from nebula_tpu.codec.rows import encode_row
        from nebula_tpu.native import lib
        from nebula_tpu.tools import bulk_load as BL
        from nebula_tpu.tools.scale_bench import powerlaw_graph

        cfg = self.cfg
        n, m = cfg["vertices"], cfg["edges"]
        if lib() is None:
            self.fail("native library not loaded (Python engine)")
        stages: Dict[str, float] = {}
        t0 = time.perf_counter()
        src, dst = powerlaw_graph(n, m, cfg["alpha"], cfg["max_deg"],
                                  cfg["seed"])
        stages["generate_s"] = time.perf_counter() - t0
        self.src, self.dst = src, dst

        self.cluster = c = LocalCluster(num_storage=1, tpu_backend=True)
        self.rt = rt = c.tpu_runtime
        self.client = g = c.client()
        self._must(g.execute(
            f"CREATE SPACE smoke(partition_num={cfg['parts']}, "
            f"replica_factor=1)"))
        c.refresh_all()
        self._must(g.execute("USE smoke"))
        self._must(g.execute("CREATE EDGE knows(w int)"))
        c.refresh_all()
        sid = c.graph_meta_client.get_space_id_by_name("smoke").value()
        et = c.schema_man.to_edge_type(sid, "knows").value()
        schema = c.schema_man.get_edge_schema(sid, et)
        blobs = [encode_row(schema, {"w": int(i)}) for i in range(97)]
        store = c.storage_nodes[0].kv
        nparts = len(store.part_ids(sid))

        t0 = time.perf_counter()
        frames = BL.edge_frames(nparts, et, src, dst, blobs,
                                (np.arange(m) % 97).astype(np.int64))
        st = BL.bulk_load(store, sid,
                          os.path.join(cfg["out"], "staging"), [frames],
                          name="smoke")
        if not st.ok():
            raise RuntimeError(f"bulk load failed: {st}")
        del frames
        stages["load_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        mir = rt.mirror(sid)
        stages["fold_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ix = rt.ell(mir)
        stages["ell_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.jax.block_until_ready(ix.device_arrays())
        stages["upload_s"] = time.perf_counter() - t0

        from nebula_tpu.tpu.runtime import HBM_MODEL
        host_table_bytes = sum(nbr.nbytes + et.nbytes
                               for nbr, et in ix.tables_host())
        self.emit(
            "loaded", seed=cfg["seed"], vertices=n, edges=m,
            mirror_rows=int(mir.m),
            ell_slots=int(2 * sum(a.size for a in ix.bucket_nbr)),
            ell_hub_rows=len(ix.extra_owner),
            stages={k: round(v, 2) for k, v in stages.items()},
            table_bytes_host_shapes=host_table_bytes,
            table_bytes_per_edge_host_shapes=round(
                host_table_bytes / max(m, 1), 2),
            table_bytes_per_edge_declared=HBM_MODEL[
                "table_bytes_per_edge"],
            memory_after_upload=self.memory())

    def _must(self, resp) -> None:
        if not resp.ok():
            raise RuntimeError(f"set-up statement failed: "
                               f"{resp.error_msg}")

    def memory(self) -> list:
        """Per-device memory_stats() (None where the backend reports
        none, as CPU jax does)."""
        out = []
        for d in self.jax.devices():
            s = d.memory_stats() or {}
            out.append({k: int(s[k]) for k in
                        ("bytes_in_use", "peak_bytes_in_use",
                         "bytes_limit") if k in s} or None)
        return out

    # ------------------------------------------------------ statements
    def statements(self) -> Dict[str, str]:
        """The smoke's statement set over seeded start vertices (every
        start has an out-edge; the path target is three real hops from
        its source so the answer is not empty)."""
        import numpy as np
        rng = np.random.default_rng(self.cfg["seed"] + 1)
        src, dst = self.src, self.dst
        picks = [int(v) for v in
                 src[rng.integers(0, len(src), 64)]]
        self.picks = picks

        def step(v: int) -> int:
            outs = dst[src == v]
            return int(outs[rng.integers(0, len(outs))]) \
                if len(outs) else v

        a = picks[40]
        b = step(step(step(a)))
        many = ",".join(map(str, picks[8:40]))
        return {
            "go1": f"GO FROM {picks[0]} OVER knows",
            "go2": f"GO 2 STEPS FROM {picks[1]} OVER knows",
            "go3": f"GO 3 STEPS FROM {picks[2]} OVER knows",
            "go4": f"GO 4 STEPS FROM {picks[3]} OVER knows",
            "go2x32": f"GO 2 STEPS FROM {many} OVER knows",
            "where": f"GO 2 STEPS FROM {picks[4]} OVER knows "
                     f"WHERE knows.w > 48 YIELD knows._dst, knows.w",
            "count": f"GO 3 STEPS FROM {picks[5]} OVER knows "
                     f"| YIELD COUNT(*)",
            "limit": f"GO 3 STEPS FROM {picks[6]} OVER knows | LIMIT 10",
            "upto": f"GO UPTO 3 STEPS FROM {picks[7]} OVER knows",
            "path": f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows "
                    f"UPTO 5 STEPS",
        }

    def reference(self, stmt: str) -> List[tuple]:
        """Row set of ``stmt`` under storage_backend=cpu (the plain
        reference), outside any timing, cached per statement."""
        if stmt not in self._refs:
            with flags_set({"storage_backend": "cpu"}):
                resp = self.client.execute(stmt)
            if not resp.ok():
                raise RuntimeError(f"reference failed for {stmt!r}: "
                                   f"{resp.error_msg}")
            self._refs[stmt] = rows_of(resp)
        return self._refs[stmt]

    def check(self, label: str, stmt: str, kind: str = "go",
              limit: Optional[int] = None) -> None:
        """Run one device-eligible statement twice — first execution
        (may compile), then a warm one — and hold it to the reference
        and to the device accounting."""
        ref_stmt = stmt.rsplit("|", 1)[0].strip() if limit else stmt
        ref = self.reference(ref_stmt)
        counter = "path_device" if kind == "path" else "go_device"
        s0, c0 = dict(self.rt.stats), self.meter.snap()
        d0 = dict(self.rt.dispatcher.stats)
        t0 = time.perf_counter()
        resp = self.client.execute(stmt)
        first_ms = (time.perf_counter() - t0) * 1e3
        c1 = self.meter.snap()
        t0 = time.perf_counter()
        again = self.client.execute(stmt)
        warm_ms = (time.perf_counter() - t0) * 1e3
        for p in response_problems(again):
            self.fail(f"{label} (repeat): {p}")
        s1, d1 = dict(self.rt.stats), dict(self.rt.dispatcher.stats)
        for p in response_problems(resp):
            self.fail(f"{label}: {p}")
        if resp.ok():
            got = rows_of(resp)
            if limit is not None:
                for p in limit_problems(got, ref, limit):
                    self.fail(f"{label}: {p}")
            elif got != ref:
                self.fail(f"{label}: {len(got)} rows differ from the "
                          f"storage_backend=cpu reference "
                          f"({len(ref)} rows)")
        served = s1.get(counter, 0) - s0.get(counter, 0)
        if served < 2:
            self.fail(f"{label}: {counter} moved {served} over two "
                      f"executions — not counted device-served")
        moved = {k: s1.get(k, 0) - s0.get(k, 0) for k in _SERVE_COUNTERS
                 if s1.get(k, 0) != s0.get(k, 0)}
        cq = d1.get("continuous_queries", 0) \
            - d0.get("continuous_queries", 0)
        if cq:
            moved["continuous_queries"] = cq
        self.emit("statement", label=label, statement=stmt[:120],
                  mode=self._mode(), rows=len(resp.rows or []),
                  first_ms=round(first_ms, 2), warm_ms=round(warm_ms, 2),
                  compile_during_first=CompileMeter.delta(c0, c1),
                  counters=moved)

    @staticmethod
    def _mode() -> str:
        from nebula_tpu.common.flags import flags
        k = int(flags.get("tpu_mesh_devices") or 0)
        return (f"mesh{k}" if k > 1
                else str(flags.get("go_dispatch_mode")))

    # ------------------------------------------------- flight recorder
    def flight_since_mark(self) -> List[dict]:
        """Flight-recorder records since the last call (sampled device
        timings are kept aside for the summary)."""
        from nebula_tpu.common.flight import recorder
        recs = [r for r in recorder.dump(limit=1 << 20)
                if r["id"] > self._flight_seen]
        if recs:
            self._flight_seen = max(r["id"] for r in recs)
        self._timings += [r for r in recs if r.get("kind") == "timing"]
        return recs

    @staticmethod
    def tick_summary(recs: List[dict]) -> dict:
        """Continuous-pump ticks: ``dur_us`` is a tick's wall (it ends
        with the blocking extract, so it contains the device's hop);
        ``hop_us`` is only the asynchronous enqueue, ``fetch_wait_us``
        the pump blocked on the device."""
        ticks = [r for r in recs if r.get("kind") == "tick"]
        if not ticks:
            return {"ticks": 0}
        wall = [r["dur_us"] / 1e3 for r in ticks]
        return {"ticks": len(ticks),
                "max_seats": max(r["seats"] for r in ticks),
                "tick_wall_ms_median": round(statistics.median(wall), 3),
                "tick_wall_ms_max": round(max(wall), 3),
                "hop_enqueue_ms_median": round(statistics.median(
                    r["hop_us"] / 1e3 for r in ticks), 3),
                "fetch_wait_ms_median": round(statistics.median(
                    r["fetch_wait_us"] / 1e3 for r in ticks), 3)}

    # ----------------------------------------------------------- steps
    def cache_probe(self, stmt: str) -> None:
        """Cold-versus-warm compile wall for ONE repeated statement:
        run it (cold unless an earlier run filled the persistent
        cache), drop every in-memory executable, run it again — the
        second run can only be fast if the persistent cache answers.
        A cache that never hits shows as warm == cold with zero hits."""
        from nebula_tpu.tpu.jax_setup import CACHE_ENV, \
            compilation_cache_dir
        runs = []
        for which in ("first", "after_clear_caches"):
            if runs:
                self.jax.clear_caches()
            c0 = self.meter.snap()
            t0 = time.perf_counter()
            resp = self.client.execute(stmt)
            wall = time.perf_counter() - t0
            for p in response_problems(resp):
                self.fail(f"cache probe ({which}): {p}")
            runs.append({"run": which, "wall_ms": round(wall * 1e3, 2),
                         **CompileMeter.delta(c0, self.meter.snap())})
        self.emit("compile_cache", statement=stmt[:120], runs=runs,
                  cache_dir=os.environ.get(CACHE_ENV)
                  or compilation_cache_dir(),
                  placed_by="environment" if os.environ.get(CACHE_ENV)
                  else "checkout default")

    def burst(self, pool: List[str]) -> None:
        """64 threads x 4 multi-hop statements at once, so the
        continuous tier seats more than one lane; every response is
        held to its statement's reference."""
        refs = {s: self.reference(s) for s in pool}
        self.flight_since_mark()
        s0 = dict(self.rt.stats)
        problems: List[str] = []
        lat: List[float] = []
        lock = threading.Lock()

        def worker(t: int) -> None:
            try:
                g = self.cluster.client()
                g.execute("USE smoke")
                for j in range(BURST_PER_THREAD):
                    stmt = pool[(t * BURST_PER_THREAD + j) % len(pool)]
                    t0 = time.perf_counter()
                    resp = g.execute(stmt)
                    dt = (time.perf_counter() - t0) * 1e3
                    bad = response_problems(resp)
                    if not bad and rows_of(resp) != refs[stmt]:
                        bad = ["rows differ from the reference"]
                    with lock:
                        lat.append(dt)
                        problems.extend(f"burst {stmt[:60]}: {p}"
                                        for p in bad)
            except Exception as e:   # noqa: BLE001 — a dead worker is
                with lock:           # a failed check, not a lost one
                    problems.append(f"burst worker {t}: "
                                    f"{type(e).__name__}: {e}")

        t0 = time.perf_counter()
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(BURST_THREADS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        wall = time.perf_counter() - t0
        if any(th.is_alive() for th in threads):
            problems.append("burst workers still running after 600 s")
        for p in problems[:10]:
            self.fail(p)
        n = BURST_THREADS * BURST_PER_THREAD
        served = self.rt.stats.get("go_device", 0) - s0.get("go_device", 0)
        if served < n:
            self.fail(f"burst: go_device moved {served} of {n}")
        ticks = self.tick_summary(self.flight_since_mark())
        if ticks.get("max_seats", 0) < 2:
            self.fail(f"burst never seated more than one lane: {ticks}")
        lat.sort()
        self.emit("burst", threads=BURST_THREADS, statements=n,
                  wall_s=round(wall, 2),
                  latency_ms_median=round(statistics.median(lat), 2)
                  if lat else None,
                  latency_ms_max=round(lat[-1], 2) if lat else None,
                  continuous=ticks)

    def insert_and_read_back(self) -> None:
        """INSERT EDGE a->b between existing vertices, then GO FROM a
        must return b from the DEVICE path: the acknowledged write is
        absorbed into the resident mirror and read back."""
        a, b = self.picks[41], self.picks[42]
        s0 = dict(self.rt.stats)
        resp = self.client.execute(
            f"INSERT EDGE knows(w) VALUES {a}->{b}@7:(96)")
        if not resp.ok():
            self.fail(f"INSERT EDGE failed: {resp.error_msg}")
            return
        self._refs.clear()              # the data changed
        stmt = f"GO FROM {a} OVER knows YIELD knows._dst, knows._rank"
        t0 = time.perf_counter()
        got = self.client.execute(stmt)
        ms = (time.perf_counter() - t0) * 1e3
        for p in response_problems(got):
            self.fail(f"read-back: {p}")
        s1 = dict(self.rt.stats)
        if got.ok():
            if (b, 7) not in set(rows_of(got)):
                self.fail(f"read-back: inserted edge {a}->{b}@7 missing "
                          f"from the device path's answer")
            if rows_of(got) != self.reference(stmt):
                self.fail("read-back: rows differ from the reference")
        if s1["go_device"] - s0["go_device"] < 1:
            self.fail("read-back: not counted device-served")
        absorbs = s1["mirror_absorbs"] - s0["mirror_absorbs"]
        builds = s1["mirror_builds"] - s0["mirror_builds"]
        if absorbs < 1:
            self.fail(f"read-back: the write was not absorbed into the "
                      f"resident mirror (absorbs {absorbs}, rebuilds "
                      f"{builds})")
        self.emit("insert_read_back", edge=[a, b, 7],
                  read_back_ms=round(ms, 2), mirror_absorbs=absorbs,
                  mirror_rebuilds=builds)

    def mesh_leg(self, S: Dict[str, str]) -> None:
        """tpu_mesh_devices=4 on the same loaded cluster: GO and FIND
        PATH through the normal path, reference-checked, the
        frontier-sharded kernels counted, tables resident on all four
        devices.  UPTO is left out: the mesh kernels carry no union
        accumulator and the runtime declines it to the CPU loop by
        declared carve-out (MESH_CARVEOUTS 'upto-mesh')."""
        k = MESH_DEVICES
        if self.device["count"] < k:
            self.emit("mesh", skipped=f"{self.device['count']} "
                      f"device(s) visible, need {k} — skipped, not "
                      f"passed")
            return
        before = self.memory()
        s0 = dict(self.rt.stats)
        with flags_set({"tpu_mesh_devices": k}):
            for label in ("go2", "go3", "go4", "go2x32", "count"):
                self.check(f"mesh/{label}", S[label])
            self.check("mesh/limit", S["limit"], limit=10)
            self.check("mesh/path", S["path"], kind="path")
            mesh = self.rt._mesh_only()
            devs = list(mesh.devices.flat)
        s1 = dict(self.rt.stats)
        for key in ("go_mesh_sparse", "bfs_mesh_sparse"):
            if s1.get(key, 0) - s0.get(key, 0) < 1:
                self.fail(f"mesh: {key} did not move")
        if len({d.id for d in devs}) != k \
                or {d.platform for d in devs} != {self.device["platform"]}:
            self.fail(f"mesh: not {k} distinct "
                      f"{self.device['platform']} devices: {devs}")
        after = self.memory()
        grown = [(a or {}).get("bytes_in_use", 0)
                 - (b or {}).get("bytes_in_use", 0)
                 for a, b in zip(after[:k], before[:k])]
        # device 0 already held the single-device tables; every OTHER
        # device must have grown by its share (CPU jax reports nothing)
        if None not in after[:k] and min(grown[1:]) <= 0:
            self.fail(f"mesh: tables not resident on every device "
                      f"(bytes_in_use growth per device {grown})")
        self.emit("mesh", devices=[str(d) for d in devs],
                  bytes_in_use_growth=grown, memory=after,
                  go_mesh_sparse=s1.get("go_mesh_sparse", 0)
                  - s0.get("go_mesh_sparse", 0),
                  bfs_mesh_sparse=s1.get("bfs_mesh_sparse", 0)
                  - s0.get("bfs_mesh_sparse", 0))

    def final_checks(self) -> None:
        rt = self.rt
        cells = rt.breaker.cells_snapshot()
        opened = [c for c in cells if c[1] != "closed"]
        if opened:
            self.fail(f"circuit breaker cells not closed: {opened}")
        d = dict(rt.dispatcher.stats)
        if d.get("query_errors"):
            self.fail(f"dispatcher query_errors = {d['query_errors']}")
        if rt.stats.get("prewarm_failed"):
            self.fail(f"{rt.stats['prewarm_failed']} kernel prewarm "
                      f"compile(s) failed (see stderr)")
        self.flight_since_mark()
        self.emit(
            "summary", failures=self.failures,
            runtime_stats={k: (round(v, 3) if isinstance(v, float)
                               else v) for k, v in rt.stats.items()},
            dispatcher_stats=d, breaker_cells=len(cells),
            compile=self.meter.snap(),
            sampled_device_timings=self._timings[:16],
            memory_at_end=self.memory())

    # ------------------------------------------------------------- run
    def run(self) -> None:
        try:
            self.load()
            S = self.statements()
            self.cache_probe(S["go2"])
            self.flight_since_mark()
            self.check("go1", S["go1"])
            for label in ("go2", "go3", "go4", "go2x32", "count",
                          "upto"):
                self.check(label, S[label])
            self.check("limit", S["limit"], limit=10)
            self.check("where", S["where"])
            self.check("path", S["path"], kind="path")
            self.emit("continuous_ticks",
                      **self.tick_summary(self.flight_since_mark()))
            pool = [f"GO {h} STEPS FROM {v} OVER knows"
                    for h in (2, 3, 4) for v in self.picks[44:48]]
            self.burst(pool)
            with flags_set({"go_dispatch_mode": "windowed"}):
                for label in ("go2", "go3", "go4"):
                    self.check(f"windowed/{label}", S[label])
            self.insert_and_read_back()
            self.mesh_leg(S)
            self.final_checks()
        finally:
            # stop the cluster and join its threads BEFORE the
            # interpreter tears down: XLA work in flight at exit aborts
            # the process ("pure virtual method called")
            if self.cluster is not None:
                if self.client is not None:
                    self.client.disconnect()
                self.cluster.stop()
            self.meter.close()


def phase_a(cfg: dict, emit_fn: Callable[[dict], None] = emit) -> dict:
    """Run phase A in THIS process (it will touch jax).  Returns
    {"ok", "failures", "device"}; raises only when set-up itself
    cannot proceed."""
    import nebula_tpu.cluster           # noqa: F401 — define the flags
    import nebula_tpu.graph.backend_router  # noqa: F401  before the
    import nebula_tpu.tpu.runtime       # noqa: F401   conf values land
    # shipped defaults, with ONE pin: the shipped graphd conf turns the
    # device-vs-CPU router on, which may serve any statement from the
    # CPU by design — the smoke pins the device path (the conf's own
    # documented "set false to pin") so device service is checkable
    from nebula_tpu.tpu.jax_setup import device_info
    device = contract_device(device_info())
    if device["platform"] != cfg["expect_platform"]:
        raise RuntimeError(f"jax reports {device}, expected platform "
                           f"{cfg['expect_platform']!r}")
    with flags_set({**shipped_defaults(), "go_backend_router": False}):
        run = PhaseA(cfg, device, emit_fn)
        run.run()
    return {"ok": not run.failures, "failures": run.failures,
            "device": run.device}


# ====================================================================
# phase B — the daemons (this process stays jax-free)
# ====================================================================
PB_VERTICES, PB_EDGES = 400, 3200
_PB_COUNTERS = ("storage.device_go.qps", "storage.device_path.qps",
                "storage.device_decline.qps")


def _pb_counters(storaged) -> Dict[str, float]:
    q = ",".join(f"{c}.sum.3600" for c in _PB_COUNTERS)
    url = f"http://127.0.0.1:{storaged.ws_port}/get_stats?stats={q}"
    with urllib.request.urlopen(url, timeout=5) as resp:
        raw = json.loads(resp.read().decode())
    return {c: float(raw.get(f"{c}.sum.3600") or 0.0)
            for c in _PB_COUNTERS}


def _pb_load(cluster, cl, seed: int) -> Dict[str, str]:
    """Schema + a small INSERTed graph (this phase proves placement,
    not scale); returns the statements to check."""
    import numpy as np

    def until_ok(stmt: str, tries: int = 60) -> None:
        # schema reaches the storaged subprocess on its (shrunk)
        # load_data interval — poll the statement in
        last = None
        for _ in range(tries):
            last = cl.execute(stmt)
            if last.ok():
                return
            time.sleep(0.5)
        raise RuntimeError(f"{stmt[:60]}: {last.error_msg}")

    until_ok("CREATE SPACE smokeb(partition_num=4, replica_factor=1)")
    until_ok("USE smokeb")
    until_ok("CREATE EDGE knows(w int)")
    rng = np.random.default_rng(seed)
    src = rng.integers(1, PB_VERTICES + 1, PB_EDGES)
    dst = rng.integers(1, PB_VERTICES + 1, PB_EDGES)
    for lo in range(0, PB_EDGES, 400):
        vals = ", ".join(f"{int(s)}->{int(d)}:({i % 97})" for i, (s, d)
                         in enumerate(zip(src[lo:lo + 400],
                                          dst[lo:lo + 400]), lo))
        until_ok(f"INSERT EDGE knows(w) VALUES {vals}")
    a = int(src[0])
    hop = {int(s): int(d) for s, d in zip(src, dst)}
    b = hop.get(hop.get(hop.get(a, a), a), a)
    return {
        "go1": f"GO FROM {a} OVER knows",
        "go3": f"GO 3 STEPS FROM {int(src[1])} OVER knows",
        "path": f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows "
                f"UPTO 5 STEPS",
    }


def _pb_check(cluster, cl, cpu, S: Dict[str, str], expect_platform: str,
              emit_fn: Callable[[dict], None]) -> List[str]:
    """Each statement through graphd -> rpc_deviceGo must equal the
    storage_backend=cpu graphd's answer, with no warnings, and must
    move storaged's device counters; storaged itself must say which
    platform served."""
    failures: List[str] = []
    storaged = cluster.daemons["storaged0"]
    device = None
    for label, stmt in S.items():
        if not storaged.alive():
            failures.append(f"{label}: storaged0 is not running "
                            f"(exit {storaged.proc.returncode})")
            return failures
        counter = ("storage.device_path.qps" if label == "path"
                   else "storage.device_go.qps")
        ref = cpu.execute(stmt)
        if not ref.ok():
            failures.append(f"{label}: cpu graphd: {ref.error_msg}")
            continue
        # graphd's deviceGo RPC gives up after 30 s and falls back to
        # its CPU loop without a warning; storaged's FIRST device
        # request (jax start-up + mirror build + compiles) can take
        # longer on a cold chip.  So: retry until storaged's own
        # counter says it served — and report every attempt.
        attempts = []
        for _ in range(4):
            c0 = _pb_counters(storaged)
            t0 = time.perf_counter()
            resp = cl.execute(stmt)
            ms = (time.perf_counter() - t0) * 1e3
            served = _pb_counters(storaged)[counter] - c0[counter]
            attempts.append({"ms": round(ms, 1), "ok": resp.ok(),
                             "storaged_served": served})
            if served >= 1 or not resp.ok():
                break
            # an abandoned first request is still compiling on
            # storaged: wait for it to land before asking again
            deadline = time.monotonic() + 180
            while time.monotonic() < deadline and \
                    _pb_counters(storaged)[counter] <= c0[counter]:
                if not storaged.alive():
                    break
                time.sleep(1.0)
        for p in response_problems(resp):
            failures.append(f"{label}: {p}")
        if resp.ok() and rows_of(resp) != rows_of(ref):
            failures.append(f"{label}: rows differ from the "
                            f"storage_backend=cpu graphd's")
        if attempts[-1]["storaged_served"] < 1:
            failures.append(f"{label}: storaged's {counter} never "
                            f"moved ({attempts})")
        device = storaged.status().get("device")
        emit_fn({"smoke": "phase_b", "event": "statement",
                 "label": label, "statement": stmt[:120],
                 "rows": len(resp.rows or []), "attempts": attempts,
                 "device": contract_device(device)})
    declines = _pb_counters(storaged)["storage.device_decline.qps"]
    if declines:
        failures.append(f"storaged declined {declines} device "
                        f"request(s)")
    if not device or device.get("platform") != expect_platform:
        failures.append(f"storaged /status reports device {device}, "
                        f"expected platform {expect_platform!r}")
    return failures


def phase_b(cfg: dict, emit_fn: Callable[[dict], None] = emit) -> dict:
    """metad + storaged + graphd as subprocesses; storaged alone gets
    the device environment.  Returns {"ok", "failures", "device"}."""
    from nebula_tpu.tools.proc_cluster import ProcCluster
    run_dir = os.path.join(cfg["out"], "phase_b")
    shutil.rmtree(run_dir, ignore_errors=True)
    failures: List[str] = []
    device = None
    cluster = ProcCluster(
        run_dir, num_storage=1, storage_backend="tpu", start=False,
        device_env={"JAX_PLATFORMS": cfg["expect_platform"]})
    cl = cpu = None
    try:
        cluster.start()
        cpu_addr = cluster.add_graphd("graphd-cpu",
                                      {"storage_backend": "cpu"})
        cl, cpu = cluster.client(), cluster.client(addr=cpu_addr)
        S = _pb_load(cluster, cl, cfg["seed"])
        if not cpu.execute("USE smokeb").ok():
            raise RuntimeError("cpu graphd cannot USE smokeb")
        failures = _pb_check(cluster, cl, cpu, S,
                             cfg["expect_platform"], emit_fn)
        storaged = cluster.daemons["storaged0"]
        if storaged.alive():
            device = contract_device(storaged.status().get("device"))
    except Exception as e:      # noqa: BLE001 — a daemon that died or a
        # boot that never went green is this phase FAILING, reported
        # like any other check
        failures.append(f"phase B aborted: {type(e).__name__}: {e}")
    finally:
        for c in (cl, cpu):
            if c is not None:
                with contextlib.suppress(Exception):
                    c.disconnect()
        cluster.stop()
        for d in cluster.daemons.values():
            if d.alive():               # stop() already SIGTERMed and
                d.kill(signal.SIGKILL)  # waited: leave nothing behind
    emit_fn({"smoke": "phase_b", "event": "result",
             "ok": not failures, "failures": failures,
             "device": device})
    return {"ok": not failures, "failures": failures, "device": device}


# ====================================================================
# parent
# ====================================================================
def run_phase_a_child(cfg: dict, timeout_s: float) -> dict:
    """Phase A in a child process that owns the chip alone.  The phase
    fails if the child reports a failed check, exits non-zero, is
    killed at its time limit, or aborts at interpreter teardown."""
    argv = [sys.executable, os.path.abspath(__file__), "--child-phase-a",
            json.dumps(cfg)]
    env = dict(os.environ)
    if cfg["expect_platform"] == "cpu":
        env["JAX_PLATFORMS"] = "cpu"     # the explicit rehearsal
    proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                            env=env, cwd=HERE)
    result: dict = {}
    timer = threading.Timer(timeout_s, proc.kill)
    timer.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            try:
                rec = json.loads(line)
            except ValueError:
                log(f"phase A child (not JSON): {line}")
                continue
            if rec.get("event") == "result":
                result = rec
            else:
                publish(line)
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    failures = list(result.get("failures") or [])
    if rc != 0:
        failures.append(
            f"phase A child exited {rc}"
            + (" (killed by signal — time limit or an abort at "
               "interpreter teardown)" if rc < 0 else ""))
    elif not result:
        failures.append("phase A child printed no result")
    return {"ok": not failures, "failures": failures,
            "device": result.get("device")}


def _child_main(cfg_json: str) -> int:
    cfg = json.loads(cfg_json)
    try:
        out = phase_a(cfg)
    except Exception as e:      # noqa: BLE001 — report, then fail
        import traceback
        traceback.print_exc()
        out = {"ok": False, "device": None,
               "failures": [f"phase A aborted: {type(e).__name__}: {e}"]}
    emit({"smoke": "phase_a", "event": "result", **out})
    return 0 if out["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--vertices", type=int, default=REAL["vertices"])
    ap.add_argument("--edges", type=int, default=REAL["edges"])
    ap.add_argument("--out", default=os.path.join(HERE, "smoke_out"),
                    help="data, staging and daemon logs land here")
    ap.add_argument("--phases", default="a,b",
                    help="comma list of phases to run (default a,b; "
                         "the contract's run is both)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="explicit CPU rehearsal: force CPU jax, label "
                         "every line platform=cpu (tests; never a "
                         "device result)")
    ap.add_argument("--child-phase-a", metavar="CFG_JSON", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child_phase_a is not None:
        return _child_main(args.child_phase_a)

    t_start = time.monotonic()
    # a polite kill must still run the finally blocks that stop the
    # phase-A child and the phase-B daemons
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    from nebula_tpu.native import ensure_built
    if not ensure_built():
        log("native library build failed (compiler output above)")
        return 1
    global _REPORT
    out_dir = os.path.abspath(args.out)
    shutil.rmtree(os.path.join(out_dir, "staging"), ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    _REPORT = open(os.path.join(out_dir, "report.jsonl"), "w")
    # what the machine lets this run write (a 1 GiB file-size limit cut
    # a one-file staging of the load short once): tools/bulk_load
    # stages at most 256 MiB at a time
    log(f"out {out_dir}: {shutil.disk_usage(out_dir).free >> 20} MiB "
        f"free, RLIMIT_FSIZE "
        f"{resource.getrlimit(resource.RLIMIT_FSIZE)[0]}")
    cfg = {"seed": args.seed, "vertices": args.vertices,
           "edges": args.edges, "alpha": REAL["alpha"],
           "max_deg": REAL["max_deg"], "parts": REAL["parts"],
           "out": out_dir,
           "expect_platform": "cpu" if args.rehearse_cpu else "tpu"}
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    results = {}
    if "a" in phases:
        results["a"] = run_phase_a_child(
            cfg, BUDGET_S - PHASE_B_RESERVE_S * ("b" in phases))
        log(f"phase A: {results['a']}")
    if "b" in phases and all(r["ok"] for r in results.values()):
        results["b"] = phase_b(cfg)
        log(f"phase B: {results['b']}")
    log(f"wall {time.monotonic() - t_start:.0f} s")
    failures = [f for r in results.values() for f in r["failures"]]
    devices = [r["device"] for r in results.values() if r["device"]]
    if len(results) != len(phases):
        failures.append("a phase did not run (an earlier one failed)")
    if not devices or any(d["platform"] != cfg["expect_platform"]
                          for d in devices):
        failures.append(f"device reports {devices} do not all say "
                        f"{cfg['expect_platform']!r}")
    if failures:
        for f in failures:
            log(f"FAILED: {f}")
        return 1
    # the device as the process that held the chip in phase A reported
    # it (phase B's storaged where only phase B ran)
    publish(json.dumps({"ok": True, "device": devices[0]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
