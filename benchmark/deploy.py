"""The deployment under test: the embedded one-process cluster
(``LocalCluster(num_storage=1, tpu_backend=True)``), loaded from the
generated arrays through the program's own bulk loader, with the
shipped conf defaults plus the configuration file's flags.

The load, the flag handling, the compile accounting and the
device-served proof are copies of what ``chip_smoke.py`` phase A proved
on the v5e (PR 21); nothing here imports ``chip_smoke``.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def shipped_defaults() -> Dict[str, str]:
    """name -> raw value of every flag the shipped graphd and storaged
    conf files set (etc/*.conf.default)."""
    out: Dict[str, str] = {}
    for daemon in ("graphd", "storaged"):
        path = os.path.join(ROOT, "etc", f"nebula-{daemon}.conf.default")
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line and not line.startswith("#") and "=" in line:
                    k, v = line.split("=", 1)
                    out[k] = v
    return out


@contextlib.contextmanager
def flags_set(values: Dict[str, object]):
    """Set already-defined flags for the run and put the old values
    back (flags are process-wide; the selfcheck runs several cells in
    one process)."""
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
        self._c = {"backend_compiles": 0, "backend_compile_seconds": 0.0,
                   "persistent_cache_hits": 0,
                   "persistent_cache_misses": 0}
        self.compiled: List[tuple] = []     # (perf_counter at end, name, s)
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **kw) -> None:
        if event == self._COMPILE:
            with self._lock:
                self._c["backend_compiles"] += 1
                self._c["backend_compile_seconds"] += float(secs)
                self.compiled.append((time.perf_counter(),
                                      str(kw.get("fun_name")), float(secs)))

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
            return dict(self._c)


def response_problems(resp) -> List[str]:
    """Why a response must not count as served even though it may be
    ok(): the degraded-decline ladder answers from the CPU loop with
    completeness < 100 and a warning."""
    out = []
    if not resp.ok():
        out.append(f"error: {resp.error_msg}")
    if resp.warnings:
        out.append(f"warnings: {resp.warnings}")
    if resp.completeness != 100:
        out.append(f"completeness {resp.completeness}")
    return out


class Deployment:
    """One loaded cluster.  ``stages`` holds the set-up stage clocks,
    ``facts`` what was loaded (counts, table shapes)."""

    def __init__(self, config: dict, work_dir: str):
        self.config = config
        self.work_dir = work_dir
        self.stages: Dict[str, float] = {}
        self.facts: Dict[str, object] = {}
        self.cluster = None
        self.rt = None
        self._clients: List[object] = []
        self._lock = threading.Lock()
        self._loaded = False

    # ---------------------------------------------------------- set-up
    def _must(self, client, stmt: str) -> None:
        resp = client.execute(stmt)
        if not resp.ok():
            raise RuntimeError(f"set-up statement failed: {stmt!r}: "
                               f"{resp.error_msg}")

    def start(self) -> None:
        """The cluster up, the space made and the schema in it: the
        empty deployment, before anything is generated or loaded."""
        from nebula_tpu.cluster import LocalCluster
        from nebula_tpu.native import lib

        cfg = self.config
        if lib() is None:
            raise RuntimeError("native library not loaded (Python engine)")
        space = cfg["space"]
        self.cluster = c = LocalCluster(num_storage=1, tpu_backend=True)
        self.rt = c.tpu_runtime
        g = self.client()
        self._must(g, f"CREATE SPACE {space}(partition_num="
                      f"{int(cfg['partition_num'])}, replica_factor="
                      f"{int(cfg['replica_factor'])})")
        c.refresh_all()
        self._must(g, f"USE {space}")
        for stmt in cfg["schema"]:
            self._must(g, stmt)
        c.refresh_all()

    def missing(self, requires: dict) -> List[str]:
        """What the configuration's ``requires`` names and this program
        lacks, on the empty space: a flag that is not in the program's
        registry, a statement its ``EXPLAIN`` refuses (the grammar or
        the planner lacks the shape), a counter that neither the
        runtime's served counters (``counters()``) nor the stats
        registry holds.  A declaration, not a setting: nothing is set
        and nothing runs."""
        from nebula_tpu.common.flags import flags
        from nebula_tpu.common.stats import stats
        out = [f"flag {name!r} is not in the program's registry"
               for name in requires.get("flags", ())
               if flags.info(name) is None]
        statements = requires.get("statements", ())
        if statements:
            g = self.client()
            self._must(g, f"USE {self.config['space']}")
            out += [f"statement {stmt!r} is refused: {resp.error_msg}"
                    for stmt in statements
                    for resp in [g.execute(f"EXPLAIN {stmt}")]
                    if not resp.ok()]
        known = set(self.counters()) | set(stats.names())
        out += [f"counter {name!r} is not registered"
                for name in requires.get("counters", ())
                if name not in known]
        return out

    def load(self, data: dict) -> None:
        """Bulk-ingest the labelled arrays into the started deployment
        (started here where the caller has not), fold the CSR mirror,
        build and upload the ELL tables — each stage timed."""
        import jax
        from nebula_tpu.codec.rows import encode_row
        from nebula_tpu.tools import bulk_load as BL

        if self.cluster is None:
            self.start()
        cfg, c, rt = self.config, self.cluster, self.rt
        space = cfg["space"]
        sid = c.graph_meta_client.get_space_id_by_name(space).value()
        store = c.storage_nodes[0].kv
        nparts = len(store.part_ids(sid))

        t0 = time.perf_counter()
        et = c.schema_man.to_edge_type(sid, cfg["edge"]).value()
        schema = c.schema_man.get_edge_schema(sid, et)
        blobs = [encode_row(schema, row) for row in data["edge_prop_table"]]
        groups = [BL.edge_frames(nparts, et, data["src"], data["dst"],
                                 blobs, data["edge_prop_idx"])]
        self.stages["frames"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        st = BL.bulk_load(store, sid, os.path.join(self.work_dir, "staging"),
                          groups, name=space)
        if not st.ok():
            raise RuntimeError(f"bulk load failed: {st}")
        del groups
        self.stages["ingest"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        mir = rt.mirror(sid)
        self.stages["fold"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ix = rt.ell(mir)
        jax.block_until_ready(ix.device_arrays())
        self.stages["ell"] = time.perf_counter() - t0
        self.facts.update(
            mirror_rows=int(mir.m),
            ell_shapes=[[int(a.shape[0]), int(a.shape[1])]
                        for a in ix.bucket_nbr],
            ell_index_itemsize=int(ix.bucket_nbr[0].dtype.itemsize),
            ell_etype_itemsize=int(ix.bucket_et[0].dtype.itemsize),
            ell_hub_rows=len(ix.extra_owner))
        self._loaded = True

    def client(self):
        """A connected client in the deployment's space (kept, so that
        stop() can disconnect it)."""
        g = self.cluster.client()
        with self._lock:
            self._clients.append(g)
        if self._loaded:
            self._must(g, f"USE {self.config['space']}")
        return g

    # ------------------------------------------------------- counters
    def counters(self) -> Dict[str, float]:
        """The program's served counters and host clocks, flat."""
        out = {f"rt.{k}": v for k, v in self.rt.stats.items()
               if isinstance(v, (int, float))}
        out.update({f"dispatcher.{k}": v
                    for k, v in self.rt.dispatcher.stats.items()
                    if isinstance(v, (int, float))})
        return out

    def health_problems(self) -> List[str]:
        """What would make a run's device service unproven: an open
        breaker cell, dispatcher errors, a failed prewarm compile."""
        out = []
        opened = [c for c in self.rt.breaker.cells_snapshot()
                  if c[1] != "closed"]
        if opened:
            out.append(f"circuit breaker cells not closed: {opened}")
        if self.rt.dispatcher.stats.get("query_errors"):
            out.append(f"dispatcher query_errors = "
                       f"{self.rt.dispatcher.stats['query_errors']}")
        if self.rt.stats.get("prewarm_failed"):
            out.append(f"{self.rt.stats['prewarm_failed']} kernel "
                       f"prewarm compile(s) failed")
        return out

    def quiesce(self, thread_prefixes: List[str], timeout_s: float) -> float:
        """Wait until no background thread with one of the prefixes is
        alive (the runtime's prewarm compiles); returns the wait."""
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < timeout_s:
            if not any(t.name.startswith(tuple(thread_prefixes))
                       for t in threading.enumerate() if t.is_alive()):
                break
            time.sleep(0.05)
        return time.perf_counter() - t0

    def stop(self) -> None:
        """Stop the cluster and join its threads BEFORE the interpreter
        tears down: XLA work in flight at exit aborts the process."""
        for g in self._clients:
            with contextlib.suppress(Exception):
                g.disconnect()
        self._clients = []
        if self.cluster is not None:
            self.cluster.stop()
            self.cluster = None


def label_data(gen: dict, seed: int) -> dict:
    """Structural ids -> vertex labels 1..n permuted from ``seed``;
    self-loops and duplicate (src, dst) pairs dropped (nebula's edge key
    is (src, type, rank, dst) with rank 0: a duplicate would overwrite).
    Returns the arrays the loader and the reference both read."""
    n = int(gen["n_vertices"])
    perm = np.random.default_rng([seed, 0x1abe1]).permutation(n) + 1
    s, d, idx = gen["src"], gen["dst"], gen["edge_prop_idx"]
    keep = s != d
    n_loops = int((~keep).sum())
    s, d, idx = s[keep], d[keep], idx[keep]
    key = s * np.int64(n) + d
    _, first = np.unique(key, return_index=True)
    first.sort()
    n_dup = len(s) - len(first)
    s, d, idx = s[first], d[first], idx[first]
    has_out = np.zeros(n, bool)
    has_out[s] = True
    return {"n_vertices": n, "perm": perm, "src": perm[s], "dst": perm[d],
            "edge_prop_idx": idx, "edge_prop_table": gen["edge_prop_table"],
            "structural_with_out_edge": np.nonzero(has_out)[0],
            "edges": int(len(s)), "self_loops_dropped": n_loops,
            "duplicates_dropped": int(n_dup),
            "generated_edges": int(gen["generated_edges"])}
