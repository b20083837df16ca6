"""bench-suite — run the BASELINE.md measurement configs and print a
markdown table + JSON.

Configs (BASELINE.md "Targets to establish", from BASELINE.json):
  1. 1-hop GO — basketballplayer fixture, cpu vs tpu, p50/p99.
  2. 3-hop GO + edge/vertex filter — basketballplayer.
  3. FIND SHORTEST PATH — LDBC-SNB-flavoured SF1-ish graph (ldbc_gen).
  4. batched interactive 3-hop GO — LDBC-shaped skewed-degree graph at
     100k persons (the round-1 weak spot: only uniform-random was
     recorded), cpu vs tpu served path, QPS + p50/p99.

Everything runs the FULL serving path: nGQL through graphd, executor,
batch dispatcher, device kernels, row materialization.

Run: ``python -m nebula_tpu.tools.bench_suite [--quick]``
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .storage_perf import percentile


def _ok(cl, stmt):
    r = cl.execute(stmt)
    assert r.ok(), f"{stmt}: {r.error_msg}"
    return r


def chip_child_env() -> Dict[str, str]:
    """``ProcCluster(device_env=...)`` for a CLI-run child-served leg:
    storaged inherits the machine's own jax platform (the chip where
    there is one).  A chip belongs to one process, so this refuses to
    hand it to a child once THIS process has imported jax — run the
    child-served legs in their own invocation (or first)."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "a child-served bench leg needs the chip for its storaged, "
            "but this process already imported jax (and may hold the "
            "chip): run --write-serve / --horizontal on their own")
    return {}


def _served_device(c, name: str = "storaged0") -> Optional[dict]:
    """The platform / device_kind / device_count the serving storaged
    CHILD reported on /status — what a child-served row is stamped
    with (never this process's own jax)."""
    return c.daemons[name].status().get("device")


def _timed_queries(c, queries: List[str], threads: int, backend: str,
                   space: str, router: bool = False) -> dict:
    from ..common.flags import flags
    flags.set("storage_backend", backend)
    flags.set("go_backend_router", router)
    # warm mirror + kernels outside the timed region — with a
    # CONCURRENT burst at the target thread count, because the batch
    # widths/sparse-ladder shapes the timed region will hit are a
    # function of concurrency, and a single warm query leaves their
    # first XLA compiles inside the measurement
    w = c.client()
    _ok(w, f"USE {space}")
    warm = queries[:min(len(queries), 2 * threads)]
    widx = [0]
    wlock = threading.Lock()

    def warm_worker():
        g = c.client()
        g.execute(f"USE {space}")
        while True:
            with wlock:
                i = widx[0]
                if i >= len(warm):
                    return
                widx[0] += 1
            g.execute(warm[i])

    wts = [threading.Thread(target=warm_worker) for _ in range(threads)]
    for t in wts:
        t.start()
    for t in wts:
        t.join()
    lat_us: List[float] = []
    errors: List[str] = []
    lock = threading.Lock()
    counter = [0]

    def worker():
        g = c.client()
        g.execute(f"USE {space}")
        while True:
            with lock:
                i = counter[0]
                if i >= len(queries):
                    return
                counter[0] += 1
            t0 = time.perf_counter()
            r = g.execute(queries[i])
            dt = (time.perf_counter() - t0) * 1e6
            with lock:
                if r.ok():
                    lat_us.append(dt)
                else:
                    errors.append(r.error_msg)

    start = time.perf_counter()
    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - start
    assert not errors, errors[:3]
    return {
        "backend": backend, "requests": len(lat_us),
        "wall_s": round(wall, 3),
        "qps": round(len(lat_us) / wall, 1),
        "p50_ms": round(percentile(lat_us, 50) / 1000, 3),
        "p99_ms": round(percentile(lat_us, 99) / 1000, 3),
    }


def _parity(c, queries: List[str], space: str) -> None:
    from ..common.flags import flags
    g = c.client()
    _ok(g, f"USE {space}")
    for q in queries:
        flags.set("storage_backend", "cpu")
        a = sorted(map(tuple, _ok(g, q).rows))
        flags.set("storage_backend", "tpu")
        b = sorted(map(tuple, _ok(g, q).rows))
        assert a == b, f"parity broke on {q!r}"


def bench_basketball(results: list) -> None:
    """Configs 1-2: the canonical small fixture, interactive latency."""
    from ..cluster import LocalCluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        cl = c.client()
        _ok(cl, "CREATE SPACE nba(partition_num=6, replica_factor=1)")
        c.refresh_all()
        _ok(cl, "USE nba")
        _ok(cl, "CREATE TAG player(name string, age int)")
        _ok(cl, "CREATE EDGE follow(degree int)")
        c.refresh_all()
        rng = np.random.default_rng(5)
        players = ", ".join(f'{100 + i}:("p{i}", {20 + i % 25})'
                            for i in range(50))
        _ok(cl, f"INSERT VERTEX player(name, age) VALUES {players}")
        edges = ", ".join(
            f"{100 + int(s)} -> {100 + int(d)}:({60 + int(d) % 40})"
            for s, d in zip(rng.integers(0, 50, 400),
                            rng.integers(0, 50, 400)))
        _ok(cl, f"INSERT EDGE follow(degree) VALUES {edges}")

        one_hop = [f"GO FROM {100 + i % 50} OVER follow" for i in range(400)]
        three_hop = [f"GO 3 STEPS FROM {100 + i % 50} OVER follow "
                     f"WHERE $$.player.age > 30 "
                     f"YIELD follow._dst, follow.degree"
                     for i in range(400)]
        _parity(c, one_hop[:8] + three_hop[:8], "nba")
        for name, qs in (("1-hop GO (basketballplayer)", one_hop),
                         ("3-hop GO + filter (basketballplayer)",
                          three_hop)):
            for backend, router in (("cpu", False), ("tpu", False),
                                    ("auto", True)):
                r = _timed_queries(c, qs, 16,
                                   "tpu" if backend == "auto" else backend,
                                   "nba", router=router)
                r["backend"] = backend
                r["config"] = name
                results.append(r)
                print(r, file=sys.stderr)
    finally:
        c.stop()


def bench_ldbc_paths(results: list, persons: int) -> None:
    """Config 3: FIND SHORTEST PATH on the LDBC-flavoured graph."""
    from ..cluster import LocalCluster
    from .ldbc_gen import generate, load_cluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        src, dst, props = generate(persons)
        load_cluster(c, "ldbc", src, dst, props)
        rng = np.random.default_rng(3)
        pairs = rng.integers(1, persons + 1, (200, 2))
        qs = [f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows "
              f"UPTO 4 STEPS" for a, b in pairs]
        _parity(c, qs[:6], "ldbc")
        for backend in ("cpu", "tpu"):
            r = _timed_queries(c, qs, 16, backend, "ldbc")
            r["config"] = f"FIND SHORTEST PATH (LDBC-ish, {persons:,} persons)"
            results.append(r)
            print(r, file=sys.stderr)
        # concurrency scaling: concurrent FIND PATHs coalesce into one
        # device BFS dispatch (batch_dispatch), so qps must grow with
        # offered concurrency instead of serializing per query
        for threads in (1, 4, 16, 64):
            r = _timed_queries(c, qs, threads, "tpu", "ldbc")
            r["config"] = (f"FIND SHORTEST PATH scaling "
                           f"({threads} workers)")
            results.append(r)
            print(r, file=sys.stderr)
    finally:
        c.stop()


def bench_ldbc_go(results: list, persons: int) -> None:
    """Config 4: batched interactive multi-hop GO on the skewed graph."""
    from ..cluster import LocalCluster
    from .ldbc_gen import generate, load_cluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        src, dst, props = generate(persons)
        load_cluster(c, "ldbc", src, dst, props)
        rng = np.random.default_rng(9)
        vids = rng.integers(1, persons + 1, 1000)
        qs = [f"GO 3 STEPS FROM {v} OVER knows" for v in vids]
        _parity(c, qs[:6], "ldbc")
        for backend, router in (("cpu", False), ("tpu", False),
                                ("auto", True)):
            r = _timed_queries(c, qs, 64,
                               "tpu" if backend == "auto" else backend,
                               "ldbc", router=router)
            r["backend"] = backend
            r["config"] = (f"3-hop GO batched (LDBC-ish skewed, "
                           f"{persons:,} persons, {len(src):,} edges)")
            results.append(r)
            print(r, file=sys.stderr)
    finally:
        c.stop()


def bench_limit_pushdown(results: list, persons: int) -> None:
    """Config: LIMIT/COUNT-shaped GO legs on the skewed graph — the
    device-side reduction pushdown's fetched-bytes story (ROADMAP
    item 2: fetched bytes/query must drop >= 4x on the LIMIT leg).

    Three timed legs over the SAME start vertices: the full 2-hop GO,
    the same GO | LIMIT 10, and GO | YIELD COUNT(*); fetch bytes per
    query come from the runtime's fetch_bytes counter snapshotted
    around each leg.  Correctness rails: the LIMIT rows are a subset
    of the full rows at the requested count, and COUNT equals the full
    row count, both against the CPU path."""
    from ..cluster import LocalCluster
    from .ldbc_gen import generate, load_cluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    try:
        src, dst, props = generate(persons)
        load_cluster(c, "ldbc", src, dst, props)
        rng = np.random.default_rng(17)
        vids = rng.integers(1, persons + 1, 400)
        full_qs = [f"GO 2 STEPS FROM {v} OVER knows "
                   f"YIELD knows._dst AS d" for v in vids]
        lim_qs = [q + " | LIMIT 10" for q in full_qs]
        cnt_qs = [q + " | YIELD COUNT(*)" for q in full_qs]

        # correctness rails (device vs CPU) on a sample
        from ..common.flags import flags
        g = c.client()
        _ok(g, "USE ldbc")
        rt = c.tpu_runtime
        for fq, lq, cq in list(zip(full_qs, lim_qs, cnt_qs))[:6]:
            flags.set("storage_backend", "cpu")
            full_cpu = [tuple(r) for r in _ok(g, fq).rows]
            cnt_cpu = _ok(g, cq).rows
            flags.set("storage_backend", "tpu")
            lim_dev = [tuple(r) for r in _ok(g, lq).rows]
            cnt_dev = _ok(g, cq).rows
            fset = set(full_cpu)
            assert len(lim_dev) == min(10, len(full_cpu)), (lq, lim_dev)
            assert all(r in fset for r in lim_dev), lq
            assert cnt_dev == cnt_cpu, (cq, cnt_dev, cnt_cpu)

        def leg(qs, config):
            before = rt.stats.get("fetch_bytes", 0)
            r = _timed_queries(c, qs, 16, "tpu", "ldbc")
            r["config"] = config
            r["fetch_bytes_per_query"] = round(
                (rt.stats.get("fetch_bytes", 0) - before)
                / max(len(qs), 1), 1)
            results.append(r)
            print(r, file=sys.stderr)
            return r

        r_full = leg(full_qs, "2-hop GO full fetch (LDBC-ish)")
        r_lim = leg(lim_qs, "2-hop GO | LIMIT 10 (pushdown)")
        r_cnt = leg(cnt_qs, "2-hop GO | YIELD COUNT(*) (pushdown)")
        for r in (r_lim, r_cnt):
            r["fetch_drop_x"] = round(
                r_full["fetch_bytes_per_query"]
                / max(r["fetch_bytes_per_query"], 1e-9), 1)
        print(f"fetch bytes/query: full {r_full['fetch_bytes_per_query']}"
              f" limit {r_lim['fetch_bytes_per_query']} "
              f"(drop {r_lim['fetch_drop_x']}x) count "
              f"{r_cnt['fetch_bytes_per_query']} "
              f"(drop {r_cnt['fetch_drop_x']}x)", file=sys.stderr)
    finally:
        c.stop()


_MESH_DRIVER = r"""
import json, sys, time
import numpy as np
from nebula_tpu.tpu.ell import (
    EllIndex, build_sharded_ell, make_batched_go_lanes_kernel,
    make_batched_sparse_go_kernel, make_frontier_sharded_sparse_go_kernel,
    make_sharded_batched_go_kernel, pack_lanes_host, shard_ell,
    sharded_device_args, sharded_sparse_pairs, sparse_caps,
    sparse_go_pairs, split_start_pairs_by_owner, unpack_lanes_host)
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

persons, steps, B = int(sys.argv[1]), 4, 512
from nebula_tpu.tools.ldbc_gen import generate
from nebula_tpu.tpu.jax_setup import device_info
src, dst, props = generate(persons)
src = np.asarray(src, np.int32) - 1
dst = np.asarray(dst, np.int32) - 1
es = np.concatenate([src, dst]); ed = np.concatenate([dst, src])
ee = np.concatenate([np.ones(len(src), np.int32),
                     -np.ones(len(src), np.int32)])
ix = EllIndex.build(es, ed, ee, persons)
devs = jax.devices()
assert len(devs) >= 8, f"need 8 virtual devices, got {devs}"
mesh = Mesh(np.array(devs[:8]), ("parts",))
rng = np.random.default_rng(1)
starts = [rng.integers(0, persons, 1, np.int32) for _ in range(B)]
f0p = jnp.asarray(pack_lanes_host(ix.start_frontier(starts, B=B)))
out = {"persons": persons, "edges": int(len(src)), "devices": 8,
       "B": B, "steps": steps, "device": device_info()}

def timeit(fn, reps=3):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps

# ---- replicated-frontier dense: sharded vs 1-device, SAME graph ----
shards, reals = shard_ell(mesh, "parts", ix)
go8 = make_sharded_batched_go_kernel(mesh, "parts", ix, steps, (1,),
                                     reals)
eslot, hrows = (jnp.asarray(a) for a in ix.hub_merge())
tables = ix.kernel_args()[1:]
single = make_batched_go_lanes_kernel(ix, steps, (1,))
ref = single(f0p, eslot, hrows, *tables)
np.testing.assert_array_equal(
    np.asarray(go8(f0p, eslot, hrows, *shards)), np.asarray(ref))
out["dense_sharded_dispatch_s"] = round(
    timeit(lambda: go8(f0p, eslot, hrows, *shards)), 3)
out["dense_1dev_dispatch_s"] = round(
    timeit(lambda: single(f0p, eslot, hrows, *tables)), 3)

# ---- frontier-sharded sparse vs 1-device sparse, SAME graph --------
# interactive shape (2-hop IS-style reads): bounded frontiers are what
# the frontier-sharded design serves; the saturating 4-hop analytics
# shape above stays on the dense kernels
steps_s = 2
sh = build_sharded_ell(ix, 8)
d_max = max(ix.bucket_D)
c0 = 256                      # per device; total start capacity 8*c0
caps = sparse_caps(c0, d_max, steps_s, 1 << 17)
kern8 = make_frontier_sharded_sparse_go_kernel(
    mesh, "parts", sh, steps_s, (1,), caps, cap_x=1 << 15,
    cap_e=c0)
ni = np.asarray([int(ix.perm[s[0]]) for s in starts], np.int32)
qi = np.arange(B, dtype=np.int32)
placed = split_start_pairs_by_owner(sh, ni, qi, c0)
assert placed is not None
sargs = sharded_device_args(mesh, "parts", sh)
def run8():
    return kern8(jnp.asarray(placed[0]), jnp.asarray(placed[1]),
                 sargs[0], sargs[1], sargs[2], *sargs[3])
ovf, oq, ou = sharded_sparse_pairs(np.asarray(run8()))
assert not ovf, "sharded sparse caps must hold the 2-hop frontier"
got = np.zeros((persons, B), bool)
got[ix.inv[ou], oq] = True
ref2 = make_batched_go_lanes_kernel(ix, steps_s, (1,))(
    f0p, eslot, hrows, *tables)
np.testing.assert_array_equal(
    got, ix.to_old(unpack_lanes_host(np.asarray(ref2), B)))
out["sparse_sharded_dispatch_s"] = round(timeit(run8), 3)

caps1 = sparse_caps(B, d_max, steps_s, 1 << 17)
kern1 = make_batched_sparse_go_kernel(ix, steps_s, (1,), caps1, qmax=B)
order1 = np.lexsort((ni, qi))
ids1 = np.full(caps1[0], ix.n_rows, np.int32)
ids1[:B] = ni[order1]
qid1 = np.zeros(caps1[0], np.int32)
qid1[:B] = qi[order1]
ecnt, e0 = (jnp.asarray(a) for a in ix.hub_expansion())
def run1():
    return kern1(jnp.asarray(ids1), jnp.asarray(qid1), ecnt, e0,
                 *ix.kernel_args()[1:])
_c, ovf1, _q, _u = sparse_go_pairs(kern1, np.asarray(run1()))
out["sparse_1dev_dispatch_s"] = None if ovf1 else round(timeit(run1), 3)

# per-device memory: the sharded-sparse design holds graph/k per chip
# and NO dense frontier anywhere (slots of both directions' tables)
slots = 2 * sum(b.size for b in ix.bucket_nbr)
out["slots_total"] = int(slots)
nb = len(ix.bucket_nbr)           # tables_s: in nbr, in et, out nbr, out et
out["slots_per_device"] = int(sum(
    a.shape[1] * a.shape[2]
    for a in sh.tables_s[:nb] + sh.tables_s[2 * nb:3 * nb]))
out["dense_frontier_bytes_per_device"] = int((ix.n_rows + 1) * (B // 8))
out["sparse_frontier_bytes_per_device"] = int(8 * caps[-1])
print(json.dumps(out))
"""


def _soak_pass(c, space: str, go_qs: List[str], path_qs: List[str],
               threads: int, duration_s: float) -> dict:
    """One closed-loop soak rung: ``threads`` workers hammer a 2:1
    GO : FIND PATH mix for ``duration_s``.  Shed/deadline-exceeded
    responses are counted separately (they are the overload valve
    working, not errors); latencies are recorded per statement class
    so the FIND PATH saturation curve is its own column."""
    import time as _time

    from ..common.status import ErrorCode
    lock = threading.Lock()
    lat = {"go": [], "path": []}
    sheds = [0]
    errors: List[str] = []
    stop_at = [0.0]

    def worker(wid: int):
        g = c.client()
        g.execute(f"USE {space}")
        i = wid
        while _time.perf_counter() < stop_at[0]:
            kind = "path" if i % 3 == 2 else "go"
            qs = path_qs if kind == "path" else go_qs
            q = qs[i % len(qs)]
            t0 = _time.perf_counter()
            r = g.execute(q)
            dt_us = (_time.perf_counter() - t0) * 1e6
            with lock:
                if r.ok():
                    lat[kind].append(dt_us)
                elif r.error_code == ErrorCode.E_DEADLINE_EXCEEDED:
                    sheds[0] += 1
                else:
                    errors.append(r.error_msg)
            i += threads

    # warm concurrently at the rung's thread count (batch shapes are a
    # function of concurrency — see _timed_queries)
    stop_at[0] = _time.perf_counter() + min(3.0, duration_s / 4)
    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    with lock:
        lat["go"].clear()
        lat["path"].clear()
        sheds[0] = 0
        errors.clear()
    start = _time.perf_counter()
    stop_at[0] = start + duration_s
    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = _time.perf_counter() - start
    n_ok = len(lat["go"]) + len(lat["path"])
    out = {
        "workers": threads, "wall_s": round(wall, 1),
        "requests": n_ok, "sheds": sheds[0],
        "errors": len(errors),
        "qps": round(n_ok / wall, 1),
        "go_p50_ms": round(percentile(lat["go"], 50) / 1000, 3)
        if lat["go"] else None,
        "go_p99_ms": round(percentile(lat["go"], 99) / 1000, 3)
        if lat["go"] else None,
        "path_p50_ms": round(percentile(lat["path"], 50) / 1000, 3)
        if lat["path"] else None,
        "path_p99_ms": round(percentile(lat["path"], 99) / 1000, 3)
        if lat["path"] else None,
    }
    if errors:
        out["first_errors"] = errors[:3]
    return out


def bench_soak(results: list, persons: int, duration_s: float = 600.0,
               workers=(8, 16, 32, 64), deadline_ms: int = 2000) -> None:
    """Sustained mixed-workload saturation curve (docs/admission.md):
    GO 3 STEPS + FIND SHORTEST PATH at a 2:1 mix, swept across worker
    counts with admission control ON (2 s whole-request deadlines —
    the overload valve the curve is recording), plus one
    admission-OFF control at the top rung.  The acceptance bar: the
    64-worker FIND PATH p50 stays within ~2x of the 16-worker p50 at
    equal-or-better qps, instead of the 3x collapse an
    admit-everything dispatcher showed in round 5."""
    from ..cluster import LocalCluster
    from ..common.flags import flags
    from .ldbc_gen import generate, load_cluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    saved = {n: flags.get(n) for n in ("admission_control",
                                       "query_deadline_ms",
                                       "storage_backend")}
    try:
        src, dst, props = generate(persons)
        load_cluster(c, "ldbc", src, dst, props)
        rng = np.random.default_rng(11)
        vids = rng.integers(1, persons + 1, 512)
        pairs = rng.integers(1, persons + 1, (256, 2))
        go_qs = [f"GO 3 STEPS FROM {v} OVER knows" for v in vids]
        path_qs = [f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows "
                   f"UPTO 4 STEPS" for a, b in pairs]
        flags.set("storage_backend", "tpu")
        # global warm with the valve open and no deadline: first-query
        # XLA compiles take longer than any sane per-query budget, and
        # a sweep that sheds its own warmup records nothing
        flags.set("admission_control", False)
        flags.set("query_deadline_ms", 0)
        g = c.client()
        g.execute("USE ldbc")
        for q in go_qs[:4] + path_qs[:4]:
            r = g.execute(q)
            assert r.ok(), r.error_msg
        per_rung = duration_s / (len(workers) + 1)
        flags.set("admission_control", True)
        flags.set("query_deadline_ms", int(deadline_ms))
        for t in workers:
            r = _soak_pass(c, "ldbc", go_qs, path_qs, t, per_rung)
            r["config"] = f"soak mixed GO+PATH ({t} workers, admission on)"
            r["backend"] = "tpu"
            r["admission"] = "on"
            results.append(r)
            print(r, file=sys.stderr)
        # control: the top rung with the valve open (round-5 behavior)
        flags.set("admission_control", False)
        flags.set("query_deadline_ms", 0)
        r = _soak_pass(c, "ldbc", go_qs, path_qs, workers[-1], per_rung)
        r["config"] = (f"soak mixed GO+PATH ({workers[-1]} workers, "
                       f"admission off)")
        r["backend"] = "tpu"
        r["admission"] = "off"
        results.append(r)
        print(r, file=sys.stderr)
    finally:
        for k, v in saved.items():
            flags.set(k, v)
        c.stop()


def _prom_value(text: str, family: str, label: str = "") -> float:
    """Sum of every sample of one Prometheus family in a /metrics
    exposition (0.0 when absent).  ``label`` filters series by a
    literal label substring — the write-while-serve gates read ONLY
    the deviceGo-serving runtime's series (runtime="device"): the
    bulk-read backend runtime is a separate epoch whose rare wakeups
    legitimately rebuild (its budget window spans however long the
    CPU path went unread)."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(family):
            continue
        rest = line[len(family):]
        if rest[:1] not in (" ", "{"):
            continue                  # longer family sharing the prefix
        if label and label not in rest:
            continue
        try:
            total += float(line.rsplit(" ", 1)[1])
        except (IndexError, ValueError):
            continue
    return total


def bench_write_serve(results: list, duration_s: float = 180.0,
                      n_vertices: int = 120, writers: int = 2,
                      readers: int = 6, chaos: bool = True,
                      run_dir: Optional[str] = None,
                      num_storage: int = 1,
                      device_env: Optional[Dict[str, str]] = None
                      ) -> dict:
    """Write-while-serve soak (ISSUE 11 acceptance): bulk ingest +
    sustained point mutations (inserts / in-place updates / deletes)
    under live GO / COUNT-pushdown / FIND PATH traffic against REAL
    subprocess daemons, with a SIGKILL of the storaged mid-soak and a
    restart that must recover to a consistent mirror generation.

    Invariants checked (AssertionError on violation):
      * bit-exact parity vs the CPU loop — a second graphd with
        ``storage_backend=cpu`` reads the same store; after
        convergence both front ends serve identical rows;
      * zero acked-write loss — every acked mutation's effect is
        visible on BOTH front ends after convergence (and deleted
        edges are gone); nothing appears that was never attempted;
      * completeness 100 after convergence;
      * the steady write window pays ZERO full rebuilds: absorb count
        grows, rebuild count is flat, delta_overflow stays 0 (storaged
        /metrics — the tpu.mirror.* / tpu.absorb.* gauges).

    ``num_storage >= 2`` is the MULTI-HOST soak (ISSUE 13 acceptance):
    parts spread across storageds, the serving host folds its peers
    through RemoteStoreView, and two more gates arm — the steady
    window records ``peer_absorbs > 0`` (peer writes STREAM through
    deviceScanDelta and fold at O(delta)) and ``remote_rebuilds == 0``
    (no peer write forced the O(m) remote mirror rebuild).  Metric
    samples sum across every storaged.

    Returns (and appends) the result row with per-class p50/p99."""
    import random
    import tempfile
    import threading as _thr
    import time as _time

    from .proc_cluster import ProcCluster

    rd = run_dir or tempfile.mkdtemp(prefix="nebula-write-serve-")
    label = ("write-while-serve soak" if num_storage == 1
             else f"peer-serve soak ({num_storage} storaged)")
    row: dict = {"config": f"{label} ({writers}w/"
                           f"{readers}r, chaos={'on' if chaos else 'off'})",
                 "backend": "tpu", "chaos": chaos,
                 "duration_s": duration_s,
                 "num_storage": num_storage}
    with ProcCluster(rd, num_storage=num_storage,
                     storage_backend="tpu", device_env=device_env) as c:
        cpu_addr = c.add_graphd("graphd-cpu",
                                {"storage_backend": "cpu"})
        cl = c.client()
        cpu = c.client(addr=cpu_addr)

        def ok(g, stmt, tries=40, sleep=0.25):
            last = None
            for _ in range(tries):
                last = g.execute(stmt)
                if last.ok():
                    return last
                _time.sleep(sleep)
            raise AssertionError(f"{stmt}: {last.error_msg}")

        # ---- phase 0: bulk ingest -----------------------------------
        n = n_vertices
        ok(cl, "CREATE SPACE ws(partition_num=3, replica_factor=1)")
        ok(cl, "USE ws")
        ok(cl, "CREATE EDGE knows(w int)")
        # seed every vertex to in-degree 10 (ring both-direction slots
        # + 4 deterministic out-edges each): the ELL rows land at
        # width 16 with ~6 free slots per vertex, so a DEGREE-BOUNDED
        # churn stream (the writers below cap their live pool and
        # spread dsts round-robin) absorbs indefinitely — unbounded
        # degree GROWTH would legitimately re-bucket via the rebuild
        # path instead (docs/durability.md decision table)
        seed_edges = [(i, i % n + 1, 0, i) for i in range(1, n + 1)]
        seed_edges += [(v, (v + 6 + 11 * j) % n + 1, 1 + j,
                        500 + 10 * v + j)
                       for v in range(1, n + 1) for j in range(4)]
        for lo in range(0, len(seed_edges), 100):
            vals = ", ".join(f"{s}->{d}@{r}:({w})"
                             for s, d, r, w in
                             seed_edges[lo:lo + 100])
            ok(cl, f"INSERT EDGE knows(w) VALUES {vals}")
        ok(cpu, "USE ws")
        probe = "GO 2 STEPS FROM 1, 5, 9 OVER knows YIELD knows._dst"
        ok(cl, probe)
        ok(cpu, probe)

        go_qs = [f"GO FROM {v} OVER knows YIELD knows._dst, knows.w"
                 for v in range(1, n + 1, 7)] + \
                [f"GO 2 STEPS FROM {v}, {v + 3} OVER knows "
                 f"YIELD knows._dst" for v in range(1, n - 3, 11)] + \
                [f"GO FROM {v} OVER knows | YIELD COUNT(*)"
                 for v in range(2, n, 13)]
        path_qs = [f"FIND SHORTEST PATH FROM {a} TO {b} OVER knows "
                   f"UPTO 4 STEPS"
                   for a, b in zip(range(1, n, 17),
                                   range(4, n, 17))]

        # ---- shadow write model ------------------------------------
        # each writer OWNS a disjoint key set (its own inserts), so no
        # two threads ever mutate the same edge identity — the shadow
        # oracle stays unambiguous without cross-thread ordering
        shadow_lock = _thr.Lock()
        shadows: list = [dict() for _ in range(writers)]
        attempted_ws: set = {w for _s, _d, _r, w in seed_edges}
        op_seq = [10_000]
        write_errors = [0]

        pool_cap = n                  # live keys per writer: bounds the
                                      # net degree growth under the
                                      # seeded slot slack

        def one_write(g, wrng, my: dict, cursor: list):
            with shadow_lock:
                op_seq[0] += 1
                w = op_seq[0]
                attempted_ws.add(w)
            alive = [k for k, v in my.items() if v["alive"]]
            roll = wrng.random()
            if alive and (len(alive) >= pool_cap or roll < 0.25):
                if len(alive) >= pool_cap or roll < 0.125:
                    # FIFO delete — the OLDEST live key.  A randomly
                    # chosen victim makes each vertex's slot occupancy
                    # a random WALK whose excursions eventually
                    # overflow the row (measured: ~46 re-buckets in a
                    # 3-minute window); FIFO retires each insert
                    # exactly pool_cap inserts later, so per-vertex
                    # occupancy stays bounded for ANY soak length
                    kind, key = "delete", alive[0]
                else:
                    kind, key = "update", wrng.choice(alive)
            elif roll < 0.45 and alive:
                kind, key = "update", wrng.choice(alive)
            else:
                # round-robin src/dst: uniform per-vertex slot growth
                # (a random tail would concentrate inserts on one
                # vertex and overflow its row early)
                kind = "insert"
                cursor[0] += 1
                key = (cursor[0] % n + 1,
                       (cursor[0] * 7 + 3) % n + 1, w)
            if kind == "delete":
                r = g.execute(f"DELETE EDGE knows {key[0]} -> "
                              f"{key[1]}@{key[2]}")
            else:
                r = g.execute(f"INSERT EDGE knows(w) VALUES "
                              f"{key[0]} -> {key[1]}@{key[2]}:({w})")
            ent = my.setdefault(
                key, {"w": None, "alive": False, "clean": True})
            if r.ok():
                ent["alive"] = kind != "delete"
                ent["w"] = w if kind != "delete" else ent["w"]
            else:
                ent["clean"] = False         # outcome unknown
                with shadow_lock:
                    write_errors[0] += 1

        # ---- traffic ------------------------------------------------
        lat_lock = _thr.Lock()
        lat = {"go": [], "path": []}
        read_errors = [0]
        partials = [0]
        stop_at = [_time.perf_counter() + duration_s]

        def writer(wid):
            g = c.client()
            g.execute("USE ws")
            wrng = random.Random(100 + wid)
            cursor = [wid * (n // max(writers, 1))]
            while _time.perf_counter() < stop_at[0]:
                one_write(g, wrng, shadows[wid], cursor)
                _time.sleep(0.02)

        def reader(wid):
            g = c.client()
            g.execute("USE ws")
            i = wid
            while _time.perf_counter() < stop_at[0]:
                kind = "path" if i % 3 == 2 else "go"
                qs = path_qs if kind == "path" else go_qs
                q = qs[i % len(qs)]
                t0 = _time.perf_counter()
                r = g.execute(q)
                dt = (_time.perf_counter() - t0) * 1e6
                with lat_lock:
                    if r.ok() and r.completeness == 100:
                        lat[kind].append(dt)
                    elif r.ok():
                        partials[0] += 1
                    else:
                        read_errors[0] += 1
                i += readers

        settle = max(3.0, duration_s * 0.15)
        ts = [_thr.Thread(target=writer, args=(w,))
              for w in range(writers)]
        ts += [_thr.Thread(target=reader, args=(w,))
               for w in range(readers)]
        t_start = _time.perf_counter()
        for t in ts:
            t.start()
        _time.sleep(settle)

        def sample():
            # one /metrics scrape per storaged: multi-host gates SUM
            # across the fleet (whichever host device-serves)
            return [c.metrics(s) for s in c.storage_names]

        # steady-window sample A: absorption must be carrying the
        # write stream from here on, rebuild-free
        m_a = sample()
        killed_at = None
        if chaos:
            _time.sleep(max(0.0, duration_s * 0.5 - settle))
            # sample B closes the zero-rebuild steady window BEFORE
            # the kill (the restart legitimately rebuilds)
            m_b = sample()
            import signal as _signal
            c.kill("storaged0", _signal.SIGKILL)
            c.wait_down("storaged0")
            killed_at = _time.perf_counter() - t_start
            c.restart("storaged0")
        else:
            _time.sleep(max(0.0, duration_s * 0.5 - settle))
            m_b = sample()
        for t in ts:
            t.join()

        # ---- convergence -------------------------------------------
        deadline = _time.monotonic() + 60
        converged = False
        while _time.monotonic() < deadline:
            r1 = cl.execute(probe)
            r2 = cpu.execute(probe)
            if r1.ok() and r2.ok() and r1.completeness == 100 \
                    and r2.completeness == 100 \
                    and sorted(map(tuple, r1.rows)) \
                    == sorted(map(tuple, r2.rows)):
                converged = True
                break
            _time.sleep(0.5)
        assert converged, "front ends never re-converged after chaos"

        # ---- parity sweep vs the CPU loop --------------------------
        for q in go_qs[:12] + path_qs[:4]:
            r1, r2 = ok(cl, q), ok(cpu, q)
            assert r1.completeness == 100 and r2.completeness == 100, q
            assert sorted(map(tuple, r1.rows)) \
                == sorted(map(tuple, r2.rows)), \
                f"device/CPU divergence after soak: {q}"

        # ---- zero acked-write loss + garbage guard -----------------
        snap: dict = {}
        for my in shadows:            # disjoint by construction
            snap.update({k: dict(v) for k, v in my.items()})
        by_src: dict = {}
        for (s, d, r), ent in snap.items():
            by_src.setdefault(s, []).append((d, r, ent))
        lost, zombies, garbage = [], [], []
        for s, ents in by_src.items():
            for g in (cl, cpu):
                rows = set(map(tuple, ok(
                    g, f"GO FROM {s} OVER knows "
                       f"YIELD knows._dst, knows.w").rows))
                for d, r, ent in ents:
                    if not ent["clean"]:
                        continue       # outcome unknown (kill window)
                    if ent["alive"] and (d, ent["w"]) not in rows:
                        lost.append((s, d, r, ent["w"]))
                    if not ent["alive"] and ent["w"] is not None \
                            and (d, ent["w"]) in rows:
                        zombies.append((s, d, r, ent["w"]))
                for d, w in rows:
                    if w >= 10_000 and w not in attempted_ws:
                        garbage.append((s, d, w))
        assert not lost, f"ACKED writes lost: {lost[:5]}"
        assert not zombies, f"acked deletes resurrected: {zombies[:5]}"
        assert not garbage, f"rows nobody wrote: {garbage[:5]}"

        # ---- absorb-vs-rebuild accounting --------------------------
        m_c = sample()

        def psum(ms, family, label=""):
            return sum(_prom_value(m, family, label) for m in ms)

        absorbs_steady = (psum(m_b, "nebula_tpu_absorb_count", 'runtime="device"')
                          - psum(m_a, "nebula_tpu_absorb_count", 'runtime="device"'))
        # per-host: a replica whose FIRST device mirror lands inside
        # the window (the failover ladder warming a second serving
        # host) is not a write-forced rebuild — the zero-rebuild claim
        # is about hosts already serving at sample A
        rebuilds_steady = 0.0
        for a, b in zip(m_a, m_b):
            a0 = _prom_value(a, "nebula_tpu_mirror_builds",
                             'runtime="device"')
            if a0 > 0:
                rebuilds_steady += _prom_value(
                    b, "nebula_tpu_mirror_builds",
                    'runtime="device"') - a0
        peer_absorbs_steady = (
            psum(m_b, "nebula_tpu_peer_absorb_count", 'runtime="device"')
            - psum(m_a, "nebula_tpu_peer_absorb_count", 'runtime="device"'))
        # the SIGKILL resets the storaged's counters, so the overflow
        # gate must cover BOTH epochs: the pre-kill sample (m_b) and
        # the post-restart one (m_c) — a pre-kill overflow must not
        # hide behind the restart zeroing the gauge
        overflow = max(
            psum(m_b, "nebula_tpu_mirror_delta_overflow", 'runtime="device"'),
            psum(m_c, "nebula_tpu_mirror_delta_overflow", 'runtime="device"'))
        counters = {
            "absorbs": [psum(m, "nebula_tpu_absorb_count", 'runtime="device"')
                        for m in (m_a, m_b, m_c)],
            "builds": [psum(m, "nebula_tpu_mirror_builds", 'runtime="device"')
                       for m in (m_a, m_b, m_c)],
            "absorb_failed": [psum(m, "nebula_tpu_absorb_failed", 'runtime="device"')
                              for m in (m_a, m_b, m_c)],
            "peer_absorbs": [psum(m, "nebula_tpu_peer_absorb_count", 'runtime="device"')
                             for m in (m_a, m_b, m_c)],
            "device_go": [psum(
                m, "nebula_storage_device_go_qps_total")
                for m in (m_a, m_b, m_c)],
            "device_decline": [psum(
                m, "nebula_storage_device_decline_qps_total")
                for m in (m_a, m_b, m_c)],
        }
        row.update({
            "requests": len(lat["go"]) + len(lat["path"]),
            "write_ops": op_seq[0] - 10_000,
            "write_errors": write_errors[0],
            "read_errors": read_errors[0],
            "partials": partials[0],
            "killed_at_s": round(killed_at, 1) if killed_at else None,
            "absorbs_steady_window": absorbs_steady,
            "rebuilds_steady_window": rebuilds_steady,
            "peer_absorbs_steady_window": peer_absorbs_steady,
            "delta_overflow": overflow,
            # counters are per-process: pre-kill and post-restart are
            # separate epochs (the kill zeroes them)
            "absorbs_pre_kill": psum(m_b,
                                     "nebula_tpu_absorb_count", 'runtime="device"'),
            "absorbs_post_restart": psum(
                m_c, "nebula_tpu_absorb_count", 'runtime="device"'),
            "go_p50_ms": round(percentile(lat["go"], 50) / 1000, 3)
            if lat["go"] else None,
            "go_p99_ms": round(percentile(lat["go"], 99) / 1000, 3)
            if lat["go"] else None,
            "path_p50_ms": round(percentile(lat["path"], 50) / 1000, 3)
            if lat["path"] else None,
            "path_p99_ms": round(percentile(lat["path"], 99) / 1000, 3)
            if lat["path"] else None,
        })
        row["device"] = _served_device(c)
        assert absorbs_steady > 0, \
            f"steady write window absorbed nothing — the device path " \
            f"is not serving writes incrementally ({counters}, {row})"
        assert rebuilds_steady == 0, \
            f"steady write window paid {rebuilds_steady} full " \
            f"rebuilds (absorption should carry it) ({counters}, {row})"
        assert overflow == 0, \
            f"delta budget overflowed {overflow} times ({row})"
        if num_storage > 1:
            # the ISSUE 13 multi-host gates: peer writes STREAMED and
            # absorbed (never the O(m) remote mirror rebuild — the
            # rebuild gate above already pinned builds flat)
            assert peer_absorbs_steady > 0, \
                f"multi-host steady window folded no PEER deltas — " \
                f"the stream is not carrying remote writes " \
                f"({counters}, {row})"
    results.append(row)
    print(row, file=sys.stderr)
    return row


def bench_peer_serve(results: list, duration_s: float = 180.0,
                     run_dir: Optional[str] = None) -> dict:
    """The ISSUE 13 multi-host soak: ≥2 storaged, graphd on the device
    path, a steady write window that must show ``peer_absorbs > 0``
    with ``remote_rebuilds == 0`` — bit-exact vs the CPU-loop oracle
    with zero acked-write loss.  Link-death chaos is covered by the
    partition cells (scripts/chaos.sh --cell partition_*); this soak
    keeps the fleet up and measures the stream under sustained load."""
    return bench_write_serve(results, duration_s=duration_s,
                             chaos=False, run_dir=run_dir,
                             num_storage=2)


def _paced_pass(c, space: str, queries: List[str], workers: int,
                offered_qps: float, duration_s: float) -> dict:
    """Open-loop FIXED-OFFERED-LOAD pass: worker w owns slots
    w, w+W, w+2W... of a global ``offered_qps`` schedule and fires its
    query at each slot time (never early; late slots fire immediately,
    so backlog shows up as latency, exactly like a real arrival
    process).  This is what makes the windowed-vs-continuous
    comparison fair: both modes see the SAME arrival schedule."""
    import time as _time

    from ..common.status import ErrorCode
    lock = threading.Lock()
    lat_us: List[float] = []
    sheds = [0]
    errors: List[str] = []
    start = [0.0]

    def worker(wid: int):
        g = c.client()
        g.execute(f"USE {space}")
        k = wid
        interval = 1.0 / offered_qps
        while True:
            slot_t = start[0] + k * interval
            now = _time.perf_counter()
            if slot_t >= start[0] + duration_s:
                return
            if slot_t > now:
                _time.sleep(slot_t - now)
            q = queries[k % len(queries)]
            t0 = _time.perf_counter()
            r = g.execute(q)
            dt_us = (_time.perf_counter() - t0) * 1e6
            with lock:
                if r.ok():
                    lat_us.append(dt_us)
                elif r.error_code == ErrorCode.E_DEADLINE_EXCEEDED:
                    sheds[0] += 1
                else:
                    errors.append(r.error_msg)
            k += workers

    start[0] = _time.perf_counter()
    ts = [threading.Thread(target=worker, args=(w,))
          for w in range(workers)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = _time.perf_counter() - start[0]
    out = {
        "workers": workers, "offered_qps": offered_qps,
        "wall_s": round(wall, 1), "requests": len(lat_us),
        "sheds": sheds[0], "errors": len(errors),
        "qps": round(len(lat_us) / wall, 1),
        "p50_ms": round(percentile(lat_us, 50) / 1000, 3)
        if lat_us else None,
        "p99_ms": round(percentile(lat_us, 99) / 1000, 3)
        if lat_us else None,
    }
    if errors:
        out["first_errors"] = errors[:3]
    return out


def bench_continuous(results: list, persons: int,
                     duration_s: float = 120.0,
                     offered_qps: float = 80.0,
                     workers: int = 8) -> None:
    """ISSUE 15 headline proof #1: at FIXED offered load, continuous
    hop-boundary dispatch vs the windowed oracle — same seeded query
    stream, same arrival schedule, p50/p99 per dispatch mode plus the
    measured device idle fraction over each leg
    (graph/batch_dispatch.py _DeviceBusyMeter: idle share of wall
    time) and the join/leave counters proving the seat map actually
    served.  The claim: continuous cuts multi-hop GO p99 at equal
    offered qps BECAUSE the device idle fraction drops — arrivals
    merge at hop boundaries instead of pooling behind a window."""
    from ..cluster import LocalCluster
    from ..common.flags import flags
    from ..common.stats import stats as _stats_mgr
    from .ldbc_gen import generate, load_cluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    saved = {n: flags.get(n) for n in ("go_dispatch_mode",
                                       "storage_backend",
                                       "admission_control",
                                       "query_deadline_ms",
                                       "tpu_sparse_go")}
    try:
        src, dst, props = generate(persons)
        load_cluster(c, "ldbc", src, dst, props)
        rng = np.random.default_rng(23)
        vids = rng.integers(1, persons + 1, 512)
        go_qs = [f"GO 3 STEPS FROM {v} OVER knows" for v in vids]
        flags.set("storage_backend", "tpu")
        # both legs on the DENSE packed kernel family: continuous only
        # rides the dense seat map, and letting the windowed leg pick
        # sparse would measure kernel choice, not dispatch mode
        flags.set("tpu_sparse_go", False)
        d = c.tpu_runtime.dispatcher
        per_leg = duration_s / 2
        for mode in ("windowed", "continuous"):
            flags.set("go_dispatch_mode", mode)
            # warm with the valve open (bench_soak stance): first-tick
            # XLA compiles inflate the hop EMA past any sane budget,
            # and a leg that sheds its own warmup records nothing
            flags.set("admission_control", False)
            flags.set("query_deadline_ms", 0)
            g = c.client()
            g.execute("USE ldbc")
            for q in go_qs[:2 * workers]:       # warm kernels + stream
                _ok(g, q)
            flags.set("admission_control", True)
            flags.set("query_deadline_ms", 10000)
            busy0, idle0 = d.meter.snapshot()
            joins0 = _stats_mgr.read_stats(
                "graph.continuous.joins.sum.600") or 0.0
            r = _paced_pass(c, "ldbc", go_qs, workers, offered_qps,
                            per_leg)
            busy1, idle1 = d.meter.snapshot()
            joins1 = _stats_mgr.read_stats(
                "graph.continuous.joins.sum.600") or 0.0
            span = (busy1 - busy0) + (idle1 - idle0)
            r["config"] = (f"continuous-vs-windowed GO 3 STEPS "
                           f"({mode}, offered {offered_qps} qps)")
            r["backend"] = "tpu"
            r["dispatch_mode"] = mode
            r["device_idle_frac"] = round((idle1 - idle0) / span, 4) \
                if span > 0 else None
            # the load-invariant form of the idle claim: how long the
            # device pipeline is OCCUPIED per served query.  At a
            # fixed offered load a mode that can't keep up shows low
            # idle (saturated on padded windows) while stretching its
            # wall clock — busy seconds per query is what actually
            # drops when arrivals merge at hop boundaries
            if r["requests"]:
                r["busy_ms_per_query"] = round(
                    (busy1 - busy0) / r["requests"] * 1e3, 3)
            r["continuous_joins"] = int(joins1 - joins0)
            results.append(r)
            print(r, file=sys.stderr)
        seated, queued = (d.continuous.seat_counts()
                          if d.continuous else (0, 0))
        assert (seated, queued) == (0, 0), "lane leak after the leg"
    finally:
        for k, v in saved.items():
            flags.set(k, v)
        c.stop()


def bench_horizontal(results: list, duration_s: float = 120.0,
                     workers: int = 16, n_vertices: int = 400,
                     run_dir: Optional[str] = None,
                     device_env: Optional[Dict[str, str]] = None
                     ) -> None:
    """ISSUE 15 headline proof #2: the stateless tier scales
    horizontally — a SECOND graphd subprocess against the SAME
    storaged/device runtime behind a round-robin client must lift
    aggregate closed-loop throughput >= 1.6x at <= 1.2x the
    single-graphd p99.  graphd is the parse/plan/merge tier (pure
    Python, one GIL per process); the storaged device runtime serves
    both front ends from one seat-map batch, which is exactly the
    continuous tier's horizontal story (ROADMAP item 3).

    The recorded ratio is a function of the HOST's core count (the
    JSON carries it): each graphd is a ~1-core GIL-bound process, so
    the >= 1.6x acceptance needs at least one spare core for the
    second front end — on a single-core container every process
    multiplexes one core and the aggregate is core-bound (the
    measured residual gain there is reduced GIL/scheduler
    contention), exactly like the virtual-mesh leg is a semantics
    measurement, not a multi-chip claim."""
    import os
    import tempfile

    from .proc_cluster import ProcCluster
    rd = run_dir or tempfile.mkdtemp(prefix="bench-horizontal-")
    with ProcCluster(rd, num_storage=1, storage_backend="tpu",
                     device_env=device_env) as c:
        cl = c.client()
        _ok(cl, "CREATE SPACE hz(partition_num=2, replica_factor=1)")
        _ok(cl, "USE hz")
        _ok(cl, "CREATE EDGE e(w int)")

        def okr(stmt, tries=40):
            # schema propagation to the storaged subprocess rides the
            # shrunk load_data interval — poll the first write in
            last = None
            for _ in range(tries):
                last = cl.execute(stmt)
                if last.ok():
                    return last
                time.sleep(0.25)
            raise AssertionError(f"{stmt}: {last.error_msg}")

        okr("INSERT EDGE e(w) VALUES 999001->999002@0:(1)")
        n = n_vertices
        edges = [f"{i}->{i % n + 1}@0:({i})" for i in range(1, n + 1)]
        edges += [f"{i}->{(i * 7 + 3) % n + 1}@1:({i})"
                  for i in range(1, n + 1, 2)]
        for lo in range(0, len(edges), 200):
            _ok(cl, "INSERT EDGE e(w) VALUES "
                + ", ".join(edges[lo:lo + 200]))
        rng = np.random.default_rng(31)
        qs = [f"GO 3 STEPS FROM {int(v)} OVER e YIELD e._dst"
              for v in rng.integers(1, n + 1, 256)]
        _ok(cl, qs[0])                    # device mirror builds

        def closed_loop(addrs: List[str], secs: float) -> dict:
            lock = threading.Lock()
            lat_us: List[float] = []
            errors: List[str] = []
            stop_at = [time.perf_counter() + secs]

            def worker(wid: int):
                g = c.round_robin_client(addrs)
                g.use("hz")
                i = wid
                while time.perf_counter() < stop_at[0]:
                    t0 = time.perf_counter()
                    r = g.execute(qs[i % len(qs)])
                    dt = (time.perf_counter() - t0) * 1e6
                    with lock:
                        if r.ok():
                            lat_us.append(dt)
                        else:
                            errors.append(r.error_msg)
                    i += workers

            # warm at the leg's concurrency, then measure
            ts = [threading.Thread(target=worker, args=(w,))
                  for w in range(workers)]
            stop_at[0] = time.perf_counter() + min(5.0, secs / 3)
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            with lock:
                lat_us.clear()
                errors.clear()
            start = time.perf_counter()
            stop_at[0] = start + secs
            ts = [threading.Thread(target=worker, args=(w,))
                  for w in range(workers)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = time.perf_counter() - start
            return {
                "workers": workers, "wall_s": round(wall, 1),
                "requests": len(lat_us), "errors": len(errors),
                "qps": round(len(lat_us) / wall, 1),
                "p50_ms": round(percentile(lat_us, 50) / 1000, 3)
                if lat_us else None,
                "p99_ms": round(percentile(lat_us, 99) / 1000, 3)
                if lat_us else None,
                "graphds": len(addrs),
                "first_errors": errors[:3] if errors else [],
            }

        cores = os.cpu_count() or 1
        per_leg = duration_s / 2
        one = closed_loop([c.graph_addr], per_leg)
        one["config"] = f"horizontal scale-out (1 graphd, {workers}w)"
        one["backend"] = "tpu"
        one["host_cores"] = cores
        results.append(one)
        print(one, file=sys.stderr)
        addr2 = c.add_graphd("graphd2")
        two = closed_loop([c.graph_addr, addr2], per_leg)
        two["config"] = f"horizontal scale-out (2 graphd, {workers}w)"
        two["backend"] = "tpu"
        two["host_cores"] = cores
        if one["qps"]:
            two["throughput_ratio"] = round(two["qps"] / one["qps"], 2)
        if one["p99_ms"]:
            two["p99_ratio"] = round(two["p99_ms"] / one["p99_ms"], 2)
        if cores < 3:
            two["platform_note"] = (
                f"{cores}-core host: metad+storaged+graphds multiplex "
                f"one core, so aggregate qps is core-bound and the "
                f">=1.6x acceptance needs a spare core for the second "
                f"front end; the residual gain here is reduced "
                f"GIL/scheduler contention.  The scaling MECHANISM "
                f"(add_graphd + RoundRobinClient + autoscale signal) "
                f"is what this leg proves on this host")
        one["device"] = two["device"] = _served_device(c)
        results.append(two)
        print(two, file=sys.stderr)


def bench_mesh_virtual(results: list, persons: int) -> None:
    """Config 5: cross-partition multi-hop GO sharded over an 8-device
    mesh.  Real multi-chip hardware is not available, so this runs the
    REAL sharded kernels (row-sharded ELL buckets, frontier
    re-replication over the mesh axis) on 8 virtual CPU devices in a
    subprocess — a semantics + plumbing measurement, not a TPU
    performance claim (the driver's dryrun compiles the same path)."""
    import os
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_DRIVER, str(persons)],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))))
    if proc.returncode != 0:
        print(f"mesh bench failed: {proc.stderr[-2000:]}", file=sys.stderr)
        results.append({"config": "8-device mesh GO (virtual CPU)",
                        "backend": "tpu-mesh", "error": "failed"})
        return
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    base = (f"({r['persons']:,} persons, {r['edges']:,} edges, "
            f"B={r['B']})")
    for kind, key, hops in (
            ("frontier-sharded sparse, 8 dev",
             "sparse_sharded_dispatch_s", 2),
            ("sparse, 1 dev", "sparse_1dev_dispatch_s", 2),
            ("replicated-frontier dense, 8 dev",
             "dense_sharded_dispatch_s", 4),
            ("dense, 1 dev", "dense_1dev_dispatch_s", 4)):
        dt = r.get(key)
        if dt is None:
            continue
        row = dict(r)
        row["config"] = f"{hops}-hop GO {kind} {base}"
        row["backend"] = "tpu-mesh" if "8 dev" in kind else "tpu-1dev"
        row["qps"] = round(r["B"] / dt, 1)
        row["p50_ms"] = row["p99_ms"] = round(dt * 1000, 1)
        results.append(row)
        print(row["config"], row["qps"], "qps", file=sys.stderr)


def _stamp_inprocess(results: list) -> None:
    """Stamp every row an IN-PROCESS leg produced with this process's
    jax device (child-served rows already carry what their storaged
    reported): a CPU-jax row must say so in the row itself."""
    from ..tpu.jax_setup import device_info
    dev = device_info()
    for r in results:
        r.setdefault("device", dev)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench-suite")
    p.add_argument("--quick", action="store_true",
                   help="small sizes (CI smoke)")
    p.add_argument("--persons", type=int, default=None)
    p.add_argument("--soak", action="store_true",
                   help="run ONLY the sustained mixed-workload "
                        "saturation sweep (admission control on, "
                        "8->64 workers + an admission-off control)")
    p.add_argument("--soak-secs", type=float, default=600.0,
                   help="total soak wall budget, split evenly across "
                        "the worker rungs (default: the 10-minute leg)")
    p.add_argument("--out", default=None,
                   help="also write the results JSON to this path")
    p.add_argument("--write-serve", action="store_true",
                   help="run ONLY the write-while-serve soak: bulk "
                        "ingest + point mutations under live GO/PATH "
                        "traffic with a storaged SIGKILL mid-soak "
                        "(real subprocess daemons; asserts parity, "
                        "zero acked loss, zero steady-window rebuilds)")
    p.add_argument("--write-serve-secs", type=float, default=180.0,
                   help="write-while-serve soak wall budget")
    p.add_argument("--no-chaos", action="store_true",
                   help="write-while-serve without the SIGKILL")
    p.add_argument("--peer-serve", action="store_true",
                   help="run ONLY the multi-host peer-serve soak "
                        "(ISSUE 13): 2 storaged, graphd on the device "
                        "path, asserts peer_absorbs > 0 with zero "
                        "remote rebuilds in the steady write window, "
                        "bit-exact vs the CPU-loop oracle with zero "
                        "acked-write loss")
    p.add_argument("--peer-serve-secs", type=float, default=180.0,
                   help="peer-serve soak wall budget")
    p.add_argument("--continuous", action="store_true",
                   help="run ONLY the continuous-vs-windowed dispatch "
                        "leg (ISSUE 15): same fixed offered load "
                        "through both go_dispatch_mode settings, "
                        "recording p50/p99 + the measured device idle "
                        "fraction per leg")
    p.add_argument("--continuous-secs", type=float, default=120.0,
                   help="continuous leg wall budget (split across the "
                        "two modes)")
    p.add_argument("--horizontal", action="store_true",
                   help="run ONLY the horizontal scale-out leg "
                        "(ISSUE 15): 1 vs 2 graphd subprocesses "
                        "sharing one storaged/device runtime behind a "
                        "round-robin client; acceptance >= 1.6x "
                        "aggregate qps at <= 1.2x p99")
    p.add_argument("--horizontal-secs", type=float, default=120.0,
                   help="horizontal leg wall budget (split across the "
                        "1- and 2-graphd legs)")
    args = p.parse_args(argv)
    persons_path = args.persons or (2000 if args.quick else 10000)
    persons_go = args.persons or (2000 if args.quick else 100000)
    persons_mesh = args.persons or (2000 if args.quick else 50000)

    results: list = []
    if args.continuous or args.horizontal:
        # child-served leg FIRST: its storaged needs the chip, which
        # this process holds once an in-process leg has touched jax
        if args.horizontal:
            bench_horizontal(results,
                             duration_s=args.horizontal_secs,
                             device_env=chip_child_env())
        if args.continuous:
            bench_continuous(results, args.persons or 2000,
                             duration_s=args.continuous_secs)
            _stamp_inprocess(results)
        print(json.dumps(results))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)
        return 0
    if args.peer_serve:
        bench_peer_serve(results, duration_s=args.peer_serve_secs)
        print(json.dumps(results))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)
        return 0
    if args.write_serve:
        bench_write_serve(results, duration_s=args.write_serve_secs,
                          chaos=not args.no_chaos,
                          device_env=chip_child_env())
        print(json.dumps(results))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)
        return 0
    if args.soak:
        bench_soak(results, persons_path, duration_s=args.soak_secs)
        _stamp_inprocess(results)
        print(json.dumps(results))
        if args.out:
            with open(args.out, "w") as fh:
                json.dump(results, fh, indent=1)
        return 0
    # dispatch self-diagnosis first (same probe as bench.py): the
    # per-batch floor is one execute + one fetch round trip to the
    # device, so record it beside the rows it bounds
    try:
        from .perf_fixture import probe_device_roundtrip_ms
        results.append({
            "config": "device round-trip probe", "backend": "-",
            "qps": 0, "p50_ms": 0, "p99_ms": 0,
            "device_roundtrip_ms": round(probe_device_roundtrip_ms(),
                                         3)})
    except Exception as e:      # noqa: BLE001 — probe is diagnostics
        results.append({"config": "device round-trip probe",
                        "backend": "-", "error": str(e)})
    bench_basketball(results)
    bench_ldbc_paths(results, persons_path)
    bench_ldbc_go(results, persons_go)
    bench_limit_pushdown(results, persons_path)
    bench_mesh_virtual(results, persons_mesh)
    _stamp_inprocess(results)

    # markdown table
    print("\n| Config | Backend | QPS | p50 | p99 |")
    print("|---|---|---|---|---|")
    for r in results:
        if "error" in r:
            print(f"| {r['config']} | {r['backend']} | — | — | — |")
            continue
        print(f"| {r['config']} | {r['backend']} | {r['qps']:,} "
              f"| {r['p50_ms']} ms | {r['p99_ms']} ms |")
    print()
    print(json.dumps(results))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(results, fh, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
