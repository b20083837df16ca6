"""Scale benchmark — serve a 10^8-edge power-law graph on one chip.

Answers the scale question directly (the reference's claim to beat is
"dozens of billions of vertices and trillions of edges … millisecond
latency", /root/reference/README.md:8, which it never quantifies):
build the CSR mirror + ELL for a >=100M-edge graph with SF100-like
degree skew, record every stage's cost (bulk load, mirror fold, ELL
build, device upload, HBM bytes), then serve batched multi-hop GO
through the FULL nGQL stack on the TPU path vs the flat CPU fallback
at matched concurrency, with result-set parity spot-checks.

Degree model: discrete power-law (Zipf alpha) out-degrees capped at
``max_deg``, endpoints uniform — matching the heavy-tailed shape of
LDBC SNB's person-knows/likes graphs where supernodes dominate
multi-hop frontiers.

Run: python -m nebula_tpu.tools.scale_bench [--edges 105000000] …
Prints one JSON object; add rows to BASELINE.md from it.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def powerlaw_graph(n: int, m: int, alpha: float, max_deg: int, seed: int):
    """(src, dst) int64 arrays: out-degrees ~ Zipf(alpha) capped, dst
    uniform.  Vectorized: sample a degree per vertex, trim/grow to m
    total, then np.repeat."""
    rng = np.random.default_rng(seed)
    deg = rng.zipf(alpha, n).astype(np.int64)
    deg = np.minimum(deg, max_deg)
    total = int(deg.sum())
    if total > m:       # trim uniformly
        drop = rng.choice(total, total - m, replace=False)
        src_all = np.repeat(np.arange(1, n + 1, dtype=np.int64), deg)
        src = np.delete(src_all, drop)
    else:               # top up with uniform extra edges
        src_all = np.repeat(np.arange(1, n + 1, dtype=np.int64), deg)
        extra = rng.integers(1, n + 1, m - total, dtype=np.int64)
        src = np.concatenate([src_all, extra])
    dst = rng.integers(1, n + 1, m, dtype=np.int64)
    return src, dst


def serve(c, space, queries, threads):
    """Timed concurrent nGQL through graphd -> (qps, p50, p99, rows).
    ``queries`` should be >= 4x threads for a SUSTAINED measurement —
    fewer than one query per worker measures unloaded solo latency,
    not serving capacity."""
    w = c.client()
    w.execute(f"USE {space}")
    r0 = w.execute(queries[0])          # warm kernels for this family
    assert r0.ok(), r0.error_msg
    solo = []
    for q in queries[:8]:               # uncontended p50 alongside
        t0 = time.perf_counter()
        r = w.execute(q)
        assert r.ok(), r.error_msg
        solo.append(time.perf_counter() - t0)
    solo.sort()
    lat, errors, nrows = [], [], [0]
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
            dt = time.perf_counter() - t0
            with lock:
                if r.ok():
                    lat.append(dt)
                    nrows[0] += len(r.rows)
                else:
                    errors.append(r.error_msg)

    t0 = time.perf_counter()
    ts = [threading.Thread(target=worker) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    wall = time.perf_counter() - t0
    assert not errors, errors[:3]
    lat.sort()
    return {
        "wall_s": round(wall, 2),
        "qps": round(len(lat) / wall, 1),
        "p50_ms": round(lat[len(lat) // 2] * 1000, 1),
        "p99_ms": round(lat[int(len(lat) * 0.99) - 1] * 1000, 1),
        "solo_p50_ms": round(solo[len(solo) // 2] * 1000, 1),
        "rows_per_query": round(nrows[0] / max(len(lat), 1), 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vertices", type=int, default=1 << 24)
    ap.add_argument("--edges", type=int, default=105_000_000)
    ap.add_argument("--alpha", type=float, default=2.2)
    ap.add_argument("--max-deg", type=int, default=20_000)
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--steps2", type=int, default=3,
                    help="second (deeper) measured hop count; 0 = skip")
    ap.add_argument("--multi-starts", type=int, default=32,
                    help="third measured leg: GO from this many start "
                         "vids per query (the IS-style batched "
                         "interactive read of BASELINE config 4 — the "
                         "CPU path pays the fan-out per query, the "
                         "device amortizes it); 0 = skip")
    ap.add_argument("--tpu-queries", type=int, default=4096)
    ap.add_argument("--cpu-queries", type=int, default=512,
                    help=">= 4x workers: the CPU number must be a "
                         "SUSTAINED load, not unloaded solo latency")
    ap.add_argument("--workers", type=int, default=128)
    ap.add_argument("--parts", type=int, default=8)
    # one chunk per load: the sorted single-run ingest (hinted O(1)
    # engine inserts) needs each part's keys to arrive as one run
    ap.add_argument("--chunk", type=int, default=1 << 27)
    ap.add_argument("--staging", default="/tmp/scale_staging")
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    from nebula_tpu.cluster import LocalCluster
    from nebula_tpu.codec.rows import encode_row
    from nebula_tpu.common.flags import flags
    from nebula_tpu.tools import bulk_load as BL

    # scale-tuned serving shape: sparse pair kernels with a deep final
    # cap; the dense bitmap path is a last resort at this graph size
    # (its fetch is tens of MB)
    flags.set("tpu_sparse_cap", 1 << 18)
    flags.set("tpu_ell_cap", 256)
    flags.set("go_batch_widths", "128")

    n, m = args.vertices, args.edges
    t_gen0 = time.perf_counter()
    src, dst = powerlaw_graph(n, m, args.alpha, args.max_deg, args.seed)
    t_gen = time.perf_counter() - t_gen0
    log(f"generated {m:,} edges over {n:,} vertices "
        f"(alpha={args.alpha}, max_deg={args.max_deg}) in {t_gen:.0f}s")

    c = LocalCluster(num_storage=1, tpu_backend=True)
    out = {"config": {
        "vertices": n, "edges": m, "alpha": args.alpha,
        "max_deg": args.max_deg, "steps": args.steps,
        "parts": args.parts, "tpu_queries": args.tpu_queries,
        "cpu_queries": args.cpu_queries, "workers": args.workers,
        "multi_starts": args.multi_starts,
    }}
    try:
        g = c.client()
        assert g.execute(
            f"CREATE SPACE scale(partition_num={args.parts}, "
            f"replica_factor=1)").ok()
        c.refresh_all()
        g.execute("USE scale")
        assert g.execute("CREATE EDGE knows(w int)").ok()
        c.refresh_all()
        sid = c.graph_meta_client.get_space_id_by_name("scale").value()
        et = c.schema_man.to_edge_type(sid, "knows").value()
        schema = c.schema_man.get_edge_schema(sid, et)
        blobs = [encode_row(schema, {"w": int(i)}) for i in range(97)]
        store = c.storage_nodes[0].kv
        nparts = len(store.part_ids(sid))

        # ---- bulk load (chunked ingest) -----------------------------
        t0 = time.perf_counter()
        for lo in range(0, m, args.chunk):
            hi = min(m, lo + args.chunk)
            w_idx = (np.arange(lo, hi) % 97).astype(np.int64)
            frames = BL.edge_frames(nparts, et, src[lo:hi], dst[lo:hi],
                                    blobs, w_idx)
            st = BL.bulk_load(store, sid, args.staging, [frames],
                              name=f"scale{lo}")
            assert st.ok(), st
            log(f"  ingested {hi:,}/{m:,} edges "
                f"({time.perf_counter() - t0:.0f}s)")
        out["t_load_s"] = round(time.perf_counter() - t0, 1)
        log(f"bulk load: {out['t_load_s']}s "
            f"({store.spaces[sid].engines[0].total_keys():,} KV rows)")

        # ---- mirror fold + ELL + device upload, staged --------------
        rt = c.tpu_runtime
        t0 = time.perf_counter()
        mir = rt.mirror(sid)
        out["t_mirror_s"] = round(time.perf_counter() - t0, 1)
        out["mirror_rows"] = int(mir.m)
        log(f"mirror fold: {out['t_mirror_s']}s ({mir.m:,} rows, "
            f"{mir.n:,} vertices)")
        t0 = time.perf_counter()
        ix = rt.ell(mir)
        out["t_ell_s"] = round(time.perf_counter() - t0, 1)
        slots = 2 * sum(a.size for a in ix.bucket_nbr)   # both tables
        out["ell_slots"] = int(slots)
        out["ell_extra_rows"] = len(ix.extra_owner)
        log(f"ELL build: {out['t_ell_s']}s ({slots:,} slots, "
            f"{len(ix.extra_owner):,} hub extra rows)")
        t0 = time.perf_counter()
        ix.device_arrays()
        table_bytes = sum(nbr.nbytes + et.nbytes
                          for nbr, et in ix.tables_host())
        out["t_upload_s"] = round(time.perf_counter() - t0, 1)
        out["table_bytes"] = int(table_bytes)
        out["table_bytes_per_edge"] = round(table_bytes / m, 1)
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats:
            out["hbm_bytes_in_use"] = int(stats.get("bytes_in_use", 0))
        # capacity ceiling: tables scale linearly in edges; budget 14 GB
        # for tables leaves headroom for frontiers/outputs on a 16 GB
        # v5e.  (Sparse serving holds NO dense frontier.)
        out["est_max_edges_per_chip"] = int(14e9 / (table_bytes / m))
        log(f"device tables: {table_bytes / 2**30:.2f} GiB "
            f"({out['table_bytes_per_edge']} B/edge; est. ceiling "
            f"{out['est_max_edges_per_chip'] / 1e6:.0f}M edges/chip); "
            f"upload {out['t_upload_s']}s")

        # ---- serving: TPU path vs flat CPU fallback -----------------
        rng = np.random.default_rng(7)
        starts = rng.integers(1, n + 1, args.tpu_queries)
        legs = [(args.steps, 1, ""),
                (args.steps2, 1, f"_{args.steps2}hop"),
                (args.steps, args.multi_starts,
                 f"_{args.multi_starts}st")]
        for hops, nst, tag in legs:
            if not hops or not nst:
                continue
            # the first leg runs the full pinned query count; the
            # deeper and multi-start legs sample a quarter (their
            # per-query work is several times larger)
            nq = args.tpu_queries if not tag \
                else max(args.tpu_queries // 4, 64)
            if nst == 1:
                queries = [f"GO {hops} STEPS FROM {v} OVER knows"
                           for v in starts[:nq]]
            else:
                # IS-style batched short read: one query fans out of
                # nst start vertices (BASELINE config 4's shape) — the
                # per-query work the CPU path multiplies by nst rides
                # the same single device batch
                queries = [
                    "GO {} STEPS FROM {} OVER knows".format(
                        hops, ",".join(map(str, rng.integers(
                            1, n + 1, nst))))
                    for _ in range(nq)]
            flags.set("storage_backend", "tpu")
            snap0 = dict(rt.stats)
            out["tpu" + tag] = serve(c, "scale", queries,
                                     args.workers)
            snap1 = dict(rt.stats)
            # per-leg roofline attribution (docs/roofline.md): sampled
            # device-compute time DISTINCT from the serve() wall p50 —
            # the difference is link RTT + queueing, so a leg losing to
            # the CPU fallback names which side to fix
            d_t = snap1.get("t_device_s", 0.0) \
                - snap0.get("t_device_s", 0.0)
            d_n = snap1.get("device_timed_dispatches", 0) \
                - snap0.get("device_timed_dispatches", 0)
            d_b = snap1.get("device_bytes_moved", 0) \
                - snap0.get("device_bytes_moved", 0)
            out["roofline" + tag] = {
                "device_compute_ms_mean":
                    round(d_t / d_n * 1e3, 3) if d_n else None,
                "achieved_hbm_gbps":
                    round(d_b / d_t / 1e9, 3) if d_t > 0 else None,
                "fetch_bytes_per_query": round(
                    (snap1.get("fetch_bytes", 0)
                     - snap0.get("fetch_bytes", 0)) / max(len(queries),
                                                          1), 1),
            }
            log(f"roofline ({hops} hops): {out['roofline' + tag]}")
            flags.set("storage_backend", "cpu")
            flags.set("flat_bound_mode", True)
            out["cpu_flat" + tag] = serve(
                c, "scale", queries[:args.cpu_queries], args.workers)
            log(f"cpu flat path ({hops} hops, {nst} starts): "
                f"{out['cpu_flat' + tag]}")
            out["p50_speedup_vs_flat_cpu" + tag] = round(
                out["cpu_flat" + tag]["p50_ms"]
                / out["tpu" + tag]["p50_ms"], 2)
            # auto-routed leg: the backend router measures both paths
            # and serves each family from the cheaper one — the light
            # shapes where the flat CPU fallback beats the device
            # must recover to >= the max of both curves here
            flags.set("storage_backend", "tpu")
            flags.set("go_backend_router", True)
            try:
                out["auto" + tag] = serve(
                    c, "scale", queries[:args.cpu_queries], args.workers)
            finally:
                flags.set("go_backend_router", False)
            out["p50_auto_vs_flat_cpu" + tag] = round(
                out["cpu_flat" + tag]["p50_ms"]
                / out["auto" + tag]["p50_ms"], 2)
            log(f"auto-routed ({hops} hops): {out['auto' + tag]} "
                f"(p50 vs flat cpu "
                f"{out['p50_auto_vs_flat_cpu' + tag]}x)")
        flags.set("storage_backend", "tpu")
        out["runtime_stats"] = {
            k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in rt.stats.items()}
        out["dispatch_stats"] = {k: rt.dispatcher.stats.get(k, 0)
                                 for k in ("batches", "batched_queries",
                                           "max_batch", "query_errors")}

        # ---- parity spot-check --------------------------------------
        parity_qs = [f"GO {max(args.steps, 2)} STEPS FROM {v} OVER knows"
                     for v in starts[:3]]
        gq = c.client()
        gq.execute("USE scale")
        for q in parity_qs:
            flags.set("storage_backend", "cpu")
            a = sorted(map(tuple, gq.execute(q).rows))
            flags.set("storage_backend", "tpu")
            b = sorted(map(tuple, gq.execute(q).rows))
            assert a == b, f"parity broke on {q!r}"
        out["parity_checked"] = 3
    finally:
        flags.set("storage_backend", "tpu")
        c.stop()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
