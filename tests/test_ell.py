"""Batched ELL traversal engine tests — parity against an independent
numpy frontier-advance and against the edge-list kernels, single-chip
and sharded over the 8-device CPU mesh (conftest).  Mirrors the
reference's strategy of checking the storage hot path against
known-good row sets (QueryBoundTest.cpp) — here the known-good is the
per-query numpy expansion."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from nebula_tpu.tpu import ell as E  # noqa: E402
from nebula_tpu.tpu import kernels as K  # noqa: E402


def _lanes_args(ix, *host_frontiers):
    """(packed frontiers..., eslot, hrows, *tables): the lanes kernels'
    positional arguments from host [n_rows+1, B] 0/1 matrices."""
    eslot, hrows = ix.hub_merge()
    return (*(jnp.asarray(E.pack_lanes_host(f)) for f in host_frontiers),
            jnp.asarray(eslot), jnp.asarray(hrows),
            *ix.kernel_args()[1:])


def _mirror_edges(es, ed, ee):
    """An edge list in the mirror's form: every edge stored both ways,
    the reverse under -etype (EllIndex.build's contract: the -etype
    rows fill the out-table, which a level that pushes walks)."""
    return (np.concatenate([es, ed]), np.concatenate([ed, es]),
            np.concatenate([ee, -ee]))


def _mirror_ell(es, ed, ee, n, **kw):
    return E.EllIndex.build(*_mirror_edges(es, ed, ee), n, **kw)


def run_go(ix, steps, etypes, f0, upto=False):
    """Build + invoke the batched GO kernel (tables as args) on a host
    [n_rows+1, B] 0/1 matrix; returns the frontier as bool
    [n_rows+1, B] (hub extra rows may hold junk)."""
    k = E.make_batched_go_lanes_kernel(ix, steps, etypes, upto=upto)
    return E.unpack_lanes_host(np.asarray(k(*_lanes_args(ix, f0))),
                               f0.shape[1])


def run_bfs_info(ix, max_steps, etypes, f0, t0, stop_when_found=True,
                 push_rows=None):
    """(int16 depths [n_rows+1, B] with INT16_INF = unreached, the
    program's info vector: levels run, levels that pushed, slots the
    pushed levels visited)."""
    k = E.make_batched_bfs_lanes_kernel(ix, max_steps, etypes,
                                        stop_when_found=stop_when_found,
                                        push_rows=push_rows)
    d, info = k(*_lanes_args(ix, f0, t0))
    d = np.asarray(d)
    if d.dtype == np.int8:           # in-kernel compression (-1 = INF)
        d = np.where(d < 0, E.INT16_INF, d).astype(np.int16)
    return d, np.asarray(info)


def run_bfs_levels(ix, max_steps, etypes, f0, t0, stop_when_found=True):
    """(depths as run_bfs_info gives them, the levels the device loop
    ran)."""
    d, info = run_bfs_info(ix, max_steps, etypes, f0, t0, stop_when_found)
    return d, int(info[E.BFS_INFO_LEVELS])


def run_bfs(ix, max_steps, etypes, f0, t0, stop_when_found=True):
    return run_bfs_levels(ix, max_steps, etypes, f0, t0,
                          stop_when_found)[0]


# The numpy oracles every kernel-parity test in this file AND
# tests/test_packed_frontier.py compares against: plain per-query
# expansion over the edge list in the OLD dense-id space, sharing
# nothing with the ELL tables.
def np_multi_hop(n, es, ed, ok, starts_per_query, steps, upto=False):
    """bool [n, nq] frontier after ``steps - 1`` advances over the edges
    selected by ``ok``; with ``upto`` the union of depths 0..steps-1."""
    nq = len(starts_per_query)
    fr = np.zeros((n, nq), bool)
    for q, s in enumerate(starts_per_query):
        fr[np.asarray(s), q] = True
    acc = fr.copy()
    for _ in range(steps - 1):
        nxt = np.zeros_like(fr)
        for q in range(nq):
            act = fr[es, q] & ok
            nxt[ed[act], q] = True
        fr = nxt
        acc |= fr
    return acc if upto else fr


def np_bfs_depths(n, es, ed, ok, starts_per_query, targets_per_query,
                  max_steps, shortest):
    """(int16 [n, nq] BFS depths with INT16_INF = unreached, levels
    run).  The whole batch advances level by level and stops at
    ``max_steps``, when no query has a live frontier, or (``shortest``)
    when no query has an unreached target — the batched kernels' exit
    rule."""
    nq = len(starts_per_query)
    d = np.full((n, nq), E.INT16_INF, np.int16)
    tgt = np.zeros((n, nq), bool)
    for q, (s, t) in enumerate(zip(starts_per_query, targets_per_query)):
        d[np.asarray(s), q] = 0
        tgt[np.asarray(t), q] = True
    fr = d == 0
    level = 0
    while level < max_steps and fr.any() \
            and not (shortest and not (tgt & (d == E.INT16_INF)).any()):
        nxt = np.zeros_like(fr)
        for q in range(nq):
            act = fr[es, q] & ok
            nxt[ed[act], q] = True
        fr = nxt & (d == E.INT16_INF)
        level += 1
        d[fr] = level
    return d, level


@pytest.mark.parametrize("cap,min_d", [(4, 1), (16, 8), (512, 8)])
def test_batched_go_parity_random(cap, min_d):
    rng = np.random.default_rng(11)
    for _ in range(3):
        n = int(rng.integers(5, 300))
        m = int(rng.integers(0, 2000))
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        ee = rng.choice([1, 2, -1, 3], m).astype(np.int32)
        etypes = (1, 3)
        steps = int(rng.integers(2, 5))
        starts = [rng.integers(0, n, int(rng.integers(1, 6)))
                  for _ in range(5)]
        ok = np.isin(ee, etypes)
        exp = np_multi_hop(n, es, ed, ok, starts, steps)

        ix = E.EllIndex.build(es, ed, ee, n, cap=cap, min_d=min_d)
        f0 = ix.start_frontier([np.asarray(s) for s in starts], B=128)
        got = ix.to_old(run_go(ix, steps, etypes, f0))[:, :5]
        np.testing.assert_array_equal(got, exp)


def test_hub_rows_split_and_merge():
    # one mega-hub: in-degree 50 with cap 8 -> extra rows + fix-up
    n = 60
    es = np.arange(50, dtype=np.int32)          # 0..49 -> hub 55
    ed = np.full(50, 55, dtype=np.int32)
    ee = np.ones(50, dtype=np.int32)
    ix = E.EllIndex.build(es, ed, ee, n, cap=8, min_d=1)
    assert len(ix.extra_owner) >= 1
    f0 = ix.start_frontier([np.asarray([49])], B=128)
    got = ix.to_old(run_go(ix, 2, (1,), f0))[:, 0] > 0
    exp = np.zeros(n, bool)
    exp[55] = True                               # only the hub reached
    np.testing.assert_array_equal(got, exp)
    # start that is NOT an in-neighbor reaches nothing
    f0 = ix.start_frontier([np.asarray([55])], B=128)
    got = ix.to_old(run_go(ix, 2, (1,), f0))[:, 0] > 0
    assert not got.any()


def test_batched_vs_edge_list_kernel():
    rng = np.random.default_rng(3)
    n, m = 128, 700
    es = rng.integers(0, n, m).astype(np.int32)
    ed = rng.integers(0, n, m).astype(np.int32)
    ee = rng.choice([1, 2], m).astype(np.int32)
    steps = 3
    ix = E.EllIndex.build(es, ed, ee, n, cap=16, min_d=4)
    start = np.arange(6, dtype=np.int32)
    f0 = ix.start_frontier([start], B=128)
    got = ix.to_old(run_go(ix, steps, (1,), f0))[:, 0] > 0

    ref = K.make_go_kernel(n, steps, (1,))(
        jnp.asarray(es), jnp.asarray(ed), jnp.asarray(ee),
        jnp.asarray(start))
    np.testing.assert_array_equal(got, np.asarray(ref[1]))


def test_batched_bfs_depths():
    # line graph 0->1->...->9 plus shortcut 0->5
    es = np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 0], np.int32)
    ed = np.array([1, 2, 3, 4, 5, 6, 7, 8, 9, 5], np.int32)
    ee = np.ones(10, np.int32)
    n = 10
    ix = _mirror_ell(es, ed, ee, n, cap=4, min_d=1)
    f0 = ix.start_frontier([np.asarray([0]), np.asarray([3])], B=128)
    t0 = ix.start_frontier([np.asarray([9]), np.asarray([9])], B=128)
    d = run_bfs(ix, 8, (1,), f0, t0, stop_when_found=False)[ix.perm]
    # query 0: depth of 9 is 0->5(1) ..9 => 1+4=5
    assert d[9, 0] == 5
    assert d[5, 0] == 1
    # query 1: from 3: 9 at depth 6
    assert d[9, 1] == 6
    assert d[0, 1] == E.INT16_INF


def test_bfs_early_exit_shortest():
    es = np.array([0, 1], np.int32)
    ed = np.array([1, 2], np.int32)
    ee = np.ones(2, np.int32)
    ix = _mirror_ell(es, ed, ee, 3, cap=2, min_d=1)
    f0 = ix.start_frontier([np.asarray([0])], B=128)
    t0 = ix.start_frontier([np.asarray([1])], B=128)
    d = run_bfs(ix, 100, (1,), f0, t0, stop_when_found=True)[ix.perm]
    assert d[1, 0] == 1     # target found; loop exited without error


def test_sharded_batched_go_parity():
    from jax.sharding import Mesh
    rng = np.random.default_rng(5)
    n, m = 100, 600
    es = rng.integers(0, n, m).astype(np.int32)
    ed = rng.integers(0, n, m).astype(np.int32)
    ee = rng.choice([1, -1], m).astype(np.int32)
    ix = E.EllIndex.build(es, ed, ee, n, cap=8, min_d=2)
    steps = 3
    starts = [rng.integers(0, n, 3) for _ in range(4)]
    # f0 stays a HOST array and each kernel call converts its own
    # device copy — the runtime's dispatch paths build theirs with
    # donate=True (single-use), which a shared device f0 would break
    f0 = ix.start_frontier([np.asarray(s) for s in starts], B=128)
    ref = run_go(ix, steps, (1,), f0)

    mesh = Mesh(np.array(jax.devices()[:8]), ("parts",))
    shards, reals = E.shard_ell(mesh, "parts", ix)
    go = E.make_sharded_batched_go_kernel(mesh, "parts", ix, steps, (1,),
                                          reals)
    eslot, hrows = ix.hub_merge()
    got = np.asarray(go(jnp.asarray(E.pack_lanes_host(f0)),
                        jnp.asarray(eslot), jnp.asarray(hrows),
                        *shards))
    np.testing.assert_array_equal(E.unpack_lanes_host(got, 128), ref)


def _follow_cluster(space):
    """(cluster, runtime, space id, edge type) of a real in-process
    cluster holding 1->2->3->4 and 1->5 over ``follow``."""
    from nebula_tpu.cluster import LocalCluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()
    assert g.execute(
        f"CREATE SPACE {space}(partition_num=3, replica_factor=1)").ok()
    c.refresh_all()
    assert g.execute(f"USE {space}").ok()
    assert g.execute("CREATE EDGE follow(w int)").ok()
    c.refresh_all()
    assert g.execute(
        "INSERT EDGE follow(w) VALUES 1->2:(1), 2->3:(1), "
        "3->4:(1), 1->5:(1)").ok()
    sid = c.graph_meta_client.get_space_id_by_name(space).value()
    et = c.schema_man.to_edge_type(sid, "follow").value()
    return c, c.tpu_runtime, sid, et


def test_runtime_go_batch_small_cluster():
    """go_batch/bfs_batch through the full runtime on a real in-process
    cluster (the batched dispatch graphd-level batching rides on)."""
    c, rt, sid, et = _follow_cluster("s")
    out = rt.go_batch(sid, [[1], [2], [1]], [et], 2)
    m = rt.mirror(sid)

    def vids_of(row):
        return {int(m.vids[i]) for i in np.nonzero(row)[0]}

    assert vids_of(out[0]) == {3}
    assert vids_of(out[1]) == {4}
    assert vids_of(out[2]) == {3}

    d = rt.bfs_batch(sid, [[1]], [[4]], [et], 10, shortest=True)
    dense4 = int(m.to_dense([4])[0])
    assert d[0, dense4] == 3


def test_async_mirror_refresh_serves_stale_then_updates():
    """mirror_refresh_mode=async keeps answering from the stale mirror
    and swaps in the rebuilt one off-thread (the reference's bounded
    staleness: caches refresh every load_data_interval_secs)."""
    import time
    from nebula_tpu.cluster import LocalCluster
    from nebula_tpu.common.flags import flags

    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()
    for stmt in ("CREATE SPACE s2(partition_num=3, replica_factor=1)",):
        assert g.execute(stmt).ok()
    c.refresh_all()
    assert g.execute("USE s2").ok()
    assert g.execute("CREATE TAG p(x int)").ok()
    assert g.execute("CREATE EDGE e(w int)").ok()
    c.refresh_all()
    assert g.execute("INSERT EDGE e(w) VALUES 1->2:(1)").ok()

    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("s2").value()
    m1 = rt.mirror(sid)
    assert m1.m >= 1

    flags.set("mirror_refresh_mode", "async")
    try:
        # a NEW-vertex write changes the vertex plan, which absorption
        # declines (docs/durability.md decision table), so it
        # exercises the async rebuild path
        assert g.execute('INSERT VERTEX p(x) VALUES 9:(5)').ok()
        stale = rt.mirror(sid)          # triggers bg rebuild, serves stale
        assert stale is m1
        deadline = time.time() + 30
        while time.time() < deadline:
            m2 = rt.mirror(sid)
            if m2 is not m1:
                break
            time.sleep(0.05)
        assert m2 is not m1, "background rebuild never landed"
        assert m2.n > m1.n              # the new vertex landed
    finally:
        flags.set("mirror_refresh_mode", "sync")
    c.stop()


def test_runtime_mesh_sharded_parity():
    """tpu_mesh_devices=8 must produce the same nGQL results as the
    single-device path — the runtime-level multi-chip check (the
    kernel-level one is test_sharded_batched_go_parity)."""
    from nebula_tpu.cluster import LocalCluster
    from nebula_tpu.common.flags import flags

    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()
    assert g.execute(
        "CREATE SPACE sm(partition_num=3, replica_factor=1)").ok()
    c.refresh_all()
    assert g.execute("USE sm").ok()
    assert g.execute("CREATE EDGE e(w int, f double)").ok()
    c.refresh_all()
    rng = np.random.default_rng(13)
    # f: doubles float32 does not hold, 0.7 among them
    vals = ", ".join(f"{a}->{b}:({i}, {(i % 10) / 10!r})" for i, (a, b) in
                     enumerate(zip(rng.integers(1, 60, 300),
                                   rng.integers(1, 60, 300))))
    assert g.execute(f"INSERT EDGE e(w, f) VALUES {vals}").ok()

    queries = [
        "GO 3 STEPS FROM 1 OVER e YIELD e._dst",
        "GO 2 STEPS FROM 5 OVER e WHERE e.w > 100 YIELD e._dst, e.w",
        "GO 3 STEPS FROM 2 OVER e WHERE e.f > 0.7 YIELD e._dst, e.f",
        "GO FROM 7, 9 OVER e WHERE e.f <= 0.7 && e.w % 2 == 1 "
        "YIELD e._dst, e.f",
        "FIND SHORTEST PATH FROM 1 TO 59 OVER e",
    ]
    n_where = sum("WHERE" in q for q in queries)
    single = [sorted(map(tuple, g.execute(q).rows)) for q in queries]
    assert all(single)
    # ... and the CPU executor answers the same
    flags.set("storage_backend", "cpu")
    try:
        for q, exp in zip(queries, single):
            assert sorted(map(tuple, g.execute(q).rows)) == exp, q
    finally:
        flags.set("storage_backend", "tpu")
    flags.set("tpu_mesh_devices", 8)
    try:
        for mode in ("sparse", "dense"):
            flags.set("tpu_mesh_mode", mode)
            before = dict(c.tpu_runtime.stats)
            for q, exp in zip(queries, single):
                r = g.execute(q)
                assert r.ok(), f"[{mode}] {q}: {r.error_msg}"
                assert sorted(map(tuple, r.rows)) == exp, (mode, q)
            # a WHERE on a mesh rides the dispatcher and filters at
            # assembly like any other: no route of its own
            grew = {k: c.tpu_runtime.stats[k] - before[k]
                    for k in ("go_where", "go_device")}
            assert grew == {"go_where": n_where,
                            "go_device": n_where + 1}, (mode, grew)
        # the frontier-sharded paths must have actually served, and
        # mesh-served FIND PATH must count in path_device like every
        # other device BFS (the serving accounting the benches report)
        assert c.tpu_runtime.stats.get("go_mesh_sparse", 0) > 0
        assert c.tpu_runtime.stats.get("bfs_mesh_sparse", 0) > 0
        assert c.tpu_runtime.stats.get("path_device", 0) > 0
        # live-vs-declared ICI accounting (common/flight.py): a healthy
        # 8-way dryrun stays IN-BOUND on every sharded kernel's
        # KernelSpec.ici_bytes model and the tpu.model_drift gauges
        # read zero — the declared models hold on live dispatches
        from nebula_tpu.common.flight import recorder
        from nebula_tpu.common.stats import stats as _stats
        mesh_kernels = ("ell_go_sharded", "ell_bfs_sharded",
                        "mesh_sparse_go", "mesh_sparse_bfs")
        cells = {k: v for k, v in recorder.drift_cells().items()
                 if k.split("/", 1)[-1] in mesh_kernels}
        assert cells, "mesh dispatches never folded ICI accounting"
        for k, cell in cells.items():
            assert 0 < cell["live"] <= cell["declared"], (k, cell)
            assert not cell["over"], (k, cell)
        drift = {labels: v for name, labels, v in _stats.gauges()
                 if name == "tpu.model_drift.ici"
                 and labels[0][1] in mesh_kernels}
        assert drift and all(v == 0.0 for v in drift.values()), drift
    finally:
        flags.set("tpu_mesh_devices", 0)
        flags.set("tpu_mesh_mode", "sparse")
    c.stop()


def test_native_builder_identical():
    """The C++ ELL builder must produce byte-identical tables to the
    numpy oracle across degree shapes incl. hubs and empty graphs."""
    from nebula_tpu.native import ensure_built, lib
    if not ensure_built() or lib() is None:
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(42)
    cases = []
    for _ in range(4):
        n = int(rng.integers(1, 500))
        m = int(rng.integers(0, 4000))
        cases.append((rng.integers(0, n, m).astype(np.int32),
                      rng.integers(0, n, m).astype(np.int32),
                      rng.choice([1, 2, -1], m).astype(np.int32), n))
    # hub case: one vertex with in-degree 900 at cap 64
    es = rng.integers(0, 50, 900).astype(np.int32)
    cases.append((es, np.full(900, 7, np.int32),
                  np.ones(900, np.int32), 50))
    cases.append((np.zeros(0, np.int32), np.zeros(0, np.int32),
                  np.zeros(0, np.int32), 0))
    for es, ed, ee, n in cases:
        for cap, min_d in ((8, 1), (64, 8), (512, 8)):
            a = E.EllIndex.build(es, ed, ee, n, cap=cap, min_d=min_d,
                                 use_native=False)
            b = E.EllIndex.build(es, ed, ee, n, cap=cap, min_d=min_d,
                                 use_native=True)
            assert a.n_rows == b.n_rows and a.bucket_D == b.bucket_D
            np.testing.assert_array_equal(a.perm, b.perm)
            np.testing.assert_array_equal(a.inv, b.inv)
            np.testing.assert_array_equal(a.extra_owner, b.extra_owner)
            assert a.shape_sig() == b.shape_sig()
            for x, y in zip(a.tables_host(), b.tables_host()):
                for xa, ya in zip(x, y):
                    assert xa.dtype == ya.dtype
                    np.testing.assert_array_equal(xa, ya)


def _lone_go_cluster():
    from nebula_tpu.cluster import LocalCluster
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()
    assert g.execute("CREATE SPACE ak(partition_num=3, replica_factor=1)").ok()
    c.refresh_all()
    assert g.execute("USE ak").ok()
    assert g.execute("CREATE EDGE e(w int)").ok()
    c.refresh_all()
    assert g.execute("INSERT EDGE e(w) VALUES 1->2:(1), 2->3:(1), "
                     "3->4:(1), 2->5:(1)").ok()
    return c, g


def _cpu_rows(g, stmt):
    from nebula_tpu.common.flags import flags
    flags.set("storage_backend", "cpu")
    try:
        r = g.execute(stmt)
    finally:
        flags.set("storage_backend", "tpu")
    assert r.ok(), r.error_msg
    return sorted(map(tuple, r.rows))


def test_lone_go_serves_cpu_rows():
    """A lone GO through the runtime is device-served and returns the
    CPU executor's rows."""
    c, g = _lone_go_cluster()
    try:
        stmt = "GO 2 STEPS FROM 1 OVER e YIELD e._dst"
        served0 = c.tpu_runtime.stats["go_device"]
        r1 = g.execute(stmt)
        assert r1.ok() and sorted(x[0] for x in r1.rows) == [3, 5]
        assert c.tpu_runtime.stats["go_device"] > served0
        assert sorted(map(tuple, r1.rows)) == _cpu_rows(g, stmt)
    finally:
        c.stop()


@pytest.mark.parametrize("mode", ["windowed", "continuous"])
def test_lone_go_with_unknown_start_is_empty(mode):
    """The one input whose route changed when the adaptive kernel
    went: a lone GO none of whose start vertices the mirror knows.
    Windowed, it is a dense dispatch over an empty frontier; either
    tier answers no rows, as the CPU executor does, on the device,
    without a decline and without moving the breaker."""
    from nebula_tpu.common.flags import flags
    c, g = _lone_go_cluster()
    rt = c.tpu_runtime
    stmt = "GO 2 STEPS FROM 777, 778 OVER e YIELD e._dst"
    flags.set("go_dispatch_mode", mode)
    try:
        assert g.execute("GO 2 STEPS FROM 1 OVER e").ok()   # mirror up
        s0 = dict(rt.stats)
        r = g.execute(stmt)
        assert r.ok(), r.error_msg
        assert r.rows == [] and not r.warnings
        assert _cpu_rows(g, stmt) == []
        assert rt.stats["go_device"] == s0["go_device"] + 1
        if mode == "windowed":
            assert rt.stats["go_dense"] == s0["go_dense"] + 1
            assert rt.stats["go_sparse"] == s0["go_sparse"]
        assert all(state == "closed"
                   for _k, state, _why in rt.breaker.cells_snapshot())
    finally:
        flags.set("go_dispatch_mode", "continuous")
        c.stop()


def test_sparse_batched_go_parity_random():
    """Sparse pair-list batched GO vs the dense kernel on random
    mirror-shaped graphs.  Small caps must REPORT overflow (the caller
    then reruns dense) — never return silently-wrong pairs; roomy caps
    must match the dense frontier exactly."""
    rng = np.random.default_rng(31)
    verified = 0
    for trial in range(8):
        n = int(rng.integers(10, 400))
        m = int(rng.integers(0, 2500))
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        ee = rng.choice([1, 2], m).astype(np.int32)
        es2 = np.concatenate([es, ed])
        ed2 = np.concatenate([ed, es])
        ee2 = np.concatenate([ee, -ee])
        steps = int(rng.integers(2, 5))
        ix = E.EllIndex.build(es2, ed2, ee2, n,
                              cap=int(rng.choice([16, 64])), min_d=4)
        nq = int(rng.integers(1, 6))
        starts = [np.unique(rng.integers(0, n, int(rng.integers(1, 4))))
                  for _ in range(nq)]
        exp = ix.to_old(run_go(ix, steps, (1,),
                               ix.start_frontier(starts,
                                                 B=128)))[:, :nq] > 0
        d_max = max(ix.bucket_D) if ix.bucket_D else 1
        c0 = 64
        cap = int(rng.choice([64, 1 << 17]))     # tight cap forces overflow
        caps = E.sparse_caps(c0, d_max, steps, cap)
        kern = E.make_batched_sparse_go_kernel(ix, steps, (1,), caps)
        ids = np.full(c0, ix.n_rows, np.int32)
        qid = np.zeros(c0, np.int32)
        o = 0
        for q, s in enumerate(starts):
            newi = np.sort(ix.perm[s])
            ids[o:o + len(newi)] = newi
            qid[o:o + len(newi)] = q
            o += len(newi)
        ecnt, e0 = (jnp.asarray(a) for a in ix.hub_expansion())
        out = np.asarray(kern(jnp.asarray(ids), jnp.asarray(qid), ecnt,
                              e0, *ix.kernel_args()[1:]))
        _cnt, overflow, qids, vnew = E.sparse_go_pairs(kern, out)
        if overflow:    # overflow reported — dense fallback covers it
            continue
        got = np.zeros((n, nq), bool)
        if len(qids):
            got[ix.inv[vnew], qids] = True
        np.testing.assert_array_equal(got, exp, err_msg=f"trial {trial}")
        verified += 1
    assert verified >= 2, "every trial overflowed; caps too tight to test"


def test_sparse_hub_push_exact():
    """Hub vertices (slot-spill extra rows) are pushed EXACTLY by the
    sparse kernel: the device expands every frontier hub into its
    extra-row run before the gather, so a hub as a push source is no
    longer an overflow condition (round-4 behavior) — the kernel's
    answer must bit-match the dense pull."""
    # chain: 0 -> 1 -> hub(2) -> {3..149}; hub spills at cap=16
    n = 200
    es = [0, 1] + [2] * 147
    ed = [1, 2] + [i for i in range(3, 150)]
    ee = [1] * len(es)
    es, ed, ee = (np.asarray(es, np.int32), np.asarray(ed, np.int32),
                  np.asarray(ee, np.int32))
    es2 = np.concatenate([es, ed]); ed2 = np.concatenate([ed, es])
    ee2 = np.concatenate([ee, -ee])
    ix = E.EllIndex.build(es2, ed2, ee2, n, cap=16, min_d=4)
    assert len(ix.extra_owner) > 0
    ecnt, e0 = (jnp.asarray(a) for a in ix.hub_expansion())
    for steps in (3, 4):    # hub in final set; hub as a push SOURCE
        caps = E.sparse_caps(64, max(ix.bucket_D), steps, 1 << 12)
        kern = E.make_batched_sparse_go_kernel(ix, steps, (1,), caps)
        ids = np.full(caps[0], ix.n_rows, np.int32)
        qid = np.zeros(caps[0], np.int32)
        ids[0] = ix.perm[0]
        out = np.asarray(kern(jnp.asarray(ids), jnp.asarray(qid), ecnt,
                              e0, *ix.kernel_args()[1:]))
        _cnt, overflow, qids, vids = E.sparse_go_pairs(kern, out)
        assert not overflow, f"steps={steps}: hub push must not overflow"
        got = np.zeros(n, bool)
        got[ix.inv[vids]] = True
        exp = ix.to_old(run_go(ix, steps, (1,),
                               ix.start_frontier([np.asarray([0])],
                                                 B=128)))[:, 0] > 0
        np.testing.assert_array_equal(got, exp, err_msg=f"steps={steps}")


def test_sparse_hub_expansion_overflow_reported():
    """A frontier whose hubs carry more extra rows than the hop budget
    must REPORT overflow (dense rerun), never drop slots silently."""
    # one vertex with in-degree 8 at cap=4 -> extra rows; budget c0=4
    # is smaller than the expansion
    n = 40
    es = list(range(1, 33))
    ed = [0] * 32
    ee = [1] * 32
    es, ed, ee = (np.asarray(es, np.int32), np.asarray(ed, np.int32),
                  np.asarray(ee, np.int32))
    es2 = np.concatenate([es, ed]); ed2 = np.concatenate([ed, es])
    ee2 = np.concatenate([ee, -ee])
    ix = E.EllIndex.build(es2, ed2, ee2, n, cap=4, min_d=4)
    assert len(ix.extra_owner) >= 4
    ecnt, e0 = (jnp.asarray(a) for a in ix.hub_expansion())
    steps = 2
    caps = (4, 1 << 10)     # hub expansion (7 extras) exceeds EX=c0=4
    kern = E.make_batched_sparse_go_kernel(ix, steps, (1,), caps)
    ids = np.full(caps[0], ix.n_rows, np.int32)
    qid = np.zeros(caps[0], np.int32)
    ids[0] = ix.perm[0]     # start ON the hub
    out = np.asarray(kern(jnp.asarray(ids), jnp.asarray(qid), ecnt, e0,
                          *ix.kernel_args()[1:]))
    assert out[1] == 1, "hub expansion past the budget must overflow"


def test_frontier_sharded_sparse_go_bitmatch():
    """The frontier-sharded sparse kernel (per-device pair lists,
    all_to_all candidate exchange, sharded hub metadata) must bit-match
    the single-device dense pull on randomized hub-bearing graphs over
    an 8-virtual-device mesh — and hold NO dense frontier anywhere."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("parts",))
    rng = np.random.default_rng(17)
    verified = 0
    for trial in range(6):
        n = int(rng.integers(50, 500))
        m = int(rng.integers(100, 3000))
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        # a deliberate hub: vertex 0 receives/sends a burst
        hub_m = int(rng.integers(0, 120))
        es = np.concatenate([es, np.zeros(hub_m, np.int32)])
        ed = np.concatenate([ed, rng.integers(0, n, hub_m).astype(np.int32)])
        ee = rng.choice([1, 2], len(es)).astype(np.int32)
        es2 = np.concatenate([es, ed])
        ed2 = np.concatenate([ed, es])
        ee2 = np.concatenate([ee, -ee])
        steps = int(rng.integers(2, 5))
        ix = E.EllIndex.build(es2, ed2, ee2, n, cap=16, min_d=4)
        sh = E.build_sharded_ell(ix, 8)
        nq = int(rng.integers(1, 6))
        starts = [np.unique(rng.integers(0, n, int(rng.integers(1, 4))))
                  for _ in range(nq)]
        exp = ix.to_old(run_go(ix, steps, (1,),
                               ix.start_frontier(starts,
                                                 B=128)))[:, :nq] > 0
        caps = tuple(min(1 << 12, 8 * (16 ** h) * 8)
                     for h in range(steps))
        kern = E.make_frontier_sharded_sparse_go_kernel(
            mesh, "parts", sh, steps, (1,), caps,
            cap_x=1 << 11, cap_e=64)
        new_ids, qids = [], []
        for q, s in enumerate(starts):
            new_ids.extend(ix.perm[s].tolist())
            qids.extend([q] * len(s))
        placed = E.split_start_pairs_by_owner(
            sh, np.asarray(new_ids, np.int32),
            np.asarray(qids, np.int32), caps[0])
        assert placed is not None
        args = E.sharded_device_args(mesh, "parts", sh)
        out = kern(jnp.asarray(placed[0]), jnp.asarray(placed[1]),
                   args[0], args[1], args[2], *args[3])
        overflow, oq, ou = E.sharded_sparse_pairs(np.asarray(out))
        if overflow:
            continue
        got = np.zeros((n, nq), bool)
        if len(oq):
            got[ix.inv[ou], oq] = True
        np.testing.assert_array_equal(got, exp, err_msg=f"trial {trial}")
        verified += 1
    assert verified >= 3, "too many overflows; caps too tight to test"


def test_frontier_sharded_sparse_bfs_bitmatch():
    """The frontier-sharded BFS (per-device depth chunks, all_to_all
    level exchange) must reproduce the single-device batched BFS depths
    on randomized hub-bearing graphs."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("parts",))
    rng = np.random.default_rng(23)
    for trial in range(4):
        n = int(rng.integers(40, 300))
        m = int(rng.integers(60, 1500))
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        hub_m = int(rng.integers(0, 80))
        es = np.concatenate([es, np.zeros(hub_m, np.int32)])
        ed = np.concatenate([ed, rng.integers(0, n, hub_m).astype(np.int32)])
        ee = np.ones(len(es), np.int32)
        es2 = np.concatenate([es, ed]); ed2 = np.concatenate([ed, es])
        ee2 = np.concatenate([ee, -ee])
        ix = E.EllIndex.build(es2, ed2, ee2, n, cap=16, min_d=4)
        sh = E.build_sharded_ell(ix, 8)
        nq = int(rng.integers(1, 5))
        max_steps = int(rng.integers(2, 7))
        shortest = bool(rng.integers(0, 2))
        starts = [np.unique(rng.integers(0, n, 2)) for _ in range(nq)]
        targets = [np.unique(rng.integers(0, n, 2)) for _ in range(nq)]
        f0 = ix.start_frontier(starts, B=128)
        t0 = ix.start_frontier(targets, B=128)
        ref = run_bfs(ix, max_steps, (1,), f0, t0,
                      stop_when_found=shortest)

        builder = E.make_frontier_sharded_sparse_bfs_kernel(
            mesh, "parts", sh, max_steps, (1,), cap=1 << 11,
            cap_x=1 << 10, cap_e=64, stop_when_found=shortest)
        kern = builder(128)
        ni, qi, ti, tq = [], [], [], []
        for q, s in enumerate(starts):
            ni.extend(ix.perm[s].tolist()); qi.extend([q] * len(s))
        for q, t in enumerate(targets):
            ti.extend(ix.perm[t].tolist()); tq.extend([q] * len(t))
        ps = E.split_start_pairs_by_owner(
            sh, np.asarray(ni, np.int32), np.asarray(qi, np.int32),
            1 << 11)
        pt = E.split_start_pairs_by_owner(
            sh, np.asarray(ti, np.int32), np.asarray(tq, np.int32),
            1 << 11)
        a = E.sharded_device_args(mesh, "parts", sh)
        dep, ovf = kern(jnp.asarray(ps[0]), jnp.asarray(ps[1]),
                        jnp.asarray(pt[0]), jnp.asarray(pt[1]),
                        a[0], a[1], a[2], *a[3])
        assert not np.asarray(ovf).any()
        got = np.asarray(dep).reshape(8 * sh.chunk, 128)[:ix.n_rows + 1]
        # strict equality incl. shortest mode: both kernels run whole
        # levels and the all-targets-found level is deterministic, so
        # early exit lands on the same level
        np.testing.assert_array_equal(
            ix.to_old(got)[:, :nq], ix.to_old(ref)[:, :nq],
            err_msg=f"trial {trial} shortest={shortest}")


# ============================================================
# The continuous hop follows the frontier (PR 25): the program that
# chooses, on the device, between a push out of the live slot rows and
# the pull over every slot must agree with the pull alone, bit for
# bit, on every row a reader looks at: rows < n, the pad row, and the
# UPTO accumulator over the same rows.  Extra rows (>= n) are scratch:
# the pull leaves partial ORs there, the push zeros.
# ============================================================
def _hop_graph(seed, n=300, m=5000, cap=16, min_d=2, etypes=(1, 2),
               multi_edges=False, src_below=None, with_edges=False):
    """A skewed two-edge-type graph in the mirror's form (both
    directions, reverse under -etype) with hubs of several extra
    rows.  ``src_below`` keeps the vertices from it up without an
    out-edge; ``with_edges`` also returns the mirror's edge list."""
    rng = np.random.default_rng(seed)
    dst = (rng.zipf(1.5, m) % n).astype(np.int32)
    src = rng.integers(0, src_below or n, m).astype(np.int32)
    et = rng.choice(np.asarray(etypes, np.int32), m)
    if not multi_edges:
        _, first = np.unique(
            (src.astype(np.int64) * n + dst) * 4 + et, return_index=True)
        src, dst, et = src[first], dst[first], et[first]
    edges = _mirror_edges(src, dst, et)
    ix = E.EllIndex.build(*edges, n, cap=cap, min_d=min_d, growth_slack=3)
    return (ix, edges) if with_edges else ix


def _hop_frontier(ix, rng, n_live, W, words=None, junk=True):
    """fp with ``n_live`` live real rows (random lane bytes in
    ``words``), random junk in the hub extra rows, a zero pad row."""
    fp = np.zeros((ix.n_rows + 1, W), np.uint8)
    rows = rng.choice(ix.n, n_live, replace=False)
    cols = list(range(W)) if words is None else list(words)
    vals = rng.integers(1, 256, (n_live, len(cols)), dtype=np.uint8)
    fp[np.asarray(rows)[:, None], np.asarray(cols)[None, :]] = vals
    if junk:
        fp[ix.n:ix.n_rows] = rng.integers(
            0, 256, (ix.n_rows - ix.n, W), dtype=np.uint8)
    return fp, rows


def _live_slot_rows(ix, rows):
    """Slot rows of a set of live vertices (new ids): one main row
    each plus their hub extra rows."""
    ecnt, _e0 = ix.hub_expansion()
    return int(len(rows) + ecnt[np.asarray(rows, np.int64)].sum())


def _live_slots(ix, rows, etypes):
    """ELL slots a push out of these live vertices (new ids) visits:
    the widths of their main rows and of their hub extra rows, in each
    table the OVER set reads."""
    widths = np.concatenate([np.full(nbr.shape[0], nbr.shape[1])
                             for nbr in ix.bucket_nbr])
    ecnt, e0 = ix.hub_expansion()
    return E.sides_read(etypes) * sum(
        int(widths[r]) + int(widths[e0[r]:e0[r] + ecnt[r]].sum())
        for r in rows)


def _pull_reference(ix, etypes, fp, accp):
    nb = len(ix.bucket_nbr)
    tables = ix.kernel_args()[1:]
    eslot, hrows = ix.hub_merge()
    nxt = E._hop_body_packed(jnp, jax, ix.n, len(ix.extra_owner),
                             E._read_sides(tuple(etypes), tables, nb),
                             jnp.asarray(eslot), jnp.asarray(hrows),
                             jnp.asarray(fp))
    return np.asarray(nxt), np.asarray(jnp.asarray(accp) | nxt)


def _run_hop(ix, etypes, fp, accp, push_rows):
    kern = E.make_continuous_hop_kernel(ix, tuple(etypes), donate=False,
                                        push_rows=push_rows)
    eslot, hrows = ix.hub_merge()
    out = kern(jnp.asarray(fp), jnp.asarray(accp), jnp.asarray(eslot),
               jnp.asarray(hrows), *ix.kernel_args()[1:])
    return [np.asarray(o) for o in out]


HOP_CASES = [
    # name, etypes, live rows, lane words, budget rule
    ("one_row", (1,), 1, None, "fits"),
    ("few_rows_over_a", (1,), 7, None, "fits"),
    ("over_a_b", (1, 2), 9, None, "fits"),
    ("reversely", (-1,), 9, None, "fits"),
    ("reversely_both", (-1, -2), 5, None, "fits"),
    ("mixed_directions", (2, -1), 6, None, "fits"),
    ("empty_frontier", (1,), 0, None, "fits"),
    ("lanes_in_one_word", (1, 2), 12, (3,), "fits"),
    ("lanes_in_several_words", (1, 2), 12, (0, 5, 15), "fits"),
    ("no_junk_in_extras", (1,), 8, None, "fits_clean"),
    ("exactly_at_the_budget", (1, 2), 25, None, "exact"),
    ("one_row_over_the_budget", (1, 2), 25, None, "over"),
    ("far_over_the_budget", (1,), 200, None, "tiny"),
    ("every_row_live", (1, 2), 300, None, "tiny"),
    ("multi_edges_between_a_pair", (1,), 10, None, "fits_multi"),
]


@pytest.mark.parametrize("name,etypes,n_live,words,rule", HOP_CASES,
                         ids=[c[0] for c in HOP_CASES])
def test_continuous_hop_agrees_with_the_pull(name, etypes, n_live, words,
                                             rule):
    ix = _hop_graph(11, multi_edges=(rule == "fits_multi"))
    assert len(ix.bucket_nbr) >= 2
    ecnt, _ = ix.hub_expansion()
    assert ecnt.max() >= 3, "the graph must have hubs of several extra rows"
    rng = np.random.default_rng(5)
    W = 16
    fp, rows = _hop_frontier(ix, rng, n_live, W, words,
                             junk=(rule != "fits_clean"))
    if n_live:
        # a hub among the live rows, so the push walks its extra rows
        hub = int(np.argmax(ecnt[:ix.n]))
        fp[hub] = fp[rows[0]]
        rows = np.unique(np.append(rows, hub))
    # UPTO lanes: the accumulator holds earlier depths, and must take
    # the new frontier in whichever branch produced it
    accp = rng.integers(0, 256, fp.shape, dtype=np.uint8)
    accp[ix.n_rows] = 0
    live = _live_slot_rows(ix, rows)
    budget = {"fits": 4096, "fits_clean": 4096, "fits_multi": 4096,
              "exact": live, "over": live - 1, "tiny": 8}[rule]
    nxt, acc, info = _run_hop(ix, etypes, fp, accp, budget)
    want_nxt, want_acc = _pull_reference(ix, etypes, fp, accp)
    sel = np.r_[0:ix.n, ix.n_rows]
    assert np.array_equal(nxt[sel], want_nxt[sel])
    assert np.array_equal(acc[sel], want_acc[sel])
    assert not nxt[ix.n_rows].any()            # the pad row stays zero
    pushed = live <= budget
    assert info[E.HOP_INFO_SPARSE] == int(pushed)
    assert info[E.HOP_INFO_ROWS] == live
    if pushed:
        assert info[E.HOP_INFO_SLOTS] == _live_slots(ix, rows, etypes)
    else:
        assert info[E.HOP_INFO_SLOTS] == E.table_slots(ix, etypes)


def test_continuous_hop_chain_push_after_pull_after_push():
    """Three hops in a row under a budget the middle frontier
    overflows: the push reads a frontier whose extra rows a pull left
    full of partial ORs, and the chain still agrees with three
    pulls."""
    ix = _hop_graph(12)
    rng = np.random.default_rng(9)
    fp, rows = _hop_frontier(ix, rng, 2, 16, junk=False)
    accp = fp.copy()
    ref_fp, ref_acc = fp.copy(), fp.copy()
    branches = []
    sel = np.r_[0:ix.n, ix.n_rows]
    for _hop in range(3):
        fp, accp, info = _run_hop(ix, (1, 2), fp, accp, push_rows=20)
        ref_fp, ref_acc = _pull_reference(ix, (1, 2), ref_fp, ref_acc)
        branches.append(int(info[0]))
        assert np.array_equal(fp[sel], ref_fp[sel])
        assert np.array_equal(accp[sel], ref_acc[sel])
    assert 1 in branches and 0 in branches, branches


def test_set_positions_is_nonzero_with_a_size():
    rng = np.random.default_rng(2)
    for R, k, cap in ((1, 1, 4), (127, 0, 8), (128, 128, 128),
                      (1000, 37, 64), (1000, 300, 64), (4097, 4097, 16)):
        mask = np.zeros(R, bool)
        mask[rng.choice(R, k, replace=False)] = True
        got = np.asarray(E._set_positions(jnp, jnp.asarray(mask), cap))
        want = np.full(cap, R, np.int64)
        idx = np.nonzero(mask)[0][:cap]
        want[:len(idx)] = idx
        assert np.array_equal(got, want), (R, k, cap)


# ============================================================
# A BFS level follows its frontier (PR 30): the lanes program takes the
# continuous hop's step every level, so depths, the levels run and the
# stall test must not depend on which branch ran a level, and the
# program must say what it did.
# ============================================================
BFS_GRAPHS = {
    # name: (seed, etypes the BFS runs over, levels that push under
    # the middle budget, which is the live slot rows of the level
    # that ^ follows)
    "reversely_the_frontier_grows": (22, (-1, -2), "11^000"),
    "over_a_hubs_then_thins_out": (21, (1,), "101^01"),
}


def _bfs_push_case(graph):
    """(ix, etypes, starts, targets, oracle edges, the middle budget's
    level, the levels it lets push): hubs of several extra rows, pairs
    named twice, a target no edge enters, a source no edge leaves."""
    seed, etypes, mid = BFS_GRAPHS[graph]
    n = 300
    ix, (es, ed, ee) = _hop_graph(seed, n=n, m=1500, multi_edges=True,
                                  src_below=n - 8, with_edges=True)
    ecnt, _ = ix.hub_expansion()
    assert ecnt.max() >= 3, "the graph must have hubs of several extra rows"
    ok = np.isin(ee, etypes)
    key = (es[ok].astype(np.int64) * n + ed[ok]) * 8 + ee[ok] + 4
    assert len(np.unique(key)) < len(key), "no pair is named twice"
    no_in = np.setdiff1d(np.arange(n), ed[ok])
    no_out = np.setdiff1d(np.arange(n), es[ok])
    assert len(no_in) and len(no_out)
    starts = [[3], [17], [int(no_out[0])], [41]]
    targets = [[250], [int(no_in[0])], [5], [299, 40]]
    pushes = [c == "1" for c in mid.replace("^", "")]
    return (ix, etypes, starts, targets, (n, es, ed, ok),
            mid.index("^") - 1, pushes)


@pytest.mark.parametrize("graph", sorted(BFS_GRAPHS))
@pytest.mark.parametrize("shortest", [True, False],
                         ids=["shortest", "all_levels"])
@pytest.mark.parametrize("budget", ["pulls", "pushes_then_pulls", "pushes"])
def test_bfs_level_follows_its_frontier(budget, shortest, graph):
    ix, etypes, starts, targets, oracle, mid, mid_pushes = \
        _bfs_push_case(graph)
    max_steps = 5
    want, want_levels = np_bfs_depths(*oracle, starts, targets, max_steps,
                                      shortest)
    # the union frontier entering level k holds the vertices some lane
    # reached at depth k: its live slot rows decide push or pull
    fronts = [ix.perm[np.flatnonzero((want == k).any(axis=1))]
              for k in range(want_levels)]
    live = [_live_slot_rows(ix, rows) for rows in fronts]
    # (a budget of 0 rows cannot be traced: the push would gather out
    # of an empty list; 1 is under every level's four sources)
    push_rows = {"pulls": 1, "pushes_then_pulls": live[mid],
                 "pushes": max(live)}[budget]
    pushed = [rows <= push_rows for rows in live]
    assert pushed == {"pulls": [False] * want_levels,
                      "pushes_then_pulls": mid_pushes,
                      "pushes": [True] * want_levels}[budget], live
    f0 = ix.start_frontier([np.asarray(s) for s in starts], B=128)
    t0 = ix.start_frontier([np.asarray(t) for t in targets], B=128)
    d, info = run_bfs_info(ix, max_steps, etypes, f0, t0,
                           stop_when_found=shortest, push_rows=push_rows)
    # every budget gives THE SAME matrix: the oracle's depths on rows
    # < n, unreached on the hub extra rows, the pad row and idle lanes
    assert np.array_equal(ix.to_old(d)[:, :len(starts)], want)
    assert (d[ix.n:] == E.INT16_INF).all()
    assert (d[:, len(starts):] == E.INT16_INF).all()
    assert info[E.BFS_INFO_LEVELS] == want_levels
    assert info[E.BFS_INFO_PUSHED] == sum(pushed)
    push_slots = sum(_live_slots(ix, rows, etypes)
                     for rows, p in zip(fronts, pushed) if p)
    assert info[E.BFS_INFO_PUSH_SLOTS] == push_slots
    assert E.bfs_slots(ix, etypes, info) == push_slots \
        + (want_levels - sum(pushed)) * E.table_slots(ix, etypes)


def test_runtime_bfs_record_says_how_its_levels_ran():
    """rt.bfs_batch on a real in-process cluster: the ell_bfs dispatch
    record carries the levels, how many pushed and the slots they
    visited, and a lone pair's levels push."""
    from nebula_tpu.common.flight import recorder
    c, rt, sid, et = _follow_cluster("sp")
    try:
        before = dict(rt.stats)
        d = rt.bfs_batch(sid, [[1]], [[4]], [et], 10, shortest=True)
        m = rt.mirror(sid)
        assert d[0, int(m.to_dense([4])[0])] == 3
        record = max((r for r in recorder.dump(limit=1 << 16)
                      if r.get("kernel") == "ell_bfs"),
                     key=lambda r: r["time_us"])
        assert record["queries"] == 1 and record["levels"] == 3
        # frontiers of one or two vertices: every level pushes, and
        # visits the live rows' slots, not the table
        assert record["levels_push"] == 3
        # OVER one edge type forwards: every level read one table
        assert record["hop_onesided"] == 3
        assert rt.stats["hop_onesided"] - before["hop_onesided"] == 3
        assert 0 < record["slots"] < 3 * E.table_slots(rt.ell(m), (et,))
        assert rt.stats["path_levels"] - before["path_levels"] == 3
        assert rt.stats["path_levels_push"] \
            - before["path_levels_push"] == 3
    finally:
        c.stop()


# ============================================================
# One table a direction (PR 35): the in-table holds a vertex's in-edge
# sources, the out-table its out-edge targets, over one row layout; a
# step reads only the table(s) its OVER set has a member for.
# ============================================================
SPLIT_CAP = 8


def _split_graph():
    """(n, src, dst, etype) of a directed two-type graph whose shapes
    the split has to get right: vertex 0 takes 20 in-edges (past the
    cap of 8) and has ONE out-edge, vertex 2 the reverse, 4 is a sink,
    5 a source, 39 isolated; pairs under both types and a pair named
    twice under one."""
    rng = np.random.default_rng(35)
    n = 40
    fans = np.arange(10, 30)
    src = [fans, np.full(20, 2), [0, 3, 5, 6, 5]]
    dst = [np.zeros(20, int), fans, [1, 2, 4, 4, 7]]
    et = [rng.choice([1, 2], 20), rng.choice([1, 2], 20), [1, 1, 1, 2, 2]]
    extra = 60
    es = rng.integers(6, 39, extra)
    src.append(es)
    dst.append((es + rng.integers(1, 30, extra) - 6) % 33 + 6)
    et.append(rng.choice([1, 2], extra))
    src, dst, et = (np.concatenate([np.asarray(a, np.int32) for a in x])
                    for x in (src, dst, et))
    src = np.append(src, [8, 8]).astype(np.int32)      # one pair twice
    dst = np.append(dst, [9, 9]).astype(np.int32)
    et = np.append(et, [1, 1]).astype(np.int32)
    touched = np.union1d(src, dst)
    assert 39 not in touched and 4 not in src and 5 not in dst
    return n, src, dst, et


def _split_ell(**kw):
    n, src, dst, et = _split_graph()
    return _mirror_ell(src, dst, et, n, cap=SPLIT_CAP, min_d=2, **kw), \
        (n, src, dst, et)


def _walk_step(n, src, dst, et, etypes, f):
    """Brute force, one edge at a time: bool [n, B] next frontier of
    ``f`` over a signed OVER set (+t follows u -> v, -t walks it
    back)."""
    nxt = np.zeros_like(f)
    for u, v, t in zip(src, dst, et):
        if t in etypes:
            nxt[v] |= f[u]
        if -t in etypes:
            nxt[u] |= f[v]
    return nxt


def _rows_of(ix, v_new):
    """Global slot rows of a vertex (new id): main row + extra rows."""
    ecnt, e0 = ix.hub_expansion()
    return [v_new] + list(range(e0[v_new], e0[v_new] + ecnt[v_new]))


def _slots_of(ix, nbrs, ets, v_new):
    """Sorted (old neighbour id, |etype|) entries of a vertex's rows in
    one table."""
    bstarts = np.cumsum([0] + [a.shape[0] for a in ix.bucket_nbr])
    out = []
    for row in _rows_of(ix, v_new):
        b = int(np.searchsorted(bstarts, row, side="right")) - 1
        nbr, et = nbrs[b][row - bstarts[b]], ets[b][row - bstarts[b]]
        fill = nbr != ix.n_rows
        assert (et[~fill] == 0).all()
        out += list(zip(ix.inv[nbr[fill]].tolist(), et[fill].tolist()))
    return sorted(out)


@pytest.mark.parametrize("native", [False, True], ids=["numpy", "native"])
def test_split_build_holds_each_direction_in_its_own_table(native):
    ix, (n, src, dst, et) = _split_ell(use_native=native, growth_slack=2)
    # one row layout: both tables have the same shapes, rows sum to
    # n_rows, the bucket is the power of two over the LARGER side
    assert [a.shape for a in ix.bucket_nbr] == \
        [a.shape for a in ix.out_nbr] == \
        [a.shape for a in ix.bucket_et] == [a.shape for a in ix.out_et]
    assert sum(a.shape[0] for a in ix.bucket_nbr) == ix.n_rows
    deg_in = np.bincount(dst, minlength=n)
    deg_out = np.bincount(src, minlength=n)
    widths = np.concatenate([np.full(a.shape[0], a.shape[1])
                             for a in ix.bucket_nbr])
    want_w = np.clip(E._next_pow2(np.minimum(
        np.maximum(deg_in, deg_out), SPLIT_CAP)), 2, SPLIT_CAP)
    assert (widths[ix.perm] == want_w).all()
    # a hub by EITHER side owns ceil(max / cap) - 1 extra rows, the
    # same rows in both tables; nobody else owns any
    ecnt, _ = ix.hub_expansion()
    assert deg_in[0] == 20 and deg_out[0] == 1
    assert deg_out[2] == 20 and deg_in[2] == 1
    big = np.maximum(deg_in, deg_out)
    want_extra = np.where(big > SPLIT_CAP, -(-big // SPLIT_CAP) - 1, 0)
    assert (ecnt[ix.perm] == want_extra).all() and want_extra[0] == 2
    assert len(ix.extra_owner) == want_extra.sum() + 2   # + the spares
    # every vertex: its in-table rows hold its in-edges' sources, its
    # out-table rows its out-edges' targets, magnitudes only
    for v in range(n):
        r = int(ix.perm[v])
        assert _slots_of(ix, ix.bucket_nbr, ix.bucket_et, r) == sorted(
            zip(src[dst == v].tolist(), et[dst == v].tolist())), v
        assert _slots_of(ix, ix.out_nbr, ix.out_et, r) == sorted(
            zip(dst[src == v].tolist(), et[src == v].tolist())), v


SPLIT_OVER = {"over_e": (1,), "over_e_reversely": (-1,),
              "mixed_sign": (1, -2), "two_types": (1, 2),
              "two_types_reversely": (-1, -2)}


@pytest.mark.parametrize("branch", ["pull", "push"])
@pytest.mark.parametrize("over", sorted(SPLIT_OVER))
def test_split_step_is_the_brute_force_walk(over, branch):
    """push = pull = the edge-by-edge walk on the same frontiers, hubs
    of either side, the sink, the source and the isolated vertex among
    the live rows; and the step says which table(s) it read."""
    etypes = SPLIT_OVER[over]
    ix, (n, src, dst, et) = _split_ell(growth_slack=2)
    B = 16
    starts = [[0], [2], [4], [5], [39], [1, 3], [10, 29], [0, 2, 8],
              [12], [6, 7]]
    f0 = ix.start_frontier([np.asarray(s) for s in starts], B=B)
    fp = E.pack_lanes_host(f0)
    one_table = sum(a.size for a in ix.bucket_nbr)
    assert E.table_slots(ix, etypes) == E.sides_read(etypes) * one_table
    sel = np.r_[0:ix.n, ix.n_rows]
    want = ix.to_old(f0).astype(bool)
    for _hop in range(3):
        live = _live_slot_rows(ix, np.flatnonzero(
            E.unpack_lanes_host(fp, B)[:ix.n].any(axis=1)))
        budget = max(live, 1) if branch == "push" else 1
        nxt, _acc, info = _run_hop(ix, etypes, fp, fp, budget)
        want = _walk_step(n, src, dst, et, etypes, want)
        got = E.unpack_lanes_host(nxt, B)
        assert np.array_equal(ix.to_old(got), want), (over, _hop)
        assert not nxt[ix.n_rows].any()
        pushed = branch == "push" or live <= 1
        assert info[E.HOP_INFO_SPARSE] == int(pushed)
        if not pushed:
            # a pull sweeps every slot of the table(s) read, no more
            assert info[E.HOP_INFO_SLOTS] == E.table_slots(ix, etypes)
        # extra rows are scratch: clear them as a reader would see them
        fp = np.zeros_like(nxt)
        fp[sel] = nxt[sel]
    # the windowed programs read the same tables: dense and pair-list
    dense = run_go(ix, 4, etypes, f0)
    assert np.array_equal(ix.to_old(dense), want)


@pytest.mark.parametrize("top,dtype", [(1, np.int8), (127, np.int8),
                                       (128, np.int16), (32767, np.int16),
                                       (32768, np.int32)])
def test_etype_column_is_as_narrow_as_the_largest_etype(top, dtype):
    """The etype columns take the narrowest signed integer type that
    holds the mirror's largest |etype|: read off the input at build,
    part of shape_sig, and what the mask compares in."""
    n, src, dst, et = _split_graph()
    et = np.where(et == 2, top, 1).astype(np.int32)
    sigs = set()
    for native in (False, True):
        ix = _mirror_ell(src, dst, et, n, cap=SPLIT_CAP, min_d=2,
                         use_native=native)
        assert ix.et_dtype == dtype
        assert all(a.dtype == dtype for a in ix.bucket_et + ix.out_et)
        assert all(a.dtype == np.int32 for a in ix.bucket_nbr + ix.out_nbr)
        assert ix.shape_sig()[-1] == np.dtype(dtype).name
        sigs.add(ix.shape_sig())
    assert len(sigs) == 1
    narrow = _mirror_ell(src, dst, np.minimum(et, 2), n, cap=SPLIT_CAP,
                         min_d=2)
    if dtype != np.int8:
        assert narrow.shape_sig() != ix.shape_sig()
    f0 = ix.start_frontier([np.asarray([0, 2, 8]), np.asarray([5])], B=8)
    for etypes in ((top,), (-top,), (1, top), (top + 1,)):
        got = run_go(ix, 3, etypes, f0)
        want = ix.to_old(f0).astype(bool)
        for _ in range(2):
            want = _walk_step(n, src, dst, et, etypes, want)
        assert np.array_equal(ix.to_old(got), want), etypes


def _absorb_case(kind, ix, n, src, dst, et):
    """(inserted (u, v, t) edges, deleted ones) for one absorb window."""
    if kind == "delete":
        gone = [(int(src[i]), int(dst[i]), int(et[i])) for i in (0, 21, 45)]
        return [], gone
    widths = np.concatenate([np.full(a.shape[0], a.shape[1])
                             for a in ix.bucket_nbr])[ix.perm]
    # free slots of each non-hub vertex's one row, by direction
    free_in = widths - np.bincount(dst, minlength=n)
    free_out = widths - np.bincount(src, minlength=n)
    free_in[[0, 2]] = free_out[[0, 2]] = 0
    have = set(zip(src.tolist(), dst.tolist()))

    def fresh_sources(v, k):
        out = []
        for u in range(n):
            if len(out) < k and u != v and (u, v) not in have \
                    and free_out[u] > 0:
                free_out[u] -= 1
                out.append(u)
        assert len(out) == k
        return out

    if kind == "insert":
        # into rows with room: the isolated vertex gains an out-edge,
        # the source an in-edge, the sink an out-edge
        targets = [v for v in (5, 6, 12) if free_in[v] > 0]
        assert 5 in targets
        return ([(u, v, 1 + i % 2) for i, v in enumerate(targets)
                 for u in fresh_sources(v, 1)]
                + [(39, 38, 2), (4, 39, 1)]), []
    # claim: vertex 7's in-edges outgrow its one row by three; no hub
    return [(u, 7, 1) for u in fresh_sources(7, int(free_in[7]) + 3)], []


@pytest.mark.parametrize("kind", ["insert", "delete", "claim"])
def test_absorb_lands_in_the_right_table(kind):
    """An overlay's +etype rows rewrite in-table rows, its -etype rows
    out-table rows; a claimed spare is the owner's in both tables; the
    absorbed tables walk like a rebuild."""
    ix, (n, src, dst, et) = _split_ell(growth_slack=3)
    ins, dels = _absorb_case(kind, ix, n, src, dst, et)

    def rows(edges):
        e = np.asarray(edges, np.int32).reshape(-1, 3)
        # the mirror's form: (dst, src, etype) rows, both directions
        return (np.concatenate([e[:, 1], e[:, 0]]),
                np.concatenate([e[:, 0], e[:, 1]]),
                np.concatenate([e[:, 2], -e[:, 2]]))

    claims = []
    plan = E.plan_ell_absorb(ix, *rows(ins), *rows(dels),
                             claims_out=claims)
    assert plan is not None
    nb = len(ix.bucket_nbr)
    assert all(0 <= t < 2 * nb for t in plan)
    ix2 = E.apply_ell_absorb_host(ix, plan, ix.m, claims=claims)
    assert ix2.shape_sig()[:3] == ix.shape_sig()[:3]
    keep = np.ones(len(src), bool)
    for u, v, t in dels:
        keep[np.flatnonzero((src == u) & (dst == v) & (et == t))[0]] = False
    src2 = np.concatenate([src[keep], [e[0] for e in ins]]).astype(np.int32)
    dst2 = np.concatenate([dst[keep], [e[1] for e in ins]]).astype(np.int32)
    et2 = np.concatenate([et[keep], [e[2] for e in ins]]).astype(np.int32)
    for v in range(n):
        r = int(ix2.perm[v])
        assert _slots_of(ix2, ix2.bucket_nbr, ix2.bucket_et, r) == sorted(
            zip(src2[dst2 == v].tolist(), et2[dst2 == v].tolist())), v
        assert _slots_of(ix2, ix2.out_nbr, ix2.out_et, r) == sorted(
            zip(dst2[src2 == v].tolist(), et2[src2 == v].tolist())), v
    if kind == "claim":
        assert claims and all(o == ix.perm[7] for _i, o in claims)
        # the spare is vertex 7's row in BOTH tables, and its
        # out-table row stays empty: only in-edges arrived
        for idx, _o in claims:
            assert (ix2.out_nbr[-1][ix.n + idx - (ix.n_rows
                    - ix.out_nbr[-1].shape[0])] == ix.n_rows).all()
    else:
        assert not claims
    # untouched buckets share memory with the old generation
    for t, (a, b) in enumerate(zip(ix.tables_host(), ix2.tables_host())):
        assert (a[0] is b[0]) == (t not in plan)
    # the device scatter gives the same tables as the host apply
    counts, upd = E.absorb_update_arrays(ix, plan)
    outs = E.make_ell_absorb_kernel(ix, counts)(
        *[jnp.asarray(u[0]) for u in upd],
        *[jnp.asarray(u[1]) for u in upd],
        *[jnp.asarray(u[2]) for u in upd], *ix.kernel_args()[1:])
    for got, w in zip(outs, ix2.bucket_nbr + ix2.bucket_et
                      + ix2.out_nbr + ix2.out_et):
        assert got.dtype == w.dtype and np.array_equal(np.asarray(got), w)
    f0 = ix2.start_frontier([np.asarray([5]), np.asarray([0, 2, 39]),
                             np.asarray([4, 7])], B=8)
    for etypes in ((1,), (-1, -2), (2, -1)):
        want = ix2.to_old(f0).astype(bool)
        for _ in range(2):
            want = _walk_step(n, src2, dst2, et2, etypes, want)
        assert np.array_equal(ix2.to_old(run_go(ix2, 3, etypes, f0)),
                              want), (kind, etypes)


def test_absorb_declines_an_etype_the_column_cannot_hold():
    ix, _ = _split_ell(growth_slack=2)
    assert ix.et_dtype == np.int8
    one = lambda *v: np.asarray(v, np.int32)          # noqa: E731
    none = np.zeros(0, np.int32)
    assert E.plan_ell_absorb(ix, one(4), one(5), one(200),
                             none, none, none) is None


# ============================================================
# PR 39: a bucket's rows stand in descending order of in-degree, the
# index carries per table, bucket and column range how many leading
# main rows hold a real slot there (EllIndex.reach), and a pull's loop
# over a column range gathers that prefix only.  A slot it skips names
# the pad row, which is zero: the pull with the reach is the pull
# without it, bit for bit, on every row a reader looks at.
# ============================================================
@pytest.fixture
def fine_reach(monkeypatch):
    """Set the module's ranges and step for one test: the shipped step
    of 1,024 rows makes every bucket of a test graph sweep whole."""
    def set_(ranges, step=4):
        monkeypatch.setattr(E, "PULL_COLUMN_RANGES", ranges)
        monkeypatch.setattr(E, "PULL_REACH_STEP", step)
    return set_


def _random_mirror(seed, n=400, m=6000):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, m).astype(np.int32)
    dst = (rng.integers(0, n, m) * rng.random(m) ** 2).astype(np.int32)
    et = rng.choice(np.asarray([1, 2], np.int32), m)
    return n, _mirror_edges(src, dst, et)


def _skewed_mirror(seed=11):
    _ix, edges = _hop_graph(seed, with_edges=True)
    return 300, edges


REACH_GRAPHS = {"random_a": lambda: _random_mirror(3, m=1800),
                "random_b": lambda: _random_mirror(4, n=150, m=900),
                "skewed": _skewed_mirror}


def _brute_reach(nbr, n_main, sentinel, bounds):
    """Leading main rows with a real slot at a column >= c, per range
    start c, one row at a time; 0 where there is none."""
    out = []
    for c in bounds[:-1]:
        reach = 0
        for r in range(n_main):
            if (nbr[r, c:] != sentinel).any():
                reach = r + 1
        out.append(reach)
    return out


@pytest.mark.parametrize("slack", [0, 3], ids=["no_spares", "spares"])
@pytest.mark.parametrize("ranges", [1, 4, 8])
@pytest.mark.parametrize("native", [False, True], ids=["numpy", "native"])
@pytest.mark.parametrize("graph", sorted(REACH_GRAPHS))
def test_every_slot_outside_a_reach_prefix_is_padding(graph, native, ranges,
                                                      slack, fine_reach):
    fine_reach(ranges, step=4)
    n, edges = REACH_GRAPHS[graph]()
    ix = E.EllIndex.build(*edges, n, cap=16, min_d=2, use_native=native,
                          growth_slack=slack)
    assert len(ix.bucket_nbr) >= 3 and len(ix.extra_owner) > slack
    mains = E._main_rows(ix.n, ix.bucket_nbr)
    assert sum(mains) == ix.n and mains[-1] < ix.bucket_nbr[-1].shape[0]
    # inside a bucket the rows stand in descending order of in-degree,
    # ties by old id
    in_deg = np.bincount(edges[1][edges[2] > 0], minlength=n)[ix.inv]
    b0 = 0
    for n_main in mains:
        d = in_deg[b0:b0 + n_main]
        assert (np.diff(d) <= 0).all()
        same = np.flatnonzero(np.diff(d) == 0)
        assert (ix.inv[b0 + same] < ix.inv[b0 + same + 1]).all()
        b0 += n_main
    assert len(ix.reach) == 2
    saved = 0
    for nbrs, reach in zip((ix.bucket_nbr, ix.out_nbr), ix.reach):
        assert len(reach) == len(nbrs)
        for nbr, n_main, rb in zip(nbrs, mains, reach):
            D = nbr.shape[1]
            bounds = E._range_bounds(D, ranges)
            assert len(rb) == len(bounds) - 1 == min(ranges, D)
            assert list(rb) == sorted(rb, reverse=True)
            want = _brute_reach(nbr, n_main, ix.n_rows, bounds)
            for c, r, w in zip(bounds, rb, want):
                assert (nbr[r:n_main, c:] == ix.n_rows).all()
                # the least whole steps that hold it, one at the least
                assert r == min(max(-(-w // 4), 1) * 4, n_main)
            saved += sum(n_main - r for r in rb)
    assert ranges == 1 or saved > 0
    # read off the slots anew, the field is what the build left
    assert E.pull_reach(ix) == ix.reach


class _EagerLax:
    """lax for a loop run in Python, one turn at a time."""

    @staticmethod
    def fori_loop(lo, hi, body, acc):
        for j in range(lo, hi):
            acc = body(j, acc)
        return acc

    @staticmethod
    def dynamic_slice(a, start, size):
        return a[start[0]:start[0] + size[0], start[1]:start[1] + size[1]]


class _EagerJax:
    lax = _EagerLax

    @staticmethod
    def named_scope(_name):
        import contextlib
        return contextlib.nullcontext()


class _CountedFrontier:
    """A frontier that counts the rows gathered from it."""

    def __init__(self, fp):
        self.fp, self.shape, self.gathered = fp, fp.shape, 0

    def __getitem__(self, idx):
        self.gathered += len(idx)
        return self.fp[idx]


def _reach_variants(ix, ranges):
    """The index's own reach and one with the zeros a rounded reach
    never holds: the exact prefixes, 0 for a range nothing reaches."""
    mains = E._main_rows(ix.n, ix.bucket_nbr)
    exact = tuple(
        tuple(tuple(_brute_reach(nbr, n_main, ix.n_rows,
                                 E._range_bounds(nbr.shape[1], ranges)))
              for nbr, n_main in zip(nbrs, mains))
        for nbrs in (ix.bucket_nbr, ix.out_nbr))
    return {"rounded": ix.reach, "exact": exact}


REACH_OVER = {"over_a": (1,), "over_a_b": (1, 2), "reversely": (-1,),
              "reversely_both": (-1, -2), "mixed_sign": (2, -1)}


@pytest.mark.parametrize("lanes", [8, 128])
@pytest.mark.parametrize("ranges", [1, 4, 8])
@pytest.mark.parametrize("over", sorted(REACH_OVER))
def test_pull_with_the_reach_is_the_pull_without(over, ranges, lanes,
                                                 fine_reach):
    """Rows < n and the pad row of a pull cut by the reach equal the
    whole sweep's and the push's; swept_slots is the gathers its loops
    make, counted one turn at a time."""
    fine_reach(ranges, step=4)
    etypes = REACH_OVER[over]
    ix = _hop_graph(11)
    W = E.lanes_width(lanes)
    rng = np.random.default_rng(ranges * 131 + lanes)
    fp, _rows = _hop_frontier(ix, rng, 120, W)
    accp = np.zeros_like(fp)
    sel = np.r_[0:ix.n, ix.n_rows]
    whole, _ = _pull_reference(ix, etypes, fp, accp)
    pushed, _acc, info = _run_hop(ix, etypes, fp, accp, 1 << 20)
    assert info[E.HOP_INFO_SPARSE] == 1
    assert np.array_equal(pushed[sel], whole[sel])
    nb = len(ix.bucket_nbr)
    tables = ix.kernel_args()[1:]
    eslot, hrows = ix.hub_merge()
    for name, reach in _reach_variants(ix, ranges).items():
        ix.reach = reach
        # the rows stand in order of IN-degree: the in-table's padding
        # is what the reach finds, the out-table's as it happens
        assert E.swept_slots(ix, etypes) <= E.table_slots(ix, etypes)
        assert E.swept_slots(ix, etypes) < E.table_slots(ix, etypes) \
            or max(etypes) < 0
        cut = np.asarray(E._hop_body_packed(
            jnp, jax, ix.n, len(ix.extra_owner),
            E._read_sides(etypes, tables, nb), jnp.asarray(eslot),
            jnp.asarray(hrows), jnp.asarray(fp),
            E._side_reaches(ix, etypes)))
        assert np.array_equal(cut[sel], whole[sel]), name
        # the program a stream runs: the pull branch of the hop, which
        # reports the TABLE's slots whatever it gathers
        nxt, _acc, info = _run_hop(ix, etypes, fp, accp, 8)
        assert info[E.HOP_INFO_SPARSE] == 0
        assert info[E.HOP_INFO_SLOTS] == E.table_slots(ix, etypes)
        assert np.array_equal(nxt[sel], whole[sel]), name
        # the same loops in numpy, a turn at a time, over a frontier
        # that counts what is gathered from it
        counted = _CountedFrontier(fp)
        outs = E._buckets_expand_packed(
            np, _EagerJax, counted,
            E._read_sides(etypes, (*ix.bucket_nbr, *ix.bucket_et,
                                   *ix.out_nbr, *ix.out_et), nb), ix.n,
            E._side_reaches(ix, etypes))
        assert counted.gathered == E.swept_slots(ix, etypes), name
        # (before the hub merge: a hub's own row lacks its extra rows)
        plain = np.flatnonzero(ix.hub_expansion()[0][:ix.n] == 0)
        assert np.array_equal(np.concatenate(outs)[plain], whole[plain])
    ix.reach = None
    assert E.swept_slots(ix, etypes) == E.table_slots(ix, etypes)


def test_shape_sig_differs_where_the_reach_does(fine_reach):
    n, edges = _skewed_mirror()
    sigs = {}
    for ranges in (4, 8):
        fine_reach(ranges, step=4)
        ix = E.EllIndex.build(*edges, n, cap=16, min_d=2)
        sigs[ranges] = ix.shape_sig()
        assert ix.reach in sigs[ranges]
    # the same tables, another cut of their columns: other programs
    assert sigs[4] != sigs[8]
    assert [s for s in sigs[4] if s != ix.reach][:5] \
        == [s for s in sigs[8] if s != ix.reach][:5]
    same = E.EllIndex.build(*edges, n, cap=16, min_d=2)
    assert same.shape_sig() == sigs[8]
    same.reach = None
    assert same.shape_sig() != sigs[8]


@pytest.mark.parametrize("ranges", [4, 8])
def test_windowed_go_and_bfs_pull_by_the_reach(ranges, fine_reach):
    """Every program that pulls takes the index's reach: the windowed
    GO and the BFS whose levels all pull give what they give over an
    index that carries none."""
    fine_reach(ranges, step=4)
    ix = _hop_graph(11)
    assert E.swept_slots(ix, (1,)) < E.table_slots(ix, (1,))
    rng = np.random.default_rng(ranges)
    starts = [rng.choice(ix.n, 3, replace=False) for _ in range(8)]
    f0 = ix.start_frontier(starts, B=8)
    t0 = ix.start_frontier([rng.choice(ix.n, 2) for _ in range(8)], B=8)
    reach = ix.reach

    def answers():
        go = [run_go(ix, 3, et, f0, upto=upto)[:ix.n]
              for et in ((1, 2), (-1,), (2, -1)) for upto in (False, True)]
        d, info = run_bfs_info(ix, 6, (1, 2), f0, t0, push_rows=1)
        assert info[E.BFS_INFO_PUSHED] == 0 < info[E.BFS_INFO_LEVELS]
        return go + [d[:ix.n]], info

    cut, info = answers()
    assert E.bfs_swept(ix, (1, 2), info) < E.bfs_slots(ix, (1, 2), info)
    ix.reach = None
    whole, info = answers()
    assert E.bfs_swept(ix, (1, 2), info) == E.bfs_slots(ix, (1, 2), info)
    ix.reach = reach
    for a, b in zip(cut, whole):
        assert np.array_equal(a, b)
