"""TPU traversal backend tests.

Two tiers, mirroring SURVEY.md §4's pyramid:
  * kernel/CSR units — build_mirror over a hand-rolled store, jitted GO /
    BFS kernels on a known graph, sharded (8-virtual-device) GO kernel
    equivalence against the single-device kernel;
  * end-to-end parity — the SAME nGQL queries against two LocalClusters
    (CPU backend vs TPU backend) must return identical row sets, and the
    TPU cluster's runtime stats must prove the device path actually ran.
"""
import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.tpu import kernels

TIM, TONY, MANU, LEBRON, KYRIE = 100, 101, 102, 103, 104
SPURS, CAVS = 200, 201

FIXTURE = [
    "CREATE TAG player(name string, age int)",
    "CREATE TAG team(name string)",
    "CREATE EDGE follow(degree int)",
    "CREATE EDGE serve(start_year int, end_year int)",
]
DATA = [
    'INSERT VERTEX player(name, age) VALUES '
    f'{TIM}:("Tim Duncan", 42), {TONY}:("Tony Parker", 36), '
    f'{MANU}:("Manu Ginobili", 41), {LEBRON}:("LeBron James", 34), '
    f'{KYRIE}:("Kyrie Irving", 26)',
    f'INSERT VERTEX team(name) VALUES {SPURS}:("Spurs"), {CAVS}:("Cavaliers")',
    'INSERT EDGE follow(degree) VALUES '
    f'{TIM} -> {TONY}:(95), {TIM} -> {MANU}:(95), '
    f'{TONY} -> {TIM}:(95), {TONY} -> {MANU}:(90), '
    f'{MANU} -> {TIM}:(90), {LEBRON} -> {KYRIE}:(80), '
    f'{KYRIE} -> {LEBRON}:(85)',
    'INSERT EDGE serve(start_year, end_year) VALUES '
    f'{TIM} -> {SPURS}:(1997, 2016), {TONY} -> {SPURS}:(1999, 2018), '
    f'{MANU} -> {SPURS}:(2002, 2018), {LEBRON} -> {CAVS}:(2003, 2010), '
    f'{KYRIE} -> {CAVS}:(2011, 2017)',
]


def _boot(tpu_backend: bool):
    c = LocalCluster(num_storage=1, tpu_backend=tpu_backend)
    client = c.client()

    def ok(stmt):
        resp = client.execute(stmt)
        assert resp.ok(), f"{stmt}: {resp.error_msg}"
        return resp

    client.ok = ok
    ok("CREATE SPACE nba(partition_num=6, replica_factor=1)")
    c.refresh_all()
    ok("USE nba")
    for stmt in FIXTURE:
        ok(stmt)
    c.refresh_all()
    for stmt in DATA:
        ok(stmt)
    return c, client


@pytest.fixture(scope="module")
def clusters():
    cpu_c, cpu = _boot(tpu_backend=False)
    tpu_c, tpu = _boot(tpu_backend=True)
    yield cpu_c, cpu, tpu_c, tpu
    cpu.disconnect()
    tpu.disconnect()
    cpu_c.stop()
    tpu_c.stop()


PARITY_QUERIES = [
    f"GO FROM {TIM} OVER follow",
    f"GO FROM {TIM} OVER follow YIELD follow._dst AS id, follow.degree AS d,"
    f" $^.player.name AS me",
    f"GO FROM {TIM} OVER follow YIELD $$.player.name AS n, $$.player.age AS a",
    f"GO 2 STEPS FROM {TIM} OVER follow",
    f"GO 3 STEPS FROM {TIM} OVER follow",
    f"GO FROM {TONY} OVER follow WHERE follow.degree > 92 YIELD follow._dst",
    # numeric (non-bool) WHERE: nonzero = truthy, and the host-filter
    # mask must be bool before it fancy-indexes candidate edges
    f"GO 2 STEPS FROM {TIM} OVER follow WHERE follow.degree "
    f"YIELD follow._dst",
    f"GO FROM {TIM},{TONY} OVER follow WHERE $^.player.age > 40 "
    f"YIELD follow._dst",
    f"GO FROM {TIM} OVER follow WHERE $$.player.age > 40 YIELD follow._dst",
    f"GO FROM {MANU} OVER follow REVERSELY",
    f"GO FROM {TIM} OVER follow, serve",
    f"GO FROM {TIM} OVER follow, serve YIELD follow._dst AS d",
    f"GO FROM {TIM} OVER follow YIELD follow._dst, follow._src, "
    f"follow._rank, follow._type",
    f"GO 2 STEPS FROM {TIM} OVER follow YIELD follow._dst AS id, "
    f"follow.degree AS deg",
    f"GO FROM {TIM} OVER follow WHERE follow.degree > 90 && "
    f"$$.player.age > 40 YIELD follow._dst, follow.degree",
    f"GO FROM {TIM} OVER follow YIELD follow._dst AS id | "
    f"GO FROM $-.id OVER follow",
    f"GO FROM {TONY} OVER follow YIELD DISTINCT follow._dst",
    f"GO FROM {TIM} OVER follow WHERE $$.player.name == \"Tony Parker\" "
    f"YIELD follow._dst, $$.player.name",
    f"GO FROM {TIM} OVER follow WHERE follow._dst == {TONY} "
    f"YIELD follow._dst",
    f"GO FROM {TIM} OVER follow YIELD follow.degree + 1 AS dd",
    f"GO FROM {TIM} OVER follow YIELD $^.player.age / 2 AS h",
    # UPTO rides the cumulative-frontier kernel variants on device
    f"GO UPTO 2 STEPS FROM {TIM} OVER follow",
    f"GO UPTO 3 STEPS FROM {TIM} OVER follow YIELD follow._dst, "
    f"follow.degree",
    f"GO UPTO 2 STEPS FROM {TIM} OVER follow WHERE follow.degree > 90 "
    f"YIELD follow._dst",
    f"FIND SHORTEST PATH FROM {TIM} TO {MANU} OVER follow",
    f"FIND SHORTEST PATH FROM {LEBRON} TO {CAVS} OVER * UPTO 3 STEPS",
    f"FIND SHORTEST PATH FROM {TIM} TO {CAVS} OVER follow",
    f"FIND ALL PATH FROM {TONY} TO {MANU} OVER follow UPTO 2 STEPS",
    f"FIND SHORTEST PATH FROM {TONY} TO {TIM},{SPURS} OVER * UPTO 3 STEPS",
]


class TestParity:
    @pytest.mark.parametrize("query", PARITY_QUERIES)
    def test_same_rows(self, clusters, query):
        _, cpu, _, tpu = clusters
        r_cpu = cpu.execute(query)
        r_tpu = tpu.execute(query)
        assert r_cpu.ok() and r_tpu.ok(), \
            f"{query}: cpu={r_cpu.error_msg} tpu={r_tpu.error_msg}"
        assert r_cpu.column_names == r_tpu.column_names
        assert sorted(map(tuple, r_cpu.rows)) == \
            sorted(map(tuple, r_tpu.rows)), query

    def test_device_path_actually_ran(self, clusters):
        _, _, tpu_c, tpu = clusters
        rt = tpu_c.tpu_runtime
        assert rt is not None
        before = rt.stats["go_device"]
        tpu.execute(f"GO FROM {TIM} OVER follow")
        assert rt.stats["go_device"] == before + 1
        before_p = rt.stats["path_device"]
        tpu.execute(f"FIND SHORTEST PATH FROM {TIM} TO {MANU} OVER follow")
        assert rt.stats["path_device"] == before_p + 1

    def test_error_parity_missing_prop(self, clusters):
        # yielding a prop of a tag the dst doesn't carry errors both ways
        _, cpu, _, tpu = clusters
        q = f"GO FROM {TIM} OVER serve YIELD $$.player.name"
        r_cpu = cpu.execute(q)
        r_tpu = tpu.execute(q)
        assert not r_cpu.ok() and not r_tpu.ok()

    def test_div_zero_pushed_filter_parity(self, clusters):
        # a zero-degree edge: CPU pushed filter drops the row on the
        # ExprError; the device guard must drop it too — not emit inf>1
        _, cpu, _, tpu = clusters
        cpu.ok(f'INSERT EDGE follow(degree) VALUES {MANU} -> {TONY}:(0)')
        tpu.ok(f'INSERT EDGE follow(degree) VALUES {MANU} -> {TONY}:(0)')
        q = (f"GO FROM {MANU} OVER follow WHERE 10 / follow.degree >= 0 "
             f"YIELD follow._dst")
        r_cpu, r_tpu = cpu.execute(q), tpu.execute(q)
        assert r_cpu.ok() and r_tpu.ok()
        # 10/90 == 0 (C-style int division) passes >= 0; the degree-0 edge
        # errors on the CPU path and must be guard-dropped on device
        assert sorted(map(tuple, r_cpu.rows)) == \
            sorted(map(tuple, r_tpu.rows)) == [(TIM,)]
        cpu.ok(f"DELETE EDGE follow {MANU} -> {TONY}")
        tpu.ok(f"DELETE EDGE follow {MANU} -> {TONY}")

    def test_ttl_expired_edges_dropped(self):
        # expired rows are skipped by the CPU read path; the mirror must
        # drop them too.  The clock is INJECTED (clock.advance_for_tests)
        # — racing a 1-second TTL against a busy box made this flake
        # (VERDICT round-2 weak #6)
        import time as _t
        from nebula_tpu.common import clock
        c, client = _boot(tpu_backend=True)
        try:
            client.ok("CREATE EDGE seen(ts timestamp) "
                      "ttl_duration = 3600, ttl_col = ts")
            c.refresh_all()
            now = int(_t.time())
            client.ok(f'INSERT EDGE seen(ts) VALUES {TIM} -> {TONY}:({now}),'
                      f' {TIM} -> {MANU}:({now - 7200})')
            r = client.ok(f"GO FROM {TIM} OVER seen")
            assert sorted(map(tuple, r.rows)) == [(TONY,)], r.rows
        finally:
            clock.reset_for_tests()
            c.stop()

    def test_ttl_expiry_boundary_parity(self):
        """Edges aging out BETWEEN queries must disappear from the
        device path in lockstep with the CPU path — the mirror records
        the earliest future expiry and rebuilds once it passes
        (expired_now), so a snapshot never outlives its rows."""
        import time as _t
        from nebula_tpu.common import clock
        from nebula_tpu.common.flags import flags
        c, client = _boot(tpu_backend=True)
        try:
            client.ok("CREATE EDGE lease(ts timestamp) "
                      "ttl_duration = 3600, ttl_col = ts")
            c.refresh_all()
            now = int(_t.time())
            # expiries now+1800 and now+5400
            client.ok(f'INSERT EDGE lease(ts) VALUES '
                      f'{TIM} -> {TONY}:({now - 1800}), '
                      f'{TIM} -> {MANU}:({now + 1800})')

            def both_paths(q):
                r1 = client.ok(q)
                flags.set("storage_backend", "cpu")
                try:
                    r2 = client.ok(q)
                finally:
                    flags.set("storage_backend", "tpu")
                a = sorted(map(tuple, r1.rows))
                assert a == sorted(map(tuple, r2.rows))
                return a

            q = f"GO FROM {TIM} OVER lease"
            assert both_paths(q) == [(TONY,), (MANU,)]
            clock.advance_for_tests(3600)      # past the first expiry
            assert both_paths(q) == [(MANU,)]
            clock.advance_for_tests(3600)      # past the second
            assert both_paths(q) == []
        finally:
            clock.reset_for_tests()
            c.stop()

    def test_mutation_invalidates_mirror(self, clusters):
        _, _, tpu_c, tpu = clusters
        rt = tpu_c.tpu_runtime
        r = tpu.ok(f"GO FROM {KYRIE} OVER follow")
        assert sorted(map(tuple, r.rows)) == [(LEBRON,)]
        tpu.ok(f'INSERT EDGE follow(degree) VALUES {KYRIE} -> {TIM}:(70)')
        r = tpu.ok(f"GO FROM {KYRIE} OVER follow")
        assert sorted(map(tuple, r.rows)) == [(TIM,), (LEBRON,)]
        # cleanup for other tests
        tpu.ok(f"DELETE EDGE follow {KYRIE} -> {TIM}")


class TestGenerativeWhereDifferential:
    """Generative CPU-vs-device WHERE differential (VERDICT r5 ask #5):
    seeded-random predicates composed from atoms covering
    int/float/string columns, src/dst vertex props MISSING on some
    vertices, TTL-expired rows, modulo and division with a zero divisor
    present — executed under every value tpu_filter_mode accepts (all
    of them the host's float64 pass at assembly) and compared against
    the CPU backend: same rows, or the same error."""

    ATOMS = [
        "rel.i > {a}",
        "rel.i % 3 == {b}",
        "rel.f * 2.0 < {c}",
        "rel.f + rel.i >= {a}",
        'rel.s == "s{b}"',
        "10 / rel.i >= {b}",          # zero divisor present in data
        "rel._rank >= 0",
        "$^.player.age > {d}",
        "$$.player.age < {d}",        # missing on tagless vertices
        "rel.i",                      # numeric truthiness
    ]

    @pytest.fixture(scope="class")
    def gen_cluster(self):
        c, client = _boot(tpu_backend=True)
        client.ok("CREATE EDGE rel(i int, f double, s string)")
        client.ok("CREATE EDGE seen(ts timestamp, v int) "
                  "ttl_duration = 3600, ttl_col = ts")
        c.refresh_all()
        rng = np.random.default_rng(42)
        # vertices 1..30; players tagged only on 1..20 (dst-prop reads
        # on 21..30 are MISSING → skip in pushed mode, raise in graphd
        # mode — both paths must agree either way)
        players = ", ".join(f'{v}:("p{v}", {18 + v})'
                            for v in range(1, 21))
        client.ok(f"INSERT VERTEX player(name, age) VALUES {players}")
        edges = ", ".join(
            f"{int(s)} -> {int(d)}:"
            f"({int(i)}, {float(f):.3f}, \"s{int(i) % 4}\")"
            for s, d, i, f in zip(
                rng.integers(1, 31, 200), rng.integers(1, 31, 200),
                rng.integers(-2, 6, 200),       # zeros present
                rng.normal(0, 3, 200)))
        client.ok(f"INSERT EDGE rel(i, f, s) VALUES {edges}")
        import time as _t
        now = int(_t.time())
        seen = ", ".join(
            f"{int(s)} -> {int(d)}:"
            f"({now - (7200 if k % 3 == 0 else 0)}, {k})"
            for k, (s, d) in enumerate(zip(rng.integers(1, 31, 60),
                                           rng.integers(1, 31, 60))))
        client.ok(f"INSERT EDGE seen(ts, v) VALUES {seen}")
        yield c, client
        from nebula_tpu.common import clock
        clock.reset_for_tests()
        c.stop()

    def _queries(self):
        rng = np.random.default_rng(7)
        out = []
        for i in range(36):
            n = rng.integers(1, 4)
            atoms = [self.ATOMS[int(k)]
                     for k in rng.choice(len(self.ATOMS), n,
                                         replace=False)]
            op = " && " if rng.random() < 0.6 else " || "
            pred = op.join(
                a.format(a=int(rng.integers(-2, 5)),
                         b=int(rng.integers(0, 4)),
                         c=round(float(rng.normal(0, 4)), 2),
                         d=int(rng.integers(18, 50)))
                for a in atoms)
            steps = int(rng.integers(1, 4))
            start = ",".join(str(int(v))
                             for v in rng.integers(1, 31,
                                                   rng.integers(1, 4)))
            out.append(f"GO {steps} STEPS FROM {start} OVER rel "
                       f"WHERE {pred} YIELD rel._dst, rel.i, rel.f")
        # TTL leg: expired rows must be invisible to every mode
        for v in (1, 5, 9):
            out.append(f"GO FROM {v} OVER seen WHERE seen.v >= 0 "
                       f"YIELD seen._dst, seen.v")
        return out

    def test_not_over_conjunction_short_circuit(self, gen_cluster):
        """`!(a && missing)` keeps the row on the CPU path when a is
        false (the && short-circuits, ! flips it) — the validity mask
        can't reproduce that, so _filter_has_or must flag NOT over a
        logical subtree and the row must decline to the per-row path
        (review finding: pure-`&&` detection missed the `!` wrapper)."""
        from nebula_tpu.common.flags import flags
        _c, client = gen_cluster
        qs = [
            # dst prop missing on vertices 21..30 (graphd raise-mode)
            "GO 2 STEPS FROM 3 OVER rel WHERE "
            "!(rel.i > 99 && $$.player.age > 0) YIELD rel._dst, rel.i",
            # src prop missing (pushed skip-mode)
            "GO 2 STEPS FROM 3 OVER rel WHERE "
            "!(rel.i > 99 && $^.player.age > 0) YIELD rel._dst, rel.i",
        ]
        for q in qs:
            flags.set("storage_backend", "cpu")
            r = client.execute(q)
            want = ("error",) if not r.ok() else \
                tuple(sorted(map(tuple, r.rows)))
            flags.set("storage_backend", "tpu")
            for mode in ("host", "device", "auto"):
                flags.set("tpu_filter_mode", mode)
                try:
                    r2 = client.execute(q)
                finally:
                    flags.set("tpu_filter_mode", "auto")
                got = ("error",) if not r2.ok() else \
                    tuple(sorted(map(tuple, r2.rows)))
                assert got == want, (mode, q, want, got)

    def test_all_filter_modes_match_cpu(self, gen_cluster):
        from nebula_tpu.common.flags import flags
        _c, client = gen_cluster

        def run(q):
            r = client.execute(q)
            if not r.ok():
                return ("error",)
            return tuple(sorted(map(tuple, r.rows)))

        mismatches = []
        for q in self._queries():
            flags.set("storage_backend", "cpu")
            want = run(q)
            flags.set("storage_backend", "tpu")
            for mode in ("host", "device", "auto"):
                flags.set("tpu_filter_mode", mode)
                try:
                    got = run(q)
                finally:
                    flags.set("tpu_filter_mode", "auto")
                if got != want:
                    mismatches.append((mode, q, want, got))
        assert not mismatches, mismatches[:3]


class TestKernels:
    """Direct kernel units on a known small graph.

    Graph (dense ids): 0->1, 0->2, 1->3, 2->3, 3->4 all etype 1.
    """

    def _arrays(self):
        import jax.numpy as jnp
        es = jnp.asarray(np.array([0, 0, 1, 2, 3], dtype=np.int32))
        ed = jnp.asarray(np.array([1, 2, 3, 3, 4], dtype=np.int32))
        ee = jnp.asarray(np.ones(5, dtype=np.int32))
        return es, ed, ee

    def test_go_one_hop(self):
        import jax.numpy as jnp
        es, ed, ee = self._arrays()
        kern = kernels.make_go_kernel(5, 1, (1,))
        mask, frontier = kern(es, ed, ee,
                              jnp.asarray(np.array([0, -1], dtype=np.int32)))
        assert np.asarray(mask).tolist() == [True, True, False, False, False]

    def test_go_two_hops(self):
        import jax.numpy as jnp
        es, ed, ee = self._arrays()
        kern = kernels.make_go_kernel(5, 2, (1,))
        mask, frontier = kern(es, ed, ee,
                              jnp.asarray(np.array([0, -1], dtype=np.int32)))
        # hop1 frontier {1,2}; final edges: 1->3, 2->3
        assert np.asarray(mask).tolist() == [False, False, True, True, False]
        assert np.asarray(frontier).tolist() == [False, True, True, False,
                                                 False]


class TestFrontierEdges:
    """_frontier_edges_multi (CSR row-slice final-hop candidate assembly) must
    equal the flat frontier[edge_src] gather in both density regimes —
    it replaces round 1's per-query O(m) host pass."""

    def _mirror(self, n, m, seed=0):
        from nebula_tpu.tpu.csr import CsrMirror
        rng = np.random.default_rng(seed)
        mir = CsrMirror(1)
        mir.n = n
        mir.m = m
        mir.vids = np.arange(n, dtype=np.int64)
        mir.edge_src = np.sort(rng.integers(0, n, m).astype(np.int32))
        mir.edge_dst = rng.integers(0, n, m).astype(np.int32)
        mir.edge_etype = rng.choice([1, 2], m).astype(np.int32)
        counts = np.bincount(mir.edge_src, minlength=n)
        mir.row_ptr = np.concatenate([[0], np.cumsum(counts)]).astype(
            np.int32)
        return mir

    @pytest.mark.parametrize("density", [0.0, 0.002, 0.05, 0.6, 1.0])
    @pytest.mark.parametrize("et_tuple", [(1,), (1, 2)])
    def test_matches_flat_gather(self, density, et_tuple):
        from nebula_tpu.tpu.runtime import TpuQueryRuntime
        n, m = 4096, 32768
        mir = self._mirror(n, m)
        rng = np.random.default_rng(1)
        frontier = np.zeros(n, dtype=bool)
        k = int(n * density)
        if k:
            frontier[rng.choice(n, k, replace=False)] = True
        flat = np.nonzero(
            frontier[mir.edge_src]
            & np.isin(mir.edge_etype, np.asarray(et_tuple, np.int32)))[0]
        got, _, _ = TpuQueryRuntime._frontier_edges_multi(
            TpuQueryRuntime.__new__(TpuQueryRuntime), mir,
            [np.nonzero(frontier)[0]], et_tuple)
        assert np.array_equal(got, flat)


    @pytest.mark.parametrize("as_runs", [False, True])
    @pytest.mark.parametrize("et_tuple,one_run", [
        ((1,), True), ((-1, 1), True), ((-1, 2), False), ((2,), True)])
    def test_over_runs_match_the_masked_walk(self, et_tuple, one_run,
                                             as_runs):
        """A mirror in (src, etype) order, as the real one is: an OVER
        set whose edges are one run a vertex is walked run by run
        (_over_ranges; handed over as _EdgeRuns where asked), any other
        row by row under the mask, and both give the flat gather's
        edges, per query."""
        from nebula_tpu.tpu.runtime import TpuQueryRuntime, _EdgeRuns
        n, m = 512, 6000
        mir = self._mirror(n, m, seed=3)
        rng = np.random.default_rng(4)
        mir.edge_etype = rng.choice([-1, 1, 2], m).astype(np.int32)
        order = np.lexsort((mir.edge_etype, mir.edge_src))
        mir.edge_etype = mir.edge_etype[order]
        rt = TpuQueryRuntime.__new__(TpuQueryRuntime)
        assert (rt._over_ranges(mir, et_tuple) is not None) == one_run
        vs_lists = [np.sort(rng.choice(n, k, replace=False))
                    for k in (0, 7, 1, 40, 0)]
        cand, qseg, qb = rt._frontier_edges_multi(mir, vs_lists, et_tuple,
                                                  as_runs=as_runs)
        assert isinstance(cand, _EdgeRuns) == (as_runs and one_run)
        assert len(qb) == len(vs_lists) + 1 and qb[-1] == len(cand)
        if isinstance(cand, _EdgeRuns):
            assert qseg is None
            idx = cand.index()
            # the runs' copy of an edge-aligned array is the gather
            # through their index, for every width the mirror holds
            for arr in (mir.edge_dst, mir.edge_etype.astype(np.int64),
                        mir.edge_etype > 0,
                        mir.edge_dst.astype(np.float64) / 7):
                assert np.array_equal(cand.take(arr), arr[idx])
            at = np.flatnonzero(mir.edge_dst[idx] % 3 == 0)
            assert np.array_equal(cand.rows(at), idx[at])
            # cut into pieces, the runs are the same runs in order
            for limit in (1, 7, 50, 10 ** 6):
                pieces = list(cand.pieces(limit))
                assert np.array_equal(np.concatenate(
                    [p.index() for p in pieces]), idx)
                assert all(len(p) - int(p.cnt[-1]) < limit
                           for p in pieces)
        else:
            idx = cand
        in_set = np.isin(mir.edge_etype, np.asarray(et_tuple, np.int32))
        for q, vs in enumerate(vs_lists):
            frontier = np.zeros(n, dtype=bool)
            frontier[vs] = True
            flat = np.nonzero(frontier[mir.edge_src] & in_set)[0]
            assert np.array_equal(idx[qb[q]:qb[q + 1]], flat)
            if qseg is not None:
                assert (qseg[qb[q]:qb[q + 1]] == q).all()

    def test_edge_runs_without_the_native_library(self, monkeypatch):
        """``take`` falls back to numpy's gather through the index."""
        import nebula_tpu.native as native
        from nebula_tpu.tpu.runtime import _EdgeRuns
        monkeypatch.setattr(native, "lib", lambda: None)
        runs = _EdgeRuns(np.asarray([5, 0, 9]), np.asarray([2, 3, 1]))
        arr = np.arange(10, dtype=np.float64) * 1.5
        assert np.array_equal(runs.take(arr), arr[[5, 6, 0, 1, 2, 9]])
        assert len(runs) == 6

    @pytest.mark.parametrize("op", ["<", "<=", ">", ">=", "==", "!="])
    def test_one_native_pass_keeps_what_the_numpy_pass_keeps(self, op):
        """``keep_f64`` (one double column against a constant, over
        runs) gives the positions numpy's float64 comparison ANDed
        with the validity gives: NaNs, invalid rows, empty runs, runs
        longer than the pass's block, equality on a stored value."""
        import operator
        from nebula_tpu.native import lib
        from nebula_tpu.tpu.runtime import _EdgeRuns
        if lib() is None or not hasattr(lib(), "neb_filter_runs_f64"):
            pytest.skip("no native library")
        rng = np.random.default_rng(32)
        m = 200_000
        values = rng.random(m)
        values[rng.integers(0, m, 500)] = np.nan
        valid = rng.random(m) > 0.05
        lo = np.sort(rng.integers(0, m - 5000, 300))
        cnt = rng.integers(0, 40, 300)
        cnt[::50] = 4321                # longer than a block of 1,024
        runs = _EdgeRuns(lo, cnt)
        idx = runs.index()
        c = float(values[idx[11]]) if op in ("==", "<=", ">=") else 0.9
        fn = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
              ">=": operator.ge, "==": operator.eq, "!=": operator.ne}[op]
        with np.errstate(invalid="ignore"):
            want = np.flatnonzero(fn(values[idx], c) & valid[idx])
        got = runs.keep_f64(values, valid, op, c)
        assert got.dtype == np.int64 and np.array_equal(got, want)
        assert len(want) > 0
        assert len(_EdgeRuns(lo[:0], cnt[:0]).keep_f64(
            values, valid, op, c)) == 0
        with pytest.raises(IndexError):
            _EdgeRuns(np.asarray([m - 2]), np.asarray([5])).keep_f64(
                values, valid, op, c)

    def test_a_column_against_a_constant_is_tagged_by_the_compiler(self):
        """``CVal.cmp`` names the one shape the native pass evaluates:
        a bare float edge column compared with a numeric literal, the
        column on the left whichever side it was written on."""
        from nebula_tpu.filter.expressions import (
            AliasPropExpr, ArithmeticExpr, LogicalExpr, PrimaryExpr,
            RelationalExpr)
        from nebula_tpu.interface.common import SupportedType
        from nebula_tpu.tpu.expr_compile import ExprCompiler

        class Col:
            dictionary = None

            def __init__(self, stype):
                self.stype = stype

        class Mirror:
            edge_cols = {(7, "w"): Col(SupportedType.DOUBLE),
                         (7, "n"): Col(SupportedType.INT)}

        def compiled(expr):
            return ExprCompiler(Mirror(), 1, None, {"e": (7,)}).compile(expr)

        w, n = AliasPropExpr("e", "w"), AliasPropExpr("e", "n")
        assert compiled(RelationalExpr(">", w, PrimaryExpr(0.5))).cmp \
            == ("e:7:w", ">", 0.5)
        assert compiled(RelationalExpr("<=", PrimaryExpr(2), w)).cmp \
            == ("e:7:w", ">=", 2.0)
        assert compiled(RelationalExpr("!=", PrimaryExpr(0.25), w)).cmp \
            == ("e:7:w", "!=", 0.25)
        # an int column, arithmetic on the column, a conjunction: numpy
        assert compiled(RelationalExpr(">", n, PrimaryExpr(3))).cmp is None
        assert compiled(RelationalExpr(">", ArithmeticExpr(
            "+", w, PrimaryExpr(1.0)), PrimaryExpr(0.5))).cmp is None
        assert compiled(LogicalExpr("&&", RelationalExpr(
            ">", w, PrimaryExpr(0.5)), RelationalExpr(
                "<", w, PrimaryExpr(0.9)))).cmp is None

    def test_edge_runs_refuse_a_run_outside_the_array(self):
        from nebula_tpu.native import lib
        from nebula_tpu.tpu.runtime import _EdgeRuns
        runs = _EdgeRuns(np.asarray([2, 8]), np.asarray([3, 4]))
        arr = np.arange(10, dtype=np.int64)
        if lib() is not None and hasattr(lib(), "neb_gather_runs"):
            with pytest.raises(IndexError):
                runs.take(arr)
        assert np.array_equal(runs.take(np.arange(12)), [2, 3, 4, 8, 9, 10, 11])
        assert np.array_equal(runs.rows(np.asarray([0, 2, 3, 6])),
                              [2, 4, 8, 11])


class TestIncrementalDelta:
    """SURVEY §7 hard part (a): committed edge inserts ride a small
    overlay (delta kernel + overlay mirror) instead of forcing the
    O(m) CSR/ELL rebuild per mutation — results must track writes
    exactly, and the rebuild count must stay ~constant under a
    sustained INSERT+GO workload."""

    def _boot(self):
        from nebula_tpu.common.flags import flags
        flags.set("storage_backend", "tpu")
        c = LocalCluster(num_storage=1, tpu_backend=True)
        cl = c.client()

        def ok(s):
            r = cl.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"
            return r
        ok("CREATE SPACE inc(partition_num=4, replica_factor=1)")
        c.refresh_all()
        ok("USE inc")
        ok("CREATE TAG player(name string, age int)")
        ok("CREATE EDGE follow(degree int)")
        c.refresh_all()
        players = ", ".join(f'{100 + i}:("p{i}", {20 + i})'
                            for i in range(30))
        ok(f'INSERT VERTEX player(name, age) VALUES {players}')
        ok('INSERT EDGE follow(degree) VALUES '
           + ", ".join(f"{100 + i} -> {100 + (i + 1) % 30}:({50 + i})"
                       for i in range(30)))
        return c, cl, ok

    def test_insert_go_workload_tracks_writes_without_rebuilds(self):
        import random
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")        # build the base mirror
            builds0 = rt.stats["mirror_builds"]
            rng = random.Random(3)
            expected = {(100 + i, 100 + (i + 1) % 30, 50 + i)
                        for i in range(30)}
            for step in range(25):
                s = rng.randrange(0, 30)
                d = rng.randrange(0, 30)
                deg = 200 + step
                ok(f"INSERT EDGE follow(degree) VALUES "
                   f"{100 + s} -> {100 + d}@{1000 + step}:({deg})")
                expected.add((100 + s, 100 + d, deg))
                r = ok("GO FROM 100, 105, 110 OVER follow "
                       "YIELD follow._src, follow._dst, follow.degree")
                # parity vs the CPU executor path every few steps
                if step % 5 == 0:
                    from nebula_tpu.common.flags import flags
                    flags.set("storage_backend", "cpu")
                    r2 = ok("GO FROM 100, 105, 110 OVER follow "
                            "YIELD follow._src, follow._dst, "
                            "follow.degree")
                    flags.set("storage_backend", "tpu")
                    assert sorted(map(tuple, r.rows)) == \
                        sorted(map(tuple, r2.rows)), f"step {step}"
            # the whole workload rode the overlay: no rebuilds
            assert rt.stats["mirror_builds"] == builds0, \
                (builds0, rt.stats["mirror_builds"])
            assert rt.stats["mirror_deltas"] > 0
            # device path actually served
            assert rt.stats["go_device"] > 0
        finally:
            c.stop()

    def test_multi_hop_through_fresh_edges(self):
        """New edges must be traversable mid-path, not only at the
        final hop (the delta rides every kernel hop)."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            builds0 = rt.stats["mirror_builds"]
            # bridge: 100 -> 400-ish via two fresh edges... endpoints
            # must already exist, so bridge through existing vertices
            ok("INSERT EDGE follow(degree) VALUES 100 -> 115@7:(99)")
            ok("INSERT EDGE follow(degree) VALUES 115 -> 120@7:(98)")
            r = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert (120,) in set(map(tuple, r.rows))
            from nebula_tpu.common.flags import flags
            flags.set("storage_backend", "cpu")
            r2 = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            flags.set("storage_backend", "tpu")
            assert sorted(map(tuple, r.rows)) == sorted(map(tuple, r2.rows))
            assert rt.stats["mirror_builds"] == builds0
        finally:
            c.stop()

    def test_delete_absorbed_for_single_hop(self):
        """An edge delete rides the overlay as a base-row tombstone:
        1-hop queries keep serving from the mirror with NO rebuild and
        must not see the dead edge."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            ok("INSERT EDGE follow(degree) VALUES 100 -> 110@5:(77)")
            r = ok("GO FROM 100 OVER follow YIELD follow._dst")
            assert (110,) in set(map(tuple, r.rows))
            builds0 = rt.stats["mirror_builds"]
            ok("DELETE EDGE follow 100 -> 110@5")
            r = ok("GO FROM 100 OVER follow YIELD follow._dst")
            assert (110,) not in set(map(tuple, r.rows))
            assert rt.stats["mirror_builds"] == builds0, "tombstone " \
                "should absorb a 1-hop-only delete without a rebuild"
            # the pre-existing ring edge from 100 still serves
            assert (101,) in set(map(tuple, r.rows))
        finally:
            c.stop()

    def test_delete_with_multi_hop_stays_correct(self):
        """Reachability-changing deletes fold into the tables as
        tombstones at absorb time — multi-hop queries stay exact
        (the rebuild-free claim is pinned in tests/test_absorb.py)."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            ok("DELETE EDGE follow 101 -> 102@0")
            r = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            got = set(map(tuple, r.rows))
            assert (102,) not in got, "deleted mid-path edge traversed"
            from nebula_tpu.common.flags import flags
            flags.set("storage_backend", "cpu")
            r2 = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            flags.set("storage_backend", "tpu")
            assert sorted(map(tuple, r.rows)) == sorted(map(tuple,
                                                            r2.rows))
        finally:
            c.stop()

    def test_update_absorbed_without_rebuild(self):
        """An in-place UPDATE (same edge identity, new props) rides the
        overlay as override rows — multi-hop safe (same dst), fresh
        props visible, no rebuild."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            builds0 = rt.stats["mirror_builds"]
            ok("INSERT EDGE follow(degree) VALUES 100 -> 101:(999)")
            r = ok("GO FROM 100 OVER follow "
                   "YIELD follow._dst, follow.degree")
            got = set(map(tuple, r.rows))
            assert (101, 999) in got, got
            assert (101, 50) not in got, "stale pre-update row served"
            # multi-hop still serves from the mirror (dst unchanged)
            r = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert (102,) in set(map(tuple, r.rows))
            assert rt.stats["mirror_builds"] == builds0, \
                "updates must absorb without a rebuild"
            # parity with the CPU path
            from nebula_tpu.common.flags import flags
            flags.set("storage_backend", "cpu")
            r2 = ok("GO FROM 100 OVER follow "
                    "YIELD follow._dst, follow.degree")
            flags.set("storage_backend", "tpu")
            r3 = ok("GO FROM 100 OVER follow "
                    "YIELD follow._dst, follow.degree")
            assert sorted(map(tuple, r3.rows)) == sorted(map(tuple,
                                                             r2.rows))
        finally:
            c.stop()

    def test_new_vertex_insert_absorbs_known_dst_rebuilds_extra_vid(self):
        """An edge to a KNOWN vertex absorbs into the tables (the dst
        row exists — no rebuild); an edge to a vid with NO vertex
        record grows the dense-id space, which only the rebuild can
        serve — and that decline must be OBSERVABLE (mirror_absorb_
        failed + the vertex-plan-change reason), never silent
        (docs/durability.md decision table)."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            ok('INSERT VERTEX player(name, age) VALUES 500:("new", 1)')
            # vertex-only write is opaque (rebuild) — anchor the count
            ok("GO FROM 100 OVER follow")
            builds1 = rt.stats["mirror_builds"]
            ok("INSERT EDGE follow(degree) VALUES 100 -> 500:(42)")
            r = ok("GO FROM 100 OVER follow YIELD follow._dst, "
                   "follow.degree")
            assert (500, 42) in set(map(tuple, r.rows))
            assert rt.stats["mirror_builds"] == builds1, \
                "known-dst edge should absorb without a rebuild"
            # an edge to a vid with NO vertex record at all grows the
            # dense-id space: a vertex-plan change — graceful,
            # OBSERVABLE rebuild (results stay exact)
            fails0 = rt.stats["mirror_absorb_failed"]
            ok("INSERT EDGE follow(degree) VALUES 100 -> 600:(44)")
            r = ok("GO FROM 100 OVER follow YIELD follow._dst, "
                   "follow.degree")
            assert (600, 44) in set(map(tuple, r.rows))
            assert rt.stats["mirror_builds"] > builds1, \
                "extra-vid edge changes the vertex plan: rebuild path"
            assert rt.stats["mirror_absorb_failed"] > fails0
            from nebula_tpu.common.events import journal
            kinds = [e for e in journal.dump(200)
                     if e["kind"] == "mirror.absorb_failed"]
            assert any(e.get("reason") == "vertex-plan-change"
                       for e in kinds), kinds
            # starting AT the fresh vertex must be exact too
            ok("INSERT EDGE follow(degree) VALUES 600 -> 103:(43)")
            r = ok("GO FROM 600 OVER follow YIELD follow._dst")
            assert set(map(tuple, r.rows)) == {(103,)}
            from nebula_tpu.common.flags import flags
            flags.set("storage_backend", "cpu")
            r2 = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            flags.set("storage_backend", "tpu")
            r3 = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert sorted(map(tuple, r3.rows)) == sorted(map(tuple,
                                                             r2.rows))
        finally:
            c.stop()

    def test_vertex_numeric_prop_update_absorbed(self):
        """A numeric tag-prop update on a known vertex applies to the
        mirror IN PLACE (csr.commit_vertex_plan) — no rebuild, and
        device-served $^-filtered queries see the fresh value."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow WHERE $^.player.age > 10 "
               "YIELD follow._dst")            # build + device serve
            builds0 = rt.stats["mirror_builds"]
            # p0's age was 20; push it over the new threshold
            ok('INSERT VERTEX player(name, age) VALUES 100:("p0", 77)')
            q = ("GO FROM 100 OVER follow WHERE $^.player.age > 50 "
                 "YIELD follow._dst, $^.player.age")
            r = ok(q)
            got = set(map(tuple, r.rows))
            assert (101, 77) in got, got
            assert rt.stats["mirror_builds"] == builds0, \
                "numeric vertex update must absorb without a rebuild"
            from nebula_tpu.common.flags import flags
            flags.set("storage_backend", "cpu")
            r2 = ok(q)
            flags.set("storage_backend", "tpu")
            assert sorted(map(tuple, r.rows)) == sorted(map(tuple,
                                                            r2.rows))
        finally:
            c.stop()

    def test_vertex_string_prop_update_rebuilds(self):
        """String tag-prop updates stay opaque (dictionaries bake into
        compiled plans) — must rebuild, and results must be fresh."""
        c, cl, ok = self._boot()
        try:
            rt = c.tpu_runtime
            ok("GO FROM 100 OVER follow")
            builds0 = rt.stats["mirror_builds"]
            ok('INSERT VERTEX player(name, age) VALUES 100:("zz", 20)')
            r = ok("GO FROM 100 OVER follow YIELD $^.player.name")
            assert set(map(tuple, r.rows)) == {("zz",)}
            assert rt.stats["mirror_builds"] > builds0
        finally:
            c.stop()

    def test_find_path_sees_fresh_edges(self):
        """FIND PATH forces the rebuild (mirror_full) and must see the
        overlay's edges."""
        c, cl, ok = self._boot()
        try:
            ok("GO FROM 100 OVER follow")
            ok("INSERT EDGE follow(degree) VALUES 100 -> 117@9:(1)")
            r = ok("FIND SHORTEST PATH FROM 100 TO 117 OVER follow "
                   "UPTO 2 STEPS")
            assert r.rows and "117" in r.rows[0][0]
        finally:
            c.stop()


class TestColumnarInterimSeams:
    """Device-served GO results are ColumnarRows (lazy columns); every
    downstream consumer — pipes, $var, ORDER BY, GROUP BY, LIMIT, set
    ops — must read them identically to plain row lists (parity with
    the CPU path pins it)."""

    def _boot(self):
        from nebula_tpu.common.flags import flags
        flags.set("storage_backend", "tpu")
        c = LocalCluster(num_storage=1, tpu_backend=True)
        cl = c.client()

        def ok(s):
            r = cl.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"
            return r
        ok("CREATE SPACE ci(partition_num=4)")
        c.refresh_all()
        ok("USE ci")
        ok("CREATE EDGE e(w int)")
        c.refresh_all()
        ok("INSERT EDGE e(w) VALUES 1->2:(5), 1->3:(9), 2->4:(7), "
           "3->4:(1), 4->1:(3)")
        return c, ok

    @staticmethod
    def _parity(c, ok, q, expect_rows=None):
        from nebula_tpu.common.flags import flags
        rt = c.tpu_runtime
        dev0 = rt.stats["go_device"]
        a = [tuple(r) for r in ok(q).rows]
        assert rt.stats["go_device"] > dev0, f"device did not serve: {q}"
        flags.set("storage_backend", "cpu")
        b = [tuple(r) for r in ok(q).rows]
        flags.set("storage_backend", "tpu")
        assert a == b, (q, a, b)
        if expect_rows is not None:
            assert a == expect_rows, (q, a)
        return a

    def test_pipe_order_by_limit(self):
        c, ok = self._boot()
        try:
            self._parity(
                c, ok,
                "GO FROM 1 OVER e YIELD e._dst AS id, e.w AS w | "
                "ORDER BY $-.w DESC | LIMIT 1",
                expect_rows=[(3, 9)])
        finally:
            c.stop()

    def test_pipe_group_by_aggregate(self):
        c, ok = self._boot()
        try:
            rows = self._parity(
                c, ok,
                "GO FROM 1, 2, 3 OVER e YIELD e._dst AS id, e.w AS w | "
                "GROUP BY $-.id YIELD $-.id AS id, count(1) AS n, "
                "sum($-.w) AS s")
            assert sorted(rows) == [(2, 1, 5), (3, 1, 9), (4, 2, 8)]
        finally:
            c.stop()

    def test_var_assignment_and_set_op(self):
        c, ok = self._boot()
        try:
            from nebula_tpu.common.flags import flags
            rt = c.tpu_runtime
            dev0 = rt.stats["go_device"]
            r = ok("$a = GO FROM 1 OVER e YIELD e._dst AS id; "
                   "GO FROM $a.id OVER e YIELD e._dst")
            assert rt.stats["go_device"] > dev0
            got = sorted(map(tuple, r.rows))
            flags.set("storage_backend", "cpu")
            r2 = ok("$a = GO FROM 1 OVER e YIELD e._dst AS id; "
                    "GO FROM $a.id OVER e YIELD e._dst")
            flags.set("storage_backend", "tpu")
            assert got == sorted(map(tuple, r2.rows))
            assert got == [(4,), (4,)]
            u = self._parity(
                c, ok,
                "GO FROM 1 OVER e YIELD e._dst AS id UNION "
                "GO FROM 2 OVER e YIELD e._dst AS id")
            assert sorted(u) == [(2,), (3,), (4,)]
        finally:
            c.stop()


class TestSparseSplit:
    """A batch whose TOTAL starts outgrow the sparse c0 ladder splits
    into ladder-sized sparse sub-launches at query boundaries instead
    of falling to the dense pull (whose [n_rows+1, B] frontier is
    GBs of upload at 10^8-edge scale)."""

    def test_oversized_batch_splits_and_matches_cpu(self):
        import threading

        from nebula_tpu.common.flags import flags

        # the sparse split is a WINDOWED-pipeline path (continuous
        # mode rides the resident dense seat map instead)
        flags.set("go_dispatch_mode", "windowed")
        c, g = _boot(tpu_backend=True)
        try:
            rng = np.random.default_rng(3)
            extra = ", ".join(
                f"{300 + int(a)} -> {300 + int(b)}:({int(i)})"
                for i, (a, b) in enumerate(zip(rng.integers(0, 60, 240),
                                               rng.integers(0, 60, 240))))
            assert g.execute(
                f"INSERT EDGE follow(degree) VALUES {extra}").ok()
            starts = [",".join(str(300 + int(v)) for v in
                               rng.integers(0, 60, 8))
                      for _ in range(12)]
            queries = [f"GO 2 STEPS FROM {s} OVER follow"
                       for s in starts]
            flags.set("storage_backend", "cpu")
            cpu_rows = [sorted(map(tuple, g.execute(q).rows))
                        for q in queries]
            flags.set("storage_backend", "tpu")
            flags.set("tpu_sparse_c0s", "16,32")   # force splitting
            flags.set("go_batch_window_ms", 120)   # coalesce the burst
            try:
                rt = c.tpu_runtime
                base_dense = rt.stats["go_dense"]
                results = {}
                lock = threading.Lock()
                # the burst has to land inside ONE pooling window to
                # be oversized: connecting and USE take a loaded host
                # longer than the window, so the statements start
                # together, after them
                together = threading.Barrier(len(queries))

                def worker(i):
                    g2 = c.client()
                    g2.execute("USE nba")
                    together.wait(timeout=60)
                    r = g2.execute(queries[i])
                    assert r.ok(), r.error_msg
                    with lock:
                        results[i] = sorted(map(tuple, r.rows))

                g.execute(queries[0])       # warm kernels
                ts = [threading.Thread(target=worker, args=(i,))
                      for i in range(len(queries))]
                [t.start() for t in ts]
                [t.join() for t in ts]
                for i, rows in results.items():
                    assert rows == cpu_rows[i], queries[i]
                assert rt.stats.get("go_sparse_split", 0) >= 1
                assert rt.stats["go_dense"] == base_dense
            finally:
                flags.set("tpu_sparse_c0s", "256,2048")
                flags.set("go_batch_window_ms", -1)
        finally:
            flags.set("storage_backend", "tpu")
            flags.set("go_dispatch_mode", "continuous")
            c.stop()


class TestUptoDevice:
    """GO UPTO serves on the device via the cumulative-frontier kernel
    variants (sparse union merge / dense OR accumulator) — not a CPU
    fallback."""

    def test_upto_runs_on_device_and_matches_cpu(self):
        from nebula_tpu.common.flags import flags

        # pin the windowed pipeline: this asserts the SPARSE UPTO
        # kernel ran (continuous mode serves UPTO from the dense
        # union accumulator instead — covered in test_continuous.py)
        flags.set("go_dispatch_mode", "windowed")
        c, g = _boot(tpu_backend=True)
        try:
            q = (f"GO UPTO 3 STEPS FROM {TIM} OVER follow "
                 f"YIELD follow._dst, follow.degree")
            flags.set("storage_backend", "cpu")
            cpu_rows = sorted(map(tuple, g.execute(q).rows))
            flags.set("storage_backend", "tpu")
            rt = c.tpu_runtime
            before = rt.stats["go_device"]
            before_sparse = rt.stats["go_sparse"]
            r = g.execute(q)
            assert r.ok(), r.error_msg
            assert sorted(map(tuple, r.rows)) == cpu_rows
            assert rt.stats["go_device"] == before + 1
            assert rt.stats["go_sparse"] == before_sparse + 1
        finally:
            flags.set("storage_backend", "tpu")
            flags.set("go_dispatch_mode", "continuous")
            c.stop()

    def test_upto_dense_kernel_union(self):
        """Dense UPTO variant ORs every depth's frontier."""
        from nebula_tpu.tpu import ell as E
        from test_ell import run_go

        rng = np.random.default_rng(5)
        n, m = 200, 900
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        ee = np.ones(m, np.int32)
        both_s = np.concatenate([es, ed])
        both_d = np.concatenate([ed, es])
        both_e = np.concatenate([ee, -ee])
        ix = E.EllIndex.build(both_s, both_d, both_e, n, cap=64, min_d=8)
        f0 = ix.start_frontier([np.asarray([3]), np.asarray([7, 11])],
                               B=8)
        steps = 3
        out = run_go(ix, steps, (1,), f0, upto=True)
        # numpy oracle: OR of frontiers at depths 0..steps-1
        adj = {}
        for s_, d_ in zip(es.tolist(), ed.tolist()):
            adj.setdefault(s_, set()).add(d_)
        for q, starts in enumerate(([3], [7, 11])):
            acc = set(starts)
            cur = set(starts)
            for _ in range(steps - 1):
                cur = set().union(*(adj.get(v, set()) for v in cur)) \
                    if cur else set()
                acc |= cur
            got = set(np.nonzero(ix.to_old(out[:ix.n_rows + 1])
                                 [:n, q])[0].tolist())
            assert got == acc, (q, got, acc)

    def test_upto_sparse_kernel_union(self):
        """Sparse UPTO variant returns the deduped union pair list."""
        import jax.numpy as jnp

        from nebula_tpu.tpu import ell as E

        rng = np.random.default_rng(11)
        n, m = 300, 1500
        es = rng.integers(0, n, m).astype(np.int32)
        ed = rng.integers(0, n, m).astype(np.int32)
        ee = np.ones(m, np.int32)
        both_s = np.concatenate([es, ed])
        both_d = np.concatenate([ed, es])
        both_e = np.concatenate([ee, -ee])
        ix = E.EllIndex.build(both_s, both_d, both_e, n, cap=64, min_d=8)
        steps = 3
        caps = E.sparse_caps(8, max(ix.bucket_D), steps, 1 << 14)
        kern = E.make_batched_sparse_go_kernel(ix, steps, (1,), caps,
                                               qmax=16, upto=True)
        starts = [[3], [7, 11], [42]]
        ids = np.full(caps[0], ix.n_rows, np.int32)
        qid = np.zeros(caps[0], np.int32)
        k = 0
        for q, ss in enumerate(starts):
            for v in ss:
                ids[k] = ix.perm[v]
                qid[k] = q
                k += 1
        ecnt, e0 = ix.hub_expansion()
        out = kern(jnp.asarray(ids), jnp.asarray(qid),
                   jnp.asarray(ecnt), jnp.asarray(e0),
                   *ix.kernel_args()[1:])
        cnt, overflow, qids, vids_new = E.sparse_go_pairs(
            kern, np.asarray(out))
        assert not overflow
        adj = {}
        for s_, d_ in zip(es.tolist(), ed.tolist()):
            adj.setdefault(s_, set()).add(d_)
        got = {}
        for qv, iv in zip(qids.tolist(), ix.inv[vids_new].tolist()):
            got.setdefault(qv, set()).add(iv)
        for q, ss in enumerate(starts):
            acc = set(ss)
            cur = set(ss)
            for _ in range(steps - 1):
                cur = set().union(*(adj.get(v, set()) for v in cur)) \
                    if cur else set()
                acc |= cur
            assert got.get(q, set()) == acc, (q, got.get(q), acc)


class TestRetraceBudget:
    """Runtime half of nebulint's jax-hotpath check: a repeated
    multi-hop traversal over the same space must not grow the jit
    trace cache (or the runtime's kernel memo) after warmup.  Growth
    here is the cache-buster class — jit construction per call,
    unhashable static args, closures over mutables — that silently
    turns every hop into a fresh XLA trace."""

    QUERY = f"GO 3 STEPS FROM {TIM} OVER follow YIELD follow._dst"

    def _snapshot(self, rt):
        with rt._lock:
            kernels = dict(rt._kernels)
        sizes = {}
        for key, kern in kernels.items():
            cs = getattr(kern, "_cache_size", None)
            sizes[key] = cs() if callable(cs) else -1
        return sizes

    def test_jit_cache_stable_after_warmup(self, clusters):
        _cpu_c, _cpu, tpu_c, tpu = clusters
        rt = tpu_c.tpu_runtime
        for _ in range(2):       # warmup: mirror + kernel builds + traces
            assert tpu.execute(self.QUERY).ok()
        before = self._snapshot(rt)
        builds_before = rt.stats["mirror_builds"]
        for _ in range(5):
            assert tpu.execute(self.QUERY).ok()
        after = self._snapshot(rt)
        assert set(after) == set(before), (
            f"kernel memo grew after warmup: {set(after) ^ set(before)}")
        grown = {k: (before[k], after[k]) for k in before
                 if after[k] != before[k]}
        assert not grown, f"jit trace cache grew after warmup: {grown}"
        assert rt.stats["mirror_builds"] == builds_before, \
            "repeat traversal rebuilt the mirror"
