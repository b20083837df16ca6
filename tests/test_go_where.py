"""A filtered GO through the system's normal entry (LocalCluster,
tpu_backend=True, the shipped flags) against the benchmark's plain
reference (benchmark/semantics/go_where.py): it rides the dispatcher
and the lanes like any other GO, its predicate meets the final
frontier's candidate edges at assembly in float64, and the counters and
the tpu.where span say what was filtered, whatever tpu_filter_mode
holds (a name nothing reads).  CPU jax: no number here is a device
number."""
from __future__ import annotations

import threading

import numpy as np
import pytest

from benchmark import reference
from benchmark.deploy import flags_set, shipped_defaults
from benchmark.workload import columns_of
import nebula_tpu.graph.backend_router    # noqa: F401 — define the flags
import nebula_tpu.tpu.runtime             # noqa: F401   the conf files set
from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight, tracing
from nebula_tpu.common.flags import flags

LEVELS = 16
N = 90
ISOLATED = N + 5        # a vertex the graph never mentions
OPS = [(">", 0.5), (">=", 0.5), ("<", 0.5),     # 0.5 is a stored level
       (">", 0.9), (">", 1.5), (">=", 0.0)]     # few / none / all kept


def _semantics(steps, op, value):
    return {"kind": "go_where", "steps": steps, "prop": "w", "op": op,
            "value": value, "yield": ["_dst"]}


def _statement(steps, op, value, start):
    return (f"GO {steps} STEPS FROM {start} OVER knows "
            f"WHERE knows.w {op} {value} YIELD knows._dst")


@pytest.fixture(scope="module")
def served():
    """(cluster, client, reference graph); vertex N has in-edges only."""
    rng = np.random.default_rng(31)
    key = np.unique(rng.integers(0, N, 900) * N + rng.integers(0, N, 900))
    src, dst = key // N + 1, key % N + 1
    keep = (src != dst) & (src != N)     # vertex N: in-edges only
    src, dst = src[keep], dst[keep]
    idx = rng.integers(0, LEVELS, len(src))
    graph = reference.Graph(src, dst,
                            [{"w": k / LEVELS} for k in range(LEVELS)], idx)
    with flags_set({**shipped_defaults(), "go_backend_router": False}):
        assert flags.get("tpu_filter_mode") == "auto"
        c = LocalCluster(num_storage=1, tpu_backend=True)
        g = c.client()

        def ok(stmt):
            r = g.execute(stmt)
            assert r.ok(), f"{stmt}: {r.error_msg}"
            return r
        ok("CREATE SPACE w(partition_num=4, replica_factor=1)")
        c.refresh_all()
        ok("USE w")
        ok("CREATE EDGE knows(w double)")
        c.refresh_all()
        ok("INSERT EDGE knows(w) VALUES " + ", ".join(
            f"{s}->{d}:({k / LEVELS})" for s, d, k in zip(src, dst, idx)))
        try:
            yield c, g, graph
        finally:
            c.stop()


def _served_rows(client, stmt):
    resp = client.execute(stmt)
    assert resp.ok(), f"{stmt}: {resp.error_msg}"
    assert not resp.warnings and resp.completeness == 100, stmt
    return columns_of(resp)


@pytest.mark.parametrize("op,value", OPS)
@pytest.mark.parametrize("steps", [1, 2, 3])
def test_filtered_go_matches_the_plain_reference(served, steps, op, value):
    c, g, graph = served
    rt = c.tpu_runtime
    before = dict(rt.stats)
    rows = candidates = 0
    starts = list(range(1, 13))
    for start in starts:
        want = graph.answer(_semantics(steps, op, value), start)
        got = _served_rows(g, _statement(steps, op, value, start))
        assert reference.same_rows(got, want), (steps, op, value, start)
        rows += reference.n_rows(want)
        candidates += int(graph.deg[graph.frontier(start, steps - 1)].sum())
    if (op, value) == (">", 1.5):
        assert rows == 0
    elif (op, value) == (">=", 0.0):
        assert rows == candidates > 0
    else:
        assert 0 < rows < candidates
    # the counters grow by what the reference says they should
    grew = {k: rt.stats[k] - before[k] for k in
            ("go_device", "go_where", "where_candidates", "where_rows")}
    assert grew == {"go_device": len(starts), "go_where": len(starts),
                    "where_candidates": candidates, "where_rows": rows}


@pytest.mark.parametrize("native", [True, False],
                         ids=["one_native_pass", "numpy"])
@pytest.mark.parametrize("piece", [1, 5, 64])
def test_a_cohort_s_candidates_are_filtered_piece_by_piece(
        served, monkeypatch, piece, native):
    """The predicate meets the candidates a piece of runs at a time;
    the answer does not depend on where the pieces are cut, nor on
    which of the two ways a piece is filtered: the one native pass a
    double column against a constant takes (_EdgeRuns.keep_f64), or
    the gathered columns and the compiled predicate in numpy."""
    c, g, graph = served
    runtime = nebula_tpu.tpu.runtime
    monkeypatch.setattr(runtime, "WHERE_PIECE_EDGES", piece)
    passes = {"native": 0, "numpy": 0}
    rt = c.tpu_runtime
    real_keep = runtime._EdgeRuns.keep_f64
    real_filter = rt._host_filter
    before = {k: rt.stats[k] for k in ("go_where", "where_native")}

    def keep(self, values, valid, op, const):
        passes["native"] += 1
        return real_keep(self, values, valid, op, const)

    def host_filter(m, plan, idx):
        passes["numpy"] += 1
        return real_filter(m, plan, idx)

    monkeypatch.setattr(runtime._EdgeRuns, "keep_f64", keep)
    monkeypatch.setattr(rt, "_host_filter", host_filter)
    if not native:              # a library without the pass
        monkeypatch.setattr(rt, "_native_filter", lambda m, plan, et: None)
    try:
        _pieces(g, graph, piece)
    finally:
        monkeypatch.undo()
    grew = {k: rt.stats[k] - before[k] for k in before}
    from nebula_tpu.native import lib
    if native and hasattr(lib(), "neb_filter_runs_f64"):
        assert passes["native"] > 8 and passes["numpy"] == 0, passes
        assert grew == {"go_where": 8, "where_native": 8}
    else:
        assert passes["numpy"] > 8 and passes["native"] == 0, passes
        assert grew == {"go_where": 8, "where_native": 0}


def _pieces(g, graph, piece):
    for start in range(1, 9):
        want = graph.answer(_semantics(3, ">", 0.5), start)
        assert reference.n_rows(want) > piece
        got = _served_rows(g, _statement(3, ">", 0.5, start))
        assert reference.same_rows(got, want), (piece, start)


@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("start", [N, ISOLATED])
def test_a_start_with_no_out_edge_answers_nothing(served, steps, start):
    c, g, graph = served
    assert start >= len(graph.deg) or graph.deg[start] == 0
    got = _served_rows(g, _statement(steps, ">", 0.5, start))
    assert reference.n_rows(got) == 0


def _spans(tree):
    stack = list(tree["roots"])
    while stack:
        node = stack.pop()
        yield node
        stack.extend(node.get("children", ()))


@pytest.mark.parametrize("steps", [1, 2, 3])
def test_a_traced_filtered_go_shows_the_dispatcher_and_tpu_where(
        served, steps):
    c, g, graph = served
    start = 3
    want = graph.answer(_semantics(steps, ">", 0.5), start)
    resp = g.execute("PROFILE " + _statement(steps, ">", 0.5, start))
    assert resp.ok(), resp.error_msg
    assert reference.same_rows(columns_of(resp), want)
    spans = list(_spans(resp.raw["profile"]))
    names = {s["name"] for s in spans}
    # the dispatcher's spans: a lane ride for 2 and 3 steps (the
    # continuous tier), the windowed batch for 1
    assert ("graph.continuous" if steps > 1 else "graph.batched") in names
    assert {"tpu.launch", "tpu.fetch", "tpu.assemble"} <= names
    where = [s for s in spans if s["name"] == "tpu.where"]
    assert len(where) == 1
    tags = where[0]["tags"]
    assert tags["site"] == "assembly" and tags["queries"] == 1
    assert tags["kept"] == reference.n_rows(want)
    assert tags["candidates"] == int(
        graph.deg[graph.frontier(start, steps - 1)].sum())
    assert tags["cpu_us"] >= 0
    assert tracing.critical_path(resp.raw["profile"])["assemble"] \
        >= where[0]["duration_us"]


def test_a_filtered_go_rides_the_lanes_beside_unfiltered_ones(served):
    """Same dispatcher key (space, OVER, steps, no reduction): a
    filtered 3-step GO and unfiltered ones leave in one cohort, and
    each gets its own rows."""
    c, g, graph = served
    rt = c.tpu_runtime
    jobs = []
    for i, start in enumerate(range(1, 13)):
        if i % 3 == 0:
            jobs.append((_statement(3, ">", 0.5, start),
                         _semantics(3, ">", 0.5), start))
        else:
            jobs.append((f"GO 3 STEPS FROM {start} OVER knows "
                         f"YIELD knows._dst",
                         {"kind": "go", "steps": 3, "yield": ["_dst"]},
                         start))
    _served_rows(g, jobs[1][0])                 # the stream exists
    streams = rt.dispatcher.continuous.streams()
    assert streams
    since = flight.recorder.note_tick(stream=-1)    # a mark in the ring
    before = dict(rt.stats)
    sampled = flags.get("trace_sample_rate")
    flags.set("trace_sample_rate", 1.0)
    tracing.trace_store.clear_for_tests()
    results, errors = {}, []
    barrier = threading.Barrier(len(jobs))

    def worker(i):
        try:
            client = c.client()
            assert client.execute("USE w").ok()
            barrier.wait()
            results[i] = _served_rows(client, jobs[i][0])
        except Exception as ex:     # noqa: BLE001 — reported below
            errors.append(ex)

    for st in streams:
        st.tick_delay_s = 0.05      # arrivals land in one tick
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        for st in streams:
            st.tick_delay_s = 0.0
        flags.set("trace_sample_rate", sampled)
    assert not errors, errors
    for i, (_stmt, sem, start) in enumerate(jobs):
        assert reference.same_rows(results[i], graph.answer(sem, start)), i
    # the pump hands every leaver its frontier and knows no WHERE: what
    # a predicate met is on the tpu.where span of the rider's OWN trace
    # (one statement a span) and in the counters
    ticks = [r for r in flight.recorder.dump(limit=1 << 20)
             if r.get("kind") == "tick" and r.get("id", 0) > since
             and r.get("stream", -1) >= 0]
    assert sum(t["leaves"] for t in ticks) == len(jobs)
    assert all(t["handed"] == t["leaves"] for t in ticks)
    assert not any(k.startswith("where_") for t in ticks for k in t)
    riders = {}                 # left_tick -> [its riders' tpu.where]
    for summary in tracing.trace_store.summaries():
        tree = tracing.trace_store.tree(int(summary["id"], 16))
        nodes = [n for root in tree["roots"] for n in _walk(root)]
        marks = [n for n in nodes if n["name"] == "graph.continuous"]
        if marks:
            riders.setdefault(marks[0]["tags"]["left_tick"], []).append(
                [n["tags"] for n in nodes if n["name"] == "tpu.where"])
    wheres = [w for cohort in riders.values() for ws in cohort for w in ws]
    assert len(wheres) == 4
    assert all(w["queries"] == 1 and w["site"] == "assembly"
               and w["candidates"] >= w["kept"] > 0 and w["cpu_us"] >= 0
               for w in wheres)
    grew = {k: rt.stats[k] - before[k] for k in
            ("go_where", "where_candidates", "where_rows")}
    assert grew == {"go_where": 4,
                    "where_candidates": sum(w["candidates"]
                                            for w in wheres),
                    "where_rows": sum(w["kept"] for w in wheres)}
    mixed = [cohort for cohort in riders.values()
             if 0 < sum(bool(ws) for ws in cohort) < len(cohort)]
    assert mixed, {tick: [len(ws) for ws in cohort]
                   for tick, cohort in riders.items()}


def _walk(node):
    yield node
    for child in node.get("children", ()):
        yield from _walk(child)


@pytest.mark.parametrize("mode", ["auto", "host", "device"])
def test_no_filter_mode_fuses(served, mode):
    """Whatever the flag holds, a WHERE goes through the dispatcher:
    no statement has a hop program of its own."""
    c, g, graph = served
    rt = c.tpu_runtime
    want = graph.answer(_semantics(2, ">", 0.5), 4)
    before = rt.stats["go_where"]
    flags.set("tpu_filter_mode", mode)
    try:
        resp = g.execute("PROFILE " + _statement(2, ">", 0.5, 4))
    finally:
        flags.set("tpu_filter_mode", "auto")
    assert resp.ok(), resp.error_msg
    assert reference.same_rows(columns_of(resp), want)
    assert reference.digest(columns_of(resp)) == reference.digest(want)
    names = {s["name"] for s in _spans(resp.raw["profile"])}
    assert {"graph.continuous", "tpu.where"} <= names
    assert rt.stats["go_where"] - before == 1


# ---- weights float32 does not hold (the cell's split levels) --------
SPLIT_LEVELS = 64


@pytest.fixture(scope="module")
def served_split():
    """(cluster, client, reference graph) over the benchmark's split
    weight table at 64 levels: beside 0.9 two stored doubles that are
    one float32, so a float32 evaluation answers one wrong."""
    from benchmark.generators.kronecker_split import split_levels
    rng = np.random.default_rng(313)
    key = np.unique(rng.integers(0, N, 1500) * N + rng.integers(0, N, 1500))
    src, dst = key // N + 1, key % N + 1
    keep = src != dst
    src, dst = src[keep], dst[keep]
    levels = [k / SPLIT_LEVELS for k in range(SPLIT_LEVELS)]
    moved = split_levels(SPLIT_LEVELS, [0.9])
    for k, v in moved.items():
        levels[k] = v
    idx = rng.integers(0, SPLIT_LEVELS, len(src))
    assert all(int((idx == k).sum()) > 5 for k in moved)
    graph = reference.Graph(src, dst, [{"w": w} for w in levels], idx)
    with flags_set({**shipped_defaults(), "go_backend_router": False}):
        c = LocalCluster(num_storage=1, tpu_backend=True)
        g = c.client()
        for stmt in ("CREATE SPACE ws(partition_num=4, replica_factor=1)",
                     "USE ws", "CREATE EDGE knows(w double)"):
            r = g.execute(stmt)
            assert r.ok(), f"{stmt}: {r.error_msg}"
            c.refresh_all()
        r = g.execute("INSERT EDGE knows(w) VALUES " + ", ".join(
            f"{s}->{d}:({levels[k]!r})" for s, d, k in zip(src, dst, idx)))
        assert r.ok(), r.error_msg
        try:
            yield c, g, graph
        finally:
            c.stop()


@pytest.mark.parametrize("steps", [1, 2, 3])
@pytest.mark.parametrize("mode", ["auto", "host", "device"])
def test_doubles_float32_does_not_hold_are_filtered_in_float64(
        served_split, steps, mode):
    """The WHERE filters on the host in float64 under every value the
    flag accepts, so every statement is device-served and the rows are
    the float64 reference's, not what a float32 evaluation of the
    reference keeps."""
    c, g, graph = served_split
    rt = c.tpu_runtime
    before = dict(rt.stats)
    told_apart = 0
    flags.set("tpu_filter_mode", mode)
    try:
        for start in range(1, 13):
            sem = _semantics(steps, ">", 0.9)
            want = graph.answer(sem, start)
            in32 = graph.answer({**sem, "precision": "float32"}, start)
            got = _served_rows(g, _statement(steps, ">", 0.9, start))
            assert reference.same_rows(got, want), (steps, mode, start)
            told_apart += reference.digest(in32) != reference.digest(want)
    finally:
        flags.set("tpu_filter_mode", "auto")
    assert told_apart >= (3 if steps == 1 else 10)
    assert rt.stats["go_device"] - before["go_device"] == 12
    assert rt.stats["go_where"] - before["go_where"] == 12


def _vectorised_and_cpu_rows(c, g, monkeypatch, stmt):
    """(rows, the CPU executor's rows) of a device-served statement
    that may not fall back to the per-row evaluator."""
    rt = c.tpu_runtime

    def per_row(*a, **kw):
        raise AssertionError("fell back to the per-row evaluator")
    monkeypatch.setattr(rt, "_materialize_per_row", per_row)
    before = rt.stats["go_device"]
    got = g.execute(stmt)
    assert got.ok() and not got.warnings, got.error_msg
    assert rt.stats["go_device"] == before + 1
    with flags_set({"storage_backend": "cpu"}):
        want = g.execute(stmt)
    assert want.ok(), want.error_msg
    return sorted(map(tuple, got.rows)), sorted(map(tuple, want.rows))


def test_a_yield_of_such_doubles_is_vectorised(served_split, monkeypatch):
    """A YIELD compiles against the host's float64 column like the
    WHERE does: no statement falls to the per-row evaluator because
    float32 would not hold a value it is never given."""
    c, g, graph = served_split
    rows, want = _vectorised_and_cpu_rows(
        c, g, monkeypatch, "GO 2 STEPS FROM 3 OVER knows "
        "WHERE knows.w > 0.5 YIELD knows._dst, knows.w")
    assert rows == want and len(rows) > 20
    assert any(np.float64(np.float32(w)) != w for _, w in rows)


def test_no_csr_column_is_on_the_device_after_a_full_build(served):
    """The device holds the ELL tables; the mirror's edge arrays (2 x
    edges rows each) stay on the host, where the WHERE and the YIELD
    read them."""
    import jax
    c, g, graph = served
    rt = c.tpu_runtime
    _served_rows(g, _statement(2, ">", 0.5, 1))
    mirror = rt.mirror(c.graph_meta_client.get_space_id_by_name("w")
                       .value())
    assert rt.stats["mirror_builds"] >= 1 and mirror.m > 1000
    assert not hasattr(mirror, "_device")
    assert not [a.shape for a in jax.live_arrays()
                if a.shape == (mirror.m,)]


# ---- casts and integer arithmetic at the CPU executor's width -------
BIG = 1 << 40


@pytest.fixture(scope="module")
def served_wide():
    """(cluster, client): 40 edges out of vertex 1 whose double is no
    float32 and whose int is no int32."""
    with flags_set({**shipped_defaults(), "go_backend_router": False}):
        c = LocalCluster(num_storage=1, tpu_backend=True)
        g = c.client()
        for stmt in ("CREATE SPACE wide(partition_num=2, replica_factor=1)",
                     "USE wide", "CREATE EDGE e(w double, big int)"):
            r = g.execute(stmt)
            assert r.ok(), f"{stmt}: {r.error_msg}"
            c.refresh_all()
        r = g.execute("INSERT EDGE e(w, big) VALUES " + ", ".join(
            f"1->{10 + k}:({(k - 20) / 10!r}, {(k - 20) * BIG + k})"
            for k in range(40)))
        assert r.ok(), r.error_msg
        try:
            yield c, g
        finally:
            c.stop()


@pytest.mark.parametrize("where,yields", [
    ("", "(double)e.w, (int)e.big"),
    ("", "(int)e.w, (double)e.big, (int)(e.w * 1000000000000.0)"),
    ("", "e.big / 7, e.big % 1000003, -e.big / 3"),
    ("WHERE (double)e.w >= 0.30000001", "e.w"),
    (f"WHERE (int)e.big >= {3 * BIG + 23}", "e.big"),
    (f"WHERE e.big / 3 > {BIG} && e.big % 1000003 < 500000", "e.big"),
])
def test_casts_and_integer_arithmetic_keep_the_executor_s_width(
        served_wide, monkeypatch, where, yields):
    """``(double)``, ``(int)``, ``/`` and ``%`` evaluate in float64 /
    int64 on the device path's host pass, as the CPU executor does."""
    c, g = served_wide
    rows, want = _vectorised_and_cpu_rows(
        c, g, monkeypatch, f"GO FROM 1 OVER e {where} YIELD e._dst, {yields}")
    assert rows == want
    assert 0 < len(rows) <= 40 and (where == "") == (len(rows) == 40)
    assert [type(v) for v in rows[0]] == [type(v) for v in want[0]]
