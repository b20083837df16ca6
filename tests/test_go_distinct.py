"""The k-hop neighbourhood itself, ``GO k STEPS FROM v OVER knows YIELD
DISTINCT knows._dst`` with nothing piped behind it, through the
system's normal entry (LocalCluster, tpu_backend=True, the shipped
flags) against a brute-force walk, the benchmark's plain reference
(benchmark/semantics/go_distinct.py) and the CPU executor on a seeded
Kronecker graph of scale 11: the statement rides k hops on the lanes
and a leaver's own bitmap, unpacked, is its answer; the windowed tier,
a mesh and a bounce launch the plain (k+1)-step GO and answer with the
frontier arrays; every other DISTINCT stays on ``_distinct_rows`` and
still answers right; and the counters, the tick record and the spans
say what was ridden.  A statement without ORDER BY promises no row
order, so rows are compared as multisets.  CPU jax: no number here is
a device number."""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from benchmark import reference
from benchmark.deploy import flags_set, label_data, shipped_defaults
from benchmark.generators import kronecker
import nebula_tpu.graph.backend_router    # noqa: F401 — define the flags
import nebula_tpu.tpu.runtime as runtime_mod
from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import trace_store
from nebula_tpu.graph.batch_dispatch import ContinuousGoScheduler
from nebula_tpu.graph.interim import ColumnarRows
from nebula_tpu.tpu import ell as E

KS = [1, 2, 3, 4, 5, 6]
COUNTERS = ("go_device", "go_distinct", "distinct_hops",
            "distinct_vertices", "go_reduced", "go_count_distinct")


def _statement(k: int, start) -> str:
    return f"GO {k} STEPS FROM {start} OVER knows YIELD DISTINCT knows._dst"


def _brute(graph, starts, k: int) -> list:
    """Edge by edge over Python sets: nothing of numpy's marking."""
    frontier = set(int(v) for v in starts)
    for _ in range(k):
        frontier = {int(graph.dst[e]) for v in frontier
                    for e in range(graph.ptr[v], graph.ptr[v + 1])}
    return sorted((v,) for v in frontier)


@pytest.fixture(scope="module")
def served():
    """(cluster, client, reference graph, the start vertices by name)
    on the Kronecker graph the configuration's rehearsal loads (scale
    11, edge factor 8), plus a second edge type for the two-edge OVER.
    The hop's push budget is cut to 8 live rows, so the lanes take
    both branches of the hop, and the slot width is capped at 256 so
    the widest vertex is a hub with an extra row: after a pull the
    extra rows hold partial ORs, which no answer may show."""
    data = label_data(kronecker.generate(
        {"scale": 11, "edgefactor": 8, "A": 0.57, "B": 0.19, "C": 0.19,
         "edge_prop": "w", "weight_levels": 16}, 50020), seed=38)
    src, dst = data["src"], data["dst"]
    graph = reference.Graph(src, dst, data["edge_prop_table"],
                            data["edge_prop_idx"])
    saved_push = E.HOP_PUSH_ROWS
    E.HOP_PUSH_ROWS = 8
    with flags_set({**shipped_defaults(), "go_backend_router": False,
                    "tpu_prewarm_kernels": False, "tpu_ell_cap": 256}):
        c = LocalCluster(num_storage=1, tpu_backend=True)
        g = c.client()

        def ok(stmt):
            r = g.execute(stmt)
            assert r.ok(), f"{stmt[:80]}: {r.error_msg}"
            return r
        ok("CREATE SPACE k(partition_num=4, replica_factor=1)")
        c.refresh_all()
        ok("USE k")
        ok("CREATE EDGE knows(w double)")
        ok("CREATE EDGE likes()")
        c.refresh_all()
        # half of the weights under the WHERE shape's constant
        for lo in range(0, len(src), 2000):
            ok("INSERT EDGE knows(w) VALUES " + ", ".join(
                f"{s}->{d}:({0.25 + 0.5 * ((s + d) % 2)})"
                for s, d in zip(src[lo:lo + 2000], dst[lo:lo + 2000])))
        ok("INSERT EDGE likes() VALUES " + ", ".join(
            f"{s}->{d}:()" for s, d in zip(src[:300], dst[::-1][:300])))
        try:
            yield c, g, graph, _named_starts(c, graph)
        finally:
            c.stop()
            E.HOP_PUSH_ROWS = saved_push


def _named_starts(c, graph) -> dict:
    """A hub that owns extra rows of the ELL table, a vertex whose
    neighbours are all sinks (no walk of two edges), and for each k a
    vertex that a walk of exactly k edges returns to."""
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("k").value()
    m = rt.mirror(sid)
    ix = rt.ell(m)
    owners = np.unique(ix.extra_owner[ix.extra_owner < ix.n])
    assert len(owners), "the graph has no hub with extra rows"
    hub = int(m.vids[ix.inv[owners[0]]])
    have_out = np.nonzero(graph.deg > 0)[0]
    sink_parent = next(int(v) for v in have_out
                       if not graph.deg[graph.frontier(int(v), 1)].any())
    returns = {k: next((int(v) for v in have_out
                        if v in graph.frontier(int(v), k)), None)
               for k in KS}
    assert returns[1] is None and all(returns[k] for k in KS[1:])
    return {"hub": hub, "sink_parent": sink_parent, "returns": returns,
            "others": [int(v) for v in have_out[7:400:41]]}


def _starts(named: dict, k: int) -> list:
    out = [named["hub"], named["sink_parent"]] + named["others"]
    if named["returns"][k]:
        out.append(named["returns"][k])
    return out


def _resp(client, stmt):
    resp = client.execute(stmt)
    assert resp.ok(), f"{stmt}: {resp.error_msg}"
    assert not resp.warnings and resp.completeness == 100, stmt
    return resp


def _rows(client, stmt) -> list:
    """The response's rows as a sorted multiset."""
    return sorted(tuple(r) for r in _resp(client, stmt).rows)


def _cpu_rows(client, stmt) -> list:
    with flags_set({"storage_backend": "cpu"}):
        return _rows(client, stmt)


def _check_tier(served, k, tier_flags, dense=False):
    """Every named start at k steps under ``tier_flags``: the brute
    force walk, the plain reference and the CPU executor agree with
    the served rows, no vertex twice; returns the counters' growth and
    the number of statements."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    starts = _starts(named, k)
    sem = {"kind": "go_distinct", "steps": k}
    total = 0
    with flags_set({**tier_flags, "tpu_sparse_go": not dense}):
        before = {key: rt.stats[key] for key in COUNTERS}
        for start in starts:
            stmt = _statement(k, start)
            resp = _resp(g, stmt)
            assert list(resp.column_names) == ["knows._dst"]
            got = sorted(tuple(r) for r in resp.rows)
            want = _brute(graph, [start], k)
            assert got == want, (k, tier_flags, start)
            assert len(set(got)) == len(got)
            assert reference.same_rows(
                (np.asarray([r[0] for r in got], np.int64),),
                graph.answer(sem, start))
            assert _cpu_rows(g, stmt) == want
            total += len(want)
        grew = {key: rt.stats[key] - before[key] for key in COUNTERS}
    # no row where no k-walk exists; a returning walk yields its start
    assert (_brute(graph, [named["sink_parent"]], k) == []) == (k > 1)
    if named["returns"][k]:
        v = named["returns"][k]
        assert (v,) in _brute(graph, [v], k)
    # answered by the reduction, by k hops each, nothing counted
    assert grew == {"go_device": len(starts), "go_distinct": len(starts),
                    "distinct_hops": k * len(starts),
                    "distinct_vertices": total,
                    "go_reduced": len(starts), "go_count_distinct": 0}
    return len(starts)


@pytest.mark.parametrize("k", KS)
def test_the_neighbourhood_rides_k_hops_on_the_lanes(served, k):
    c, g, graph, named = served
    rt = c.tpu_runtime
    launched = {key: rt.stats[key] for key in ("go_sparse", "go_dense")}
    joined = rt.dispatcher.stats.get("continuous_queries", 0)
    n = _check_tier(served, k, {"go_dispatch_mode": "continuous"})
    # every statement a rider of the continuous tier, no windowed
    # launch, no candidate edge assembled
    assert rt.dispatcher.stats["continuous_queries"] - joined == n
    assert {key: rt.stats[key] - v for key, v in launched.items()} \
        == {"go_sparse": 0, "go_dense": 0}


@pytest.mark.parametrize("k", KS)
def test_the_windowed_tier_answers_with_the_frontier_arrays(served, k):
    c, g, graph, named = served
    rt = c.tpu_runtime
    # the pair-list program at 1 and 2 steps, the dense lanes program
    # beyond (the pair-list program of four and more advances takes
    # CPU jax minutes to compile)
    dense = k > 2
    launched = {key: rt.stats[key] for key in ("go_sparse", "go_dense")}
    n = _check_tier(served, k, {"go_dispatch_mode": "windowed"},
                    dense=dense)
    assert {key: rt.stats[key] - v for key, v in launched.items()} \
        == {"go_sparse": 0 if dense else n, "go_dense": n if dense else 0}


@pytest.mark.parametrize("k", [1, 3])
def test_a_bounce_off_the_continuous_tier_is_answered_windowed(
        served, k, monkeypatch):
    """A stream that cannot anchor a session (continuous_session gives
    None: a mesh, an unbuildable mirror) bounces its riders typed; the
    windowed pipeline answers them by the same reduction."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    monkeypatch.setattr(rt, "continuous_session",
                        lambda *a, **kw: None)
    for st in rt.dispatcher.continuous.streams():
        with st.cond:           # idle: nobody seated, nobody queued
            assert not st.seated and not st.queue
            st.session = None   # the next rider's tick anchors anew
    joined = rt.dispatcher.stats.get("continuous_queries", 0)
    _check_tier(served, k, {"go_dispatch_mode": "continuous"},
                dense=k > 2)
    assert rt.dispatcher.stats.get("continuous_queries", 0) == joined


def test_a_mesh_answers_with_the_frontier_arrays():
    """``tpu_mesh_devices`` > 1 keeps the statement off the lanes; the
    mesh's plain (k+1)-step GO gives the same frontier."""
    rng = np.random.default_rng(38)
    n = 60
    key = np.unique(rng.integers(1, 40, 260) * 100
                    + rng.integers(1, n, 260))
    src, dst = key // 100, key % 100
    keep = src != dst
    src, dst = src[keep], dst[keep]
    graph = reference.Graph(src, dst, [{"w": 0.0}],
                            np.zeros(len(src), np.int64))
    with flags_set({**shipped_defaults(), "go_backend_router": False,
                    "tpu_prewarm_kernels": False}):
        c = LocalCluster(num_storage=1, tpu_backend=True)
        try:
            g = c.client()
            for stmt in ("CREATE SPACE ms(partition_num=2, "
                         "replica_factor=1)",):
                assert g.execute(stmt).ok()
            c.refresh_all()
            assert g.execute("USE ms").ok()
            assert g.execute("CREATE EDGE knows(w double)").ok()
            c.refresh_all()
            assert g.execute("INSERT EDGE knows(w) VALUES " + ", ".join(
                f"{s}->{d}:(0.5)" for s, d in zip(src, dst))).ok()
            rt = c.tpu_runtime
            with flags_set({"tpu_mesh_devices": 8,
                            "tpu_mesh_mode": "dense"}):
                rt.mirrors.clear()      # rebuild under the mesh gate
                before = rt.stats["go_distinct"]
                for k in (1, 2, 3):
                    for start in (1, 2, 17):
                        assert _rows(g, _statement(k, start)) \
                            == _brute(graph, [start], k), (k, start)
                assert rt.stats["go_distinct"] - before == 9
                assert not ContinuousGoScheduler.route_eligible(
                    ("go_batch_execute", 1, (1,), 2, False,
                     ("distinct",)))
        finally:
            c.stop()


def test_several_starts_are_one_neighbourhood(served):
    """The k-th frontier of a set of starts: what DISTINCT over the
    rows of all of them keeps."""
    c, g, graph, named = served
    starts = named["others"][:3] + [named["hub"]]
    for k in (1, 2, 3):
        stmt = _statement(k, ", ".join(str(v) for v in starts))
        for mode in ("continuous", "windowed"):
            with flags_set({"go_dispatch_mode": mode}):
                assert _rows(g, stmt) == _brute(graph, starts, k)
        assert _cpu_rows(g, stmt) == _brute(graph, starts, k)


def _brute_reversely(graph, start: int, k: int) -> list:
    """The walk against the edges: the sources of the in-edges."""
    src = np.repeat(np.arange(len(graph.deg)), graph.deg)
    frontier = {int(start)}
    for _ in range(k):
        frontier = {int(s) for s in src[np.isin(graph.dst,
                                                 sorted(frontier))]}
    return sorted((v,) for v in frontier)


@pytest.mark.parametrize("mode", ["continuous", "windowed"])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_reversely_is_reduced_like_the_forward_walk(served, k, mode):
    """The sign flip reads the other table; the lanes, the leaver's
    bitmap and the counters are the forward statement's."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    starts = [named["hub"]] + named["others"][:3]
    with flags_set({"go_dispatch_mode": mode, "tpu_sparse_go": k <= 2}):
        before = {key: rt.stats[key] for key in COUNTERS}
        total = 0
        for start in starts:
            stmt = (f"GO {k} STEPS FROM {start} OVER knows REVERSELY "
                    f"YIELD DISTINCT knows._dst")
            want = _brute_reversely(graph, start, k)
            assert _rows(g, stmt) == want, (k, mode, start)
            assert _cpu_rows(g, stmt) == want
            total += len(want)
        grew = {key: rt.stats[key] - before[key] for key in COUNTERS}
    assert total
    assert grew == {"go_device": len(starts), "go_distinct": len(starts),
                    "distinct_hops": k * len(starts),
                    "distinct_vertices": total,
                    "go_reduced": len(starts), "go_count_distinct": 0}


def _first_seen(rows) -> list:
    seen, out = set(), []
    for r in rows:
        if tuple(r) not in seen:
            seen.add(tuple(r))
            out.append(tuple(r))
    return out


NOT_REDUCED = {
    "two_columns": "GO 2 STEPS FROM {v} OVER knows "
                   "YIELD DISTINCT knows._dst, knows._src",
    "src": "GO 2 STEPS FROM {v} OVER knows YIELD DISTINCT knows._src",
    "rank": "GO 2 STEPS FROM {v} OVER knows "
            "YIELD DISTINCT knows._dst, knows._rank",
    "where": "GO 2 STEPS FROM {v} OVER knows WHERE knows.w > 0.5 "
             "YIELD DISTINCT knows._dst",
    "upto": "GO UPTO 2 STEPS FROM {v} OVER knows "
            "YIELD DISTINCT knows._dst",
    "over_all": "GO 1 STEPS FROM {v} OVER * YIELD DISTINCT knows._dst",
    "two_edge_over": "GO 1 STEPS FROM {v} OVER knows, likes "
                     "YIELD DISTINCT knows._dst",
    "piped_input": "GO 1 STEPS FROM {v} OVER knows YIELD knows._dst AS d "
                   "| GO 1 STEPS FROM $-.d OVER knows "
                   "YIELD DISTINCT knows._dst",
    "limit_behind": "GO 2 STEPS FROM {v} OVER knows "
                    "YIELD DISTINCT knows._dst | LIMIT 5",
}


@pytest.mark.parametrize("shape", sorted(NOT_REDUCED))
def test_every_other_distinct_stays_on_distinct_rows(served, shape,
                                                     monkeypatch):
    """Not reduced (no counter moves), de-duplicated by _distinct_rows
    where the device path serves it, and the CPU executor's rows."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    calls = []
    real = runtime_mod._distinct_rows

    def spy(rows):
        out = real(rows)
        calls.append((len(rows), len(out)))
        return out
    monkeypatch.setattr(runtime_mod, "_distinct_rows", spy)
    before = {key: rt.stats[key]
              for key in ("go_distinct", "go_device")}
    starts = [named["hub"]] + named["others"][:4]
    for start in starts:
        stmt = NOT_REDUCED[shape].format(v=start)
        got = _rows(g, stmt)
        assert got, stmt
        assert len(set(got)) == len(got)
        if shape == "limit_behind":
            # the cut takes the first rows of _distinct_rows' order;
            # which five is the route's choice, that they are five of
            # the neighbourhood is not
            assert len(got) == 5
            assert set(got) <= set(_brute(graph, [start], 2))
        else:
            assert _cpu_rows(g, stmt) == got, stmt
    assert rt.stats["go_distinct"] == before["go_distinct"]
    served_here = rt.stats["go_device"] - before["go_device"]
    # one pass of _distinct_rows for each DISTINCT GO the device served
    assert len(calls) == len(starts) and served_here >= len(starts)
    assert all(kept <= n for n, kept in calls)


def test_a_limit_behind_keeps_the_order_of_distinct_rows(served):
    """``| LIMIT`` cuts the rows as they come, so the GO at its left
    hands them in first-occurrence order: the same five as the
    unreduced rows de-duplicated in order."""
    c, g, graph, named = served
    start = named["others"][1]
    plain = [tuple(r) for r in _resp(
        g, f"GO 2 STEPS FROM {start} OVER knows YIELD knows._dst").rows]
    cut = [tuple(r) for r in _resp(
        g, _statement(2, start) + " | LIMIT 5").rows]
    assert cut == _first_seen(plain)[:5]
    # an ORDER BY between them makes the cut exact, and the GO at its
    # left (no LIMIT right behind it) is reduced again
    before = c.tpu_runtime.stats["go_distinct"]
    ordered = [tuple(r) for r in _resp(
        g, f"GO 2 STEPS FROM {start} OVER knows YIELD DISTINCT "
           f"knows._dst AS d | ORDER BY $-.d | LIMIT 5").rows]
    assert ordered == _brute(graph, [start], 2)[:5]
    assert c.tpu_runtime.stats["go_distinct"] == before + 1


def test_the_shape_gate_names_one_shape():
    from nebula_tpu.graph.executors.traverse import (_go_distinct_dst,
                                                     _go_reduce_shape)
    from nebula_tpu.graph.parser import GQLParser

    def go(stmt):
        r = GQLParser().parse(stmt)
        assert r.ok(), stmt
        return r.value().sentences[0]

    assert _go_distinct_dst(go(_statement(3, 1)))
    assert _go_distinct_dst(go(
        "GO FROM 1 OVER knows AS k YIELD DISTINCT k._dst AS d"))
    # the k-th frontier is the k-th frontier whatever tables the hops
    # read: one edge name forwards, from its far end or from either
    for word in ("REVERSELY", "BIDIRECT"):
        assert _go_distinct_dst(go(
            f"GO 2 STEPS FROM 1 OVER knows {word} "
            f"YIELD DISTINCT knows._dst"))
        assert not _go_distinct_dst(go(
            f"GO 2 STEPS FROM 1 OVER knows, likes {word} "
            f"YIELD DISTINCT knows._dst"))
        assert not _go_distinct_dst(go(
            f"GO 2 STEPS FROM 1 OVER * {word} YIELD DISTINCT knows._dst"))
    for stmt in (v.format(v=1) for k, v in NOT_REDUCED.items()
                 if k not in ("piped_input", "limit_behind")):
        assert not _go_distinct_dst(go(stmt)), stmt
    assert not _go_distinct_dst(go(
        "GO 3 STEPS FROM 1 OVER knows YIELD knows._dst"))
    assert not _go_distinct_dst(go("GO 3 STEPS FROM 1 OVER knows"))
    # the pipe's own reductions are as they were
    piped = go(_statement(3, 1) + " | YIELD COUNT(*)")
    assert _go_reduce_shape(piped.left, piped.right) \
        == ("count_distinct", "COUNT()")
    piped = go(_statement(3, 1) + " | LIMIT 3")
    assert _go_reduce_shape(piped.left, piped.right) is None


def test_the_count_still_counts_and_does_not_fetch(served):
    """``| YIELD COUNT(*)`` behind the same GO is the pipe's
    reduction: the executor's own does not displace it."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    before = {key: rt.stats[key] for key in COUNTERS}
    start = named["others"][2]
    assert _rows(g, _statement(3, start) + " | YIELD COUNT(*)") \
        == [(len(_brute(graph, [start], 3)),)]
    grew = {key: rt.stats[key] - before[key] for key in COUNTERS}
    assert grew["go_count_distinct"] == 1 and grew["go_distinct"] == 0


def test_the_configuration_s_set_up_statements_are_taken(served):
    """``query_deadline_ms`` is a managed flag (no new flag, no new
    value), so the neighbourhood configuration's set-up statements are
    taken by this program, a tree that does not manage it refuses the
    second pin, and a flag nobody manages is refused here too; the
    deadline a statement gets is the flag's value when it arrives."""
    from benchmark import run
    c, g, graph, named = served
    config = run.load_json(run.ROOT, "benchmark", "configs",
                           "graph500-s20-neigh.json")
    pins = [s for s in config["schema"] if s.startswith("UPDATE CONFIGS")]
    assert pins == ["UPDATE CONFIGS graph:go_dispatch_mode=continuous",
                    "UPDATE CONFIGS graph:query_deadline_ms=300000"]
    saved = flags.get("query_deadline_ms")
    try:
        resp = g.execute("UPDATE CONFIGS graph:query_deadline_ms=120000")
        assert resp.ok(), resp.error_msg
        assert flags.get("query_deadline_ms") == 120000
        for pin in pins:
            resp = g.execute(pin)
            assert resp.ok(), f"{pin}: {resp.error_msg}"
        assert flags.get("query_deadline_ms") == 300000
        assert flags.get("go_dispatch_mode") == "continuous"
        assert not g.execute(
            "UPDATE CONFIGS graph:query_no_such_deadline_ms=300000").ok()
    finally:
        flags.set("query_deadline_ms", saved, force=True)
    start = named["others"][1]
    assert _rows(g, _statement(2, start)) == _brute(graph, [start], 2)


def _burst(c, statements):
    out, errors = {}, []
    barrier = threading.Barrier(len(statements))

    def worker(i):
        try:
            g2 = c.client()
            g2.execute("USE k")
            barrier.wait()
            out[i] = _rows(g2, statements[i])
        except Exception as ex:     # noqa: BLE001 — reported below
            errors.append(ex)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(statements))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errors, errors
    end = time.monotonic() + 5.0
    while time.monotonic() < end and \
            c.tpu_runtime.dispatcher.continuous.seat_counts() != (0, 0):
        time.sleep(0.01)
    time.sleep(0.05)
    return [out[i] for i in range(len(statements))]


def test_a_cohort_of_all_three_leavers_and_the_records_say_so(served):
    """Neighbourhoods beside counts and plain rows in the same ticks:
    each gets its own answer, the tick record says how many leavers'
    frontiers were their answers, and the spans and marker tags are
    where the metrics read them."""
    c, g, graph, named = served
    statements = []
    for i, v in enumerate(named["others"][:8]):
        k = 2 + i % 2
        statements.append((_statement(k, v), _brute(graph, [v], k)))
        if i % 2:
            statements.append((
                _statement(k, v) + " | YIELD COUNT(*)",
                [(len(_brute(graph, [v], k)),)]))
        else:
            statements.append((
                f"GO {k} STEPS FROM {v} OVER knows YIELD knows._dst",
                sorted((int(d),) for d in graph.dst[graph.edge_positions(
                    graph.frontier(v, k - 1))])))
    saved = flags.get("trace_sample_rate")
    trace_store.clear_for_tests()
    flight.recorder.clear_for_tests()
    flags.set("trace_sample_rate", 1.0)
    try:
        got = _burst(c, [s for s, _ in statements])
    finally:
        flags.set("trace_sample_rate", saved)
    for (stmt, want), rows in zip(statements, got):
        assert rows == want, stmt
    ticks = [r for r in flight.recorder.dump(limit=4096)
             if r["kind"] == "tick"]
    assert sum(t["leaves"] for t in ticks) == 16
    assert sum(t["distinct"] for t in ticks) == 8
    assert sum(t["counted"] for t in ticks) == 4
    assert sum(t["handed"] for t in ticks) == 12
    assert sum(t["unpack_leavers"] for t in ticks) == 12
    for t in ticks:
        assert t["distinct"] <= t["handed"] <= t["unpack_leavers"]
        parts = ("fetch_wait_us", "d2h_us", "unpack_us", "rows_us",
                 "handover_us")
        assert t["assemble_us"] == sum(t[p] for p in parts)
    trees = [trace_store.tree(int(s["id"], 16))
             for s in trace_store.summaries()]

    def walk(node):
        yield node
        for ch in node.get("children", ()):
            yield from walk(ch)
    spans = [n for t in trees for r in t["roots"] for n in walk(r)]
    # no span name the benchmark's breakdown does not know and would
    # charge to ``other``: a neighbourhood's tree holds the names a
    # GO's rows always had
    from benchmark.spans import PHASE_OF
    for t in trees:
        nodes = [n for r in t["roots"] for n in walk(r)]
        if any(n["tags"].get("reduce") == "distinct" for n in nodes
               if n["name"] == "tpu.assemble"):
            assert {n["name"] for n in nodes
                    if n["name"].startswith("tpu.")} <= set(PHASE_OF)
    made = [n for n in spans if n["name"] == "tpu.assemble"
            and n["tags"].get("reduce") == "distinct"]
    assert len(made) == 8
    assert sorted(n["tags"]["vertices"] for n in made) \
        == sorted(len(want) for s, want in statements[::2])
    markers = [n["tags"] for n in spans if n["name"] == "graph.continuous"
               and n["tags"].get("reduce") == "distinct"]
    assert len(markers) == 8
    assert sorted(t["hops"] for t in markers) == [2, 2, 2, 2, 3, 3, 3, 3]
    assert all("assemble_us" in t and "assemble_cpu_us" in t
               for t in markers)


def test_a_wide_frontier_leaves_by_the_whole_bitmap_route(served):
    """A neighbourhood of over a fifth of the vertex rows
    (LANE_UNPACK_LIVE_SHARE) is unpacked out of its whole bitmap
    through ``perm``, a small one out of its non-zero bytes: the same
    ids either way, and the tick record says which."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("k").value()
    n = rt.ell(rt.mirror(sid)).n
    start = named["hub"]
    wide = _brute(graph, [start], 3)
    small = _brute(graph, [named["others"][0]], 1)
    assert len(wide) > runtime_mod.LANE_UNPACK_LIVE_SHARE * n > len(small)
    flight.recorder.clear_for_tests()
    with flags_set({"go_dispatch_mode": "continuous"}):
        assert _rows(g, _statement(3, start)) == wide
        assert _rows(g, _statement(1, named["others"][0])) == small
    ticks = sorted((r for r in flight.recorder.dump(limit=4096)
                    if r["kind"] == "tick" and r["unpack_leavers"]),
                   key=lambda r: r["time_us"])
    assert [(t["unpack_leavers"], t["unpack_live"]) for t in ticks] \
        == [(1, 0), (1, 1)]
    assert [t["unpack_rows"] for t in ticks] == [len(wide), len(small)]


def test_distinct_results_is_the_frontier_under_the_mirror_s_ids(served):
    c, g, graph, named = served
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("k").value()
    m = rt.mirror(sid)
    from types import SimpleNamespace
    from nebula_tpu.filter.expressions import EdgeDstIdExpr
    q = SimpleNamespace(yield_cols=[SimpleNamespace(
        expr=EdgeDstIdExpr("knows"), alias=None)])
    named_q = SimpleNamespace(yield_cols=[SimpleNamespace(
        expr=EdgeDstIdExpr("knows"), alias="d")])
    before = {key: rt.stats[key] for key in COUNTERS}
    out = rt.distinct_results(
        m, [q, named_q, q], [np.asarray([0, 2, 5]), [1], []], [2, 3, 6])
    assert [cols for cols, _rows in out] \
        == [["knows._dst"], ["d"], ["knows._dst"]]
    assert isinstance(out[0][1], ColumnarRows)
    assert out[0][1]._cols[0].dtype == np.int64
    assert out[0][1]._cols[0].tolist() == m.vids[[0, 2, 5]].tolist()
    assert [list(r) for r in out[1][1]] == [[int(m.vids[1])]]
    assert out[2][1] == []
    grew = {key: rt.stats[key] - before[key] for key in COUNTERS}
    assert grew == {"go_device": 0, "go_distinct": 3, "distinct_hops": 11,
                    "distinct_vertices": 4, "go_reduced": 3,
                    "go_count_distinct": 0}


@pytest.mark.parametrize("seed", [1, 38, 3_999_999_999])
def test_distinct_rows_of_one_column_take_the_plain_unique(seed,
                                                           monkeypatch):
    """A single integer column goes through numpy's 1-D unique and
    never through ``axis=0`` (the structured view that sorts eight
    times as long); several columns still do.  Same rows, same order
    as the row-by-row loop."""
    rng = np.random.default_rng(seed)
    col = rng.integers(-50, 50, 4000)
    axes = []
    real = np.unique

    def spy(a, *args, **kw):
        axes.append(kw.get("axis"))
        return real(a, *args, **kw)
    monkeypatch.setattr(runtime_mod.np, "unique", spy)
    one = runtime_mod._distinct_rows(ColumnarRows([col], len(col)))
    assert axes == [None]
    assert isinstance(one, ColumnarRows)
    assert [r[0] for r in one] == [r[0] for r in _first_seen(
        (int(v),) for v in col)]
    other = rng.integers(0, 3, 4000)
    two = runtime_mod._distinct_rows(ColumnarRows([col, other], len(col)))
    assert axes == [None, 0]
    assert [tuple(r) for r in two] == _first_seen(
        zip(col.tolist(), other.tolist()))
