"""The k-hop neighbourhood count, ``GO k STEPS FROM v OVER knows YIELD
DISTINCT knows._dst | YIELD COUNT(*)``, through the system's normal
entry (LocalCluster, tpu_backend=True, the shipped flags) against the
benchmark's plain reference (benchmark/semantics/go_count_distinct.py)
on a seeded Kronecker graph of scale 11: the statement rides k hops on
the lanes and leaves with the size of its k-th frontier, counted on
the device over the real vertex rows; the windowed tier and the CPU
executor give the same answer; every other DISTINCT stays unreduced
and still answers right; and the counters, the tick record and the
spans say what was counted.  CPU jax: no number here is a device
number."""
from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from benchmark import reference
from benchmark.deploy import flags_set, label_data, shipped_defaults
from benchmark.generators import kronecker
from benchmark.semantics import go_count_distinct as khop
import nebula_tpu.graph.backend_router    # noqa: F401 — define the flags
import nebula_tpu.tpu.runtime as runtime_mod
from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import trace_store
from nebula_tpu.graph.interim import ColumnarRows
from nebula_tpu.tpu import ell as E

KS = [1, 2, 3, 6]
MODES = ["continuous", "windowed"]
COUNTERS = ("go_device", "go_count_distinct", "count_distinct_hops",
            "count_distinct_vertices", "go_reduced")


def _statement(k: int, start: int) -> str:
    return (f"GO {k} STEPS FROM {start} OVER knows "
            f"YIELD DISTINCT knows._dst | YIELD COUNT(*)")


@pytest.fixture(scope="module")
def served():
    """(cluster, client, reference graph, the start vertices by name)
    on the Kronecker graph the khop configuration's rehearsal loads
    (scale 11, edge factor 8), plus a second edge type for the
    two-edge OVER.  The hop's push budget is cut to 8 live rows, so
    the lanes take both branches of the hop: after a pull the hub
    extra rows hold partial ORs, which the count must not see."""
    data = label_data(kronecker.generate(
        {"scale": 11, "edgefactor": 8, "A": 0.57, "B": 0.19, "C": 0.19,
         "edge_prop": "w", "weight_levels": 16}, 50020), seed=33)
    src, dst = data["src"], data["dst"]
    graph = reference.Graph(src, dst, data["edge_prop_table"],
                            data["edge_prop_idx"])
    saved_push = E.HOP_PUSH_ROWS
    E.HOP_PUSH_ROWS = 8
    # no background compiles of the other rungs: on CPU jax the
    # windowed tier's sort-based programs take minutes each
    # slot width capped at 256: the rehearsal graph's widest vertex has
    # 403 in-edges, a hub with an extra row at that cap
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
        for lo in range(0, len(src), 2000):
            ok("INSERT EDGE knows(w) VALUES " + ", ".join(
                f"{s}->{d}:(0.5)"
                for s, d in zip(src[lo:lo + 2000], dst[lo:lo + 2000])))
        ok("INSERT EDGE likes() VALUES " + ", ".join(
            f"{s}->{d}:()" for s, d in zip(src[:300], dst[::-1][:300])))
        try:
            yield c, g, graph, _named_starts(c, graph)
        finally:
            c.stop()
            E.HOP_PUSH_ROWS = saved_push


def _named_starts(c, graph) -> dict:
    """The start vertices the issue names, found on the loaded graph:
    a hub that owns extra rows of the ELL table, a vertex whose
    neighbours are all sinks, and for each k a vertex that a walk of
    exactly k edges returns to."""
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("k").value()
    m = rt.mirror(sid)
    ix = rt.ell(m)
    owners = np.unique(ix.extra_owner[ix.extra_owner < ix.n])
    assert len(owners), "the graph has no hub with extra rows"
    assert len(ix.extra_owner) > len(owners) or \
        (ix.extra_owner >= ix.n).any(), "no spare row to leave out"
    hub = int(m.vids[ix.inv[owners[0]]])
    have_out = np.nonzero(graph.deg > 0)[0]
    sink_parent = next(int(v) for v in have_out
                       if not graph.deg[graph.frontier(int(v), 1)].any())
    returns = {k: next((int(v) for v in have_out
                        if v in graph.frontier(int(v), k)), None)
               for k in KS}
    assert returns[1] is None and all(returns[k] for k in KS[1:])
    return {"hub": hub, "sink_parent": sink_parent, "returns": returns,
            "others": [int(v) for v in have_out[5:400:37]]}


def _starts(named: dict, k: int) -> list:
    out = [named["hub"], named["sink_parent"]] + named["others"]
    if named["returns"][k]:
        out.append(named["returns"][k])
    return out


def _rows(client, stmt):
    resp = client.execute(stmt)
    assert resp.ok(), f"{stmt}: {resp.error_msg}"
    assert not resp.warnings and resp.completeness == 100, stmt
    return [tuple(r) for r in resp.rows]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("k", KS)
def test_khop_count_matches_the_plain_reference(served, k, mode):
    c, g, graph, named = served
    rt = c.tpu_runtime
    starts = _starts(named, k)
    sem = {"kind": "go_count_distinct", "steps": k}
    # the windowed tier serves the count from whichever of its
    # programs the starts select: the pair-list program at 1 and 2
    # steps here, the dense lanes program at 3 and 6 (the pair-list
    # program of 4 and 7 advances takes CPU jax minutes to compile)
    dense = mode == "windowed" and k > 2
    with flags_set({"go_dispatch_mode": mode,
                    "tpu_sparse_go": not dense}):
        before = {key: rt.stats[key] for key in COUNTERS}
        launched = {key: rt.stats[key] for key in ("go_sparse",
                                                   "go_dense")}
        fetched = rt.stats["fetch_bytes"]
        total = 0
        for start in starts:
            want = graph.answer(sem, start)
            assert _rows(g, _statement(k, start)) == want, (k, mode, start)
            total += want[0][0] if want else 0
        grew = {key: rt.stats[key] - before[key] for key in COUNTERS}
        fetched = rt.stats["fetch_bytes"] - fetched
    # the sink parent: its neighbours end every walk, so no row past
    # one hop; the returning seed counts itself
    want = graph.answer(sem, named["sink_parent"])
    assert (want == []) == (k > 1)
    if named["returns"][k]:
        v = named["returns"][k]
        assert v in graph.frontier(v, k) and graph.answer(sem, v)
    # answered by the reduction, by k hops each, and counted
    assert grew == {"go_device": len(starts),
                    "go_count_distinct": len(starts),
                    "count_distinct_hops": k * len(starts),
                    "count_distinct_vertices": total,
                    "go_reduced": len(starts)}
    launched = {key: rt.stats[key] - n for key, n in launched.items()}
    if mode == "continuous":
        # one int32 a lane a statement crosses the link, no column
        assert fetched == len(starts) * 4 * 128
        assert launched == {"go_sparse": 0, "go_dense": 0}
    else:
        assert launched == {"go_sparse": 0 if dense else len(starts),
                            "go_dense": len(starts) if dense else 0}


@pytest.mark.parametrize("k", KS)
def test_the_cpu_executor_gives_the_same_rows(served, k):
    c, g, graph, named = served
    sem = {"kind": "go_count_distinct", "steps": k}
    rt = c.tpu_runtime
    before = rt.stats["go_device"]
    with flags_set({"storage_backend": "cpu"}):
        for start in _starts(named, k):
            assert _rows(g, _statement(k, start)) \
                == graph.answer(sem, start), (k, start)
    assert rt.stats["go_device"] == before      # the device sat out


def _distinct_pairs(graph, start, k):
    last = graph.frontier(start, k - 1)
    pos = graph.edge_positions(last)
    src = np.repeat(last, graph.deg[last])
    return len({(int(d), int(s)) for d, s in zip(graph.dst[pos], src)})


NOT_REDUCED = {
    "two_columns": (
        "GO 2 STEPS FROM {v} OVER knows YIELD DISTINCT knows._dst, "
        "knows._src | YIELD COUNT(*)",
        lambda graph, v: _distinct_pairs(graph, v, 2)),
    "where": (
        "GO 2 STEPS FROM {v} OVER knows WHERE knows.w > 0.1 "
        "YIELD DISTINCT knows._dst | YIELD COUNT(*)",
        lambda graph, v: len(graph.frontier(v, 2))),
    "upto": (
        "GO UPTO 2 STEPS FROM {v} OVER knows YIELD DISTINCT knows._dst "
        "| YIELD COUNT(*)",
        lambda graph, v: len(np.union1d(graph.frontier(v, 1),
                                        graph.frontier(v, 2)))),
    "two_edge_over": (
        "GO 1 STEPS FROM {v} OVER knows, likes YIELD DISTINCT knows._dst "
        "| YIELD COUNT(*)", None),
}


@pytest.mark.parametrize("shape", sorted(NOT_REDUCED))
def test_every_other_distinct_stays_unreduced_and_right(served, shape):
    c, g, graph, named = served
    rt = c.tpu_runtime
    template, want_of = NOT_REDUCED[shape]
    before = rt.stats["go_count_distinct"]
    for start in [named["hub"]] + named["others"][:4]:
        stmt = template.format(v=start)
        got = _rows(g, stmt)
        with flags_set({"storage_backend": "cpu"}):
            assert _rows(g, stmt) == got, stmt
        if want_of is not None:
            n = want_of(graph, start)
            assert got == ([(n,)] if n else []), stmt
        else:
            assert got and got[0][0] > 0
    assert rt.stats["go_count_distinct"] == before


def test_the_shape_gate_names_one_shape():
    from nebula_tpu.graph.executors.traverse import _go_reduce_shape
    from nebula_tpu.graph.parser import GQLParser

    def shape(stmt):
        r = GQLParser().parse(stmt)
        assert r.ok(), stmt
        piped = r.value().sentences[0]
        return _go_reduce_shape(piped.left, piped.right)

    tail = " | YIELD COUNT(*)"
    assert shape("GO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._dst"
                 + tail) == ("count_distinct", "COUNT()")
    assert shape("GO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._dst"
                 " | YIELD COUNT(*) AS n") == ("count_distinct", "n")
    assert shape("GO 3 STEPS FROM 1 OVER knows YIELD knows._dst" + tail) \
        == ("count", "COUNT()")
    # one edge name from its far end, or from either: the same shape
    for word in ("REVERSELY", "BIDIRECT"):
        assert shape(f"GO 3 STEPS FROM 1 OVER knows {word} "
                     f"YIELD DISTINCT knows._dst" + tail) \
            == ("count_distinct", "COUNT()")
    for left in (
            "GO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._src",
            "GO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._dst, "
            "knows._rank",
            "GO 3 STEPS FROM 1 OVER knows, likes REVERSELY YIELD DISTINCT "
            "knows._dst",
            "GO 3 STEPS FROM 1 OVER * BIDIRECT YIELD DISTINCT knows._dst",
            "GO 3 STEPS FROM 1 OVER * YIELD DISTINCT knows._dst",
            "GO 3 STEPS FROM 1 OVER knows, likes YIELD DISTINCT knows._dst",
            "GO UPTO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._dst",
            "GO 3 STEPS FROM 1 OVER knows WHERE knows.w > 0 "
            "YIELD DISTINCT knows._dst"):
        assert shape(left + tail) is None, left
    assert shape("GO 3 STEPS FROM 1 OVER knows YIELD DISTINCT knows._dst"
                 " | LIMIT 3") is None


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("k", [1, 2, 3])
def test_reversely_counts_the_k_th_frontier_against_the_edges(served, k,
                                                              mode):
    """``REVERSELY`` reads the other table and rides the same count."""
    c, g, graph, named = served
    rt = c.tpu_runtime
    src = np.repeat(np.arange(len(graph.deg)), graph.deg)
    starts = [named["hub"]] + named["others"][:3]
    some = 0
    with flags_set({"go_dispatch_mode": mode, "tpu_sparse_go": k <= 2}):
        before = rt.stats["go_count_distinct"]
        for start in starts:
            frontier = {int(start)}
            for _ in range(k):
                frontier = {int(s) for s in src[np.isin(
                    graph.dst, sorted(frontier))]}
            want = [(len(frontier),)] if frontier else []
            stmt = (f"GO {k} STEPS FROM {start} OVER knows REVERSELY "
                    f"YIELD DISTINCT knows._dst | YIELD COUNT(*)")
            assert _rows(g, stmt) == want, (k, mode, start)
            with flags_set({"storage_backend": "cpu"}):
                assert _rows(g, stmt) == want
            some += bool(want)
        assert rt.stats["go_count_distinct"] - before == len(starts)
    assert some


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


def test_a_cohort_counts_and_fetches_and_the_records_say_so(served):
    """Counting leavers beside leavers that take their rows, in the
    same ticks: each gets its own answer, the tick record says how
    many were counted and what the count cost inside the fetch wait,
    and the spans and marker tags are where the metrics read them."""
    c, g, graph, named = served
    others = named["others"]
    statements = []
    for i, v in enumerate(others[:8]):
        k = 2 + i % 2
        statements.append((_statement(k, v), graph.answer(
            {"kind": "go_count_distinct", "steps": k}, v)))
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
        assert sorted(rows) == want, stmt
    ticks = [r for r in flight.recorder.dump(limit=4096)
             if r["kind"] == "tick"]
    assert sum(t["counted"] for t in ticks) == 8
    assert sum(t["leaves"] for t in ticks) == 16
    assert sum(t["handed"] for t in ticks) == 8
    assert any(t["counted"] and t["handed"] for t in ticks)   # mixed
    for t in ticks:
        parts = ("fetch_wait_us", "d2h_us", "unpack_us", "rows_us",
                 "handover_us")
        assert t["assemble_us"] == sum(t[p] for p in parts)
        assert 0 <= t["count_us"] <= t["fetch_wait_us"]
        assert (t["count_us"] > 0) == (t["counted"] > 0) or \
            t["count_us"] == 0
    trees = [trace_store.tree(int(s["id"], 16))
             for s in trace_store.summaries()]

    def walk(node):
        yield node
        for ch in node.get("children", ()):
            yield from walk(ch)
    spans = [n for t in trees for r in t["roots"] for n in walk(r)]
    counts = [n for n in spans if n["name"] == "tpu.count"]
    assert counts and sum(n["tags"]["leavers"] for n in counts) == 8
    assert all(n["tags"]["bytes"] == 4 * 128 for n in counts)
    pumped = [n for n in spans if n["name"] == "pump.count"]
    assert pumped and sum(n["tags"]["counted"] for n in pumped) == 8
    kinds = {n["tags"].get("kind") for n in spans
             if n["name"] == "tpu.kernel"}
    assert "ell_lane_count" in kinds
    markers = [n for n in spans if n["name"] == "graph.continuous"]
    riders = [n["tags"] for n in markers
              if n["tags"].get("reduce") == "count_distinct"]
    assert len(riders) == 8
    assert sorted(t["hops"] for t in riders) == [2, 2, 2, 2, 3, 3, 3, 3]
    assert all("assemble_us" in t for t in riders)


def test_the_count_program_counts_vertices_not_rows():
    """Set bits per lane over the real vertex rows only: junk in the
    hub extra rows, the spare rows and the pad row is not counted."""
    rng = np.random.default_rng(5)
    n, m = 300, 6000
    src, dst = rng.integers(0, n, m), rng.integers(0, n, m)
    ix = E.EllIndex.build(np.concatenate([src, dst]),
                          np.concatenate([dst, src]),
                          np.concatenate([np.ones(m, np.int32),
                                          -np.ones(m, np.int32)]),
                          n, cap=8, growth_slack=4)
    assert ix.n_rows > ix.n + 4        # hub extra rows and spares
    B = 128
    bits = rng.random((ix.n_rows + 1, B)) < 0.3     # junk everywhere
    fp = E.pack_lanes_host(bits)
    counts = np.asarray(E.make_lane_count_kernel(ix)(fp))
    assert counts.dtype == np.int32 and counts.shape == (B,)
    assert (counts == bits[:ix.n].sum(axis=0)).all()
    assert (counts < bits.sum(axis=0)).any()


def _columnar(*cols):
    return ColumnarRows([np.asarray(c) for c in cols], len(cols[0]))


@pytest.mark.parametrize("cols", [
    ([5, 3, 5, 9, 3, 3, 7],),
    ([1, 1, 2, 2, 1, 3], [7, 7, 7, 8, 7, 7]),
    ([4, 4, 4], [1, 2, 1], [9, 9, 9]),
    ([],),
], ids=["one_column", "two_columns", "three_columns", "empty"])
def test_distinct_in_one_pass_keeps_what_the_loop_keeps(cols):
    cols = [np.asarray(c, np.int64) for c in cols]
    fast = runtime_mod._distinct_rows(_columnar(*cols))
    assert isinstance(fast, ColumnarRows)
    slow = runtime_mod._distinct_rows([list(r) for r in zip(*cols)])
    assert isinstance(slow, list)
    assert [list(r) for r in fast] == slow
    seen, want = set(), []
    for r in zip(*(c.tolist() for c in cols)):
        if r not in seen:
            seen.add(r)
            want.append(list(r))
    assert slow == want


def test_distinct_keeps_the_loop_for_anything_but_integer_columns():
    rows = _columnar(np.asarray([0.5, 0.5, 1.5]))
    out = runtime_mod._distinct_rows(rows)
    assert isinstance(out, list) and out == [[0.5], [1.5]]
    mixed = ColumnarRows([np.asarray([1, 1, 2], np.int64),
                          np.asarray([1, 1, 2], np.uint64)], 3)
    assert runtime_mod._distinct_rows(mixed) == [[1, 1], [2, 2]]


def test_distinct_rows_through_the_served_path(served):
    """A GO that yields DISTINCT rows takes the one-pass way and gives
    the CPU executor's rows in its order-free sense."""
    c, g, graph, named = served
    for start in [named["hub"]] + named["others"][:3]:
        stmt = (f"GO 2 STEPS FROM {start} OVER knows "
                f"YIELD DISTINCT knows._dst")
        got = _rows(g, stmt)
        assert len(got) == len(set(got))
        assert sorted(r[0] for r in got) \
            == graph.frontier(start, 2).tolist()
        assert khop.khop_count(graph, start, 2) == len(got)


def test_the_configuration_s_set_up_can_pin_the_dispatch_tier(served):
    """``go_dispatch_mode`` is a managed flag (no new flag, no new
    value), so the khop configuration's set-up statement is taken by
    this program and refused by one that does not manage it; a flag
    nobody manages is refused here too."""
    c, g, graph, named = served
    for mode in ("windowed", "continuous"):
        resp = g.execute(f"UPDATE CONFIGS graph:go_dispatch_mode={mode}")
        assert resp.ok(), resp.error_msg
        assert flags.get("go_dispatch_mode") == mode
    assert not g.execute(
        "UPDATE CONFIGS graph:go_dispatch_no_such_mode=continuous").ok()
    start = named["others"][0]
    assert _rows(g, _statement(2, start)) == graph.answer(
        {"kind": "go_count_distinct", "steps": 2}, start)
