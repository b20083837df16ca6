"""``GO ... OVER <edges> BIDIRECT``: each step crosses an edge of the
OVER set from either end.  A row for an out-edge is the forward
statement's row, a row for an in-edge the ``REVERSELY`` statement's
(``_dst`` the neighbour reached, ``_src`` the frontier vertex), a
step's answer the multiset union of both and the next frontier the
union of both ``_dst`` sets.  Every shape below is answered by the CPU
executor, by the device path under ``go_dispatch_mode=continuous`` and
under ``windowed``, and by a brute-force walk over Python sets, and the
four are compared as multisets (a statement without ORDER BY promises
no row order) on a seeded hub-heavy Kronecker graph of scale 10 with a
second edge type; the tick records, the dispatch records, the kernel
span and ``rt.stats["go_bidirect"]`` say that both direction tables
were read.  CPU jax: no number here is a device number."""
from __future__ import annotations

import threading
import time
from collections import Counter, defaultdict

import numpy as np
import pytest

from benchmark import reference
from benchmark.deploy import flags_set, label_data, shipped_defaults
from benchmark.generators import kronecker
from benchmark.semantics import go_count_distinct_bidirect as bidir
import nebula_tpu.graph.backend_router    # noqa: F401 — define the flags
from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import trace_store
from nebula_tpu.tpu import ell as E

KS = [1, 2, 3, 4]
OVERS = {"one": "knows", "two": "knows, likes"}
TIERS = ("continuous", "windowed")


def _weight(s: int, d: int) -> float:
    return 0.25 + 0.5 * ((s + d) % 2)   # half under the WHERE's constant


class Walk:
    """The brute-force reading of the loaded edges: per edge name, the
    out-edges and the in-edges of every vertex, walked a vertex at a
    time over Python sets."""

    def __init__(self, edges: dict):
        self.out = {name: defaultdict(list) for name in edges}
        self.inn = {name: defaultdict(list) for name in edges}
        for name, triples in edges.items():
            for s, d, p in triples:
                self.out[name][s].append((d, p))
                self.inn[name][d].append((s, p))

    def step(self, frontier, names, signs=(1, -1)):
        """Rows (dst, src, name, prop) of one step out of ``frontier``:
        the forward statement's for every out-edge, the REVERSELY
        statement's for every in-edge."""
        rows = []
        for v in frontier:
            for name in names:
                if 1 in signs:
                    rows += [(d, v, name, p) for d, p in self.out[name][v]]
                if -1 in signs:
                    rows += [(s, v, name, p) for s, p in self.inn[name][v]]
        return rows

    def go(self, starts, k: int, names, upto=False, signs=(1, -1)):
        """The rows of ``GO [UPTO] k STEPS FROM starts OVER names``."""
        frontier, union = set(starts), set()
        for _ in range(k - 1):
            union |= frontier
            frontier = {r[0] for r in self.step(frontier, names, signs)}
        return self.step(sorted(union | frontier) if upto
                         else sorted(frontier), names, signs)


@pytest.fixture(scope="module")
def served():
    """(cluster, client, the brute-force walk, the reference graph of
    the knows edges, the start vertices by name).  The hop's push budget
    is cut to 8 live rows, so the lanes take both branches of the hop,
    and the slot width is capped at 64 so the hubs own extra rows."""
    data = label_data(kronecker.generate(
        {"scale": 10, "edgefactor": 8, "A": 0.57, "B": 0.19, "C": 0.19,
         "edge_prop": "w", "weight_levels": 16}, 50020), seed=40)
    src, dst = data["src"].tolist(), data["dst"].tolist()
    knows = [(s, d, _weight(s, d)) for s, d in zip(src, dst)]
    # a second relation over the same vertices, crossing the first
    likes = sorted({(s, d, (s * 7 + d) % 11)
                    for s, d in zip(src[:400], dst[::-1][:400]) if s != d})
    walk = Walk({"knows": knows, "likes": likes})
    graph = reference.Graph(data["src"], data["dst"],
                            data["edge_prop_table"], data["edge_prop_idx"])
    saved_push = E.HOP_PUSH_ROWS
    E.HOP_PUSH_ROWS = 8
    with flags_set({**shipped_defaults(), "go_backend_router": False,
                    "tpu_prewarm_kernels": False, "tpu_ell_cap": 64}):
        c = LocalCluster(num_storage=1, tpu_backend=True)
        g = c.client()

        def ok(stmt):
            r = g.execute(stmt)
            assert r.ok(), f"{stmt[:80]}: {r.error_msg}"
            return r
        ok("CREATE SPACE b(partition_num=4, replica_factor=1)")
        c.refresh_all()
        ok("USE b")
        ok("CREATE EDGE knows(w double)")
        ok("CREATE EDGE likes(n int)")
        c.refresh_all()
        for lo in range(0, len(knows), 2000):
            ok("INSERT EDGE knows(w) VALUES " + ", ".join(
                f"{s}->{d}:({w})" for s, d, w in knows[lo:lo + 2000]))
        ok("INSERT EDGE likes(n) VALUES " + ", ".join(
            f"{s}->{d}:({n})" for s, d, n in likes))
        try:
            yield c, g, walk, graph, _named_starts(c, walk, graph)
        finally:
            c.stop()
            E.HOP_PUSH_ROWS = saved_push


def _named_starts(c, walk, graph) -> dict:
    """A hub that owns extra rows of the ELL tables, a vertex that only
    receives knows edges (no forward walk leaves it; an undirected one
    does), a label no edge touches, and a spread of others."""
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("b").value()
    m = rt.mirror(sid)
    ix = rt.ell(m)
    owners = np.unique(ix.extra_owner[ix.extra_owner < ix.n])
    assert len(owners), "the graph has no hub with extra rows"
    hub = int(m.vids[ix.inv[owners[0]]])
    have_out = np.nonzero(graph.deg > 0)[0]
    receivers = sorted(v for v in walk.inn["knows"]
                       if walk.inn["knows"][v]
                       and not walk.out["knows"][v]
                       and not walk.out["likes"][v])
    assert receivers
    touched = set(walk.out["knows"]) | set(walk.inn["knows"]) \
        | set(walk.out["likes"]) | set(walk.inn["likes"])
    lonely = next(v for v in range(1, 2000) if v not in touched)
    return {"hub": hub, "receiver": int(receivers[0]), "lonely": lonely,
            "others": [int(v) for v in have_out[3:300:61]]}


def _starts(named: dict) -> list:
    return [named["hub"], named["receiver"], named["lonely"]] \
        + named["others"]


def _resp(client, stmt):
    resp = client.execute(stmt)
    assert resp.ok(), f"{stmt}: {resp.error_msg}"
    assert not resp.warnings and resp.completeness == 100, stmt
    return resp


def _rows(client, stmt) -> Counter:
    return Counter(tuple(r) for r in _resp(client, stmt).rows)


def _everywhere(served, stmt: str, device_statements: int = 1) -> Counter:
    """The statement's row multiset, which the CPU executor and both
    device tiers agree on; on the device each of its ``GO`` over a
    two-signed set is counted once."""
    c, g, *_ = served
    rt = c.tpu_runtime
    with flags_set({"storage_backend": "cpu"}):
        before = rt.stats["go_device"]
        want = _rows(g, stmt)
        assert rt.stats["go_device"] == before      # the device sat out
    steps = int(stmt.split(" STEPS")[0].split()[-1]) \
        if " STEPS" in stmt else 1
    for tier in TIERS:
        # the windowed pair-list program of three and more advances
        # takes CPU jax minutes to compile: the dense lanes program
        with flags_set({"go_dispatch_mode": tier,
                        "tpu_sparse_go": steps <= 2}):
            before = {k: rt.stats[k] for k in ("go_device", "go_bidirect")}
            assert _rows(g, stmt) == want, (tier, stmt)
            grew = {k: rt.stats[k] - v for k, v in before.items()}
            assert grew["go_bidirect"] == device_statements, (tier, stmt)
            assert grew["go_device"] >= device_statements
    return want


# what each shape yields of a walk's rows (dst, src, name, prop)
SHAPES = {
    "rows": ("GO {k} STEPS FROM {v} OVER {over} BIDIRECT "
             "YIELD knows._dst, knows._src, knows._type",
             lambda rows: Counter((d, s, t) for d, s, t, _p in rows)),
    "default_yield": ("GO {k} STEPS FROM {v} OVER {over} BIDIRECT",
                      None),
    "where": ("GO {k} STEPS FROM {v} OVER {over} BIDIRECT "
              "WHERE knows.w > 0.5 YIELD knows._dst, knows._src",
              # a likes row has no knows.w: the predicate drops it
              lambda rows: Counter((d, s) for d, s, t, p in rows
                                   if t == "knows" and p > 0.5)),
    "upto": ("GO UPTO {k} STEPS FROM {v} OVER {over} BIDIRECT "
             "YIELD knows._dst, knows._src",
             lambda rows: Counter((d, s) for d, s, _t, _p in rows)),
    "distinct": ("GO {k} STEPS FROM {v} OVER {over} BIDIRECT "
                 "YIELD DISTINCT knows._dst",
                 lambda rows: Counter({(d,) for d, *_ in rows})),
    "count": ("GO {k} STEPS FROM {v} OVER {over} BIDIRECT "
              "YIELD DISTINCT knows._dst | YIELD COUNT(*)",
              lambda rows: Counter([(len({d for d, *_ in rows}),)]
                                   if rows else [])),
}


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("over", sorted(OVERS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_every_tier_answers_the_walk_s_rows(served, shape, over, k):
    c, g, walk, graph, named = served
    template, project = SHAPES[shape]
    names = OVERS[over].split(", ")
    some = 0
    for v in _starts(named):
        stmt = template.format(k=k, v=v, over=OVERS[over])
        got = _everywhere(served, stmt)
        rows = walk.go([v], k, names, upto=shape == "upto")
        if project is None:
            # one _dst column an edge name, every row's own end in each
            want = Counter((d,) * len(names) for d, *_ in rows)
        else:
            want = project(rows)
        assert got == want, stmt
        some += bool(want)
    assert some >= len(named["others"])
    # a vertex no edge touches has no row, one that only receives has
    # rows where the forward statement has none
    assert not walk.go([named["lonely"]], k, names)
    assert walk.go([named["receiver"]], k, names) \
        and not walk.go([named["receiver"]], k, names, signs=(1,))


@pytest.mark.parametrize("k", KS)
def test_a_step_is_the_forward_rows_and_the_reversely_rows(served, k):
    """Column for column: out of the (k-1)-th UNDIRECTED frontier the
    forward statement's rows and the REVERSELY statement's are the
    BIDIRECT statement's, on every tier."""
    c, g, walk, graph, named = served
    cols = "knows._dst, knows._src, knows._type, knows.w, knows._rank"
    for v in (named["hub"], named["others"][0]):
        both = _everywhere(
            served, f"GO {k} STEPS FROM {v} OVER knows BIDIRECT YIELD {cols}")
        last = sorted({r[0] for r in walk.go([v], k - 1, ["knows"])}) \
            if k > 1 else [v]
        starts = ", ".join(str(u) for u in last)
        halves = Counter()
        for word in ("", " REVERSELY"):
            halves += _rows(
                g, f"GO FROM {starts} OVER knows{word} YIELD {cols}")
        assert both == halves and both


@pytest.mark.parametrize("over", sorted(OVERS))
def test_a_limit_behind_cuts_the_same_multiset(served, over):
    """``| LIMIT`` cuts the rows as a route hands them, so which seven
    is the route's choice; that they are seven of the walk's rows, no
    row more often than the walk has it, is not."""
    c, g, walk, graph, named = served
    names = OVERS[over].split(", ")
    for v in (named["hub"], named["others"][1]):
        stmt = (f"GO 2 STEPS FROM {v} OVER {OVERS[over]} BIDIRECT "
                f"YIELD knows._dst, knows._src | LIMIT 7")
        whole = Counter((d, s) for d, s, *_ in walk.go([v], 2, names))
        for fl in ({"storage_backend": "cpu"},
                   {"go_dispatch_mode": "continuous"},
                   {"go_dispatch_mode": "windowed"}):
            with flags_set(fl):
                got = _rows(g, stmt)
            assert sum(got.values()) == min(7, sum(whole.values()))
            assert not got - whole, (fl, stmt)


def test_piped_and_variable_starts_walk_both_ways(served):
    c, g, walk, graph, named = served
    v = named["others"][2]
    first = sorted({d for d, *_ in walk.go([v], 1, ["knows"],
                                           signs=(1,))})
    want = Counter((d,) for d, *_ in walk.go(first, 2, ["knows"]))
    assert want
    piped = (f"GO FROM {v} OVER knows YIELD knows._dst AS d | "
             f"GO 2 STEPS FROM $-.d OVER knows BIDIRECT YIELD knows._dst")
    assert _everywhere(served, piped) == want
    by_var = (f"$a = GO FROM {v} OVER knows YIELD knows._dst AS d; "
              f"GO 2 STEPS FROM $a.d OVER knows BIDIRECT YIELD knows._dst")
    assert _everywhere(served, by_var) == want
    # BIDIRECT on both sides of a pipe is two statements over the set
    both = (f"GO FROM {v} OVER knows BIDIRECT YIELD knows._dst AS d | "
            f"GO FROM $-.d OVER knows BIDIRECT YIELD knows._dst")
    assert _everywhere(served, both, device_statements=2) \
        == Counter((d,) for d, *_ in walk.go([v], 2, ["knows"]))


def test_over_all_and_aliases_name_both_signs(served):
    c, g, walk, graph, named = served
    v = named["hub"]
    rows = walk.go([v], 2, ["knows", "likes"])
    assert _everywhere(
        served, f"GO 2 STEPS FROM {v} OVER * BIDIRECT "
                f"YIELD knows._dst, likes._src, likes._type") \
        == Counter((d, s, t) for d, s, t, _p in rows)
    rows = walk.go([v], 2, ["likes"])
    assert _everywhere(
        served, f"GO 2 STEPS FROM {v} OVER likes AS l BIDIRECT "
                f"WHERE l.n >= 5 YIELD l._dst AS d, l.n, l._type") \
        == Counter((d, p, "l") for d, _s, _t, p in rows if p >= 5)


@pytest.mark.parametrize("k", KS)
def test_the_count_is_the_plain_reference_s(served, k):
    """benchmark/semantics/go_count_distinct_bidirect.py, the walk and
    the served count agree, and the reductions ride the lanes of the
    (-t, +t) stream: k hops a statement, nothing fetched."""
    c, g, walk, graph, named = served
    rt = c.tpu_runtime
    sem = {"kind": "go_count_distinct_bidirect", "steps": k}
    keys = ("go_count_distinct", "go_reduced", "count_distinct_hops",
            "go_bidirect", "go_device")
    starts = _starts(named)
    with flags_set({"go_dispatch_mode": "continuous"}):
        before = {key: rt.stats[key] for key in keys}
        joined = rt.dispatcher.stats.get("continuous_queries", 0)
        for v in starts:
            n = len({d for d, *_ in walk.go([v], k, ["knows"])})
            assert graph.answer(sem, v) == ([(n,)] if n else [])
            assert bidir.khop_count(graph, v, k, pull=True) == n
            got = _resp(g, f"GO {k} STEPS FROM {v} OVER knows BIDIRECT "
                           f"YIELD DISTINCT knows._dst | YIELD COUNT(*)")
            assert [tuple(r) for r in got.rows] == graph.answer(sem, v)
        grew = {key: rt.stats[key] - before[key] for key in keys}
    assert grew == {"go_count_distinct": len(starts),
                    "go_reduced": len(starts),
                    "count_distinct_hops": k * len(starts),
                    "go_bidirect": len(starts), "go_device": len(starts)}
    assert rt.dispatcher.stats["continuous_queries"] - joined \
        == len(starts)


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("reduce", ["distinct", "count_distinct"])
def test_reversely_rides_the_same_reductions(served, reduce, k):
    """A sign flip reads the other table and nothing else differs: the
    k-th frontier of the REVERSELY walk, reduced on both tiers."""
    c, g, walk, graph, named = served
    rt = c.tpu_runtime
    tail = " | YIELD COUNT(*)" if reduce == "count_distinct" else ""
    counter = "go_" + reduce
    for v in (named["hub"], named["receiver"], named["others"][0]):
        stmt = (f"GO {k} STEPS FROM {v} OVER knows REVERSELY "
                f"YIELD DISTINCT knows._dst{tail}")
        ends = {d for d, *_ in walk.go([v], k, ["knows"], signs=(-1,))}
        want = Counter([(len(ends),)] if ends else []) if tail \
            else Counter((d,) for d in ends)
        with flags_set({"storage_backend": "cpu"}):
            assert _rows(g, stmt) == want
        for tier in TIERS:
            with flags_set({"go_dispatch_mode": tier,
                            "tpu_sparse_go": k <= 2}):
                before = (rt.stats[counter], rt.stats["go_bidirect"])
                assert _rows(g, stmt) == want, (tier, stmt)
                assert (rt.stats[counter], rt.stats["go_bidirect"]) \
                    == (before[0] + 1, before[1])


def _burst(c, statements):
    out, errors = {}, []
    barrier = threading.Barrier(len(statements))

    def worker(i):
        try:
            g2 = c.client()
            g2.execute("USE b")
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


def _spans(trees) -> list:
    def walk(node):
        yield node
        for ch in node.get("children", ()):
            yield from walk(ch)
    return [n for t in trees for r in t["roots"] for n in walk(r)]


def test_the_records_say_both_tables_were_read(served):
    """A stream is one OVER set: the ticks of the (-t, +t) stream read
    ``hop_onesided`` 0, a pull reports 2 x the table and gathers what
    the two reaches leave of it, and the kernel span says ``sides`` 2
    where the forward stream's says 1."""
    c, g, walk, graph, named = served
    rt = c.tpu_runtime
    sid = c.graph_meta_client.get_space_id_by_name("b").value()
    ix = rt.ell(rt.mirror(sid))
    t = c.schema_man.to_edge_type(sid, "knows").value()
    table = sum(int(a.shape[0]) * int(a.shape[1]) for a in ix.bucket_nbr)
    assert E.sides_read((-t, t)) == 2 and E.sides_read((-t,)) == 1
    assert E.table_slots(ix, (-t, t)) == 2 * table
    assert E.swept_slots(ix, (-t, t)) \
        == E.swept_slots(ix, (t,)) + E.swept_slots(ix, (-t,))
    others = named["others"]
    ticks, sides = {}, {}
    saved = flags.get("trace_sample_rate")
    for word in (" BIDIRECT", ""):
        statements = [
            (f"GO {2 + i % 2} STEPS FROM {v} OVER knows{word} "
             f"YIELD DISTINCT knows._dst | YIELD COUNT(*)")
            for i, v in enumerate(others)]
        trace_store.clear_for_tests()
        flight.recorder.clear_for_tests()
        flags.set("trace_sample_rate", 1.0)
        try:
            with flags_set({"go_dispatch_mode": "continuous"}):
                got = _burst(c, statements)
        finally:
            flags.set("trace_sample_rate", saved)
        for i, (v, rows) in enumerate(zip(others, got)):
            ends = {d for d, *_ in walk.go(
                [v], 2 + i % 2, ["knows"],
                signs=(1, -1) if word else (1,))}
            assert rows == Counter([(len(ends),)] if ends else [])
        ticks[word] = [r for r in flight.recorder.dump(limit=4096)
                       if r["kind"] == "tick" and r["hop_reads"]]
        trees = [trace_store.tree(int(s["id"], 16))
                 for s in trace_store.summaries()]
        sides[word] = {n["tags"]["sides"] for n in _spans(trees)
                       if n["name"] == "tpu.kernel"
                       and n["tags"].get("kind") == "ell_go_hop"}
    assert sides == {" BIDIRECT": {2}, "": {1}}
    two, one = ticks[" BIDIRECT"], ticks[""]
    assert two and one
    assert all(r["hop_onesided"] == 0 for r in two)
    assert all(r["hop_onesided"] == r["hop_reads"] for r in one)
    pulled = 0
    for sided, recs in ((2, two), (1, one)):
        for r in recs:
            pulls = r["hop_reads"] - r["hop_sparse"]
            assert r["hop_slots"] >= pulls * sided * table
            assert r["hop_swept"] <= r["hop_slots"]
            if pulls and not r["hop_sparse"]:
                assert r["hop_slots"] == pulls * sided * table
                assert r["hop_swept"] == pulls * E.swept_slots(
                    ix, (-t, t) if sided == 2 else (t,))
                pulled += sided == 2
    assert pulled       # a two-sided pull was seen whole


def test_the_windowed_dispatch_records_carry_the_sides(served):
    c, g, walk, graph, named = served
    v = named["others"][3]
    seen = {}
    for word, sparse in ((" BIDIRECT", True), (" BIDIRECT", False),
                         ("", True), (" REVERSELY", False)):
        flight.recorder.clear_for_tests()
        with flags_set({"go_dispatch_mode": "windowed",
                        "tpu_sparse_go": sparse}):
            _rows(g, f"GO 2 STEPS FROM {v} OVER knows{word} "
                     f"YIELD knows._dst")
        recs = [r for r in flight.recorder.dump(limit=256)
                if r["kind"] == "dispatch"]
        assert len(recs) == 1
        seen[word, sparse] = (recs[0]["kernel"], recs[0]["sides"])
    assert seen == {(" BIDIRECT", True): ("sparse_go", 2),
                    (" BIDIRECT", False): ("ell_go", 2),
                    ("", True): ("sparse_go", 1),
                    (" REVERSELY", False): ("ell_go", 1)}
