"""The seat takes the first hop (PR 43; docs/admission.md "Continuous
dispatch"): ``_ContinuousGoSession.join`` scatters a joiner's FIRST
FRONTIER, its starts' neighbours over the OVER set, which the host
holds, and the stream counts the rider one hop fewer to ride.

The answer is the same answer: every statement kind, sign and depth
through a session that advances and through one forced not to (the
test-only ``seat_rows`` of ``continuous_session``, as ``push_rows`` is
ell.py's) gives identical rows, and the CPU executor's.  CPU jax: no
number here is a device number.
"""
import threading
import time

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight
from nebula_tpu.common.flags import flags
from nebula_tpu.common.stats import stats
from nebula_tpu.tpu import ell as E
from nebula_tpu.tpu import runtime as R
from nebula_tpu.tpu.csr import CsrMirror

# the test's own row budget: a first frontier of more distinct rows
# than this is seated the old way (HOP_PUSH_ROWS = 2,048 in a cell)
BUDGET = 11
NO_OUT, LOOP, TWICE, BIG = 90, 7, 1, 11


def _boot():
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE sh(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE sh")
    ok("CREATE EDGE e(w int)")
    c.refresh_all()
    rng = np.random.default_rng(43)
    pairs = {(int(a), int(b)) for a, b in zip(rng.integers(1, 41, 120),
                                              rng.integers(1, 41, 120))
             if a != b and a not in (TWICE, LOOP, BIG)}
    pairs |= {(LOOP, LOOP), (LOOP, 8),          # a self-loop
              (TWICE, 3), (3, NO_OUT),          # NO_OUT: in-edges only
              (5, NO_OUT)}
    pairs |= {(BIG, v) for v in range(50, 62)}  # over the budget
    vals = [f"{a} -> {b}@0:({(a * 31 + b) % 97})" for a, b in sorted(pairs)]
    # a pair stored twice: one more edge 1 -> 2 at another rank
    vals += [f"{TWICE} -> 2@0:(50)", f"{TWICE} -> 2@1:(60)"]
    ok("INSERT EDGE e(w) VALUES " + ", ".join(vals))
    return c, g, ok


def _marker(prof):
    def walk(n):
        yield n
        for ch in n.get("children", []):
            yield from walk(ch)

    marks = [s["tags"] for root in prof["roots"] for s in walk(root)
             if s["name"] == "graph.continuous"]
    assert len(marks) == 1, marks
    return marks[0]


KINDS = {
    "rows": "GO {k} STEPS FROM {v} OVER e{d} YIELD e._dst",
    "count": "GO {k} STEPS FROM {v} OVER e{d} YIELD e._dst "
             "| YIELD COUNT(*)",
    "count_distinct": "GO {k} STEPS FROM {v} OVER e{d} "
                      "YIELD DISTINCT e._dst | YIELD COUNT(*)",
    "distinct": "GO {k} STEPS FROM {v} OVER e{d} YIELD DISTINCT e._dst",
    "where": "GO {k} STEPS FROM {v} OVER e{d} WHERE e.w > 40 "
             "YIELD e._dst, e.w",
}
SIGNS = {"forward": "", "reversely": " REVERSELY", "bidirect": " BIDIRECT"}
DEPTHS = (2, 3, 6)
# several starts a statement: the pair stored twice, the self-loop, the
# vertex without an out-edge, a vid the space does not hold
STARTS = f"{TWICE}, {LOOP}, {NO_OUT}, 4, 999"
CASES = [(kind, sign, k) for kind in KINDS for sign in SIGNS
         for k in DEPTHS]


def _stmt(kind, sign, k, v=STARTS):
    return KINDS[kind].format(k=k, v=v, d=SIGNS[sign])


def _rows(r):
    return sorted(map(tuple, r.rows))


@pytest.fixture(scope="module")
def cluster():
    flags.set("go_dispatch_mode", "continuous")
    c, g, ok = _boot()
    yield c, g, ok
    c.stop()


def _reanchor(c, seat_rows):
    """Every stream's next statement anchors a session with this row
    budget (negative: no seat takes a hop)."""
    rt = c.tpu_runtime
    real = type(rt).continuous_session

    def anchored(space_id, et_tuple, min_lanes=1):
        return real(rt, space_id, et_tuple, min_lanes=min_lanes,
                    seat_rows=seat_rows)

    rt.continuous_session = anchored
    for st in rt.dispatcher.continuous.streams():
        st._widen = True


@pytest.fixture(scope="module")
def both(cluster):
    """Every case's statement PROFILEd through a session that advances
    (within BUDGET rows) and through one forced not to, and answered
    by the CPU executor: {mode: {case: (rows, marker)}}."""
    c, g, ok = cluster
    out = {"cpu": {}}
    try:
        for mode, seat_rows in (("seat", BUDGET), ("ride", -1)):
            _reanchor(c, seat_rows)
            out[mode] = {}
            for case in CASES:
                r = ok("PROFILE " + _stmt(*case))
                out[mode][case] = (_rows(r), _marker(r.raw["profile"]))
        flags.set("storage_backend", "cpu")
        for case in CASES:
            out["cpu"][case] = _rows(ok(_stmt(*case)))
        # the distinct rows of the statements' first frontier, a sign
        out["first"] = {sign: len(set(_rows(ok(_stmt("rows", sign, 1)))))
                        for sign in SIGNS}
    finally:
        flags.set("storage_backend", "tpu")
        del c.tpu_runtime.continuous_session
        _settle(c)
        for st in c.tpu_runtime.dispatcher.continuous.streams():
            st._widen = True
    return out


def _settle(c, timeout_s=5.0):
    d = c.tpu_runtime.dispatcher
    end = time.monotonic() + timeout_s
    while time.monotonic() < end \
            and d.continuous.seat_counts() != (0, 0):
        time.sleep(0.01)
    time.sleep(0.05)


@pytest.mark.parametrize("kind,sign,k", CASES,
                         ids=[f"{a}-{b}-{k}" for a, b, k in CASES])
def test_the_answer_is_the_same_answer(both, kind, sign, k):
    case = (kind, sign, k)
    seat_rows, seat = both["seat"][case]
    ride_rows, ride = both["ride"][case]
    assert seat_rows == ride_rows == both["cpu"][case], _stmt(*case)
    # what the statement rides is its hops either way; the seat took
    # the first of them wherever one stays on the lanes afterwards
    hops = k if kind in ("count_distinct", "distinct") else k - 1
    assert seat["hops"] == ride["hops"] == hops
    assert ride["seat_hops"] == 0
    assert seat["seat_hops"] == int(hops >= 2
                                    and both["first"][sign] <= BUDGET)
    for m in (seat, ride):
        assert m["left_tick"] - m["joined_tick"] \
            == hops - m["seat_hops"], m


def _forward(c):
    """The stream of the statements OVER e forwards."""
    return next(s for s in c.tpu_runtime.dispatcher.continuous.streams()
                if len(s.et_tuple) == 1 and s.et_tuple[0] > 0)


def _session(c, seat_rows):
    st = _forward(c)
    return c.tpu_runtime.continuous_session(
        st.space_id, st.et_tuple, seat_rows=seat_rows)


# (lane, starts, the stream lets its seat take a hop, it does)
COHORT = [
    (3, [NO_OUT], True, True),          # no out-edge: an empty lane
    (9, [LOOP], True, True),            # a self-loop
    (10, [TWICE], True, True),          # a pair stored twice
    (17, [TWICE, LOOP, 4, 4, 999], True, True),   # several starts
    (18, [BIG], True, False),           # over the budget
    (25, [4], False, False),            # an UPTO rider
    (26, [LOOP], False, False),         # a rider of one hop
]


def test_the_budget_cuts_between_the_signs(both):
    """Forwards and REVERSELY the statements' first frontier fits the
    test's budget and BIDIRECT's, both tables' neighbours, does not:
    the cases above hold both outcomes."""
    first = both["first"]
    assert max(first["forward"], first["reversely"]) <= BUDGET \
        < first["bidirect"], first


def test_one_cohort_mixes_those_that_advance_and_those_that_do_not(
        cluster, both):
    """ONE join over the seven kinds of joiner: the lanes whose seat
    took the hop hold after one device hop what a session forced not
    to holds after two, the others what it holds after one."""
    c, g, ok = cluster
    sess, plain = _session(c, BUDGET), _session(c, -1)
    cohort = [(lane, vs, may) for lane, vs, may, _ in COHORT]
    assert sess.join(cohort) == [took for *_, took in COHORT]
    assert plain.join(cohort) == [False] * len(COHORT)
    leavers = [(lane, False) for lane, *_ in COHORT]
    lanes = [lane for lane, *_ in COHORT]
    sess.hop()
    got, got_n = sess.extract(leavers)(), sess.count(lanes)()
    plain.hop()
    one, one_n = plain.extract(leavers)(), plain.count(lanes)()
    plain.hop()
    two, two_n = plain.extract(leavers)(), plain.count(lanes)()
    for i, (_lane, _vs, _may, took) in enumerate(COHORT):
        want, want_n = (two, two_n) if took else (one, one_n)
        assert np.array_equal(got[i], want[i]), COHORT[i]
        assert got_n[i] == want_n[i] == len(want[i])
    assert len(got[0]) == 0             # nothing leaves NO_OUT
    # the rows it scattered: first frontiers and starts, each once
    assert sess.join_rows == sum(
        len(set(r[0] for r in _cpu_rows(ok, 1, vs))) if took
        else len({v for v in vs if v != 999})
        for _lane, vs, _may, took in COHORT)


def _cpu_rows(ok, k, vs):
    flags.set("storage_backend", "cpu")
    try:
        return ok(f"GO {k} STEPS FROM {', '.join(map(str, vs))} "
                  f"OVER e YIELD e._dst").rows
    finally:
        flags.set("storage_backend", "tpu")


def test_a_cohorts_edges_bound_what_the_seat_expands(cluster, both):
    """The cohort's joiners are expanded cheapest first while their
    first-hop edges stay within seat_cohort_edges: one of more edges
    than that alone stands in nobody's way, and a heavy one seated
    first takes the budget from nobody lighter."""
    c, g, ok = cluster
    sess = _session(c, 100)
    deg = {v: len(_cpu_rows(ok, 1, [v])) for v in (TWICE, LOOP, BIG, 5)}
    # 1 -> 2 twice and 1 -> 3; 7 -> 7, 7 -> 8
    assert (deg[BIG], deg[TWICE], deg[LOOP]) == (12, 3, 2)
    assert deg[5] >= 1                  # 5 -> NO_OUT at the least
    sess.seat_cohort_edges = deg[LOOP] + deg[TWICE]
    took = sess.join([(1, [BIG], True), (2, [TWICE], True),
                      (3, [LOOP], True)])
    assert took == [False, True, True]
    # two of TWICE's weight: the one seated first fits beside LOOP
    sess.clear([1, 2, 3])
    took = sess.join([(1, [TWICE], True), (2, [TWICE], True),
                      (3, [LOOP], True)])
    assert took == [True, False, True]


@pytest.mark.parametrize("size", [1, 7, 64])
def test_join_is_one_pass_whatever_the_cohort(cluster, both,
                                              monkeypatch, size):
    c, g, ok = cluster
    sess = _session(c, BUDGET)
    calls = {"to_dense": 0, "edges": 0, "unique": 0}
    real_dense, real_edges = CsrMirror.to_dense, \
        R.TpuQueryRuntime._frontier_edges_multi
    real_unique = np.unique

    def dense(self, vids):
        calls["to_dense"] += 1
        return real_dense(self, vids)

    def edges(self, *a, **k):
        calls["edges"] += 1
        return real_edges(self, *a, **k)

    def unique(*a, **k):
        calls["unique"] += 1
        return real_unique(*a, **k)

    monkeypatch.setattr(CsrMirror, "to_dense", dense)
    monkeypatch.setattr(R.TpuQueryRuntime, "_frontier_edges_multi", edges)
    monkeypatch.setattr(R.np, "unique", unique)
    took = sess.join([(lane, [1 + lane % 40, 2 + lane % 7], lane % 3 != 1)
                      for lane in range(size)])
    assert len(took) == size
    assert calls == {"to_dense": 1, "edges": 1, "unique": 1}


def test_a_second_cohort_of_any_size_compiles_nothing(cluster, both):
    """The session ran the join ladder itself before its first join
    (_join_kernel): a later cohort of any number of rows up to the cap
    meets no shape for the first time."""
    import jax.monitoring
    c, g, ok = cluster
    rt = c.tpu_runtime
    sess = _session(c, 10_000)
    sess.seat_cohort_edges = 10**6
    compiled = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name"))

    rt.join_rungs_run.discard((sess.ix.shape_sig(), sess.B))
    sess.join([(0, [TWICE], True)])     # the first join runs the ladder
    sess.clear(range(128))
    assert (sess.ix.shape_sig(), sess.B) in rt.join_rungs_run
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        everyone = list(range(1, 41)) + list(range(50, 62)) + [NO_OUT]
        for lanes in (1, 2, 5, 11, 40, 100, 128):
            sess.clear(range(128))
            took = sess.join([(lane, everyone[lane % 7::3], True)
                              for lane in range(lanes)])
            assert all(took)
        # several programs of the top rung, then the rest at its rung
        assert sess.join_rows > 2 * E.LANE_JOIN_RUNGS[-1]
        sess.fp.block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiled == []


def test_the_ladder():
    assert E.LANE_JOIN_RUNGS == (8, 32, 128, 512)
    assert [E.lane_join_rung(s) for s in (1, 8, 9, 32, 33, 512, 513,
                                          4096)] \
        == [8, 8, 32, 32, 128, 512, 512, 512]


def test_one_tick_seats_a_mixed_cohort_and_says_so(cluster, both):
    """Through the stream: a burst seated behind a slow tick is ONE
    cohort of riders whose seat takes the hop and riders whose seat
    cannot (over the budget, UPTO, a single hop); the tick record and
    the stat carry what the session did."""
    c, g, ok = cluster
    rt = c.tpu_runtime
    _reanchor(c, BUDGET)
    stmts = [
        (f"GO 3 STEPS FROM {TWICE} OVER e YIELD e._dst", 1),
        (f"GO 3 STEPS FROM {LOOP}, 4 OVER e YIELD e._dst "
         f"| YIELD COUNT(*)", 1),
        (f"GO 2 STEPS FROM {NO_OUT} OVER e YIELD DISTINCT e._dst", 1),
        (f"GO 3 STEPS FROM {BIG} OVER e YIELD e._dst", 0),
        (f"GO UPTO 3 STEPS FROM 4 OVER e YIELD e._dst", 0),
        (f"GO 2 STEPS FROM {LOOP} OVER e YIELD e._dst", 0),
    ]
    try:
        ok(stmts[0][0])                 # the stream is anchored
        _settle(c)
        st = _forward(c)
        flight.recorder.clear_for_tests()
        took0 = stats.read_stats("graph.continuous.seat_hops.sum.600") or 0
        got, errors = {}, []
        st.tick_delay_s = 0.25
        try:
            def run(i):
                try:
                    g2 = c.client()
                    g2.execute("USE sh")
                    r = g2.execute("PROFILE " + stmts[i][0])
                    assert r.ok(), r.error_msg
                    got[i] = r
                except Exception as ex:     # noqa: BLE001 — reported
                    errors.append(ex)

            ts = [threading.Thread(target=run, args=(i,))
                  for i in range(len(stmts))]
            [t.start() for t in ts]
            [t.join() for t in ts]
        finally:
            st.tick_delay_s = 0.0
        assert not errors, errors
        _settle(c)
        ticks = [r for r in flight.recorder.dump(limit=4096)
                 if r["kind"] == "tick"]
        for i, (stmt, took) in enumerate(stmts):
            m = _marker(got[i].raw["profile"])
            assert m["seat_hops"] == took, (stmt, m)
            assert m["left_tick"] - m["joined_tick"] \
                == m["hops"] - took, (stmt, m)
            flags.set("storage_backend", "cpu")
            try:
                assert _rows(got[i]) == _rows(ok(stmt)), stmt
            finally:
                flags.set("storage_backend", "tpu")
        # all six were seated by one tick, behind its delay
        seat = [t for t in ticks if t["joins"]]
        assert [t["joins"] for t in seat] == [len(stmts)], seat
        assert seat[0]["seat_hops"] == 3 and seat[0]["join_rows"] > 0
        assert all(t["seat_hops"] == t["join_rows"] == 0
                   for t in ticks if not t["joins"])
        assert (stats.read_stats("graph.continuous.seat_hops.sum.600")
                or 0) - took0 == 3
    finally:
        del rt.continuous_session
        _settle(c)
        for s in rt.dispatcher.continuous.streams():
            s._widen = True


def test_the_seat_tables_are_filled_at_the_anchor(cluster, both):
    """The mirror's per-(generation, OVER set) host tables that the
    seat's expansion leans on are filled where the session is made,
    not at its first join."""
    c, g, ok = cluster
    rt = c.tpu_runtime
    sess = _session(c, BUDGET)
    m, et = sess.m, sess.et_tuple
    for attr in ("_over_range_cache", "_etype_mask_cache", "_deg_cache"):
        getattr(m, attr).pop(et, None)
    again = rt.continuous_session(sess.space_id, et)
    assert again.m is m
    for attr in ("_over_range_cache", "_etype_mask_cache", "_deg_cache"):
        assert et in getattr(m, attr), attr
