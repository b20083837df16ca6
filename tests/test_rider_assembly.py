"""A continuous leaver assembles its own answer (graph/batch_dispatch.py
_finish / _assemble_own): the pump fetches and unpacks a leave cohort,
answers what it keeps to itself (the COUNT riders' fold, and a WHERE
that filters in numpy: tpu/runtime.py rider_assembles), hands every
other leaver its frontier and goes on to the next tick; the rider's
own thread, woken in submit() and outside the stream condition, runs
the same continuous_results over its one statement.

  (a) a mixed cohort answers row for row what continuous_results over
      the whole cohort (the path the pump took before) answers, and a
      rider whose WHERE reads an invalid prop declines alone;
  (b) a rider parked in its own assembly holds neither the pump nor a
      rider seated after it;
  (c) the assembly's spans land on the rider's own trace, the counters
      grow as they did, every cohort's leavers are handed, counted or
      filtered by the pump, and the five waits tile the rider's time in
      submit();
  (d) a WHERE that would filter in numpy stays on the pump, as does
      every WHERE where the library lacks the native pass;
  (e) the O(edges) per-generation tables are filled once, however many
      riders ask for them in the same instant.

CPU jax: no number here is a device number."""
from __future__ import annotations

import inspect
import sys
import threading
import time

import numpy as np
import pytest

import nebula_tpu.graph.backend_router    # noqa: F401 — define the flags
from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import flight, hostclock, tracing
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import trace_store
from nebula_tpu.graph import batch_dispatch as bd
from nebula_tpu.storage.device import TpuDecline

N = 40
# one dispatcher stream (space, OVER e, f), one leave tick (two hops in,
# of which the seat takes the first; the UPTO rider, whose seat cannot,
# has one): rows, a COUNT rider, a WHERE on a double column (one
# native pass: the rider's own), a LIMIT rider, DISTINCT, an UPTO
# union, a WHERE whose || reads f.x on e's edges, where it is invalid
# (the CPU loop's short-circuit decides that one, so the device path
# declines it), a second COUNT, and a WHERE on an int column (numpy:
# the pump's)
COHORT = [
    "GO 3 STEPS FROM 1 OVER e, f YIELD e._dst, f._dst",
    "GO 3 STEPS FROM 2 OVER e, f YIELD e._dst, f._dst | YIELD COUNT(*)",
    "GO 3 STEPS FROM 3 OVER e, f WHERE e.d > 0.4 YIELD e._dst, e.w",
    "GO 3 STEPS FROM 4 OVER e, f YIELD e._dst AS d | LIMIT 5",
    "GO 3 STEPS FROM 5 OVER e, f YIELD DISTINCT e._dst",
    "GO UPTO 2 STEPS FROM 6 OVER e, f YIELD e._dst, f._dst",
    "GO 3 STEPS FROM 7 OVER e, f WHERE e.w > 40 || f.x > 5 "
    "YIELD e._dst, f._dst",
    "GO 3 STEPS FROM 8 OVER e, f YIELD e._dst | YIELD COUNT(*)",
    "GO 3 STEPS FROM 9 OVER e, f WHERE e.w > 60 YIELD e._dst, e.w",
]
DECLINES = 6                    # COHORT's index: the invalid-prop rider
COUNTS = (1, 7)
NATIVE = 2                      # its WHERE is one native pass
PUMPED = (6, 8)                 # their WHEREs filter in numpy


def _counts(r):
    return r.reduce is not None and r.reduce[0] == "count"


@pytest.fixture(scope="module")
def served():
    saved = flags.get("go_dispatch_mode")
    flags.set("go_dispatch_mode", "continuous")
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE s(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE s")
    ok("CREATE EDGE e(w int, d double)")
    ok("CREATE EDGE f(x int)")
    c.refresh_all()
    rng = np.random.default_rng(32)

    def pairs(m):
        return sorted({(int(a), int(b)) for a, b in
                       zip(rng.integers(1, N + 1, m),
                           rng.integers(1, N + 1, m)) if a != b})

    ok("INSERT EDGE e(w, d) VALUES " + ", ".join(
        f"{a}->{b}:({(a * 31 + b) % 97}, {(a * 31 + b) % 97 / 97.0!r})"
        for a, b in pairs(200)))
    ok("INSERT EDGE f(x) VALUES " + ", ".join(
        f"{a}->{b}:({(a * 7 + b) % 13})" for a, b in pairs(60)))
    ok(COHORT[0])               # the stream is anchored, compiled
    # conftest built the library: without the native pass every WHERE
    # is the pump's, which test (d) shows and the others do not expect
    from nebula_tpu.native import lib
    assert hasattr(lib(), "neb_filter_runs_f64")
    try:
        yield c, ok
    finally:
        c.stop()
        flags.set("go_dispatch_mode", saved)


@pytest.fixture(autouse=True)
def _clean(served):
    _settle(served[0])
    sampled = flags.get("trace_sample_rate")
    trace_store.clear_for_tests()
    flight.recorder.clear_for_tests()
    yield
    flags.set("trace_sample_rate", sampled)
    trace_store.clear_for_tests()


def _settle(c, timeout_s=5.0):
    d = c.tpu_runtime.dispatcher
    end = time.monotonic() + timeout_s
    while time.monotonic() < end \
            and d.continuous.seat_counts() != (0, 0):
        time.sleep(0.01)
    time.sleep(0.05)            # the last tick's record lands


def _stream(c):
    return next(s for s in c.tpu_runtime.dispatcher.continuous.streams()
                if s.session is not None)


def _ticks():
    return [r for r in flight.recorder.dump(limit=4096)
            if r["kind"] == "tick"]


def _walk(node):
    yield node
    for child in node.get("children", ()):
        yield from _walk(child)


def _rider_nodes(trace_id):
    """The span nodes of one rider's own trace, as a flat list."""
    tree = trace_store.tree(trace_id)
    return [n for root in tree["roots"] for n in _walk(root)]


class _Spy:
    """Every continuous_results call of a burst: who called (the pump
    or a rider's thread), over what, and what came back."""

    def __init__(self, rt):
        self.rt = rt
        self.real = rt.continuous_results
        self.calls = []
        self.lock = threading.Lock()

    def __call__(self, space_id, m, queries, reduces, vs_lists, et):
        out = self.real(space_id, m, queries, reduces, vs_lists, et)
        with self.lock:
            self.calls.append({
                "thread": threading.current_thread().name,
                "space": space_id, "m": m, "queries": list(queries),
                "reduces": list(reduces), "vs": list(vs_lists),
                "et": et, "out": list(out)})
        return out

    def by_start(self):
        """start vid -> (query, reduce, frontier, result, thread)."""
        got = {}
        for call in self.calls:
            for q, red, vs, out in zip(call["queries"], call["reduces"],
                                       call["vs"], call["out"]):
                (start,) = q.start_vids
                assert start not in got, start
                got[int(start)] = (q, red, vs, out, call["thread"])
        return got


def _burst(c, statements, spy=None, finishes=None):
    """The statements at once, arrivals pooled into one tick; returns
    their responses (failed ones included)."""
    rt = c.tpu_runtime
    st = _stream(c)
    out, errors = {}, []
    barrier = threading.Barrier(len(statements))

    def worker(i):
        try:
            client = c.client()
            assert client.execute("USE s").ok()
            barrier.wait()
            out[i] = client.execute(statements[i])
        except Exception as ex:     # noqa: BLE001 — reported below
            errors.append(ex)

    real_finish = st._finish
    if finishes is not None:
        def finish(pending):
            got = real_finish(pending)
            finishes.append((list(pending[1]), got))
            return got
        st._finish = finish
    if spy is not None:
        rt.continuous_results = spy
    st.tick_delay_s = 0.05
    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(statements))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        st.tick_delay_s = 0.0
        if spy is not None:
            del rt.continuous_results
        if finishes is not None:
            del st._finish
    assert not errors, errors
    _settle(c)
    return [out[i] for i in range(len(statements))]


def _cpu_rows(ok, stmt):
    flags.set("storage_backend", "cpu")
    try:
        return sorted(map(tuple, ok(stmt).rows))
    finally:
        flags.set("storage_backend", "tpu")


# ======================================= (a) the answers are the same
def test_a_mixed_cohort_answers_what_the_cohort_assembly_answers(served):
    c, ok = served
    rt = c.tpu_runtime
    spy = _Spy(rt)
    responses = _burst(c, COHORT, spy=spy)
    # every statement is answered, and as the CPU executor answers it
    # (the declined one by that executor itself; a LIMIT without an
    # ORDER BY may keep any five of the unlimited rows)
    for stmt, resp in zip(COHORT, responses):
        assert resp.ok(), f"{stmt}: {resp.error_msg}"
        rows = sorted(map(tuple, resp.rows))
        if "| LIMIT 5" in stmt:
            pool = _cpu_rows(ok, stmt.replace(" | LIMIT 5", ""))
            assert len(rows) == 5 and not set(rows) - set(pool), stmt
        else:
            assert rows == _cpu_rows(ok, stmt), stmt
    got = spy.by_start()
    assert sorted(got) == list(range(1, len(COHORT) + 1))
    # the cohort left in one tick, mixed: COUNT riders and others
    assert max(t["leaves"] for t in _ticks()) == len(COHORT)
    # the parent's path: ONE continuous_results over the whole cohort,
    # same generation, same frontiers
    order = sorted(got)
    ms = {id(call["m"]) for call in spy.calls}
    assert len(ms) == 1
    want = rt.continuous_results(
        spy.calls[0]["space"], spy.calls[0]["m"],
        [got[s][0] for s in order], [got[s][1] for s in order],
        [got[s][2] for s in order], spy.calls[0]["et"])
    declined = []
    for start, cohort_out in zip(order, want):
        own_out = got[start][3]
        if isinstance(cohort_out, Exception):
            assert type(own_out) is type(cohort_out), start
            assert str(own_out) == str(cohort_out), start
            declined.append(start)
            continue
        # row for row: same columns, same rows, same order
        assert own_out[0] == cohort_out[0], start
        assert [list(r) for r in own_out[1]] \
            == [list(r) for r in cohort_out[1]], start
        assert len(own_out[1]) > 0, start
    # the invalid-prop rider declines, alone
    assert declined == [DECLINES + 1]
    assert isinstance(got[DECLINES + 1][3], TpuDecline)


# =========================================== (b) the pump is not held
def test_a_parked_rider_holds_neither_the_pump_nor_a_later_rider(served):
    c, ok = served
    rt = c.tpu_runtime
    st = _stream(c)
    slow = "GO 3 STEPS FROM 11 OVER e, f YIELD e._dst, f._dst"
    # three hops: the seat's, then two ticks on the lanes
    later = "GO 4 STEPS FROM 12 OVER e, f WHERE e.d > 0.1 YIELD e._dst"
    want_slow, want_later = _cpu_rows(ok, slow), _cpu_rows(ok, later)
    entered, release = threading.Event(), threading.Event()
    assemblers = []             # the threads that ran _assemble_results
    real_results, real_group = rt._assemble_results, rt._assemble_group

    def results(space_id, m, queries, vs_lists, et):
        assemblers.append(threading.current_thread().name)
        return real_results(space_id, m, queries, vs_lists, et)

    def group(space_id, m, queries, idxs, vs_lists, et, results_):
        if tuple(queries[idxs[0]].start_vids) == (11,):
            entered.set()
            assert release.wait(30), "never released"
        return real_group(space_id, m, queries, idxs, vs_lists, et,
                          results_)

    rt._assemble_results, rt._assemble_group = results, group
    first = {}

    def park():
        client = c.client()
        assert client.execute("USE s").ok()
        first["resp"] = client.execute(slow)

    t = threading.Thread(target=park)
    try:
        before = flight.recorder.note_tick(stream=-1)
        t.start()
        assert entered.wait(30), "the first rider never assembled"
        # the record of the tick that handed the first rider its
        # frontier lands after the rider is woken, and under a loaded
        # host the rider can be here first: wait for that record, so
        # the mark falls after it
        end = time.monotonic() + 5.0
        while not any(r["handed"] for r in _ticks()
                      if r["id"] > before and r["stream"] >= 0) \
                and time.monotonic() < end:
            time.sleep(0.01)
        mark = flight.recorder.note_tick(stream=-1)
        resp = ok(later)        # seated after it, leaves and returns
        assert t.is_alive() and "resp" not in first
        assert sorted(map(tuple, resp.rows)) == want_later
        # the tick records in between keep coming, all while the first
        # is parked (the record of the tick that handed the later rider
        # its frontier lands after the rider is woken: wait for it)
        end = time.monotonic() + 5.0
        while True:
            between = [r for r in _ticks() if r["id"] > mark
                       and r["stream"] >= 0]
            if sum(r["handed"] for r in between) or time.monotonic() > end:
                break
            time.sleep(0.01)
        assert t.is_alive() and "resp" not in first
        assert sum(r["leaves"] for r in between) == 1
        assert sum(r["handed"] for r in between) == 1
        assert sum(1 for r in between if r["hop_us"] > 0) >= 2
    finally:
        release.set()
        t.join(30)
        del rt._assemble_results, rt._assemble_group
    assert first["resp"].ok(), first["resp"].error_msg
    assert sorted(map(tuple, first["resp"].rows)) == want_slow
    # the pump never assembled a row: each rider's own thread did
    pump = st._pump_thread.name
    assert len(assemblers) == 2 and pump not in assemblers, assemblers


def test_the_windowed_leader_still_assembles_its_batch(served):
    """No flag selects the handover: the continuous tier hands a
    leaver its frontier by what the statement is, the windowed tier's
    leader keeps assembling the whole batch."""
    c, ok = served
    rt = c.tpu_runtime
    stmt = "GO 3 STEPS FROM 13 OVER e, f YIELD e._dst, f._dst"
    want = sorted(map(tuple, ok(stmt).rows))
    calls = []
    real = rt._assemble_results

    def results(space_id, m, queries, vs_lists, et):
        calls.append(len(queries))
        return real(space_id, m, queries, vs_lists, et)

    rt._assemble_results = results
    flags.set("go_dispatch_mode", "windowed")
    try:
        got = sorted(map(tuple, ok(stmt).rows))
    finally:
        flags.set("go_dispatch_mode", "continuous")
        del rt._assemble_results
    assert got == want and calls == [1]
    src = inspect.getsource(bd._ContinuousStream)
    assert "flags.get" not in src[src.index("def _finish"):
                                  src.index("def submit")]


# ===================================== (c) the tracing it brings along
def test_spans_counters_handed_and_the_five_waits(served, monkeypatch):
    c, ok = served
    rt = c.tpu_runtime
    flags.set("trace_sample_rate", 1.0)
    walls = {}          # COHORT's index -> (trace id, wall us, cpu us)
    real_submit = bd._ContinuousStream.submit

    def timed(self, key, payload, steps, upto, reduce):
        h0 = hostclock.stamp()
        try:
            return real_submit(self, key, payload, steps, upto, reduce)
        finally:
            (start,) = payload.start_vids
            wall, cpu, _runq = hostclock.split(h0, hostclock.stamp())
            walls[int(start) - 1] = (tracing.current_context()[0],
                                     wall, cpu)

    monkeypatch.setattr(bd._ContinuousStream, "submit", timed)
    spy, finishes = _Spy(rt), []
    keys = ("go_where", "where_candidates", "where_rows",
            "where_native", "go_reduced")
    before = {k: rt.stats[k] for k in keys}
    _burst(c, COHORT, spy=spy, finishes=finishes)
    grew = {k: rt.stats[k] - before[k] for k in keys}

    # every cohort's leavers are handed their frontier, counted, or
    # filtered by the pump, and the tick records say so
    assert sum(len(leavers) for leavers, _ in finishes) == len(COHORT)
    for leavers, (_stamps, handed, met) in finishes:
        kept = sum(1 for r in leavers if _counts(r) or int(
            r.payload.start_vids[0]) - 1 in PUMPED)
        assert handed + kept == len(leavers) == met["unpack_leavers"]
        assert all((r.frontier is None) == (
            _counts(r) or int(r.payload.start_vids[0]) - 1 in PUMPED)
            for r in leavers)
    ticks = _ticks()
    assert sum(t["handed"] for t in ticks) + len(COUNTS) + len(PUMPED) \
        == sum(t["leaves"] for t in ticks) == len(COHORT)
    for t in ticks:
        assert 0 <= t["handed"] <= t["unpack_leavers"]
        assert not any(k.startswith("where_") or k == "leaver_rows"
                       for k in t), t
    # the pump answered the COUNT riders and the numpy WHEREs, in one
    # call a cohort; every other leaver called for itself, from its
    # own thread
    pump = _stream(c)._pump_thread.name
    for call in spy.calls:
        kept = [red is not None and red[0] == "count"
                or int(q.start_vids[0]) - 1 in PUMPED
                for q, red in zip(call["queries"], call["reduces"])]
        if call["thread"] == pump:
            assert all(kept)
        else:
            assert len(kept) == 1 and not kept[0]

    # the counters grow by what the parent's path grows them by: the
    # same statements through ONE continuous_results over the cohort
    got = spy.by_start()
    order = sorted(got)
    again = {k: rt.stats[k] for k in keys}
    rt.continuous_results(
        spy.calls[0]["space"], spy.calls[0]["m"],
        [got[s][0] for s in order], [got[s][1] for s in order],
        [got[s][2] for s in order], spy.calls[0]["et"])
    assert grew == {k: rt.stats[k] - again[k] for k in keys}
    assert grew["go_where"] == 2 and grew["go_reduced"] == 3
    assert grew["where_native"] == 1

    # the spans land on the rider's OWN trace (the pump's, as before
    # this handover, on its cohort's first leaver's)
    assert sorted(walls) == list(range(len(COHORT)))
    all_wheres = []
    outside = []
    for i, stmt in enumerate(COHORT):
        trace_id, wall, cpu = walls[i]
        nodes = _rider_nodes(trace_id)
        names = [n["name"] for n in nodes]
        mark = [n["tags"] for n in nodes
                if n["name"] == "graph.continuous"][0]
        # (what the pump kept it assembles on its cohort's first
        # leaver's trace, whoever that is: more than one statement
        # unless the burst split)
        own = [n for n in nodes if n["name"] == "tpu.assemble"
               and n["tags"]["queries"] == 1]
        wheres = [n for n in nodes if n["name"] == "tpu.where"]
        all_wheres += wheres
        if i not in COUNTS and i not in PUMPED:
            assert own, (stmt, names)
            # its own assembly is inside its fifth wait
            assert mark["assemble_us"] >= min(n["duration_us"]
                                              for n in own)
        if i == NATIVE:
            # (the pump's two numpy passes land here too where this
            # rider is its cohort's first leaver)
            mine = [n for n in wheres if n["tags"]["native"] == 1]
            assert len(mine) == 1, (stmt, names)
            tags = mine[0]["tags"]
            assert tags["queries"] == 1 and tags["site"] == "assembly"
            assert tags["native"] == 1
            assert tags["candidates"] >= tags["kept"] > 0
            assert tags["cpu_us"] >= 0
        # the five waits tile the rider's time in submit()
        if i == DECLINES:
            assert mark["ending"] != "left-batch"
        else:
            assert mark["ending"] == "left-batch"
        assert all(mark[w] >= 0 for w in tracing.RIDER_WAITS), mark
        total = sum(mark[w] for w in tracing.RIDER_WAITS)
        # the stamps tile enq_t -> its rows; submit() adds admission
        # before and the marker after.  What they cost is read on this
        # thread's own CPU clock: its wall also holds every wait for
        # the interpreter (nine run at once, beside other workers)
        assert total <= wall
        outside.append(cpu - mark["wait_cpu_us"] - mark["assemble_cpu_us"])
    # three tpu.where spans (the declined statement's group opens one
    # over the candidates it has left: none): the rider's own pass is
    # native, the pump's two are numpy
    assert sorted(n["tags"]["native"] for n in all_wheres) == [0, 0, 1]
    assert sorted(outside)[len(outside) // 2] <= 600, outside


# ================================ (d) a numpy WHERE stays on the pump
@pytest.mark.parametrize("library", ["with_the_native_pass", "without"])
def test_a_where_that_filters_in_numpy_is_the_pump_s(served, stale_native,
                                                     library):
    """Sixteen numpy passes at once are slower than one thread running
    them in turn (PERF.md section 6, PR 32), so the pump keeps a WHERE
    the native pass cannot take: an int column, arithmetic on the
    column, a conjunction — and every WHERE when the library lacks the
    pass.  What runs is what the parent ran, on the thread it ran on."""
    c, ok = served
    rt = c.tpu_runtime
    if library == "without":
        stale_native("neb_filter_runs_f64")
    statements = [
        "GO 3 STEPS FROM 21 OVER e, f WHERE e.d > 0.4 YIELD e._dst, e.w",
        "GO 3 STEPS FROM 22 OVER e, f WHERE e.w > 40 YIELD e._dst, e.w",
        "GO 3 STEPS FROM 23 OVER e, f WHERE e.d + 0.0 > 0.4 YIELD e._dst",
        "GO 3 STEPS FROM 24 OVER e, f WHERE e.d > 0.2 && e.d < 0.8 "
        "YIELD e._dst, e.w",
        "GO 3 STEPS FROM 25 OVER e, f YIELD e._dst, f._dst",
    ]
    want = [_cpu_rows(ok, stmt) for stmt in statements]
    spy = _Spy(rt)
    before = {k: rt.stats[k] for k in ("go_where", "where_native")}
    responses = _burst(c, statements, spy=spy)
    for stmt, resp, rows in zip(statements, responses, want):
        assert resp.ok(), f"{stmt}: {resp.error_msg}"
        assert sorted(map(tuple, resp.rows)) == rows and rows, stmt
    pump = _stream(c)._pump_thread.name
    threads = {start: got[4] for start, got in spy.by_start().items()}
    own = {21, 25} if library == "with_the_native_pass" else {25}
    assert {s for s, t in threads.items() if t != pump} == own, threads
    assert rt.stats["go_where"] - before["go_where"] == 4
    assert rt.stats["where_native"] - before["where_native"] \
        == len(own) - 1
    ticks = _ticks()
    assert sum(t["handed"] for t in ticks) == len(own)
    assert sum(t["leaves"] for t in ticks) == len(statements)


def test_what_the_native_pass_takes(served):
    """_native_filter reads the statement's shape, the column as the
    generation stores it, the OVER set's layout and the library."""
    c, ok = served
    rt = c.tpu_runtime
    spy = _Spy(rt)
    _burst(c, [COHORT[NATIVE], COHORT[8], COHORT[0]], spy=spy)
    got = spy.by_start()
    m, et = spy.calls[0]["m"], spy.calls[0]["et"]
    plan = got[NATIVE + 1][0].plan
    col, op, const = rt._native_filter(m, plan, et)
    assert (op, const) == (">", 0.4) and col.values.dtype == np.float64
    assert rt.rider_assembles(m, got[NATIVE + 1][0], et)
    assert rt._native_filter(m, got[9][0].plan, et) is None
    assert not rt.rider_assembles(m, got[9][0], et)
    assert rt.rider_assembles(m, got[1][0], et)      # no WHERE
    # the column as another generation might store it
    key = plan.filter_used[plan.filter_cval.cmp[0]][1:]
    real = m.edge_cols[key]

    class As:
        def __init__(self, values, valid):
            self.values, self.valid = values, valid

    try:
        for other in (As(real.values.astype(np.float32), real.valid),
                      As(real.values, real.valid.astype(np.uint8)),
                      As(real.values[::2], real.valid[::2])):
            m.edge_cols[key] = other
            assert rt._native_filter(m, plan, et) is None
        del m.edge_cols[key]
        assert rt._native_filter(m, plan, et) is None
    finally:
        m.edge_cols[key] = real
    # an OVER set whose edges are not one run a vertex
    cache = m._over_range_cache
    saved = cache[et]
    try:
        cache[et] = None
        assert rt._native_filter(m, plan, et) is None
    finally:
        cache[et] = saved
    assert rt._native_filter(m, plan, et) is not None


# =========================== (e) a generation's tables are filled once
def test_a_fresh_generation_s_tables_are_filled_once(served, monkeypatch):
    """After a generation change every leaver of the first cohort finds
    the per-(mirror, OVER) tables empty in the same instant; one of
    them makes each O(edges) pass and the others wait for it."""
    from nebula_tpu.tpu import runtime
    c, ok = served
    rt = c.tpu_runtime
    spy = _Spy(rt)
    _burst(c, [COHORT[0]], spy=spy)
    m, et = spy.calls[0]["m"], spy.calls[0]["et"]
    want = {"ranges": rt._over_ranges(m, et), "deg": rt._deg_host(m, et),
            "mask": rt._etype_edge_mask(m, et)}
    for attr in ("_over_range_cache", "_deg_cache", "_etype_mask_cache",
                 "_alias_code_cache"):
        monkeypatch.delattr(m, attr, raising=False)
    fills = {"isin": 0, "bincount": 0}
    real_isin, real_bincount = np.isin, np.bincount

    def isin(*a, **k):
        fills["isin"] += 1
        time.sleep(0.05)        # long enough for every rider to arrive
        return real_isin(*a, **k)

    def bincount(*a, **k):
        fills["bincount"] += 1
        return real_bincount(*a, **k)

    monkeypatch.setattr(runtime.np, "isin", isin)
    monkeypatch.setattr(runtime.np, "bincount", bincount)
    riders = 16
    barrier = threading.Barrier(riders)
    got, errors = [None] * riders, []

    def ask(i):
        try:
            barrier.wait()
            got[i] = (rt._over_ranges(m, et), rt._deg_host(m, et),
                      rt._etype_edge_mask(m, et))
        except Exception as ex:     # noqa: BLE001 — reported below
            errors.append(ex)

    threads = [threading.Thread(target=ask, args=(i,))
               for i in range(riders)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    assert fills == {"isin": 1, "bincount": 1}
    for ranges, deg, mask in got:
        # every rider holds the ONE copy
        assert ranges is got[0][0] and deg is got[0][1] \
            and mask is got[0][2]
        assert np.array_equal(ranges[0], want["ranges"][0])
        assert np.array_equal(deg, want["deg"])
        assert np.array_equal(mask, want["mask"])


def test_a_rider_killed_or_late_after_the_handover_skips_its_pass(
        served, monkeypatch):
    """Between the handover and its own pass a rider checks what the
    pump checks at a hop boundary: a KILL or a spent budget ends it
    typed, and it assembles nothing."""
    from nebula_tpu.common.deadline import DeadlineExceeded
    from nebula_tpu.graph.query_registry import KilledError
    c, ok = served
    rt = c.tpu_runtime
    st = _stream(c)
    calls = []
    monkeypatch.setattr(
        rt, "continuous_results",
        lambda *a, **k: calls.append(a) or [(["x"], [])])

    class Rider:
        qid = None
        deadline = None
        frontier = [1]
        distinct = False
        error = result = mirror = payload = reduce = None

    class Spent:
        @staticmethod
        def expired():
            return True

    key = ("go_batch_execute", st.space_id, st.et_tuple, 3, False, None)
    late = Rider()
    late.deadline = Spent()
    st._assemble_own(key, late)
    assert isinstance(late.error, DeadlineExceeded) and not calls
    killed = Rider()
    killed.qid = 1 << 40
    monkeypatch.setattr(bd.query_registry, "is_killed",
                        lambda qid: qid == 1 << 40)
    st._assemble_own(key, killed)
    assert isinstance(killed.error, KilledError) and not calls
    fine = Rider()
    st._assemble_own(key, fine)
    assert fine.error is None and fine.result == (["x"], []) and calls


# ============================ the handover under an impatient interpreter
def test_more_riders_than_cores_with_a_short_switch_interval(served):
    """The pump writes a rider's frontier and generation under the
    stream condition and the rider reads them after it: forty callers
    over three rounds, the interpreter switching every 10 us, and every
    answer is the one the statement gets alone; every leaver was handed
    its frontier or answered by the pump."""
    c, ok = served
    statements = [COHORT[i % len(COHORT)].replace(
        f"FROM {i % len(COHORT) + 1} ", f"FROM {i % N + 1} ")
        for i in range(40) if i % len(COHORT) != DECLINES]
    want = [sorted(map(tuple, ok(stmt).rows)) for stmt in statements]
    flight.recorder.clear_for_tests()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        rounds = [_burst(c, statements) for _ in range(3)]
    finally:
        sys.setswitchinterval(interval)
    for responses in rounds:
        for stmt, resp, rows in zip(statements, responses, want):
            assert resp.ok(), f"{stmt}: {resp.error_msg}"
            got = sorted(map(tuple, resp.rows))
            if "| LIMIT 5" in stmt:
                assert len(got) == len(rows), stmt
            else:
                assert got == rows, stmt
    kept = 3 * sum("COUNT(*)" in stmt or "e.w >" in stmt
                   for stmt in statements)
    ticks = _ticks()
    assert sum(t["leaves"] for t in ticks) == 3 * len(statements)
    assert sum(t["handed"] for t in ticks) + kept \
        == sum(t["leaves"] for t in ticks)
