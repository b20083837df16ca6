"""The continuous pump timed from inside (docs/observability.md "The
pump trace"): one set of perf_counter stamps per tick feeds

  * the flight recorder's tick record (always on): ``seat_us`` and the
    five parts of ``assemble_us``;
  * a ``pump.tick`` trace of its own (only for ticks that touched a
    traced rider), emitted post hoc from those stamps, with the idle
    stretch before it as ``pump.idle``;
  * each rider's ``graph.continuous`` marker: the five waits its
    ``submit()`` was made of.

And the instrument that measured by stopping the pump is off it: the
continuous hop never blocks on the device and writes no ``timing`` row.
"""
import json
import re
import threading
import time
import tracemalloc
import urllib.request

import numpy as np
import pytest

from nebula_tpu.common import clock, flight, hostclock, tracing
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import slow_log, trace_store
from nebula_tpu.graph import batch_dispatch as bd

from test_continuous import _boot_graph, _paced, stub  # noqa: F401

PARTS = ("fetch_wait_us", "d2h_us", "unpack_us", "rows_us",
         "handover_us")
WAITS = tracing.RIDER_WAITS
CHILDREN = {"pump.hold", "pump.seat", "pump.enqueue", "pump.count", "pump.fetch_wait",
            "pump.d2h", "pump.unpack", "pump.rows", "pump.handover"}


@pytest.fixture(scope="module")
def graph():
    flags.set("go_dispatch_mode", "continuous")
    c, g, ok = _boot_graph(seed=24)
    ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored, compiled
    ok("GO 3 STEPS FROM 1 OVER e YIELD e._dst")
    yield c, g, ok
    c.stop()


@pytest.fixture(autouse=True)
def _clean(graph):
    _settle(graph[0])
    saved = flags.get("trace_sample_rate")
    trace_store.clear_for_tests()
    flight.recorder.clear_for_tests()
    yield
    flags.set("trace_sample_rate", saved)
    clock.reset_for_tests()
    trace_store.clear_for_tests()


def _settle(c, timeout_s=5.0):
    """Wait until nothing is queued or seated on any stream."""
    d = c.tpu_runtime.dispatcher
    end = time.monotonic() + timeout_s
    while time.monotonic() < end \
            and d.continuous.seat_counts() != (0, 0):
        time.sleep(0.01)
    time.sleep(0.05)        # the last tick's record and trace land


def _burst(c, statements):
    """Run the statements concurrently; returns their responses."""
    out, errors = {}, []
    barrier = threading.Barrier(len(statements))

    def worker(i):
        try:
            g2 = c.client()
            g2.execute("USE s")
            barrier.wait()
            r = g2.execute(statements[i])
            assert r.ok(), r.error_msg
            out[i] = r
        except Exception as ex:     # noqa: BLE001 — reported below
            errors.append(ex)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(len(statements))]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert not errors, errors
    _settle(c)
    return [out[i] for i in range(len(statements))]


def _mixed(n):
    """Statements of 2 to 5 steps: one to four hops, ridden in one to
    three ticks (the seat takes the first hop of those of two or
    more)."""
    return [f"GO {2 + i % 4} STEPS FROM {1 + i} OVER e YIELD e._dst"
            for i in range(n)]


def _walk(node):
    yield node
    for ch in node.get("children", ()):
        yield from _walk(ch)


def _trees():
    # a trace listed and dropped from the bounded store before it is
    # asked for reads as None
    trees = (trace_store.tree(int(s["id"], 16))
             for s in trace_store.summaries())
    return [t for t in trees if t is not None]


def _pump_roots(name):
    return [r for t in _trees() for r in t["roots"] if r["name"] == name]


def _ticks():
    return [r for r in flight.recorder.dump(limit=4096)
            if r["kind"] == "tick"]


def _held_stream(stub, traced=False):
    """A stream over a stub device that takes 60 ms a hop
    (tests/test_continuous.py TestHold), two closed-loop callers on
    it: most ticks hold their door.  Returns it once they are done."""
    s = stub(0.06)
    _paced(s, 0.06)
    a, b = [], []
    ta = s.caller(1, 5, 0.005, a, traced=traced)
    tb = s.caller(2, 5, 0.005, b, traced=traced)
    ta.join(30.0)
    tb.join(30.0)
    assert len(a) == len(b) == 5
    return s


# ===================================================== (a) the tick
class TestTickTrace:
    def test_every_flight_tick_has_one_tiled_tree(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        _burst(c, _mixed(10))
        ticks = _ticks()
        assert len(ticks) >= 3
        roots = _pump_roots("pump.tick")
        assert len(roots) == len(ticks)
        by_rec = {r["tags"]["rec"]: r for r in roots}
        for rec in ticks:
            root = by_rec[rec["id"]]
            for k in ("stream", "tick", "seats", "joins", "leaves"):
                assert root["tags"][k] == rec[k]
            assert abs(root["duration_us"] - rec["dur_us"]) <= 2
            kids = root["children"]
            assert {k["name"] for k in kids} <= CHILDREN
            lo = root["start_us"]
            hi = lo + root["duration_us"]
            at = lo
            for k in kids:                  # sorted by start
                assert k["start_us"] >= at, (k, at)     # disjoint
                at = k["start_us"] + k["duration_us"]
                assert at <= hi                         # inside
            covered = sum(k["duration_us"] for k in kids)
            assert covered >= 0.95 * root["duration_us"], (root, rec)
            # the record's seat_us and parts are the same stamps
            seat = [k for k in kids if k["name"] == "pump.seat"]
            assert len(seat) == 1
            assert abs(seat[0]["duration_us"] - rec["seat_us"]) <= 2
            for part in PARTS:
                # a counting cohort's pump.count heads the fetch wait
                # (tests/test_go_count_distinct.py has such cohorts)
                names = {"pump." + part[:-3]} | (
                    {"pump.count"} if part == "fetch_wait_us" else set())
                got = sum(k["duration_us"] for k in kids
                          if k["name"] in names)
                assert abs(got - rec[part]) <= 4, (part, got, rec)

    def test_assemble_us_is_the_sum_of_its_parts(self, graph):
        c, g, ok = graph                    # untraced: always on
        flags.set("trace_sample_rate", 0.0)
        _burst(c, _mixed(8))
        ticks = _ticks()
        assert any(t["assemble_us"] > 0 for t in ticks)
        for t in ticks:
            assert t["assemble_us"] == sum(t[p] for p in PARTS)
            assert t["seat_us"] >= 0
            assert t["seat_us"] + t["assemble_us"] <= t["dur_us"]
        # frontiers are handed over where leavers were finished: every
        # statement of the burst yields rows, so every leaver got its
        # own to assemble
        assert sum(t["handed"] for t in ticks) \
            == sum(t["leaves"] for t in ticks) == 8
        assert all(t["handed"] == 0 for t in ticks
                   if t["assemble_us"] == 0)


class TestHoldTrace:
    """The hold in the pump's own records (graph/batch_dispatch.py
    _hold): ``hold_us`` / ``hold_joins`` of the tick record, the
    ``pump.hold`` child that heads a tick that held."""

    @pytest.mark.parametrize("on", ["cluster", "stub"])
    def test_a_fast_device_is_never_held_for(self, graph, stub, on):
        """(b) a fetch that returns at once (CPU jax's hops; a stub
        whose hops take no time): no tick holds, no span says so."""
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        if on == "cluster":
            _burst(c, _mixed(10))
            ticks = _ticks()
        else:
            s = stub(0.0)
            s.seated_long()
            out = []
            ts = [s.caller(k, 6, 0.001, out, traced=True)
                  for k in (1, 2, 3)]
            [t.join(30.0) for t in ts]
            assert len(out) == 18
            ticks = s.ticks()
        assert len(ticks) >= 3
        assert all(t["hold_us"] == 0 and t["hold_joins"] == 0
                   and t["hold_cpu_us"] == 0 for t in ticks)
        roots = _pump_roots("pump.tick")
        assert roots
        assert not [k for r in roots for k in r["children"]
                    if k["name"] == "pump.hold"]

    def test_a_held_tick_has_one_pump_hold_at_its_head(self, graph,
                                                       stub):
        s = _held_stream(stub, traced=True)
        s.until(lambda: len(s.st.seated) == 1 and not s.st.queue)
        time.sleep(0.1)
        ticks = {t["id"]: t for t in s.ticks()}
        roots = [r for r in _pump_roots("pump.tick")
                 if r["tags"]["rec"] in ticks]
        assert roots
        n_held = 0
        for root in roots:
            rec = ticks[root["tags"]["rec"]]
            kids = root["children"]
            assert {k["name"] for k in kids} <= CHILDREN
            holds = [k for k in kids if k["name"] == "pump.hold"]
            seat = [k for k in kids if k["name"] == "pump.seat"][0]
            assert abs(seat["duration_us"] - rec["seat_us"]) <= 2
            if rec["hold_us"] == 0:
                assert not holds
                assert abs(seat["start_us"] - root["start_us"]) <= 2
                continue
            n_held += 1
            assert len(holds) == 1 and kids[0] is holds[0]
            hold = holds[0]
            assert abs(hold["duration_us"] - rec["hold_us"]) <= 2
            assert hold["start_us"] == root["start_us"]
            assert hold["tags"]["joins"] == rec["hold_joins"]
            assert hold["tags"]["cpu_us"] == rec["hold_cpu_us"]
            # the seat starts where the door shut
            assert abs(seat["start_us"] - hold["start_us"]
                       - hold["duration_us"]) <= 2
            # the children still tile the tick
            covered = sum(k["duration_us"] for k in kids)
            assert covered >= 0.95 * root["duration_us"], (root, rec)
        assert n_held >= 3
        # the two stats count what the records count
        from nebula_tpu.common.stats import stats
        assert stats.read_stats("graph.continuous.hold_us.sum.600") \
            >= sum(t["hold_us"] for t in ticks.values())
        assert stats.read_stats("graph.continuous.held_joins.sum.600") \
            >= sum(t["hold_joins"] for t in ticks.values())


class TestUnpackCounters:
    """What ``unpack_us`` met rides the same way the stamps do: from
    the resolver (tpu/runtime.py _LaneFetch) through _finish to the
    tick record and the ``pump.unpack`` span."""
    FIELDS = ("unpack_leavers", "unpack_live", "unpack_rows",
              "unpack_native")

    @pytest.mark.parametrize("library", ["with_the_native_pass", "without"])
    def test_tick_record_says_what_the_unpack_met(
            self, graph, library, stale_native, monkeypatch, capfd):
        c, g, ok = graph
        rt = c.tpu_runtime
        if library == "without":
            # a build from before native/unpack.cc: numpy unpacks, and
            # says so once
            from nebula_tpu.tpu import runtime
            stale_native("neb_unpack_lanes")
            monkeypatch.setattr(runtime, "_said", set())
        flags.set("trace_sample_rate", 1.0)
        before = rt.stats["unpack_native"]
        capfd.readouterr()
        _burst(c, _mixed(9))
        ticks = _ticks()
        # the native pass takes every leaver of every cohort, or none
        took = 9 if library == "with_the_native_pass" else 0
        for t in ticks:
            assert t["unpack_native"] == (t["unpack_leavers"] if took
                                          else 0)
        assert sum(t["unpack_native"] for t in ticks) == took \
            == rt.stats["unpack_native"] - before
        assert capfd.readouterr().err.count(
            "native lane unpack missing") == (0 if took else 1)
        for t in ticks:
            for f in self.FIELDS:
                assert isinstance(t[f], int) and t[f] >= 0, (f, t)
            assert t["unpack_live"] <= t["unpack_leavers"]
            # the five parts are what they were
            assert t["assemble_us"] == sum(t[p] for p in PARTS)
            if t["assemble_us"] == 0:
                assert not any(t[f] for f in self.FIELDS)
        # every statement left once, through the resolver
        assert sum(t["unpack_leavers"] for t in ticks) \
            == sum(t["leaves"] for t in ticks) == 9
        assert sum(t["unpack_rows"] for t in ticks) > 0
        # 40 vertices: a lone leaver's frontier can pass a fifth of
        # them, a cohort's live rows per leaver need not
        assert 0 <= sum(t["unpack_live"] for t in ticks) <= 9
        # the span carries the same numbers, cohort by cohort
        for f, tag in zip(self.FIELDS,
                          ("leavers", "live", "rows", "native")):
            spans = [k for r in _pump_roots("pump.tick")
                     for k in r["children"] if k["name"] == "pump.unpack"]
            assert sum(k["tags"][tag] for k in spans) \
                == sum(t[f] for t in ticks)

    def test_a_resolver_without_the_counters_still_yields_a_record(
            self, graph):
        """A stand-in resolver (a plain callable: no stamps, no
        counters) reads as zeros, and the parts still sum."""
        c, g, ok = graph
        d = c.tpu_runtime.dispatcher
        st = next(s for s in d.continuous.streams()
                  if s.session is not None)
        sess = st.session
        real = sess.extract

        def bare(leavers):
            resolver = real(leavers)
            return lambda: resolver()

        sess.extract = bare
        try:
            r = ok("GO 3 STEPS FROM 2 OVER e YIELD e._dst")
        finally:
            del sess.extract
        _settle(c)
        flags.set("storage_backend", "cpu")
        try:
            want = ok("GO 3 STEPS FROM 2 OVER e YIELD e._dst")
        finally:
            flags.set("storage_backend", "tpu")
        assert sorted(map(tuple, r.rows)) == sorted(map(tuple, want.rows))
        done = [t for t in _ticks() if t["assemble_us"] > 0]
        assert done
        for t in done:
            assert not any(t[f] for f in self.FIELDS)
            # no stamps: the stretch up to the rows is one part
            assert t["d2h_us"] == t["unpack_us"] == 0
            assert t["assemble_us"] == sum(t[p] for p in PARTS)


# =================================================== (b) the rider
class TestRiderWaits:
    def test_five_waits_sum_to_the_submit_wall(self, graph, monkeypatch):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        walls = {}
        real = bd._ContinuousStream.submit

        def timed(self, key, payload, steps, upto, reduce):
            t = time.perf_counter()
            try:
                return real(self, key, payload, steps, upto, reduce)
            finally:
                ctx = tracing.current_context()
                walls[ctx[0]] = (time.perf_counter() - t) * 1e6

        monkeypatch.setattr(bd._ContinuousStream, "submit", timed)
        assert bd.PUMP_TICK_RIDER_TAGS >= 8
        _burst(c, _mixed(8))
        ticks_of = {}
        for r in _pump_roots("pump.tick"):
            for rider in r["tags"]["riders"]:
                ticks_of.setdefault(rider, []).append(r["tags"]["tick"])
        seen, outside = 0, []
        for tree in _trees():
            marks = [n["tags"] for r in tree["roots"] for n in _walk(r)
                     if n["name"] == "graph.continuous"]
            if not marks:
                continue
            m = marks[0]
            seen += 1
            assert m["ending"] == "left-batch"
            assert all(m[w] >= 0 for w in WAITS), m
            wall = walls[int(tree["trace_id"], 16)]
            total = sum(m[w] for w in WAITS)
            # the stamps tile enq_t -> its rows; submit() adds
            # admission before and the marker after: a few us each,
            # unless this thread loses the interpreter there (eight
            # run at once)
            assert total <= wall
            outside.append(wall - total)
            assert m["joined_tick"] < m["left_tick"]
            # the seat took the first hop of every rider that keeps
            # one on the lanes afterwards (no UPTO here), PR 43
            assert m["seat_hops"] == int(m["hops"] >= 2), m
            assert m["left_tick"] - m["joined_tick"] \
                == m["hops"] - m["seat_hops"]
            # joined to its ticks by trace id: every tick it rode
            # names it (a pump.tick names up to 8 riders, and this
            # burst has no more)
            mine = ticks_of.get(tree["trace_id"], [])
            assert mine, (tree["trace_id"], ticks_of)
            assert set(range(m["joined_tick"] + 1, m["left_tick"] + 1)) \
                <= set(mine), (m, mine)
        assert seen == 8
        assert sorted(outside)[len(outside) // 2] <= 400, outside

    def test_slow_log_entry_says_which_wait_was_slow(self, graph):
        c, g, ok = graph
        saved = flags.get("slow_query_threshold_ms")
        flags.set("slow_query_threshold_ms", 1)
        slow_log.clear_for_tests()
        st = next(iter(c.tpu_runtime.dispatcher.continuous.streams()))
        st.tick_delay_s = 0.03
        try:
            ok("GO 5 STEPS FROM 5 OVER e YIELD e._dst")
        finally:
            st.tick_delay_s = 0.0
            flags.set("slow_query_threshold_ms", saved)
        e = [e for e in slow_log.dump() if "5 STEPS FROM 5" in e["stmt"]]
        assert e, slow_log.dump()
        e = e[0]
        assert all(k in e for k in WAITS + ("left_tick",)), e
        # four hops, the first of them the seat's, so three ridden at
        # >= 30 ms of tick delay each: the ride was slow
        assert e["ride_us"] >= 55_000 and e["ride_us"] == max(
            e[w] for w in WAITS)
        assert sum(e[w] for w in WAITS) <= e["latency_us"]


# ============================================== (c) sampling 0 is free
class TestUntraced:
    def test_no_pump_span_no_trace_no_span_allocated(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 0.0)
        _burst(c, _mixed(6))                # warm code paths
        trace_store.clear_for_tests()
        flight.recorder.clear_for_tests()
        tracemalloc.start()
        try:
            snap1 = tracemalloc.take_snapshot()
            _burst(c, _mixed(6))
            snap2 = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        assert _ticks(), "the burst never ticked"
        assert trace_store.summaries() == []
        # nothing allocated by Span or emit (a new thread's first read
        # of the thread-local context allocates its dict in
        # current_context()/span(): not a span, not the pump's)
        import inspect
        span_lines = set()
        for obj in (tracing.Span, tracing.emit):
            src, first = inspect.getsourcelines(obj)
            span_lines |= set(range(first, first + len(src)))
        filt = [tracemalloc.Filter(True, "*/common/tracing.py")]
        grew = [s for s in snap2.filter_traces(filt).compare_to(
                    snap1.filter_traces(filt), "lineno")
                if (s.size_diff > 0 or s.count_diff > 0)
                and s.traceback[0].lineno in span_lines]
        assert grew == [], f"a Span was built on an untraced tick: {grew}"


# ======================================================= (d) PROFILE
class TestProfile:
    def test_marker_has_the_waits_and_traces_lists_its_ticks(self, graph):
        from nebula_tpu.webservice import WebService
        c, g, ok = graph
        flags.set("trace_sample_rate", 0.0)     # PROFILE alone traces
        r = ok("PROFILE GO 2 STEPS FROM 3 OVER e YIELD e._dst")
        _settle(c)
        prof = r.raw["profile"]
        marks = [n["tags"] for root in prof["roots"] for n in _walk(root)
                 if n["name"] == "graph.continuous"]
        assert len(marks) == 1
        m = marks[0]
        assert all(m[w] >= 0 for w in WAITS), m
        assert m["left_tick"] == m["joined_tick"] + 1   # one hop
        # no span was added to the rider's own tree for this
        names = {n["name"] for root in prof["roots"] for n in _walk(root)}
        assert not any(n.startswith("pump.") for n in names), names
        ws = WebService("test").start()
        base = f"http://127.0.0.1:{ws.port}"
        try:
            listing = json.load(urllib.request.urlopen(
                f"{base}/traces"))["traces"]
            pumps = [t for t in listing if t["name"] == "pump.tick"]
            assert pumps, listing
            rode = []
            for t in pumps:
                tree = json.load(urllib.request.urlopen(
                    f"{base}/traces?id={t['id']}"))
                root = [x for x in tree["roots"]
                        if x["name"] == "pump.tick"][0]
                if prof["trace_id"] in root["tags"]["riders"]:
                    rode.append(root["tags"]["tick"])
        finally:
            ws.stop()
        # only the ticks the PROFILEd statement rode were traced
        assert len(rode) == len(pumps)
        assert m["left_tick"] in rode


# ==================================================== (e) fake clock
class TestFakeClock:
    def test_advance_for_tests_ages_the_post_hoc_spans(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        ok("GO 2 STEPS FROM 2 OVER e YIELD e._dst")
        _settle(c)
        before = max(r["start_us"] for r in _pump_roots("pump.tick"))
        live0 = max(t["roots"][0]["start_us"] for t in _trees()
                    if t["roots"][0]["name"] == "graph.query")
        clock.advance_for_tests(3600.0)
        ok("GO 2 STEPS FROM 2 OVER e YIELD e._dst")
        _settle(c)
        after = max(r["start_us"] for r in _pump_roots("pump.tick"))
        live1 = max(t["roots"][0]["start_us"] for t in _trees()
                    if t["roots"][0]["name"] == "graph.query")
        assert 3600e6 <= after - before < 3660e6
        # ...exactly like the spans that time themselves
        assert abs((after - before) - (live1 - live0)) < 1e6
        # and the emitted tick still sits where its rider's query does
        q = [t["roots"][0] for t in _trees()
             if t["roots"][0]["name"] == "graph.query"
             and t["roots"][0]["start_us"] == live1][0]
        assert q["start_us"] <= after <= q["start_us"] + q["duration_us"]

    def test_emit_takes_explicit_timing(self):
        tid = tracing.new_trace_id()
        root = tracing.emit("pump.tick", tid, None, 1_000, 500, tick=7)
        tracing.emit("pump.seat", tid, root, 1_000, 20)
        tree = trace_store.tree(tid)
        assert tree["roots"][0]["start_us"] == 1_000
        assert tree["roots"][0]["duration_us"] == 500
        assert tree["roots"][0]["tags"] == {"tick": 7}
        assert tree["roots"][0]["children"][0]["name"] == "pump.seat"
        assert tracing.current_context() is None


# ========================================================= (f) idle
class TestIdle:
    def test_idle_stretch_between_bursts_is_one_pump_idle(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        _burst(c, _mixed(4))
        n0 = len(_pump_roots("pump.idle"))
        t_gap = time.perf_counter()
        time.sleep(0.4)
        gap_us = (time.perf_counter() - t_gap) * 1e6
        _burst(c, _mixed(4))
        idles = sorted(_pump_roots("pump.idle"),
                       key=lambda r: -r["duration_us"])
        assert len(idles) > n0
        long = [r for r in idles if r["duration_us"] >= 0.9 * gap_us]
        assert len(long) == 1, idles
        assert long[0]["tags"]["why"] == "no_work"
        # it ends where a tick starts, and that tick's record has it
        ticks = _pump_roots("pump.tick")
        end = long[0]["start_us"] + long[0]["duration_us"]
        assert any(abs(t["start_us"] - end) <= 2 for t in ticks)
        assert any(r["idle_us"] == long[0]["duration_us"]
                   for r in _ticks())
        # a stretch under the floor is loop overhead, not a span
        assert all(r["duration_us"] >= bd.PUMP_IDLE_SPAN_MIN_US
                   for r in idles)

    def test_a_delayed_tick_is_not_no_work(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        st = next(iter(c.tpu_runtime.dispatcher.continuous.streams()))
        st.tick_delay_s = 0.02
        try:
            ok("GO 4 STEPS FROM 6 OVER e YIELD e._dst")
        finally:
            st.tick_delay_s = 0.0
        _settle(c)
        whys = [r["tags"]["why"] for r in _pump_roots("pump.idle")]
        assert "tick_delay" in whys, whys


# ================================== (g) the probe is off the pump
class TestNoStoppingProbe:
    def test_hop_never_blocks_and_writes_no_timing_row(self, graph,
                                                       monkeypatch):
        import jax
        c, g, ok = graph
        rt = c.tpu_runtime
        saved = flags.get("tpu_device_timing_every")
        flags.set("tpu_device_timing_every", 1)      # every dispatch
        in_fetch = threading.local()
        outside = []
        real_call = type(rt)._maybe_time_device
        probed = []
        monkeypatch.setattr(
            type(rt), "_maybe_time_device",
            lambda self, *a, **k: (probed.append(k.get("kind")),
                                   real_call(self, *a, **k))[1])
        real_bur = jax.block_until_ready
        monkeypatch.setattr(
            jax, "block_until_ready",
            lambda x: (outside.append("jax.block_until_ready"),
                       real_bur(x))[1])
        from nebula_tpu.tpu.runtime import _LaneFetch
        real_fetch = _LaneFetch.__call__

        def fetch(self):
            in_fetch.on = True
            try:
                return real_fetch(self)
            finally:
                in_fetch.on = False

        monkeypatch.setattr(_LaneFetch, "__call__", fetch)
        try:
            _burst(c, _mixed(8))
        finally:
            flags.set("tpu_device_timing_every", saved)
        assert _ticks()
        assert probed == [], probed
        assert outside == [], outside
        rows = [r for r in flight.recorder.dump(limit=4096)
                if r["kind"] == "timing"]
        assert rows == [], rows
        # the device wait is read where the pump blocks anyway
        assert any(t["fetch_wait_us"] > 0 for t in _ticks())

    def test_no_probe_call_in_the_session_source(self):
        import inspect
        from nebula_tpu.tpu.runtime import _ContinuousGoSession
        src = inspect.getsource(_ContinuousGoSession)
        assert not re.search(r"_maybe_time_device\(", src)


# ================================== the hop's branch in the tick record
class TestHopBranchFields:
    """PR 25: the hop program says which branch it took; the session
    reads that without waiting on the hop, the tick record carries
    it."""

    def test_tick_record_says_which_branch_ran(self, graph):
        c, g, ok = graph
        rt = c.tpu_runtime
        before = dict(rt.stats)
        _burst(c, _mixed(6))
        ticks = _ticks()
        for field in ("hop_reads", "hop_sparse", "hop_slots",
                      "hop_onesided", "hop_swept"):
            assert all(field in t for t in ticks), field
        reads = sum(t["hop_reads"] for t in ticks)
        # OVER one edge type forwards: every hop read one direction's
        # table only (PR 35), in the records and in rt.stats
        assert all(t["hop_onesided"] == t["hop_reads"] for t in ticks)
        assert rt.stats["hop_onesided"] - before["hop_onesided"] == reads
        # every hop of the burst was read into exactly one record
        assert reads == sum(1 for t in ticks if t["hop_us"] > 0)
        assert reads == (rt.stats["hop_sparse"] - before["hop_sparse"]
                         + rt.stats["hop_dense"] - before["hop_dense"])
        # a 40-vertex graph: every frontier is under the budget
        assert sum(t["hop_sparse"] for t in ticks) == reads
        assert all(0 <= t["hop_sparse"] <= t["hop_reads"] for t in ticks)
        pushed = [t for t in ticks if t["hop_sparse"]]
        assert pushed and all(
            0 < t["hop_slots"] < 42 * 512 * t["hop_reads"] for t in pushed)
        # a push gathers the slots it reports (PR 39): every hop here
        # pushed, so the two counts are one, in the records and in
        # rt.stats
        assert all(t["hop_swept"] == t["hop_slots"] for t in ticks)
        assert rt.stats["hop_swept_slots"] - before["hop_swept_slots"] \
            == sum(t["hop_slots"] for t in ticks)

    def test_show_timeline_renders_the_branch(self, graph):
        c, g, ok = graph
        _burst(c, _mixed(4))
        r = ok("SHOW TIMELINE 64")
        detail = [row[5] for row in r.rows if row[3] == "tick"]
        assert detail
        for field in ("hop_reads=", "hop_sparse=", "hop_slots="):
            assert all(field in d for d in detail), (field, detail[0])

    def test_reading_the_branch_never_waits_on_a_hop(self, graph):
        """A hop still in flight keeps its info vector for a later
        read: nothing in the session blocks on it (hop_us stays an
        enqueue time)."""
        import inspect
        from nebula_tpu.tpu.runtime import _ContinuousGoSession
        src = inspect.getsource(_ContinuousGoSession)
        assert "block_until_ready" not in src
        assert "is_ready()" in inspect.getsource(
            _ContinuousGoSession.read_hop_info)

        class InFlight:
            asked = 0

            def is_ready(self):
                InFlight.asked += 1
                return False

            def __array__(self, *a, **k):
                raise AssertionError("read before it was ready")

        c, g, ok = graph
        st = next(iter(c.tpu_runtime.dispatcher.continuous.streams()))
        sess = st.session
        assert sess is not None
        sess.hop_reads()                    # drain what is there
        sess._hop_info.append(InFlight())
        try:
            assert sess.hop_reads() == (0, 0, 0, 0, 0)
            assert InFlight.asked >= 1 and len(sess._hop_info) == 1
        finally:
            sess._hop_info.clear()


# ================================== (g) the host's time has an owner
PHASES = bd.PUMP_PHASES
JOIN_PARTS = ("join_map_us", "join_pack_us", "join_enqueue_us")
RIDER_HOST = ("wait_cpu_us", "wait_runq_us", "assemble_cpu_us",
              "assemble_runq_us")


def _marks():
    return [n["tags"] for t in _trees() for r in t["roots"]
            for n in _walk(r) if n["name"] == "graph.continuous"]


class TestHostClocks:
    """Every stamp that bounds a phase is the thread's three clocks
    (common/hostclock.py): the tick record, the pump.* children and
    the rider's marker say how long the thread ran and how long it was
    runnable without a core beside the wall."""

    @pytest.mark.parametrize("held", [False, True],
                             ids=["cluster", "held-stub"])
    def test_the_parts_tile_the_tick_and_the_join(self, graph, stub,
                                                  held):
        """The eleven parts and ``other_us`` tile ``dur_us``: on the
        cluster, where CPU jax's hops leave nothing to hold for, and
        on a stream whose stub device takes 60 ms a hop, where most
        ticks hold their door (tests/test_continuous.py TestHold)."""
        c, g, ok = graph
        flags.set("trace_sample_rate", 0.0)     # always on
        assert len(PHASES) == 11 and PHASES[0] == "hold"
        if held:
            ticks = _held_stream(stub).ticks()
            assert sum(t["hold_us"] > 0 for t in ticks) >= 5
            assert sum(t["hold_joins"] for t in ticks) >= 5
        else:
            _burst(c, _mixed(9))
            ticks = _ticks()
            assert all(t["hold_us"] == 0 for t in ticks)
        assert len(ticks) >= 3
        has_runq = hostclock.stamp()[2] is not None
        for t in ticks:
            assert 0 <= t["hold_joins"] <= t["joins"], t
            assert t["hold_us"] > 0 or t["hold_joins"] == 0, t
            assert sum(t[p + "_us"] for p in PHASES) + t["other_us"] \
                == t["dur_us"], t
            assert t["other_us"] >= 0
            assert sum(t[k] for k in JOIN_PARTS) == t["join_us"], t
            assert all(t[k] >= 0 for k in JOIN_PARTS), t
            assert t["assemble_us"] == sum(t[p] for p in PARTS)
            for prefix in ("",) + tuple(p + "_" for p in PHASES):
                assert t[prefix + "cpu_us"] >= 0, (prefix, t)
                assert (prefix + "runq_us" in t) == has_runq, (prefix, t)
            # a thread cannot run for longer than the wall says (the
            # clocks are read a microsecond apart, once a stamp)
            assert t["cpu_us"] <= t["dur_us"] + 50, t
            if t["joins"] == 0:
                assert t["join_us"] == 0 and t["join_cpu_us"] == 0
        if not held:                    # the stub's join is no work
            assert any(t["join_map_us"] > 0 for t in ticks)
            assert any(t["join_enqueue_us"] > 0 for t in ticks)

    def test_every_pump_child_says_what_the_thread_did(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        _burst(c, _mixed(8))
        ticks = {t["id"]: t for t in _ticks()}
        roots = _pump_roots("pump.tick")
        assert roots
        has_runq = hostclock.stamp()[2] is not None
        for root in roots:
            rec = ticks[root["tags"]["rec"]]
            for k in root["children"]:
                assert k["name"] in CHILDREN
                assert k["tags"]["cpu_us"] >= 0, k
                assert ("runq_us" in k["tags"]) == has_runq, k
            enq = [k for k in root["children"]
                   if k["name"] == "pump.enqueue"]
            assert len(enq) == 1
            for f in ("join_us", "hop_us", "extract_us", "clear_us"):
                assert enq[0]["tags"][f] == rec[f], (f, enq[0], rec)
            # the children's run time is the record's, phase by phase
            for part in PARTS + ("seat_us",):
                names = {"pump." + part[:-3]} | (
                    {"pump.count"} if part == "fetch_wait_us" else set())
                got = sum(k["tags"]["cpu_us"] for k in root["children"]
                          if k["name"] in names)
                assert abs(got - rec[part[:-3] + "_cpu_us"]) <= 4, \
                    (part, got, rec)

    def test_the_riders_marker_has_its_own_threads_clocks(self, graph):
        c, g, ok = graph
        flags.set("trace_sample_rate", 1.0)
        _burst(c, _mixed(6))
        marks = _marks()
        assert len(marks) == 6
        has_runq = hostclock.stamp()[2] is not None
        for m in marks:
            for tag in RIDER_HOST:
                if "runq" in tag and not has_runq:
                    assert tag not in m
                else:
                    assert m[tag] >= 0, (tag, m)
            # the waits are what they were
            assert all(m[w] >= 0 for w in WAITS), m
            assert m["assemble_cpu_us"] <= m["assemble_us"] + 50

    def test_without_schedstat_no_runq_anywhere_and_no_zero(
            self, graph, monkeypatch):
        c, g, ok = graph
        monkeypatch.setattr(hostclock, "_runq", False)
        flags.set("trace_sample_rate", 1.0)
        _burst(c, _mixed(6))
        ticks = _ticks()
        assert ticks
        for t in ticks:
            assert not [k for k in t if "runq" in k], t
            assert sum(t[p + "_us"] for p in PHASES) + t["other_us"] \
                == t["dur_us"]
            assert "cpu_us" in t and "unpack_cpu_us" in t
        for root in _pump_roots("pump.tick"):
            for k in root["children"]:
                assert "cpu_us" in k["tags"]
                assert "runq_us" not in k["tags"], k
        marks = _marks()
        assert len(marks) == 6
        for m in marks:
            assert "wait_cpu_us" in m and "assemble_cpu_us" in m
            assert not [k for k in m if "runq" in k], m

    def test_a_rider_woken_for_nothing_pays_for_it(self, monkeypatch):
        """A stream driven by hand: the rider's thread sleeps in
        cond.wait, N notify_all wake it to test rider.done and sleep
        again, then its answer comes.  Its wait_cpu_us grows with N;
        no time is asserted, only that more wake-ups cost more."""

        class Woken(threading.Condition):
            wakes = 0

            def wait(self, timeout=None):
                got = super().wait(timeout)
                self.wakes += 1
                return got

        class Disp:
            _lock = threading.Lock()
            stats = {}

        class Sched:
            dispatcher = Disp()
            runtime = None

        class Hand(bd._ContinuousStream):
            def _pump(self):            # the test is the pump
                return

        got = []
        monkeypatch.setattr(
            bd.tracing, "annotate",
            lambda name, **tags: got.append(tags)
            if name == "graph.continuous" else None)

        def ride(n_wakes):
            st = Hand(Sched(), 1, (1,))
            st.cond = Woken()
            out = []
            t = threading.Thread(target=lambda: out.append(
                st.submit(("k",), object(), 2, False, None)))
            t.start()
            end = time.monotonic() + 30.0
            while time.monotonic() < end:
                with st.cond:
                    if st.queue:
                        rider = st.queue.pop(0)
                        break
                time.sleep(0.001)
            for i in range(n_wakes):
                with st.cond:
                    st.cond.notify_all()
                while st.cond.wakes <= i and time.monotonic() < end:
                    time.sleep(0)       # until it has woken and slept
            assert st.cond.wakes >= n_wakes
            with st.cond:
                rider.result, rider.mirror = (["c"], []), "m"
                rider.done = True
                st.cond.notify_all()
            t.join(30.0)
            assert out == [((["c"], []), "m")]
            return got.pop()

        few = ride(0)
        many = ride(3000)
        assert few["ending"] == many["ending"] == "left-batch"
        assert many["wait_cpu_us"] > few["wait_cpu_us"]
        # three thousand wake-ups are at least a microsecond each
        assert many["wait_cpu_us"] >= 3000
        assert "assemble_cpu_us" in many


# ============================= SHOW TIMELINE / timeline detail
class TestTimelineDetail:
    def test_show_timeline_detail_has_the_new_fields(self, graph):
        c, g, ok = graph
        _burst(c, _mixed(4))
        r = ok("SHOW TIMELINE 64")
        detail = [row[5] for row in r.rows if row[3] == "tick"]
        assert detail
        for field in ("seat_us=", "handed=") + tuple(
                p + "=" for p in PARTS):
            assert all(field in d for d in detail), (field, detail[0])


# ================================================ kernel named scopes
class TestKernelScopes:
    def _index(self):
        from nebula_tpu.tpu import ell as E
        rng = np.random.default_rng(3)
        n, m = 400, 6000
        # a skewed in-degree so several buckets and hub rows exist
        ed = (rng.zipf(1.6, m) % n).astype(np.int32)
        es = rng.integers(0, n, m).astype(np.int32)
        ee = np.ones(m, np.int32)
        return E, E.EllIndex.build(es, ed, ee, n, cap=32, min_d=2)

    def test_hop_has_one_scope_per_bucket_width(self):
        import jax.numpy as jnp
        E, ix = self._index()
        widths = [int(nbr.shape[1]) for nbr in ix.bucket_nbr]
        assert len(widths) >= 2 and len(ix.extra_owner) > 0
        kern = E.make_continuous_hop_kernel(ix, (1,), donate=False)
        fp = jnp.zeros((ix.n_rows + 1, E.lanes_width(128)), jnp.uint8)
        eslot, hrows = ix.hub_merge()
        lowered = kern.lower(fp, fp, jnp.asarray(eslot),
                             jnp.asarray(hrows), *ix.kernel_args()[1:])
        assert lowered.compile() is not None
        text = lowered.as_text(debug_info=True)
        for d in widths:
            assert f"hop/bucket_w{d}" in text, d
        assert "hop/hub_merge" in text
        # the jitted function keeps its name: the benchmark's
        # hop_kernel_ms matches ^jit_hop$
        assert "jit_hop" in text

    @pytest.mark.parametrize("make,scope,name", [
        ("make_lane_join_kernel", "lane/join", "jit_join"),
        ("make_lane_extract_kernel", "lane/extract", "jit_extract"),
        ("make_lane_clear_kernel", "lane/clear", "jit_clear")])
    def test_lane_kernels_have_a_scope_each(self, make, scope, name):
        import jax.numpy as jnp
        E, ix = self._index()
        W = E.lanes_width(128)
        fp = jnp.zeros((ix.n_rows + 1, W), jnp.uint8)
        i32 = jnp.zeros(8, jnp.int32)
        u8 = jnp.zeros(8, jnp.uint8)
        if make == "make_lane_join_kernel":
            low = E.make_lane_join_kernel(ix, donate=False).lower(
                fp, fp, i32, i32, u8)
        elif make == "make_lane_extract_kernel":
            low = E.make_lane_extract_kernel(ix).lower(
                fp, fp, jnp.zeros((3, 8), jnp.int32))
        else:
            low = E.make_lane_clear_kernel(donate=False).lower(
                fp, fp, jnp.zeros(W, jnp.uint8))
        text = low.as_text(debug_info=True)
        assert scope in text and name in text
