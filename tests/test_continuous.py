"""Continuous hop-boundary dispatch — the seat-map tier
(graph/batch_dispatch.py ContinuousGoScheduler + tpu/runtime.py
_ContinuousGoSession, docs/admission.md "Continuous dispatch").

Three layers:

  * _LaneLedger unit suite: join/leave/fragmentation/wraparound — no
    lane is ever double-seated, freed lanes hand out lowest-first.
  * The generative parity differential: the same seeded query mix
    (mixed hop counts, UPTO, LIMIT/COUNT pushdown riders, forced
    mid-flight joins) through ``go_dispatch_mode=windowed`` vs
    ``continuous`` must be bit-exact — the windowed pipeline is the
    oracle.
  * Serving semantics: mid-flight joins journal + count, deadline
    evictions free their lanes typed, the seat map drains to zero, and
    write-fresh generations re-anchor the stream (read-your-writes).
"""
import threading
import time

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.events import journal
from nebula_tpu.common.flags import flags
from nebula_tpu.common.stats import stats
from nebula_tpu.graph.batch_dispatch import _LaneLedger


# ===================================================== lane ledger
class TestLaneLedger:
    def test_alloc_lowest_first(self):
        led = _LaneLedger(16)
        assert [led.alloc() for _ in range(4)] == [0, 1, 2, 3]
        assert led.seated_count() == 4
        assert led.free_count() == 12

    def test_release_and_wraparound(self):
        led = _LaneLedger(4)
        lanes = [led.alloc() for _ in range(4)]
        assert lanes == [0, 1, 2, 3]
        with pytest.raises(RuntimeError):
            led.alloc()                     # exhausted
        for ln in lanes:
            led.release(ln)
        # full wraparound: every lane usable again, lowest-first
        assert [led.alloc() for _ in range(4)] == [0, 1, 2, 3]

    def test_fragmentation_fills_lowest_hole(self):
        led = _LaneLedger(8)
        lanes = [led.alloc() for _ in range(8)]
        led.release(2)
        led.release(5)
        led.release(3)
        # holes re-seat lowest-first so occupancy clusters into few
        # words (the leave-extract fetch is per WORD)
        assert led.alloc() == 2
        assert led.alloc() == 3
        assert led.alloc() == 5
        assert lanes == list(range(8))

    def test_no_double_seat_or_double_release(self):
        led = _LaneLedger(2)
        a = led.alloc()
        with pytest.raises(RuntimeError):
            led.release(a + 1)              # not seated
        led.release(a)
        with pytest.raises(RuntimeError):
            led.release(a)                  # double release
        seen = set()
        for _ in range(2):
            ln = led.alloc()
            assert ln not in seen
            seen.add(ln)

    def test_interleaved_churn_never_double_seats(self):
        rng = np.random.default_rng(11)
        led = _LaneLedger(16)
        seated = set()
        for _ in range(500):
            if seated and (led.free_count() == 0 or rng.random() < 0.5):
                ln = int(rng.choice(sorted(seated)))
                led.release(ln)
                seated.discard(ln)
            else:
                ln = led.alloc()
                assert ln not in seated
                seated.add(ln)
        assert led.seated_count() == len(seated)


# ===================================================== cluster fixture
def _boot_graph(seed=7, n=40, m=160):
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE s(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE s")
    ok("CREATE EDGE e(w int)")
    c.refresh_all()
    rng = np.random.default_rng(seed)
    src = rng.integers(1, n + 1, m)
    dst = rng.integers(1, n + 1, m)
    pairs = sorted({(int(a), int(b)) for a, b in zip(src, dst)
                    if a != b})
    vals = ", ".join(f"{a} -> {b}:({(a * 31 + b) % 97})"
                     for a, b in pairs)
    ok(f"INSERT EDGE e(w) VALUES {vals}")
    return c, g, ok


@pytest.fixture(scope="module")
def nba():
    flags.set("go_dispatch_mode", "continuous")
    c, g, ok = _boot_graph()
    yield c, g, ok
    c.stop()
    flags.set("go_dispatch_mode", "continuous")
    flags.set("tpu_sparse_go", True)


def _mix_queries(rng, n_queries=24, max_vid=40):
    """The seeded differential mix: mixed hop counts, multi-start
    roots, UPTO, WHERE, LIMIT/COUNT pushdown riders."""
    out = []
    for _ in range(n_queries):
        starts = ",".join(str(int(v)) for v in
                          rng.integers(1, max_vid + 1,
                                       int(rng.integers(1, 4))))
        steps = int(rng.integers(2, 5))
        kind = int(rng.integers(0, 5))
        if kind == 0:
            out.append(f"GO {steps} STEPS FROM {starts} OVER e "
                       f"YIELD e._dst")
        elif kind == 1:
            out.append(f"GO UPTO {steps} STEPS FROM {starts} OVER e "
                       f"YIELD e._dst")
        elif kind == 2:
            out.append(f"GO {steps} STEPS FROM {starts} OVER e "
                       f"YIELD e._dst | YIELD COUNT(*)")
        elif kind == 3:
            out.append(f"GO {steps} STEPS FROM {starts} OVER e "
                       f"YIELD e._dst | LIMIT {int(rng.integers(1, 6))}")
        else:
            out.append(f"GO {steps} STEPS FROM {starts} OVER e "
                       f"WHERE e.w > 40 YIELD e._dst, e.w")
    return out


class TestParityDifferential:
    def test_windowed_vs_continuous_bit_exact(self, nba):
        """The headline oracle: the same seeded mix through both
        dispatch modes is bit-exact.  Sparse kernels are disabled for
        the windowed leg so LIMIT riders take the dense route in both
        modes — a sparse in-kernel cut may pick a DIFFERENT (legal)
        subset, which is route semantics, not a dispatch-mode
        difference (docs/roofline.md)."""
        c, g, ok = nba
        queries = _mix_queries(np.random.default_rng(3))
        flags.set("tpu_sparse_go", False)
        try:
            flags.set("go_dispatch_mode", "continuous")
            cont = [sorted(map(tuple, ok(q).rows)) for q in queries]
            flags.set("go_dispatch_mode", "windowed")
            wind = [sorted(map(tuple, ok(q).rows)) for q in queries]
        finally:
            flags.set("go_dispatch_mode", "continuous")
            flags.set("tpu_sparse_go", True)
        for q, a, b in zip(queries, cont, wind):
            assert a == b, f"dispatch-mode divergence: {q}\n{a}\n{b}"

    def test_limit_rider_default_flags_membership(self, nba):
        """With default flags a windowed LIMIT may ride the sparse cut
        (route-dependent subset): assert the mode-invariant contract —
        row COUNT matches and every row is in the full result."""
        c, g, ok = nba
        full = set(map(tuple,
                       ok("GO 2 STEPS FROM 1,2,3 OVER e "
                          "YIELD e._dst").rows))
        r = ok("GO 2 STEPS FROM 1,2,3 OVER e YIELD e._dst | LIMIT 3")
        assert len(r.rows) == min(3, len(full))
        assert all(tuple(row) in full for row in r.rows)

    def test_concurrent_mix_parity_with_forced_joins(self, nba):
        """The mid-flight leg: a slow tick cadence forces the burst's
        arrivals to OR-merge into an already-running lane batch, and
        the results must still match the windowed oracle."""
        c, g, ok = nba
        queries = _mix_queries(np.random.default_rng(5), n_queries=12)
        flags.set("tpu_sparse_go", False)
        try:
            flags.set("go_dispatch_mode", "windowed")
            oracle = [sorted(map(tuple, ok(q).rows)) for q in queries]
            flags.set("go_dispatch_mode", "continuous")
            ok("GO 2 STEPS FROM 1 OVER e")      # streams exist
            d = c.tpu_runtime.dispatcher
            for st in d.continuous.streams():
                st.tick_delay_s = 0.02
            joins0 = stats.read_stats(
                "graph.continuous.joins.sum.60") or 0.0
            results = {}
            errors = []
            barrier = threading.Barrier(len(queries))

            def worker(i):
                try:
                    g2 = c.client()
                    g2.execute("USE s")
                    barrier.wait()
                    r = g2.execute(queries[i])
                    assert r.ok(), r.error_msg
                    results[i] = sorted(map(tuple, r.rows))
                except Exception as ex:     # noqa: BLE001
                    errors.append(ex)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(len(queries))]
            [t.start() for t in ts]
            [t.join() for t in ts]
            for st in d.continuous.streams():
                st.tick_delay_s = 0.0
        finally:
            flags.set("go_dispatch_mode", "continuous")
            flags.set("tpu_sparse_go", True)
        assert not errors, errors
        for i, q in enumerate(queries):
            assert results[i] == oracle[i], q
        joins1 = stats.read_stats("graph.continuous.joins.sum.60") or 0.0
        assert joins1 > joins0, "burst never rode the seat map"


class TestServingSemantics:
    def test_midflight_join_journaled_and_counted(self, nba):
        c, g, ok = nba
        ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored
        d = c.tpu_runtime.dispatcher
        st = next(iter(d.continuous.streams()))
        st.tick_delay_s = 0.05
        try:
            done = []

            def long_query():
                g2 = c.client()
                g2.execute("USE s")
                r = g2.execute("GO 4 STEPS FROM 1 OVER e YIELD e._dst")
                done.append(r)

            t = threading.Thread(target=long_query)
            t.start()
            time.sleep(0.08)        # the 4-hop rider is mid-flight
            r2 = ok("GO 2 STEPS FROM 2 OVER e YIELD e._dst")
            t.join()
        finally:
            st.tick_delay_s = 0.0
        assert done and done[0].ok(), done
        assert r2.ok()
        kinds = [e["kind"] for e in journal.dump(200)]
        assert "query.joined_midflight" in kinds
        ev = [e for e in journal.dump(200)
              if e["kind"] == "query.joined_midflight"][-1]
        assert "lane=" in ev["detail"]

    def test_profile_carries_continuous_marker(self, nba):
        c, g, ok = nba
        r = ok("PROFILE GO 3 STEPS FROM 1 OVER e YIELD e._dst")
        prof = r.raw.get("profile")
        assert prof

        def walk(n):
            yield n
            for ch in n.get("children", []):
                yield from walk(ch)

        spans = [s for root in prof["roots"] for s in walk(root)]
        cont = [s for s in spans if s["name"] == "graph.continuous"]
        assert cont, [s["name"] for s in spans]
        tags = cont[0]["tags"]
        assert tags.get("lane") is not None
        assert tags.get("hops") == 2

    def test_deadline_eviction_frees_lane_typed(self, nba):
        from nebula_tpu.common.status import ErrorCode
        c, g, ok = nba
        ok("GO 2 STEPS FROM 1 OVER e")
        d = c.tpu_runtime.dispatcher
        st = next(iter(d.continuous.streams()))
        st.tick_delay_s = 0.15
        try:
            t0 = time.perf_counter()
            r = g.execute("TIMEOUT 120 GO 4 STEPS FROM 1 OVER e "
                          "YIELD e._dst")
            wall = time.perf_counter() - t0
        finally:
            st.tick_delay_s = 0.0
        assert r.error_code == ErrorCode.E_DEADLINE_EXCEEDED, \
            r.error_msg
        assert wall < 3.0
        # the evicted rider's lane must drain — no seat leak
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            seated, queued = d.continuous.seat_counts()
            if seated == 0 and queued == 0:
                break
            time.sleep(0.05)
        assert (seated, queued) == (0, 0)

    def test_seat_map_drains_and_balances(self, nba):
        c, g, ok = nba
        for q in _mix_queries(np.random.default_rng(9), n_queries=8):
            ok(q)
        d = c.tpu_runtime.dispatcher
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            seated, queued = d.continuous.seat_counts()
            if seated == 0 and queued == 0:
                break
            time.sleep(0.05)
        assert (seated, queued) == (0, 0), "lane leak"
        joins = stats.read_stats("graph.continuous.joins.sum.600") or 0
        leaves = stats.read_stats("graph.continuous.leaves.sum.600") or 0
        evics = stats.read_stats(
            "graph.continuous.evictions.sum.600") or 0
        assert joins > 0
        assert joins == leaves + evics, (joins, leaves, evics)

    @pytest.mark.parametrize("holding", [False, True],
                             ids=["plain", "hold-in-progress"])
    def test_write_fresh_generation_reanchors(self, nba, monkeypatch,
                                              holding):
        """Read-your-writes across the stream: a write that publishes
        a new mirror generation must be visible to the next continuous
        query (the pump re-anchors instead of serving the stale
        resident tables).  With a hold in progress too (the pump
        holds its door 50 ms a tick while other statements ride): the
        generation check comes after the hold, so whoever the hold
        let in is checked like anybody else."""
        from nebula_tpu.common import flight
        from nebula_tpu.graph import batch_dispatch
        c, g, ok = nba
        before = sorted(map(tuple,
                            ok("GO 2 STEPS FROM 1 OVER e "
                               "YIELD e._dst").rows))
        stop = threading.Event()
        riders = []
        if holding:
            # CPU jax's hops take no time: say the device has 100 ms
            # of its hop left whenever one is in flight
            monkeypatch.setattr(batch_dispatch._HopInFlight, "slack",
                                lambda self, now, carried: 0.1)
            flight.recorder.clear_for_tests()

            def ride_on():
                g2 = c.client()
                g2.execute("USE s")
                while not stop.is_set():
                    g2.execute("GO 4 STEPS FROM 2 OVER e YIELD e._dst")

            riders = [threading.Thread(target=ride_on) for _ in range(2)]
            [t.start() for t in riders]
            time.sleep(0.2)
        try:
            # vertices of its own for each case: the graph has 1..40
            via, end = (139, 138) if holding else (39, 38)
            assert (end,) not in before
            ok(f"INSERT EDGE e(w) VALUES 1 -> {via}:(1), "
               f"{via} -> {end}:(2)")
            deadline = time.monotonic() + 10.0
            after = None
            while time.monotonic() < deadline:
                after = sorted(map(tuple,
                                   ok("GO 2 STEPS FROM 1 OVER e "
                                      "YIELD e._dst").rows))
                if (end,) in after:
                    break
                time.sleep(0.1)
        finally:
            stop.set()
            [t.join() for t in riders]
        assert after is not None and (end,) in after, (before, after)
        if holding:
            held = [r for r in flight.recorder.dump(limit=4096)
                    if r["kind"] == "tick" and r["hold_us"] > 0]
            assert held, "no tick held its door"

    def test_metrics_surface(self, nba):
        """graph.continuous.* and the idle-frac gauges render in the
        Prometheus exposition (the chaos lane-leak assertion's
        surface)."""
        c, g, ok = nba
        ok("GO 3 STEPS FROM 2 OVER e YIELD e._dst")
        text = stats.prometheus_text()
        assert "nebula_graph_continuous_joins_total" in text
        assert "nebula_graph_continuous_seated" in text
        assert "nebula_graph_continuous_lane_occupancy" in text
        assert "nebula_tpu_device_idle_frac" in text
        assert "nebula_graph_autoscale_recommended_replicas" in text

    def test_served_go_counts_the_branch_its_hops_took(self,
                                                       monkeypatch):
        """A lone 2-step and 3-step GO push (hop_sparse moves); a
        many-start GO whose frontier is over the budget pulls
        (hop_dense moves) — same answers either way."""
        from nebula_tpu.tpu import ell as E
        # the budget is read when the program is built: a cluster of
        # its own, so the nba stream's compiled program is not reused
        monkeypatch.setattr(E, "HOP_PUSH_ROWS", 6)
        flags.set("go_dispatch_mode", "continuous")
        c, g, ok = _boot_graph(seed=25)
        try:
            rt = c.tpu_runtime
            s0 = dict(rt.stats)
            r2 = ok("GO 2 STEPS FROM 3 OVER e YIELD e._dst")
            s1 = dict(rt.stats)
            assert s1["hop_sparse"] - s0["hop_sparse"] == 1
            assert s1["hop_dense"] == s0["hop_dense"]
            starts = ", ".join(str(v) for v in range(1, 31))
            many = ok(f"GO 2 STEPS FROM {starts} OVER e YIELD e._dst")
            s2 = dict(rt.stats)
            assert s2["hop_dense"] - s1["hop_dense"] == 1
            assert s2["hop_sparse"] == s1["hop_sparse"]
            took0 = stats.read_stats(
                "graph.continuous.seat_hops.sum.600") or 0
            ok("GO 3 STEPS FROM 3 OVER e YIELD e._dst")
            s3 = dict(rt.stats)
            took = (stats.read_stats(
                "graph.continuous.seat_hops.sum.600") or 0) - took0
            # two hops, of which the seat takes the first where the
            # start's out-neighbours fit the budget's six rows (the
            # session's is the program's); the start's own row always
            # fits, a vertex's out-neighbours may or may not
            assert took in (0, 1)
            assert s3["hop_sparse"] - s2["hop_sparse"] >= 1 - took
            assert (s3["hop_sparse"] + s3["hop_dense"]
                    - s2["hop_sparse"] - s2["hop_dense"]) == 2 - took
            # the windowed tier is the oracle for the rows
            flags.set("go_dispatch_mode", "windowed")
            w2 = ok("GO 2 STEPS FROM 3 OVER e YIELD e._dst")
            wm = ok(f"GO 2 STEPS FROM {starts} OVER e YIELD e._dst")
            assert sorted(map(tuple, r2.rows)) == \
                sorted(map(tuple, w2.rows))
            assert sorted(map(tuple, many.rows)) == \
                sorted(map(tuple, wm.rows))
            assert rt.stats["go_device"] > s0["go_device"]
        finally:
            flags.set("go_dispatch_mode", "continuous")
            c.stop()

    def test_extract_failure_wakes_leavers_typed(self, nba):
        """Review regression: leavers leave the seat map BEFORE the
        extract/clear ops run, so a device failure there must wake
        them explicitly (the pump-level recovery can no longer reach
        them) — a rider must get a typed error, never a hang, and the
        stream must recover for the next query."""
        c, g, ok = nba
        ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored
        d = c.tpu_runtime.dispatcher
        st = next(s for s in d.continuous.streams()
                  if s.session is not None)

        def boom(*a, **k):
            raise RuntimeError("simulated extract failure")

        st.session.extract = boom
        t0 = time.perf_counter()
        r = g.execute("GO 3 STEPS FROM 2 OVER e YIELD e._dst")
        wall = time.perf_counter() - t0
        assert wall < 10.0, "rider hung on a failed extract"
        assert not r.ok() and "simulated extract failure" in \
            (r.error_msg or "")
        # the pump dropped the broken session; the stream re-anchors
        # and serves again
        r2 = ok("GO 3 STEPS FROM 2 OVER e YIELD e._dst")
        assert r2.ok()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if d.continuous.seat_counts() == (0, 0):
                break
            time.sleep(0.05)
        assert d.continuous.seat_counts() == (0, 0)

    def test_idle_stream_releases_session(self, nba, monkeypatch):
        """Review regression: an idle stream must drop its resident
        device frontier pair after CONTINUOUS_IDLE_RELEASE_S instead
        of holding HBM forever; the next query re-anchors."""
        import nebula_tpu.graph.batch_dispatch as bd
        c, g, ok = nba
        monkeypatch.setattr(bd, "CONTINUOUS_IDLE_RELEASE_S", 0.3)
        ok("GO 2 STEPS FROM 1 OVER e")
        d = c.tpu_runtime.dispatcher
        st = next(s for s in d.continuous.streams()
                  if s.session is not None)
        deadline = time.monotonic() + 6.0
        while time.monotonic() < deadline and st.session is not None:
            time.sleep(0.1)
        assert st.session is None, "idle session never released"
        r = ok("GO 2 STEPS FROM 1 OVER e YIELD e._dst")
        assert r.ok()
        assert st.session is not None or r.rows is not None

    def test_saturated_seat_map_widens_to_next_rung(self, nba):
        """Review regression: a seat map saturated with a backlog
        drains and re-anchors one batch-width rung wider (the same
        pinned ladder the windowed kernels use) instead of pinning
        every stream at the smallest rung forever."""
        c, g, ok = nba
        saved = flags.get("go_batch_widths")
        flags.set("go_batch_widths", "8,16")
        d = c.tpu_runtime.dispatcher
        try:
            # force any session earlier tests anchored on the default
            # ladder to re-anchor against the shrunk one
            for s in d.continuous.streams():
                s._widen = True
            ok("GO 2 STEPS FROM 1 OVER e")      # anchors at rung 8
            deadline = time.monotonic() + 5.0
            st = None
            while time.monotonic() < deadline:
                st = next((s for s in d.continuous.streams()
                           if s.session is not None
                           and s.session.B == 8), None)
                if st is not None:
                    break
                ok("GO 2 STEPS FROM 1 OVER e")
                time.sleep(0.05)
            assert st is not None, "stream never anchored at rung 8"
            st.tick_delay_s = 0.02              # hold lanes busy
            results = {}
            errors = []

            def worker(i):
                try:
                    g2 = c.client()
                    g2.execute("USE s")
                    r = g2.execute(f"GO 3 STEPS FROM {i % 30 + 1} "
                                   f"OVER e YIELD e._dst")
                    assert r.ok(), r.error_msg
                    results[i] = True
                except Exception as ex:         # noqa: BLE001
                    errors.append(ex)

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(14)]
            [t.start() for t in ts]
            [t.join() for t in ts]
            st.tick_delay_s = 0.0
            assert not errors, errors
            assert len(results) == 14
            # saturation must have forced (or anchored) a wider rung
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                sess = st.session
                if sess is not None and sess.B == 16:
                    break
                time.sleep(0.05)
            sess = st.session
            assert sess is not None and sess.B == 16, \
                (sess.B if sess else None)
        finally:
            flags.set("go_batch_widths", saved)
            # drop the off-ladder session so later tests re-anchor on
            # the restored rung ladder
            d = c.tpu_runtime.dispatcher
            for s in d.continuous.streams():
                s._widen = True

    @pytest.mark.slow
    def test_bench_legs_smoke(self, tmp_path):
        """Slow-marked smoke of the two BENCH_SUITE_r10 legs at tiny
        durations: the continuous-vs-windowed fixed-offered-load leg
        (device_idle_frac recorded per mode, no lane leak) and the
        1-vs-2-graphd horizontal leg (ratios recorded; the >=1.6x
        throughput acceptance is core-count-dependent — the JSON
        carries host_cores and a platform note on small hosts)."""
        from nebula_tpu.tools.bench_suite import (bench_continuous,
                                                  bench_horizontal)
        results: list = []
        bench_continuous(results, persons=800, duration_s=10.0,
                         offered_qps=40.0, workers=4)
        assert len(results) == 2
        modes = {r["dispatch_mode"]: r for r in results}
        assert modes["continuous"]["requests"] > 0
        assert modes["continuous"]["continuous_joins"] > 0
        assert modes["windowed"]["continuous_joins"] == 0
        assert modes["continuous"]["device_idle_frac"] is not None
        hz: list = []
        bench_horizontal(hz, duration_s=20.0, workers=6,
                         n_vertices=120, run_dir=str(tmp_path))
        assert len(hz) == 2
        assert hz[0]["graphds"] == 1 and hz[1]["graphds"] == 2
        assert hz[1]["errors"] == 0 and hz[1]["requests"] > 0
        assert "throughput_ratio" in hz[1]

    def test_windowed_fallback_for_ineligible_space(self, nba):
        """A space with no edges cannot anchor a session: the rider
        bounces to the windowed pipeline typed (ContinuousUnavailable
        never surfaces) and still gets its (empty) answer."""
        c, g, ok = nba
        ok("CREATE SPACE empty_sp(partition_num=1, replica_factor=1)")
        c.refresh_all()
        ok("USE empty_sp")
        ok("CREATE EDGE e2(w int)")
        c.refresh_all()
        r = ok("GO 2 STEPS FROM 1 OVER e2 YIELD e2._dst")
        assert r.rows == [] or list(r.rows) == []
        ok("USE s")


# ===================================================== the hold
# (graph/batch_dispatch.py _ContinuousStream._hold): a stream driven
# over a session stub whose "device" runs one hop after the other,
# each for a fixed time, and whose count blocks until the hop it sits
# behind has ended — the pace of a cell in which the device, not the
# host, is what a tick waits for.  No jax, no cluster.
from nebula_tpu.graph import batch_dispatch as bd  # noqa: E402
from nebula_tpu.graph.query_registry import (  # noqa: E402
    KilledError, bind as bind_qid, registry as query_registry)

STUB_SPACE = 1
STUB_ET = (1,)


class _StubCount:
    """The per-lane count's resolver: waits for the hop it was
    enqueued behind (tpu/runtime.py _LaneCount)."""

    def __init__(self, sess, ends_at, lanes):
        self.sess, self.ends_at, self.lanes = sess, ends_at, lanes
        self.t_done = None

    def __call__(self):
        left = self.ends_at - time.perf_counter()
        if left > 0:
            time.sleep(left)
        self.sess.read_hop_info()
        self.t_done = bd.hostclock.stamp()
        return [7] * len(self.lanes)


class _StubSession:
    """_ContinuousGoSession's surface over a simulated device queue:
    a hop starts when it is enqueued or when the one before it ends,
    whichever is later, and takes ``hop_s`` (a pull) or ``push_s``
    where ``rt.pushes`` says so."""
    B = 16

    def __init__(self, rt):
        self.rt, self.m = rt, rt.mirrors[STUB_SPACE]
        self.free_at = 0.0
        self.fp = self
        self.join_marks = None
        self._info = []                 # (ready_at, pushed)
        self._ends = []                 # when the last two hops end
        self._read = [0, 0]
        self.hops = 0

    def block_until_ready(self):
        left = self.free_at - time.perf_counter()
        if left > 0:
            time.sleep(left)

    def join(self, joiners):
        """The seat takes the first hop of whoever the stream lets
        (``rt.seat_takes`` False: of nobody, the tree before PR 43)."""
        t = time.perf_counter()
        self.join_marks = (t, t)
        return [may and self.rt.seat_takes for _, _, may in joiners]

    def hop(self):
        pushed = self.rt.pushes(self.hops)
        self.rt.pushed.append(pushed)
        self.hops += 1
        # the device's queue pushes back: one hop waits behind the
        # one that runs, the enqueue of a third blocks
        if len(self._ends) >= 2:
            left = self._ends[-2] - time.perf_counter()
            if left > 0:
                time.sleep(left)
        start = max(time.perf_counter(), self.free_at)
        self.free_at = start + (self.rt.push_s if pushed
                                else self.rt.hop_s)
        self._info.append((self.free_at, pushed))
        self._ends = self._ends[-1:] + [self.free_at]

    def read_hop_info(self):
        now = time.perf_counter()
        while self._info and self._info[0][0] <= now:
            self._read[0] += 1
            self._read[1] += int(self._info.pop(0)[1])

    def hop_reads(self):
        self.read_hop_info()
        reads, sparse = self._read
        self._read = [0, 0]
        return reads, sparse, 0, reads, 0

    def count(self, lanes):
        return _StubCount(self, self.free_at, list(lanes))

    def clear(self, lanes):
        pass


class _StubMirror:
    def __init__(self, generation):
        self.generation = generation


class _StubRuntime:
    def __init__(self, hop_s, push_s=0.0, pushes=lambda i: False,
                 seat_takes=False):
        self.hop_s, self.push_s, self.pushes = hop_s, push_s, pushes
        self.seat_takes = seat_takes
        self.mirrors = {STUB_SPACE: _StubMirror(1)}
        self.sessions = []
        self.pushed = []                # hop by hop, what it did

    def mirror(self, space_id):
        return self.mirrors[space_id]

    def continuous_session(self, space_id, et_tuple, min_lanes=1):
        self.sessions.append(_StubSession(self))
        return self.sessions[-1]

    def rider_assembles(self, m, payload, et_tuple):
        return True

    def count_distinct_results(self, counts, hops):
        return [(["__count__"], [[int(c)]]) for c in counts]


class _StubPayload:
    start_vids = (1,)


class _StubStream:
    """One stream of a real dispatcher over the stub, and the callers
    that ride it: each statement a k-hop neighbourhood count (the one
    reduction whose leaver needs no frontier).  ``ride`` returns the
    tags of the statement's graph.continuous marker, which its own
    thread annotates (joined_tick, left_tick, the waits)."""

    def __init__(self, monkeypatch, hop_s, **kw):
        self.rt = _StubRuntime(hop_s, **kw)
        self.disp = bd.GoBatchDispatcher(self.rt)
        self.st = self.disp.continuous._stream(STUB_SPACE, STUB_ET)
        self.threads = []
        self.errors = []
        self._tags = threading.local()
        real = bd.tracing.annotate

        def note(name, **tags):
            if name == "graph.continuous":
                self._tags.last = tags
            return real(name, **tags)

        monkeypatch.setattr(bd.tracing, "annotate", note)

    def ride(self, hops, qid=None, traced=False):
        key = ("go_batch_execute", STUB_SPACE, STUB_ET, hops, False,
               ("count_distinct",))
        self._tags.last = None
        with bind_qid(qid), bd.tracing.start_trace("graph.query",
                                                   forced=traced):
            try:
                self.disp.continuous.submit(key, _StubPayload())
            finally:
                tags = self._tags.last
        return tags

    def start(self, fn, *args):
        def run():
            try:
                fn(*args)
            except Exception as ex:     # noqa: BLE001 — reported
                self.errors.append(ex)
        t = threading.Thread(target=run, daemon=True)
        self.threads.append(t)
        t.start()
        return t

    def caller(self, hops, n, think_s, out, traced=False):
        """A closed-loop caller: ``n`` statements, ``think_s`` between
        an answer and the next statement."""
        def loop():
            for _ in range(n):
                out.append(self.ride(hops, traced=traced))
                time.sleep(think_s)
        return self.start(loop)

    def outlasts(self, hops):
        """A statement the test does not wait for: the stream's stop
        ends it."""
        def loop():
            try:
                self.ride(hops)
            except RuntimeError as ex:
                assert "stopped" in str(ex), ex
        return self.start(loop)

    def seated_long(self):
        """A rider that outlasts the test: somebody stays seated, and
        the hop in flight carries on into the next."""
        self.outlasts(10_000)
        self.until(lambda: self.st.seated)

    def until(self, cond, timeout_s=10.0):
        end = time.monotonic() + timeout_s
        while not cond():
            assert time.monotonic() < end, "stub stream stood still"
            time.sleep(0.001)

    def ticks(self):
        """The stream's tick records, oldest first."""
        from nebula_tpu.common import flight
        return [r for r in reversed(flight.recorder.dump(limit=4096))
                if r["kind"] == "tick" and r["stream"] == STUB_SPACE]

    def close(self):
        self.disp.continuous.shutdown(timeout_s=5.0)
        for t in self.threads:
            t.join(5.0)


@pytest.fixture
def stub(monkeypatch):
    from nebula_tpu.common import flight
    flight.recorder.clear_for_tests()
    made = []

    def make(hop_s, **kw):
        made.append(_StubStream(monkeypatch, hop_s, **kw))
        return made[-1]

    yield make
    for s in made:
        s.close()
        assert not s.errors, s.errors


def _paced(s, hop_s):
    """Ride short statements until the stream has timed a pull from an
    observed start to the blocked fetch behind it: the estimate the
    hold is read off."""
    s.seated_long()
    # four statements that leave at four ticks in a row: the fetch of
    # each cohort blocks until its hop has ended
    warm = [s.start(s.ride, hops) for hops in (1, 2, 3, 4)]
    for t in warm:
        t.join(30.0)
    assert not s.errors, s.errors
    est = s.st._flight.hop_s[False]
    assert 0.8 * hop_s <= est <= 1.5 * hop_s, est


class TestSeatHop:
    """The stream counts what the session did (PR 43): a rider whose
    seat took its first hop (the session scattered its first frontier,
    _ContinuousGoSession.join) rides one tick fewer; its ``hops`` stay
    its statement's."""

    @pytest.mark.parametrize("hops", [1, 2, 3, 6])
    @pytest.mark.parametrize("seat_takes", [False, True],
                             ids=["rides-all", "seat-takes-first"])
    def test_a_rider_rides_its_hops_less_the_seats(self, stub,
                                                   seat_takes, hops):
        s = stub(0.0, seat_takes=seat_takes)
        progress = []
        real = query_registry.note_hop
        s.st.sched.runtime.count_distinct_results = \
            lambda counts, hs: [(["n"], [[h]]) for h in hs]
        # the registry is the process's: a stream another test left
        # pumping in this worker reports its riders' hops through the
        # same door, so the rider has a qid of its own and only its
        # calls are counted
        mine = query_registry.register(f"GO {hops} STEPS")
        query_registry.note_hop = lambda qid, hop: (
            progress.append(hop) if qid == mine else None) \
            or real(qid, hop)
        try:
            m = s.ride(hops, qid=mine)
        finally:
            query_registry.note_hop = real
            query_registry.unregister(mine)
        # a rider of one hop keeps none on the lanes after the seat's:
        # it rides as ever
        took = int(seat_takes and hops >= 2)
        assert m["hops"] == hops and m["seat_hops"] == took
        assert m["left_tick"] - m["joined_tick"] == hops - took
        # the registry hears of the hops done, the seat's among them
        assert progress == list(range(1 + took, hops + 1))
        ticks = s.ticks()
        assert sum(t["seat_hops"] for t in ticks) == took
        assert sum(t["joins"] for t in ticks) == 1
        assert [t["seat_hops"] for t in ticks if not t["joins"]] \
            == [0] * (len(ticks) - 1)

    def test_admission_counts_the_ticks_a_rider_may_ride(self, stub):
        """The feasibility estimate is a LOWER bound: a rider of three
        hops whose seat may take the first rides two ticks."""
        from nebula_tpu.common import deadline as deadlines
        s = stub(0.0, seat_takes=True)
        s.ride(1)                       # the stream is anchored
        with s.st.cond:
            s.st.hop_ema_s = 1.0
        try:
            for hops, budget_s, admitted in ((3, 2.5, True),
                                             (3, 1.5, False),
                                             (1, 1.5, True)):
                with deadlines.bind(deadlines.Deadline.after_s(budget_s)):
                    with s.st.cond:
                        s.st.hop_ema_s = 1.0
                    try:
                        s.ride(hops)
                        got = True
                    except bd.DeadlineExceeded:
                        got = False
                assert got == admitted, (hops, budget_s)
        finally:
            with s.st.cond:
                s.st.hop_ema_s = 0.0


class TestHold:
    HOP_S = 0.08

    @pytest.mark.parametrize("hold", [True, False],
                             ids=["hold", "no-hold"])
    def test_a_caller_back_in_time_rides_the_next_hop(
            self, stub, monkeypatch, hold):
        """(a) a device-paced stream (a hop takes 80 ms, the count
        behind it blocks that long): two callers, each back 10 ms
        after its answer.  The hop enqueued before the answer existed
        is lost to the caller either way; with the hold it rides the
        one after (seated in the tick after the one that handed it its
        answer: joined_tick = left_tick + 1, its first hop is
        left_tick + 2), and without, the door of that tick shuts
        before it is back and it sits out a whole hop more."""
        if not hold:
            monkeypatch.setattr(bd, "HOLD_FLOOR_S", float("inf"))
        s = stub(self.HOP_S)
        if hold:
            _paced(s, self.HOP_S)
        else:
            s.seated_long()
        a, b = [], []
        ta = s.caller(1, 7, 0.010, a)
        # the second caller a tick behind the first
        s.until(lambda: a or s.st.tick_no >= 2)
        tb = s.caller(1, 7, 0.010, b)
        ta.join(30.0)
        tb.join(30.0)
        assert len(a) == len(b) == 7 and not s.errors, s.errors
        gaps = [nxt["joined_tick"] - prev["left_tick"]
                for ms in (a, b) for prev, nxt in zip(ms[2:], ms[3:])]
        ticks = s.ticks()
        if hold:
            assert gaps and all(g == 1 for g in gaps), (gaps, a, b)
            held = [t for t in ticks if t["hold_us"] > 0]
            assert len(held) >= 8
            # never past the hop in flight: the device did not run dry
            assert all(t["hold_us"] < self.HOP_S * 1e6 * 0.75
                       for t in held), held
            assert sum(t["hold_joins"] for t in ticks) >= len(gaps)
        else:
            assert gaps and all(g >= 2 for g in gaps), (gaps, a, b)
            assert all(t["hold_us"] == 0 and t["hold_joins"] == 0
                       for t in ticks)

    @pytest.mark.parametrize("how", ["stop", "generation", "kill",
                                     "lanes-full"])
    def test_what_ends_a_hold_ends_it_at_once(self, stub, how):
        """(c) a hold of ~200 ms in progress (a hop of 400 ms): the
        stream's stop, a mirror generation the runtime published, a
        KILL QUERY of a seated rider and a taker for every free lane
        each end it well before its time; the generation check that
        follows seats nobody on the old session, the killed rider
        leaves at this tick's boundary."""
        hop_s = 0.4
        s = stub(hop_s)
        st = s.st
        if how == "kill":
            qid = query_registry.register("GO 10000 STEPS")
            ended = []

            def victim():
                try:
                    s.ride(10_000, qid=qid)
                except KilledError as ex:
                    ended.append(ex)
            s.start(victim)
            s.until(lambda: st.seated)
        _paced(s, hop_s)
        # a statement whose answer starts a tick: that tick holds
        s.ride(1)
        t_handed = time.perf_counter()
        s.until(lambda: st._flight.began > 0)
        time.sleep(0.03)                # the hold is in progress
        n_ticks = len(s.ticks())
        if how == "stop":
            s.disp.continuous.shutdown(timeout_s=5.0)
        elif how == "generation":
            s.rt.mirrors[STUB_SPACE] = _StubMirror(2)
            s.outlasts(1)               # the writer's next read
        elif how == "kill":
            assert query_registry.kill(qid)
        else:
            free = st.ledger.free_count()
            for _ in range(free):
                s.start(s.ride, 1)
        s.until(lambda: len(s.ticks()) > n_ticks or st.stopping)
        if how == "stop":
            assert time.perf_counter() - t_handed < 0.15
            return
        rec = s.ticks()[n_ticks]
        # it held, and gave up long before half of the 400 ms
        assert 20_000 <= rec["hold_us"] <= 120_000, rec
        if how == "generation":
            s.until(lambda: st.draining)
            assert rec["joins"] == 0
        elif how == "kill":
            assert rec["evictions"] == 1
            s.until(lambda: ended)
            query_registry.unregister(qid)
        else:
            assert rec["joins"] == rec["hold_joins"] == free

    def test_a_pull_predicted_where_the_device_pushes_costs_one_hold(
            self, stub):
        """(e) the branch is the device's choice: after pulls of 80 ms
        the hops push, 2 ms each.  The first tick after a pull still
        expects a pull and holds; the report of the push reaches the
        next tick and the estimate follows: no hold while the hops
        push (a push never passes the floor), and the pull's estimate
        is kept for when the pulls are back."""
        flips = []
        s = stub(self.HOP_S, push_s=0.002,
                 pushes=lambda i: bool(flips) and i >= flips[0])
        _paced(s, self.HOP_S)
        out = []
        flips.append(s.rt.sessions[-1].hops + 1)
        n0 = len(s.ticks())
        s.caller(1, 12, 0.003, out).join(30.0)
        assert len(out) == 12
        ticks = s.ticks()[n0:]
        pushed = [i for i, t in enumerate(ticks) if t["hop_sparse"]]
        assert pushed, ticks
        # from the tick after the first push's report on, no hold
        late = ticks[pushed[0] + 1:]
        assert len(late) >= 6
        assert all(t["hold_us"] == 0 for t in late), late
        # the mistake: a hold while the hop in flight (the one before
        # the hop this tick enqueued, record ``tick``) pushed
        wrong = [t for t in ticks if t["hold_us"] > 0
                 and s.rt.pushed[t["tick"] - 2]]
        assert len(wrong) == 1, wrong
        fl = s.st._flight
        assert fl.pushed is True
        assert fl.hop_s[True] < 0.02
        # pushes moved the pulls' estimate by nothing
        assert 0.8 * self.HOP_S <= fl.hop_s[False] <= 1.5 * self.HOP_S


class TestHopInFlight:
    """The estimate the hold is read off, by hand (no thread, no
    clock): times in seconds on an invented perf_counter."""

    def _paced(self, fl, t=100.0, hop=0.05):
        """Three hops back to back, each enqueued 5 ms after the fetch
        before it came back, each fetch blocked until its hop ended."""
        fl.enqueued(t, t + 0.002)        # device idle
        fl.read(0, 0)
        end = t + 0.002 + hop
        for _ in range(3):
            fl.enqueued(end - hop + 0.004, end - hop + 0.006)
            fl.read(1, 0)                           # the one that ended pulled
            fl.fetched(end - 0.04, end, True, True)
            end += hop
        return end - hop                            # the last fetch's end

    def test_a_pull_timed_from_its_observed_start(self):
        fl = bd._HopInFlight()
        t_w = self._paced(fl)
        assert fl.pushed is False and fl.seen
        assert fl.began == t_w
        assert abs(fl.hop_s[False] - 0.05) < 1e-6
        assert fl.hop_s[True] == 0.0
        assert 0.0015 < fl.turn_s < 0.0025
        # 4 ms into the hop in flight: 50 - 4 - 2 of turn-around
        assert abs(fl.slack(t_w + 0.004, True) - 0.044) < 1e-3
        # first hops alone are taken for a push: nothing known of one
        assert fl.slack(t_w + 0.004, False) == 0.0

    @pytest.mark.parametrize("why", ["assumed-start", "not-blocked",
                                     "branch-unread", "branches-mixed"])
    def test_what_gives_no_sample(self, why):
        fl = bd._HopInFlight()
        t_w = self._paced(fl)
        before = dict(fl.hop_s)
        if why == "assumed-start":
            # a tick without a cohort: the next hop is enqueued behind
            # one nobody waited for, and began later than its enqueue
            fl.enqueued(t_w + 0.004, t_w + 0.006)
            fl.read(0, 0)
            assert not fl.seen
            fl.enqueued(t_w + 0.007, t_w + 0.009)
            fl.read(1, 0)
            fl.fetched(t_w + 0.01, t_w + 0.1, True, True)
            assert fl.seen and fl.began == t_w + 0.1    # synced again
        elif why == "not-blocked":
            fl.enqueued(t_w + 0.06, t_w + 0.062)
            fl.read(1, 0)
            fl.fetched(t_w + 0.0621, t_w + 0.0622, True, True)
            assert not fl.seen and fl.began == t_w + 0.062
        elif why == "branch-unread":
            fl.enqueued(t_w + 0.004, t_w + 0.006)
            fl.read(0, 0)
            fl.fetched(t_w + 0.01, t_w + 0.05, True, False)
        else:
            fl.enqueued(t_w + 0.004, t_w + 0.006)
            fl.read(2, 1)
            fl.fetched(t_w + 0.01, t_w + 0.05, True, True)
            assert fl.pushed is None
            assert fl.slack(t_w + 0.051, True) == 0.0
        assert fl.hop_s == before

    def test_a_stalled_fetch_counts_for_twice_the_estimate_at_most(self):
        """The device (or the runtime) stands for 1.4 s behind a pull
        of 50 ms: the estimate moves as for a sample of 100 ms, and
        the next hold is still shorter than the hop."""
        fl = bd._HopInFlight()
        t_w = self._paced(fl)
        fl.enqueued(t_w + 0.004, t_w + 0.006)
        fl.read(1, 0)
        fl.fetched(t_w + 0.01, t_w + 1.4, True, True)
        assert abs(fl.hop_s[False] - (0.7 * 0.05 + 0.3 * 0.1)) < 1e-6
        assert bd.HOLD_SHARE * fl.slack(t_w + 1.404, True) < 0.05

    def test_a_hold_that_outlasted_its_hop_brings_the_estimate_down(self):
        """An estimate far over the hop (however it came about): the
        pump holds past the hop's end, so the fetch behind it does not
        block and no sample comes; that the hop had ended by then is
        itself a reading, and the estimate comes down to it, tick by
        tick, until a fetch blocks again."""
        fl = bd._HopInFlight()
        t = self._paced(fl)                 # hop k began at t (seen)
        fl.hop_s[False] = 0.5               # ten times the hop
        holds = []
        for _ in range(6):
            hold = bd.HOLD_SHARE * max(fl.slack(t + 0.002, True), 0.0)
            holds.append(hold)
            e = t + 0.002 + hold + 0.002    # hop k+1 enqueued here
            fl.enqueued(e - 0.002, e)
            fl.read(1, 0)
            end = fl.before[0] + 0.05       # when hop k really ended
            if end <= e + 0.0005:           # before the pump asked
                fl.fetched(e + 0.0005, e + 0.0006, True, True)
                t = e                       # k+1 starts on an idle device
            else:
                fl.fetched(e + 0.0005, end, True, True)
                t = end
        assert holds[0] > 0.2               # the first outlasts its hop
        assert holds[-1] < 0.05, holds      # and the last no longer
        assert 0.04 < fl.hop_s[False] < 0.08, fl.hop_s

    def test_a_pump_that_came_late_saw_its_own_lateness(self):
        """A traced window while the profiler writes its trace: the
        join and the enqueue take 100 ms of waiting for the
        interpreter, the fetch another 15, and the time since the hop
        began is the pump's period (260 ms), not the hop (50).  No
        sample (the wait is a sliver of it), the hop behind it starts
        where it was enqueued, the turn-around says how slow the pump
        is, and there is nothing to hold for."""
        fl = bd._HopInFlight()
        t = self._paced(fl)
        for _ in range(8):
            e = t + 0.030 + 0.100           # door + join + enqueue
            fl.enqueued(t + 0.030, e)
            fl.read(1, 0)
            fl.fetched(e + 0.001, e + 0.016, True, True)
            assert not fl.seen and fl.began == e
            t = e + 0.016
        assert abs(fl.hop_s[False] - 0.05) < 1e-6
        assert fl.turn_s > 0.08
        assert fl.slack(t + 0.002, True) < 0.0

    def test_a_flush_or_an_idle_stream_has_no_hop_in_flight(self):
        fl = bd._HopInFlight()
        t_w = self._paced(fl)
        fl.fetched(t_w + 0.01, t_w + 0.05, False, True)  # its cohort, no new hop
        assert fl.began == 0.0 and fl.slack(t_w + 0.051, True) == 0.0
        assert abs(fl.hop_s[False] - 0.05) < 1e-6   # and it was a sample
        self._paced(fl, t=200.0)
        fl.idle()
        assert fl.began == 0.0 and not fl.seen
