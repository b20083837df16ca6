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

    def test_write_fresh_generation_reanchors(self, nba):
        """Read-your-writes across the stream: a write that publishes
        a new mirror generation must be visible to the next continuous
        query (the pump re-anchors instead of serving the stale
        resident tables)."""
        c, g, ok = nba
        before = sorted(map(tuple,
                            ok("GO 2 STEPS FROM 1 OVER e "
                               "YIELD e._dst").rows))
        ok("INSERT EDGE e(w) VALUES 1 -> 39:(1), 39 -> 38:(2)")
        deadline = time.monotonic() + 10.0
        after = None
        while time.monotonic() < deadline:
            after = sorted(map(tuple,
                               ok("GO 2 STEPS FROM 1 OVER e "
                                  "YIELD e._dst").rows))
            if (38,) in after:
                break
            time.sleep(0.1)
        assert after is not None and (38,) in after, (before, after)

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
            ok("GO 3 STEPS FROM 3 OVER e YIELD e._dst")
            s3 = dict(rt.stats)
            # two hops; the second frontier (a vertex's out-neighbours)
            # may or may not fit six rows, the first always does
            assert s3["hop_sparse"] - s2["hop_sparse"] >= 1
            assert (s3["hop_sparse"] + s3["hop_dense"]
                    - s2["hop_sparse"] - s2["hop_dense"]) == 2
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
