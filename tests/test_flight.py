"""nebulaprof — the device flight recorder (docs/observability.md
"The device timeline"):

  * Recorder ring units: wrap at `flight_recorder_size`, newest-first
    dump vs oldest-first export, ring-wrap under concurrent scrape
    (the webservice is threaded; the recorder is process-global),
    deterministic aging through clock.advance_for_tests.
  * Drift fold semantics: a live measurement past its declared bound
    fires the typed tpu.model_drift event ONCE on the transition,
    staying over does not re-fire, returning in-bound re-arms; the
    scrape-time collector publishes the overshoot fraction and
    self-clears to zero (fire-and-clear).
  * chrome_trace is a pure function — the byte-stable golden
    (tests/golden_timeline.json) pins the Perfetto/Chrome-trace
    schema; scripts/ci.sh ships the golden beside the SARIF artifacts.
  * /timeline webservice endpoint (every daemon), plain + ?format=trace.
  * e2e: PROFILE FORMAT=trace returns openable Chrome-trace JSON with
    host spans above device tick rows, SHOW TIMELINE fans out like
    SHOW QUERIES, and a slow continuous rider's slow-log entry anchors
    its [first, last] recorder tick-id window.
"""
import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import clock, flight
from nebula_tpu.common.events import journal
from nebula_tpu.common.flags import flags
from nebula_tpu.common.stats import PROC_TOKEN, stats
from nebula_tpu.common.tracing import slow_log
from nebula_tpu.webservice import WebService

GOLDEN = Path(__file__).parent / "golden_timeline.json"


# ==================================================== ICI byte model
class TestIciByteModel:
    def test_factors_match_the_static_model(self):
        # docs/static_analysis.md, re-stated for the live path — the
        # same factors meshaudit proves the declared bounds against
        assert flight.ici_exchange_bytes("psum", 1024, 8) == \
            2 * 7 * 1024 // 8
        assert flight.ici_exchange_bytes("all_gather", 1024, 8) == \
            7 * 1024
        for op in ("all_to_all", "reduce_scatter", "psum_scatter",
                   "sharding_constraint"):
            assert flight.ici_exchange_bytes(op, 1024, 8) == \
                7 * 1024 // 8, op
        assert flight.ici_exchange_bytes("ppermute", 1024, 8) == 1024

    def test_single_device_moves_nothing(self):
        for op in ("psum", "all_gather", "all_to_all", "ppermute"):
            assert flight.ici_exchange_bytes(op, 1 << 20, 1) == 0

    def test_collective_rows_shape(self):
        rows = flight.collective_rows(
            [("sharding_constraint", 800), ("psum", 32)], 8)
        assert rows == [{"op": "sharding_constraint", "bytes": 700},
                        {"op": "psum", "bytes": 56}]


# ======================================================= ring units
class TestRecorderRing:
    def test_ring_wraps_at_capacity(self):
        saved = flags.get("flight_recorder_size")
        flags.set("flight_recorder_size", 8)
        r = flight.FlightRecorder()
        try:
            for i in range(20):
                r.note_tick(0, tick=i)
            dump = r.dump(limit=64)
            assert len(dump) == 8
            # newest first, ids monotonic from the 20th note down
            assert [e["id"] for e in dump] == list(range(20, 12, -1))
            assert dump[0]["tick"] == 19
            # export is the oldest-first mirror (trace stitch order)
            exp = r.export()
            assert [e["id"] for e in exp] == list(range(13, 21))
        finally:
            flags.set("flight_recorder_size", saved)

    def test_note_beat_is_one_record_like_the_others(self):
        r = flight.FlightRecorder()
        a = r.note_tick(0, tick=1)
        b = r.note_beat(clock="thread_time", n=100, py_late_sum_us=7000,
                        py_late_max_us=250)
        assert b == a + 1
        newest = r.dump()[0]
        assert newest["kind"] == "beat" and newest["id"] == b
        assert newest["clock"] == "thread_time" and newest["n"] == 100
        assert newest["time_us"] > 0
        # no native beat: its fields are left off, not written as 0
        assert not any(k.startswith("nat_") for k in newest)
        assert [e["kind"] for e in r.export()] == ["tick", "beat"]

    def test_export_clamped_by_flag(self):
        saved = flags.get("timeline_export_max_ticks")
        flags.set("timeline_export_max_ticks", 4)
        r = flight.FlightRecorder()
        try:
            for i in range(10):
                r.note_dispatch("k", rung=i)
            assert len(r.export()) == 4
            assert len(r.export(limit=2)) == 2      # tighter wins
            assert len(r.export(limit=99)) == 4     # flag caps
        finally:
            flags.set("timeline_export_max_ticks", saved)

    def test_ring_wrap_under_concurrent_scrape(self):
        """Writers wrapping the ring while scrapes run: every scrape's
        tpu.flight.records gauge and every dump snapshot must be
        internally consistent (the webservice is threaded; the
        recorder — like stats — is process-global)."""
        saved = flags.get("flight_recorder_size")
        flags.set("flight_recorder_size", 16)
        rec = flight.recorder
        rec.clear_for_tests()
        stop = threading.Event()
        errors = []

        def writer():
            i = 0
            while not stop.is_set():
                rec.note_tick(i % 3, tick=i)
                rec.note_sharded_dispatch(
                    "unit_wrap_kernel", 8,
                    [("sharding_constraint", 1 << 12)], 1 << 13)
                i += 1

        def scraper():
            try:
                for _ in range(50):
                    rows = {name: v for name, labels, v
                            in stats.gauges() if not labels}
                    n = rows.get("tpu.flight.records")
                    assert n is not None and 0 <= n <= 16, rows
                    dump = rec.dump(limit=32)
                    assert len(dump) <= 16
                    ids = [e["id"] for e in dump]
                    assert ids == sorted(ids, reverse=True), ids
            except Exception as e:    # noqa: BLE001 — surfaced below
                errors.append(e)

        ws = [threading.Thread(target=writer) for _ in range(2)]
        ss = [threading.Thread(target=scraper) for _ in range(3)]
        try:
            for t in ws + ss:
                t.start()
            for t in ss:
                t.join()
        finally:
            stop.set()
            for t in ws:
                t.join()
            flags.set("flight_recorder_size", saved)
            rec.clear_for_tests()
        assert not errors, errors

    def test_clock_advance_ages_records_deterministically(self):
        r = flight.FlightRecorder()
        try:
            a = r.note_tick(0)
            clock.advance_for_tests(2.5)
            b = r.note_timing("ell_go", 10.0, 4096, 0.4)
            recs = {e["id"]: e for e in r.dump()}
            aged = recs[b]["time_us"] - recs[a]["time_us"]
            assert aged >= 2_500_000, aged
            assert aged < 2_600_000, "wall time dwarfed the fake skew?"
        finally:
            clock.reset_for_tests()


# ======================================================= drift folds
class TestDriftFold:
    def _drift_events(self, key):
        return [e for e in journal.dump(limit=500)
                if e["kind"] == "tpu.model_drift" and e.get("key") == key]

    def _gauge(self, axis, key):
        for name, labels, v in stats.gauges():
            if name == f"tpu.model_drift.{axis}" \
                    and labels == (("key", key),):
                return v
        return None

    def test_fires_on_transition_once_then_rearms(self):
        rec = flight.recorder
        rec.clear_for_tests()
        key = "unit_drift_kernel"
        try:
            # in-bound: no cell event, gauge publishes 0.0
            assert rec.fold("ici", key, 80.0, 100.0) is False
            assert not self._drift_events(key)
            assert self._gauge("ici", key) == 0.0
            # the over-bound TRANSITION fires the typed event
            assert rec.fold("ici", key, 160.0, 100.0) is True
            evs = self._drift_events(key)
            assert len(evs) == 1
            assert evs[0]["axis"] == "ici"
            assert evs[0]["live"] == 160.0 and evs[0]["declared"] == 100.0
            # overshoot fraction on the gauge family
            assert self._gauge("ici", key) == pytest.approx(0.6)
            # STAYING over does not re-fire
            assert rec.fold("ici", key, 170.0, 100.0) is False
            assert len(self._drift_events(key)) == 1
            # returning in-bound re-arms and the gauge self-clears —
            # fire-and-clear (the gauge table is re-set every scrape)
            assert rec.fold("ici", key, 50.0, 100.0) is False
            assert self._gauge("ici", key) == 0.0
            assert rec.fold("ici", key, 120.0, 100.0) is True
            assert len(self._drift_events(key)) == 2
        finally:
            rec.clear_for_tests()

    def test_zero_declared_never_fires(self):
        # a kernel with no declared bound can't drift (div-zero guard)
        rec = flight.FlightRecorder()
        assert rec.fold("ici", "unbounded", 1e9, 0.0) is False
        assert rec.drift_cells()["ici/unbounded"]["over"] is False

    def test_sharded_dispatch_records_rows_and_folds(self):
        rec = flight.FlightRecorder()
        rec.note_sharded_dispatch(
            "unit_sharded", 8, [("sharding_constraint", 1 << 13)],
            1 << 13, rung=512)
        (e,) = rec.dump()
        assert e["kernel"] == "unit_sharded" and e["k"] == 8
        assert e["ici"] == [{"op": "sharding_constraint",
                             "bytes": 7 * (1 << 13) // 8}]
        assert e["ici_bytes"] == 7 * (1 << 13) // 8
        assert e["ici_declared"] == 1 << 13
        cell = rec.drift_cells()["ici/unit_sharded"]
        assert cell["over"] is False       # (k-1)/k of the bound


# ============================================== chrome_trace + golden
def _golden_inputs():
    """Fixed inputs for the byte-stable pin: one host span tree with a
    seat marker (with the five waits), one tick with all ten pump
    phases (assemble_us is the sum of its five parts), one sharded
    dispatch, one timing probe, one second-stream tick."""
    tree = {
        "trace_id": "00000000deadbeef",
        "roots": [{
            "name": "graph.query", "start_us": 1000, "duration_us": 900,
            "tags": {"stmt_kind": "GoSentence"},
            "children": [
                {"name": "graph.parse", "start_us": 1010,
                 "duration_us": 40, "tags": {}, "children": []},
                {"name": "graph.executor", "start_us": 1060,
                 "duration_us": 700,
                 "tags": {"executor": "GoExecutor"}, "children": []},
            ],
        }],
    }
    seat = {"lane": 3, "joined_tick": 17, "left_tick": 19, "hops": 2,
            "ending": "left-batch", "timeline": [41, 44],
            "seat_wait_us": 120, "ride_us": 410, "result_wait_us": 90,
            "wake_us": 30, "assemble_us": 45}
    ticks = [
        {"kind": "tick", "stream": 0, "id": 41, "time_us": 1400,
         "dur_us": 360, "seat_us": 15, "join_us": 20, "hop_us": 180,
         "extract_us": 30, "clear_us": 10, "fetch_wait_us": 60,
         "d2h_us": 5, "unpack_us": 12, "rows_us": 18, "handover_us": 5,
         "assemble_us": 100, "handed": 1, "seats": 2, "joins": 1,
         "leaves": 1, "evictions": 0, "generation": 5},
        {"kind": "dispatch", "kernel": "ell_go_sharded", "id": 42,
         "time_us": 1500, "k": 8, "rung": 1024, "steps": 3,
         "ici_bytes": 917504, "ici_declared": 1048576,
         "ici": [{"op": "sharding_constraint", "bytes": 917504}]},
        {"kind": "timing", "op": "ell_go", "id": 43, "time_us": 1700,
         "wall_us": 120.0, "bytes": 4096, "gbps": 0.034},
        {"kind": "tick", "stream": 1, "id": 44, "time_us": 1900,
         "dur_us": 150, "seat_us": 4, "join_us": 0, "hop_us": 120,
         "extract_us": 20, "clear_us": 0, "fetch_wait_us": 0,
         "d2h_us": 0, "unpack_us": 0, "rows_us": 0, "handover_us": 0,
         "assemble_us": 0, "handed": 0, "seats": 1},
    ]
    return tree, ticks, seat


class TestChromeTrace:
    def test_golden_is_byte_stable(self):
        """chrome_trace is a PURE function — same inputs, byte-identical
        JSON.  A diff here is a trace-schema change: regenerate with
        `PYTHONPATH=. python tests/test_flight.py` and eyeball the golden in
        chrome://tracing before committing (ci.sh ships it as an
        artifact beside the SARIF files)."""
        tree, ticks, seat = _golden_inputs()
        got = json.dumps(flight.chrome_trace(tree=tree, ticks=ticks,
                                             seat=seat),
                         indent=1, sort_keys=True) + "\n"
        assert got == GOLDEN.read_text(), \
            "trace schema drifted from tests/golden_timeline.json"

    def test_structure_host_above_device(self):
        tree, ticks, seat = _golden_inputs()
        trace = flight.chrome_trace(tree=tree, ticks=ticks, seat=seat)
        assert trace["displayTimeUnit"] == "ms"
        ev = trace["traceEvents"]
        # process metadata names both lanes
        meta = {(e["pid"], e["args"]["name"]) for e in ev
                if e["ph"] == "M" and e["name"] == "process_name"}
        assert (1, "host spans") in meta
        # process 2 is the PUMP's host timeline, not a device's
        assert (2, "pump flight recorder (host)") in meta
        # every span in the tree renders as a host "X" slice
        host = {e["name"] for e in ev
                if e["ph"] == "X" and e["pid"] == 1}
        assert host == {"graph.query", "graph.parse", "graph.executor"}
        # the seat instant rides the host lane at the root's start
        seat_ev = [e for e in ev if e["ph"] == "i" and e["pid"] == 1]
        assert seat_ev and seat_ev[0]["ts"] == 1000
        assert seat_ev[0]["args"]["lane"] == 3
        # ticks become stream-thread slices with nested phase slices
        tick_ev = [e for e in ev if e.get("cat") == "tick"]
        assert len(tick_ev) == 2
        t0 = tick_ev[0]
        assert t0["ts"] == 1400 - 360 and t0["dur"] == 360
        phases = [e for e in ev if e.get("cat") == "phase"
                  and e["tid"] == t0["tid"]]
        assert [p["name"] for p in phases] == \
            ["seat", "join", "hop", "extract", "clear", "fetch_wait",
             "d2h", "unpack", "rows", "handover"]
        # the five parts of assemble_us render in its place
        assert sum(p["dur"] for p in phases[5:]) == \
            t0["args"]["assemble_us"]
        # phases tile the tick start-to-busy, in pump order
        assert phases[0]["ts"] == t0["ts"]
        for a, b in zip(phases, phases[1:]):
            assert b["ts"] == a["ts"] + a["dur"]
        # dispatches are instant markers on the dispatch thread
        disp = [e for e in ev if e["ph"] == "i" and e["pid"] == 2]
        assert disp and disp[0]["name"] == "ell_go_sharded"
        assert disp[0]["args"]["ici_declared"] == 1048576
        # timing probes are duration slices on the timing thread
        tim = [e for e in ev if e.get("cat") == "timing"]
        assert tim and tim[0]["name"] == "ell_go"
        assert tim[0]["dur"] == 120

    def test_beat_records_are_no_device_row(self):
        """The host's beats (common/hostclock.py) live in the same ring
        and the export skips them: the golden stands."""
        tree, ticks, seat = _golden_inputs()
        beat = {"kind": "beat", "id": 45, "time_us": 1950,
                "clock": "schedstat", "n": 99, "py_late_sum_us": 9000,
                "py_late_max_us": 400, "nat_n": 100,
                "nat_late_sum_us": 8000, "nat_late_max_us": 300}
        want = flight.chrome_trace(tree=tree, ticks=ticks, seat=seat)
        got = flight.chrome_trace(tree=tree, ticks=ticks[:2] + [beat]
                                  + ticks[2:] + [beat], seat=seat)
        assert got == want

    def test_empty_inputs_still_valid(self):
        trace = flight.chrome_trace()
        assert [e["ph"] for e in trace["traceEvents"]] == ["M"] * 4


# ================================================= /timeline endpoint
class TestTimelineEndpoint:
    def test_endpoint_plain_and_trace_formats(self):
        ws = WebService("nebula-graphd", host="127.0.0.1").start()
        base = f"http://127.0.0.1:{ws.port}"
        rid = flight.recorder.note_dispatch("unit_endpoint", rung=64)
        try:
            body = json.load(urllib.request.urlopen(
                f"{base}/timeline", timeout=30))
            mine = [t for t in body["ticks"] if t.get("id") == rid]
            assert mine and mine[0]["kernel"] == "unit_endpoint"
            # newest first, like /events
            times = [t.get("time_us", 0) for t in body["ticks"]]
            assert times == sorted(times, reverse=True)
            # ?format=trace returns an openable Chrome-trace object
            trace = json.load(urllib.request.urlopen(
                f"{base}/timeline?format=trace", timeout=30))
            assert trace["displayTimeUnit"] == "ms"
            assert any(e.get("name") == "unit_endpoint"
                       for e in trace["traceEvents"])
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/timeline?limit=x")
            assert ei.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/timeline?format=trace&trace=nothex")
            assert ei.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"{base}/timeline?format=trace&trace=deadbeef")
            assert ei.value.code == 404
        finally:
            ws.stop()


# ============================================================== e2e
@pytest.fixture(scope="module")
def fl():
    c = LocalCluster(num_storage=1, tpu_backend=True)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE fl(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE fl")
    ok("CREATE EDGE e(w int)")
    c.refresh_all()
    rng = np.random.default_rng(7)
    pairs = sorted({(int(a), int(b)) for a, b in
                    zip(rng.integers(1, 40, 160),
                        rng.integers(1, 40, 160)) if a != b})
    vals = ", ".join(f"{a} -> {b}:({(a * 31 + b) % 97})"
                     for a, b in pairs)
    ok(f"INSERT EDGE e(w) VALUES {vals}")
    yield c, g, ok
    c.stop()


class TestProfileTraceE2E:
    def test_profile_format_trace_is_openable_chrome_json(self, fl):
        c, g, ok = fl
        r = ok("PROFILE FORMAT=trace GO 2 STEPS FROM 1 OVER e "
               "YIELD e._dst")
        prof = r.profile
        assert prof is not None and prof["displayTimeUnit"] == "ms"
        ev = json.loads(json.dumps(prof))["traceEvents"]   # round-trips
        host = {e["name"] for e in ev
                if e.get("ph") == "X" and e.get("pid") == 1}
        assert {"graph.query", "graph.parse", "graph.executor"} <= host
        # device rows under the host spans: the continuous pump's tick
        # slices (this rider rode a lane batch)
        assert [e for e in ev if e.get("cat") == "tick"], \
            "no device tick rows in the trace"

    def test_plain_profile_still_returns_span_tree(self, fl):
        c, g, ok = fl
        r = ok("PROFILE GO FROM 1 OVER e YIELD e._dst")
        assert r.profile["roots"][0]["name"] == "graph.query"
        assert "critical_path" in r.profile
        r = ok("PROFILE FORMAT=tree GO FROM 1 OVER e YIELD e._dst")
        assert r.profile["roots"][0]["name"] == "graph.query"

    def test_bogus_format_is_a_syntax_error(self, fl):
        c, g, ok = fl
        r = g.execute("PROFILE FORMAT=perfetto GO FROM 1 OVER e")
        assert not r.ok()
        assert "PROFILE FORMAT" in (r.error_msg or "")


class TestShowTimelineE2E:
    def test_shape_ordering_and_count(self, fl):
        c, g, ok = fl
        ok("GO 2 STEPS FROM 2 OVER e YIELD e._dst")     # records exist
        r = ok("SHOW TIMELINE")
        assert r.column_names == ["Host", "Id", "Time(us)", "Kind",
                                  "Source", "Detail"]
        assert r.rows
        times = [row[2] for row in r.rows]
        assert times == sorted(times, reverse=True)      # newest first
        kinds = {row[3] for row in r.rows}
        assert "tick" in kinds
        r5 = ok("SHOW TIMELINE 5")
        assert 0 < len(r5.rows) <= 5
        bad = g.execute("SHOW TIMELINE 0")
        assert not bad.ok()

    def test_metad_fanout_mirrors_show_queries(self, fl):
        c, g, ok = fl
        ok("GO FROM 3 OVER e YIELD e._dst")
        resp = c.meta_service.rpc_showTimeline({"limit": 8})
        assert resp["ticks"], "fan-out returned no recorder rows"
        for t in resp["ticks"]:
            assert t["host"]
        # the graphd-side rpc tags rows with this process' identity so
        # SHOW TIMELINE never double-lists LocalCluster's shared ring
        local = c.graph_service.rpc_listTimeline({"limit": 4})
        assert all(t["proc"] == PROC_TOKEN for t in local["ticks"])


class TestSlowRiderTimelineAnchor:
    def test_slow_log_entry_anchors_recorder_window(self, fl):
        c, g, ok = fl
        saved = flags.get("slow_query_threshold_ms")
        flags.set("slow_query_threshold_ms", 1)
        ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored
        d = c.tpu_runtime.dispatcher
        st = next(iter(d.continuous.streams()))
        st.tick_delay_s = 0.05                  # deliberately slowed
        try:
            ok("GO 4 STEPS FROM 4 OVER e YIELD e._dst")
        finally:
            st.tick_delay_s = 0.0
            flags.set("slow_query_threshold_ms", saved)
        entries = [e for e in slow_log.dump()
                   if "4 STEPS FROM 4" in e["stmt"]]
        assert entries, slow_log.dump()
        e = entries[0]
        # the anchor: [first, last] flight-recorder tick ids for the
        # rider's flight — SHOW TIMELINE (or /timeline) scoped to that
        # id window is the statement's device-side story
        first, last = e["timeline"]
        assert 0 < first <= last
        ids = {t["id"] for t in flight.recorder.dump(limit=1024)}
        assert last in ids, "anchor points past the ring"


if __name__ == "__main__":
    # regenerate the golden after a DELIBERATE trace-schema change:
    #   PYTHONPATH=. python tests/test_flight.py
    tree, ticks, seat = _golden_inputs()
    GOLDEN.write_text(json.dumps(
        flight.chrome_trace(tree=tree, ticks=ticks, seat=seat),
        indent=1, sort_keys=True) + "\n")
    print(f"wrote {GOLDEN}")
