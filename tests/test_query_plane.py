"""The serving control plane's eyes (docs/observability.md "The live
query plane" / "SLO burn rates"):

  * Live query registry unit surface — register/snapshot/overflow,
    kill marks, the /queries webservice endpoint.
  * SHOW QUERIES / KILL QUERY end-to-end: a barrier-held continuous
    rider is listed mid-flight with its lane seat and hop index, the
    kill ends it typed (E_KILLED) within one hop boundary, the lane
    frees, and the continuous ledger stays balanced.
  * Slow continuous riders land in the slow-query log WITH their seat
    markers (lane, joined_tick, hops, typed ending).
  * SLO burn rates: the multi-window engine fires/self-clears
    deterministically, and the chaos leg — an injected storage-latency
    fault pushes the go-class burn over the fast pair, slo.burn_alert
    journals, graph.slo.* gauges export, the graphd /healthz slo check
    flips 503, and healing self-clears it.
  * Per-replica load briefs: dispatcher → graph.load.* gauges →
    role=graph heartbeat → metad listDeviceBriefs graph_briefs.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import slo
from nebula_tpu.common.events import journal
from nebula_tpu.common.flags import flags
from nebula_tpu.common.stats import stats
from nebula_tpu.common.status import ErrorCode
from nebula_tpu.common.tracing import slow_log
from nebula_tpu.graph.query_registry import (KilledError, registry)
from nebula_tpu.webservice import WebService


def _stat(name, win=600):
    return stats.read_stats(f"{name}.sum.{win}") or 0.0


# ===================================================== registry unit
class TestQueryRegistry:
    def test_register_snapshot_unregister(self):
        qid = registry.register("GO FROM 1 OVER e", session=7,
                                user="u", cls="go", space="s",
                                mode="continuous")
        assert qid is not None
        rows = {r["id"]: r for r in registry.snapshot()}
        assert qid in rows
        r = rows[qid]
        assert r["stmt"] == "GO FROM 1 OVER e"
        assert r["class"] == "go" and r["space"] == "s"
        assert r["mode"] == "continuous" and r["session"] == 7
        assert r["lane"] == -1          # never seated
        registry.unregister(qid)
        assert qid not in {x["id"] for x in registry.snapshot()}

    def test_ids_are_process_tagged_and_monotonic(self):
        a = registry.register("a")
        b = registry.register("b")
        try:
            assert b > a
            # same process tag (top bits), distinct sequence
            assert (a >> 40) == (b >> 40)
        finally:
            registry.unregister(a)
            registry.unregister(b)

    def test_overflow_cap_statement_still_runs(self):
        saved = flags.get("query_registry_size")
        flags.set("query_registry_size", 2)
        qids = []
        try:
            before = _stat("graph.query_registry.overflow")
            qids = [registry.register(f"q{i}") for i in range(3)]
            assert qids[0] is not None and qids[1] is not None
            assert qids[2] is None      # over cap: untracked, not failed
            assert _stat("graph.query_registry.overflow") > before
            # unregister of the untracked statement is a no-op
            registry.unregister(None)
        finally:
            flags.set("query_registry_size", saved)
            for q in qids:
                registry.unregister(q)

    def test_kill_marks_and_check_raises_typed(self):
        qid = registry.register("victim")
        try:
            assert registry.kill(qid) is True
            assert registry.is_killed(qid)
            with pytest.raises(KilledError):
                registry.check_killed(qid)
        finally:
            registry.unregister(qid)
        # unknown / finished ids are a miss, not an error (the metad
        # fan-out ORs per-replica answers)
        assert registry.kill(qid) is False
        assert registry.kill(123456789) is False
        registry.check_killed(None)     # untracked: never raises

    def test_seat_markers_only_after_a_seat(self):
        qid = registry.register("never seated")
        try:
            assert registry.seat_markers(qid) is None
            registry.note_seat(qid, 5, 17)
            registry.note_hop(qid, 2)
            m = registry.seat_markers(qid)
            assert m == {"lane": 5, "joined_tick": 17, "hops": 2,
                         "ending": None}
            # once submit() returns, the tick it left on and the waits
            # its time there was made of ride along (the slow log
            # copies them)
            waits = {"seat_wait_us": 40, "ride_us": 900,
                     "result_wait_us": 120, "wake_us": 15}
            registry.note_waits(qid, 19, waits)
            assert registry.seat_markers(qid) == {
                **m, "left_tick": 19, **waits}
        finally:
            registry.unregister(qid)

    def test_queries_endpoint_serves_registry(self):
        ws = WebService("nebula-graphd", host="127.0.0.1").start()
        qid = registry.register("SHOW ME", user="ops")
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{ws.port}/queries", timeout=30)
            body = json.load(resp)
            assert resp.status == 200
            mine = [q for q in body["queries"] if q["id"] == qid]
            assert mine and mine[0]["stmt"] == "SHOW ME"
            assert mine[0]["user"] == "ops"
        finally:
            registry.unregister(qid)
            ws.stop()


# ===================================================== slo engine unit
def _note_at(cls, ok, sec, n=1):
    """slo.note shaped into a chosen epoch second: unit tests stamp a
    FAR-FUTURE ring region so the real-time rings the e2e chaos leg
    (and every healthz probe in this process) reads stay clean."""
    for _ in range(n):
        stats._stats[f"graph.slo.{cls}.served"].add(1.0, now=sec)
        if not ok:
            stats._stats[f"graph.slo.{cls}.errors"].add(1.0, now=sec)


class TestSloEngine:
    # distinct far-future regions per test — ring aliasing is safe
    # (stamps are exact-second checked) but shared regions are not
    _BASE = int(time.time()) + 500_000

    def setup_method(self):
        slo.slo_engine.clear_for_tests()

    def teardown_method(self):
        slo.slo_engine.clear_for_tests()

    def test_note_ignores_undeclared_class(self):
        slo.note("no_such_class", 1.0, True)      # must not register

    def test_fires_on_both_fast_windows_then_self_clears(self):
        # availability burn on the admin class: errors/served over the
        # 0.01 budget — well past the fast threshold on BOTH windows
        base = self._BASE
        _note_at("admin", False, base, n=5)
        rows = slo.slo_engine.evaluate(now=base)
        mine = [r for r in rows if r["class"] == "admin"
                and r["objective"] == "availability"]
        assert mine and mine[0]["firing"] == "fast"
        ev = [e for e in journal.dump(200)
              if e["kind"] == "slo.burn_alert"][0]
        assert ev["state"] == "firing" and ev["slo_class"] == "admin"
        # past the fast pair the slow pair (600/3600 s) still sees the
        # errors: the alert degrades fast -> slow, not to silence
        rows = slo.slo_engine.evaluate(now=base + 90)
        mine = [r for r in rows if r["class"] == "admin"
                and r["objective"] == "availability"]
        assert mine and mine[0]["firing"] == "slow"
        # and once every window has aged out it SELF-CLEARS
        rows = slo.slo_engine.evaluate(now=base + 4000)
        mine = [r for r in rows if r["class"] == "admin"
                and r["objective"] == "availability"]
        assert mine and mine[0]["firing"] is None
        ev = [e for e in journal.dump(200)
              if e["kind"] == "slo.burn_alert"][0]
        assert ev["state"] == "resolved"

    def test_one_window_spike_does_not_fire(self):
        # the multi-window guard: at base+10 the errors are outside
        # the 5 s window but inside 60 s — one window alone must not
        # page
        base = self._BASE + 50_000
        _note_at("admin", False, base, n=5)
        rows = slo.slo_engine.evaluate(now=base + 10)
        mine = [r for r in rows if r["class"] == "admin"
                and r["objective"] == "availability"]
        assert mine and mine[0]["firing"] != "fast"

    def test_evaluate_memoizes_per_second(self):
        sec = int(time.time()) + 7200
        r1 = slo.slo_engine.evaluate(now=sec)
        r2 = slo.slo_engine.evaluate(now=sec + 0.4)
        assert r1 is r2                 # same epoch second: cached rows

    def test_disabled_flag_short_circuits(self):
        saved = flags.get("slo_enabled")
        flags.set("slo_enabled", False)
        try:
            assert slo.slo_engine.evaluate() == []
            ok, detail = slo.slo_engine.health()
            assert ok
        finally:
            flags.set("slo_enabled", saved)

    def test_stats_rows_shape(self):
        rows = slo.slo_engine.stats_rows()
        # two objectives per declared class, 4 burn columns + state
        assert len(rows) == 2 * len(slo.SLO_OBJECTIVES)
        for r in rows:
            assert r[0].startswith("slo.") and len(r) == 6
            assert r[5] in ("ok", "fast", "slow")


# ===================================================== cluster fixture
def _boot(seed=13, n=40, m=160):
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
def qp():
    c, g, ok = _boot()
    yield c, g, ok
    c.stop()


# ===================================================== SHOW / KILL e2e
class TestShowKillE2E:
    def test_show_queries_statement_shape(self, qp):
        c, g, ok = qp
        r = ok("SHOW QUERIES")
        assert r.column_names == ["Id", "Session", "User", "Statement",
                               "Class", "Space", "Mode", "Phase",
                               "Hop", "Lane", "Elapsed(us)",
                               "DeadlineLeft(ms)"]
        # SHOW QUERIES always sees at least itself, registered
        assert any("SHOW QUERIES" in row[3] for row in r.rows)

    def test_kill_unknown_id_is_typed_miss(self, qp):
        c, g, ok = qp
        r = g.execute("KILL QUERY 999999999999")
        assert not r.ok()
        assert "not found" in (r.error_msg or "").lower()

    def test_kill_midflight_seated_rider(self, qp):
        """The acceptance round-trip: a barrier-held continuous rider
        shows in SHOW QUERIES with its lane seat and hop index; KILL
        QUERY ends it typed within one hop boundary; the lane frees
        and the continuous ledger balances."""
        c, g, ok = qp
        ok("GO 2 STEPS FROM 1 OVER e")          # stream anchored
        d = c.tpu_runtime.dispatcher
        st = next(iter(d.continuous.streams()))
        st.tick_delay_s = 0.05
        # ledger snapshot over the full ring: the balance check below
        # must be a DELTA — absolute counters carry every join the
        # rest of the suite made in the shared windows
        j0 = _stat("graph.continuous.joins", 3600)
        l0 = _stat("graph.continuous.leaves", 3600)
        e0 = _stat("graph.continuous.evictions", 3600)
        res = []
        try:
            def rider():
                g2 = c.client()
                g2.execute("USE s")
                res.append(g2.execute(
                    "GO 6 STEPS FROM 1 OVER e YIELD e._dst"))

            t = threading.Thread(target=rider)
            t.start()
            # poll until the rider shows up seated — a fixed sleep
            # flakes on a loaded box (ticks and the rider's admission
            # stretch together, so waiting longer stays mid-flight)
            row = None
            poll_end = time.monotonic() + 8.0
            while time.monotonic() < poll_end:
                rows = ok("SHOW QUERIES").rows
                mine = [r for r in rows
                        if "6 STEPS" in r[3] and r[9] >= 0]
                if mine:
                    row = mine[0]
                    break
                time.sleep(0.02)
            assert row is not None, "rider never seated"
            qid, lane, hop = row[0], row[9], row[8]
            assert row[4] == "go" and row[6] == "continuous"
            assert lane >= 0, "rider not seated with a lane"
            assert hop >= 0
            # the metad fan-out sees the same rider, host-stamped
            mq = c.meta_service.rpc_showQueries({})
            fan = [q for q in mq["queries"] if q["id"] == qid]
            assert fan and fan[0]["host"]
            t0 = time.perf_counter()
            rk = ok(f"KILL QUERY {qid}")
            assert rk.rows == [[qid, True]]
            t.join(timeout=10)
            wall = time.perf_counter() - t0
        finally:
            st.tick_delay_s = 0.0
        assert res, "rider thread never finished"
        assert res[0].error_code == ErrorCode.E_KILLED, res[0].error_msg
        assert "KILL QUERY" in res[0].error_msg
        # "within one hop boundary": well under the 6-hop flight time
        # (generous bound — the typed E_KILLED above is the real
        # proof; this only guards against waiting out a whole flight)
        assert wall < 5.0, wall
        # journaled, typed
        kinds = [e["kind"] for e in journal.dump(200)]
        assert "query.killed" in kinds
        # lane freed: the seat map drains to zero
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if d.continuous.seat_counts() == (0, 0):
                break
            time.sleep(0.05)
        assert d.continuous.seat_counts() == (0, 0), "lane leak"
        # ledger balance: every join left or was evicted — kills ride
        # the eviction leg, so nothing leaks
        joins = _stat("graph.continuous.joins", 3600) - j0
        leaves = _stat("graph.continuous.leaves", 3600) - l0
        evics = _stat("graph.continuous.evictions", 3600) - e0
        assert joins > 0
        assert joins == leaves + evics, (joins, leaves, evics)
        # the kill fan-out through metad answers a live id too
        assert c.meta_service.rpc_killQuery({"qid": 1}) == \
            {"killed": False}

    def test_registry_empty_between_statements(self, qp):
        c, g, ok = qp
        # every statement unregisters on the way out — nothing lingers
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if not registry.snapshot():
                break
            time.sleep(0.05)
        assert registry.snapshot() == []
        fin = _stat("graph.query_registry.finished") \
            + _stat("graph.query_registry.killed")
        assert _stat("graph.query_registry.registered") <= fin + 1


# ===================================================== slow-log seats
class TestSlowRiderSeatMarkers:
    def test_slow_continuous_rider_lands_with_seat_markers(self, qp):
        c, g, ok = qp
        saved = flags.get("slow_query_threshold_ms")
        flags.set("slow_query_threshold_ms", 1)
        d = c.tpu_runtime.dispatcher
        ok("GO 2 STEPS FROM 1 OVER e")
        st = next(iter(d.continuous.streams()))
        st.tick_delay_s = 0.05                  # deliberately slowed
        try:
            ok("GO 4 STEPS FROM 2 OVER e YIELD e._dst")
        finally:
            st.tick_delay_s = 0.0
            flags.set("slow_query_threshold_ms", saved)
        entries = [e for e in slow_log.dump()
                   if "4 STEPS FROM 2" in e["stmt"]]
        assert entries, slow_log.dump()
        e = entries[0]
        assert e["lane"] >= 0
        assert e["joined_tick"] >= 0
        assert e["hops"] >= 1
        assert e["ending"] == "left-batch"      # finished, not evicted
        # windowed/unseated statements carry no seat keys at all
        plain = [x for x in slow_log.dump() if "SHOW" in x["stmt"]]
        for x in plain:
            assert "lane" not in x


# ===================================================== slo chaos e2e
@pytest.fixture(scope="module")
def chaos():
    """CPU-path cluster (GO -> storaged getBound RPC) so the wire
    injector can add real storage latency, plus a graphd-shaped ws
    wired like daemons/graphd.py."""
    c = LocalCluster(num_storage=1)
    g = c.client()

    def ok(stmt):
        r = g.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE ch(partition_num=3, replica_factor=1)")
    c.refresh_all()
    ok("USE ch; CREATE EDGE e(w int)")
    c.refresh_all()
    edges = ", ".join(f"{i} -> {i + 1}:({i})" for i in range(48))
    ok(f"INSERT EDGE e(w) VALUES {edges}")
    ws = WebService("nebula-graphd", host="127.0.0.1").start()
    ws.register_health_check("slo", slo.slo_engine.health)
    yield c, g, ok, ws
    ws.stop()
    c.stop()


def _healthz(ws):
    try:
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{ws.port}/healthz", timeout=30)
        return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


class TestSloBurnChaos:
    def test_storage_latency_fault_fires_then_self_clears(self, chaos):
        from nebula_tpu.interface.faults import default_injector
        c, g, ok, ws = chaos
        slo.slo_engine.clear_for_tests()
        go = "GO FROM 1 OVER e YIELD e._dst"
        ok(go)                                  # healthy baseline
        code, body = _healthz(ws)
        assert code == 200 and body["checks"]["slo"]["ok"]
        # inject: every getBound pays 1.1 s — past the 1 s go-class
        # latency objective, so every GO under the fault is a breach
        default_injector.configure(
            [{"kind": "delay", "method": "getBound", "delay_s": 1.1}],
            seed=3)
        try:
            for _ in range(2):
                ok(go)
        finally:
            default_injector.clear()
        # poll across the epoch-second boundary (the evaluator memoizes
        # per second) — a single fixed-sleep probe flakes on a loaded
        # box; don't wait past the 5 s fast window or the breaches
        # age out of it
        code, body = _healthz(ws)
        poll_end = time.monotonic() + 3.0
        while code != 503 and time.monotonic() < poll_end:
            time.sleep(0.25)
            code, body = _healthz(ws)
        assert code == 503, body
        assert body["checks"]["slo"]["ok"] is False
        assert "go/latency" in body["checks"]["slo"]["detail"]
        ev = [e for e in journal.dump(300)
              if e["kind"] == "slo.burn_alert"
              and e.get("slo_class") == "go"][0]
        assert ev["state"] == "firing"
        # gauges export on scrape
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{ws.port}/metrics",
            timeout=30).read().decode()
        assert "nebula_graph_slo_burn_rate" in text
        assert 'nebula_graph_slo_firing{objective="latency",' \
               'slo_class="go"} 1' in text
        # heal: dilute the windows with fast statements until the
        # breach fraction is back inside every pair's budget
        for _ in range(250):
            ok(go)
        # here time only helps: the diluted windows keep decaying as
        # the breaches age out, so poll until the alert resolves
        code, body = _healthz(ws)
        poll_end = time.monotonic() + 15.0
        while code != 200 and time.monotonic() < poll_end:
            time.sleep(0.5)
            code, body = _healthz(ws)
        assert code == 200, body
        assert body["checks"]["slo"]["ok"] is True
        ev = [e for e in journal.dump(300)
              if e["kind"] == "slo.burn_alert"
              and e.get("slo_class") == "go"][0]
        assert ev["state"] == "resolved"

    def test_show_stats_carries_slo_rows(self, chaos):
        c, g, ok, ws = chaos
        r = ok("SHOW STATS")
        slo_rows = [row for row in r.rows
                    if str(row[1]).startswith("slo.")]
        names = {row[1] for row in slo_rows}
        assert "slo.go.latency" in names
        assert "slo.go.availability" in names
        assert len(slo_rows) == 2 * len(slo.SLO_OBJECTIVES)


# ===================================================== load briefs
class TestLoadBriefs:
    def test_dispatcher_brief_shape_and_gauges(self, qp):
        c, g, ok = qp
        ok("GO 2 STEPS FROM 1 OVER e")
        d = c.tpu_runtime.dispatcher
        brief = d.load_brief()
        assert set(brief) == {"queue_depth", "lane_seated",
                              "lane_queued", "busy_frac",
                              "shed_rate_5s"}
        assert 0.0 <= brief["busy_frac"] <= 1.0
        assert brief["queue_depth"] >= 0
        text = stats.prometheus_text()
        for k in brief:
            assert f"nebula_graph_load_{k}" in text

    def test_metad_serves_graph_briefs(self, qp):
        c, g, ok = qp
        ok("GO FROM 1 OVER e")          # dispatcher exists now
        c.refresh_all()                 # role=graph beat carries brief
        r = c.meta_service.rpc_listDeviceBriefs({})
        gb = r.get("graph_briefs", {})
        assert gb, r
        (_host, load), = list(gb.items())[:1] or [(None, None)]
        assert "busy_frac" in load and "queue_depth" in load
        # and the client-side accessor (same cached round trip as
        # device_briefs) sees the identical serving-tier map once its
        # heartbeat-window cache is expired
        c.graph_meta_client._device_briefs_at = 0.0
        assert c.graph_meta_client.graph_briefs() == gb


# ===================================================== critical path
class TestCriticalPathProfile:
    def test_profile_carries_phase_table_and_summary(self, qp):
        c, g, ok = qp
        before = stats.read_stats("graph.query.phase_us.count.600") or 0
        r = ok("PROFILE GO 3 STEPS FROM 1 OVER e YIELD e._dst")
        prof = r.raw.get("profile")
        assert prof and "critical_path" in prof, prof
        phases = prof["critical_path"]
        assert sum(phases.values()) > 0
        assert set(phases) <= {"queue", "mirror", "hop-kernel",
                               "fetch", "assemble", "other"}
        summary = prof["critical_path_summary"]
        assert "critical path" in summary
        # every finished trace feeds the fleet-wide histogram
        after = stats.read_stats("graph.query.phase_us.count.600") or 0
        assert after > before
