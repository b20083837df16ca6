"""nebulatrace tests — span mechanics, fake-clock determinism, RPC
propagation (loopback + TCP envelope), the /traces endpoint, PROFILE /
EXPLAIN statements, the slow-query log, and the tracing-disabled
overhead guard on RpcChannel.call (tier-1 acceptance:
docs/observability.md)."""
import json
import tracemalloc
import urllib.error
import urllib.request

import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common import clock, tracing
from nebula_tpu.common.flags import flags
from nebula_tpu.common.tracing import slow_log, trace_store
from nebula_tpu.interface.common import HostAddr
from nebula_tpu.interface.rpc import LoopbackChannel, RpcChannel, RpcServer


@pytest.fixture(autouse=True)
def _clean_tracing():
    trace_store.clear_for_tests()
    slow_log.clear_for_tests()
    yield
    clock.reset_for_tests()
    trace_store.clear_for_tests()
    slow_log.clear_for_tests()
    assert tracing.current_context() is None, \
        "a span leaked thread-local trace context"


def _names(tree, out=None):
    out = out if out is not None else set()
    for root in tree["roots"]:
        _walk(root, out)
    return out


def _walk(node, out):
    out.add(node["name"])
    for child in node["children"]:
        _walk(child, out)


# ================================================================ spans
class TestSpanMechanics:
    def test_disabled_is_shared_noop(self):
        assert tracing.span("rpc.client") is tracing._NOOP
        assert tracing.start_trace("graph.query") is tracing._NOOP
        with tracing.span("rpc.client") as s:
            assert s is None
        assert trace_store.summaries() == []

    def test_forced_trace_nests_and_tags(self):
        with tracing.start_trace("graph.query", forced=True) as root:
            with tracing.span("graph.parse", stmt="GO") as child:
                child.tag(tokens=7)
        tree = trace_store.tree(root.trace_id)
        assert len(tree["roots"]) == 1
        r = tree["roots"][0]
        assert r["name"] == "graph.query"
        assert [c["name"] for c in r["children"]] == ["graph.parse"]
        assert r["children"][0]["tags"] == {"stmt": "GO", "tokens": 7}

    def test_exception_tags_error_and_propagates(self):
        with pytest.raises(ValueError):
            with tracing.start_trace("graph.query", forced=True) as root:
                with tracing.span("graph.executor"):
                    raise ValueError("boom")
        tree = trace_store.tree(root.trace_id)
        child = tree["roots"][0]["children"][0]
        assert "ValueError" in child["tags"]["error"]

    def test_sample_rate_one_samples(self):
        saved = flags.get("trace_sample_rate")
        flags.set("trace_sample_rate", 1.0)
        try:
            with tracing.start_trace("graph.query") as root:
                assert root is not None
        finally:
            flags.set("trace_sample_rate", saved)
        assert trace_store.tree(root.trace_id) is not None

    def test_fake_clock_advances_span_duration(self):
        """Satellite: spans ride clock.Duration plus the fake-clock
        offset — advance_for_tests ages a span deterministically."""
        with tracing.start_trace("graph.query", forced=True) as root:
            clock.advance_for_tests(2.5)
        clock.reset_for_tests()
        tree = trace_store.tree(root.trace_id)
        dur = tree["roots"][0]["duration_us"]
        assert 2_500_000 <= dur < 3_000_000

    def test_inflight_trace_pinned_against_ring_pressure(self):
        """A slow traced query must not come back gutted: while its
        root is open the trace cannot be evicted, however many other
        traces land in the ring."""
        saved = flags.get("trace_buffer_size")
        flags.set("trace_buffer_size", 2)
        try:
            with tracing.start_trace("graph.query", forced=True) as root:
                with tracing.span("graph.parse"):
                    pass
                for _ in range(6):   # flood the ring while in flight
                    with tracing.start_trace("graph.query",
                                             forced=True):
                        pass
            tree = trace_store.tree(root.trace_id)
            assert tree is not None and len(tree["roots"]) == 1
            assert [c["name"] for c in tree["roots"][0]["children"]] \
                == ["graph.parse"]
        finally:
            flags.set("trace_buffer_size", saved)

    def test_late_span_never_evicts_its_own_fresh_trace(self):
        """cap=1 with a pinned in-flight trace: a late span for an
        already-evicted trace re-creates its entry, and the victim
        search must not pick that fresh entry (KeyError otherwise)."""
        saved = flags.get("trace_buffer_size")
        flags.set("trace_buffer_size", 1)
        try:
            with tracing.start_trace("graph.query", forced=True) as old:
                pass                      # completed trace in the ring
            with tracing.start_trace("graph.query",
                                     forced=True) as live:
                # live is pinned; a LATE span for the old trace arrives
                # (the pipelined-finish shape) — must not crash
                trace_store.record(
                    {"trace_id": old.trace_id, "span_id": 42,
                     "parent_id": old.span_id, "name": "tpu.fetch",
                     "start_us": 0, "duration_us": 1, "tags": {}})
            assert trace_store.tree(live.trace_id) is not None
        finally:
            flags.set("trace_buffer_size", saved)

    def test_profile_stays_usable_as_identifier(self):
        """PROFILE/EXPLAIN are statement prefixes, NOT reserved words —
        columns/tags named profile/explain must keep parsing."""
        from nebula_tpu.graph.parser import GQLParser
        p = GQLParser()
        assert p.parse("GO FROM 1 OVER e YIELD e.w AS profile "
                       "| ORDER BY profile").ok()
        assert p.parse("CREATE TAG profile(name string)").ok()
        assert p.parse("GO FROM 1 OVER explain").ok()
        assert p.parse("FETCH PROP ON explain 1 "
                       "YIELD explain.profile").ok()

    def test_ring_buffer_evicts_oldest_trace(self):
        saved = flags.get("trace_buffer_size")
        flags.set("trace_buffer_size", 3)
        try:
            ids = []
            for _ in range(5):
                with tracing.start_trace("graph.query",
                                         forced=True) as root:
                    pass
                ids.append(root.trace_id)
            assert trace_store.tree(ids[0]) is None
            assert trace_store.tree(ids[-1]) is not None
            assert len(trace_store.summaries()) == 3
        finally:
            flags.set("trace_buffer_size", saved)

    def test_capture_attach_crosses_threads(self):
        import threading
        got = {}

        def worker(cap):
            with tracing.attach_captured(cap):
                with tracing.span("rpc.client", method="x"):
                    got["ctx"] = tracing.current_context()

        with tracing.start_trace("graph.query", forced=True) as root:
            t = threading.Thread(target=worker,
                                 args=(tracing.capture(),))
            t.start()
            t.join()
        assert got["ctx"][0] == root.trace_id
        names = _names(trace_store.tree(root.trace_id))
        assert "rpc.client" in names


# ====================================================== rpc propagation
class _Handler:
    def rpc_ping(self, req):
        # a server-side child span must join the caller's trace
        with tracing.span("graph.executor", executor="Ping"):
            return {"pong": req.get("n", 0)}

    def rpc_boom(self, req):
        raise RuntimeError("kaput")


class TestLoopbackPropagation:
    def test_client_server_spans_share_trace(self):
        ch = LoopbackChannel(_Handler())
        with tracing.start_trace("graph.query", forced=True) as root:
            assert ch.call("ping", {"n": 1}) == {"pong": 1}
        tree = trace_store.tree(root.trace_id)
        r = tree["roots"][0]
        client = r["children"][0]
        assert client["name"] == "rpc.client"
        server = client["children"][0]
        assert server["name"] == "rpc.server"
        assert [c["name"] for c in server["children"]] == \
            ["graph.executor"]

    def test_untraced_loopback_records_nothing(self):
        ch = LoopbackChannel(_Handler())
        assert ch.call("ping", {"n": 2}) == {"pong": 2}
        assert trace_store.summaries() == []


class TestTcpPropagation:
    def test_envelope_carries_spans_across_the_wire(self):
        srv = RpcServer(_Handler()).start()
        ch = RpcChannel(srv.addr)
        try:
            with tracing.start_trace("graph.query", forced=True) as root:
                assert ch.call("ping", {"n": 3}) == {"pong": 3}
            tree = trace_store.tree(root.trace_id)
            names = _names(tree)
            assert {"rpc.client", "rpc.server",
                    "graph.executor"} <= names
            # server spans absorbed from the envelope parent correctly:
            # rpc.server hangs under rpc.client, one root overall
            assert len(tree["roots"]) == 1
            client = tree["roots"][0]["children"][0]
            assert client["children"][0]["name"] == "rpc.server"
        finally:
            ch.close()
            srv.stop()

    def test_server_error_still_returns_spans(self):
        from nebula_tpu.interface.rpc import RpcError
        srv = RpcServer(_Handler()).start()
        ch = RpcChannel(srv.addr)
        try:
            with tracing.start_trace("graph.query", forced=True) as root:
                with pytest.raises(RpcError):
                    ch.call("boom", {})
            names = _names(trace_store.tree(root.trace_id))
            assert "rpc.server" in names
        finally:
            ch.close()
            srv.stop()

    def test_untraced_call_keeps_plain_frames(self):
        srv = RpcServer(_Handler()).start()
        ch = RpcChannel(srv.addr)
        try:
            assert ch.call("ping", {"n": 4}) == {"pong": 4}
            assert trace_store.summaries() == []
        finally:
            ch.close()
            srv.stop()


# ====================================================== overhead guard
class TestDisabledOverheadGuard:
    def test_rpc_call_disabled_path_allocates_nothing_in_tracing(self):
        """Tier-1 acceptance: with tracing off (no context, sample rate
        0) RpcChannel.call must not allocate in the tracing module —
        the disabled hot path is one thread-local read."""
        srv = RpcServer(_Handler()).start()
        ch = RpcChannel(srv.addr)
        try:
            for _ in range(20):                       # warm pool + code
                ch.call("ping", {"n": 0})
            tracemalloc.start()
            try:
                snap1 = tracemalloc.take_snapshot()
                for _ in range(100):
                    ch.call("ping", {"n": 0})
                snap2 = tracemalloc.take_snapshot()
            finally:
                tracemalloc.stop()
            filt = [tracemalloc.Filter(True, "*/common/tracing.py")]
            grew = [s for s in
                    snap2.filter_traces(filt).compare_to(
                        snap1.filter_traces(filt), "lineno")
                    if s.size_diff > 0 or s.count_diff > 0]
            assert grew == [], \
                f"tracing allocated on the disabled path: {grew}"
            assert trace_store.summaries() == []
        finally:
            ch.close()
            srv.stop()


# ====================================================== /traces endpoint
class TestTracesEndpoint:
    def test_listing_fetch_and_slow_log(self):
        from nebula_tpu.webservice import WebService
        with tracing.start_trace("graph.query", forced=True) as root:
            with tracing.span("graph.parse"):
                pass
        slow_log.record("GO FROM 1 OVER e", 123456, root.trace_id)
        ws = WebService("test").start()
        base = f"http://127.0.0.1:{ws.port}"
        try:
            listing = json.load(urllib.request.urlopen(f"{base}/traces"))
            tid = f"{root.trace_id:016x}"
            assert any(t["id"] == tid and t["name"] == "graph.query"
                       and t["spans"] == 2 for t in listing["traces"])
            tree = json.load(urllib.request.urlopen(
                f"{base}/traces?id={tid}"))
            assert tree["trace_id"] == tid
            assert tree["roots"][0]["children"][0]["name"] == \
                "graph.parse"
            slow = json.load(urllib.request.urlopen(
                f"{base}/traces?slow=1"))
            assert slow["slow_queries"][0]["trace_id"] == tid
            assert slow["slow_queries"][0]["latency_us"] == 123456
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/traces?id=nothex")
            assert ei.value.code == 400
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(f"{base}/traces?id=deadbeef")
            assert ei.value.code == 404
        finally:
            ws.stop()


# ============================================== PROFILE / EXPLAIN e2e
@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster(num_storage=2)
    cl = c.client()

    def ok(stmt):
        r = cl.execute(stmt)
        assert r.ok(), f"{stmt}: {r.error_msg}"
        return r

    ok("CREATE SPACE tr(partition_num=4, replica_factor=1)")
    c.refresh_all()
    ok("USE tr")
    ok("CREATE TAG player(name string, age int)")
    ok("CREATE EDGE follow(degree int)")
    c.refresh_all()
    ok('INSERT VERTEX player(name, age) VALUES 100:("Tim", 42), '
       '101:("Tony", 36), 102:("Manu", 41)')
    ok("INSERT EDGE follow(degree) VALUES 100->101:(95), "
       "101->102:(90), 102->100:(90)")
    cl.ok = ok
    yield c, cl
    cl.disconnect()
    c.stop()


class TestProfileStatement:
    def test_profile_go_returns_span_tree(self, cluster):
        _, cl = cluster
        r = cl.ok("PROFILE GO FROM 100 OVER follow YIELD follow._dst")
        assert sorted(map(tuple, r.rows)) == [(101,)]
        prof = r.profile
        assert prof is not None
        assert len(prof["roots"]) == 1
        root = prof["roots"][0]
        assert root["name"] == "graph.query"
        assert root["tags"].get("stmt_kind") == "GoSentence"
        names = set()
        _walk(root, names)
        # parse → executor → scatter-gather pass → per-storage-node RPC
        assert {"graph.parse", "graph.executor", "storage.collect.pass",
                "rpc.client", "rpc.server"} <= names

    def test_profile_renders_in_console(self, cluster):
        from nebula_tpu.console.repl import render_profile
        _, cl = cluster
        r = cl.ok("PROFILE GO FROM 100 OVER follow")
        text = render_profile(r.profile)
        assert "graph.query" in text and "rpc.client" in text
        assert "us" in text

    def test_unprofiled_query_attaches_nothing(self, cluster):
        _, cl = cluster
        r = cl.ok("GO FROM 100 OVER follow")
        assert r.profile is None

    def test_profile_multi_partition_fanout_shares_one_trace(self,
                                                             cluster):
        """Multi-start GO fans out to several parts across BOTH
        storage nodes — every rpc.client span must hang in the same
        tree (one trace id)."""
        _, cl = cluster
        r = cl.ok("PROFILE GO FROM 100,101,102 OVER follow "
                  "YIELD follow._dst")
        prof = r.profile
        assert len(prof["roots"]) == 1      # nothing orphaned
        rpc_spans = []

        def collect(node):
            if node["name"] == "rpc.client":
                rpc_spans.append(node)
            for ch in node["children"]:
                collect(ch)

        collect(prof["roots"][0])
        assert rpc_spans, "no RPC spans in the profile tree"

    def test_piped_profile_shows_per_half_spans_with_rows_in(self,
                                                             cluster):
        """A piped statement profiles as PipeExecutor plus one span per
        half, and the right half's rows_in is the left half's output."""
        _, cl = cluster
        r = cl.ok("PROFILE GO FROM 100 OVER follow YIELD follow._dst "
                  "AS id | GO FROM $-.id OVER follow YIELD follow._dst")
        execs = []

        def collect(node):
            if node["name"] == "graph.executor":
                execs.append(node["tags"])
            for ch in node["children"]:
                collect(ch)

        collect(r.profile["roots"][0])
        kinds = [t["executor"] for t in execs]
        assert kinds.count("GoExecutor") == 2 and "PipeExecutor" in kinds
        right = [t for t in execs
                 if t["executor"] == "GoExecutor" and t["rows_in"] > 0]
        assert right and right[0]["rows_in"] == 1  # 100 -> {101}

    def test_union_profile_shows_both_arms(self, cluster):
        _, cl = cluster
        r = cl.ok("PROFILE GO FROM 100 OVER follow UNION "
                  "GO FROM 101 OVER follow")
        execs = []

        def collect(node):
            if node["name"] == "graph.executor":
                execs.append(node["tags"]["executor"])
            for ch in node["children"]:
                collect(ch)

        collect(r.profile["roots"][0])
        assert execs.count("GoExecutor") == 2 and "SetExecutor" in execs

    def test_profile_after_leading_comment(self, cluster):
        """The parser accepts leading comments — the forced-trace
        sniff must agree, or the PROFILE silently returns no tree."""
        _, cl = cluster
        r = cl.ok("/* hint */ PROFILE GO FROM 100 OVER follow")
        assert r.profile is not None
        assert r.profile["roots"][0]["name"] == "graph.query"

    def test_sniff_is_token_aware(self):
        """The word PROFILE INSIDE a leading comment must not force a
        trace; real prefixes in any comment/whitespace shape must."""
        from nebula_tpu.graph.service import ExecutionEngine
        sniff = ExecutionEngine._sniff_profile
        assert sniff("PROFILE GO FROM 1 OVER e")
        assert sniff("/* c */ profile $a = GO FROM 1 OVER e")
        assert sniff("-- x\n# y\n  PROFILE GO")
        assert not sniff("-- PROFILE later\nGO FROM 1 OVER e")
        assert not sniff("/* PROFILE */ GO FROM 1 OVER e")
        assert not sniff("PROFILER GO")
        assert not sniff("\n" + " " * 3000 + "GO FROM 1 OVER e")

    def test_comment_mentioning_profile_stays_untraced(self, cluster):
        _, cl = cluster
        r = cl.ok("-- PROFILE someday\nGO FROM 100 OVER follow")
        assert r.profile is None
        assert trace_store.summaries() == []

    def test_profile_assignment_statement(self, cluster):
        """PROFILE must accept every statement form — `$var = ...`
        assignments included."""
        _, cl = cluster
        r = cl.ok("PROFILE $a = GO FROM 100 OVER follow "
                  "YIELD follow._dst")
        assert r.profile is not None
        names = set()
        _walk(r.profile["roots"][0], names)
        assert "graph.executor" in names

    def test_sniffed_profile_that_fails_parse_discards_trace(self,
                                                             cluster):
        """A force-started trace whose statement turns out not to be a
        valid PROFILE must not squat in the ring buffer."""
        _, cl = cluster
        r = cl.execute("PROFILE 123")
        assert not r.ok()
        assert trace_store.summaries() == []

    def test_explain_returns_plan_without_executing(self, cluster):
        _, cl = cluster
        r = cl.ok("EXPLAIN INSERT EDGE follow(degree) VALUES "
                  "100->999:(1)")
        assert r.column_names == ["step", "sentence", "executor"]
        assert r.rows == [[0, "InsertEdgeSentence",
                           "InsertEdgeExecutor"]]
        # the insert did NOT run
        check = cl.ok("GO FROM 100 OVER follow YIELD follow._dst")
        assert (999,) not in set(map(tuple, check.rows))
        # and EXPLAIN does not trace: no junk entries in the ring
        assert trace_store.summaries() == []


class TestSlowQueryLog:
    def test_password_statements_redacted(self):
        """/traces?slow=1 is unauthenticated — credential literals must
        never land in the log verbatim."""
        slow_log.record('CREATE USER u WITH PASSWORD "s3cret"', 99, None)
        slow_log.record("CHANGE PASSWORD 'old1' TO 'new2' FOR u", 99,
                        None)
        dumped = json.dumps(slow_log.dump())
        for secret in ("s3cret", "old1", "new2"):
            assert secret not in dumped
        assert '***' in dumped

    def test_huge_statements_truncated(self):
        slow_log.record("INSERT EDGE e(w) VALUES " + "x" * 100_000,
                        99, None)
        entry = slow_log.dump()[0]
        assert len(entry["stmt"]) < 5000
        assert entry["stmt"].endswith("chars]")

    def test_slow_statement_lands_in_log(self, cluster):
        _, cl = cluster
        saved = flags.get("slow_query_threshold_ms")
        flags.set("slow_query_threshold_ms", 1)
        try:
            cl.ok("PROFILE GO 2 STEPS FROM 100,101,102 OVER follow")
            entries = slow_log.dump()
            assert entries, "slow query did not land in the log"
            assert "GO 2 STEPS" in entries[0]["stmt"]
            # the PROFILEd statement was traced, so the log links it
            assert entries[0]["trace_id"] is not None
        finally:
            flags.set("slow_query_threshold_ms", saved)


class TestProfileTpuPhases:
    def test_profile_covers_device_phases(self):
        """Acceptance: PROFILE GO on a multi-partition space served by
        the (remote) device runtime shows mirror/transfer/kernel/gather
        phases in the same trace as the RPC hops, and /traces serves
        the trace on the daemons' webservices."""
        from nebula_tpu.common.stats import stats
        from nebula_tpu.webservice import WebService
        prev = flags.get("storage_backend")
        flags.set("storage_backend", "tpu")
        c = LocalCluster(num_storage=2, tpu_backend="remote")
        try:
            cl = c.client()

            def ok(stmt):
                r = cl.execute(stmt)
                assert r.ok(), f"{stmt}: {r.error_msg}"
                return r

            ok("CREATE SPACE devtr(partition_num=4, replica_factor=1)")
            c.refresh_all()
            ok("USE devtr")
            ok("CREATE EDGE follow(degree int)")
            c.refresh_all()
            ok("INSERT EDGE follow(degree) VALUES 100->101:(95), "
               "101->102:(90), 102->100:(90), 100->102:(80)")
            go0 = stats.read_stats("storage.device_go.qps.count.3600") \
                or 0
            r = ok("PROFILE GO 2 STEPS FROM 100 OVER follow "
                   "YIELD follow._dst")
            assert sorted(map(tuple, r.rows)) == [(100,), (102,)]
            assert (stats.read_stats("storage.device_go.qps.count.3600")
                    or 0) > go0, "device path did not serve the query"
            prof = r.profile
            assert prof is not None and len(prof["roots"]) == 1
            names = set()
            _walk(prof["roots"][0], names)
            assert {"graph.parse", "graph.executor", "rpc.client",
                    "rpc.server", "tpu.mirror.build",
                    "tpu.launch", "tpu.kernel", "tpu.fetch",
                    "tpu.assemble"} <= names, names
            # the trace is fetchable over /traces on both daemons' web
            # surfaces (same built-in handler graphd and storaged mount)
            tid = prof["trace_id"]
            for daemon in ("nebula-graphd", "nebula-storaged"):
                ws = WebService(daemon).start()
                try:
                    tree = json.load(urllib.request.urlopen(
                        f"http://127.0.0.1:{ws.port}/traces?id={tid}"))
                    got = set()
                    for root in tree["roots"]:
                        _walk(root, got)
                    assert "tpu.kernel" in got
                finally:
                    ws.stop()
        finally:
            flags.set("storage_backend", prev)
            c.stop()
