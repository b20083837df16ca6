"""Cross-process device serving (storage/device.py + rpc_deviceGo).

The round-2 flagship seam: the standalone graphd ships whole GO /
FIND PATH queries over the StorageService RPC boundary to storaged's
device runtime (tpu/runtime.py serve_go), replacing round 1's
in-process-only attachment.  Tests cover:

  * row parity remote-device vs CPU per-hop path, over loopback AND
    over real TCP sockets (full wire serialization);
  * the device counters increment (proof the device actually served);
  * graceful decline → CPU fallback (multi-host placement, $-input);
  * hard errors surface as query errors, not CPU fallbacks.
"""
import time

import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags
from nebula_tpu.common.stats import stats


def _seed(c, cl):
    def ok(s):
        r = cl.execute(s)
        assert r.ok(), f"{s}: {r.error_msg}"
        return r
    ok("CREATE SPACE dev(partition_num=4, replica_factor=1)")
    c.refresh_all()
    ok("USE dev")
    ok("CREATE TAG player(name string, age int)")
    ok("CREATE EDGE follow(degree int)")
    c.refresh_all()
    ok('INSERT VERTEX player(name, age) VALUES '
       '100:("Tim", 42), 101:("Tony", 36), 102:("Manu", 41), '
       '103:("LeBron", 34)')
    ok('INSERT EDGE follow(degree) VALUES '
       '100->101:(95), 101->102:(90), 102->100:(90), 100->102:(80), '
       '102->103:(70)')
    return ok


QUERIES = [
    "GO FROM 100 OVER follow",
    "GO UPTO 2 STEPS FROM 100 OVER follow YIELD follow._dst",
    "GO 2 STEPS FROM 100 OVER follow YIELD follow._dst, follow.degree",
    "GO 3 STEPS FROM 100 OVER follow WHERE follow.degree > 85 "
    "YIELD follow._dst, $$.player.name",
    "GO FROM 100, 102 OVER follow WHERE $^.player.age > 40 "
    "YIELD DISTINCT follow._dst",
    "GO FROM 102 OVER follow REVERSELY YIELD follow._dst",
    "FIND SHORTEST PATH FROM 100 TO 103 OVER follow UPTO 5 STEPS",
    "FIND ALL PATH FROM 100 TO 102 OVER follow UPTO 3 STEPS",
]


@pytest.fixture(scope="module",
                params=[(False, 1), (True, 1), (False, 2)],
                ids=["loopback", "tcp", "loopback-2storaged"])
def remote_cluster(request):
    use_tcp, num_storage = request.param
    prev = flags.get("storage_backend")
    flags.set("storage_backend", "tpu")
    c = LocalCluster(num_storage=num_storage, use_tcp=use_tcp,
                     tpu_backend="remote")
    cl = c.client()
    _seed(c, cl)
    yield c, cl
    flags.set("storage_backend", prev)
    c.stop()


class TestRemoteParity:
    @pytest.mark.parametrize("query", QUERIES)
    def test_same_rows_as_cpu(self, remote_cluster, query):
        _, cl = remote_cluster
        r = cl.execute(query)
        assert r.ok(), f"{query}: {r.error_msg}"
        device_rows = sorted(map(tuple, r.rows))
        flags.set("storage_backend", "cpu")
        try:
            r2 = cl.execute(query)
        finally:
            flags.set("storage_backend", "tpu")
        assert r2.ok(), f"{query}: {r2.error_msg}"
        assert device_rows == sorted(map(tuple, r2.rows)), query

    def test_device_counters_increment(self, remote_cluster):
        _, cl = remote_cluster
        go0 = stats.read_stats("storage.device_go.qps.count.3600") or 0
        path0 = stats.read_stats("storage.device_path.qps.count.3600") or 0
        assert cl.execute("GO 2 STEPS FROM 100 OVER follow").ok()
        assert cl.execute("FIND SHORTEST PATH FROM 100 TO 103 OVER follow "
                          "UPTO 5 STEPS").ok()
        assert (stats.read_stats("storage.device_go.qps.count.3600")
                or 0) > go0
        assert (stats.read_stats("storage.device_path.qps.count.3600")
                or 0) > path0


class TestReducePushdownWire:
    """LIMIT/COUNT pushdown over the deviceGo RPC boundary: the reduce
    descriptor rides the request, the response carries the reduced
    shape + capability echo (storage/device.py, docs/roofline.md)."""

    def test_limit_over_rpc(self, remote_cluster):
        _, cl = remote_cluster
        base = "GO 2 STEPS FROM 100 OVER follow YIELD follow._dst AS d"
        full = cl.execute(base)
        assert full.ok()
        fset = {tuple(r) for r in full.rows}
        r = cl.execute(base + " | LIMIT 1")
        assert r.ok(), r.error_msg
        assert len(r.rows) == min(1, len(full.rows))
        assert all(tuple(row) in fset for row in r.rows)

    def test_count_over_rpc_matches_cpu(self, remote_cluster):
        _, cl = remote_cluster
        q = ("GO 2 STEPS FROM 100, 102 OVER follow "
             "YIELD follow._dst AS d | YIELD COUNT(*) AS n")
        go0 = stats.read_stats("storage.device_go.qps.count.3600") or 0
        r = cl.execute(q)
        assert r.ok(), r.error_msg
        assert (stats.read_stats("storage.device_go.qps.count.3600")
                or 0) > go0, "count pipe must still serve on device"
        flags.set("storage_backend", "cpu")
        try:
            r2 = cl.execute(q)
        finally:
            flags.set("storage_backend", "tpu")
        assert r2.ok()
        assert r.column_names == r2.column_names == ["n"]
        assert sorted(map(tuple, r.rows)) == sorted(map(tuple, r2.rows))


class TestDeclineFallback:
    def test_piped_input_runs_cpu(self, remote_cluster):
        """$- input is gated client-side; the piped GO must still return
        correct rows via the CPU per-hop loop."""
        _, cl = remote_cluster
        r = cl.execute("GO FROM 100 OVER follow YIELD follow._dst AS id | "
                       "GO FROM $-.id OVER follow YIELD follow._dst")
        assert r.ok(), r.error_msg
        assert sorted(map(tuple, r.rows)) == [(100,), (102,), (103,)]

    def test_multi_host_space_serves_on_device(self):
        """Parts spread over two storaged hosts: the chosen storaged
        folds the peer's parts into its mirror through deviceScan and
        answers on the device (VERDICT round-2 missing #1 — the gate
        that silently degraded distributed clusters to CPU is gone)."""
        prev = flags.get("storage_backend")
        flags.set("storage_backend", "tpu")
        c = LocalCluster(num_storage=2, tpu_backend="remote")
        try:
            cl = c.client()
            ok = _seed(c, cl)
            # both storageds must actually hold parts of the space
            sid = c.graph_meta_client.get_space_id_by_name("dev").value()
            owned = [len(n.kv.part_ids(sid)) for n in c.storage_nodes]
            assert all(o > 0 for o in owned), owned
            go0 = stats.read_stats("storage.device_go.qps.count.3600") or 0
            r = ok("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert sorted(map(tuple, r.rows)) == [(100,), (102,), (103,)]
            assert (stats.read_stats("storage.device_go.qps.count.3600")
                    or 0) > go0, "device did not serve the 2-host space"
            # writes through the OTHER host must be visible on the next
            # device query (version poll → rebuild)
            ok("INSERT EDGE follow(degree) VALUES 103->100:(60)")
            r2 = ok("GO 2 STEPS FROM 102 OVER follow YIELD follow._dst")
            assert (100,) in set(map(tuple, r2.rows))
        finally:
            flags.set("storage_backend", prev)
            c.stop()

    def test_multi_host_peer_down_falls_back_cpu(self):
        """A peer holding parts becomes unreachable: the serving host
        can't cover the space, declines, and the CPU scatter-gather
        path still answers from the surviving... (the CPU path needs
        the peer too, so here we only assert the DECLINE is clean and
        an error-free response comes back once the peer returns)."""
        prev = flags.get("storage_backend")
        flags.set("storage_backend", "tpu")
        c = LocalCluster(num_storage=2, tpu_backend="remote")
        try:
            cl = c.client()
            ok = _seed(c, cl)
            ok("GO FROM 100 OVER follow")          # device-served once
            # cut peer RPC: the serving host's deviceScan/deviceVersion
            # to the other node now fail
            from nebula_tpu.interface.common import HostAddr
            victims = []
            for n in c.storage_nodes[1:]:
                addr = HostAddr.parse(n.host)
                victims.append((addr, n.handler))
                c.cm.unregister_loopback(addr)   # crash-simulate peer
            # a fresh write bumps versions so the mirror must rebuild —
            # which now fails → decline; the CPU path also needs the
            # peer, so the query errors (partial storage) or succeeds
            # only if the serving host leads every part
            go0 = stats.read_stats("storage.device_go.qps.count.3600") or 0
            r = cl.execute("GO 2 STEPS FROM 100 OVER follow")
            # no NEW device serve happened against a stale/unreachable view
            assert (stats.read_stats("storage.device_go.qps.count.3600")
                    or 0) == go0
            for addr, h in victims:
                c.cm.register_loopback(addr, h)
            r = cl.execute("GO 2 STEPS FROM 100 OVER follow YIELD follow._dst")
            assert r.ok() and sorted(map(tuple, r.rows)) == \
                [(100,), (102,), (103,)]
        finally:
            flags.set("storage_backend", prev)
            c.stop()

    def test_cpu_flag_disables_device(self, remote_cluster):
        _, cl = remote_cluster
        flags.set("storage_backend", "cpu")
        try:
            go0 = stats.read_stats("storage.device_go.qps.count.3600") or 0
            r = cl.execute("GO FROM 100 OVER follow")
            assert r.ok()
            assert (stats.read_stats("storage.device_go.qps.count.3600")
                    or 0) == go0
        finally:
            flags.set("storage_backend", "tpu")


class TestServeGoWire:
    """serve_go's wire decode path directly (no graphd executor)."""

    def test_decline_reasons_on_wire(self, remote_cluster):
        c, _ = remote_cluster
        node = c.storage_nodes[0]
        # non-existent part in the client's view → gate declines
        resp = node.service.rpc_deviceGo({
            "space_id": 1, "parts": [999], "start_vids": [100],
            "etypes": [1], "steps": 1, "etype_to_alias": {1: "follow"},
            "yield": [], "distinct": False, "where": None,
            "pushed_mode": False})
        assert resp["ok"] is False and "999" in resp["reason"]

    def test_undecodable_expression_declines(self, remote_cluster):
        c, _ = remote_cluster
        node = c.storage_nodes[0]
        space_id = node.meta_client.get_space_id_by_name("dev").value()
        parts = sorted(node.kv.part_ids(space_id))
        resp = node.service.rpc_deviceGo({
            "space_id": space_id, "parts": parts, "start_vids": [100],
            "etypes": [1], "steps": 1, "etype_to_alias": {1: "follow"},
            "yield": [[b"\x00garbage", None]], "distinct": False,
            "where": None, "pushed_mode": False})
        assert resp["ok"] is False and resp.get("reason")


class TestTornScanGuard:
    """RemoteStoreView.prefix: a write landing BETWEEN scan chunks gives
    the peer's mirror a torn view of a multi-key commit — the version
    echo must fail the scan (build fails → CPU fallback → next query
    rebuilds) instead of serving torn rows."""

    class _FakeCM:
        def __init__(self, rows_per_chunk=2, bump_at_chunk=None):
            self.rows = [(b"k%02d" % i, b"v%d" % i) for i in range(6)]
            self.per = rows_per_chunk
            self.bump_at = bump_at_chunk
            self.version = 7
            self.chunks_served = 0

        def call(self, addr, method, payload, timeout=None):
            assert method == "deviceScan"
            if self.bump_at is not None \
                    and self.chunks_served == self.bump_at:
                self.version += 1         # a commit landed mid-scan
            cur = payload.get("cursor")
            start = 0
            if cur is not None:
                start = next(i for i, (k, _v) in enumerate(self.rows)
                             if k == cur) + 1
            chunk = self.rows[start:start + self.per]
            self.chunks_served += 1
            return {"ok": True, "rows": chunk,
                    "cursor": chunk[-1][0] if chunk else cur,
                    "done": start + self.per >= len(self.rows),
                    "version": self.version}

    def _view(self, cm):
        from nebula_tpu.interface.common import HostAddr
        from nebula_tpu.storage.device import RemoteStoreView
        return RemoteStoreView(HostAddr("p", 1), 1, cm)

    def test_stable_version_streams_all_rows(self):
        cm = self._FakeCM()
        got = list(self._view(cm).prefix(1, 1, b"k"))
        assert got == cm.rows

    def test_mid_scan_version_bump_fails_the_scan(self):
        from nebula_tpu.interface.rpc import RpcError
        cm = self._FakeCM(bump_at_chunk=2)
        with pytest.raises(RpcError):
            list(self._view(cm).prefix(1, 1, b"k"))


class TestUptoRpcSkew:
    """The deviceGo response must ECHO the upto field: an older
    storaged that ignores it would silently serve exact depth, so a
    missing echo is a decline (cached per space — the round trip is
    not re-paid per query)."""

    def _runtime(self, responses):
        from types import SimpleNamespace

        from nebula_tpu.storage.device import RemoteDeviceRuntime

        rt = RemoteDeviceRuntime(meta_client=None, schema_man=None,
                                 client_manager=None)
        calls = []

        def fake_call(host, method, req, ExcType):
            calls.append(req)
            return responses.pop(0)

        rt._call = fake_call
        rt._device_hosts = lambda sid: [(("h", 1), [1])]
        rt.calls = calls
        return rt

    def _go(self, rt, upto):
        from types import SimpleNamespace

        from nebula_tpu.filter.expressions import PrimaryExpr
        sentence = SimpleNamespace(step=SimpleNamespace(steps=3,
                                                        upto=upto))
        executor = SimpleNamespace(sentence=sentence)
        return rt.run_go(executor, 7, [1], [1], 3, {1: "e"},
                         [SimpleNamespace(expr=PrimaryExpr(1),
                                          alias="c")],
                         False, None, {}, [], upto=upto)

    def test_missing_echo_declines_and_caches(self):
        from nebula_tpu.storage.device import TpuDecline

        import pytest as _pytest
        # old build: ok response WITHOUT the upto echo
        rt = self._runtime([{"ok": True, "columns": ["c"], "rows": []}])
        with _pytest.raises(TpuDecline):
            self._go(rt, upto=True)
        assert 7 in rt._upto_declined
        # next UPTO query on the space declines BEFORE any RPC
        sentence = type("S", (), {})()
        sentence.step = type("T", (), {"steps": 3, "upto": True})()
        assert rt.can_run_go(7, [1], sentence, None, None, [], [],
                             False) is False
        assert len(rt.calls) == 1          # no second round trip

    def test_echo_accepted(self):
        from nebula_tpu.graph.interim import InterimResult
        rt = self._runtime([{"ok": True, "columns": ["c"], "rows": [],
                             "upto": True}])
        out = self._go(rt, upto=True)
        assert isinstance(out, InterimResult)
        assert 7 not in rt._upto_declined

    def test_exact_depth_needs_no_echo(self):
        rt = self._runtime([{"ok": True, "columns": ["c"], "rows": []}])
        out = self._go(rt, upto=False)
        assert out is not None


class TestUptoDeclineCacheHealing:
    """The UPTO negative cache must HEAL: entries lapse after
    upto_decline_ttl_s (a restarted/upgraded storaged gets UPTO traffic
    again without a graphd restart) and drop immediately when a
    placement refresh moves the space's device host."""

    def _declined_runtime(self):
        from nebula_tpu.storage.device import TpuDecline
        helper = TestUptoRpcSkew()
        # old build: ok response WITHOUT the upto echo -> decline cached
        rt = helper._runtime([{"ok": True, "columns": ["c"], "rows": []}])
        with pytest.raises(TpuDecline):
            helper._go(rt, upto=True)
        assert 7 in rt._upto_declined
        return rt

    def _can_run(self, rt):
        sentence = type("S", (), {})()
        sentence.step = type("T", (), {"steps": 3, "upto": True})()
        return rt.can_run_go(7, [1], sentence, None, None, [], [], False)

    def test_decline_lapses_after_ttl(self):
        saved = flags.get("upto_decline_ttl_s")
        flags.set("upto_decline_ttl_s", 0.05)
        try:
            rt = self._declined_runtime()
            assert self._can_run(rt) is False     # cached decline binds
            time.sleep(0.06)
            # TTL lapsed: the space is probed again (entry dropped)
            assert self._can_run(rt) is True
            assert 7 not in rt._upto_declined
        finally:
            flags.set("upto_decline_ttl_s", saved)

    def test_decline_dropped_on_placement_change(self):
        rt = self._declined_runtime()
        assert self._can_run(rt) is False
        # placement refresh moved the space's device host: the old
        # host's decline no longer describes the serving storaged
        rt._device_hosts = lambda sid: [(("h2", 1), [1])]
        assert self._can_run(rt) is True
        assert 7 not in rt._upto_declined

    def test_decline_dropped_on_meta_refresh(self):
        """A storaged restarted WITHOUT mesh
        sharding (same host, same placement) must resume UPTO traffic
        as soon as graphd's meta cache refreshes — not only after the
        TTL or a graphd restart.  load_data bumps
        MetaClient.data_generation; any bump drops the entry."""
        from types import SimpleNamespace
        meta = SimpleNamespace(data_generation=41)
        rt = self._declined_runtime()
        rt.meta = meta
        # re-note against the live meta so the entry carries its gen
        rt._note_upto_declined(7, ("h", 1))
        assert self._can_run(rt) is False      # same generation: binds
        meta.data_generation += 1              # a load_data completed
        assert self._can_run(rt) is True
        assert 7 not in rt._upto_declined

    def test_meta_client_load_data_bumps_generation(self):
        """The generation the decline cache keys on really moves on
        every completed load_data."""
        from nebula_tpu.interface.common import HostAddr
        from nebula_tpu.interface.rpc import ClientManager
        from nebula_tpu.meta.client import MetaClient
        from nebula_tpu.meta.service import MetaService

        cm = ClientManager()
        svc = MetaService()
        addr = HostAddr("127.0.0.1", 45990)
        cm.register_loopback(addr, svc)
        mc = MetaClient([addr], client_manager=cm)
        g0 = mc.data_generation
        mc.load_data()
        assert mc.data_generation == g0 + 1
        mc.load_data()
        assert mc.data_generation == g0 + 2
