"""Differential tests: the vectorized bulk mirror builder
(tpu/csr_bulk.py) must produce BIT-IDENTICAL mirrors to the per-row
reference builder (tpu/csr._build_mirror_slow) on adversarial fixtures:
multi-version rows, schema evolution (older rows as prefixes), TTL
expiry, string/bool/float/int columns, missing tags, empty blobs,
multi-part + multi-etype spread, and randomized graphs.
"""
import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.common.flags import flags
from nebula_tpu.native import available
from nebula_tpu.tpu.csr import _build_mirror_slow, build_mirror
from nebula_tpu.tpu.csr_bulk import build_mirror_bulk

pytestmark = pytest.mark.skipif(
    not available(), reason="native library not built")


def _assert_mirrors_equal(a, b):
    np.testing.assert_array_equal(a.vids, b.vids)
    assert a.n == b.n and a.m == b.m
    np.testing.assert_array_equal(a.edge_src, b.edge_src)
    np.testing.assert_array_equal(a.edge_dst, b.edge_dst)
    np.testing.assert_array_equal(a.edge_etype, b.edge_etype)
    np.testing.assert_array_equal(a.edge_rank, b.edge_rank)
    np.testing.assert_array_equal(a.row_ptr, b.row_ptr)
    assert set(a.edge_cols) == set(b.edge_cols)
    for k in a.edge_cols:
        ca, cb = a.edge_cols[k], b.edge_cols[k]
        np.testing.assert_array_equal(ca.valid, cb.valid, err_msg=str(k))
        np.testing.assert_array_equal(ca.values, cb.values,
                                      err_msg=str(k))
        if ca.raw is not None or cb.raw is not None:
            assert [str(x) for x in ca.raw] == [str(x) for x in cb.raw], k
    assert set(a.vertex_cols) == set(b.vertex_cols)
    for k in a.vertex_cols:
        ca, cb = a.vertex_cols[k], b.vertex_cols[k]
        np.testing.assert_array_equal(ca.valid, cb.valid, err_msg=str(k))
        np.testing.assert_array_equal(ca.values, cb.values,
                                      err_msg=str(k))
        if ca.raw is not None or cb.raw is not None:
            assert [str(x) for x in ca.raw] == [str(x) for x in cb.raw], k
    assert set(a.has_tag) == set(b.has_tag)
    for t in a.has_tag:
        np.testing.assert_array_equal(a.has_tag[t], b.has_tag[t])
    # TTL bookkeeping must match so rebuild cadence is identical
    assert (a.expires_at_s is None) == (b.expires_at_s is None)
    if a.expires_at_s is not None:
        assert abs(a.expires_at_s - b.expires_at_s) < 1e-6


def _diff(cluster, space_name):
    sid = cluster.graph_meta_client.get_space_id_by_name(space_name).value()
    stores = [n.kv for n in cluster.storage_nodes]
    slow = _build_mirror_slow(sid, stores, cluster.schema_man)
    fast = build_mirror_bulk(sid, stores, cluster.schema_man)
    assert fast is not None, "bulk builder unexpectedly declined"
    _assert_mirrors_equal(fast, slow)
    return fast


@pytest.fixture(scope="module")
def cluster():
    c = LocalCluster(num_storage=1, tpu_backend=False)
    yield c
    c.stop()


class TestBulkMirrorParity:
    def test_rich_fixture(self, cluster):
        g = cluster.client()

        def ok(s):
            r = g.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"

        ok("CREATE SPACE bulk1(partition_num=5, replica_factor=1)")
        cluster.refresh_all()
        ok("USE bulk1")
        ok("CREATE TAG player(name string, age int, score double, "
           "active bool)")
        ok("CREATE TAG team(name string)")
        ok("CREATE EDGE follow(degree int, note string)")
        ok("CREATE EDGE serve(start_year int)")
        cluster.refresh_all()
        ok('INSERT VERTEX player(name, age, score, active) VALUES '
           '1:("a", 10, 1.5, true), 2:("b", 20, -2.25, false), '
           '3:("c", 30, 0.0, true), 4:("", -1, 1e18, false)')
        ok('INSERT VERTEX team(name) VALUES 100:("t1"), 101:("")')
        ok('INSERT EDGE follow(degree, note) VALUES '
           '1 -> 2:(95, "x"), 2 -> 3:(90, ""), 3 -> 1:(85, "yy"), '
           '1 -> 3@7:(80, "r7"), 1 -> 100:(1, "to-team")')
        ok('INSERT EDGE serve(start_year) VALUES 1 -> 100:(1999), '
           '2 -> 101:(2001)')
        # multi-version: overwrite 1->2 (same identity, fresher version)
        ok('INSERT EDGE follow(degree, note) VALUES 1 -> 2:(96, "x2")')
        ok('INSERT VERTEX player(name, age, score, active) VALUES '
           '2:("b2", 21, -2.25, true)')
        m = _diff(cluster, "bulk1")
        assert m.m > 0 and m.n >= 6
        # spot-check the multi-version winner landed
        d1 = m.to_dense([1])[0]
        e = None
        for i in range(int(m.row_ptr[d1]), int(m.row_ptr[d1 + 1])):
            if (int(m.edge_dst[i]) == m.to_dense([2])[0]
                    and int(m.edge_etype[i]) > 0
                    and int(m.edge_rank[i]) == 0):
                key = (int(m.edge_etype[i]), "degree")
                e = int(m.edge_cols[key].values[i])
        assert e == 96

    def test_schema_evolution_old_rows_as_prefixes(self, cluster):
        g = cluster.client()

        def ok(s):
            r = g.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"

        ok("CREATE SPACE bulk2(partition_num=3, replica_factor=1)")
        cluster.refresh_all()
        ok("USE bulk2")
        ok("CREATE EDGE rel(w int)")
        cluster.refresh_all()
        ok('INSERT EDGE rel(w) VALUES 1 -> 2:(7), 2 -> 3:(8)')
        ok("ALTER EDGE rel ADD (note2 string)")
        cluster.refresh_all()
        ok('INSERT EDGE rel(w, note2) VALUES 3 -> 4:(9, "new")')
        m = _diff(cluster, "bulk2")
        # old rows miss the appended column; new row carries it
        et = [k[0] for k in m.edge_cols if k[1] == "note2"][0]
        tag_col = m.edge_cols[(et, "note2")]
        assert tag_col.valid.sum() == 1

    def test_ttl_expiry(self, cluster):
        import time as _t
        g = cluster.client()

        def ok(s):
            r = g.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"

        ok("CREATE SPACE bulk3(partition_num=3, replica_factor=1)")
        cluster.refresh_all()
        ok("USE bulk3")
        ok("CREATE EDGE seen(ts timestamp) ttl_duration = 3600, "
           "ttl_col = ts")
        ok("CREATE TAG mark(ts timestamp) ttl_duration = 3600, "
           "ttl_col = ts")
        cluster.refresh_all()
        now = int(_t.time())
        ok(f'INSERT EDGE seen(ts) VALUES 1 -> 2:({now}), '
           f'1 -> 3:({now - 7200}), 2 -> 3:({now + 50})')
        ok(f'INSERT VERTEX mark(ts) VALUES 1:({now}), 9:({now - 7200})')
        m = _diff(cluster, "bulk3")
        # expired edge 1->3 dropped (both directions), live ones kept
        assert m.m == 4
        # expired tag row on 9: vertex exists (edge endpoints) is false —
        # 9 only existed via the tag row, which expired, but the vid was
        # still collected pre-filter (slow-path parity)
        assert 9 in m.vids.tolist()
        t = list(m.has_tag)[0]
        assert not m.has_tag[t][m.to_dense([9])[0]]

    def test_randomized_graphs(self, cluster):
        g = cluster.client()
        rng = np.random.default_rng(7)

        def ok(s):
            r = g.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"

        ok("CREATE SPACE bulk4(partition_num=7, replica_factor=1)")
        cluster.refresh_all()
        ok("USE bulk4")
        ok("CREATE EDGE e1(a int, b double)")
        ok("CREATE EDGE e2(s string)")
        ok("CREATE TAG t1(x int)")
        cluster.refresh_all()
        n = 60
        for _ in range(3):
            vals = ", ".join(
                f"{rng.integers(1, n)} -> {rng.integers(1, n)}"
                f"@{rng.integers(0, 3)}:({rng.integers(-5, 5)}, "
                f"{float(rng.integers(-100, 100)) / 4})"
                for _ in range(120))
            ok(f"INSERT EDGE e1(a, b) VALUES {vals}")
            vals2 = ", ".join(
                f'{rng.integers(1, n)} -> {rng.integers(1, n)}:'
                f'("s{rng.integers(0, 9)}")' for _ in range(60))
            ok(f"INSERT EDGE e2(s) VALUES {vals2}")
            vv = ", ".join(f"{v}:({rng.integers(0, 100)})"
                           for v in rng.choice(n - 1, 25, replace=False) + 1)
            ok(f"INSERT VERTEX t1(x) VALUES {vv}")
        _diff(cluster, "bulk4")

    def test_dispatcher_uses_bulk_and_flag_disables(self, cluster):
        g = cluster.client()

        def ok(s):
            r = g.execute(s)
            assert r.ok(), f"{s}: {r.error_msg}"

        ok("CREATE SPACE bulk5(partition_num=3, replica_factor=1)")
        cluster.refresh_all()
        ok("USE bulk5")
        ok("CREATE EDGE r(w int)")
        cluster.refresh_all()
        ok('INSERT EDGE r(w) VALUES 1 -> 2:(1)')
        sid = cluster.graph_meta_client.get_space_id_by_name("bulk5").value()
        stores = [n.kv for n in cluster.storage_nodes]
        m1 = build_mirror(sid, stores, cluster.schema_man)
        flags.set("mirror_bulk_build", False)
        try:
            m2 = build_mirror(sid, stores, cluster.schema_man)
        finally:
            flags.set("mirror_bulk_build", True)
        _assert_mirrors_equal(m1, m2)
