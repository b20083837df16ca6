"""Bulk loader parity: a space loaded via vectorized ingest files
(tools/bulk_load.py) must be indistinguishable — scan-for-scan and
query-for-query — from the same data loaded through INSERT statements.
"""
import numpy as np
import pytest

from nebula_tpu.cluster import LocalCluster
from nebula_tpu.codec.rows import encode_row
from nebula_tpu.tools import bulk_load as BL


@pytest.fixture()
def cluster():
    c = LocalCluster(num_storage=1, tpu_backend=False)
    yield c
    c.stop()


def _mk_space(c, g, name):
    def ok(s):
        r = g.execute(s)
        assert r.ok(), f"{s}: {r.error_msg}"

    ok(f"CREATE SPACE {name}(partition_num=4, replica_factor=1)")
    c.refresh_all()
    ok(f"USE {name}")
    ok("CREATE TAG person(age int)")
    ok("CREATE EDGE knows(w int)")
    c.refresh_all()
    sid = c.graph_meta_client.get_space_id_by_name(name).value()
    tag = c.schema_man.to_tag_id(sid, "person").value()
    et = c.schema_man.to_edge_type(sid, "knows").value()
    return ok, sid, tag, et


def test_bulk_load_matches_insert_load(cluster, tmp_path):
    c = cluster
    g = c.client()
    rng = np.random.default_rng(3)
    n, m = 50, 200
    src = rng.integers(1, n + 1, m)
    dst = rng.integers(1, n + 1, m)
    w = rng.integers(0, 7, m)
    vids = np.arange(1, n + 1)
    ages = rng.integers(18, 25, n)

    # ---- reference: INSERT statements -------------------------------
    ok, _, _, _ = _mk_space(c, g, "ins")
    vv = ", ".join(f"{v}:({a})" for v, a in zip(vids, ages))
    ok(f"INSERT VERTEX person(age) VALUES {vv}")
    ev = ", ".join(f"{s} -> {d}:({x})" for s, d, x in zip(src, dst, w))
    ok(f"INSERT EDGE knows(w) VALUES {ev}")

    # ---- bulk: vectorized ingest ------------------------------------
    ok2, sid, tag, et = _mk_space(c, g, "blk")
    schema_e = c.schema_man.get_edge_schema(sid, et)
    schema_t = c.schema_man.get_tag_schema(sid, tag)
    # low-cardinality blobs + per-row index (fixed-width requirement)
    e_blobs = [encode_row(schema_e, {"w": int(i)}) for i in range(7)]
    t_blobs = [encode_row(schema_t, {"age": int(a)})
               for a in range(18, 25)]
    store = c.storage_nodes[0].kv
    nparts = len(store.part_ids(sid))
    groups = [
        BL.edge_frames(nparts, et, src, dst, e_blobs, w),
        BL.vertex_frames(nparts, tag, vids, t_blobs, ages - 18),
    ]
    st = BL.bulk_load(store, sid, str(tmp_path), groups)
    assert st.ok(), st

    # ---- parity: same queries, same rows ----------------------------
    for q in [
        "GO FROM 1 OVER knows YIELD knows._dst, knows.w",
        "GO 2 STEPS FROM 5 OVER knows",
        "GO FROM 7 OVER knows WHERE knows.w > 3 YIELD knows._dst",
        "GO FROM 3 OVER knows YIELD $$.person.age AS a",
        "GO FROM 11 OVER knows REVERSELY",
        "FETCH PROP ON person 9 YIELD person.age",
    ]:
        g.execute("USE ins")
        a = g.execute(q)
        g.execute("USE blk")
        b = g.execute(q)
        assert a.ok() and b.ok(), (q, a.error_msg, b.error_msg)
        assert sorted(map(tuple, a.rows)) == sorted(map(tuple, b.rows)), q

    # ---- parity at the mirror level ---------------------------------
    from nebula_tpu.tpu.csr import build_mirror
    sid_ins = c.graph_meta_client.get_space_id_by_name("ins").value()
    m_ins = build_mirror(sid_ins, [store], c.schema_man)
    m_blk = build_mirror(sid, [store], c.schema_man)
    np.testing.assert_array_equal(m_ins.vids, m_blk.vids)
    np.testing.assert_array_equal(m_ins.edge_src, m_blk.edge_src)
    np.testing.assert_array_equal(m_ins.edge_dst, m_blk.edge_dst)
    # etype ids differ across spaces (meta assigns per space); the
    # direction structure must match
    np.testing.assert_array_equal(np.sign(m_ins.edge_etype),
                                  np.sign(m_blk.edge_etype))


def test_bulk_load_bumps_version_and_serves_device(cluster, tmp_path):
    """Ingest must invalidate mirrors (store version bump) and the
    bulk-loaded graph must serve on the device path."""
    c = cluster
    g = c.client()
    ok, sid, tag, et = _mk_space(c, g, "blk2")
    store = c.storage_nodes[0].kv
    v0 = store.mutation_version(sid)
    src = np.asarray([1, 2, 3])
    dst = np.asarray([2, 3, 4])
    schema_e = c.schema_man.get_edge_schema(sid, et)
    blobs = [encode_row(schema_e, {"w": 1})]
    st = BL.bulk_load(store, sid, str(tmp_path),
                      [BL.edge_frames(len(store.part_ids(sid)), et,
                                      src, dst, blobs,
                                      np.zeros(3, np.int64))])
    assert st.ok()
    assert store.mutation_version(sid) > v0
    r = g.execute("GO 3 STEPS FROM 1 OVER knows")
    assert r.ok() and sorted(map(tuple, r.rows)) == [(4,)]


def test_bulk_load_stages_bounded_files(cluster, tmp_path, monkeypatch):
    """No staging file grows past STAGE_BYTES (a part larger than that
    is cut at row boundaries), each file is gone once ingested, and the
    load is still complete."""
    import os
    c = cluster
    g = c.client()
    ok, sid, tag, et = _mk_space(c, g, "blk3")
    store = c.storage_nodes[0].kv
    monkeypatch.setattr(BL, "STAGE_BYTES", 1024)
    rng = np.random.default_rng(5)
    m = 400
    src = rng.integers(1, 40, m)
    dst = rng.integers(1, 40, m)
    schema_e = c.schema_man.get_edge_schema(sid, et)
    blobs = [encode_row(schema_e, {"w": int(i)}) for i in range(3)]
    frames = BL.edge_frames(len(store.part_ids(sid)), et, src, dst,
                            blobs, np.arange(m) % 3)
    assert max(len(v) for v in frames.values()) > 1     # parts were cut
    staged = []
    ingest = store.ingest

    def spy(space_id, paths):
        staged.extend(os.path.getsize(p) for p in paths)
        assert os.listdir(tmp_path) == [os.path.basename(p)
                                        for p in paths]
        return ingest(space_id, paths)

    monkeypatch.setattr(store, "ingest", spy)
    assert BL.bulk_load(store, sid, str(tmp_path), [frames]).ok()
    assert staged and max(staged) <= 1024
    assert sum(staged) == sum(v.nbytes for vs in frames.values()
                              for v in vs)
    assert os.listdir(tmp_path) == []
    pairs = set(zip(src.tolist(), dst.tolist()))
    for v in (int(src[0]), int(src[1])):
        r = g.execute(f"GO FROM {v} OVER knows")
        assert r.ok()
        assert sorted(x[0] for x in r.rows) == \
            sorted(d for s, d in pairs if s == v)


@pytest.mark.parametrize("vlen", [0, 9, 13])
def test_frames_of_one_length_are_the_scattered_frames(vlen, monkeypatch):
    """Values of one length take the row-matrix path
    (_frames_one_length); the byte-column scatters of the mixed-length
    path, forced here by hiding the short cut, build the same buffers,
    cut at the same rows."""
    rng = np.random.default_rng(11)
    m = 500
    src = rng.integers(1, 90, m)
    dst = rng.integers(1, 90, m)
    blobs = [bytes(rng.integers(0, 256, vlen, dtype=np.uint8))
             for _ in range(5)] if vlen else []
    idx = rng.integers(0, 5, m) if vlen else np.zeros(m, np.int64)
    fast = BL.edge_frames(4, 7, src, dst, blobs, idx, version=123)
    monkeypatch.setattr(BL, "_one_length", lambda blob_len: False)
    slow = BL.edge_frames(4, 7, src, dst, blobs, idx, version=123)
    assert fast.keys() == slow.keys()
    for part in fast:
        assert len(fast[part]) == len(slow[part])
        for a, b in zip(fast[part], slow[part]):
            assert a.dtype == b.dtype == np.uint8
            assert np.array_equal(a, b)


def test_frames_of_mixed_lengths_keep_the_scatter_path():
    rng = np.random.default_rng(12)
    m = 300
    src = rng.integers(1, 60, m)
    dst = rng.integers(1, 60, m)
    blobs = [b"ab", b"cdefg", b"", b"hij"]
    idx = rng.integers(0, 4, m)
    frames = BL.edge_frames(3, 5, src, dst, blobs, idx, version=9)
    total = sum(v.nbytes for vs in frames.values() for v in vs)
    klen = BL._EDGE_KEY.itemsize
    lens = np.array([len(b) for b in blobs])[idx]
    assert total == 2 * int((8 + klen + lens).sum())


@pytest.mark.parametrize("lo,hi,nparts", [(0, 60, 4), (1, 1 << 20, 3),
                                          (1 << 33, 1 << 34, 16)])
def test_the_packed_order_is_the_lexsort_order(lo, hi, nparts,
                                               monkeypatch):
    """Where part, owner, etype and other pack into 64 bits the frames
    are sorted by one argsort and the sorted fields read back off the
    key (_packed_order); the five-key lexsort and a gather a field,
    which negative vids and ranks that differ still take, give the
    same buffers: duplicates of one edge keep their order (both sorts
    are stable), vids over 32 bits take the 64-bit modulo."""
    rng = np.random.default_rng(13)
    m = 3000
    src = rng.integers(lo, hi, m)
    dst = rng.integers(lo, hi, m)
    src[:4], dst[:4] = src[4], dst[4]           # one edge five times
    blobs = [b"123456789", b"abcdefghi", b"ABCDEFGHI"]
    idx = rng.integers(0, 3, m)
    packed = BL.edge_frames(nparts, 7, src, dst, blobs, idx, version=5)
    monkeypatch.setattr(BL, "_packed_order", lambda *a: None)
    plain = BL.edge_frames(nparts, 7, src, dst, blobs, idx, version=5)
    assert packed.keys() == plain.keys()
    for part in packed:
        assert len(packed[part]) == len(plain[part])
        for a, b in zip(packed[part], plain[part]):
            assert np.array_equal(a, b)
