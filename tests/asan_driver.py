"""Lean native-ABI exerciser for the ASAN/UBSAN build.

Run by tests/test_native.py::test_native_suite_under_asan inside an
instrumented process (LD_PRELOAD=libasan, NEBULA_NATIVE_SO pointing at
the `make asan` artifact).  Deliberately avoids pytest and jax device
work — the instrumented interpreter makes those minutes-slow — while
still driving every native entry point: engine CRUD/scans/snapshot
ingest (fuzzed against MemEngine), the batch column decoder, the
C++ ELL builder and the lane unpack.
"""
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from nebula_tpu.codec.rows import encode_row
from nebula_tpu.interface.common import ColumnDef, Schema, SupportedType
from nebula_tpu.kvstore.engine import MemEngine
from nebula_tpu.kvstore.native import NativeEngine
from nebula_tpu.native import available, batch
from nebula_tpu.tpu.ell import EllIndex


def main(tmp_dir: str) -> None:
    assert available(), "native lib did not load under ASAN"

    # engine: fuzz CRUD + scans against MemEngine
    rng = random.Random(3)
    e, m = NativeEngine(), MemEngine()
    keys = [b"k%02d" % i for i in range(40)]
    for step in range(2000):
        k = rng.choice(keys)
        roll = rng.random()
        if roll < 0.5:
            v = bytes(rng.getrandbits(8)
                      for _ in range(rng.randrange(0, 64)))
            e.put(k, v)
            m.put(k, v)
        elif roll < 0.7:
            e.remove(k)
            m.remove(k)
        elif roll < 0.8:
            e.remove_prefix(k[:2])
            m.remove_prefix(k[:2])
        elif roll < 0.85:
            # bulk ABI: neb_multi_put / neb_multi_remove
            kvs = [(rng.choice(keys),
                    bytes(rng.getrandbits(8)
                          for _ in range(rng.randrange(0, 32))))
                   for _ in range(rng.randrange(1, 8))]
            e.multi_put(kvs)
            m.multi_put(kvs)
        elif roll < 0.9:
            doomed = [rng.choice(keys) for _ in range(rng.randrange(1, 5))]
            e.multi_remove(doomed)
            m.multi_remove(doomed)
        elif roll < 0.95:
            a, b = sorted((rng.choice(keys), rng.choice(keys)))
            e.remove_range(a, b)
            m.remove_range(a, b)
        else:
            assert e.get(k) == m.get(k)
    assert list(e.prefix(b"")) == list(m.prefix(b""))
    # range scan + key count over the ABI (neb_scan_range/neb_total_keys)
    assert list(e.range(b"k10", b"k30")) == list(m.range(b"k10", b"k30"))
    assert e.total_keys() == sum(1 for _ in m.prefix(b""))
    snap = os.path.join(tmp_dir, "snap")
    e.flush(snap)
    e2 = NativeEngine()
    e2.ingest(snap)
    assert list(e2.prefix(b"")) == list(m.prefix(b""))

    # batch codec over the ABI (decode_field + parse_keys)
    schema = Schema(columns=[ColumnDef("a", SupportedType.INT),
                             ColumnDef("s", SupportedType.STRING)])
    rows = [encode_row(schema, {"a": i, "s": "x" * (i % 7)})
            for i in range(500)]
    blob, offs, lens = batch.concat_blobs(rows)
    cols = batch.decode_field(blob, offs, lens, schema, 0)
    if cols is not None:
        assert [int(v) for v in cols.i64[:500]] == list(range(500))
    from nebula_tpu.common.keys import KeyUtils
    ekeys = [KeyUtils.edge_key(1, s, 7, 0, d, 5)
             for s, d in [(1, 2), (3, 4), (5, 6)]]
    kb, ko, kl = batch.concat_blobs(ekeys)
    parsed = batch.parse_keys(kb, ko, kl)
    if parsed is not None:
        assert [int(x) for x in parsed.a[:3]] == [1, 3, 5]

    # multi-prefix bulk scan (round 4): counts and content must match
    # per-prefix scans, including empty and all-0xFF-adjacent prefixes
    e2 = NativeEngine()
    from nebula_tpu.common.keys import KeyUtils as KU
    for part in (1, 2):
        for vid in range(6):
            for ver in (5, 6):
                e2.put(KU.edge_key(part, vid, 3, 0, vid + 1, ver),
                       b"v%d" % ver)
    prefixes = [KU.edge_prefix(1, v, 3) for v in range(8)]   # 6,7 empty
    got = e2.multi_prefix_packed(prefixes)
    if got is not None:
        packed, counts = got
        assert [int(c) for c in counts] == [2] * 6 + [0, 0], counts
        singles = b"".join(e2.scan_prefix_packed(p) for p in prefixes)
        assert packed == singles

    # C++ ELL builder
    es = np.asarray(rng.choices(range(64), k=600), dtype=np.int32)
    ed = np.asarray(rng.choices(range(64), k=600), dtype=np.int32)
    ee = np.ones(600, np.int32)
    ix = EllIndex.build(es, ed, ee, 64)
    assert ix.n == 64

    # the lane unpack (neb_count_lanes / neb_unpack_lanes), called as
    # tpu/runtime.py _unpack_lanes calls it: strided rows that start
    # off a word, n not a multiple of 64, an empty and a full leaver,
    # the rung's padding and the bits of rows from n on all ones, an
    # inv with ids outside the table
    from nebula_tpu.native import lib
    from nebula_tpu.tpu.ell import lane_bitmap_bytes
    from nebula_tpu.tpu.runtime import _unpack_lanes, _unpack_lanes_numpy
    assert hasattr(lib(), "neb_unpack_lanes")
    nrng = np.random.default_rng(3)
    for n in (1, 61, 1003, 4099):
        nb = lane_bitmap_bytes(n)
        inv = nrng.permutation(n).astype(np.int32)
        perm = np.empty(n, np.int32)
        perm[inv] = np.arange(n, dtype=np.int32)
        wide = np.full(8 * (nb + 5) + 3, 0xFF, np.uint8)
        packed = wide[3:].reshape(8, nb + 5)[:, :nb]
        packed[:5] = 0
        for i, k in enumerate((0, n, n // 2, min(n, 7), n // 5)):
            rows = nrng.choice(n, k, replace=False)
            np.bitwise_or.at(packed[i], rows % nb,
                             (1 << (rows // nb)).astype(np.uint8))
        got = _unpack_lanes(packed, n, perm, inv, 5)
        ref = _unpack_lanes_numpy(packed, n, perm, inv, 5)
        assert got[3] == 5 and got[1:3] == ref[1:]
        assert all(np.array_equal(a, b) for a, b in zip(got[0], ref[0]))
        # a padding row: every bit set, those of rows >= n too
        full = _unpack_lanes(packed[5:], n, perm, inv, 1)
        assert full[0][0].tolist() == list(range(n))
        bad = inv.copy()
        bad[0] = n
        bad[n - 1] = -1
        assert len(_unpack_lanes(packed[5:], n, perm, bad, 1)[0][0]) \
            == max(n - 2, 0)
    print("ASAN DRIVER OK")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "/tmp")
