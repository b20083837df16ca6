"""Native library tests — C++ engine/codec parity with the Python paths.

Mirrors the reference's native-vs-managed parity testing (NebulaCodecTest
for the JNI codec, RocksEngineTest for the engine): every native entry
must agree byte-for-byte / value-for-value with the Python
implementation it accelerates.
"""
import os
import random

import numpy as np
import pytest

from nebula_tpu.codec.rows import RowReader, encode_row
from nebula_tpu.common.keys import KeyUtils
from nebula_tpu.interface.common import ColumnDef, Schema, SupportedType
from nebula_tpu.kvstore.engine import MemEngine
from nebula_tpu.native import available, batch

pytestmark = pytest.mark.skipif(not available(),
                                reason="native lib not built")

SCHEMA = Schema(columns=[
    ColumnDef("flag", SupportedType.BOOL),
    ColumnDef("cnt", SupportedType.INT),
    ColumnDef("name", SupportedType.STRING),
    ColumnDef("score", SupportedType.DOUBLE),
    ColumnDef("ratio", SupportedType.FLOAT),
    ColumnDef("ts", SupportedType.TIMESTAMP),
], version=3)


def make_engine():
    from nebula_tpu.kvstore.native import NativeEngine
    return NativeEngine()


class TestNativeEngine:
    def test_basic_roundtrip(self):
        e = make_engine()
        assert e.get(b"absent") is None
        e.put(b"k1", b"v1")
        assert e.get(b"k1") == b"v1"
        e.put(b"k1", b"v2")
        assert e.get(b"k1") == b"v2"
        e.remove(b"k1")
        assert e.get(b"k1") is None
        assert e.total_keys() == 0

    def test_empty_value_and_binary_keys(self):
        e = make_engine()
        key = bytes([0, 255, 1, 128])
        e.put(key, b"")
        assert e.get(key) == b""
        assert e.total_keys() == 1

    def test_scans_match_memengine(self):
        rng = random.Random(7)
        native, mem = make_engine(), MemEngine()
        kvs = []
        for _ in range(500):
            k = bytes(rng.randrange(256) for _ in range(rng.randrange(1, 12)))
            v = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 9)))
            kvs.append((k, v))
        native.multi_put(kvs)
        mem.multi_put(kvs)
        assert native.total_keys() == mem.total_keys()
        for prefix in (b"", b"\x00", b"\x7f", bytes([255]), b"ab"):
            assert list(native.prefix(prefix)) == list(mem.prefix(prefix))
        assert list(native.range(b"\x10", b"\xe0")) == \
            list(mem.range(b"\x10", b"\xe0"))

    def test_remove_prefix_and_range(self):
        native, mem = make_engine(), MemEngine()
        kvs = [(bytes([i, j]), bytes([i])) for i in range(8)
               for j in range(8)]
        native.multi_put(kvs)
        mem.multi_put(kvs)
        native.remove_prefix(bytes([3]))
        mem.remove_prefix(bytes([3]))
        native.remove_range(bytes([5, 2]), bytes([6, 1]))
        mem.remove_range(bytes([5, 2]), bytes([6, 1]))
        assert list(native.prefix(b"")) == list(mem.prefix(b""))

    def test_remove_prefix_all_ff(self):
        e = make_engine()
        e.put(b"\xff\xff\x01", b"a")
        e.put(b"\xff\xff\xff", b"b")
        e.put(b"\x01", b"keep")
        e.remove_prefix(b"\xff\xff")
        assert list(e.prefix(b"")) == [(b"\x01", b"keep")]

    def test_flush_ingest_interop_with_memengine(self, tmp_path):
        native, mem = make_engine(), MemEngine()
        kvs = [(b"k%03d" % i, b"v%d" % i) for i in range(100)]
        native.multi_put(kvs)
        p1 = str(tmp_path / "native.snap")
        native.flush(p1)
        mem.ingest(p1)
        assert list(mem.prefix(b"")) == kvs
        # and the reverse direction
        mem2 = MemEngine()
        mem2.multi_put(kvs)
        p2 = str(tmp_path / "mem.snap")
        mem2.flush(p2)
        native2 = make_engine()
        native2.ingest(p2)
        assert list(native2.prefix(b"")) == kvs

    def test_ingest_missing_file(self):
        e = make_engine()
        assert not e.ingest("/nonexistent/nope.snap").ok()

    def test_compaction_filter(self):
        from nebula_tpu.kvstore.native import NativeEngine
        e = NativeEngine(compaction_filter=lambda k, v: k.startswith(b"x"))
        e.multi_put([(b"x1", b""), (b"y1", b""), (b"x2", b"")])
        e.compact()
        assert [k for k, _ in e.prefix(b"")] == [b"y1"]


class TestBatchCodec:
    def _rows(self, n=200):
        rng = random.Random(3)
        rows, vals = [], []
        for i in range(n):
            v = {
                "flag": bool(rng.getrandbits(1)),
                "cnt": rng.randrange(-2**40, 2**40),
                "name": f"row-{i}-é{rng.randrange(100)}",
                "score": rng.random() * 1000 - 500,
                "ratio": float(np.float32(rng.random())),
                "ts": rng.randrange(0, 2**33),
            }
            vals.append(v)
            rows.append(encode_row(SCHEMA, v))
        return rows, vals

    def test_decode_field_parity(self):
        rows, vals = self._rows()
        blob, offs, lens = batch.concat_blobs(rows)
        for fi, col in enumerate(SCHEMA.columns):
            res = batch.decode_field(blob, offs, lens, SCHEMA, fi)
            assert res is not None
            assert (res.valid == 1).all()
            for r, v in enumerate(vals):
                expect = v[col.name]
                if col.type == SupportedType.BOOL:
                    assert bool(res.i64[r]) == expect
                elif col.type in (SupportedType.INT, SupportedType.TIMESTAMP):
                    assert int(res.i64[r]) == expect
                elif col.type == SupportedType.STRING:
                    s = res.blob[int(res.str_off[r]):
                                  int(res.str_off[r] + res.str_len[r])]
                    assert s.decode() == expect
                elif col.type == SupportedType.FLOAT:
                    assert res.f64[r] == pytest.approx(expect, rel=1e-6)
                else:
                    assert res.f64[r] == expect

    def test_version_mismatch_flagged(self):
        rows, _ = self._rows(5)
        other = Schema(columns=SCHEMA.columns, version=9)
        mixed = rows[:3] + [encode_row(other, {"cnt": 1})] + rows[3:]
        blob, offs, lens = batch.concat_blobs(mixed)
        res = batch.decode_field(blob, offs, lens, SCHEMA, 1)
        assert res.valid[3] == 2              # wrong version
        assert (np.delete(res.valid, 3) == 1).all()

    def test_older_schema_prefix_row_reads_missing(self):
        short_schema = Schema(columns=SCHEMA.columns[:2], version=3)
        old_row = encode_row(short_schema, {"flag": True, "cnt": 5})
        blob, offs, lens = batch.concat_blobs([old_row])
        res = batch.decode_field(blob, offs, lens, SCHEMA, 2)
        assert res.valid[0] == 0              # missing, like RowReader
        # python reader agrees
        assert RowReader(old_row, SCHEMA).get("name") == ""

    def test_parse_keys_parity(self):
        rng = random.Random(11)
        keys = []
        expect = []
        for _ in range(100):
            if rng.getrandbits(1):
                args = (rng.randrange(1, 100), rng.randrange(-2**62, 2**62),
                        rng.randrange(-500, 500), rng.randrange(0, 2**62))
                keys.append(KeyUtils.vertex_key(*args))
                expect.append(("v",) + args)
            else:
                args = (rng.randrange(1, 100), rng.randrange(-2**62, 2**62),
                        rng.randrange(-500, 500), rng.randrange(-2**30, 2**30),
                        rng.randrange(-2**62, 2**62), rng.randrange(0, 2**62))
                keys.append(KeyUtils.edge_key(*args))
                expect.append(("e",) + args)
        keys.append(b"junk")
        blob, offs, lens = batch.concat_blobs(keys)
        res = batch.parse_keys(blob, offs, lens)
        assert res.kind[-1] == 0
        for i, exp in enumerate(expect):
            if exp[0] == "v":
                assert res.kind[i] == 1
                assert (res.part[i], res.a[i], res.b[i], res.ver[i]) == exp[1:]
            else:
                assert res.kind[i] == 2
                assert (res.part[i], res.a[i], res.b[i], res.c[i],
                        res.d[i], res.ver[i]) == exp[1:]

    def test_split_frames_roundtrip(self):
        from nebula_tpu.kvstore.native import NativeEngine
        e = NativeEngine()
        kvs = [(b"a%02d" % i, b"val%d" % i) for i in range(50)]
        e.multi_put(kvs)
        packed = e.scan_prefix_packed(b"")
        parts = batch.split_frames(packed)
        assert parts is not None
        ko, kl, vo, vl = parts
        got = [(packed[int(o):int(o + l)],
                packed[int(vo[i]):int(vo[i] + vl[i])])
               for i, (o, l) in enumerate(zip(ko, kl))]
        assert got == kvs


class TestNativeEngineInStore:
    def test_store_uses_native_when_auto(self):
        from nebula_tpu.common.flags import flags
        from nebula_tpu.kvstore import KVOptions, MemPartManager, NebulaStore
        from nebula_tpu.kvstore.native import NativeEngine
        pm = MemPartManager()
        kv = NebulaStore(KVOptions(part_man=pm))
        pm.register_handler(kv)
        pm.add_part(1, 1)
        assert isinstance(kv.spaces[1].engines[0], NativeEngine)
        kv.put(1, 1, b"k", b"v")
        got, st = kv.get(1, 1, b"k")
        assert st.ok() and got == b"v"


def test_native_suite_under_asan(tmp_path):
    """Exercise the full native C ABI (engine CRUD/scan/ingest, batch
    codec, ELL builder) under the ASAN+UBSAN build (reference
    ENABLE_ASAN + SanitizerOptions.cpp:8-50 spirit): any heap overflow
    or UB at the ctypes boundary aborts the run.  Runs the lean
    asan_driver.py script, not pytest — the instrumented interpreter is
    too slow for the whole suite."""
    import shutil
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    native = os.path.join(repo, "native")
    if shutil.which("g++") is None or shutil.which("gcc") is None:
        pytest.skip("no g++/gcc")
    libasan = subprocess.run(
        ["gcc", "-print-file-name=libasan.so"],
        capture_output=True, text=True).stdout.strip()
    if not libasan or not os.path.exists(libasan):
        pytest.skip("no libasan")
    r = subprocess.run(["make", "-C", native, "asan"],
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    env = dict(
        os.environ,
        LD_PRELOAD=libasan,
        NEBULA_NATIVE_SO=os.path.join(native, "libnebula_native_asan.so"),
        JAX_PLATFORMS="cpu",
        # reference SanitizerOptions.cpp defaults; leak check off — the
        # Python interpreter itself reports benign leaks at exit
        ASAN_OPTIONS=("strict_init_order=true:"
                      "detect_stack_use_after_return=true:"
                      "detect_container_overflow=true:detect_leaks=0"),
        UBSAN_OPTIONS="halt_on_error=1:print_stacktrace=1")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tests", "asan_driver.py"),
         str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=repo, timeout=300)
    assert r.returncode == 0, f"ASAN run failed:\n{r.stdout}\n{r.stderr}"
    assert "ASAN DRIVER OK" in r.stdout
    assert "AddressSanitizer" not in r.stderr, r.stderr


def test_the_lane_unpack_is_built_into_both_artifacts():
    """neb_unpack_lanes (native/unpack.cc, PR 47) is there after
    ensure_built(), with the count that sizes its output, and the
    Makefile builds its source into the ASAN artifact too (one SRCS
    for both targets; test_native_suite_under_asan builds and drives
    it)."""
    from nebula_tpu.native import ensure_built, lib
    assert ensure_built()
    for entry in ("neb_unpack_lanes", "neb_count_lanes"):
        assert hasattr(lib(), entry), entry
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "native", "Makefile")) as f:
        make = f.read()
    srcs = next(line for line in make.splitlines()
                if line.startswith("SRCS :="))
    assert "unpack.cc" in srcs.split()
    assert make.count("$(SRCS)") >= 4       # both targets: deps + inputs
    # the count holds the interpreter lock, the pass gives it up
    FUNCFLAG_PYTHONAPI = 0x4                # ctypes' own, not exported
    assert lib().neb_count_lanes._flags_ & FUNCFLAG_PYTHONAPI
    assert not lib().neb_unpack_lanes._flags_ & FUNCFLAG_PYTHONAPI
    packed = np.array([[0b101, 0, 0, 0, 0, 0, 0, 0x80]] * 2, np.uint8)
    counts = np.zeros(2, np.int64)
    assert lib().neb_count_lanes(packed.ctypes.data, 8, 8, 2,
                                 counts.ctypes.data) == 6
    assert counts.tolist() == [3, 3]


def test_split_rowset_rejects_overflowing_varint():
    """A corrupt row-length varint near 2^64 must fail the split, not
    wrap the bounds check into an out-of-bounds row (review finding)."""
    from nebula_tpu.native import ensure_built
    from nebula_tpu.native.batch import split_rowset
    if not ensure_built():
        import pytest
        pytest.skip("native lib unavailable")
    # uvarint encoding ~2^64-6 (nine 0x80|x bytes + terminator) + junk
    evil = bytes([0xFA, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
                  0xFF, 0x01]) + b"abcdef" * 50
    assert split_rowset(evil) is None
    # sane blobs still split
    from nebula_tpu.codec.rows import RowSetWriter, encode_row
    from nebula_tpu.interface.common import ColumnDef, Schema, SupportedType
    sch = Schema(columns=[ColumnDef("x", SupportedType.INT)])
    w = RowSetWriter()
    w.add_row(encode_row(sch, {"x": 5}))
    offs, lens = split_rowset(w.data())
    assert len(offs) == 1
