"""Bulk loader — vectorized ingest-file generation for 10^8-row loads.

The reference's bulk path is Spark-generated SSTs fetched with
``DOWNLOAD HDFS`` and installed by ``INGEST``
(/root/reference/src/tools/spark-sstfile-generator/…/SparkSstFileGenerator.scala,
RocksEngine.h:156); the statement/RPC write path is never asked to
carry dataset-scale loads.  This module is the same idea with numpy as
the cluster-side generator: keys for every edge/vertex build in one
vectorized pass over the whole id arrays (structured big-endian dtypes
reproduce the order-preserving sign-flipped layout of common/keys.py
bit-for-bit), frames stream to snapshot-format files, and
``NebulaStore.ingest`` installs them engine-side and bumps the space
version so CSR mirrors rebuild.

Property values ride as PRE-ENCODED row blobs: datasets at this scale
have low-cardinality property shapes, so callers encode each distinct
blob once (codec.rows.encode_row) and pass a per-edge index — the
frame assembly is then one np.take, no per-row Python.

tests/test_bulk_load.py proves byte-parity: a bulk-loaded space must be
indistinguishable (scan-for-scan, query-for-query) from the same data
loaded through INSERT statements.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..common.clock import inverted_version
from ..common.keys import id_hash
from ..common.status import Status

# the largest staging file bulk_load writes (and the engine reads back
# whole): big enough that a 2^24-edge load is one file per part, far
# enough under 1 GiB that a file-size limit never cuts a load short
STAGE_BYTES = 256 << 20

_S32 = np.uint64(1 << 31)
_S64 = np.uint64(1 << 63)

_EDGE_KEY = np.dtype([("part", ">u4"), ("src", ">u8"), ("et", ">u4"),
                      ("rank", ">u8"), ("dst", ">u8"), ("ver", ">u8")])
_VERT_KEY = np.dtype([("part", ">u4"), ("vid", ">u8"), ("tag", ">u4"),
                      ("ver", ">u8")])


def _flip32(v: np.ndarray) -> np.ndarray:
    return (v.astype(np.int64) + np.int64(1 << 31)).astype(np.uint64) \
        & np.uint64(0xFFFFFFFF)


def _flip64(v: np.ndarray) -> np.ndarray:
    return v.astype(np.uint64, copy=False) + _S64   # wraps mod 2^64


def _parts_of(vids: np.ndarray, nparts: int) -> np.ndarray:
    """Vectorized id_hash (common/keys.py): unsigned modulo, 1-based."""
    return (vids.astype(np.uint64) % np.uint64(nparts)).astype(np.int64) + 1


def _frames_varlen(keys: np.ndarray, blobs: List[bytes],
                   val_idx: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Assemble frames of MIXED value lengths into one contiguous
    uint8 buffer IN ROW ORDER (vectorized byte scatters, no per-row
    Python).  Preserving the caller's order is the point: a
    key-sorted run stays one ascending run on disk, which the engine's
    hinted insert turns into O(1)-amortized ingest.  Returns
    (buffer, row byte-offsets [m+1])."""
    m = len(keys)
    klen = keys.dtype.itemsize
    blob_len = np.asarray([len(b) for b in blobs], np.int64)
    val_idx = np.asarray(val_idx, np.int64)
    if m and _one_length(blob_len):
        return _frames_one_length(
            keys, blobs, val_idx, int(blob_len[0]) if len(blobs) else 0)
    vlen = blob_len[val_idx] if len(blobs) else np.zeros(m, np.int64)
    off = np.zeros(m + 1, np.int64)
    np.cumsum(8 + klen + vlen, out=off[1:])
    buf = np.empty(int(off[-1]), np.uint8)
    base = off[:-1]
    kl_b = np.frombuffer(np.array(klen, ">u4").tobytes(), np.uint8)
    pos = base.copy()           # one running index array: per-byte
    for i in range(4):          # scatters reuse it instead of paying a
        buf[pos] = kl_b[i]      # fresh base+i allocation each pass
        pos += 1
    vl_b = vlen.astype(">u4").view(np.uint8).reshape(m, 4)
    for i in range(4):
        buf[pos] = vl_b[:, i]
        pos += 1
    kb = keys.view(np.uint8).reshape(m, klen)
    for i in range(klen):
        buf[pos] = kb[:, i]
        pos += 1
    for L in np.unique(blob_len).tolist() if m else []:
        same = np.nonzero(blob_len == L)[0]
        rows = np.nonzero(vlen == L)[0]
        if L == 0 or len(rows) == 0:
            continue
        remap = np.zeros(len(blobs), np.int64)
        remap[same] = np.arange(len(same))
        vmat = np.frombuffer(b"".join(blobs[int(j)] for j in same),
                             np.uint8).reshape(len(same), L)
        rv = vmat[remap[val_idx[rows]]]
        rb = base[rows] + 8 + klen
        for i in range(L):
            buf[rb + i] = rv[:, i]
    return buf, off


def _one_length(blob_len: np.ndarray) -> bool:
    return not len(blob_len) or blob_len.min() == blob_len.max()


def _frames_one_length(keys: np.ndarray, blobs: List[bytes],
                       val_idx: np.ndarray, vlen: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """_frames_varlen where every value has ``vlen`` bytes (a schema
    of fixed-width columns: every bulk load the benchmark makes): the
    frames are the rows of ONE [m, 8 + klen + vlen] byte matrix, filled
    a field at a time by four strided copies where the mixed-length
    form scatters a byte column at a time, 8 + klen + vlen passes over
    an index array (13 of the 44 s of a 16 M-edge load's frames on the
    v5e's host, PERF.md section 6, PR 41).  The same bytes in the same
    order."""
    m = len(keys)
    klen = keys.dtype.itemsize
    head = np.frombuffer(np.array([klen, vlen], ">u4").tobytes(), np.uint8)
    frames = np.empty((m, 8 + klen + vlen), np.uint8)
    frames[:, :8] = head
    frames[:, 8:8 + klen] = keys.view(np.uint8).reshape(m, klen)
    if vlen:
        frames[:, 8 + klen:] = np.frombuffer(
            b"".join(blobs), np.uint8).reshape(len(blobs), vlen)[val_idx]
    return frames.reshape(-1), \
        np.arange(m + 1, dtype=np.int64) * frames.shape[1]


def _split_by_part(parts: np.ndarray, nparts: int, buf: np.ndarray,
                   off: np.ndarray) -> Dict[int, List[np.ndarray]]:
    """Slice a part-major frame buffer into per-part byte views
    (``parts`` must be sorted ascending — both frame builders sort
    part-major).  A part whose frames exceed ``STAGE_BYTES`` is cut at
    row boundaries into consecutive views of at most that size (one
    row at least), so no staging file grows past it."""
    out: Dict[int, List[np.ndarray]] = {}
    bounds = np.searchsorted(parts, np.arange(nparts + 2))
    for p in np.unique(parts).tolist():
        row, end = int(bounds[p]), int(bounds[p + 1])
        views = []
        while row < end:
            nxt = int(np.searchsorted(off, off[row] + STAGE_BYTES,
                                      side="right")) - 1
            nxt = min(max(nxt, row + 1), end)
            views.append(buf[int(off[row]):int(off[nxt])])
            row = nxt
        out[int(p)] = views
    return out


def edge_frames(nparts: int, etype: int, src: np.ndarray, dst: np.ndarray,
                blobs: List[bytes], val_idx: np.ndarray,
                rank: Optional[np.ndarray] = None,
                version: Optional[int] = None
                ) -> Dict[int, List[np.ndarray]]:
    """Both storage directions of the declared edges (forward under
    +etype partitioned by src, reverse under -etype partitioned by dst
    — the mutate executors' layout), grouped by partition id.

    Each part's frames come back as ONE buffer sorted in storage-key
    order, so the engine ingests it as a single ascending run (hinted
    O(1) inserts — native/kv_engine.cc neb_multi_put).  Returns
    {part: [frame buffer]}."""
    src = np.asarray(src, np.int64)
    dst = np.asarray(dst, np.int64)
    m = len(src)
    rank = np.zeros(m, np.int64) if rank is None else \
        np.asarray(rank, np.int64)
    ver = inverted_version() if version is None else version
    owner = np.concatenate([src, dst])
    other = np.concatenate([dst, src])
    vidx2 = np.concatenate([np.asarray(val_idx, np.int64)] * 2)
    n2 = 2 * m
    # storage-key order == tuple order of the sign-flipped fields.
    # Common case (non-negative vids fitting 28 bits, tiny etype ids,
    # constant rank): one packed-u64 argsort instead of a 5-key
    # lexsort — the lexsort's per-key passes dominated frame build at
    # 10^8 rows — and the sorted fields read back off the sorted key
    # by shifts, where each was a gather of its own
    packed = _packed_order(nparts, etype, owner, other, m) \
        if m and (rank == rank[0]).all() else None
    if packed is not None:
        order, parts, owner, ets, other = packed
        rank2 = np.full(n2, rank[0], np.int64)
    else:
        ets = np.concatenate([np.full(m, etype, np.int64),
                              np.full(m, -etype, np.int64)])
        rank2 = np.concatenate([rank, rank])
        parts = _parts_of(owner, nparts)
        order = np.lexsort((_flip64(other), _flip64(rank2),
                            _flip32(ets), _flip64(owner), parts))
        owner, other = owner[order], other[order]
        ets, rank2, parts = ets[order], rank2[order], parts[order]
    vidx2 = vidx2[order]
    keys = np.zeros(n2, dtype=_EDGE_KEY)
    keys["part"] = _flip32(parts)
    keys["src"] = _flip64(owner)
    keys["et"] = _flip32(ets)
    keys["rank"] = _flip64(rank2)
    keys["dst"] = _flip64(other)
    keys["ver"] = _flip64(np.full(n2, ver, np.int64))
    buf, off = _frames_varlen(keys, blobs, vidx2)
    return _split_by_part(parts, nparts, buf, off)


def _packed_order(nparts: int, etype: int, owner: np.ndarray,
                  other: np.ndarray, m: int):
    """edge_frames' order where (part, owner, etype, other) pack into
    64 bits: (the stable argsort of the packed key; part, owner, etype
    and other in that order, int64, read off the sorted key), or None
    where a vid is negative or the fields do not fit."""
    et_vals = np.unique(np.array([etype, -etype], np.int64))
    vmax = max(int(owner.max()), int(other.max()))
    vmin = min(int(owner.min()), int(other.min()))
    bw = max(vmax.bit_length(), 1)
    be = max(len(et_vals).bit_length(), 1)
    bp = max(int(nparts).bit_length() + 1, 1)
    if vmin < 0 or bp + bw + be + bw > 64:
        return None
    u = np.uint64
    # id_hash is the unsigned modulo: in 32 bits where the vids fit
    # them, which is four times as fast
    small = np.uint32 if vmax < (1 << 32) else np.uint64
    owner_u = owner.astype(u)
    key = (owner.astype(small) % small(nparts)).astype(u) + u(1)
    key <<= u(bw + be + bw)
    key |= owner_u << u(be + bw)
    et_idx = np.searchsorted(et_vals, [etype, -etype]).astype(u)
    key[:m] |= et_idx[0] << u(bw)
    key[m:] |= et_idx[1] << u(bw)
    key |= other.astype(u)
    order = np.argsort(key, kind="stable")
    key = key[order]
    mask = u((1 << bw) - 1)
    return (order,
            (key >> u(bw + be + bw)).astype(np.int64),
            ((key >> u(be + bw)) & mask).astype(np.int64),
            et_vals[((key >> u(bw)) & u((1 << be) - 1)).astype(np.int64)],
            (key & mask).astype(np.int64))


def vertex_frames(nparts: int, tag_id: int, vids: np.ndarray,
                  blobs: List[bytes], val_idx: np.ndarray,
                  version: Optional[int] = None
                  ) -> Dict[int, List[np.ndarray]]:
    """Vertex tag rows grouped by partition id."""
    vids = np.asarray(vids, np.int64)
    n = len(vids)
    val_idx = np.asarray(val_idx, np.int64)
    ver = inverted_version() if version is None else version
    parts = _parts_of(vids, nparts)
    # storage-key order per part (tag/ver constant) -> one sorted run
    # per part, same hinted-insert win as the edge path
    order = np.lexsort((_flip64(vids), parts))
    vids, parts, val_idx = vids[order], parts[order], val_idx[order]
    keys = np.zeros(n, dtype=_VERT_KEY)
    keys["part"] = _flip32(parts)
    keys["vid"] = _flip64(vids)
    keys["tag"] = _flip32(np.full(n, tag_id, np.int64))
    keys["ver"] = _flip64(np.full(n, ver, np.int64))
    buf, off = _frames_varlen(keys, blobs, val_idx)
    return _split_by_part(parts, nparts, buf, off)


def _assert_be(c: np.ndarray) -> np.ndarray:
    """Defensive byte-order check before bytes hit disk: any numpy op
    that rebuilt the dtype (concatenate!) normalizes the big-endian
    frame fields to native order and would corrupt the wire.  Raw
    uint8 buffers (_frames_varlen) carry explicit bytes already."""
    if c.dtype.fields is None:
        return c
    for fname in ("kl", "vl"):
        dt = c.dtype.fields[fname][0]
        if dt.byteorder != ">":
            be = np.dtype([(n2, c.dtype.fields[n2][0].newbyteorder(">")
                            if n2 in ("kl", "vl") else c.dtype.fields[n2][0])
                           for n2 in c.dtype.names])
            return c.astype(be)
    return c


def bulk_load(store, space_id: int, staging_dir: str,
              frame_groups: Sequence[Dict[int, List[np.ndarray]]],
              name: str = "bulk"):
    """Stage and ingest the frames ONE BUFFER AT A TIME: each per-part
    buffer is written to a snapshot-format file (named *.engineN.snap
    so NebulaStore.ingest routes it to the engine whose parts read
    it), ingested, and removed before the next one is staged.  Staging
    — the file on disk and the engine's read of it — therefore never
    holds more than ``STAGE_BYTES``: a 2^24-edge load staged as ONE
    file is 1.7 GB, past the 1 GiB file-size limit some machines run
    under.  Buffers go in ascending part order, so the engine still
    sees ascending runs (hinted inserts).  Returns the first failing
    ingest Status, else OK."""
    os.makedirs(staging_dir, exist_ok=True)
    seq = 0
    for group in frame_groups:
        for part, chunks in sorted(group.items()):
            ei = store.engine_index_of_part(space_id, part)
            if ei is None:
                raise ValueError(f"part {part} not on this store")
            for c in chunks:
                path = os.path.join(
                    staging_dir,
                    f"{name}_{space_id}.{seq}.engine{ei}.snap")
                seq += 1
                try:
                    with open(path, "wb") as f:
                        _assert_be(c).tofile(f)
                    st = store.ingest(space_id, [path])
                finally:
                    if os.path.exists(path):
                        os.remove(path)
                if not st.ok():
                    return st
    return Status.OK()
