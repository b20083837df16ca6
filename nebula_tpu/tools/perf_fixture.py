"""Perf-tool fixtures: the perf space schema + an in-process cluster.

The reference perf tool assumes an operator-prepared space; we provision
it programmatically (space "perf", tag item(idx int), edge rel(w int))
so the tool is runnable out of the box either in-process or against a
live cluster (--meta_server_addrs).
"""
from __future__ import annotations

from ..codec.rows import encode_row
from ..interface.common import (ColumnDef, Schema, SupportedType,
                                schema_to_wire)

ITEM = Schema(columns=[ColumnDef("idx", SupportedType.INT)])
REL = Schema(columns=[ColumnDef("w", SupportedType.INT)])


def ensure_perf_space(meta_client):
    """Create (or reuse) the perf space; returns (sid, tag_id, etype)."""
    r = meta_client.create_space("perf", partition_num=6)
    if r.ok():
        sid = r.value()
        for s in (meta_client.create_tag_schema(sid, "item",
                                                schema_to_wire(ITEM)),
                  meta_client.create_edge_schema(sid, "rel",
                                                 schema_to_wire(REL))):
            if not s.ok():
                raise RuntimeError(f"perf fixture schema DDL failed: "
                                   f"{s.status}")
    else:
        sid = meta_client.get_space_id_by_name("perf").value()
    meta_client.load_data()
    tag_id = meta_client.get_tag_id(sid, "item").value()
    etype = meta_client.get_edge_type(sid, "rel").value()
    return sid, tag_id, etype


def build_inprocess():
    from ..cluster import LocalCluster
    cluster = LocalCluster(num_storage=1)
    sid, tag_id, etype = ensure_perf_space(cluster.graph_meta_client)
    cluster.refresh_all()
    return cluster, cluster.storage_client, sid, tag_id, etype


def vertex(vid: int, tag_id: int, idx: int) -> dict:
    return {"id": vid, "tags": [[tag_id, encode_row(ITEM, {"idx": idx})]]}


def edge(src: int, etype: int, dst: int, w: int) -> dict:
    return {"src": src, "etype": etype, "rank": 0, "dst": dst,
            "props": encode_row(REL, {"w": w})}


def probe_device_roundtrip_ms(reps: int = 5) -> float:
    """Measured host->device->host round trip (one jitted execute +
    fetch of a tiny array, averaged over ``reps``).  The serving path's
    per-batch floor is one execute + one fetch, so bench outputs record
    it beside the rows it bounds — the ONE probe bench.py and
    bench_suite share, so their numbers stay comparable."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.int32)
    np.asarray(f(x))                     # warm the compile
    t0 = time.perf_counter()
    for _ in range(reps):
        np.asarray(f(x))
    return (time.perf_counter() - t0) / reps * 1000
