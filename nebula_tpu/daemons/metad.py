"""nebula-metad — catalog / cluster-manager daemon.

Reference wiring (MetaDaemon.cpp:58-242): kvstore over a single
space(0)/part(0) whose raft peers are all metad addrs → cluster id →
web handlers → MetaServiceHandler → serve. Replicated metad uses the
same raftex as storage (SURVEY.md §2.8); single-instance runs
single-replica.

Run: ``python -m nebula_tpu.daemons.metad --port 45500``
"""
from __future__ import annotations

import sys

from ..interface.rpc import ClientManager, RpcServer
from ..kvstore.partman import MemPartManager
from ..kvstore.store import KVOptions, NebulaStore
from ..meta.service import META_PART, META_SPACE, MetaService
from ..webservice import WebService
from .common import (apply_flag_overrides, base_parser, load_flagfile,
                     native_built, parse_meta_addrs, serve_forever,
                     write_pidfile)


def build(args, cm=None):
    import os
    cm = cm or ClientManager()
    local = f"{args.local_ip}:{args.port}"
    metas = [str(a) for a in parse_meta_addrs(args.meta_server_addrs)]
    if local not in metas and len(metas) <= 1:
        # a lone metad whose --meta_server_addrs was left at the default
        # while --port moved: the catalog raft group is just us — a peer
        # list without the local address would never elect
        metas = [local]
    data_path = getattr(args, "data_path", None)
    wal_path = getattr(args, "wal_path", None)
    if wal_path is None and data_path:
        wal_path = os.path.join(data_path, "wal")
    raft_service = None
    if len(metas) > 1 or wal_path:
        # replicated catalog: one raft group over all metad peers.  A
        # single metad with a wal/data path still runs raft (quorum 1) —
        # the WAL is what replays acked DDL after a crash, exactly the
        # reference's single-metad shape (MetaDaemon.cpp:58-78)
        from ..raftex import RaftexService
        raft_service = RaftexService(local, cm, wal_root=wal_path)
    pm = MemPartManager()
    kv = NebulaStore(KVOptions(part_man=pm, snapshot_whole_engine=True,
                               data_paths=[data_path] if data_path else []),
                     raft_service=raft_service)
    pm.add_part(META_SPACE, META_PART, peers=metas if raft_service else None)
    # crash-recovery observability: a metad restart over a durable
    # catalog journals node.recovered (kvstore/store.py)
    from ..kvstore.store import journal_recovered_parts
    journal_recovered_parts(kv, local)
    service = MetaService(kv)
    service.wire_balancer(cm)
    # peer metads dial the SAME address for MetaService and raft RPCs —
    # serve both from one handler (cluster.CompositeHandler)
    if raft_service is not None:
        from ..cluster import CompositeHandler
        handler = CompositeHandler(service, raft_service)
    else:
        handler = service
    return service, cm, handler, raft_service


def main(argv=None) -> int:
    p = base_parser("nebula-metad", 45500)
    p.add_argument("--wal_path", default=None)
    p.add_argument("--data_path", default=None,
                   help="catalog data dir (enables the persistent "
                        "engine + durable WAL)")
    args = p.parse_args(argv)
    load_flagfile(args.flagfile)
    apply_flag_overrides(args.flag)
    write_pidfile(args.pid_file)

    if not native_built("nebula-metad"):
        return 1

    service, cm, handler, raft_service = build(args)
    rpc = RpcServer(handler, host=args.local_ip, port=args.port).start()
    ws = WebService("nebula-metad", host=args.local_ip,
                    port=args.ws_http_port).start()
    ws.register_handler(
        "/balance", lambda q, b: (200, service.rpc_balance(
            {k: v for k, v in q.items() if not k.startswith("__")})))
    # metad's /events serves the CLUSTER aggregation (heartbeat-absorbed
    # events merged with its own journal) instead of the local-only
    # builtin every other daemon keeps
    ws.register_handler(
        "/events", lambda q, b: (200, service.rpc_listEvents(
            {"limit": q.get("limit", 200)})))

    def _catalog_serving():
        from ..meta.service import META_PART, META_SPACE
        p = service.kv.part(META_SPACE, META_PART)
        if p is None:
            return False, "catalog part missing"
        if p.raft is not None and p.leader() is None:
            return False, "catalog raft group has no leader yet"
        return True, "catalog serving"

    ws.register_health_check("catalog", _catalog_serving)
    from ..meta.http_dispatch import register_dispatch_handlers
    register_dispatch_handlers(ws, service)
    sys.stderr.write(f"metad serving on {rpc.addr} (ws :{ws.port})\n")

    def cleanup():
        ws.stop()
        rpc.stop()
        if raft_service is not None:
            raft_service.stop()

    serve_forever(cleanup)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
