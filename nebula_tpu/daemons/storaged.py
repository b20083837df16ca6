"""nebula-storaged — partitioned storage daemon.

Reference wiring (StorageDaemon.cpp → StorageServer.cpp:91-146):
MetaClient(heartbeat) → waitForMetadReady → SchemaManager →
NebulaStore(MetaServerBasedPartManager, compaction filter) with the
RaftexService for replication → StorageService + raft RPCs on one
address → web handlers /status /download /ingest /admin → serve.

Run: ``python -m nebula_tpu.daemons.storaged --port 44500 \
      --meta_server_addrs 127.0.0.1:45500``
"""
from __future__ import annotations

import sys

from ..cluster import CompositeHandler, StorageNode
from ..common.flags import flags
from ..interface.rpc import ClientManager, RpcServer
from ..webservice import WebService
from .common import (apply_flag_overrides, base_parser, load_flagfile,
                     native_built, parse_meta_addrs, serve_forever,
                     write_pidfile)


def resolve_store_type(cli_value):
    """CLI-vs-conf precedence for --store_type (reference gflags
    semantics): an EXPLICIT CLI value always beats the conf-file value
    (so `--store_type nebula` overrides a conf `hbase`), an unset CLI
    (None — the argparse default) falls through to the conf, and an
    unset conf falls through to "nebula"."""
    if cli_value is not None:
        return str(cli_value)
    conf_value = flags.get("store_type")
    return str(conf_value) if conf_value not in (None, "") else "nebula"


def main(argv=None) -> int:
    p = base_parser("nebula-storaged", 44500)
    p.add_argument("--data_path", default=None,
                   help="comma-separated engine data dirs")
    p.add_argument("--wal_path", default=None)
    p.add_argument("--no_raft", action="store_true",
                   help="single-replica mode (no consensus)")
    p.add_argument("--store_type", default=None,
                   help='storage service type: "nebula" (the built-in '
                        'KV engines — C++ in-memory, durable disk, or '
                        'pure-python fallback, chosen by --data_path). '
                        '"hbase" is recognized for reference-flag '
                        'parity and refused the same way the '
                        'reference refuses it (StorageServer.cpp:52)')
    args = p.parse_args(argv)
    load_flagfile(args.flagfile)
    apply_flag_overrides(args.flag)
    # reference parity: StorageServer.cpp:44-55 instantiates only
    # kStore and errors "Unknown store type" for everything else (its
    # HBase plugin is dormant); same contract here.  The gate runs
    # AFTER the flagfile/--flag overrides so a conf-file
    # `store_type=hbase` (the reference's idiom) is refused too, while
    # default=None above keeps an explicit CLI value distinguishable
    # from "unset" (resolve_store_type)
    store_type = resolve_store_type(args.store_type)
    if store_type != "nebula":
        print(f"nebula-storaged: unknown store type "
              f"'{store_type}' (only 'nebula' is served)",
              file=sys.stderr)
        return 1
    write_pidfile(args.pid_file)

    if not native_built("nebula-storaged"):
        return 1

    cm = ClientManager()
    local = f"{args.local_ip}:{args.port}"
    metas = parse_meta_addrs(args.meta_server_addrs)
    wal_root = args.wal_path
    if wal_root is None and args.data_path:
        # a data path means the operator wants durability — the raft WAL
        # must survive restarts too (it is the redo log above the disk
        # engine's flushed runs), so default it under the data dir
        import os
        wal_root = os.path.join(args.data_path.split(",")[0], "wal")
    node = StorageNode(
        local, metas, cm,
        data_paths=args.data_path.split(",") if args.data_path else None,
        use_raft=not args.no_raft, wal_root=wal_root)
    rpc = RpcServer(node.handler, host=args.local_ip,
                    port=args.port).start()
    node.start_loops()

    ws = WebService("nebula-storaged", host=args.local_ip,
                    port=args.ws_http_port).start()
    from ..storage.web import register_web_handlers
    register_web_handlers(ws, node)
    # advertise the web port to metad so /ingest-dispatch can reach us
    node.meta_client.hb_info["ws_port"] = ws.port
    st = node.meta_client.heartbeat()
    if not st.ok():
        # not fatal — the heartbeat loop keeps beating — but an operator
        # watching startup needs to know metad did not hear us yet
        sys.stderr.write(f"storaged: initial heartbeat failed: {st}\n")
    sys.stderr.write(f"storaged serving on {rpc.addr} (ws :{ws.port})\n")

    def cleanup():
        ws.stop()
        node.stop()
        rpc.stop()

    serve_forever(cleanup)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
