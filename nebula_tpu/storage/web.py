"""storaged web handlers — /status is WebService-builtin (extended
here with the device runtime's platform / device_kind / count); this
module adds the bulk-load pair the reference serves from storaged's proxygen
server (StorageHttpDownloadHandler / StorageHttpIngestHandler,
StorageServer.cpp:60-89):

  GET /download?space=N&url=file:///dir   stage bulk-load files locally
  GET /ingest?space=N[&path=a,b]          ingest staged (or explicit)
                                          snapshot files into the space
  GET /admin                              raft part status

The WebService builtins ride along on every storaged too — notably
GET /timeline (the device flight recorder, common/flight.py): this
host's absorb windows and peer-delta serves land there, so a slow
continuous tick on a graphd can be cross-read against the storaged
that fed it (docs/observability.md "The device timeline").

The reference's /download shells out to ``hdfs dfs -get``
(/root/reference/src/common/hdfs/HdfsCommandHelper.h); we do the same
for ``hdfs://`` urls when an ``hdfs`` binary is on PATH (tests fake one,
like the reference's MockHdfsHelper), and additionally accept
``file://`` source directories (shared filesystem — the common on-prem
layout) and plain local paths.  Everything else — staging dir per
space, separate download/ingest phases, meta-side fan-out
(meta/http_dispatch.py) — matches the reference flow.
"""
from __future__ import annotations

import os
import shutil
import subprocess
from typing import Optional
from urllib.parse import urlparse


def _staging_dir(node, space_id: int) -> str:
    root = (node.data_paths[0] if getattr(node, "data_paths", None)
            else os.path.join(os.path.expanduser("~"), ".nebula_tpu"))
    # node-qualified: co-located storaged sharing a data root must not
    # share staging (each would re-ingest the others' files)
    node_tag = str(getattr(node, "host", "local")).replace(":", "_")
    d = os.path.join(root, "download", node_tag, f"space_{space_id}")
    os.makedirs(d, exist_ok=True)
    return d


def _hdfs_download(node, space_id: int, url: str) -> dict:
    """``hdfs dfs -get <url>/* <staging>`` — the reference's transfer
    verb (HdfsCommandHelper::copyToLocal).  Requires an ``hdfs`` binary
    on PATH (a real Hadoop client, or a test shim)."""
    if shutil.which("hdfs") is None:
        return {"ok": False,
                "error": "hdfs:// url but no `hdfs` binary on PATH"}
    dest = _staging_dir(node, space_id)
    before = set(os.listdir(dest))
    try:
        proc = subprocess.run(
            ["hdfs", "dfs", "-get", url.rstrip("/") + "/*", dest],
            capture_output=True, text=True, timeout=600)
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "hdfs dfs -get timed out"}
    if proc.returncode != 0:
        return {"ok": False,
                "error": f"hdfs dfs -get failed: {proc.stderr.strip()}"}
    staged = sorted(set(os.listdir(dest)) - before) or sorted(
        os.listdir(dest))
    return {"ok": True, "staged": staged, "dest": dest}


def _download(node, space_id: int, url: str) -> dict:
    p = urlparse(url)
    if p.scheme == "hdfs":
        return _hdfs_download(node, space_id, url)
    if p.scheme not in ("", "file"):
        return {"ok": False,
                "error": f"unsupported url scheme {p.scheme!r} "
                         "(hdfs://, file:// or local path)"}
    src = p.path if p.scheme == "file" else url
    if not os.path.isdir(src):
        return {"ok": False, "error": f"no such directory {src}"}
    dest = _staging_dir(node, space_id)
    copied = []
    for name in sorted(os.listdir(src)):
        full = os.path.join(src, name)
        if os.path.isfile(full):
            shutil.copy2(full, os.path.join(dest, name))
            copied.append(name)
    return {"ok": True, "staged": copied, "dest": dest}


def _ingest(node, space_id: int, path: Optional[str]) -> dict:
    staged = path is None
    if path:
        files = path.split(",")
    else:
        dest = _staging_dir(node, space_id)
        files = [os.path.join(dest, n) for n in sorted(os.listdir(dest))
                 if os.path.isfile(os.path.join(dest, n))]
    if not files:
        return {"ok": False, "error": "nothing staged to ingest"}
    st = node.kv.ingest(space_id, files)
    if st.ok() and staged:
        # consume the staging area — a later dispatch must not silently
        # re-ingest superseded snapshots
        for f in files:
            try:
                os.remove(f)
            except OSError:
                pass
    return {"ok": st.ok(), "files": len(files),
            **({} if st.ok() else {"error": st.msg})}


def _meta_reachable(node):
    """Healthz: one live heartbeat round-trip — metad down, partitioned
    (or fault-injected away) flips this red within one probe."""
    st = node.meta_client.heartbeat()
    return st.ok(), "heartbeat ok" if st.ok() else st.to_string()


def _breaker_health(node):
    """Healthz: no device circuit breaker OPEN.  Queries still answer
    (CPU fallback) while one is open, but the node is degraded — a 503
    here lets load balancers prefer device-healthy peers, and the check
    detail names the open (space, kernel-class) cells so an operator
    sees WHAT tripped without scraping /metrics (docs/durability.md)."""
    cells = node.service.breaker_snapshot()
    opened = [f"space {k[0]}/{k[1]}: {reason or 'repeated failures'}"
              for k, state, reason in cells if state == "open"]
    if opened:
        return False, "device breaker open — " + "; ".join(sorted(opened))
    return True, f"{len(cells)} breaker cell(s), none open"


def _peer_mirror_health(node):
    """Healthz: no subscribed peer-delta stream wedged.  A cursor that
    has not advanced past a peer's published version for more than two
    poll windows (heartbeat_interval_secs each) means the mirror is
    serving stale rows and every absorb window is declining — a
    503-worthy degradation operators (and the failover ladder, via the
    degraded /healthz) should see BEFORE queries do
    (docs/durability.md "The peer-delta cursor protocol")."""
    from ..common.flags import flags
    window_s = float(flags.get("heartbeat_interval_secs", 10) or 10)
    stalls = node.service.peer_mirror_stalls()
    wedged = [f"space {sid} peer {host}: {reason} for {s:.1f}s"
              for sid, host, s, reason in stalls if s > 2 * window_s]
    if wedged:
        return False, "peer delta stream wedged — " + "; ".join(
            sorted(wedged))
    return True, f"{len(stalls)} stream(s) catching up, none wedged"


def _parts_serving(node):
    """Healthz: every hosted partition exists and (when replicated)
    knows a raft leader — a part mid-election or mid-snapshot can't
    serve reads/writes yet."""
    total = unserved = 0
    for sid in list(node.kv.spaces):
        for pid in node.kv.part_ids(sid):
            total += 1
            part = node.kv.part(sid, pid)
            if part is None or (part.raft is not None
                                and part.leader() is None):
                unserved += 1
    return unserved == 0, f"{total - unserved}/{total} parts serving"


def register_web_handlers(ws, node) -> None:
    """Wire the storaged handlers onto a WebService (shared by
    daemons/storaged.py and the in-process test clusters)."""
    ws.register_handler(
        "/admin", lambda q, b: (200, node.service.rpc_raftPartStatus({})))
    ws.register_handler(
        "/download", lambda q, b: (200, _download(
            node, int(q.get("space", 0)), q.get("url", ""))))
    ws.register_handler(
        "/ingest", lambda q, b: (200, _ingest(
            node, int(q.get("space", 0)), q.get("path"))))
    # readiness (/healthz): meta reachable, partitions serving, device
    # runtime importable (docs/observability.md "Metrics & events")
    ws.register_health_check("meta", lambda: _meta_reachable(node))
    ws.register_health_check("parts", lambda: _parts_serving(node))
    ws.register_health_check("device", node.service.device_ready)
    # /status additionally says WHERE this storaged's device runtime
    # landed (null until the first device request builds it)
    ws.register_status_field("device", node.service.device_info)
    # degradation signal: 503 while a device circuit breaker is OPEN
    # (queries keep answering via the CPU fallback — docs/durability.md)
    ws.register_health_check("device_breaker",
                             lambda: _breaker_health(node))
    # degradation signal: 503 while a subscribed peer-delta stream is
    # wedged (cursor not advancing past a peer's published version for
    # > 2 poll windows) — the mirror is stale-serving and rebuilding
    ws.register_health_check("peer_mirror",
                             lambda: _peer_mirror_health(node))
