"""GflagsManager — declare process flags as remotely managed.

Capability parity with /root/reference/src/meta/GflagsManager.h:18-50:
at boot each daemon registers its managed flags into metad's config
registry (regConfig); `UPDATE CONFIGS` then round-trips through metad and
MUTABLE flags hot-update in-process via the flags registry watchers.
"""
from __future__ import annotations

from ..common.flags import flags
from ..interface.common import ConfigModule
from .client import MetaClient

# flags each module declares (reference declareGflags picks a curated set)
_MANAGED = {
    ConfigModule.GRAPH: ["session_idle_timeout_secs",
                         "session_reclaim_interval_secs",
                         "storage_backend",
                         "find_path_max_paths",
                         "tpu_filter_mode",
                         "go_dispatch_mode",
                         "query_deadline_ms"],
    ConfigModule.META: ["expired_threshold_sec"],
    ConfigModule.STORAGE: ["heartbeat_interval_secs",
                           "load_data_interval_secs",
                           "max_handlers_per_req",
                           "min_vertices_per_bucket",
                           "find_path_max_paths",
                           "raft_heartbeat_interval_s",
                           "raft_election_timeout_s",
                           "wal_buffer_size_bytes"],
}


class GflagsManager:
    def __init__(self, meta_client: MetaClient, module: ConfigModule):
        self.meta = meta_client
        self.module = module

    def declare_gflags(self) -> None:
        items = []
        for name in _MANAGED.get(self.module, []):
            info = flags.info(name)
            if info is None:
                continue
            items.append({"module": int(self.module), "name": name,
                          "mode": int(info.mode), "value": info.value})
        if items:
            self.meta.call("regConfig", {"items": items})

    def sync_from_meta(self) -> None:
        """Pull MUTABLE values from the registry into process flags (the
        reference applies these during the meta cache refresh)."""
        r = self.meta.call("listConfigs", {"module": int(self.module)})
        if not r.ok():
            return
        for item in r.value().get("items", []):
            if item.get("value") is not None:
                flags.set(item["name"], item["value"])
