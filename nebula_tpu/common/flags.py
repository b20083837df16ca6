"""Process-wide flag registry — the gflags equivalent.

Capability parity with the reference's layered config system (SURVEY.md
§5.6): (1) per-daemon flags with defaults, loadable from a conf file;
(2) flags declared as remotely-managed register into metad's config
registry (GflagsManager) and MUTABLE ones hot-update via the meta cache
refresh; (3) runtime get/set over the web service (/flags).
"""
from __future__ import annotations

import json
from typing import Any, Callable, Dict, List, Optional

from ..interface.common import ConfigMode, ConfigModule
from .ordered_lock import OrderedLock


class FlagInfo:
    __slots__ = ("name", "default", "value", "help", "mode", "module", "watchers")

    def __init__(self, name: str, default: Any, help_: str, mode: ConfigMode,
                 module: ConfigModule):
        self.name = name
        self.default = default
        self.value = default
        self.help = help_
        self.mode = mode
        self.module = module
        self.watchers: List[Callable[[Any], None]] = []


class FlagsRegistry:
    def __init__(self):
        self._flags: Dict[str, FlagInfo] = {}
        self._lock = OrderedLock("flags.registry")

    def define(self, name: str, default: Any, help_: str = "",
               mode: ConfigMode = ConfigMode.MUTABLE,
               module: ConfigModule = ConfigModule.ALL) -> None:
        with self._lock:
            if name not in self._flags:
                self._flags[name] = FlagInfo(name, default, help_, mode, module)

    def get(self, name: str, default: Any = None) -> Any:
        # lock-free read path: hot loops (raft tick, storage collect)
        # read flags per call; a torn value is impossible (one attribute
        # load) and staleness across one read is fine
        f = self._flags.get(name)
        return f.value if f is not None else default

    def set(self, name: str, value: Any, force: bool = False) -> bool:
        with self._lock:
            f = self._flags.get(name)
            if f is None:
                return False
            if f.mode == ConfigMode.IMMUTABLE and not force:
                return False
            # coerce to the default's type when possible
            if f.default is not None \
                    and not isinstance(value, type(f.default)):
                try:
                    if isinstance(f.default, bool):
                        value = str(value).lower() in ("1", "true", "yes")
                    else:
                        value = type(f.default)(value)
                except (TypeError, ValueError):
                    return False
            f.value = value
            watchers = list(f.watchers)
        # watchers run OUTSIDE the registry lock: a callback that reads
        # or sets another flag must not deadlock the registry
        for w in watchers:
            w(value)
        return True

    def watch(self, name: str, fn: Callable[[Any], None]) -> None:
        with self._lock:
            f = self._flags.get(name)
            if f is not None:
                f.watchers.append(fn)

    def names(self, module: Optional[ConfigModule] = None) -> List[str]:
        # snapshot under the lock: lazy subsystem imports define() flags
        # while an operator polls /flags (dict-changed-size otherwise)
        with self._lock:
            items = list(self._flags.items())
        return sorted(n for n, f in items
                      if module in (None, ConfigModule.ALL) or
                      f.module in (module, ConfigModule.ALL))

    def info(self, name: str) -> Optional[FlagInfo]:
        return self._flags.get(name)

    def dump(self) -> Dict[str, Any]:
        with self._lock:
            items = sorted(self._flags.items())
        return {n: f.value for n, f in items}

    def load_file(self, path: str) -> None:
        """Conf file: json object or ``--name=value`` lines."""
        with open(path) as fh:
            text = fh.read()
        try:
            for k, v in json.loads(text).items():
                self.define(k, v)
                self.set(k, v, force=True)
            return
        except json.JSONDecodeError:
            pass
        for line in text.splitlines():
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if line.startswith("--"):
                line = line[2:]
            if "=" in line:
                k, v = line.split("=", 1)
                for cast in (int, float):
                    try:
                        v = cast(v)
                        break
                    except ValueError:
                        continue
                else:
                    if v in ("true", "false"):
                        v = v == "true"
                self.define(k, v)
                self.set(k, v, force=True)


flags = FlagsRegistry()

# framework defaults (reference GraphFlags.cpp:10-29, MetaClient.cpp:13-14)
flags.define("session_idle_timeout_secs", 600, "session reclaim timeout")
flags.define("session_reclaim_interval_secs", 10, "reclaim cadence")
flags.define("heartbeat_interval_secs", 10, "storaged->metad heartbeat")
flags.define("load_data_interval_secs", 120, "meta cache refresh cadence")
flags.define("expired_threshold_sec", 10 * 60, "host liveness TTL")
flags.define("max_handlers_per_req", 10, "per-request bucket fan-out")
flags.define("min_vertices_per_bucket", 3, "min vertices per bucket")
flags.define("storage_backend", "auto", "storage traversal backend: cpu|tpu|auto")
flags.define("find_path_max_paths", 1000,
             "most rows one FIND PATH answers; over it the first that many "
             "under the order docs/STATUS.md states (FIND PATH).  Read by "
             "graphd's executor and by the device runtime's path walk, "
             "so a storaged that serves deviceFindPath takes the same value")
flags.define("storage_engine", "auto",
             "kv engine: native (C++ kv_engine.cc) | mem | auto")
flags.define("store_type", None,
             "storage service type (reference StorageServer.cpp:44-55 "
             "parity; only 'nebula' is served) — set from conf files, "
             "overridden by the storaged --store_type CLI flag")
# NOTE: the raft timing knobs live where raftex defines them
# (raft_heartbeat_interval_s / raft_election_timeout_s in
# raftex/raft_part.py) — the old *_ms duplicates here were dead
# (flag-registry check) and are gone; wal_buffer_size_bytes is now read
# by kvstore/wal.py instead of a hardcoded default
flags.define("wal_buffer_size_bytes", 256 * 1024, "wal flush buffer")

# ---- robustness / fault injection (interface/faults.py) -------------
flags.define("fault_injection_rules", "",
             "JSON list of wire-fault rules (docs/fault_injection.md); "
             "empty disables injection")
flags.define("fault_injection_seed", 0,
             "seed for the fault injector's probability draws")
# storage client retry policy (storage/client.py collect)
flags.define("storage_client_retry_backoff_ms", 20,
             "base backoff between scatter-gather retry passes")
flags.define("storage_client_retry_backoff_max_ms", 1000,
             "cap on one storage-client backoff sleep")
flags.define("storage_client_request_deadline_ms", 15000,
             "overall per-request budget for one scatter-gather collect "
             "(passes + backoff); 0 disables the deadline")
# meta client retry policy (meta/client.py _call)
flags.define("meta_client_retry_backoff_ms", 100,
             "base backoff between whole-peer-set retry passes")
flags.define("meta_client_retry_backoff_max_ms", 2000,
             "cap on one meta-client backoff sleep")
flags.define("meta_client_max_hint_chase", 3,
             "max not-a-leader hints chased inside one peer pass "
             "(bounds adversarial/looping hint chains)")
# UPTO negative-cache policy (storage/device.py RemoteDeviceRuntime)
flags.define("upto_decline_ttl_s", 300.0,
             "seconds an UPTO decline is remembered per space before "
             "the device host is probed again (restart/upgrade recovery)")
