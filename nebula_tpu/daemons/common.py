"""Shared daemon scaffolding — flagfile loading, pidfile, signals.

Capability parity with the reference's daemon wiring (GraphDaemon.cpp:
36-162: folly::init → daemonize/pidfile via ProcessUtils → WebService →
ThriftServer): each main parses flags (CLI > flagfile > defaults),
optionally writes a pidfile, installs SIGTERM/SIGINT shutdown, starts
the web service, then serves RPC until signalled.
"""
from __future__ import annotations

import argparse
import os
import signal
import sys
import threading
from typing import Callable, List, Optional

from ..common.flags import flags
from ..interface.common import HostAddr


def base_parser(name: str, default_port: int) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog=name)
    p.add_argument("--flagfile", default=None,
                   help="conf file of name=value lines (etc/*.conf)")
    p.add_argument("--local_ip", default="127.0.0.1")
    p.add_argument("--port", type=int, default=default_port)
    p.add_argument("--ws_http_port", type=int, default=0,
                   help="web service port (0 = auto)")
    p.add_argument("--pid_file", default=None)
    p.add_argument("--meta_server_addrs", default="127.0.0.1:45500",
                   help="comma-separated host:port list")
    p.add_argument("--flag", action="append", default=[],
                   metavar="name=value", help="override any defined flag")
    return p


def load_flagfile(path: Optional[str]) -> None:
    """Delegates to FlagsRegistry.load_file — values are CAST
    (int/float/bool) there, so a flag defined lazily after the flagfile
    loads (import-time defines in graph/tpu modules) still compares
    against properly-typed values."""
    if not path:
        return
    flags.load_file(path)


def apply_flag_overrides(pairs: List[str]) -> None:
    for pair in pairs:
        if "=" in pair:
            k, v = pair.split("=", 1)
            flags.define(k, v)
            flags.set(k, v, force=True)


def write_pidfile(path: Optional[str]) -> None:
    if path:
        with open(path, "w") as f:
            f.write(str(os.getpid()))


def native_built(daemon: str) -> bool:
    """Build + load the native library before serving, not during.  A
    failed build (ensure_built printed the compiler output) is fatal
    to a daemon: the Python engines are a library fallback, not a
    deployment mode."""
    from ..native import ensure_built
    if ensure_built():
        return True
    print(f"{daemon}: native library build failed (compiler output "
          f"above)", file=sys.stderr)
    return False


def parse_meta_addrs(s: str) -> List[HostAddr]:
    return [HostAddr.parse(a.strip()) for a in s.split(",") if a.strip()]


def serve_forever(cleanup: Callable[[], None]) -> None:
    """Block until SIGTERM/SIGINT, then run cleanup."""
    stop = threading.Event()

    def on_signal(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    try:
        stop.wait()
    finally:
        cleanup()
        sys.stderr.write("daemon stopped\n")
