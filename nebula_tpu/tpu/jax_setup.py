"""One-time JAX configuration for the device runtime.

The dominant first-touch cost on TPU is XLA compilation (seconds per
traversal-kernel shape).  JAX's persistent compilation cache removes it
for every program shape seen before — across processes and across
serving restarts — so steady-state serving never pays a compile for a
warm shape.  The runtime keeps the number of distinct program shapes
small on top of this (batch-width ladder, tables-as-arguments kernels;
see tpu/ell.py and tpu/runtime.py).

Where the cache lives is decided OUTSIDE the code: when
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing
here touches ``jax_compilation_cache_dir``; when it is not, the cache
is ``<checkout>/.jax_cache`` (git-ignored) — a fixed path, because the
directory is part of the cache key (``compilation_cache_dir()``;
tests/conftest.py spells the same default into the environment, before
jax is imported, so daemon subprocesses inherit it).

The reference has no analogue (C++ is ahead-of-time compiled); this is
TPU-native operational hygiene, like RocksDB keeping its SST block
cache warm.
"""
from __future__ import annotations

import os
import sys
import threading
from typing import Optional

from ..common.flags import flags

flags.define(
    "py_switch_interval_ms", 1.0,
    "CPython thread switch interval while device-serving (0 keeps the "
    "5 ms default).  With a hundred request threads parked on the GIL, "
    "the batch leader's launch/assembly code pays up to a full switch "
    "interval every time it re-acquires the GIL between C calls — a "
    "measured ~100x inflation of the leader's host phases.  1 ms cuts "
    "the convoy while leaving pure-Python throughput intact")

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
_done = False


def compilation_cache_dir() -> Optional[str]:
    """The directory this code must point JAX's persistent cache at, or
    None when the environment already placed it (JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` on its own — overriding it here would
    move the cache away from where the operator put it)."""
    if os.environ.get(CACHE_ENV):
        return None
    return os.path.join(_REPO_ROOT, ".jax_cache")


def device_info() -> dict:
    """What jax landed on, in the one shape every result row, log line
    and /status field stamps: platform, device_kind, device_count."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def ensure_jax_configured() -> None:
    """Idempotent: set up the persistent compilation cache before the
    first jit.  Called by every device-touching entry point."""
    global _done
    if _done:
        return
    with _lock:
        if _done:
            return
        interval = float(flags.get("py_switch_interval_ms") or 0)
        if interval > 0:
            sys.setswitchinterval(interval / 1000.0)
        # several kernels donate single-use uploads whose shape matches
        # no output (count-reduced GO returns int64[nq], BFS returns
        # depths): XLA cannot alias those and JAX warns once per
        # compile, on every backend.  The donation claim itself is
        # audited on the lowered IR (tools/lint/jaxaudit.py), so the
        # warning carries nothing the lint does not
        import warnings
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        try:
            import jax
            cache_dir = compilation_cache_dir()
            if cache_dir is not None:
                os.makedirs(cache_dir, exist_ok=True)
                jax.config.update("jax_compilation_cache_dir", cache_dir)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.2)
        except Exception as e:   # noqa: BLE001 — cache is an optimization;
            # serving must boot without it, but every restart then
            # recompiles every kernel — say so once
            sys.stderr.write(
                f"[jax_setup] persistent compilation cache disabled: "
                f"{type(e).__name__}: {e}\n")
        _done = True
