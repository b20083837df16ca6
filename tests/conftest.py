"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding
paths (frontier all_to_all/psum over a Mesh) run without TPU hardware.

Mirrors the reference's strategy of in-process multi-instance harnesses
(SURVEY.md §4): our "cluster" tests also run all daemons in one process.
"""
import os
import sys

# Must happen before jax is imported anywhere.  FORCE (not setdefault):
# the suite's counts and shapes assume the 8-device virtual CPU mesh,
# and a JAX_PLATFORMS inherited from a chip machine's environment
# would put the "8 virtual device" mesh tests on the accelerator.
os.environ["JAX_PLATFORMS"] = "cpu"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Persistent compile cache: kernel-shape compiles dominate suite wall
# time; warm reruns skip them.  Same placement rule as serving
# (tpu/jax_setup.compilation_cache_dir): wherever the environment says,
# else <checkout>/.jax_cache — set through the environment so daemon
# subprocesses inherit it
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO_ROOT, ".jax_cache"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.2")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, _REPO_ROOT)

try:
    import jax
    assert jax.devices()[0].platform == "cpu", jax.devices()
except ImportError:
    pass

# Build the native library once per test session (engine default is
# "auto": C++ engine when built, MemEngine otherwise).
try:
    from nebula_tpu.native import ensure_built
    ensure_built()
except Exception:    # noqa: BLE001 — tests fall back to the Python paths
    pass

import pytest  # noqa: E402

# The multi-daemon suites exercise the real 19-thread mesh — run them
# under the lock-order watchdog (common/ordered_lock.py, the runtime
# half of nebulint's static lock-order check) and fail the test if the
# observed acquisition graph ever contains a cycle.
# (test_raftex.py is excluded: its adaptive-pipelining tests assert
# sub-millisecond replication RTTs that per-acquire bookkeeping skews)
_WATCHDOG_FILES = ("test_chaos.py", "test_cluster_replicated.py",
                   "test_metad_replicated.py", "test_proc_chaos.py")


@pytest.fixture(autouse=True)
def _lock_order_watchdog(request):
    fspath = getattr(request.node, "fspath", None)
    if fspath is None or os.path.basename(str(fspath)) not in _WATCHDOG_FILES:
        yield
        return
    from nebula_tpu.common.ordered_lock import watchdog
    was_enabled = watchdog.enabled   # NEBULA_LOCK_WATCHDOG=1 session?
    watchdog.enable()
    try:
        yield
        violations = watchdog.drain()
        assert not violations, (
            "lock-order inversions observed:\n" + "\n".join(violations))
    finally:
        # restore rather than unconditionally disable: an env-var
        # session-wide enable must survive past the first fixture use
        if not was_enabled:
            watchdog.disable()


@pytest.fixture
def stale_native(monkeypatch):
    """``stale_native("neb_x", ...)``: until the test ends, the native
    library reads as a build from before those entries were written
    (every other entry stays) — how each entry's Python fallback is
    reached anywhere."""
    def hide(*entries):
        from nebula_tpu import native
        real = native.lib()

        class Stale:
            def __getattr__(self, name):
                if name in entries:
                    raise AttributeError(name)
                return getattr(real, name)

        monkeypatch.setattr(native, "lib", lambda: Stale())
    return hide
