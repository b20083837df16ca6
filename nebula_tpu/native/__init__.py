"""ctypes loader for the native library (native/libnebula_native.so).

The native layer supplies the RocksEngine-equivalent storage core and
the batch row/key codec (reference's C++ dataman + kvstore engine,
SURVEY.md §2.6-2.7). Pure-Python fallbacks exist for every entry point —
``lib()`` returning None simply means slower paths.

Build: ``make -C native`` (repo root).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import Optional

_LIB: Optional[ctypes.CDLL] = None
_TRIED = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# NEBULA_NATIVE_SO overrides the artifact (e.g. the ASAN build —
# native/Makefile `make asan`)
_SO_PATH = os.environ.get("NEBULA_NATIVE_SO") or os.path.join(
    _REPO_ROOT, "native", "libnebula_native.so")


def _sig(fn, restype, argtypes):
    fn.restype = restype
    fn.argtypes = argtypes
    return fn


def ensure_built() -> bool:
    """Build the native library from the tracked ``native/*.cc``
    sources (``make`` — a no-op when the artifact is newer than them),
    then load it.  Call this from process STARTUP paths only (daemon
    mains, test session setup, CLI tools) — never from a serving
    thread: the compile takes seconds and lib() itself deliberately
    never builds.

    A failed build is reported on stderr WITH the compiler output and
    returns False: daemon mains treat that as fatal, library callers
    keep the pure-Python paths."""
    global _TRIED
    makefile = os.path.join(_REPO_ROOT, "native", "Makefile")
    if "NEBULA_NATIVE_SO" not in os.environ and os.path.exists(makefile):
        cmd = ["make", "-C", os.path.dirname(makefile)]
        if os.path.exists(_SO_PATH):
            try:
                ctypes.CDLL(_SO_PATH)
            except OSError:
                # the artifact exists but won't load here (built by
                # another toolchain — glibc symbol versions): mtime
                # says up-to-date, it isn't
                cmd.insert(1, "-B")
        try:
            subprocess.run(cmd, capture_output=True, timeout=120,
                           check=True, text=True)
        except (OSError, subprocess.SubprocessError) as e:
            sys.stderr.write(
                f"[native] `{' '.join(cmd)}` failed — the C++ engine, "
                f"codec and ELL builder fall back to Python: {e}\n"
                f"{getattr(e, 'stdout', '') or ''}"
                f"{getattr(e, 'stderr', '') or ''}")
            return False
        _TRIED = False                   # allow lib() to retry the load
    return lib() is not None


def lib() -> Optional[ctypes.CDLL]:
    """Load (once) and return the native library, or None if the .so is
    absent (build it via ensure_built / ``make -C native``)."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    if not os.path.exists(_SO_PATH):
        return None
    try:
        L = ctypes.CDLL(_SO_PATH)
    except OSError:
        return None

    u8p = ctypes.POINTER(ctypes.c_uint8)
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    vp = ctypes.c_void_p

    # engine
    _sig(L.neb_engine_create, vp, [])
    _sig(L.neb_engine_destroy, None, [vp])
    _sig(L.neb_buf_free, None, [u8p])
    _sig(L.neb_put, ctypes.c_int,
         [vp, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
          ctypes.c_uint64])
    _sig(L.neb_multi_put, ctypes.c_int, [vp, ctypes.c_char_p,
                                         ctypes.c_uint64])
    _sig(L.neb_get, ctypes.c_int64,
         [vp, ctypes.c_char_p, ctypes.c_uint64, ctypes.POINTER(u8p)])
    _sig(L.neb_remove, ctypes.c_int, [vp, ctypes.c_char_p, ctypes.c_uint64])
    _sig(L.neb_multi_remove, ctypes.c_int, [vp, ctypes.c_char_p,
                                            ctypes.c_uint64])
    _sig(L.neb_remove_range, ctypes.c_int64,
         [vp, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
          ctypes.c_uint64])
    _sig(L.neb_remove_prefix, ctypes.c_int64,
         [vp, ctypes.c_char_p, ctypes.c_uint64])
    _sig(L.neb_scan_prefix, u8p,
         [vp, ctypes.c_char_p, ctypes.c_uint64, u64p, u64p])
    _sig(L.neb_scan_range, u8p,
         [vp, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
          ctypes.c_uint64, u64p, u64p])
    # round-4 addition — guarded like ell_build below (stale .so)
    if hasattr(L, "neb_scan_multi_prefix"):
        _sig(L.neb_scan_multi_prefix, u8p,
             [vp, u8p, u64p, u64p, ctypes.c_int64, u64p, u64p])
    _sig(L.neb_total_keys, ctypes.c_int64, [vp])
    _sig(L.neb_flush, ctypes.c_int, [vp, ctypes.c_char_p])
    _sig(L.neb_ingest, ctypes.c_int, [vp, ctypes.c_char_p])

    # codec
    _sig(L.neb_decode_field, ctypes.c_int64,
         [u8p, u64p, u64p, ctypes.c_int64, u8p, ctypes.c_int32,
          ctypes.c_int32, ctypes.c_uint64, i64p, f64p, u64p, u64p, u8p])
    _sig(L.neb_parse_keys, None,
         [u8p, u64p, u64p, ctypes.c_int64, u8p, i32p, i64p, i32p, i64p,
          i64p, i64p])
    _sig(L.neb_split_frames, ctypes.c_int64,
         [u8p, ctypes.c_uint64, u64p, u64p, u64p, u64p, ctypes.c_int64])
    # round-3 additions — guarded like ell_build below (stale .so)
    if hasattr(L, "neb_split_rowset"):
        _sig(L.neb_split_rowset, ctypes.c_int64,
             [u8p, ctypes.c_uint64, u64p, u64p, ctypes.c_int64])
        _sig(L.neb_encode_pseudo_rowset, ctypes.c_int64,
             [i64p, i64p, ctypes.c_int64, ctypes.c_uint64,
              ctypes.c_int64, u8p, ctypes.c_int64])

    # ELL slot-table builder (tpu/ell.py fast path). Guarded: a stale
    # .so built before ell_build.cc filled one table a direction must
    # degrade this feature, not break the whole native layer with
    # AttributeError
    if hasattr(L, "ell_fill_split"):
        _sig(L.ell_build, ctypes.c_int64,
             [i32p, i32p, i32p, ctypes.c_int64, ctypes.c_int64,
              ctypes.c_int64, ctypes.c_int64])
        _sig(L.ell_counts, ctypes.c_int64, [ctypes.c_int64, i64p])
        _sig(L.ell_bucket_dims, ctypes.c_int64, [ctypes.c_int64, i64p])
        _sig(L.ell_fill_split, ctypes.c_int64,
             [ctypes.c_int64, i32p, i32p, i32p, ctypes.c_int64,
              i32p, i32p, i32p, i32p, vp, i32p, vp, ctypes.c_int64])
        _sig(L.ell_free, None, [ctypes.c_int64])

    # run gather (tpu/runtime.py _EdgeRuns). Guarded like ell_build
    if hasattr(L, "neb_gather_runs"):
        _sig(L.neb_gather_runs, None,
             [vp, ctypes.c_int64, vp, vp, ctypes.c_int64, vp])
    # one double column against a constant over candidate runs
    # (tpu/runtime.py _EdgeRuns.keep_f64). Guarded like ell_build
    if hasattr(L, "neb_filter_runs_f64"):
        _sig(L.neb_filter_runs_f64, ctypes.c_int64,
             [vp, vp, vp, vp, ctypes.c_int64, ctypes.c_int32,
              ctypes.c_double, vp])

    # a leave cohort's bitmaps to its leavers' id arrays (tpu/runtime.py
    # _unpack_lanes). Guarded like ell_build. The count sizes the pass's
    # output and is microseconds of work: it is called WITH the
    # interpreter lock (PYFUNCTYPE), because getting the lock back
    # beside working riders costs more than the count does
    if hasattr(L, "neb_unpack_lanes"):
        L.neb_count_lanes = ctypes.PYFUNCTYPE(
            ctypes.c_int64, vp, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, vp)(("neb_count_lanes", L))
        _sig(L.neb_unpack_lanes, ctypes.c_int64,
             [vp, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
              ctypes.c_int64, vp, vp, vp])

    # the native beat (common/hostclock.py). Guarded like ell_build
    if hasattr(L, "neb_beat_start"):
        _sig(L.neb_beat_start, ctypes.c_int, [ctypes.c_int64])
        _sig(L.neb_beat_read, None, [i64p])
        _sig(L.neb_beat_stop, None, [])

    _LIB = L
    return _LIB


def available() -> bool:
    return lib() is not None
