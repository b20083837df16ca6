"""From a profiler trace (``.xplane.pb``) to what the metrics read:
busy intervals of the device, device time per program, the operations
that took most time, and the longest idle gaps.

Layout of a TPU trace as ``jax.profiler.ProfileData`` shows it (looked
at by hand on the v5e, PR 23): one plane per chip named
``/device:TPU:<i>``; in it the line ``XLA Ops`` holds one event per
executed HLO operation and the line ``XLA Modules`` one event per
executed program, named ``<jitted function>(<fingerprint>)``.  Host
threads are lines of the plane ``/host:CPU``; a
``jax.profiler.TraceAnnotation`` is an event on its thread's line.
Event times are nanoseconds from the start of the profile.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SYNC_NAME = "bench_sync"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"[})] ([a-z][\w-]*)\(")

Interval = Tuple[float, float]


def newest_trace(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle stretches of [lo, hi] between merged busy intervals."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def program_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%fusion.39 fusion`` from the HLO text the trace gives an
    operation as its name (result name and opcode, no shapes)."""
    head = event_name.split(" = ", 1)[0]
    m = _OPCODE.search(event_name)
    return f"{head} {m.group(1)}"[:120] if m else head[:120]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, start_ns, dur_ns)],
    "modules": [...]}}, "sync_ns": start of the sync annotation or
    None} — the whole of what the reduction below needs from the file."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    sync = None
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            rec = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    rec[key] += [(ev.name, float(ev.start_ns),
                                  float(ev.duration_ns))
                                 for ev in line.events]
        elif plane.name.startswith("/host:") and sync is None:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == SYNC_NAME:
                        sync = float(ev.start_ns)
                        break
                if sync is not None:
                    break
    return {"devices": devices, "sync_ns": sync}


def reduce(planes: dict, lo_ns: Optional[float] = None,
           hi_ns: Optional[float] = None, top: int = 10) -> Optional[dict]:
    """Device busy time, per-program time and the top operations inside
    [lo_ns, hi_ns] (default: from the first to the last device event).
    Nothing on any device plane: returns None.

    busy_s is averaged over the chips; an operation that spans a window
    edge counts only its part inside."""
    devs = {k: v for k, v in planes["devices"].items()
            if v["ops"] or v["modules"]}
    if not devs:
        return None
    every = [e for v in devs.values() for e in (v["ops"] or v["modules"])]
    lo = min(s for _, s, _ in every) if lo_ns is None else lo_ns
    hi = max(s + d for _, s, d in every) if hi_ns is None else hi_ns
    busy_total, op_time, prog_time, prog_count = 0.0, {}, {}, {}
    busy_by_dev = {}
    for name, v in devs.items():
        src = v["ops"] or v["modules"]
        busy = clip(merge((s, s + d) for _, s, d in src), lo, hi)
        busy_by_dev[name] = busy
        busy_total += total(busy)
        for op, s, d in src:
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                op = op_name(op)
                op_time[op] = op_time.get(op, 0.0) + part
        for mod, s, d in v["modules"]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                p = program_name(mod)
                prog_time[p] = prog_time.get(p, 0.0) + part
                prog_count[p] = prog_count.get(p, 0) + 1
    n = len(devs)
    first = sorted(busy_by_dev)[0]
    ranked = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_total / n / 1e9,
            "program_s": {k: v / n / 1e9 for k, v in prog_time.items()},
            "program_runs": {k: v / n for k, v in prog_count.items()},
            "device_ops": [[k, v / n / 1e9] for k, v in ranked],
            "idle_gaps_ns": sorted(gaps(busy_by_dev[first], lo, hi),
                                   key=lambda g: g[0] - g[1])[:top]}


def attribute_gaps(gaps_ns: List[Interval], host_spans: List[tuple]
                   ) -> List[list]:
    """Name each idle gap by what the host was doing in it: of the host
    spans (name, start_ns, end_ns) that cover at least half of the gap
    the narrowest, else the one that covers most of it; ``idle`` where
    none overlaps."""
    out = []
    for s, e in gaps_ns:
        half, most = None, None
        for name, hs, he in host_spans:
            cover = min(e, he) - max(s, hs)
            if cover <= 0:
                continue
            if 2 * cover >= e - s and (half is None or he - hs < half[0]):
                half = (he - hs, name)
            if most is None or cover > most[0]:
                most = (cover, name)
        out.append([(half or most or (0, "idle"))[1], (e - s) / 1e9])
    return out
