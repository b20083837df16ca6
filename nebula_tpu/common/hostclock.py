"""hostclock — three clocks for the host's time, and two beats
(docs/observability.md "The device timeline").

A wall-clock difference on a host thread says how long something took
and not why.  Linux keeps, per thread, the time it RAN
(``time.thread_time()``) and the time it sat RUNNABLE on a run queue
waiting for a core (``/proc/thread-self/schedstat``, second field:
settled at every switch-in, so exact whenever the thread itself reads
it).  What is left of the wall is the time the thread was BLOCKED: on
the interpreter lock, a condition, the device.  ``stamp()`` reads all
three for the calling thread; the continuous pump stamps its phases
with it (graph/batch_dispatch.py) and a rider its own wait and assembly,
so a tick record and a ``graph.continuous`` marker say which of the
three a millisecond was.  Blocked time is never stored: it is
``wall - cpu - runq`` for whoever reads.

The file is read natively (native/beat.cc neb_runq_ns, a descriptor a
thread, kept in C) through a handle that keeps the interpreter lock: an
``os.pread`` would release it at every stamp.  Where the file is
missing or unreadable, or the library lacks the call, ``runq`` is None
in every stamp, every ``*_runq_us`` field and tag is LEFT OFF (never
written as 0), and the module says so once on stderr.

The beats are for the stalls no stamp explains (a thread that stood
still for seconds cannot say whether the whole host did): a native
pthread (native/beat.cc) that needs neither the interpreter nor the
device, and a Python daemon thread that needs the interpreter lock to
run, both sleeping BEAT_PERIOD_S and keeping count, sum and maximum of
their lateness (woke - due).  Once a second the Python beat writes one
``beat`` record into the flight recorder (common/flight.py note_beat).
A beat later than STALL_US journals one typed ``host.stall`` event:
``who`` is ``host`` when the native beat was late too (the guest was
paused or had no core), ``interpreter`` when the Python beat alone was
(something held the lock), ``device`` when the pump waited that long
for the device with both beats on time and its own thread neither
running nor runnable (note_wait).  The mean lateness of the Python beat
over the native one's is what a thread that wants to run pays for the
interpreter lock, sampled a hundred times a second whatever the pump
is doing.
"""
from __future__ import annotations

import atexit
import collections
import ctypes
import sys
import threading
import time
from typing import Dict, Optional, Tuple

from .. import native
from . import flight
from .events import journal
from .ordered_lock import OrderedLock

# (wall_s, cpu_s, runq_s) of one thread at one instant; runq_s is None
# where the machine has no schedstat
Stamp = Tuple[float, float, Optional[float]]

BEAT_PERIOD_S = 0.010
BEAT_RECORD_S = 1.0
# a beat (or a device wait of the pump's) later than this is a stall
STALL_US = 100_000
# a wait is the device's where the waiting thread ran or was runnable
# for at most this share of it
DEVICE_WAIT_HOST_SHARE = 0.2


def _no_runq(why: str) -> None:
    sys.stderr.write(
        f"[hostclock] no run-queue clock here ({why}): every "
        f"*_runq_us field and tag is left off\n")


def _probe():
    """Find, once a process, how this machine reads the time a thread
    sat runnable: native/beat.cc neb_runq_ns (the second field of
    /proc/thread-self/schedstat, from a descriptor the thread opens
    once and keeps) through a handle that KEEPS the interpreter lock
    (ctypes.PyDLL).  os.open and os.pread release it, and a stamp must
    not be a place where the pump hands the interpreter to a waiting
    rider and waits to get it back.  False where the library lacks the
    call or the file cannot be read, said once on stderr."""
    global _runq
    lib = native.lib()
    if lib is None or not hasattr(lib, "neb_runq_ns"):
        _no_runq("the native library has no neb_runq_ns")
        _runq = False
        return _runq
    fn = ctypes.PyDLL(lib._name).neb_runq_ns
    fn.restype = ctypes.c_int64
    fn.argtypes = []
    if fn() < 0:
        _no_runq("/proc/thread-self/schedstat is not readable")
        fn = False
    _runq = fn
    return _runq


# the native read, False where there is none, None until probed (the
# first stamp, or the beats' start: a start-up path has built the
# library by then, native.ensure_built)
_runq = None


def clock_name() -> str:
    """Which clocks a stamp has here: on every ``beat`` record."""
    return "schedstat" if (_runq if _runq is not None else _probe()) \
        else "thread_time"


def stamp() -> Stamp:
    """(wall_s, cpu_s, runq_s) of the calling thread, now.  The wall
    is read first: what the other two reads cost lands in the stretch
    that follows the stamp."""
    wall = time.perf_counter()
    fn = _runq if _runq is not None else _probe()
    runq = None
    if fn:
        ns = fn()
        if ns >= 0:             # < 0: this thread got no descriptor
            runq = ns * 1e-9
    return wall, time.thread_time(), runq


def split(a: Stamp, b: Stamp) -> Tuple[int, int, Optional[int]]:
    """(wall_us, cpu_us, runq_us) between two stamps of one thread."""
    runq = None if a[2] is None or b[2] is None \
        else int((b[2] - a[2]) * 1e6)
    return int((b[0] - a[0]) * 1e6), int((b[1] - a[1]) * 1e6), runq


def host_fields(prefix: str, cpu_us: int,
                runq_us: Optional[int]) -> Dict[str, int]:
    """``<prefix>cpu_us`` and, where there is one, ``<prefix>runq_us``:
    the two clocks of a tick record's phase or a span's tags."""
    out = {prefix + "cpu_us": int(cpu_us)}
    if runq_us is not None:
        out[prefix + "runq_us"] = int(runq_us)
    return out


def span_fields(prefix: str, a: Stamp, b: Stamp) -> Dict[str, int]:
    """host_fields of the stretch between two stamps."""
    _wall, cpu, runq = split(a, b)
    return host_fields(prefix, cpu, runq)


# ================================================================ beats
def classify_beats(py_late_max_us: float,
                   nat_late_max_us: Optional[float]) -> Optional[str]:
    """Who stood still in a second whose worst beats were these: the
    ``host`` when the beat that needs no interpreter was late (and
    where there is no such beat, since then nothing says it was not),
    the ``interpreter`` when the Python beat alone was, nobody when
    both were on time."""
    if nat_late_max_us is not None and nat_late_max_us > STALL_US:
        return "host"
    if py_late_max_us > STALL_US:
        return "host" if nat_late_max_us is None else "interpreter"
    return None


def classify_wait(wall_us: int, cpu_us: int, runq_us: Optional[int],
                  beats_late: bool) -> Optional[str]:
    """A wait of the pump's for the device is the ``device``'s where it
    passed STALL_US with both beats on time and the thread neither
    running nor runnable for more than DEVICE_WAIT_HOST_SHARE of it."""
    if wall_us <= STALL_US or beats_late:
        return None
    if cpu_us + (runq_us or 0) > DEVICE_WAIT_HOST_SHARE * wall_us:
        return None
    return "device"


class _Beats:
    """The process's one pair of beats."""

    def __init__(self):
        self._lock = OrderedLock("hostclock.beats")
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._native = False
        self._registered = False
        # the pump's long device waits, for the next second's close:
        # (t0_s, t1_s, phase, wall_us, cpu_us, runq_us)
        self._waits: collections.deque = collections.deque(maxlen=64)
        # (t0_s, t1_s, late) of the seconds closed lately
        self._seconds: collections.deque = collections.deque(maxlen=64)

    # ------------------------------------------------------ lifecycle
    def start(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop = False
            lib = native.lib()
            if lib is not None and hasattr(lib, "neb_beat_start"):
                self._native = lib.neb_beat_start(
                    int(BEAT_PERIOD_S * 1e6)) >= 0
            else:
                self._native = False
                sys.stderr.write(
                    "[hostclock] the native library has no "
                    "neb_beat_start: the Python beat runs alone, a "
                    "late beat cannot tell the host from the "
                    "interpreter\n")
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="host-beat")
            self._thread.start()
            if not self._registered:
                self._registered = True
                atexit.register(self.stop)

    def stop(self) -> None:
        with self._lock:
            t, self._thread = self._thread, None
            self._stop = True
            was_native, self._native = self._native, False
        if t is not None:
            t.join(timeout=1.0)
        if was_native:
            native.lib().neb_beat_stop()

    def running(self) -> bool:
        with self._lock:
            return self._thread is not None and self._thread.is_alive()

    # ----------------------------------------------------------- loop
    def _read_native(self, buf) -> Optional[Tuple[int, int, int]]:
        if not self._native:
            return None
        native.lib().neb_beat_read(buf)
        return int(buf[0]), int(buf[1]), int(buf[2])

    def _run(self) -> None:
        buf = (ctypes.c_int64 * 3)()
        nat0 = self._read_native(buf)
        n, late_sum, late_max = 0, 0.0, 0.0
        t_rec = time.perf_counter()
        while not self._stop:
            due = time.perf_counter() + BEAT_PERIOD_S
            time.sleep(BEAT_PERIOD_S)
            woke = time.perf_counter()
            late = max(0.0, woke - due)
            n += 1
            late_sum += late
            late_max = max(late_max, late)
            if woke - t_rec < BEAT_RECORD_S:
                continue
            nat = None
            nat1 = self._read_native(buf)
            if nat0 is not None and nat1 is not None:
                nat = (nat1[0] - nat0[0], (nat1[1] - nat0[1]) / 1e3,
                       nat1[2] / 1e3)
            nat0 = nat1
            self.close_second(t_rec, woke, n, late_sum * 1e6,
                              late_max * 1e6, nat)
            n, late_sum, late_max = 0, 0.0, 0.0
            t_rec = woke

    # ------------------------------------------------------- a second
    def close_second(self, t0: float, t1: float, n: int,
                     py_late_sum_us: float, py_late_max_us: float,
                     nat: Optional[Tuple[int, float, float]]) -> dict:
        """One second of beats [t0, t1] (perf_counter seconds) is over:
        write its ``beat`` record, journal who stood still if anyone
        did, and judge the pump's long device waits that ended in it.
        ``nat`` is the native beat's (n, late_sum_us, late_max_us) of
        the same second, None where there is no native beat.  Returns
        the record's fields."""
        fields = {"clock": clock_name(), "n": int(n),
                  "py_late_sum_us": int(py_late_sum_us),
                  "py_late_max_us": int(py_late_max_us)}
        nat_max = None
        if nat is not None:
            nat_max = nat[2]
            fields.update(nat_n=int(nat[0]), nat_late_sum_us=int(nat[1]),
                          nat_late_max_us=int(nat[2]))
        flight.recorder.note_beat(**fields)
        who = classify_beats(py_late_max_us, nat_max)
        if who is not None:
            journal.record(
                "host.stall",
                f"a beat of {BEAT_PERIOD_S * 1e3:.0f} ms was "
                f"{py_late_max_us / 1e3:.0f} ms late"
                + ("" if nat is not None else
                   " (no native beat: the host or the interpreter)"),
                who=who, py_late_max_us=int(py_late_max_us),
                nat_late_max_us=None if nat_max is None
                else int(nat_max))
        with self._lock:
            self._seconds.append((t0, t1, who is not None))
            waits = [w for w in self._waits if w[1] <= t1]
            later = [w for w in self._waits if w[1] > t1]
            self._waits.clear()
            self._waits.extend(later)
            seconds = list(self._seconds)
        for w0, w1, phase, wall_us, cpu_us, runq_us in waits:
            late = any(bad for s0, s1, bad in seconds
                       if s1 > w0 and s0 < w1)
            if classify_wait(wall_us, cpu_us, runq_us, late) is None:
                continue
            journal.record(
                "host.stall",
                f"the pump's {phase} stood {wall_us / 1e3:.0f} ms "
                f"with both beats on time",
                who="device", phase=phase, wall_us=int(wall_us),
                cpu_us=int(cpu_us), runq_us=runq_us)
        return fields

    def note_wait(self, phase: str, a: Stamp, b: Stamp) -> None:
        wall_us, cpu_us, runq_us = split(a, b)
        if wall_us <= STALL_US:
            return
        with self._lock:
            self._waits.append((a[0], b[0], phase, wall_us, cpu_us,
                                runq_us))


beats = _Beats()


def ensure_started() -> None:
    """Start the process's pair of beats; a second call is a no-op."""
    beats.start()


def note_wait(phase: str, a: Stamp, b: Stamp) -> None:
    """The pump waited for the device from stamp ``a`` to ``b``: where
    that passed STALL_US it is judged when the beats' second closes."""
    beats.note_wait(phase, a, b)
