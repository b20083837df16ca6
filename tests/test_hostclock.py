"""common/hostclock.py: the three clocks of a stamp, and the two beats
(docs/observability.md "The device timeline").

Nothing here rests on a sleep being punctual: the clocks are injected
or compared with themselves, the beats' seconds are hand-made, and the
threads that really run are waited for until they have made progress.
"""
import ctypes
import os
import threading
import time

import pytest

from nebula_tpu import native
from nebula_tpu.common import flight, hostclock
from nebula_tpu.common.events import journal


def _stalls(since_us=0):
    return [e for e in journal.dump(limit=4096)
            if e["kind"] == "host.stall" and e["time_us"] >= since_us]


@pytest.fixture
def quiet_beats():
    """The process's own beats off, so a test's hand-made seconds and
    the native beat it starts itself are the only ones."""
    was = hostclock.beats.running()
    hostclock.beats.stop()
    yield
    if was:
        hostclock.beats.start()


# ============================================================ the clocks
class TestStamp:
    def test_split_is_the_three_differences_in_micros(self):
        a = (10.0, 1.0, 0.25)
        b = (10.5, 1.125, 0.3125)
        assert hostclock.split(a, b) == (500_000, 125_000, 62_500)
        assert hostclock.split(a, a) == (0, 0, 0)

    def test_stamp_reads_the_injected_clocks(self, monkeypatch):
        monkeypatch.setattr(time, "perf_counter", lambda: 77.0)
        monkeypatch.setattr(time, "thread_time", lambda: 3.5)
        monkeypatch.setattr(hostclock, "_runq", lambda: 2_000_000_000)
        assert hostclock.stamp() == (77.0, 3.5, 2.0)
        monkeypatch.setattr(time, "perf_counter", lambda: 77.25)
        monkeypatch.setattr(time, "thread_time", lambda: 3.75)
        monkeypatch.setattr(hostclock, "_runq", lambda: 2_000_500_000)
        assert hostclock.split((77.0, 3.5, 2.0), hostclock.stamp()) \
            == (250_000, 250_000, 500)

    def test_a_busy_thread_runs_for_what_it_takes(self):
        a = hostclock.stamp()
        x = 0
        while hostclock.split(a, hostclock.stamp())[1] < 20_000:
            x += 1                      # until 20 ms of thread time
        wall, cpu, runq = hostclock.split(a, hostclock.stamp())
        assert cpu >= 20_000 and wall >= cpu - 1_000
        if runq is not None:            # the machine has schedstat
            assert runq >= 0
            assert hostclock.clock_name() == "schedstat"

    def test_without_schedstat_runq_is_left_off_not_zero(
            self, monkeypatch):
        monkeypatch.setattr(hostclock, "_runq", False)
        a = hostclock.stamp()
        b = hostclock.stamp()
        assert a[2] is None and b[2] is None
        assert hostclock.split(a, b)[2] is None
        assert hostclock.clock_name() == "thread_time"
        got = hostclock.span_fields("unpack_", a, b)
        assert set(got) == {"unpack_cpu_us"}
        assert hostclock.host_fields("", 7, None) == {"cpu_us": 7}
        assert hostclock.host_fields("x_", 7, 0) \
            == {"x_cpu_us": 7, "x_runq_us": 0}
        # one stamp with the clock and one without: no reading
        assert hostclock.split((1.0, 1.0, 0.5), (2.0, 1.5, None))[2] \
            is None

    def test_a_thread_that_got_no_descriptor_leaves_runq_off(
            self, monkeypatch):
        monkeypatch.setattr(hostclock, "_runq", lambda: -1)
        assert hostclock.stamp()[2] is None

    def test_probe_says_once_why_there_is_no_runq_clock(
            self, monkeypatch, capsys):
        class Bare:                     # a library from before beat.cc
            pass
        monkeypatch.setattr(native, "lib", lambda: Bare())
        monkeypatch.setattr(hostclock, "_runq", None)
        assert hostclock.stamp()[2] is None
        assert hostclock.stamp()[2] is None
        assert hostclock.clock_name() == "thread_time"
        err = capsys.readouterr().err
        assert err.count("[hostclock] no run-queue clock") == 1
        assert "neb_runq_ns" in err

    def test_a_threads_descriptor_is_closed_with_it(self):
        if hostclock.stamp()[2] is None:
            pytest.skip("no schedstat on this machine")
        fds = lambda: len(os.listdir("/proc/self/fd"))  # noqa: E731
        before = fds()
        got = []
        ts = [threading.Thread(target=lambda: got.append(
            hostclock.stamp())) for _ in range(8)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert len(got) == 8 and all(g[2] is not None for g in got)
        # join() returns when the thread's Python half is over; its
        # locals go a moment later, with the OS thread
        end = time.monotonic() + 10.0
        while fds() > before and time.monotonic() < end:
            time.sleep(0.01)
        assert fds() <= before


# ============================================================= the beats
class TestClassifiers:
    @pytest.mark.parametrize("py_us, nat_us, who", [
        (900, 400, None),                       # both on time
        (2_400_000, 2_350_000, "host"),         # the guest stood still
        (50_000, 150_000, "host"),              # no core for either
        (640_000, 900, "interpreter"),          # the lock alone
        (640_000, None, "host"),                # no native beat to tell
        (90_000, None, None),
    ])
    def test_who_stood_still(self, py_us, nat_us, who):
        assert hostclock.classify_beats(py_us, nat_us) == who

    @pytest.mark.parametrize("wall, cpu, runq, late, who", [
        (13_500_000, 300, 900, False, "device"),
        (13_500_000, 300, None, False, "device"),   # no runq clock
        (13_500_000, 300, 900, True, None),     # the beats say host
        (99_000, 10, 10, False, None),          # under the constant
        (400_000, 150_000, 0, False, None),     # the thread was running
        (400_000, 1_000, 200_000, False, None),  # runnable, no core
    ])
    def test_a_wait_is_the_devices(self, wall, cpu, runq, late, who):
        assert hostclock.classify_wait(wall, cpu, runq, late) == who


class TestBeatSecond:
    def test_record_fields_and_no_event_on_time(self, quiet_beats):
        flight.recorder.clear_for_tests()
        n0 = len(_stalls())
        b = hostclock._Beats()
        got = b.close_second(100.0, 101.0, 99, 12_345.6, 870.2,
                             (100, 9_876.5, 410.9))
        want = {"clock": hostclock.clock_name(), "n": 99,
                "py_late_sum_us": 12_345, "py_late_max_us": 870,
                "nat_n": 100, "nat_late_sum_us": 9_876,
                "nat_late_max_us": 410}
        assert got == want
        recs = [r for r in flight.recorder.dump() if r["kind"] == "beat"]
        assert len(recs) == 1
        assert {k: recs[0][k] for k in want} == want
        assert recs[0]["id"] >= 1 and recs[0]["time_us"] > 0
        assert len(_stalls()) == n0

    def test_without_a_native_beat_its_fields_are_left_off(
            self, quiet_beats):
        flight.recorder.clear_for_tests()
        got = hostclock._Beats().close_second(5.0, 6.0, 100, 9_000.0,
                                              300.0, None)
        assert not any(k.startswith("nat_") for k in got)
        rec = flight.recorder.dump()[0]
        assert rec["kind"] == "beat"
        assert not any(k.startswith("nat_") for k in rec)

    @pytest.mark.parametrize("py_max, nat, who", [
        (2_400_000.0, (60, 2_500_000.0, 2_350_000.0), "host"),
        (640_000.0, (100, 9_000.0, 700.0), "interpreter"),
    ])
    def test_a_late_beat_journals_who(self, quiet_beats, py_max, nat,
                                      who):
        mark = max([e["time_us"] for e in _stalls()] + [0]) + 1
        hostclock._Beats().close_second(7.0, 8.0, 60, py_max, py_max,
                                        nat)
        got = _stalls(mark)
        assert len(got) == 1 and got[0]["who"] == who
        assert got[0]["py_late_max_us"] == int(py_max)
        assert got[0]["nat_late_max_us"] == int(nat[2])

    def test_a_long_device_wait_with_the_beats_on_time(
            self, quiet_beats):
        mark = max([e["time_us"] for e in _stalls()] + [0]) + 1
        b = hostclock._Beats()
        # 13.5 s in block_until_ready: the thread ran 300 us and was
        # runnable 900, and every second of it the beats were on time
        b.note_wait("fetch_wait", (20.0, 1.0, 0.5),
                    (33.5, 1.0003, 0.5009))
        # a wait that is no stall is not even queued
        b.note_wait("fetch_wait", (33.5, 1.0, 0.5), (33.55, 1.0, 0.5))
        for sec in range(20, 33):
            b.close_second(float(sec), sec + 1.0, 100, 9_000.0, 400.0,
                           (100, 8_000.0, 300.0))
        assert not _stalls(mark)        # not over yet
        b.close_second(33.0, 34.0, 100, 9_000.0, 400.0,
                       (100, 8_000.0, 300.0))
        got = _stalls(mark)
        assert [e["who"] for e in got] == ["device"]
        assert got[0]["phase"] == "fetch_wait"
        assert got[0]["wall_us"] == 13_500_000
        assert got[0]["cpu_us"] <= 301 and got[0]["runq_us"] <= 901
        b.close_second(34.0, 35.0, 100, 9_000.0, 400.0, None)
        assert len(_stalls(mark)) == 1  # judged once

    def test_a_long_wait_under_a_late_beat_is_not_the_devices(
            self, quiet_beats):
        mark = max([e["time_us"] for e in _stalls()] + [0]) + 1
        b = hostclock._Beats()
        b.note_wait("fetch_wait", (50.2, 1.0, 0.5), (52.8, 1.0, 0.5))
        b.close_second(50.0, 51.0, 100, 9_000.0, 400.0,
                       (100, 8_000.0, 300.0))
        b.close_second(51.0, 52.0, 40, 700_000.0, 650_000.0,
                       (100, 8_000.0, 300.0))
        b.close_second(52.0, 53.0, 100, 9_000.0, 400.0,
                       (100, 8_000.0, 300.0))
        assert [e["who"] for e in _stalls(mark)] == ["interpreter"]


class TestThreads:
    def test_native_beat_starts_counts_and_stops(self, quiet_beats):
        lib = native.lib()
        if lib is None or not hasattr(lib, "neb_beat_start"):
            pytest.skip("the native library has no neb_beat_start")
        buf = (ctypes.c_int64 * 3)()
        assert lib.neb_beat_start(1_000) == 0       # 1 ms
        try:
            assert lib.neb_beat_start(1_000) == 1   # one a process
            end = time.monotonic() + 20.0
            while time.monotonic() < end:
                lib.neb_beat_read(buf)
                if buf[0] >= 3:
                    break
                time.sleep(0.005)
            n0 = buf[0]
            assert n0 >= 3, "the native beat never beat"
            # it needs no interpreter: this thread keeps the lock (no
            # other Python thread asks for it) and the beat goes on
            t_end = time.perf_counter() + 0.25
            while time.perf_counter() < t_end:
                sum(range(50_000))
            lib.neb_beat_read(buf)
            assert buf[0] > n0
            assert buf[1] >= 0 and buf[2] >= 0
            lib.neb_beat_read(buf)
            first = buf[0]
        finally:
            lib.neb_beat_stop()
        lib.neb_beat_read(buf)
        assert buf[0] <= first + 1                  # stopped
        lib.neb_beat_stop()                         # twice is fine
        assert lib.neb_beat_start(0) == -1

    def test_ensure_started_is_one_pair_a_process(self, quiet_beats):
        flight.recorder.clear_for_tests()
        hostclock.ensure_started()
        hostclock.ensure_started()
        assert hostclock.beats.running()
        assert sum(t.name == "host-beat"
                   for t in threading.enumerate()) == 1
        end = time.monotonic() + 30.0
        recs = []
        while time.monotonic() < end and not recs:
            time.sleep(0.05)
            recs = [r for r in flight.recorder.dump()
                    if r["kind"] == "beat"]
        assert recs, "no beat record within 30 s"
        rec = recs[-1]
        assert rec["clock"] in ("schedstat", "thread_time")
        assert rec["n"] >= 1 and rec["py_late_sum_us"] >= 0
        assert rec["py_late_max_us"] * rec["n"] >= rec["py_late_sum_us"]
        lib = native.lib()
        if lib is not None and hasattr(lib, "neb_beat_start"):
            assert rec["nat_n"] >= 1
            assert rec["nat_late_max_us"] * rec["nat_n"] \
                >= rec["nat_late_sum_us"]
        hostclock.beats.stop()
        assert not hostclock.beats.running()
        assert not any(t.name == "host-beat" and t.is_alive()
                       for t in threading.enumerate())
