"""The host-time families (PR 36) are data only: one file each under
``benchmark/layer_metrics`` and ``per_layer`` entries, read by readers
the benchmark already has (``flight_field``, ``flight_ratio``,
``span_tag``, ``span_tag_ratio``) off what the program's stamps and
beats write (``nebula_tpu/common/hostclock.py``): the tick record's
closed parts and its ``*_cpu_us``, the two ``*_cpu_us`` tags of a
rider's ``graph.continuous`` marker, and the ``beat`` records.  On a
record without the field (the parent) each reads nothing: left out of
the line, named on stderr, exit 0.  No family reads a ``*_runq_*``
field or tag: the chip machine has no run-queue clock (it is gVisor,
no ``/proc/thread-self/schedstat``), the program leaves them off
there, and a listed metric that a traced run cannot report refuses
the run.  PR 45 took ``pump_seat_cpu_share`` and
``pump_handover_cpu_share`` off the list (a share over 1: those phases
take 0.1-0.6 ms a tick and the chip host's thread clock comes in 10 ms
ticks) and brought the four readers that had waited for room, data
only like these: ``seat_hop_share``, ``pump_hold_ms``,
``held_join_share`` (the tick record's ``seat_hops`` / ``hold_us`` /
``hold_joins``) and ``bfs_swept_share`` (the ``ell_bfs`` dispatch
record's ``swept`` over ``slots``).  CPU only: no number here is a
device number."""
from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

SPEC = run.load_json(ROOT, "BENCHMARK.json")
LAT = ("graph500-s20.lone8", "graph500-s20.steady")
C64, F16, K16, P16 = (
    "graph500-s20.closed64", "graph500-s20-where.filtered16",
    "graph500-s20-khop.count16", "graph500-s20-path.closed16")
N16, B16 = "graph500-s20-neigh.rows16", "graph500-s20-bidir.bicount16"

# two ticks of a window (the second has no joiner), a dispatch record
# of another tier, two seconds of beats
TICKS = [
    {"kind": "tick", "dur_us": 10_000, "cpu_us": 4_000, "runq_us": 1_500,
     "joins": 3, "seat_hops": 2, "hold_joins": 1, "hold_us": 6_000,
     "seat_us": 1_000, "seat_cpu_us": 200, "seat_runq_us": 100,
     "join_us": 800, "join_cpu_us": 600, "join_runq_us": 50,
     "join_map_us": 500, "join_pack_us": 100, "join_enqueue_us": 200,
     "hop_us": 700, "extract_us": 300, "clear_us": 100,
     "unpack_us": 2_000, "unpack_cpu_us": 900, "unpack_runq_us": 800,
     "handover_us": 3_000, "handover_cpu_us": 300,
     "handover_runq_us": 400, "other_us": 250, "idle_us": 40},
    {"kind": "tick", "dur_us": 30_000, "cpu_us": 8_000, "runq_us": 4_500,
     "joins": 1, "seat_hops": 1, "hold_joins": 0, "hold_us": 0,
     "seat_us": 3_000, "seat_cpu_us": 1_000, "seat_runq_us": 300,
     "join_us": 0, "join_cpu_us": 0, "join_runq_us": 0,
     "join_map_us": 0, "join_pack_us": 0, "join_enqueue_us": 0,
     "hop_us": 900, "extract_us": 500, "clear_us": 300,
     "unpack_us": 6_000, "unpack_cpu_us": 1_500, "unpack_runq_us": 3_200,
     "handover_us": 9_000, "handover_cpu_us": 900,
     "handover_runq_us": 1_400, "other_us": 750, "idle_us": 1_960},
    {"kind": "dispatch", "kernel": "ell_bfs", "levels": 5,
     "slots": 1_000, "swept": 700},
    {"kind": "dispatch", "kernel": "ell_bfs", "levels": 2,
     "slots": 600, "swept": 580},
    {"kind": "beat", "clock": "schedstat", "n": 99,
     "py_late_sum_us": 19_800, "py_late_max_us": 1_000, "nat_n": 100,
     "nat_late_sum_us": 8_000, "nat_late_max_us": 400},
    {"kind": "beat", "clock": "schedstat", "n": 101,
     "py_late_sum_us": 180_200, "py_late_max_us": 9_000, "nat_n": 100,
     "nat_late_sum_us": 12_000, "nat_late_max_us": 600},
]


def _tree(name: str, **tags) -> dict:
    return {"roots": [{"name": "graph.query", "start_us": 0,
                       "duration_us": 30_000, "tags": {},
                       "children": [{"name": name, "start_us": 100,
                                     "duration_us": 0, "tags": tags,
                                     "children": []}]}]}


TREES = [
    _tree("graph.continuous", assemble_us=10_000, assemble_cpu_us=3_000,
          assemble_runq_us=5_000, wait_cpu_us=400, wait_runq_us=900),
    _tree("graph.continuous", assemble_us=30_000, assemble_cpu_us=9_000,
          assemble_runq_us=3_000, wait_cpu_us=200, wait_runq_us=100),
    _tree("tpu.path_reconstruct", cpu_us=10_000, runq_us=2_000),
    _tree("tpu.path_reconstruct", cpu_us=11_000, runq_us=3_000),
    _tree("tpu.path_reconstruct", cpu_us=12_000, runq_us=40_000),
]
RECORD = {"flight": TICKS, "trees": TREES}

# the same window on the parent, or on a machine with no run-queue
# clock and no native beat: none of the fields these families read
BARE = {"flight": [
    {"kind": "tick", "dur_us": 10_000, "seat_us": 1_000, "join_us": 800,
     "hop_us": 700, "extract_us": 300, "clear_us": 100,
     "unpack_us": 2_000, "handover_us": 3_000, "idle_us": 40},
    {"kind": "dispatch", "kernel": "ell_bfs", "levels": 5}],
    "trees": [_tree("graph.continuous", assemble_us=10_000),
              _tree("tpu.path_reconstruct", cpu_us=10_000)]}

# family -> (reader, the value RECORD reads, the FIRST cells that list
# it: a later PR appends a cell of its own to a family's ``workloads``
# where a traced run of that cell reports the field, so the lists are
# held by containment; PR 42 appended rows16 to all nineteen and
# bicount16 to the seventeen that need no unpack)
GO5 = LAT + (C64, F16, K16)
FETCHING = LAT + (C64, F16)
ALL6 = GO5 + (P16,)
FAMILIES = {
    "pump_join_ms": ("flight_field", 0.4, GO5),
    "pump_hop_enqueue_ms": ("flight_field", 0.8, GO5),
    "pump_extract_ms": ("flight_field", 0.4, GO5),
    "pump_clear_ms": ("flight_field", 0.2, GO5),
    "pump_other_ms": ("flight_field", 0.5, GO5),
    "pump_idle_ms": ("flight_field", 1.0, GO5),
    "pump_join_map_ms": ("flight_field", 0.25, GO5),
    "pump_join_enqueue_ms": ("flight_field", 0.1, GO5),
    "pump_cpu_share": ("flight_ratio", 0.3, GO5),
    "pump_join_cpu_share": ("flight_ratio", 0.75, GO5),
    "pump_unpack_cpu_share": ("flight_ratio", 0.3, FETCHING),
    "rider_cpu_share": ("span_tag_ratio", 0.3, FETCHING),
    "rider_wait_cpu_ms": ("span_tag", 0.3, GO5),
    "gil_late_ms": ("flight_ratio", 1.0, ALL6),
    "host_late_ms": ("flight_ratio", 0.1, ALL6),
    "gil_late_worst_ms": ("flight_field", 5.0, ALL6),
    "host_late_worst_ms": ("flight_field", 0.5, ALL6),
}

# the readers that waited for room until PR 45: family -> (reader, the
# value RECORD reads, unit, better, the cells that list it at least);
# ``.qps`` alone, so they stand apart from FAMILIES
PULLING = (C64, F16, K16, N16, B16)
# where the pump holds at all: ``closed64`` and ``filtered16`` read 0.0
# and 0.0 in every run (a hop there is over before the next joiner
# comes), and an entry that reads one number is a notes key at best
HOLDING = (K16, N16, B16)
WAITED = {
    "seat_hop_share": ("flight_ratio", 0.75, "ratio", "higher", PULLING),
    "pump_hold_ms": ("flight_field", 3.0, "ms", "lower", HOLDING),
    "held_join_share": ("flight_ratio", 0.25, "ratio", "higher", HOLDING),
    "bfs_swept_share": ("flight_ratio", 0.8, "ratio", "lower", (P16,)),
}


def _layer(family: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         family + ".json")


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_each_family_reads_a_hand_made_record(family):
    reader_name, want, _cells = FAMILIES[family]
    layer = _layer(family)
    assert layer["reader"] == reader_name
    reader = importlib.import_module(f"benchmark.readers.{reader_name}")
    assert reader.read(layer["select"], RECORD) == pytest.approx(want)


@pytest.mark.parametrize("family", sorted(
    f for f in FAMILIES if f not in (
        # what a tick record had before this PR: read on the parent too
        "pump_join_ms", "pump_hop_enqueue_ms", "pump_extract_ms",
        "pump_clear_ms", "pump_idle_ms")))
def test_each_family_reads_nothing_without_its_field(family):
    reader_name, _want, _cells = FAMILIES[family]
    layer = _layer(family)
    reader = importlib.import_module(f"benchmark.readers.{reader_name}")
    assert reader.read(layer["select"], BARE) is None
    assert reader.read(layer["select"],
                       {"flight": [], "trees": []}) is None


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_each_family_is_listed_where_it_has_something_to_read(family):
    _reader, _want, cells = FAMILIES[family]
    entries = [m for m in SPEC["per_layer"]
               if m["name"].split(".")[0] == family]
    by_suffix = {m["name"].split(".")[1]: m for m in entries}
    assert set(by_suffix) == {"lat", "qps"}
    listed = set()
    for suffix, m in by_suffix.items():
        assert m["moves"] == {"lat": "trav_p50_ms", "qps": "qps"}[suffix]
        assert m["unit"] == ("ratio" if family.endswith("_share")
                             else "ms")
        assert m["source"] == ("program_span" if FAMILIES[family][0]
                               .startswith("span_tag")
                               else "program_counter")
        listed |= set(m["workloads"])
    assert listed >= set(cells)
    assert listed <= {w["name"] for w in SPEC["workloads"]}
    for cell in sorted(listed):         # and the harness finds the file
        names = {m["name"].split(".")[0]
                 for m in run.resolve(SPEC, cell)["per_layer"]}
        assert family in names


@pytest.mark.parametrize("family", sorted(WAITED))
def test_each_reader_that_waited_reads_a_hand_made_record(family):
    reader_name, want, _unit, _better, _cells = WAITED[family]
    layer = _layer(family)
    assert layer["reader"] == reader_name
    reader = importlib.import_module(f"benchmark.readers.{reader_name}")
    assert reader.read(layer["select"], RECORD) == pytest.approx(want)


@pytest.mark.parametrize("family", sorted(WAITED))
def test_each_reader_that_waited_reads_nothing_without_its_field(family):
    """PR 41's / PR 43's / PR 30's parent: tick records without the
    hold's and the seat's fields, ``ell_bfs`` records without their
    slots.  None (left out of the line, named on stderr), no raise."""
    reader_name = WAITED[family][0]
    layer = _layer(family)
    reader = importlib.import_module(f"benchmark.readers.{reader_name}")
    assert reader.read(layer["select"], BARE) is None
    assert reader.read(layer["select"],
                       {"flight": [], "trees": []}) is None


@pytest.mark.parametrize("family", sorted(WAITED))
def test_each_reader_that_waited_is_listed_where_it_reads(family):
    _reader, _want, unit, better, cells = WAITED[family]
    entry, = [m for m in SPEC["per_layer"]
              if m["name"].split(".")[0] == family]
    assert entry["name"] == family + ".qps" and entry["moves"] == "qps"
    assert (entry["unit"], entry["better"]) == (unit, better)
    assert entry["source"] == "program_counter"
    assert set(cells) <= set(entry["workloads"]) \
        <= {w["name"] for w in SPEC["workloads"]}
    for cell in entry["workloads"]:     # and the harness finds the file
        assert family + ".qps" in {
            m["name"] for m in run.resolve(SPEC, cell)["per_layer"]}
    # a cell of the windowed tier has no tick and no seat; a GO cell no
    # BFS dispatch
    assert (P16 in entry["workloads"]) == (family == "bfs_swept_share")
    if cells is HOLDING:
        assert not {C64, F16} & set(entry["workloads"])


def test_lanes_seated_reads_lower_as_better_since_the_seat_hops():
    """A seat that takes a hop takes a lane-tick off every statement
    (9.14 -> 7.30 beside qps +27 %, PR 43): fewer lanes seated for the
    same callers is the gain, and the latency cells' entry (0.0 and
    0.001: nobody waits for a lane at 8 a second) is gone."""
    entry, = [m for m in SPEC["per_layer"]
              if m["name"].split(".")[0] == "lanes_seated"]
    assert entry["name"] == "lanes_seated.qps"
    assert entry["better"] == "lower" and entry["moves"] == "qps"


def test_no_listed_metric_reads_a_run_queue_field():
    """The program writes ``*_runq_us`` only where the machine has a
    schedstat, and the chip machine has none: a metric over one could
    never be reported there."""
    for m in SPEC["per_layer"]:
        layer = _layer(m["name"].split(".")[0])
        assert not [v for v in layer["select"].values()
                    if isinstance(v, str) and "runq" in v], m["name"]


def test_the_list_is_within_the_benchmarks_size():
    """128 is the most the contract takes, and the only size held
    here: a PR which may only ADD entries cannot edit this file, so
    the room PR 45 made (116 listed, CHANGES.md) is its to use."""
    assert len(SPEC["per_layer"]) <= 128
    names = [m["name"] for m in SPEC["per_layer"]]
    assert len(names) == len(set(names))
    # every entry has its family's file, and every file an entry
    files = {f[:-5] for f in os.listdir(os.path.join(
        ROOT, "benchmark", "layer_metrics"))}
    assert files == {n.split(".")[0] for n in names}


RETIRED = ("compiles_in_window", "khop_counted_share",
           "neigh_ridden_share", "where_native_share",
           "hop_onesided_share", "where_keep_share",
           "pump_seat_cpu_share", "pump_handover_cpu_share",
           "where_cpu_ns_per_edge", "hop_sides_roofline")


@pytest.mark.parametrize("family", RETIRED)
def test_a_retired_family_is_gone_whole(family):
    """Entry and file both: a family file without an entry is dead
    data, an entry without its file ends every run in resolve."""
    assert not [m["name"] for m in SPEC["per_layer"]
                if m["name"].split(".")[0] == family]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", family + ".json"))


@pytest.mark.parametrize("name", ["rider_share.lat", "lanes_seated.lat"])
def test_a_retired_suffix_is_gone_and_its_family_stays(name):
    listed = {m["name"] for m in SPEC["per_layer"]}
    assert name not in listed
    assert name.split(".")[0] + ".qps" in listed
