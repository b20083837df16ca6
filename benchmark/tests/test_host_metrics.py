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
the run.  CPU only: no number here is a device number."""
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

# two ticks of a window (the second has no joiner), a dispatch record
# of another tier, two seconds of beats
TICKS = [
    {"kind": "tick", "dur_us": 10_000, "cpu_us": 4_000, "runq_us": 1_500,
     "seat_us": 1_000, "seat_cpu_us": 200, "seat_runq_us": 100,
     "join_us": 800, "join_cpu_us": 600, "join_runq_us": 50,
     "join_map_us": 500, "join_pack_us": 100, "join_enqueue_us": 200,
     "hop_us": 700, "extract_us": 300, "clear_us": 100,
     "unpack_us": 2_000, "unpack_cpu_us": 900, "unpack_runq_us": 800,
     "handover_us": 3_000, "handover_cpu_us": 300,
     "handover_runq_us": 400, "other_us": 250, "idle_us": 40},
    {"kind": "tick", "dur_us": 30_000, "cpu_us": 8_000, "runq_us": 4_500,
     "seat_us": 3_000, "seat_cpu_us": 1_000, "seat_runq_us": 300,
     "join_us": 0, "join_cpu_us": 0, "join_runq_us": 0,
     "join_map_us": 0, "join_pack_us": 0, "join_enqueue_us": 0,
     "hop_us": 900, "extract_us": 500, "clear_us": 300,
     "unpack_us": 6_000, "unpack_cpu_us": 1_500, "unpack_runq_us": 3_200,
     "handover_us": 9_000, "handover_cpu_us": 900,
     "handover_runq_us": 1_400, "other_us": 750, "idle_us": 1_960},
    {"kind": "dispatch", "kernel": "ell_bfs", "levels": 5},
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

# family -> (reader, the value RECORD reads, the cells that list it)
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
    "pump_handover_cpu_share": ("flight_ratio", 0.1, GO5),
    "pump_seat_cpu_share": ("flight_ratio", 0.3, GO5),
    "pump_join_cpu_share": ("flight_ratio", 0.75, GO5),
    "pump_unpack_cpu_share": ("flight_ratio", 0.3, FETCHING),
    "rider_cpu_share": ("span_tag_ratio", 0.3, FETCHING),
    "rider_wait_cpu_ms": ("span_tag", 0.3, GO5),
    "gil_late_ms": ("flight_ratio", 1.0, ALL6),
    "host_late_ms": ("flight_ratio", 0.1, ALL6),
    "gil_late_worst_ms": ("flight_field", 5.0, ALL6),
    "host_late_worst_ms": ("flight_field", 0.5, ALL6),
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
    assert listed == set(cells)
    for cell in cells:                  # and the harness finds the file
        names = {m["name"].split(".")[0]
                 for m in run.resolve(SPEC, cell)["per_layer"]}
        assert family in names


def test_no_listed_metric_reads_a_run_queue_field():
    """The program writes ``*_runq_us`` only where the machine has a
    schedstat, and the chip machine has none: a metric over one could
    never be reported there."""
    for m in SPEC["per_layer"]:
        layer = _layer(m["name"].split(".")[0])
        assert not [v for v in layer["select"].values()
                    if isinstance(v, str) and "runq" in v], m["name"]


def test_the_list_is_within_the_benchmarks_size():
    assert len(SPEC["per_layer"]) <= 128
    names = [m["name"] for m in SPEC["per_layer"]]
    assert len(names) == len(set(names))
