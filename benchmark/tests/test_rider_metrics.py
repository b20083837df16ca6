"""``rider_share.*`` and ``rider_assemble_ms.*`` (PR 32) are data only:
two files under ``benchmark/layer_metrics`` and four ``per_layer``
entries, read by readers the benchmark already has (``flight_ratio``
over the tick record's ``handed`` / ``leaves``, ``span_tag`` over the
``assemble_us`` tag of a rider's ``graph.continuous`` marker).  On a
program whose records lack them (PR 32's parent: the pump assembles,
the marker has four waits) both read nothing: left out of the line,
named on stderr, exit 0.  ``rider_share.lat`` went with PR 45 (half the
latency cells' statements are counts: it read 0.5 in every run), and
so did ``where_native_share.qps`` (1.0 in every run): "the native
filter ran" is a key of every run's notes line now, the growth of
``rt.where_native`` over that of ``rt.go_where`` through
``counter_delta`` (``harness.json`` "notes"), and is compared with
nothing.  CPU only: no number here is a device number."""
from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.readers import (  # noqa: E402
    counter_delta, flight_ratio, span_tag)

SPEC = run.load_json(ROOT, "BENCHMARK.json")
RIDER_CELLS = {"graph500-s20.lone8": ("lat", "trav_p50_ms"),
               "graph500-s20.steady": ("lat", "trav_p50_ms"),
               "graph500-s20.closed64": ("qps", "qps"),
               "graph500-s20-where.filtered16": ("qps", "qps")}
# three cohorts: all rows, half COUNT riders, nobody left; a dispatch
# record of another tier
TICKS = [{"kind": "tick", "leaves": 4, "handed": 4, "rows_us": 3},
         {"kind": "tick", "leaves": 6, "handed": 3, "rows_us": 40},
         {"kind": "tick", "leaves": 0, "handed": 0, "rows_us": 0},
         {"kind": "dispatch", "kernel": "ell_bfs", "levels": 5}]
# the parent's tick records: rows and WHERE counts, no ``handed``
PARENT_TICKS = [{"kind": "tick", "leaves": 4, "leaver_rows": 900,
                 "where_stmts": 2, "rows_us": 27_000},
                {"kind": "tick", "leaves": 0, "leaver_rows": 0,
                 "where_stmts": 0, "rows_us": 0}]


def _rider_layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         name + ".json")


def _rider_tree(**waits) -> dict:
    tags = {"lane": 3, "joined_tick": 7, "left_tick": 9, "hops": 2,
            "ending": "left-batch", **waits}
    return {"roots": [{"name": "graph.query", "start_us": 0,
                       "duration_us": 30_000, "tags": {},
                       "children": [
                           {"name": "graph.continuous", "start_us": 29_000,
                            "duration_us": 0, "tags": tags,
                            "children": []}]}]}


FOUR = {"seat_wait_us": 100, "ride_us": 9_000, "result_wait_us": 4_000,
        "wake_us": 300}


def test_the_rider_readers_on_hand_made_records():
    record = {"flight": TICKS,
              "trees": [_rider_tree(**FOUR, assemble_us=200),
                        _rider_tree(**FOUR, assemble_us=18_000),
                        _rider_tree(**FOUR, assemble_us=2)]}
    share = _rider_layer("rider_share")
    assert share["reader"] == "flight_ratio"
    assert share["select"] == {"kind": "tick", "top": "handed",
                               "bottom": "leaves", "scale": 1}
    assert flight_ratio.read(share["select"], record) \
        == pytest.approx(0.7)
    took = _rider_layer("rider_assemble_ms")
    assert took["reader"] == "span_tag"
    assert took["select"]["span"] == "graph.continuous"
    assert took["select"]["reduce"] == "mean"
    assert span_tag.read(took["select"], record) \
        == pytest.approx(18.202 / 3)


@pytest.mark.parametrize("record", [
    # the parent: the pump assembled, the marker has four waits
    {"flight": PARENT_TICKS, "trees": [_rider_tree(**FOUR)]},
    # nobody left in the window
    {"flight": [TICKS[2], TICKS[3]], "trees": []},
], ids=["records_without_the_fields", "no_leaver_in_the_window"])
def test_the_rider_readers_read_nothing_and_do_not_raise(record):
    assert flight_ratio.read(_rider_layer("rider_share")["select"],
                             record) is None
    assert span_tag.read(_rider_layer("rider_assemble_ms")["select"],
                         record) is None


@pytest.mark.parametrize("cell", sorted(RIDER_CELLS))
def test_the_rider_metrics_are_listed_where_the_continuous_tier_serves(
        cell):
    suffix, moves = RIDER_CELLS[cell]
    listed = {m["name"]: m for m in run.resolve(SPEC, cell)["per_layer"]
              if m["name"].split(".")[0] in ("rider_share",
                                             "rider_assemble_ms")}
    # the share reads the statement mix in the latency cells (half are
    # counts: 0.5 in every run), so only the .qps entry stays (PR 45)
    assert sorted(listed) == [f"rider_assemble_ms.{suffix}"] + (
        [f"rider_share.{suffix}"] if suffix == "qps" else [])
    for m in listed.values():
        assert m["moves"] == moves
        assert m["layer"] == \
            "fetch + host assembly (tpu/runtime.py _assemble_*)"
        assert m["unit"] == ("ratio" if m["name"].startswith("rider_share")
                             else "ms")


def test_the_path_cell_lists_neither_rider_metric():
    """FIND PATH rides the windowed tier, whose leader assembles the
    batch: no handover, nothing to read."""
    parts = run.resolve(SPEC, "graph500-s20-path.closed16")
    assert not [m["name"] for m in parts["per_layer"]
                if m["name"].startswith("rider_")]


def _note(name: str) -> dict:
    """A guard of the notes line (``harness.json`` "notes")."""
    return run.load_json(ROOT, "benchmark", "harness.json")["notes"][name]


def test_the_native_filter_guard_on_hand_made_counters():
    note = _note("where_native_share")
    assert note["reader"] == "counter_delta"
    assert note["select"] == {"counter": "rt.where_native",
                              "per": "rt.go_where", "scale": 1}
    # five filtered statements in the window, three of them by the one
    # native pass, two by numpy on the pump
    record = {"counters": {
        "before": {"rt.go_where": 10, "rt.where_native": 10},
        "after": {"rt.go_where": 15, "rt.where_native": 13}}}
    assert counter_delta.read(note["select"], record) \
        == pytest.approx(0.6)
    # a window without a filtered statement, and a program without the
    # counter: nothing to read, the key is left off the line
    idle = {"counters": {"before": {"rt.go_where": 10,
                                    "rt.where_native": 10},
                         "after": {"rt.go_where": 10,
                                   "rt.where_native": 10}}}
    assert counter_delta.read(note["select"], idle) is None
    parent = {"counters": {"before": {"rt.go_where": 1},
                           "after": {"rt.go_where": 4}}}
    assert counter_delta.read(note["select"], parent) is None


def test_the_retired_guards_are_notes_and_listed_in_no_cell():
    notes = run.load_json(ROOT, "benchmark", "harness.json")["notes"]
    assert sorted(notes) == ["khop_counted_share", "neigh_ridden_share",
                             "where_native_share"]
    families = {m["name"].split(".")[0] for m in SPEC["per_layer"]}
    assert not families & (set(notes) | {"compiles_in_window"})
    cells = {w["name"] for w in SPEC["workloads"]}
    for name, note in notes.items():    # each by a reader that is there
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", note["reader"] + ".py")), name
        # watched in the cells that listed it, where it read 1.0 in
        # every run: any other reading is MARKED on stderr
        assert note["cells"] and set(note["cells"]) <= cells, name
