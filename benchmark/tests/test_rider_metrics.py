"""``rider_share.*`` and ``rider_assemble_ms.*`` (PR 32) are data only:
two files under ``benchmark/layer_metrics`` and four ``per_layer``
entries, read by readers the benchmark already has (``flight_ratio``
over the tick record's ``handed`` / ``leaves``, ``span_tag`` over the
``assemble_us`` tag of a rider's ``graph.continuous`` marker).  On a
program whose records lack them (PR 32's parent: the pump assembles,
the marker has four waits) both read nothing: left out of the line,
named on stderr, exit 0.  ``where_native_share.qps`` is the same kind
of thing: the ``native`` tag of the ``tpu.where`` spans (statements the
one native pass filtered) over their ``queries`` tag, through
``span_tag_ratio``; the parent's spans have no such tag.  CPU only: no
number here is a device number."""
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
    flight_ratio, span_tag, span_tag_ratio)

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
    assert sorted(listed) == [f"rider_assemble_ms.{suffix}",
                              f"rider_share.{suffix}"]
    for m in listed.values():
        assert m["moves"] == moves
        assert m["layer"] == \
            "fetch + host assembly (tpu/runtime.py _assemble_*)"
    assert listed[f"rider_share.{suffix}"]["unit"] == "ratio"
    assert listed[f"rider_assemble_ms.{suffix}"]["unit"] == "ms"


def test_the_path_cell_lists_neither_rider_metric():
    """FIND PATH rides the windowed tier, whose leader assembles the
    batch: no handover, nothing to read."""
    parts = run.resolve(SPEC, "graph500-s20-path.closed16")
    assert not [m["name"] for m in parts["per_layer"]
                if m["name"].startswith("rider_")]


def _where_tree(**tags) -> dict:
    return {"roots": [{"name": "graph.query", "start_us": 0,
                       "duration_us": 30_000, "tags": {},
                       "children": [
                           {"name": "tpu.where", "start_us": 100,
                            "duration_us": 900, "children": [],
                            "tags": {"site": "assembly",
                                     "candidates": 5000, "kept": 50,
                                     "cpu_us": 800, **tags}}]}]}


def test_where_native_share_on_hand_made_records():
    layer = _rider_layer("where_native_share")
    assert layer["reader"] == "span_tag_ratio"
    assert layer["select"] == {"span": "tpu.where", "top": "native",
                               "bottom": "queries", "scale": 1}
    # three riders' own native passes, one group of two the pump
    # filtered in numpy
    record = {"trees": [_where_tree(queries=1, native=1),
                        _where_tree(queries=1, native=1),
                        _where_tree(queries=2, native=0),
                        _where_tree(queries=1, native=1)]}
    assert span_tag_ratio.read(layer["select"], record) \
        == pytest.approx(0.6)
    # the parent's spans carry no ``native``: nothing to read
    parent = {"trees": [_where_tree(queries=2), _where_tree(queries=1)]}
    assert span_tag_ratio.read(layer["select"], parent) is None
    assert span_tag_ratio.read(layer["select"], {"trees": []}) is None


def test_where_native_share_is_listed_in_the_filtered_cell_alone():
    for cell in [w["name"] for w in SPEC["workloads"]]:
        listed = [m for m in run.resolve(SPEC, cell)["per_layer"]
                  if m["name"] == "where_native_share.qps"]
        if cell == "graph500-s20-where.filtered16":
            assert len(listed) == 1 and listed[0]["moves"] == "qps"
            assert listed[0]["unit"] == "ratio"
        else:
            assert not listed
