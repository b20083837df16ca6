"""The per-layer metrics PR 27 brought for the path cell: the bytes of
a BFS level, the roofline reader over dispatch records, and every
listed metric of every cell naming a reader that is there (run:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``)."""
from __future__ import annotations

import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bfs_bytes, bytes_model, run  # noqa: E402
from benchmark.readers import levels_roofline  # noqa: E402

SHAPES = [[1000, 8], [10, 512]]
SELECT = run.load_json(ROOT, "benchmark", "layer_metrics",
                       "bfs_roofline.json")["select"]


def test_a_level_moves_the_table_and_the_depth_matrix():
    slots = bytes_model.table_slots(SHAPES)
    assert slots == 13120
    # per slot: index + edge type + one 16-byte word row gathered; per
    # row: two word rows written, 128 int16 depths read and written
    assert bfs_bytes.level_bytes(SHAPES, 4, 4, 128) \
        == slots * (4 + 4 + 16) + 1010 * (2 * 16 + 2 * 128 * 2)
    # the sweep costs the rung, whatever lanes are used: twice the
    # lanes, twice the lane-wide part
    assert bfs_bytes.level_bytes(SHAPES, 4, 4, 256) \
        - bfs_bytes.level_bytes(SHAPES, 4, 4, 128) \
        == slots * 16 + 1010 * (2 * 16 + 2 * 128 * 2)


def _record(flight, program_s=0.5):
    return {"trace": {"program_s": {"jit_bfs": program_s, "jit_hop": 9.0},
                      "program_runs": {"jit_bfs": 2, "jit_hop": 3}},
            "traced_us": (1000.0, 2000.0),
            "peaks": {"hbm_bytes_per_s": 1e9},
            "facts": {"ell_shapes": SHAPES, "ell_index_itemsize": 4,
                      "ell_etype_itemsize": 4},
            "flight": flight}


def _dispatch(time_us, levels=5, **more):
    return {"kind": "dispatch", "kernel": "ell_bfs", "rung": 128,
            "steps": 5, "levels": levels, "queries": 9,
            "time_us": time_us, **more}


def test_the_roofline_counts_the_levels_of_the_interval_s_records():
    level = bfs_bytes.level_bytes(SHAPES, 4, 4, 128)
    flight = [_dispatch(500.0), _dispatch(1200.0, levels=5),
              _dispatch(1900.0, levels=3), _dispatch(2500.0),
              {"kind": "dispatch", "kernel": "ell_go", "rung": 128,
               "time_us": 1500.0},
              {"kind": "tick", "time_us": 1500.0, "levels": 40}]
    got = levels_roofline.read(SELECT, _record(flight))
    assert got == pytest.approx(100.0 * 8 * level / 1e9 / 0.5)


@pytest.mark.parametrize("why", ["no_levels", "no_records", "no_trace",
                                 "no_peaks", "no_program"])
def test_the_roofline_reads_nothing_where_there_is_nothing(why):
    """On this PR's parent the record has no ``levels``; on CPU there
    are no peaks and no trace: None, never 0."""
    rec = _record([_dispatch(1200.0)])
    if why == "no_levels":
        del rec["flight"][0]["levels"]
    elif why == "no_records":
        rec["flight"] = [_dispatch(2500.0)]
    elif why == "no_trace":
        rec["trace"] = rec["traced_us"] = None
    elif why == "no_peaks":
        rec["peaks"] = None
    else:
        rec["trace"]["program_s"].pop("jit_bfs")
        rec["trace"]["program_runs"].pop("jit_bfs")
    assert levels_roofline.read(SELECT, rec) is None


def test_every_listed_metric_of_every_cell_resolves():
    spec = run.load_json(ROOT, "BENCHMARK.json")
    for cell in spec["workloads"]:
        parts = run.resolve(spec, cell["name"])
        assert parts["end_to_end"] and parts["per_layer"], cell["name"]
        for m in parts["per_layer"]:
            reader = importlib.import_module(
                f"benchmark.readers.{m['reader']}")
            assert callable(reader.read), m["name"]



@pytest.mark.parametrize("family, span, tag, want_ms", [
    ("pool_wait_ms", "graph.batched", "pool_wait_us", 3.0),
    # the mean: the chip's host counts thread time in ticks of 10 ms,
    # so one walk reads 0 or 10 ms and only the mean says anything
    ("path_walk_cpu_ms", "tpu.path_reconstruct", "cpu_us", 18.0)])
def test_a_rider_s_wait_and_a_walk_s_own_time_are_read_off_their_tags(
        family, span, tag, want_ms):
    """The tag in ms; nothing where the program writes no such tag
    (this PR's parent), never 0."""
    from benchmark.readers import span_tag
    select = run.load_json(ROOT, "benchmark", "layer_metrics",
                           family + ".json")["select"]

    def tree(tags):
        return {"roots": [{"name": "graph.query", "children": [
            {"name": span, "tags": tags, "duration_us": 9}]}]}

    trees = [tree({tag: us}) for us in (1000, 3000, 50000)]
    assert span_tag.read(select, {"trees": trees}) == pytest.approx(want_ms)
    assert span_tag.read(select, {"trees": [tree({"paths": 3})]}) is None
    assert span_tag.read(select, {"trees": []}) is None
