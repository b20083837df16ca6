"""``unpack_live_share.*`` (PR 28) is data only: the shipped
``benchmark/layer_metrics/unpack_live_share.json`` and two
``per_layer`` entries, read by the reader the benchmark already has.
On a program whose tick records lack the fields (this PR's parent) it
reads nothing: the metric is left out of the line and named on stderr.
"""
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

SPEC = run.load_json(ROOT, "BENCHMARK.json")
CELLS = {"graph500-s20.lone8": ("unpack_live_share.lat", "trav_p50_ms"),
         "graph500-s20.steady": ("unpack_live_share.lat", "trav_p50_ms"),
         "graph500-s20.closed64": ("unpack_live_share.qps", "qps")}
WITH = [{"kind": "tick", "time_us": 10, "unpack_leavers": 3,
         "unpack_live": 3, "unpack_rows": 900, "unpack_us": 700},
        {"kind": "tick", "time_us": 20, "unpack_leavers": 1,
         "unpack_live": 0, "unpack_rows": 300_000, "unpack_us": 2_900},
        {"kind": "tick", "time_us": 30, "unpack_leavers": 0,
         "unpack_live": 0, "unpack_rows": 0, "unpack_us": 0},
        {"kind": "dispatch", "time_us": 40}]
# the parent's records: the stamps, not the counters
WITHOUT = [{k: v for k, v in r.items() if not k.startswith("unpack_l")
            and k != "unpack_rows"} for r in WITH]


def _read(cell, flight, tmp_path):
    """The cell's ``unpack_live_share`` entry through the harness's own
    resolve + reader loop (``run.traced_metrics``), on a run that has
    tick records and no profiler trace."""
    parts = run.resolve(SPEC, cell)
    parts["per_layer"] = [m for m in parts["per_layer"]
                          if m["name"].split(".")[0] == "unpack_live_share"]
    window = types.SimpleNamespace(dir=str(tmp_path), error="no profiler",
                                   sync_wall_ns=0, stop_wall_ns=0)
    ev = {"window": window, "records": [], "trees": [], "flight": flight,
          "counters": {}, "t_end": 0.0, "t0": 0.0, "wall_minus_perf_ns": 0,
          "stages": {}, "facts": {}, "mix": None}
    return parts["per_layer"], run.traced_metrics(parts, ev, {}, {}, {})


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_shipped_metric_resolves_and_reads_a_tick_record(cell,
                                                             tmp_path):
    listed, (metrics, missing, _breakdown) = _read(cell, WITH, tmp_path)
    name, moves = CELLS[cell]
    assert [m["name"] for m in listed] == [name]
    entry = listed[0]
    assert entry["reader"] == "flight_ratio" and entry["moves"] == moves
    assert entry["select"] == {"kind": "tick", "top": "unpack_live",
                               "bottom": "unpack_leavers", "scale": 1}
    assert entry["layer"].startswith("fetch + host assembly")
    assert missing == []
    assert metrics == {name: {"value": pytest.approx(0.75),
                              "unit": "ratio"}}


def test_the_path_cell_does_not_list_it():
    parts = run.resolve(SPEC, "graph500-s20-path.closed16")
    assert not [m for m in parts["per_layer"]
                if m["name"].startswith("unpack_live_share")]


@pytest.mark.parametrize("flight", [
    WITHOUT,                                            # the parent
    [r for r in WITH if not r.get("unpack_leavers")],   # nobody left
], ids=["records_without_the_fields", "no_leaver_in_the_window"])
def test_nothing_to_read_is_left_out_and_named(flight, tmp_path, capsys):
    cell = "graph500-s20.closed64"
    _listed, (metrics, missing, _b) = _read(cell, flight, tmp_path)
    assert metrics == {} and missing == ["unpack_live_share.qps"]
    # the line of a run whose other readers read: exit 0, the metric
    # absent from it and named on stderr
    capsys.readouterr()
    result = {"correct": True, "attempted": 1, "failed": 0,
              "metrics": {"tick_ms.qps": {"value": 1.0, "unit": "ms"}},
              "device": {}, "compared": {},
              "notes": {"compiles_in_window": 0,
                        "missing_per_layer": missing}}
    assert run.finish(result, trace=True) == 0
    said = capsys.readouterr()
    line = json.loads(said.out.strip().splitlines()[-1])
    assert "unpack_live_share.qps" not in line["metrics"]
    assert "unpack_live_share.qps" in said.err
