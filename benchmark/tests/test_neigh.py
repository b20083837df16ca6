"""The k-hop neighbourhood deployment PR 38 brought (run:
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``): the
reference of the kind against a brute-force walk, its control (a
vertex dropped, one duplicated, frontier k-1 in place of frontier k:
each ``correct: false``), the kind found by name and the cell
resolved, one rehearsal through the harness, and the new per-layer
metrics' readers on hand-made records.  CPU only: no number here is a
device number."""
from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import reference, run  # noqa: E402
from benchmark.readers import counter_delta, flight_ratio  # noqa: E402
from benchmark.semantics import go_distinct  # noqa: E402

NEIGH_CELL = "graph500-s20-neigh.rows16"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW_METRICS = ["neigh_hops_per_stmt.qps", "neigh_vertices_per_stmt.qps"]


def _neigh_graph(seed: int, n: int = 300, m: int = 1500,
                 sources: float = 1.0) -> reference.Graph:
    """``m`` draws of an edge over ``n`` vertices, the start of each
    among the first ``sources`` of them (the rest are sinks)."""
    rng = np.random.default_rng(seed)
    key = np.unique(rng.integers(0, int(n * sources), m) * n
                    + rng.integers(0, n, m))
    src, dst = key // n + 1, key % n + 1
    keep = src != dst
    return reference.Graph(src[keep], dst[keep], [{"w": 0.0}],
                           np.zeros(int(keep.sum()), np.int64))


def _walk(g: reference.Graph, start: int, steps: int) -> list:
    """Edge by edge over Python sets: nothing of numpy's marking."""
    frontier = {start}
    for _ in range(steps):
        frontier = {int(g.dst[e]) for v in frontier
                    for e in range(g.ptr[v], g.ptr[v + 1])}
    return sorted(frontier)


@pytest.mark.parametrize("steps", [1, 2, 3, 6])
@pytest.mark.parametrize("seed", [13, 3_999_999_998])
def test_neigh_reference_matches_a_brute_force_walk(seed, steps):
    g = _neigh_graph(seed, m=600, sources=0.6)   # walks that end early
    sem = {"kind": "go_distinct", "steps": steps}
    some = none = back = 0
    for start in range(1, 60):
        want = _walk(g, start, steps)
        got = g.answer(sem, start)
        assert isinstance(got, tuple) and len(got) == 1
        assert got[0].dtype == np.int64
        assert sorted(got[0].tolist()) == want       # each once
        assert reference.n_rows(got) == len(want)
        # ... and Graph.frontier's set, from either side of the hop
        for pull in (None, False, True):
            assert np.array_equal(got[0],
                                  g.frontier(start, steps, pull=pull))
        some += bool(want)
        none += not want
        back += start in want
    assert some > 20 and (none or steps == 1) and (back or steps < 3)


@pytest.mark.parametrize("seed", [13, 2_345_678_902, 3_999_999_998])
@pytest.mark.parametrize("weaken", ["vertex_dropped", "vertex_twice",
                                    "a_hop_short"])
def test_neigh_control_is_not_correct(seed, weaken):
    """The reference in the program's place with the seventh guarantee
    broken: one vertex of the neighbourhood missing, one returned
    twice (a hub's extra row read as a vertex, a DISTINCT that was
    not), or frontier k-1 where frontier k is asked (a rider that left
    a hop early, the budget a GO's rows need): the digest and the
    exact comparison both say so."""
    g = _neigh_graph(seed)
    judged = 0
    for steps in (2, 3):
        sem = {"kind": "go_distinct", "steps": steps}
        for start in range(1, 40):
            want = g.answer(sem, start)
            if not reference.n_rows(want):
                continue
            col = want[0]
            if weaken == "vertex_dropped":
                bad = (col[1:],)
            elif weaken == "vertex_twice":
                bad = (np.append(col, col[len(col) // 2]),)
            else:
                bad = g.answer({**sem, "steps": steps - 1}, start)
                if sorted(bad[0].tolist()) == sorted(col.tolist()):
                    continue        # the walk has stopped widening
            assert reference.digest(bad) != reference.digest(want)
            assert not reference.same_rows(bad, want)
            # ... and another order of the same rows is the same answer
            again = (col[::-1].copy(),)
            assert reference.digest(again) == reference.digest(want)
            assert reference.same_rows(again, want)
            judged += 1
    assert judged > 20


def test_the_neigh_kind_is_found_by_name_and_its_cell_resolves():
    assert reference.semantics_module("go_distinct") is go_distinct
    spec = run.load_json(ROOT, "BENCHMARK.json")
    parts = run.resolve(spec, NEIGH_CELL)
    assert parts["cell"]["chips"] == 1
    classes = parts["traffic"]["classes"]
    assert [(c["semantics"]["kind"], c["semantics"]["steps"])
            for c in classes.values()] \
        == [("go_distinct", k) for k in (2, 3)]
    for c in classes.values():          # the statement says the same
        assert c["template"] == (
            f"GO {c['semantics']['steps']} STEPS FROM {{v}} OVER knows "
            f"YIELD DISTINCT knows._dst")
        assert c["served_counter"] == "rt.go_device"
    group, = parts["traffic"]["groups"]
    assert (group["loop"], group["clients"], group["sequence"]) \
        == ("closed", 16, 16384)
    assert len(set(group["shares"].values())) == 1
    assert parts["traffic"]["warmup"] == {"starts": [9],
                                          "bursts": [1, 4, 16],
                                          "seconds": 4}
    assert parts["traffic"]["check"] == {"keep_share": 0.05,
                                         "keep_rows_cap": 30_000_000}
    assert parts["traffic"]["trace"] == {"seconds": 5}
    assert {m["name"] for m in parts["end_to_end"]} \
        == {"qps", "device_bytes_per_edge", "setup_s"}
    # the k-hop count's deployment edge for edge ...
    khop = run.load_json(ROOT, "benchmark", "configs",
                         "graph500-s20-khop.json")
    config = parts["config"]
    for key in ("generator", "generator_params", "structure_seed",
                "partition_num", "replica_factor", "flags",
                "layout", "edge", "space", "selfcheck", "reduced"):
        assert config[key] == khop[key], key
    # ... what it needs of the program declared (PR 45): the k-hop
    # count's flag and the deadline qps counts inside.  Both are still
    # pinned too, at their shipped values: tests/test_go_distinct.py
    # holds the two statements in this file's schema, and a benchmark
    # PR edits nothing under tests/ (PERF.md section 7, Left by PR 45)
    assert config["requires"] == {
        "flags": khop["requires"]["flags"] + ["query_deadline_ms"]}
    assert config["schema"] == khop["schema"] + [
        "UPDATE CONFIGS graph:go_dispatch_mode=continuous",
        "UPDATE CONFIGS graph:query_deadline_ms=300000"]
    with open(os.path.join(ROOT, "etc",
                           "nebula-graphd.conf.default")) as fh:
        assert "query_deadline_ms=300000" in fh.read().split()
    assert config["guarantees"][:3] == khop["guarantees"][:3]
    assert len(config["guarantees"]) == 4
    assert "each once" in config["guarantees"][-1]
    assert len(config["source"]) <= 200
    entry = next(c for c in spec["configs"]
                 if c["name"] == "graph500-s20-neigh")
    assert entry["source"] == config["source"]
    assert entry["reduced"] == config["reduced"] == ["scale"]
    listed = [m["name"] for m in parts["per_layer"]]
    assert set(NEW_METRICS) <= set(listed)     # and whatever came later
    # what the fetching cells list and the counting cell cannot
    for name in ("fetch_assemble_ms.qps", "pump_d2h_ms.qps",
                 "pump_unpack_ms.qps", "pump_rows_ms.qps",
                 "unpack_live_share.qps", "rider_share.qps",
                 "rider_assemble_ms.qps", "hop_roofline.qps",
                 "device_idle_pct.qps", "seat_hop_share.qps",
                 "pump_hold_ms.qps", "held_join_share.qps"):
        assert name in listed, name
    assert not [n for n in listed if n.startswith("khop_")]
    # since PR 42 the nineteen host families of PR 36 list the cell
    # too (test_host_metrics.py holds their lists by containment), and
    # hop_swept_share.qps does
    for name in ("pump_cpu_share.qps", "rider_cpu_share.qps",
                 "pump_join_map_ms.qps", "gil_late_ms.qps",
                 "hop_swept_share.qps"):
        assert name in listed, name
    assert len(spec["per_layer"]) <= 128     # the contract's size


def test_a_rehearsal_of_the_cell_is_correct_and_rides_every_statement(
        monkeypatch):
    """The cell through the harness as a chip run goes (the
    rehearsal's size, CPU jax), traced: every answer right, every
    leaver's frontier its answer, no row de-duplicated on the host,
    and the readers of what PR 38 added to the program find it."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import nebula_tpu.tpu.runtime as runtime_mod
    sorted_rows = []
    monkeypatch.setattr(runtime_mod, "_distinct_rows",
                        lambda rows: sorted_rows.append(len(rows)) or rows)
    parts = run.resolve(run.load_json(ROOT, "BENCHMARK.json"), NEIGH_CELL)
    out = run.run_cell(parts, seed=3_800_000_029, seconds=2.0,
                       trace=True, device=CPU, tiny=True)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 16
    for name, number in out["compared"].items():
        assert number["value"] == number.get("limit", number["value"]), \
            name
    assert not sorted_rows
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    # the retired guard is a key of the notes line, never compared
    assert out["notes"]["neigh_ridden_share"] == 1.0
    assert "neigh_ridden_share" not in out["compared"]
    assert "khop_counted_share" not in out["notes"]   # the count cells'
    assert out["notes"]["guards_off"] == {}
    assert metrics["rider_share.qps"] == 1.0
    # the seat took a first hop for nearly every joiner (k = 2, 3)
    assert 0.5 < metrics["seat_hop_share.qps"] <= 1.0
    assert metrics["pump_hold_ms.qps"] >= 0
    assert 0.0 <= metrics["held_join_share.qps"] <= 1.0
    assert 2.2 < metrics["neigh_hops_per_stmt.qps"] < 2.8     # (2+3)/2
    assert metrics["neigh_vertices_per_stmt.qps"] > 1
    assert 0.0 <= metrics["unpack_live_share.qps"] <= 1.0
    assert metrics["rider_assemble_ms.qps"] >= 0
    assert set(out["notes"]["missing_per_layer"]) <= {
        m["name"] for m in parts["per_layer"]
        if m["source"] == "device_trace"}
    grown = out["notes"]["counter_growth"]
    assert grown["rt.go_distinct"] == grown["rt.go_device"] \
        == grown["rt.go_reduced"]
    assert "rt.go_count_distinct" not in grown


def _layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         name + ".json")


def _note(name: str) -> dict:
    """A guard of the notes line (``harness.json`` "notes")."""
    return run.load_json(ROOT, "benchmark", "harness.json")["notes"][name]


def _record(**over) -> dict:
    record = {
        "trees": [],
        "flight": [{"kind": "tick", "leaves": 3, "handed": 3,
                    "counted": 0, "distinct": 3},
                   {"kind": "tick", "leaves": 1, "handed": 1,
                    "counted": 0, "distinct": 1},
                   {"kind": "tick", "leaves": 0, "handed": 0,
                    "counted": 0, "distinct": 0},
                   {"kind": "beat", "n": 100}],
        "counters": {
            "before": {"rt.go_distinct": 10, "rt.distinct_hops": 25,
                       "rt.distinct_vertices": 1_000},
            "after": {"rt.go_distinct": 14, "rt.distinct_hops": 35,
                      "rt.distinct_vertices": 697_000}},
        "statements_done": 4}
    record.update(over)
    return record


def test_the_neigh_readers_on_hand_made_records():
    record = _record()
    assert _note("neigh_ridden_share")["reader"] == "flight_ratio"
    assert flight_ratio.read(_note("neigh_ridden_share")["select"],
                             record) == 1.0
    assert counter_delta.read(_layer("neigh_hops_per_stmt")["select"],
                              record) == 2.5
    assert counter_delta.read(
        _layer("neigh_vertices_per_stmt")["select"], record) == 174_000.0
    # a cohort that mixes the three leavers reads its share
    mixed = _record(flight=[{"kind": "tick", "leaves": 4, "handed": 3,
                             "counted": 1, "distinct": 2}])
    assert flight_ratio.read(_note("neigh_ridden_share")["select"],
                             mixed) == 0.5


def test_the_neigh_readers_read_nothing_on_a_program_without_them():
    """The parent: no ``distinct`` on its tick records, no
    ``rt.distinct_*`` counter.  Each reader returns None (left out of
    the line, named on stderr) and does not raise."""
    record = _record(
        flight=[{"kind": "tick", "leaves": 3, "handed": 3,
                 "counted": 0}],
        counters={"before": {"rt.go_device": 1},
                  "after": {"rt.go_device": 5}})
    for name, reader in (("neigh_hops_per_stmt", counter_delta),
                         ("neigh_vertices_per_stmt", counter_delta)):
        assert reader.read(_layer(name)["select"], record) is None, name
    assert flight_ratio.read(_note("neigh_ridden_share")["select"],
                             record) is None
