"""The filtered-GO deployment PR 31 brought (run: ``JAX_PLATFORMS=cpu
python -m pytest benchmark/tests -q``): the reference of the kind
against brute force, its control (one kept row dropped, one filtered-out
row let through: each ``correct: false``), the kind found by name, the
weight table's split levels beside the traffic's two constants and the
reference evaluated in float32 held to ``correct: false`` by them, and
the new per-layer readers on hand-made records.  CPU only:
no number here is a device number."""
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
from benchmark.generators import kronecker, kronecker_split  # noqa: E402
from benchmark.readers import (counter_delta, span_duration,  # noqa: E402
                               span_tag)
from benchmark.semantics import go_where  # noqa: E402
from benchmark.semantics.go import go  # noqa: E402

WHERE_CELL = "graph500-s20-where.filtered16"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
LEVELS = 16


def _where_graph(seed: int) -> reference.Graph:
    rng = np.random.default_rng(seed)
    n, m = 300, 3000
    key = np.unique(rng.integers(0, n, m) * n + rng.integers(0, n, m))
    src, dst = key // n + 1, key % n + 1
    return reference.Graph(src, dst,
                           [{"w": k / LEVELS} for k in range(LEVELS)],
                           rng.integers(0, LEVELS, len(src)))


def _brute(g: reference.Graph, start: int, steps: int, op: str,
           value: float) -> list:
    """Edge by edge in Python floats: nothing of numpy's comparison."""
    frontier = {start}
    for _ in range(steps - 1):
        frontier = {int(g.dst[e]) for v in frontier
                    for e in range(g.ptr[v], g.ptr[v + 1])}
    keep = {">": lambda w: w > value, ">=": lambda w: w >= value,
            "<": lambda w: w < value, "<=": lambda w: w <= value}[op]
    return sorted(int(g.dst[e]) for v in frontier
                  for e in range(g.ptr[v], g.ptr[v + 1])
                  if keep(g.etable[int(g.eidx[e])]["w"]))


@pytest.mark.parametrize("op,value", [
    (">", 0.5), (">=", 0.5), ("<", 0.5), ("<=", 0.5),   # a stored level
    (">", 0.9), ("<", 0.03), (">", 1.0), (">=", 0.0)])  # none / all kept
@pytest.mark.parametrize("steps", [1, 2, 3])
def test_go_where_reference_matches_brute_force(steps, op, value):
    g = _where_graph(5)
    sem = {"kind": "go_where", "steps": steps, "prop": "w", "op": op,
           "value": value, "yield": ["_dst"]}
    rows = 0
    for start in range(1, 25):
        got = g.answer(sem, start)
        want = _brute(g, start, steps, op, value)
        assert sorted(got[0].tolist()) == want
        rows += len(want)
    unfiltered = sum(reference.n_rows(go(g, s, steps, ["_dst"]))
                     for s in range(1, 25))
    if (op, value) == (">", 1.0):
        assert rows == 0
    elif (op, value) == (">=", 0.0):
        assert rows == unfiltered > 0
    else:
        assert 0 < rows < unfiltered


@pytest.mark.parametrize("seed", [11, 2_345_678_901, 3_999_999_999])
@pytest.mark.parametrize("weaken", ["kept_row_dropped",
                                    "filtered_row_let_through"])
def test_where_control_is_not_correct(seed, weaken):
    """The reference in the program's place with the filter's guarantee
    broken: the digest and the exact comparison both say so."""
    g = _where_graph(seed)
    sem = {"kind": "go_where", "steps": 2, "prop": "w", "op": ">",
           "value": 0.5, "yield": ["_dst"]}
    judged = 0
    for start in range(1, 40):
        want = g.answer(sem, start)
        everything = go(g, start, 2, ["_dst"])[0]
        if not reference.n_rows(want) \
                or len(everything) == reference.n_rows(want):
            continue
        again = (want[0][::-1],)                    # sound: another order
        assert reference.digest(again) == reference.digest(want)
        assert reference.same_rows(again, want)
        if weaken == "kept_row_dropped":
            bad = (want[0][1:],)
        else:
            # a destination whose edge the predicate refuses
            pos = g.edge_positions(g.frontier(start, 1))
            w = np.asarray([row["w"] for row in g.etable])[g.eidx[pos]]
            bad = (np.append(want[0], g.dst[pos[w <= 0.5][0]]),)
        assert reference.digest(bad) != reference.digest(want)
        assert not reference.same_rows(bad, want)
        judged += 1
    assert judged > 10


def test_the_kind_is_found_by_name_and_the_cell_resolves():
    assert reference.semantics_module("go_where") is go_where
    parts = run.resolve(run.load_json(ROOT, "BENCHMARK.json"), WHERE_CELL)
    classes = parts["traffic"]["classes"]
    assert {c["semantics"]["kind"] for c in classes.values()} \
        == {"go_where"}
    assert [(c["semantics"]["steps"], c["semantics"]["value"])
            for c in classes.values()] == [(2, 0.9), (3, 0.99)]
    # the statement says what its semantics say
    for c in classes.values():
        s = c["semantics"]
        assert f"GO {s['steps']} STEPS" in c["template"]
        assert f"WHERE knows.{s['prop']} {s['op']} {s['value']} " \
            in c["template"]
    assert {m["name"] for m in parts["end_to_end"]} \
        == {"qps", "device_bytes_per_edge", "setup_s"}
    assert parts["config"]["flags"] == {"go_backend_router": False}


def _where_parts() -> dict:
    return run.resolve(run.load_json(ROOT, "BENCHMARK.json"), WHERE_CELL)


def test_the_split_levels_are_two_doubles_and_one_float32():
    """Beside each constant of the traffic the configuration's weight
    table holds two values that float64 tells apart and float32 does
    not, one on each side: ``w > c`` in float32 answers one of them
    wrong, and in float64 keeps what it kept on the even levels."""
    parts = _where_parts()
    params = parts["config"]["generator_params"]
    assert parts["config"]["generator"] == "kronecker_split"
    k = int(params["weight_levels"])
    constants = sorted(float(c["semantics"]["value"])
                       for c in parts["traffic"]["classes"].values())
    assert sorted(params["weight_split"]) == constants == [0.9, 0.99]
    moved = kronecker_split.split_levels(k, constants)
    assert len(moved) == 4
    even = np.arange(k, dtype=np.float64) / k
    levels = even.copy()
    levels[list(moved)] = list(moved.values())
    assert np.all(np.diff(levels) > 0)      # each stays in its place
    assert np.abs(levels - even).max() < 1.0 / k
    for c in constants:
        below = int(np.flatnonzero(levels < c)[-1])
        above = below + 1
        assert {below, above} <= set(moved)
        assert levels[below] < c < levels[above]
        assert np.float32(levels[below]) == np.float32(c) \
            == np.float32(levels[above])
        assert np.array_equal(levels > c, even > c)
        kept32 = levels.astype(np.float32) > np.float32(c)
        assert kept32[below] == kept32[above]
        assert np.flatnonzero(kept32 != (levels > c)).tolist() \
            in ([below], [above])


def test_the_split_generator_keeps_the_graph_of_the_plain_one():
    params = {"scale": 8, "edgefactor": 8, "A": 0.57, "B": 0.19,
              "C": 0.19, "edge_prop": "w", "weight_levels": 256,
              "weight_split": [0.9, 0.99]}
    plain = kronecker.generate(params, 50020)
    split = kronecker_split.generate(params, 50020)
    for key in ("src", "dst", "edge_prop_idx"):
        assert np.array_equal(plain[key], split[key])
    differ = [i for i, (a, b) in enumerate(zip(plain["edge_prop_table"],
                                               split["edge_prop_table"]))
              if a != b]
    assert differ == sorted(kronecker_split.split_levels(256, [0.9, 0.99]))
    with pytest.raises(ValueError):     # no level above 0.999 of 256
        kronecker_split.split_levels(256, [0.999])
    with pytest.raises(ValueError):     # one level cannot serve two
        kronecker_split.split_levels(256, [0.99, 0.9901])


@pytest.mark.parametrize("precision,correct", [("float64", True),
                                               ("float32", False)])
def test_the_reference_in_float32_is_not_correct(monkeypatch, precision,
                                                 correct):
    """The control for the guarantee's precision, through the harness
    as a chip run goes (the rehearsal's size, CPU jax): the reference
    evaluated in the nearest precision below float64 is ``correct:
    false`` by the answers' limits and by no other."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    parts = _where_parts()
    for cls in parts["traffic"]["classes"].values():
        cls["semantics"]["precision"] = precision
    out = run.run_cell(parts, seed=3_100_000_023, seconds=2.0,
                       trace=False, device=CPU, tiny=True)
    compared = out["compared"]
    assert out["correct"] is correct
    assert compared["served_counter_short"]["value"] == 0
    assert compared["health_problems"]["value"] == 0
    if correct:
        assert out["failed"] == 0
        assert compared["digest_mismatches"]["value"] == 0
        assert compared["exact_mismatches"]["value"] == 0
    else:
        wrong = compared["digest_mismatches"]["value"]
        assert wrong > 0.05 * compared["responses"]["value"]
        assert compared["exact_mismatches"]["value"] > 0


def _layer(name: str) -> dict:
    return run.load_json(ROOT, "benchmark", "layer_metrics",
                         name + ".json")


def _tree(*spans) -> dict:
    return {"roots": [{"name": "graph.query", "start_us": 0,
                       "duration_us": 10_000, "tags": {},
                       "children": [
                           {"name": "tpu.assemble", "start_us": 100,
                            "duration_us": 9_000, "tags": {},
                            "children": list(spans)}]}]}


def _where_span(dur_us: int, cpu_us: int) -> dict:
    return {"name": "tpu.where", "start_us": 200, "duration_us": dur_us,
            "tags": {"queries": 2, "candidates": 100, "kept": 1,
                     "site": "assembly", "cpu_us": cpu_us},
            "children": []}


def test_the_where_readers_on_hand_made_records():
    record = {
        "trees": [_tree(_where_span(4_000, 3_000)),
                  _tree(_where_span(2_000, 1_000), _where_span(300, 200)),
                  _tree()],
        "counters": {
            "before": {"rt.go_where": 10, "rt.where_candidates": 1_000,
                       "rt.where_rows": 100},
            "after": {"rt.go_where": 14, "rt.where_candidates": 9_000,
                      "rt.where_rows": 188}},
        "statements_done": 4}
    assert _layer("where_filter_ms")["reader"] == "span_duration"
    assert span_duration.read(_layer("where_filter_ms")["select"], record) \
        == pytest.approx(2.1)
    assert span_tag.read(_layer("where_filter_cpu_ms")["select"], record) \
        == pytest.approx(1.4)
    assert counter_delta.read(
        _layer("where_candidates_per_stmt")["select"], record) == 2_000.0
    # what where_cpu_ns_per_edge.qps read until PR 45 (thread time for
    # each candidate edge) is the quotient of two listed metrics: 1.4 ms
    # a span over 2,000 candidates a statement
    assert _layer("where_filter_cpu_ms")["select"]["tag"] == "cpu_us"
    assert _layer("where_candidates_per_stmt")["select"]["counter"] \
        == "rt.where_candidates"
    # what where_keep_share.qps read (a property of the traffic) is the
    # quotient of two counters every run's notes print under
    # counter_growth
    assert (record["counters"]["after"]["rt.where_rows"]
            - record["counters"]["before"]["rt.where_rows"]) / 8_000 \
        == pytest.approx(0.011)
    for gone in ("where_keep_share", "where_cpu_ns_per_edge",
                 "where_native_share"):
        assert not os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", gone + ".json")), gone


def test_the_where_readers_read_nothing_on_a_program_without_them():
    """The parent: no ``tpu.where`` span, no ``rt.where_*`` counter.
    Each reader returns None (left out of the line, named on stderr)
    and does not raise."""
    record = {"trees": [_tree()], "statements_done": 4,
              "counters": {"before": {"rt.go_device": 1},
                           "after": {"rt.go_device": 5}}}
    for name, reader in (("where_filter_ms", span_duration),
                         ("where_filter_cpu_ms", span_tag),
                         ("where_candidates_per_stmt", counter_delta)):
        assert reader.read(_layer(name)["select"], record) is None
